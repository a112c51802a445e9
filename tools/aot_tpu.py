#!/usr/bin/env python3
"""Compile a trainer config for a TPU topology with no TPU attached.

libtpu is installed in the sandbox, so XLA:TPU and Mosaic run for real
against compile-only devices from `jax.experimental.topologies`. This is
the check to make before chip time is spent: a sharding the partitioner
refuses, a kernel Mosaic rejects, or a step that does not fit in HBM
shows up here in under a minute. It is a compile, not a run: it says
nothing about speed and cannot see a run-time fault.

    JAX_PLATFORMS=cpu python tools/aot_tpu.py --config cfg.yaml \
        --topology v5e:2x2

Prints one JSON line: compile seconds, the compiler's memory analysis
and the number of Mosaic kernels in the step.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# get_topology_desc warns without these; any value will do off a TPU VM
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", required=True,
                   help="TrainConfig JSON/YAML, as given to the launcher")
    p.add_argument("--topology", default="v5e:2x2",
                   help="topology name for get_topology_desc "
                        "(v5e:1x1 = one chip, v5e:2x2 = one four-chip host)")
    args = p.parse_args()

    from jax.experimental import topologies

    from kubeflow_tpu.ops import flash_attention
    from kubeflow_tpu.parallel.mesh import build_mesh
    from kubeflow_tpu.runtime.launcher import load_config
    from kubeflow_tpu.runtime.trainer import TrainConfig, Trainer

    # the host is a CPU; the kernels must be the compiled ones
    flash_attention.INTERPRET = False
    # a v5e host holds 2x2 chips; a smaller topology must say so
    dims = tuple(int(n) for n in args.topology.split(":")[1].split("x"))
    small = ({"chips_per_host_bounds": dims + (1,) * (3 - len(dims))}
             if dims in ((1, 1), (1, 2), (2, 1)) else {})
    devices = topologies.get_topology_desc(
        platform="tpu", topology_name=args.topology, **small).devices
    cfg = TrainConfig.from_dict(load_config(args.config))
    trainer = Trainer(cfg, mesh=build_mesh(cfg.mesh, devices))

    with trainer.mesh:
        t0 = time.perf_counter()
        lowered = trainer._train_step.trace(
            trainer.abstract_state, trainer.abstract_batch).lower(
            lowering_platforms=("tpu",))
        compiled = lowered.compile()
        dt = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    print(json.dumps({
        "topology": args.topology,
        "devices": len(devices),
        "mesh": {k: v for k, v in trainer.mesh.shape.items() if v > 1},
        "n_params_m": round(trainer.n_params / 1e6, 1),
        "compile_s": round(dt, 1),
        "mosaic_kernels": lowered.as_text().count("tpu_custom_call"),
        "per_device_gb": {
            "arguments": round(mem.argument_size_in_bytes / 1e9, 2),
            "outputs": round(mem.output_size_in_bytes / 1e9, 2),
            "temporaries": round(mem.temp_size_in_bytes / 1e9, 2),
            "aliased": round(mem.alias_size_in_bytes / 1e9, 2),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
