"""The controls and the planted faults of a training cell, read on the chip
at the cell's own size (PERF.md, "How correct is decided"): for each seed
the plain reference, then the reference in float8 (the control: the nearest
precision below the bfloat16 the configuration states) and the reference on
half of the batch (a planted fault), each compared as the program would be.

    python3 benchmarks/tests/controls_on_chip.py --workload ft-8k-1chip --seeds 1,2,3

A serving cell's control is the program's own int4 path:
    python3 benchmarks/run.py --workload chat-saturated --seed 1 --seconds 8 \
        --trace 0 --override param_dtype=int4
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    args = p.parse_args()

    import jax.numpy as jnp

    from benchmarks.lib import harness, spec, train

    cell = spec.cell(args.workload)
    harness.devices_for(cell.chips)
    harness.configure_cache()
    half = slice(0, cell.config["trainer"]["global_batch"] // 2)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.monotonic()
        ref = train.run_reference(cell, seed)
        ref_s = time.monotonic() - t
        out = {"seed": seed, "reference_s": ref_s, "loss": ref["loss"]}
        for name, kw in (("float8", {"lowp": jnp.float8_e4m3fn}),
                         ("half_batch", {"rows": half})):
            got = train.run_reference(cell, seed, **kw)
            out[name] = {n: v for n, v, _ in train.compare(cell, got, ref)}
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
