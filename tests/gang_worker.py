"""Worker payload for the multi-process gang e2e test.

What a real JAXJob training container does (the launcher contract,
reference tf-cnn/launcher.py:59-93): join the distributed world from
JAXJOB_* env, build a mesh, train with checkpointing, exit 0. Run by
LocalPodExecutor as an actual subprocess.

Under JAXJOB_COLLECTIVES_BACKEND=loopback (the tier-1 mode) the gang
forms over the LoopbackBackend's TCP join barrier — real membership,
coordinator, and teardown semantics, hermetic on CPU — and each rank
then trains an identical replica on its own local devices with a
per-rank checkpoint dir (this image's multi-process jax.distributed CPU
worlds crash in flax init, so the real-backend path is the @slow
variant). Without the env the worker keeps the real jax.distributed
contract: one process-spanning mesh, shared checkpoints.

Env knobs (set by the test through the pod spec / env_hook):
  GANG_CKPT_DIR     orbax checkpoint root (per-rank subdir on loopback)
  GANG_TOTAL_STEPS  global step target
  GANG_STEP_DELAY_S per-step sleep so the test can kill a worker mid-run
"""

import json
import os
import sys

# test workers are CPU processes whatever the machine holds, as in
# tests/conftest.py
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kubeflow_tpu.parallel import backends as B  # noqa: E402
from kubeflow_tpu.parallel import dist as D  # noqa: E402
from kubeflow_tpu.parallel.dist import initialize_from_env  # noqa: E402


def main() -> int:
    dist = initialize_from_env()
    loopback = isinstance(D.active_backend(), B.LoopbackBackend)
    if loopback:
        # the TCP barrier released us: the whole gang is live — the
        # membership proof the device-count assertion gives on the
        # real backend
        world = D.active_world()
        assert world is not None \
            and world.num_processes == dist.num_processes, world
        mesh_extent = jax.local_device_count()
        ckpt_dir = os.path.join(os.environ["GANG_CKPT_DIR"],
                                f"r{dist.process_id}")
    else:
        assert jax.device_count() == dist.num_processes, \
            (jax.device_count(), dist.num_processes)
        mesh_extent = dist.num_processes
        ckpt_dir = os.environ["GANG_CKPT_DIR"]

    import time

    from kubeflow_tpu.parallel.mesh import MeshSpec
    from kubeflow_tpu.runtime.trainer import TrainConfig, Trainer

    delay = float(os.environ.get("GANG_STEP_DELAY_S", "0"))
    # resnet classification, not the LM: this image's flax crashes in
    # transformer init (the known test_bench_lm_pipeline failure
    # family), and the contract under test is the gang, not the model
    cfg = TrainConfig.from_dict(dict(
        model="resnet18",
        model_kwargs={"num_filters": 8},
        task="classification",
        global_batch=2 * dist.num_processes,
        image_size=16,
        num_classes=10,
        mesh=MeshSpec(data=mesh_extent),
        optimizer="adamw",
        learning_rate=1e-3,
        total_steps=int(os.environ["GANG_TOTAL_STEPS"]),
        warmup_steps=1,
        checkpoint_dir=ckpt_dir,
        checkpoint_every=1,
        log_every=10**9,
    ))
    trainer = Trainer(cfg)
    cb = (lambda i, m: time.sleep(delay)) if delay else None
    # Same SIGTERM contract as the launcher: checkpoint + EX_TEMPFAIL.
    # The trainer turns the per-worker notice into a gang-agreed stop
    # (all ranks break at the same step) when num_processes > 1.
    from kubeflow_tpu.runtime.preemption import EX_TEMPFAIL, PreemptionNotice

    notice = PreemptionNotice().install()
    state, summary = trainer.fit(callback=cb, stop=notice)
    line = json.dumps({"rank": dist.process_id,
                       "start_step": summary["start_step"],
                       "final_step": int(state.step),
                       "preempted": bool(summary.get("preempted", False)),
                       "loss": summary["final"].get("loss")})
    print(line, flush=True)
    # Also append to a shared log so the test can assert per-run
    # start_steps (stdout is swallowed by the executor on success).
    log_path = os.environ.get("GANG_LOG")
    if log_path:
        with open(log_path, "a") as f:
            f.write(line + "\n")
    return EX_TEMPFAIL if summary.get("preempted") else 0


if __name__ == "__main__":
    sys.exit(main())
