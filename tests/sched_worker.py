"""Worker payload for the SCHEDULER gang e2e test.

Joins the jax.distributed world from the JAXJOB_* env the controller
injected and proves ONE world formed across the scheduler-placed pods:
after initialize_from_env, jax.device_count() equals num_processes only
when every rank's topology exchange with the coordinator succeeded (a
lone process would see 1). Deliberately stops short of the full flax
trainer (that path is gang_worker.py's job): the scheduler e2e isolates
placement → world formation, so it must not inherit the trainer's
model-layer dependencies — or the CPU backend's lack of multiprocess
collectives.
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
    if isinstance(D.active_backend(), B.LoopbackBackend):
        # tier-1 mode: the TCP join barrier only releases once every
        # rank has checked in, so reaching this line IS the formation
        # proof; the world stamp carries the agreed size
        world = D.active_world()
        assert world is not None, "loopback world did not form"
        size = world.num_processes
    else:
        # real jax.distributed: every process sees every process's
        # devices (ranks that failed to join would leave this at 1)
        assert jax.device_count() == dist.num_processes, \
            (jax.device_count(), dist.num_processes)
        assert jax.process_count() == dist.num_processes
        size = jax.device_count()
    assert size == dist.num_processes, (size, dist.num_processes)

    with open(os.environ["GANG_LOG"], "a") as f:
        f.write(json.dumps({"rank": dist.process_id,
                            "world": size}) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
