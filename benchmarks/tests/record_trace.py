"""Records the small trace test_xplane.py reads, on a chip:

    python3 benchmarks/tests/record_trace.py chiprun_out/small.xplane.pb
"""

import shutil
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")
from benchmarks.lib import harness, xplane  # noqa: E402


def recorded_step(x):
    for _ in range(4):
        x = jnp.tanh(x @ x) * 0.01
    return x


def main(out):
    step = jax.jit(recorded_step)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    step(x).block_until_ready()
    win = harness.TraceWindow(True)
    win.start()
    for _ in range(3):
        x = step(x)
        x.block_until_ready()
        time.sleep(0.002)
    win.stop()
    shutil.copy(xplane.find_xplane(win.dir), out)
    print(xplane.reduce(xplane.read(out)))


if __name__ == "__main__":
    main(sys.argv[1])
