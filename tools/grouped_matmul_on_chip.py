#!/usr/bin/env python3
"""The compiled grouped matmul against `jax.lax.ragged_dot`, on the chip:
the same rows, and the time each takes.

tests/test_grouped_matmul.py judges the Pallas INTERPRETER (the test
conftest forces the CPU); this is the kernel Mosaic compiles, which only
a TPU can run:

    chiprun -- python tools/grouped_matmul_on_chip.py

Shapes: the block-diffusion cell's experts (128 groups of
`[2048, 768]` for gate and up, `[768, 2048]` for down) at the rows of a
pass and of the prefill's rungs (2,048 to 8,192: 16 to 64 rows a group)
and beyond (to 512 a group), which is where `grouped_matmul.
MAX_MEAN_ROWS` comes from. Group sizes are a seeded multinomial over
skewed expert probabilities (the fullest 2.4 times the mean, as
`moe.load_max_over_mean.blockdiff` reads), with a twentieth of the rows
left dead behind the last group. `--groups G --k K --n N --rows-a-group
a,b,..` tries another expert instead (a chip's share of Trinity-Large:
`--groups 32 --k 3072 --n 3072 --rows-a-group 1,2,16,128`, one matrix
18.9 MB, which is what `grouped_matmul.MAX_GROUP_BYTES` was read at). One JSON line a shape: the largest
difference on the groups' rows over the largest reference value (both
round a float32 sum to bfloat16 once: 2**-7 bounds it), the
milliseconds of each, and the share of the chip's published bandwidth
(benchmarks/lib/opcount.py) the kernel's time is of the visited experts'
bytes. Exits 69 when there is no TPU, 1 on a difference over the bound.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOLERANCE = 2.0 ** -7
GROUPS = 128
ROWS = (2048, 4096, 6144, 8192, 16384, 32768, 65536)
REPEATS = 30


def group_sizes(rng, rows: int, groups: int = GROUPS):
    import numpy as np

    p = rng.dirichlet(np.full(groups, 3.0))
    return rng.multinomial(rows - rows // 20, p).astype(np.int32)


def timed(fn, *args) -> float:
    """Milliseconds a call, the device kept busy by REPEATS of them."""
    fn(*args).block_until_ready()
    fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(REPEATS):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / REPEATS * 1e3


def shapes(argv):
    """(groups, [(k, n), ...], rows): the block-diffusion cell's by
    default, one expert of the caller's where `--k` is given."""
    p = argparse.ArgumentParser()
    p.add_argument("--groups", type=int, default=GROUPS)
    p.add_argument("--k", type=int, default=0)
    p.add_argument("--n", type=int, default=0)
    p.add_argument("--rows-a-group", default="")
    a = p.parse_args(argv)
    if not a.k:
        return GROUPS, ((2048, 768), (768, 2048)), ROWS
    rows = tuple(max(int(float(r) * a.groups), 8)
                 for r in a.rows_a_group.split(","))
    return a.groups, ((a.k, a.n or a.k),), rows


def main(argv=None) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib import opcount
    from kubeflow_tpu.ops.flash_attention import interpret_mode
    from kubeflow_tpu.ops.grouped_matmul import grouped_matmul
    from kubeflow_tpu.runtime.metrics import device_info

    device = device_info()
    if interpret_mode():
        print(f"grouped_matmul_on_chip: no TPU: JAX found {device}",
              file=sys.stderr)
        return 69
    bandwidth = opcount.peaks(device["kind"])["hbm_bytes_per_s"]
    reference = jax.jit(jax.lax.ragged_dot)
    rng = np.random.default_rng(32)
    ok = True
    groups, experts, all_rows = shapes(argv)
    for k, n in experts:
        rhs = jax.random.normal(jax.random.PRNGKey(k), (groups, k, n),
                                jnp.bfloat16) * k ** -0.5
        for rows in all_rows:
            sizes = group_sizes(rng, rows, groups)
            lhs = jax.random.normal(jax.random.PRNGKey(rows), (rows, k),
                                    jnp.bfloat16)
            counts = jnp.asarray(sizes)
            got = np.asarray(grouped_matmul(lhs, rhs, counts), np.float32)
            want = np.asarray(reference(lhs, rhs, counts), np.float32)
            used = int(sizes.sum())
            err = float(np.abs(got[:used] - want[:used]).max()
                        / np.abs(want).max())
            ok &= err <= TOLERANCE and bool(np.isfinite(got[:used]).all())
            ms = timed(grouped_matmul, lhs, rhs, counts)
            nbytes = int((sizes > 0).sum()) * k * n * 2
            print(json.dumps({
                "k": k, "n": n, "rows": rows,
                "groups": groups,
                "rows_a_group": rows / groups, "fullest": int(sizes.max()),
                "max_err_rel_to_max": round(err, 6),
                "kernel_ms": round(ms, 4),
                "ragged_dot_ms": round(timed(reference, lhs, rhs, counts), 4),
                "kernel_share_of_bandwidth": round(
                    nbytes / bandwidth / (ms * 1e-3), 4)}), flush=True)
    print(json.dumps({"ok": ok, "device": device, "tolerance": TOLERANCE}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
