#!/usr/bin/env python3
"""Latent attention's two kernels on the chip, at `latent-saturated`'s
shapes (64 heads, a latent of 512 and a rotary part of 64 in rows of 640,
keys of 192 and values of 128), each against its plain form:

- the tick's `paged_latent_attention` (ops/paged_latent_attention.py)
  against the gather it replaces, 64 slots over a 35,841-page pool at
  live lengths of 1k to 9k, at several PAGES_PER_BLOCK;
- the rung's flash forward with the pair (192, 128) as it is against q,
  k and v zero-padded to 256 (what it would cost to keep one head size),
  and against `reference_attention` where its scores fit.

One JSON line a case: the largest difference and the milliseconds a
call. Exit 1 where a difference is over bfloat16's step (2 ** -6 of the
reference's largest value). Needs a TPU: exit 69 without one.

    python3 tools/latent_on_chip.py
"""

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOLERANCE = 2 ** -6


def timed(fn, *args, n=10):
    import jax

    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t) / n, out


def latent_kernel(report):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.ops import paged_latent_attention as pla

    slots, heads, w, rank, ps, pages, mp = 64, 64, 640, 512, 16, 35841, 560
    scale = 192 ** -0.5 * (0.1 * np.log(32) + 1) ** 2
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)
    pool = jax.random.normal(key, (pages, ps, w), jnp.bfloat16)
    pool = pool.at[..., 576:].set(0)
    q = jax.random.normal(jax.random.fold_in(key, 1), (slots, heads, w),
                          jnp.bfloat16).at[..., 576:].set(0)
    table = jnp.asarray(1 + rng.permutation(pages - 1)[:slots * mp]
                        .reshape(slots, mp), jnp.int32)
    last = jnp.asarray(rng.integers(1100, 8900, slots), jnp.int32)
    start = jnp.asarray(rng.integers(0, 1000, slots), jnp.int32)
    positions = int(jnp.sum(last - start + 1))

    @jax.jit
    def gather(q, pool, table, start, last):
        rows = pool[table].reshape(slots, mp * ps, w)
        s = jnp.einsum("bhw,bsw->bhs", q, rows,
                       preferred_element_type=jnp.float32) * scale
        pos = jnp.arange(mp * ps)[None, None]
        seen = (pos >= start[:, None, None]) & (pos <= last[:, None, None])
        p = jax.nn.softmax(jnp.where(seen, s, -1e30), -1)
        return jnp.einsum("bhs,bsr->bhr", p.astype(pool.dtype),
                          rows[..., :rank])

    ms_g, want = timed(gather, q, pool, table, start, last, n=3)
    want = np.asarray(want, np.float32)
    ok = True
    for ppb in (8, 16, 32, 64):
        pla.PAGES_PER_BLOCK = ppb
        fn = jax.jit(functools.partial(
            pla._call.__wrapped__, scale=float(scale), rank=rank,
            interpret=False))
        ms, got = timed(fn, q, pool, table, start, last)
        worst = float(np.abs(np.asarray(got, np.float32) - want).max()
                      / np.abs(want).max())
        ok &= worst <= TOLERANCE
        report(case=f"latent-kernel-ppb{ppb}", worst=worst, ms=ms,
               gather_ms=ms_g, positions=positions,
               gb_per_s_needed=positions * 1152 / ms / 1e6,
               gb_per_s_rows=positions * 1280 / ms / 1e6)
    return ok


def flash_pair(report):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.ops.attention import reference_attention
    from kubeflow_tpu.ops.flash_attention import flash_attention

    heads, dk, dv = 64, 192, 128
    scale = 0.1
    ok = True
    for rung in (2048, 4096, 8192):
        key = jax.random.PRNGKey(rung)
        q, k = (jax.random.normal(jax.random.fold_in(key, i),
                                  (1, rung, heads, dk), jnp.bfloat16)
                for i in (0, 1))
        v = jax.random.normal(jax.random.fold_in(key, 2),
                              (1, rung, heads, dv), jnp.bfloat16)
        seg = (jnp.arange(rung)[None] >= 37).astype(jnp.int32)

        pair = jax.jit(lambda q, k, v, seg: flash_attention(
            q, k, v, causal=True, scale=scale, segment_ids=seg))

        def pad(x):
            return jnp.pad(x, ((0, 0),) * 3 + ((0, 256 - x.shape[-1]),))

        padded = jax.jit(lambda q, k, v, seg: flash_attention(
            pad(q), pad(k), pad(v), causal=True, scale=scale,
            segment_ids=seg)[..., :dv])
        ms_pair, got = timed(pair, q, k, v, seg, n=5)
        ms_pad, alt = timed(padded, q, k, v, seg, n=5)
        worst = float(jnp.abs(got.astype(jnp.float32)
                              - alt.astype(jnp.float32)).max())
        line = dict(case=f"flash-192-128-rung{rung}", ms_pair=ms_pair,
                    ms_padded_256=ms_pad, pair_against_padded=worst,
                    tflops_pair=2 * heads * (dk + dv) * rung * (rung + 1)
                    / 2 / ms_pair / 1e9)
        if rung == 2048:
            want = jax.jit(lambda q, k, v, seg: reference_attention(
                q, k, v, causal=True, scale=scale, segment_ids=seg))(
                q, k, v, seg)
            ref = float(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))[:, 37:].max()
                        / jnp.abs(want.astype(jnp.float32)).max())
            line["worst_against_reference"] = ref
            ok &= ref <= TOLERANCE
        ok &= worst <= 0.05
        report(**line)
    return ok


def main() -> int:
    import jax

    if jax.default_backend() != "tpu":
        print("tools/latent_on_chip.py: no TPU", file=sys.stderr)
        return 69
    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/latent_on_chip.jsonl", "a")

    def report(**line):
        line["device"] = jax.devices()[0].device_kind
        print(json.dumps(line), flush=True)
        out.write(json.dumps(line) + "\n")

    ok = latent_kernel(report)
    ok = flash_pair(report) and ok
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
