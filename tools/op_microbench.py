"""Per-op microbenchmark: attribute the train-step MFU gap to kernels.

Times the individual hot ops at bench shapes (gpt-350m / llama-1b,
seq 2048) and prints each op's achieved fraction of the chip's peak
bf16 FLOPs. The train-step MFU ceiling is a FLOPs-weighted mix of these
rates, so a low rate here names the kernel to fix — ablation timing by
host clock, not a per-op parse of a profiler trace.

Usage: python tools/op_microbench.py [--model gpt-350m] [--batch 8]
Writes one JSON line per op; run with the chip otherwise idle.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


def peak_flops(kind: str) -> float:
    from kubeflow_tpu.runtime.metrics import peak_flops as pf

    return pf(kind)


def _time(fn, *args, iters=20, warmup=3):
    """Chained dispatch, one readback sync."""
    out = None
    for _ in range(warmup):
        out = fn(*args)
    jax.tree.leaves(out)[0].block_until_ready()
    _ = float(jax.tree.leaves(out)[0].ravel()[0])  # force a readback
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _ = float(jax.tree.leaves(out)[0].ravel()[0])
    return (time.perf_counter() - t0) / iters


def bench_matmul(m, k, n, peak):
    """The MXU yardstick: one big bf16 matmul at LM-layer shape."""
    a = jnp.ones((m, k), jnp.bfloat16)
    b = jnp.ones((k, n), jnp.bfloat16)
    f = jax.jit(lambda a, b: jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(jnp.bfloat16))
    dt = _time(f, a, b)
    fl = 2.0 * m * k * n
    return {"op": f"matmul_{m}x{k}x{n}", "ms": round(dt * 1e3, 3),
            "util": round(fl / dt / peak, 4)}


def bench_flash(b, l, h, d, peak, bwd=False):
    from kubeflow_tpu.ops.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, l, h, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, l, h, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, l, h, d), jnp.bfloat16)

    if bwd:
        f = jax.jit(jax.grad(
            lambda q, k, v: flash_attention(q, k, v, causal=True)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2)))
        dt = _time(f, q, k, v)
        # causal fwd ~2*L^2*D*B*H MACs halved; bwd ~2.5x fwd kernel work
        fl = 2.0 * 2 * b * h * l * l * d / 2 * 3.5
        tag = "flash_fwd_bwd"
    else:
        f = jax.jit(functools.partial(flash_attention, causal=True))
        dt = _time(f, q, k, v)
        fl = 2.0 * 2 * b * h * l * l * d / 2
        tag = "flash_fwd"
    return {"op": f"{tag}_b{b}h{h}_l{l}", "ms": round(dt * 1e3, 3),
            "util": round(fl / dt / peak, 4)}


def bench_chunked_head(tokens, d, v, chunks, peak):
    from kubeflow_tpu.ops.xent import chunked_lm_xent

    hidden = jax.random.normal(jax.random.PRNGKey(0), (1, tokens, d),
                               jnp.bfloat16)
    kernel = jax.random.normal(jax.random.PRNGKey(1), (d, v), jnp.float32)
    labels = jnp.zeros((1, tokens), jnp.int32)

    f = jax.jit(jax.grad(
        lambda h, w: chunked_lm_xent(h, w, labels, chunks)[0],
        argnums=(0, 1)))
    dt = _time(f, hidden, kernel)
    fl = 6.0 * tokens * d * v  # fwd + bwd + chunk re-projection
    return {"op": f"chunked_head_{tokens}x{d}x{v}", "ms": round(dt * 1e3, 3),
            "util": round(fl / dt / peak, 4)}


def bench_block_soup(b, l, d, dff, peak):
    """One transformer block minus attention kernel: the rmsnorm / rope /
    swiglu elementwise soup fused around its matmuls — how much the
    non-matmul work drags the block below the pure-matmul rate."""
    x = jax.random.normal(jax.random.PRNGKey(0), (b, l, d), jnp.bfloat16)
    wg = jnp.ones((d, dff), jnp.bfloat16)
    wu = jnp.ones((d, dff), jnp.bfloat16)
    wd = jnp.ones((dff, d), jnp.bfloat16)
    scale = jnp.ones((d,), jnp.float32)

    def block(x, wg, wu, wd, scale):
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, -1, keepdims=True) + 1e-6)
        y = (y * scale).astype(jnp.bfloat16)
        g = jax.lax.dot_general(y.reshape(-1, d), wg,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        u = jax.lax.dot_general(y.reshape(-1, d), wu,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(jnp.bfloat16)
        o = jax.lax.dot_general(h, wd, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        return x + o.reshape(b, l, d).astype(jnp.bfloat16)

    f = jax.jit(block)
    dt = _time(f, x, wg, wu, wd, scale)
    fl = 2.0 * b * l * (3 * d * dff)
    return {"op": f"mlp_block_{b}x{l}_d{d}_ff{dff}", "ms": round(dt * 1e3, 3),
            "util": round(fl / dt / peak, 4)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=2048)
    args = ap.parse_args()

    devs = jax.devices()
    kind = devs[0].device_kind
    peak = peak_flops(kind)
    print(json.dumps({"device": kind, "peak_flops": peak}), flush=True)

    b, l = args.batch, args.seq
    tokens = b * l
    results = [
        # gpt-350m shapes
        bench_matmul(tokens, 1024, 4096, peak),
        bench_matmul(tokens, 4096, 1024, peak),
        bench_matmul(tokens, 1024, 32000, peak),
        bench_flash(b, l, 16, 64, peak, bwd=False),
        bench_flash(b, l, 16, 64, peak, bwd=True),
        bench_chunked_head(tokens, 1024, 32000, 8, peak),
        bench_block_soup(b, l, 1024, 4096, peak),
        # llama-1b shapes
        bench_matmul(tokens, 2048, 8192, peak),
        bench_flash(b, l, 32, 64, peak, bwd=True),
        bench_block_soup(b, l, 2048, 8192, peak),
        # llama-1b-hd128 head shape: same total head width (16x128 vs
        # 32x64) — the direct measurement of the head_dim-64 MXU
        # half-contraction penalty the r5 attribution blamed for the
        # attention utilization floor
        bench_flash(b, l, 16, 128, peak, bwd=False),
        bench_flash(b, l, 16, 128, peak, bwd=True),
    ]
    for r in results:
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
