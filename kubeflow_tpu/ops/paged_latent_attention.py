"""Pallas TPU paged decode attention over latents: one query a slot in
latent attention's absorbed form, read straight out of the latent pool.

A latent layer's pool (models/transformer.py `LatentAttention`) is
`[kv_pages, page_size, W]`: a position's row is `[ckv | k_pe | zeros]`,
the kv latent (`rank` values), the key's rotated part, and zeros up to
whole 128-lane tiles. At a decode tick every slot has one query a head,
already taken into the latent's space, `[q_lat | q_pe | zeros]` of the
same width, at position `last[b]`, and sees positions
`start[b]..last[b]`. The kernel walks the pages `start // PS .. last //
PS` of the slot's table row as ops/paged_attention.py does (one DMA a
page, PAGES_PER_BLOCK pages to a compute block, two blocks in flight,
the next slot's first block fetched behind this slot's last; an idle
slot, `start > last`, fetches nothing and gives zeros), and uses each
streamed block twice: whole as the keys (`q . rows^T`, every head
against every position: there are no kv heads to tell apart), and its
first `rank` lanes as the values. The result is `[heads, rank]` a slot,
still in the latent's space; the caller takes it out through
`W_kv_b`'s value part.

Numerics are the gather path's (`LatentAttention._decode_paged`): bf16
pool, scores, running max, sum and accumulator in float32, probabilities
cast to the pool's dtype before the product with the values.

Off the TPU the kernel runs in Pallas interpret mode, for its own tests
only: `use_kernel` keeps the model on the gather path there.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.ops.flash_attention import NEG_INF, interpret_mode
from kubeflow_tpu.parallel.mesh import current_mesh

log = logging.getLogger("kubeflow_tpu.paged_latent_attention")

# Pages to a compute block: 32 pages of 16 positions are 512 rows of 640
# values, 640 KB in bfloat16; two such buffers.
PAGES_PER_BLOCK = 32
# The custom call's name in the device trace (`%<name>.N = ...
# custom-call(`); the benchmark finds the kernel by it
# (tests/test_trace_names.py).
KERNEL_NAME = "paged_latent_attention"


def use_kernel(lq: int, pool_shape, pool_dtype) -> bool:
    """Whether a latent layer's paged read runs the kernel or the gather
    path, from what the code can observe, logged with the reason (as
    ops/paged_attention.py:use_kernel): the kernel for one query a slot
    on a TPU backend, a pool `[pages, PS, W]` of 16-bit values whose rows
    are whole 128-lane tiles, living whole on one device; the gather
    path for a chunk of several queries a slot (a gathered prefill), off
    the TPU, and under a mesh of several devices."""
    backend = jax.default_backend()
    mesh = current_mesh()
    width = pool_shape[-1]
    if lq != 1:
        choice, why = "gather", f"a chunk of {lq} queries a slot"
    elif backend != "tpu":
        choice, why = "gather", f"default backend is {backend!r}, not tpu"
    elif width % 128 or jnp.dtype(pool_dtype).itemsize != 2:
        choice, why = "gather", (
            f"rows of {width} {jnp.dtype(pool_dtype).name} values are no "
            "whole lane tiles of 16-bit values")
    elif mesh is not None and mesh.size > 1:
        choice, why = "gather", f"mesh of {mesh.size} devices shards the pool"
    else:
        choice, why = "kernel", (
            f"tpu backend, rows of {width}, one query a slot")
    log.info("paged decode: latent attention -> %s (%s)", choice, why)
    return choice == "kernel"


def _kernel(pt_ref, start_ref, last_ref,     # scalar prefetch
            q_ref, pool_hbm, o_ref,
            buf, sems, buf_ref, m_s, l_s, acc_s,
            *, scale: float, rank: int):
    _, ppb, ps, w = buf.shape
    heads = q_ref.shape[1]
    rows = ppb * ps                          # positions a block
    b, nb = pl.program_id(0), pl.num_programs(0)

    def span(j):
        """First logical page and page count of slot j's walk."""
        s, e = start_ref[j], last_ref[j]
        p0 = jax.lax.div(s, ps)
        return p0, jnp.where(s <= e, jax.lax.div(e, ps) - p0 + 1, 0)

    def next_with_work(j):
        """The first slot at or after j that walks any page, else nb."""
        return jax.lax.while_loop(
            lambda i: jnp.logical_and(
                i < nb, span(jnp.minimum(i, nb - 1))[1] == 0),
            lambda i: i + 1, j)

    def copies(j, blk, x, go):
        """Start (or wait for) the DMAs of block `blk` of slot j into
        buffer x, a page in one piece. Pages past the walk's end are not
        fetched; their rows keep what an earlier block left there, which
        the position mask hides."""
        p0, n = span(j)
        first = blk * ppb

        def page(i, carry):
            # a wait needs the copy's size and semaphore, not its source
            phys = pt_ref[j, p0 + first + i] if go else 0
            dma = pltpu.make_async_copy(
                pool_hbm.at[phys], buf.at[x, i], sems.at[x])
            if go:
                dma.start()
            else:
                dma.wait()
            return carry

        jax.lax.fori_loop(0, jnp.minimum(ppb, n - first), page, 0)

    @pl.when(b == 0)
    def _():
        # rows no DMA ever wrote must still be finite: a masked
        # probability is an exact 0, and 0 x NaN is not
        buf[...] = jnp.zeros_like(buf)
        buf_ref[0] = 0
        first = next_with_work(0)

        @pl.when(first < nb)
        def _():
            copies(first, 0, 0, True)

    p0, n = span(b)
    n_blocks = jax.lax.div(n + ppb - 1, ppb)
    start, last = start_ref[b], last_ref[b]
    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)

    def attend(blk, x, edge: bool):
        q = q_ref[0]                                         # [heads, w]
        kv = buf.at[x].reshape(rows, w)[...]
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [heads, rows]
        if edge:   # the walk's first and last blocks hold its two ends
            pos = (p0 + blk * ppb) * ps + jax.lax.broadcasted_iota(
                jnp.int32, (heads, rows), 1)
            s = jnp.where((pos >= start) & (pos <= last), s, NEG_INF)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # every visited block holds a visible position: m_new is a real
        # score and the masked entries underflow to exact zeros
        p = jnp.exp(s - m_new)
        l_s[...] = alpha * l_s[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_s[...] = alpha * acc_s[...] + jax.lax.dot_general(
            p.astype(kv.dtype), kv[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[...] = m_new

    def block(blk, x):
        # fetch ahead: this slot's next block or, behind its last one,
        # the first block of the next slot that has work
        ends = blk + 1 >= n_blocks
        nxt = jax.lax.cond(ends, lambda: next_with_work(b + 1), lambda: b)

        @pl.when(nxt < nb)
        def _():
            copies(nxt, jnp.where(ends, 0, blk + 1), 1 - x, True)

        copies(b, blk, x, False)
        lo = (p0 + blk * ppb) * ps
        edge = jnp.logical_or(lo < start, lo + rows - 1 > last)

        @pl.when(edge)
        def _():
            attend(blk, x, True)

        @pl.when(jnp.logical_not(edge))
        def _():
            attend(blk, x, False)

        return 1 - x

    buf_ref[0] = jax.lax.fori_loop(0, n_blocks, block, buf_ref[0])
    o_ref[0] = (acc_s[...] / jnp.maximum(l_s[...], 1e-20)).astype(o_ref.dtype)


def paged_latent_attention(q, pool, page_table, start, last, *,
                           scale: float, rank: int):
    """q [b, heads, W], the absorbed queries `[q_lat | q_pe | zeros]`;
    pool [kv_pages, PS, W] (it stays in HBM, in that layout); page_table
    [b, MP], start [b], last [b] int32. Returns [b, heads, rank]: each of
    slot b's queries over the positions start[b]..last[b] of its pages,
    weighing the rows' first `rank` values; zeros where start > last.
    Every table entry of the pages that hold those positions must be a
    page of the pool; entries outside them are never read."""
    return _call(q, pool, page_table, start, last, scale=scale, rank=rank,
                 interpret=interpret_mode())


# One jit for every layer's call (ops/paged_attention.py says why).
@functools.partial(jax.jit, static_argnames=("scale", "rank", "interpret"))
def _call(q, pool, page_table, start, last, *, scale: float, rank: int,
          interpret: bool):
    b, heads, w = q.shape
    _, ps, _ = pool.shape
    # the query rows fill whole sublane tiles of the pool's dtype; rows
    # added here are cut off again below
    tile = 8 * 4 // q.dtype.itemsize
    padded = -(-heads // tile) * tile
    qp = jnp.pad(q, ((0, 0), (0, padded - heads), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, rank=rank),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[pl.BlockSpec((1, padded, w), lambda i, *_: (i, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, padded, rank), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, PAGES_PER_BLOCK, ps, w), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),        # the buffer that is ahead
                pltpu.VMEM((padded, 1), jnp.float32),    # running max
                pltpu.VMEM((padded, 1), jnp.float32),    # running sum
                pltpu.VMEM((padded, rank), jnp.float32),  # accumulator
            ]),
        out_shape=jax.ShapeDtypeStruct((b, padded, rank), q.dtype),
        # slots in order: a slot's last block fetches the next slot's first
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(page_table.astype(jnp.int32), start.astype(jnp.int32),
      last.astype(jnp.int32), qp, pool)
    return out[:, :heads]
