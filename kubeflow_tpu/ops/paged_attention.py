"""Pallas TPU paged decode attention: one query a slot, or the queries of
one block a slot, read straight out of the page pool.

The paged KV cache (runtime/kvcache.py) is a pool `[kv_pages, page_size,
kv_heads, head_dim]` and a table `[slots, max_pages]` of the pool pages
behind each slot's logical pages. At a decode step every slot has ONE
query, at position `last[b]`, that sees positions `start[b]..last[b]`
(left padding and the sliding window both only raise `start`). The
kernel walks the pages `start // PS .. last // PS` of the slot's table
row and nothing else: an idle slot (`start > last`) fetches no page and
gives zeros. A block model's step (models/transformer.py `gen_block`)
has `lq` queries a slot that all see that one range, the block's end
being `last`: they go through the same walk as `lq x heads` query rows
against each streamed block of pages. A chunk whose rows see different
ranges (prefill, speculative verify) is not this kernel's.

How it reads the pool as it is. A page `[PS, kv_heads, head_dim]` is one
contiguous block and is fetched by one DMA for K and one for V,
PAGES_PER_BLOCK pages to a compute block, two blocks in flight (the
next block of the slot, or the first block of the next slot that has
work, is fetched while this one is computed). In VMEM a block is viewed
as `[positions * kv_heads, head_dim]` rows, ALL kv heads interleaved,
and multiplied whole: `q [heads, hd] x rows^T` gives every query head
against every (position, kv head) row, and the columns of the other kv
heads are masked away before the softmax, so their probabilities are
exact zeros in the product with V. That spends kv_heads times the MXU
and exp work the scores need, on a kernel whose floor is the pages'
bytes: the MXU takes the K and V rows as its stationary operand once
either way, and no strided per-head load or second pool layout is
needed (ROADMAP D1: one cache layout).

Numerics are the gather path's (`Attention._decode_paged`): bf16 pool,
scores, running max, sum and accumulator in float32, probabilities cast
to the pool's dtype before the product with V. The two agree to the
pool dtype's rounding (tests/test_paged_attention.py).

Off the TPU the kernel runs in Pallas interpret mode
(`flash_attention.interpret_mode()`), for its own tests only:
`use_kernel` keeps the model on the gather path there.
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

log = logging.getLogger("kubeflow_tpu.paged_attention")

# Pages to a compute block: 16 pages of 16 positions are 256 positions,
# 2,048 (position, kv head) rows of Mistral's 8 kv heads, 512 KB each of
# K and V; four such buffers are 2 MB of VMEM.
PAGES_PER_BLOCK = 16
# A page's [kv_heads, head_dim] slabs are DMA'd whole: Mosaic wants the
# lanes full (compiled for a v5e: 64 is refused) and the kv heads to fill
# 32-bit sublane words in a power of two (bf16: 1 and 6 are refused).
PAGED_HEAD_DIMS = (128, 256)


def use_kernel(lq: int, pool_shape, pool_dtype,
               one_range: bool = False) -> bool:
    """Whether a paged decode step runs the kernel or the gather path,
    from what the code can observe, logged with the reason (the rule
    `ops/attention.py:resolve_impl` uses for flash): the kernel for one
    query a slot, or for a chunk whose rows all see one range
    (`one_range`: a block model's step), on a TPU backend, a pool
    `[pages, PS, kv_heads, head_dim]` whose pages it tiles, and a pool
    that lives whole on one device; the gather path for chunks whose
    rows see different ranges (prefill, speculative verify), off the
    TPU, and under a mesh of several devices, where the pool is sharded
    over kv heads."""
    backend = jax.default_backend()
    mesh = current_mesh()
    kv_heads, head_dim = pool_shape[-2:]
    pack = max(1, 4 // jnp.dtype(pool_dtype).itemsize)
    if lq != 1 and not one_range:
        # (a chunk whose rows see different ranges)
        choice, why = "gather", f"a chunk of {lq} queries a slot"
    elif backend != "tpu":
        choice, why = "gather", f"default backend is {backend!r}, not tpu"
    elif head_dim not in PAGED_HEAD_DIMS:
        choice, why = "gather", f"head_dim {head_dim} not in {PAGED_HEAD_DIMS}"
    elif kv_heads % pack or kv_heads & (kv_heads - 1):
        choice, why = "gather", (
            f"{kv_heads} kv heads of {jnp.dtype(pool_dtype).name} do not "
            "fill sublane tiles")
    elif mesh is not None and mesh.size > 1:
        choice, why = "gather", f"mesh of {mesh.size} devices shards the pool"
    else:
        choice, why = "kernel", (
            f"tpu backend, head_dim {head_dim}, "
            + ("one query a slot" if lq == 1
               else f"a block of {lq} queries a slot that see one range"))
    log.info("paged decode: paged attention -> %s (%s)", choice, why)
    return choice == "kernel"


def _kernel(pt_ref, start_ref, last_ref,     # scalar prefetch
            q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sems, buf_ref, m_s, l_s, acc_s,
            *, scale: float, group: int, q_heads: int, lq: int):
    _, ppb, ps, hkv, hd = kbuf.shape
    heads = q_ref.shape[1]                   # query rows: lq x q_heads, padded
    rows = ppb * ps * hkv                    # (position, kv head) rows a block
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
        buffer x: a page's K and V each in one piece. Pages past the
        walk's end are not fetched; their rows keep what an earlier
        block left there, which the position mask hides."""
        p0, n = span(j)
        first = blk * ppb

        def page(i, carry):
            # a wait needs the copy's size and semaphore, not its source
            phys = pt_ref[j, p0 + first + i] if go else 0
            for hbm, buf in ((k_hbm, kbuf), (v_hbm, vbuf)):
                dma = pltpu.make_async_copy(
                    hbm.at[phys], buf.at[x, i], sems.at[x])
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
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
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

    row = jax.lax.broadcasted_iota(jnp.int32, (heads, rows), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (heads, rows), 1)
    # a block's query rows lie query by query, each with every head
    head = row if lq == 1 else jax.lax.rem(row, q_heads)
    own_head = jax.lax.div(head, group) == jax.lax.rem(col, hkv)

    def attend(blk, x, edge: bool):
        q = q_ref[0]                                         # [heads, hd]
        k = kbuf.at[x].reshape(rows, hd)[...]
        v = vbuf.at[x].reshape(rows, hd)[...]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [heads, rows]
        ok = own_head
        if edge:   # the walk's first and last blocks hold its two ends
            pos = (p0 + blk * ppb) * ps + jax.lax.div(col, hkv)
            ok = ok & (pos >= start) & (pos <= last)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # every visited block holds a visible position, and each query
        # head its own kv head's column there: m_new is a real score and
        # the masked entries underflow to exact zeros
        p = jnp.exp(s - m_new)
        l_s[...] = alpha * l_s[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_s[...] = alpha * acc_s[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
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
        edge = jnp.logical_or(lo < start, lo + ppb * ps - 1 > last)

        @pl.when(edge)
        def _():
            attend(blk, x, True)

        @pl.when(jnp.logical_not(edge))
        def _():
            attend(blk, x, False)

        return 1 - x

    buf_ref[0] = jax.lax.fori_loop(0, n_blocks, block, buf_ref[0])
    o_ref[0] = (acc_s[...] / jnp.maximum(l_s[...], 1e-20)).astype(o_ref.dtype)


# The custom call's names in the device trace (`%<name>.N = ...
# custom-call(`): one query a slot, and a block of queries a slot. The
# benchmark finds the kernels by them (tests/test_trace_names.py).
KERNEL_NAME = "paged_decode_attention"
BLOCK_KERNEL_NAME = "paged_block_attention"


def paged_decode_attention(q, k_pages, v_pages, page_table, start, last):
    """q [b, heads, hd], or [b, lq, heads, hd] for the lq queries of a
    block; k_pages, v_pages [kv_pages, PS, kv_heads, hd] (they stay in
    HBM, in that layout); page_table [b, MP], start [b], last [b] int32.
    Returns q's shape: each of slot b's queries over the positions
    start[b]..last[b] of its pages, zeros where start > last. Every
    table entry of the pages that hold those positions must be a page of
    the pool; entries outside them are never read."""
    lq = q.shape[1] if q.ndim == 4 else 1
    out = _call(q.reshape(q.shape[0], -1, q.shape[-1]), k_pages, v_pages,
                page_table, start, last, lq=lq, interpret=interpret_mode())
    return out.reshape(q.shape)


# One jit for every layer's call: a model's layers share the shapes, so
# the kernel is traced once a process and lowered once a program, not
# once a layer (0.3 s a trace on the chip's host, my chip run, PR 27;
# the tick and the fused round call it 16 times, and set-up is measured).
@functools.partial(jax.jit, static_argnames=("lq", "interpret"))
def _call(q, k_pages, v_pages, page_table, start, last, *, lq: int,
          interpret: bool):
    b, heads, hd = q.shape                   # heads: lq x the query heads
    _, ps, hkv, _ = k_pages.shape
    q_heads = heads // lq
    if q_heads % hkv:
        raise ValueError(
            f"{q_heads} query heads do not group over {hkv} kv heads")
    # the query rows fill whole sublane tiles of the pool's dtype; rows
    # added here match no kv head and are cut off again below
    tile = 8 * 4 // q.dtype.itemsize
    padded = -(-heads // tile) * tile
    qp = jnp.pad(q, ((0, 0), (0, padded - heads), (0, 0)))
    q_spec = pl.BlockSpec((1, padded, hd), lambda i, *_: (i, 0, 0))
    buf = pltpu.VMEM((2, PAGES_PER_BLOCK, ps, hkv, hd), k_pages.dtype)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=hd ** -0.5, group=q_heads // hkv,
                          q_heads=q_heads, lq=lq),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[q_spec, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=q_spec,
            scratch_shapes=[
                buf, buf,
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),        # the buffer that is ahead
                pltpu.VMEM((padded, 1), jnp.float32),    # running max
                pltpu.VMEM((padded, 1), jnp.float32),    # running sum
                pltpu.VMEM((padded, hd), jnp.float32),   # accumulator
            ]),
        out_shape=jax.ShapeDtypeStruct((b, padded, hd), q.dtype),
        # slots in order: a slot's last block fetches the next slot's first
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=KERNEL_NAME if lq == 1 else BLOCK_KERNEL_NAME,
    )(page_table.astype(jnp.int32), start.astype(jnp.int32),
      last.astype(jnp.int32), qp, k_pages, v_pages)
    return out[:, :heads]
