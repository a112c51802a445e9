"""Pallas TPU flash attention (forward kernel + blockwise backward).

The hot op of the transformer path, built for the MXU:

- Forward is a Pallas kernel: grid (batch*heads, q_blocks, kv_blocks),
  streaming-softmax accumulators (running max / sum / output) in VMEM
  scratch that persist across the sequential kv-block grid dimension, so
  attention memory is O(BLOCK_Q x BLOCK_K) instead of O(L^2). Logits and
  accumulation in f32 on the MXU (`preferred_element_type`), inputs bf16.
- Causal blocks above the diagonal are predicated off with `@pl.when`
  (skipped entirely, ~2x speedup), diagonal blocks masked with
  `broadcasted_iota` (TPU needs >=2D iota).
- Sequence packing: optional per-position segment ids mask q->k pairs
  across document boundaries inside the same kernels (a separate
  custom_vjp variant, so the unsegmented hot path is untouched).
- Backward is fused Pallas too: a dq kernel (accumulates over kv blocks)
  and a dk/dv kernel (accumulates over q blocks), both recomputing
  probabilities from the saved logsumexp (the flash trick) so memory is
  O(BLOCK_Q x BLOCK_K); all matmuls on the MXU in f32. A blockwise XLA
  backward (`_flash_bwd_xla`) remains as the differential-test oracle.

Off the TPU the kernel runs in Pallas interpret mode (tests on the
virtual CPU mesh exercise the same code path). That choice is made once
per process, in `interpret_mode()`, and logged.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

log = logging.getLogger("kubeflow_tpu.flash_attention")

# 512x512 blocks amortize the per-block HBM re-reads of K/V across 4x
# more MXU work than 128x128 and still fit VMEM comfortably; what that
# buys in step time is not measured on the chip. Blocks clamp to the
# sequence length, so short-seq callers are unaffected; override per-run
# with KFTPU_FLASH_BLOCK_Q/K.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30

# None = not decided yet: the first flash_attention() call decides from
# the default backend and logs it. A test that lowers for the TPU from a
# CPU host sets False before tracing.
INTERPRET: bool | None = None


def interpret_mode() -> bool:
    """Whether the Pallas kernels run interpreted (plain jnp, any
    backend) or compiled by Mosaic (TPU only). Decided once per process."""
    global INTERPRET
    if INTERPRET is None:
        backend = jax.default_backend()
        INTERPRET = backend != "tpu"
        if INTERPRET:
            log.warning(
                "flash attention: default backend is %r, not tpu; the Pallas "
                "kernels run in INTERPRET mode (correct, slow, not the "
                "compiled kernel)", backend)
    return INTERPRET


def _vmem_spec(shape, imap) -> "pl.BlockSpec":
    return pl.BlockSpec(shape, imap, memory_space=pltpu.VMEM)


def _block_mask(*, causal, block_q, block_k, qi, ki, offset,
                qseg_row=None, kseg_row=None, window=0):
    """The block's combined validity mask: causal diagonal, sliding
    window (query i sees keys in (i - window, i]), and/or segment
    equality (sequence packing). None = nothing masked."""
    mask = None
    rows = cols = None
    if causal or window > 0:
        rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    if causal:
        mask = (qi * block_q + rows + offset) >= (ki * block_k + cols)
    if window > 0:
        near = ((qi * block_q + rows + offset)
                - (ki * block_k + cols)) < window
        mask = near if mask is None else mask & near
    if qseg_row is not None:
        seg = qseg_row[:, None] == kseg_row[None, :]   # [BQ, BK]
        mask = seg if mask is None else mask & seg
    return mask


def _block_runs(*, causal, block_q, block_k, qi, ki, offset, window=0):
    """Whether a (qi, ki) block pair can contain ANY valid logits —
    blocks past the causal diagonal or entirely left of the sliding
    window are skipped outright (never computed)."""
    run = True
    if causal:
        # the block's lowest k column vs its highest causal q row
        run = ki * block_k <= qi * block_q + (block_q - 1) + offset
    if window > 0:
        # smallest (qpos - kpos) over the block pair = LOWEST q row vs
        # HIGHEST k column; if even that closest pair is >= window away,
        # no pair in the block is inside the window => skip
        closest = ((qi * block_q + offset)            # lowest q row
                   - (ki * block_k + block_k - 1))    # highest k col
        run = jnp.logical_and(run, closest < window) if causal \
            else closest < window
    return run


def _recompute_p_ds(q, k, v, g, lse_row, delta_row, *, scale, causal,
                    block_q, block_k, qi, ki, offset,
                    qseg_row=None, kseg_row=None, window=0):
    """Shared backward block math: recompute probabilities from the saved
    lse and form ds = p * (dp - delta) * scale. Used by BOTH backward
    kernels so the masking/scaling convention can never diverge between
    dq and dk/dv."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                          # [BQ, BK]
    mask = _block_mask(causal=causal, block_q=block_q, block_k=block_k,
                       qi=qi, ki=ki, offset=offset,
                       qseg_row=qseg_row, kseg_row=kseg_row, window=window)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse_row[:, None])                  # [BQ, BK]
    dp = jax.lax.dot_general(
        g, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta_row[:, None]) * scale
    return p, ds


# --------------------------------------------------------------------------
# forward kernel
# --------------------------------------------------------------------------

def _kb_lo(qi, *, block_q, block_k, offset, window):
    """First k-block the sliding window can reach for q-block `qi`:
    the lowest q row's earliest in-window key position, floor-divided
    to blocks. Shared by the kernel and the BlockSpec index maps so the
    loaded block and the mask arithmetic can never disagree."""
    lo_pos = qi * block_q + offset - (window - 1)
    return jnp.maximum(0, lo_pos // block_k)


def _fwd_kernel(*refs, scale: float, causal: bool, block_q: int,
                block_k: int, offset: int, has_seg: bool, window: int = 0,
                nk_total: int = 0, pruned: bool = False):
    # offset = lk - lq: causality is end-aligned (query row i may attend
    # keys <= i + offset), matching reference_attention's tril(k=lk-lq) —
    # the KV-cache decode / chunked-prefill convention.
    if has_seg:
        (q_ref, k_ref, v_ref, qseg_ref, kseg_ref,
         o_ref, lse_ref, m_s, l_s, acc_s) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s = refs
        qseg_ref = kseg_ref = None
    qi = pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    if pruned:
        # windowed grid: axis 2 walks only the k-blocks the window can
        # reach (the BlockSpec index map loads block kb_lo + j, clamped);
        # ki here is the UNclamped logical block for the mask arithmetic
        ki = _kb_lo(qi, block_q=block_q, block_k=block_k, offset=offset,
                    window=window) + j
    else:
        ki = j

    @pl.when(j == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    # blocks past the causal diagonal / outside the sliding window
    # contribute nothing and are skipped outright
    run = _block_runs(causal=causal, block_q=block_q, block_k=block_k,
                      qi=qi, ki=ki, offset=offset, window=window)
    if pruned:
        # clamped duplicate loads past the last real k block never run
        run = jnp.logical_and(run, ki <= nk_total - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0]                                   # [BQ, D]
        k = k_ref[0]                                   # [BK, D]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                      # [BQ, BK]
        mask = _block_mask(
            causal=causal, block_q=block_q, block_k=block_k,
            qi=qi, ki=ki, offset=offset,
            qseg_row=None if qseg_ref is None else qseg_ref[0, 0],
            kseg_row=None if kseg_ref is None else kseg_ref[0, 0],
            window=window)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_s[:]                                # [BQ, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                         # [BQ, BK]
        l_new = l_s[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_s[:] = acc_s[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_s[:] = m_new
        l_s[:] = l_new

    @pl.when(j == nj - 1)
    def _finalize():
        l = jnp.maximum(l_s[:], 1e-20)
        o_ref[0] = (acc_s[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_s[:] + jnp.log(l))[:, 0]


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
               qseg=None, kseg=None, window=0):
    """q,k,v: [BH, L, D] (kv already repeated to q heads; v's last
    size may be another than q's and k's, and is the output's); qseg/kseg:
    optional [BH, 1, L] int32 segment ids (sequence packing)."""
    bh, lq, d = q.shape
    lk, dv = k.shape[1], v.shape[2]
    nq = pl.cdiv(lq, block_q)
    nk = pl.cdiv(lk, block_k)
    offset = lk - lq
    has_seg = qseg is not None
    # Windowed grid pruning: with a sliding window only the k-blocks in
    # (qpos - window, qpos] are reachable, so the k axis of the grid
    # shrinks from nk to the window span — out-of-window blocks are
    # never DMA'd at all (round 3 skipped their COMPUTE but still
    # streamed them from HBM). Index maps load kb_lo(qi) + j, clamped;
    # the kernel re-derives the logical ki for its masks.
    pruned = causal and window > 0 and lq > 1
    nkw = min(nk, pl.cdiv(block_q + window, block_k) + 1) if pruned else nk

    def kj(b, i, j):
        if not pruned:
            return (b, j, 0)
        lo = _kb_lo(i, block_q=block_q, block_k=block_k, offset=offset,
                    window=window)
        return (b, jnp.minimum(lo + j, nk - 1), 0)

    def kj_seg(b, i, j):
        bj, kb, _ = kj(b, i, j)
        return (bj, 0, kb)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, offset=offset, has_seg=has_seg,
        window=window, nk_total=nk, pruned=pruned,
    )
    scratch = [
        pltpu.VMEM((block_q, 1), jnp.float32),   # running max
        pltpu.VMEM((block_q, 1), jnp.float32),   # running sum
        pltpu.VMEM((block_q, dv), jnp.float32),  # output accumulator
    ]
    bs = _vmem_spec

    in_specs = [
        bs((1, block_q, d), lambda b, i, j: (b, i, 0)),
        bs((1, block_k, d), kj),
        bs((1, block_k, dv), kj),
    ]
    operands = [q, k, v]
    if has_seg:
        in_specs += [
            bs((1, 1, block_q), lambda b, i, j: (b, 0, i)),
            bs((1, 1, block_k), kj_seg),
        ]
        operands += [qseg, kseg]

    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nkw),
        in_specs=in_specs,
        out_specs=[
            bs((1, block_q, dv), lambda b, i, j: (b, i, 0)),
            # lse rides as [BH, 1, L] so the block's trailing dims are
            # (1, block_q) — legal under Mosaic's (8, 128) tiling rule
            # (1 == the full middle dim; block_q % 128 == 0).
            bs((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lq, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, lq), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(*operands)
    return out, lse.reshape(bh, lq)


# --------------------------------------------------------------------------
# backward: fused Pallas kernels (dq; dk/dv), with the saved-lse flash
# trick — probabilities are recomputed blockwise, memory stays
# O(BLOCK_Q x BLOCK_K). Two kernels because the two gradients accumulate
# over different grid axes (dq over kv blocks, dk/dv over q blocks);
# each keeps its accumulator in VMEM scratch across the sequential inner
# grid dimension, exactly like the forward.
# --------------------------------------------------------------------------

def _bwd_dq_kernel(*refs, scale, causal, block_q, block_k, offset, has_seg,
                   window=0, nk_total=0, pruned=False):
    if has_seg:
        (q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
         qseg_ref, kseg_ref, dq_ref, acc_s) = refs
    else:
        (q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
         dq_ref, acc_s) = refs
        qseg_ref = kseg_ref = None
    qi = pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    if pruned:
        # windowed grid (see _flash_fwd): axis 2 walks only the
        # window-reachable k blocks; the index map loads kb_lo + j
        ki = _kb_lo(qi, block_q=block_q, block_k=block_k, offset=offset,
                    window=window) + j
    else:
        ki = j

    @pl.when(j == 0)
    def _init():
        acc_s[:] = jnp.zeros_like(acc_s)

    run = _block_runs(causal=causal, block_q=block_q, block_k=block_k,
                      qi=qi, ki=ki, offset=offset, window=window)
    if pruned:
        run = jnp.logical_and(run, ki <= nk_total - 1)

    @pl.when(run)
    def _compute():
        k = k_ref[0]                                   # [BK, D]
        _, ds = _recompute_p_ds(
            q_ref[0], k, v_ref[0], g_ref[0], lse_ref[0, 0], delta_ref[0, 0],
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            qi=qi, ki=ki, offset=offset,
            qseg_row=None if qseg_ref is None else qseg_ref[0, 0],
            kseg_row=None if kseg_ref is None else kseg_ref[0, 0],
            window=window)
        acc_s[:] = acc_s[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == nj - 1)
    def _finalize():
        dq_ref[0] = acc_s[:].astype(dq_ref.dtype)


def _qb_lo(ki, *, block_q, block_k, offset):
    """First q-block the CAUSAL constraint lets attend k-block `ki`
    (qpos + offset >= kpos). The window bounds the other end: q rows
    further than window-1 past a key can't see it, so the valid q span
    per k block is at most cdiv(block_k + window, block_q) + 1 blocks."""
    return jnp.maximum(0, (ki * block_k - offset) // block_q)


def _bwd_dkv_kernel(*refs, scale, causal, block_q, block_k, offset, has_seg,
                    window=0, nq_total=0, pruned=False):
    if has_seg:
        (q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
         qseg_ref, kseg_ref, dk_ref, dv_ref, dk_s, dv_s) = refs
    else:
        (q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_s, dv_s) = refs
        qseg_ref = kseg_ref = None
    ki = pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    if pruned:
        qi = _qb_lo(ki, block_q=block_q, block_k=block_k, offset=offset) + j
    else:
        qi = j

    @pl.when(j == 0)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    run = _block_runs(causal=causal, block_q=block_q, block_k=block_k,
                      qi=qi, ki=ki, offset=offset, window=window)
    if pruned:
        run = jnp.logical_and(run, qi <= nq_total - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0]                                   # [BQ, D]
        g = g_ref[0]
        p, ds = _recompute_p_ds(
            q, k_ref[0], v_ref[0], g, lse_ref[0, 0], delta_ref[0, 0],
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            qi=qi, ki=ki, offset=offset,
            qseg_row=None if qseg_ref is None else qseg_ref[0, 0],
            kseg_row=None if kseg_ref is None else kseg_ref[0, 0],
            window=window)
        dv_s[:] = dv_s[:] + jax.lax.dot_general(
            p.astype(g.dtype), g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                              # [BK, D]
        dk_s[:] = dk_s[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == nj - 1)
    def _finalize():
        dk_ref[0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[:].astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, out, lse, g, scale, causal, block_q, block_k,
                      interpret, qseg=None, kseg=None, window=0):
    """Fused backward: q,k,v,out,g [BH, L, D]; lse [BH, L]; qseg/kseg
    optional [BH, 1, L] int32."""
    bh, lq, d = q.shape
    lk = k.shape[1]
    nq = pl.cdiv(lq, block_q)
    nk = pl.cdiv(lk, block_k)
    offset = lk - lq
    has_seg = qseg is not None
    # delta_i = sum_d(do_i * o_i): one cheap rowwise reduction in XLA.
    # lse/delta ride as [BH, 1, L] for Mosaic's (8, 128) tiling rule.
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = delta.reshape(bh, 1, lq)
    lse = lse.reshape(bh, 1, lq)

    bs = _vmem_spec
    # windowed grid pruning, mirrored from _flash_fwd: out-of-window
    # blocks are never DMA'd in the backward either (it carries ~2x the
    # forward's attention HBM traffic)
    pruned = causal and window > 0 and lq > 1
    nkw = min(nk, pl.cdiv(block_q + window, block_k) + 1) if pruned else nk
    nqw = min(nq, pl.cdiv(block_k + window, block_q) + 1) if pruned else nq

    def kj(b, i, j):
        if not pruned:
            return (b, j, 0)
        lo = _kb_lo(i, block_q=block_q, block_k=block_k, offset=offset,
                    window=window)
        return (b, jnp.minimum(lo + j, nk - 1), 0)

    def kj_seg(b, i, j):
        bj, kb, _ = kj(b, i, j)
        return (bj, 0, kb)

    dq_specs = [
        bs((1, block_q, d), lambda b, i, j: (b, i, 0)),   # q
        bs((1, block_k, d), kj),                          # k
        bs((1, block_k, d), kj),                          # v
        bs((1, block_q, d), lambda b, i, j: (b, i, 0)),   # g
        bs((1, 1, block_q), lambda b, i, j: (b, 0, i)),   # lse
        bs((1, 1, block_q), lambda b, i, j: (b, 0, i)),   # delta
    ]
    dq_operands = [q, k, v, g, lse, delta]
    if has_seg:
        dq_specs += [
            bs((1, 1, block_q), lambda b, i, j: (b, 0, i)),   # qseg
            bs((1, 1, block_k), kj_seg),                      # kseg
        ]
        dq_operands += [qseg, kseg]

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, offset=offset,
                          has_seg=has_seg, window=window, nk_total=nk,
                          pruned=pruned),
        grid=(bh, nq, nkw),
        in_specs=dq_specs,
        out_specs=bs((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, lq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(*dq_operands)

    def qi_map(b, kb, j):
        # dkv grid is (bh, k-block, q-walk): q block loaded = qb_lo + j
        if not pruned:
            return (b, j, 0)
        lo = _qb_lo(kb, block_q=block_q, block_k=block_k, offset=offset)
        return (b, jnp.minimum(lo + j, nq - 1), 0)

    def qi_row(b, kb, j):
        bj, qb, _ = qi_map(b, kb, j)
        return (bj, 0, qb)

    dkv_specs = [
        bs((1, block_q, d), qi_map),                      # q
        bs((1, block_k, d), lambda b, j, i: (b, j, 0)),   # k
        bs((1, block_k, d), lambda b, j, i: (b, j, 0)),   # v
        bs((1, block_q, d), qi_map),                      # g
        bs((1, 1, block_q), qi_row),                      # lse
        bs((1, 1, block_q), qi_row),                      # delta
    ]
    dkv_operands = [q, k, v, g, lse, delta]
    if has_seg:
        dkv_specs += [
            bs((1, 1, block_q), qi_row),                      # qseg
            bs((1, 1, block_k), lambda b, j, i: (b, 0, j)),   # kseg
        ]
        dkv_operands += [qseg, kseg]

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, offset=offset,
                          has_seg=has_seg, window=window, nq_total=nq,
                          pruned=pruned),
        grid=(bh, nk, nqw),
        in_specs=dkv_specs,
        out_specs=[
            bs((1, block_k, d), lambda b, j, i: (b, j, 0)),
            bs((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, lk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(*dkv_operands)
    return dq, dk, dv


# --------------------------------------------------------------------------
# backward (blockwise XLA fallback / differential-test oracle)
# --------------------------------------------------------------------------

def _flash_bwd_xla(q, k, v, out, lse, g, scale, causal, block_k):
    """Recompute-p backward. All [BH, L, D]; lse [BH, L]."""
    f32 = jnp.float32
    qf, kf, vf, gf = (x.astype(f32) for x in (q, k, v, g))
    # delta_i = sum_d(do_i * o_i) (rowwise), the standard flash-bwd term
    delta = jnp.sum(gf * out.astype(f32), axis=-1)           # [BH, L]
    lk = k.shape[1]
    nk = pl.cdiv(lk, block_k)
    positions_q = jnp.arange(q.shape[1])

    def kv_block(carry, jb):
        dq_acc = carry
        ks = jax.lax.dynamic_slice_in_dim(kf, jb * block_k, block_k, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(vf, jb * block_k, block_k, axis=1)
        s = jnp.einsum("bqd,bkd->bqk", qf, ks) * scale
        if causal:
            cols = jb * block_k + jnp.arange(block_k)
            mask = (positions_q[:, None] + (lk - q.shape[1])) >= cols[None, :]
            s = jnp.where(mask[None], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])                      # [BH, Lq, BK]
        dv = jnp.einsum("bqk,bqd->bkd", p, gf)
        dp = jnp.einsum("bqd,bkd->bqk", gf, vs)
        ds = p * (dp - delta[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum("bqk,bkd->bqd", ds, ks)
        dk = jnp.einsum("bqk,bqd->bkd", ds, qf)
        return dq_acc, (dk, dv)

    dq, (dk_blocks, dv_blocks) = jax.lax.scan(
        kv_block, jnp.zeros_like(qf), jnp.arange(nk)
    )
    dk = jnp.moveaxis(dk_blocks, 0, 1).reshape(k.shape[0], nk * block_k, k.shape[2])
    dv = jnp.moveaxis(dv_blocks, 0, 1).reshape(*dk.shape)
    dk = dk[:, :lk]
    dv = dv[:, :lk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------

# qseg/kseg are None (empty pytrees) on the unsegmented hot path —
# has_seg resolves statically at trace time, so the compiled kernel is
# bit-identical to the pre-segments one.
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash(q, k, v, qseg, kseg, scale, causal, block_q, block_k, window,
           interpret):
    out, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                        interpret, qseg=qseg, kseg=kseg, window=window)
    return out


def _flash_vjp_fwd(q, k, v, qseg, kseg, scale, causal, block_q, block_k,
                   window, interpret):
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                          interpret, qseg=qseg, kseg=kseg, window=window)
    # jax.checkpoint partial-eval looks THROUGH custom_vjp fwd rules, so
    # these residuals are policy-visible equations: naming them lets a
    # remat policy keep exactly (out, lse) — and with q/k/v anchored by
    # the model, the backward then runs with ZERO flash-forward replay.
    # checkpoint_name is identity outside remat; the hot path is
    # unchanged.
    from jax.ad_checkpoint import checkpoint_name

    out_r = checkpoint_name(out, "attn_flash")
    lse_r = checkpoint_name(lse, "attn_flash")
    return out, (q, k, v, qseg, kseg, out_r, lse_r)


def _flash_vjp_bwd(scale, causal, block_q, block_k, window, interpret, res,
                   g):
    import numpy as np

    q, k, v, qseg, kseg, out, lse = res
    dq, dk, dv = _flash_bwd_pallas(
        q, k, v, out, lse, g, scale, causal, block_q, block_k,
        interpret, qseg=qseg, kseg=kseg, window=window)
    # integer segment ids take float0 cotangents (None stays None)
    zero = lambda a: (None if a is None  # noqa: E731
                      else np.zeros(a.shape, jax.dtypes.float0))
    return dq, dk, dv, zero(qseg), zero(kseg)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    segment_ids: jax.Array | None = None,
    kv_segment_ids: jax.Array | None = None,
    window: int = 0,
) -> jax.Array:
    """Fused attention. [B, L, H, D] in / out; GQA via fewer KV heads.
    Values narrower than the keys (v [B, L, H, Dv], Dv != D: latent
    attention's up-projected form) give [B, L, H, Dv], forward only: the
    backward kernels are written for one size.

    window > 0 = sliding-window attention: keys further than window-1
    positions in the PAST are masked (one-sided; with causal=False,
    future keys stay fully attended — same convention as
    reference_attention). A causal windowed call walks a window-sized
    grid of key blocks (`_flash_fwd`: `pruned`, `_kb_lo`), so the blocks
    left of the window are neither computed nor fetched: MXU work and HBM
    traffic are both O(L * window).

    segment_ids: optional [B, L] int32 sequence-packing ids — query i
    attends key j only when their ids match (on top of causality), so
    one row can carry several packed documents without cross-attention.
    kv_segment_ids defaults to segment_ids (self-attention)."""
    b, lq, h, d = q.shape
    lk = k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    if k.shape[2] != h:
        assert h % k.shape[2] == 0, (h, k.shape[2])
        rep = h // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    # Clamp to the sequence, then halve until the block divides it (not
    # below the 128-lane tile): a 640-token sequence runs at block 128
    # instead of erroring against the swept 512 default.
    block_q = min(block_q, lq)
    while block_q > 128 and lq % block_q:
        block_q //= 2
    block_k = min(block_k, lk)
    while block_k > 128 and lk % block_k:
        block_k //= 2
    if lq % block_q or lk % block_k:
        raise ValueError(
            f"sequence lengths ({lq}, {lk}) must be multiples of the block "
            f"sizes ({block_q}, {block_k}); pad inputs or pass block sizes"
        )
    # [B, L, H, D] -> [B*H, L, D]
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, lq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, lk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, lk, v.shape[-1])
    qseg = kseg = None
    if kv_segment_ids is not None and segment_ids is None:
        raise ValueError(
            "kv_segment_ids without segment_ids — key-side masking would "
            "be silently dropped; pass the query ids too")
    if segment_ids is not None:
        if kv_segment_ids is None:
            kv_segment_ids = segment_ids
        # [B, L] -> [B*H, 1, L]: per-head copies of the per-batch ids
        # (int32, ~1 MB at bench shapes — negligible next to K/V).
        qseg = jnp.repeat(segment_ids.astype(jnp.int32)[:, None], h, axis=1
                          ).reshape(b * h, 1, lq)
        kseg = jnp.repeat(kv_segment_ids.astype(jnp.int32)[:, None], h, axis=1
                          ).reshape(b * h, 1, lk)
    if vt.shape[-1] != d:
        # outside the custom_vjp: the forward kernel alone takes the pair
        out, _ = _flash_fwd(qt, kt, vt, scale, causal, block_q, block_k,
                            interpret_mode(), qseg, kseg, window)
    else:
        out = _flash(qt, kt, vt, qseg, kseg, scale, causal, block_q,
                     block_k, window, interpret_mode())
    return out.reshape(b, h, lq, -1).transpose(0, 2, 1, 3)
