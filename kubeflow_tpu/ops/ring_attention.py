"""Ring attention: exact causal attention over sequence-sharded inputs.

Long-context is first-class in the TPU build (the reference has nothing —
SURVEY.md §5 "Long-context / sequence parallelism: Absent"). Sequences are
sharded over the mesh's `seq` axis; each device holds one block of Q/K/V.
K/V blocks rotate around the ring with `lax.ppermute` (nearest-neighbor
ICI hops, no all-gather), and each device maintains a streaming-softmax
accumulator (running max / sum / output), so memory stays O(L/ring) and
the math is exactly softmax(QK^T)V.

Implementation is `shard_map` over the ambient mesh: inside, arrays are
the local blocks and collectives are explicit. Per ring step the K/V
transfer overlaps the block matmul (XLA schedules ppermute async).

References (public technique literature): Liu et al., "Ring Attention
with Blockwise Transformers for Near-Infinite Context" (2023);
flash-attention streaming softmax (Dao et al. 2022).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from kubeflow_tpu.parallel.mesh import AXIS_SEQ, BATCH_AXES

NEG_INF = -1e30


from kubeflow_tpu.parallel.mesh import current_mesh as _current_mesh


def _ring_perm(n: int) -> list[tuple[int, int]]:
    # send block to the next device; receive from the previous
    return [(i, (i + 1) % n) for i in range(n)]


def _block_attn(q, k, v, row_ids, col_ids, scale, causal,
                qseg=None, kseg=None, window=0):
    """One block pair: returns (unnormalized out, row max, row sum).
    qseg/kseg: optional [b, lq]/[b, lk] packing ids — cross-document
    pairs are masked like causal violations. window > 0 masks keys
    further than window-1 positions in the past (global indices, so the
    bound holds across ring hops)."""
    h = q.shape[2]
    if k.shape[2] != h:
        k = jnp.repeat(k, h // k.shape[2], axis=2)
        v = jnp.repeat(v, h // v.shape[2], axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    mask = None                                        # [b, q, k] or None
    if causal:
        mask = jnp.broadcast_to(
            row_ids[:, None] >= col_ids[None, :],      # global indices
            (q.shape[0],) + (row_ids.shape[0], col_ids.shape[0]))
    if window > 0:
        near = jnp.broadcast_to(
            (row_ids[:, None] - col_ids[None, :]) < window,
            (q.shape[0],) + (row_ids.shape[0], col_ids.shape[0]))
        mask = near if mask is None else mask & near
    if qseg is not None:
        seg = qseg[:, :, None] == kseg[:, None, :]
        mask = seg if mask is None else mask & seg
    if mask is not None:
        logits = jnp.where(mask[:, None], logits, NEG_INF)
    m = jnp.max(logits, axis=-1)                       # [b,h,q]
    # guard fully-masked rows: exp(NEG_INF - NEG_INF) would be 1
    m_safe = jnp.maximum(m, -1e29)
    p = jnp.exp(logits - m_safe[..., None])
    if mask is not None:
        p = jnp.where(mask[:, None], p, 0.0)
    l = jnp.sum(p, axis=-1)                            # [b,h,q]
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o, m_safe, l


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = AXIS_SEQ,
    mesh: Mesh | None = None,
    causal: bool = True,
    segment_ids: jax.Array | None = None,
    window: int = 0,
) -> jax.Array:
    """Exact attention over seq-sharded [B, L, H, D] arrays.

    ``causal=False`` gives the bidirectional (BERT-style) long-context
    path: same ring rotation and streaming softmax, no block masking.
    ``segment_ids`` ([B, L], sharded over `seq` like Q/K/V) mask packed
    documents apart; the K-side ids rotate around the ring with K/V.
    Falls back to single-block reference attention when the mesh has no
    `seq` axis (so the same model code runs on any mesh spec).
    """
    mesh = mesh or _current_mesh()
    if mesh is None or axis_name not in mesh.axis_names or mesh.shape[axis_name] == 1:
        from kubeflow_tpu.ops.attention import reference_attention

        return reference_attention(q, k, v, causal=causal,
                                   segment_ids=segment_ids, window=window)

    n_ring = mesh.shape[axis_name]
    scale = q.shape[-1] ** -0.5
    l_total = q.shape[1]
    l_block = l_total // n_ring
    assert l_block * n_ring == l_total, (l_total, n_ring)

    # GQA: repeat KV heads up to Q heads *before* sharding so the head dim
    # of all three operands shards identically over `model`. Without this,
    # n_kv_heads < model-axis size crashes shard_map (the weight-sharding
    # heuristic in parallel/shardings.py deliberately replicates such KV
    # weights, so the activations really do arrive with few heads).
    h = q.shape[2]
    if k.shape[2] != h:
        assert h % k.shape[2] == 0, (h, k.shape[2])
        k = jnp.repeat(k, h // k.shape[2], axis=2)
        v = jnp.repeat(v, h // v.shape[2], axis=2)
    from kubeflow_tpu.ops.attention import mesh_head_axis

    head_axis = mesh_head_axis(mesh, h)
    qkv_spec = P(BATCH_AXES, axis_name, head_axis, None)
    seg_spec = P(BATCH_AXES, axis_name)
    has_seg = segment_ids is not None

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec)
        + ((seg_spec,) if has_seg else ()),
        out_specs=qkv_spec,
        check_vma=False,
    )
    def _ring(q_blk, k_blk, v_blk, *maybe_seg):
        seg_blk = maybe_seg[0] if has_seg else None
        seq_idx = jax.lax.axis_index(axis_name)
        b, lq, h, d = q_blk.shape
        row_ids = seq_idx * l_block + jnp.arange(lq)
        perm = _ring_perm(n_ring)

        def accumulate(o, m, l, k_cur, v_cur, kseg_cur, i):
            src = (seq_idx - i) % n_ring           # owner of current K/V block
            col_ids = src * l_block + jnp.arange(k_cur.shape[1])
            o_i, m_i, l_i = _block_attn(q_blk, k_cur, v_cur, row_ids, col_ids,
                                        scale, causal,
                                        qseg=seg_blk, kseg=kseg_cur,
                                        window=window)
            m_new = jnp.maximum(m, m_i)
            alpha = jnp.exp(m - m_new)             # rescale old accumulator
            beta = jnp.exp(m_i - m_new)
            l_new = l * alpha + l_i * beta
            o_new = o * alpha[..., None].transpose(0, 2, 1, 3) + \
                o_i * beta[..., None].transpose(0, 2, 1, 3)
            return o_new, m_new, l_new

        def step(carry, i):
            o, m, l, k_cur, v_cur, kseg_cur = carry
            o, m, l = accumulate(o, m, l, k_cur, v_cur, kseg_cur, i)
            k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
            # the K-side packing ids travel WITH their K/V block
            kseg_nxt = (jax.lax.ppermute(kseg_cur, axis_name, perm)
                        if has_seg else kseg_cur)
            return (o, m, l, k_nxt, v_nxt, kseg_nxt), None

        o0 = jnp.zeros((b, lq, h, d), jnp.float32)
        m0 = jnp.full((b, h, lq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, lq), jnp.float32)
        kseg0 = seg_blk if has_seg else jnp.zeros((b, 1), jnp.int32)
        # causal + window: hop i's closest (q, k) pair sits (i-1)*l_block+1
        # positions apart, so blocks past ceil((window-1)/l_block) hops are
        # entirely outside the window — skip their compute AND their
        # ppermute traffic (static cap: window/l_block are Python ints).
        n_hops = n_ring
        if causal and window > 0:
            n_hops = min(n_ring, max(1, (window - 2) // l_block + 2))
        # scan the first n_hops-1 rotations; peel the last block so its
        # K/V are not ppermuted onward (that transfer is never read).
        (o, m, l, k_last, v_last, kseg_last), _ = jax.lax.scan(
            step, (o0, m0, l0, k_blk, v_blk, kseg0), jnp.arange(n_hops - 1)
        )
        o, m, l = accumulate(o, m, l, k_last, v_last, kseg_last, n_hops - 1)
        l = jnp.maximum(l, 1e-20)
        out = o / l[..., None].transpose(0, 2, 1, 3)
        return out.astype(q_blk.dtype)

    args = (q, k, v) + ((segment_ids,) if has_seg else ())
    return _ring(*args)
