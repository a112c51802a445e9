"""Ulysses sequence parallelism: all-to-all head sharding for attention.

The second long-context strategy of the §2.5 parallelism matrix
(SURVEY.md: "optional Ulysses-style all-to-all head sharding" — the
reference has no sequence parallelism at all). Complements ring
attention:

- **Ring** keeps sequence sharded and rotates K/V around the ICI ring —
  O(L/sp) memory per device, nearest-neighbor traffic, best for very
  long sequences.
- **Ulysses** re-shards *heads* instead: an all-to-all converts
  seq-sharded [B, L/sp, H, D] into head-sharded [B, L, H/sp, D], each
  device runs ordinary (flash) attention over the FULL sequence for its
  head group, and a second all-to-all restores sequence sharding. Two
  collectives per attention instead of sp-1 ppermutes; attention itself
  is completely local, so the fused flash kernel applies unmodified.

Both are exact. On a TPU torus the all-to-all rides ICI; XLA lowers
`lax.all_to_all` to the native collective.

Reference (public technique literature): Jacobs et al., "DeepSpeed
Ulysses: System Optimizations for Enabling Training of Extreme Long
Sequence Transformer Models" (2023).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from kubeflow_tpu.parallel.mesh import (
    BATCH_AXES,
    AXIS_MODEL,
    AXIS_SEQ,
    current_mesh as _current_mesh,
)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = AXIS_SEQ,
    mesh: Mesh | None = None,
    causal: bool = True,
    impl: str = "auto",
    segment_ids: jax.Array | None = None,
    block_q: int = 0,
    block_k: int = 0,
    window: int = 0,
) -> jax.Array:
    """Causal attention over seq-sharded [B, L, H, D] via head all-to-all.

    Requires heads-per-device (H / model-axis) divisible by the seq-axis
    size. ``segment_ids`` ([B, L], seq-sharded) support packed
    sequences: each device all-gathers the ids (int32, tiny next to
    K/V) and the local flash kernel masks cross-document pairs. Falls
    back to the dispatching local attention when the mesh has no `seq`
    axis, so the same model code runs on any mesh spec.
    """
    mesh = mesh or _current_mesh()
    if mesh is None or axis_name not in mesh.axis_names or mesh.shape[axis_name] == 1:
        from kubeflow_tpu.ops.attention import attention

        return attention(q, k, v, causal=causal, impl=impl,
                         segment_ids=segment_ids,
                         block_q=block_q, block_k=block_k, window=window)

    sp = mesh.shape[axis_name]
    h = q.shape[2]
    # GQA: repeat KV heads up to Q heads before sharding (same reasoning
    # as ring_attention: KV weights with few heads are replicated over
    # `model`, so activations arrive with the original head count).
    if k.shape[2] != h:
        assert h % k.shape[2] == 0, (h, k.shape[2])
        k = jnp.repeat(k, h // k.shape[2], axis=2)
        v = jnp.repeat(v, h // v.shape[2], axis=2)

    from kubeflow_tpu.ops.attention import (
        local_attention, mesh_head_axis, resolve_impl,
    )

    impl = resolve_impl(impl, q.shape[-1], who="ulysses")
    head_axis = mesh_head_axis(mesh, h)
    h_local = h // mesh.shape[AXIS_MODEL] if head_axis else h
    if h_local % sp != 0:
        raise ValueError(
            f"ulysses needs heads-per-device {h_local} divisible by "
            f"seq-axis size {sp} (H={h}, "
            f"model={mesh.shape.get(AXIS_MODEL, 1)})"
        )
    assert q.shape[1] % sp == 0, (q.shape, sp)

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
    def _ulysses(q_blk, k_blk, v_blk, *maybe_seg):
        # [b, L/sp, h_loc, d] -> [b, L, h_loc/sp, d]: gather sequence,
        # scatter heads. tiled=True keeps the named axes merged in-place.
        a2a = functools.partial(
            jax.lax.all_to_all, axis_name=axis_name, tiled=True
        )
        q_g = a2a(q_blk, split_axis=2, concat_axis=1)
        k_g = a2a(k_blk, split_axis=2, concat_axis=1)
        v_g = a2a(v_blk, split_axis=2, concat_axis=1)
        seg_full = None
        if has_seg:
            # attention is over the FULL sequence here: gather the ids
            seg_full = jax.lax.all_gather(
                maybe_seg[0], axis_name, axis=1, tiled=True)

        # already inside a shard_map: the single-device dispatch
        out = local_attention(q_g, k_g, v_g, causal=causal, impl=impl,
                              segment_ids=seg_full, block_q=block_q,
                              block_k=block_k, window=window)

        # [b, L, h_loc/sp, d] -> [b, L/sp, h_loc, d]: scatter sequence,
        # gather heads.
        return a2a(out, split_axis=1, concat_axis=2)

    args = (q, k, v) + ((segment_ids,) if has_seg else ())
    return _ulysses(*args)
