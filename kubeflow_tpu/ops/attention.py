"""Attention entry point: one call site, backend chosen per platform.

The reference platform never owns attention math (it ships TF images);
for the TPU build it is in-scope. `attention()` routes to:

- the Pallas flash-attention kernel on TPU (fused, O(L) memory, MXU-tiled),
  inside `jax.shard_map` when the ambient mesh has more than one device;
- a plain XLA einsum path elsewhere (tests on the virtual CPU mesh) and
  for head sizes the kernel doesn't tile.

`impl="auto"` is resolved by `resolve_impl`, by one rule (backend and
head size), and it logs its choice.

All shapes are [batch, length, heads, head_dim] ("BLHD"), GQA supported by
passing fewer KV heads than Q heads.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from kubeflow_tpu.parallel.mesh import AXIS_MODEL, BATCH_AXES, current_mesh

log = logging.getLogger("kubeflow_tpu.attention")


def _repeat_kv(k: jax.Array, num_q_heads: int) -> jax.Array:
    """Broadcast KV heads up to Q heads for grouped-query attention."""
    num_kv = k.shape[2]
    if num_kv == num_q_heads:
        return k
    assert num_q_heads % num_kv == 0, (num_q_heads, num_kv)
    return jnp.repeat(k, num_q_heads // num_kv, axis=2)


def reference_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    segment_ids: jax.Array | None = None,
    kv_segment_ids: jax.Array | None = None,
    window: int = 0,
) -> jax.Array:
    """XLA attention in f32 accumulation. BLHD in, BLHD out.
    window > 0 = sliding-window: query i attends keys in
    (i - window, i] (end-aligned like the causal mask).
    kv_segment_ids: the keys' ids where there are more keys than
    queries; the queries' own by default."""
    b, lq, h, d = q.shape
    lk = k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    if causal:
        mask = jnp.tril(jnp.ones((lq, lk), dtype=bool), k=lk - lq)
        logits = jnp.where(mask[None, None], logits, -1e30)
    if window > 0:
        qpos = jnp.arange(lq)[:, None] + (lk - lq)
        kpos = jnp.arange(lk)[None, :]
        near = qpos - kpos < window
        logits = jnp.where(near[None, None], logits, -1e30)
    if segment_ids is not None:
        kseg = segment_ids if kv_segment_ids is None else kv_segment_ids
        seg_mask = segment_ids[:, :, None] == kseg[:, None, :]
        logits = jnp.where(seg_mask[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


FLASH_HEAD_DIMS = (64, 128, 256)
# (key size, value size) of heads whose values are narrower than their
# keys, which the forward kernel takes as they are (latent attention's
# up-projected form); forward only
FLASH_HEAD_PAIRS = ((192, 128),)


def resolve_impl(impl: str, head_dim: int, who: str = "attention",
                 v_dim: int | None = None) -> str:
    """Turn ``auto`` into ``flash`` or ``reference`` and log which, with
    the reason: the flash kernel on a TPU backend for the head sizes it
    tiles (`v_dim`: the values' size where it is not the keys'), the
    reference path (whose [B, H, L, L] float32 scores do not fit at
    training lengths) otherwise. That is the whole rule: a sequence
    length the kernel cannot tile raises in `flash_attention` rather
    than quietly taking the reference path. Other values pass
    through."""
    if impl != "auto":
        return impl
    backend = jax.default_backend()
    pair = v_dim not in (None, head_dim)
    if backend != "tpu":
        choice, why = "reference", f"default backend is {backend!r}, not tpu"
    elif pair and (head_dim, v_dim) not in FLASH_HEAD_PAIRS:
        choice, why = "reference", (
            f"keys of {head_dim} and values of {v_dim} not in "
            f"{FLASH_HEAD_PAIRS}")
    elif pair:
        choice, why = "flash", (
            f"tpu backend, keys of {head_dim} and values of {v_dim}")
    elif head_dim not in FLASH_HEAD_DIMS:
        choice, why = "reference", (
            f"head_dim {head_dim} not in {FLASH_HEAD_DIMS}")
    else:
        choice, why = "flash", f"tpu backend, head_dim {head_dim}"
    log.info("%s: attention impl auto -> %s (%s)", who, choice, why)
    return choice


def mesh_head_axis(mesh: Mesh, n_heads: int) -> str | None:
    """The mesh axis attention heads shard over inside a shard_map:
    `model` when it is wider than 1 and divides the heads, else None
    (heads replicated)."""
    model_size = mesh.shape.get(AXIS_MODEL, 1)
    return AXIS_MODEL if model_size > 1 and n_heads % model_size == 0 else None


# The name of this nested jit is a contract: the flash kernels carry no
# name of their own, so their custom calls appear in the device trace as
# `%local_attention.N`, and the benchmark's flash_roofline.train finds
# them by that (benchmarks/metrics/flash_roofline.train.json; pinned by
# tests/test_trace_names.py). A `name=` on the pallas_calls would replace
# it (the instruction becomes `%<name>.N`) and silence the metric.
@functools.partial(jax.jit,
                   static_argnames=("causal", "impl", "block_q", "block_k",
                                    "window", "scale"))
def local_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    impl: str,
    segment_ids: jax.Array | None = None,
    block_q: int = 0,
    block_k: int = 0,
    window: int = 0,
    kv_segment_ids: jax.Array | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Attention over arrays that live on ONE device (or inside a
    shard_map body). impl: flash | reference — `auto` is settled by the
    caller (`resolve_impl`), before any shard_map is entered. `scale`:
    the softmax scale where it is not head_dim ** -0.5.

    segment_ids (sequence-packing masks) run through the Pallas kernel
    too — the reference path's [B, H, L, L] scores are unusable at
    training lengths. More keys than queries (a prompt's rung behind the
    pages of a prefix hit) are end-aligned, and bring `kv_segment_ids`.
    """
    if impl == "reference":
        return reference_attention(q, k, v, causal=causal, scale=scale,
                                   segment_ids=segment_ids,
                                   kv_segment_ids=kv_segment_ids,
                                   window=window)
    if impl != "flash":
        raise ValueError(f"unknown attention impl {impl!r} (flash|reference; "
                         "auto is for attention() or the model config)")
    import os

    from kubeflow_tpu.ops.flash_attention import (
        DEFAULT_BLOCK_Q,
        DEFAULT_BLOCK_K,
        flash_attention,
    )

    # kernel tile sizes: explicit args win (config-plumbed operating
    # points), else the env override (autotuning sweeps set it per
    # subprocess; read at trace time), else the default
    bq = block_q or int(os.environ.get("KFTPU_FLASH_BLOCK_Q",
                                       DEFAULT_BLOCK_Q))
    bk = block_k or int(os.environ.get("KFTPU_FLASH_BLOCK_K",
                                       DEFAULT_BLOCK_K))
    return flash_attention(q, k, v, causal=causal, scale=scale,
                           block_q=bq, block_k=bk,
                           segment_ids=segment_ids,
                           kv_segment_ids=kv_segment_ids, window=window)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    impl: str = "auto",
    segment_ids: jax.Array | None = None,
    block_q: int = 0,
    block_k: int = 0,
    window: int = 0,
    kv_segment_ids: jax.Array | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Dispatching attention for the model. impl: auto | flash | reference.

    Under an ambient mesh with more than one device the flash kernel
    runs inside `jax.shard_map`: a Mosaic kernel is opaque to GSPMD
    ("Mosaic kernels cannot be automatically partitioned"), so each
    device gets its batch rows (over BATCH_AXES) and its heads (over
    `model`, where they divide) and runs the kernel on that block. The
    sequence is whole on every device: a `seq` axis wider than 1 costs
    an all-gather here, which is what ring/ulysses exist to avoid. The
    reference path is plain jnp and is left to GSPMD.

    `auto` reaches here only from callers that did not go through the
    model registry, which settles it at build (`resolve_impl`).
    """
    impl = resolve_impl(impl, q.shape[-1], v_dim=v.shape[-1])
    local = functools.partial(local_attention, causal=causal, impl=impl,
                              block_q=block_q, block_k=block_k, window=window)
    if scale is not None:   # (a call without one is the call it was)
        local = functools.partial(local, scale=scale)
    mesh = current_mesh()
    # the ids that were given, under local_attention's names for them
    segs = {name: ids for name, ids in (("segment_ids", segment_ids),
                                        ("kv_segment_ids", kv_segment_ids))
            if ids is not None}
    if impl != "flash" or mesh is None or mesh.size == 1:
        return local(q, k, v, **segs)
    head_axis = mesh_head_axis(mesh, q.shape[2])
    if head_axis and k.shape[2] % mesh.shape[AXIS_MODEL]:
        # GQA with fewer KV heads than `model` is wide: repeat them up
        # to the Q heads so all three operands shard alike
        k = _repeat_kv(k, q.shape[2])
        v = _repeat_kv(v, q.shape[2])
    qkv_spec = P(BATCH_AXES, None, head_axis, None)
    in_specs = (qkv_spec,) * 3 + (P(BATCH_AXES, None),) * len(segs)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=qkv_spec, check_vma=False)
    def _sharded(q_blk, k_blk, v_blk, *ids):
        return local(q_blk, k_blk, v_blk, **dict(zip(segs, ids)))

    return _sharded(q, k, v, *segs.values())
