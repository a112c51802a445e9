"""Decoder-only transformer LM — the flagship distributed workload.

The reference's "big" workloads are opaque TF payloads; its platform
capabilities (PS data-parallelism, MPI allreduce) cap out at data
parallelism (SURVEY.md §2.5). This model is where the TPU build goes
beyond: every weight carries a mesh-axis annotation, so one module
definition runs under any combination of

- data / fsdp  (batch + ZeRO-3 parameter sharding)
- model        (Megatron-style tensor parallelism: column-parallel up
                projections, row-parallel down projections — XLA inserts
                the psum on the row-parallel matmul output)
- seq          (sequence/context parallelism; long sequences route
                attention through ops.ring_attention over the ICI ring)
- pipe         (pipeline stages via parallel.pipeline.PipelinedTransformer)
- expert       (MoE blocks; ops.moe all-to-all dispatch)

Architecture: pre-RMSNorm, rotary embeddings, GQA, SwiGLU — the standard
modern decoder (Llama-class), in bf16 with f32 logits.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from kubeflow_tpu.models.registry import register_model
from kubeflow_tpu.parallel.mesh import (
    AXIS_EXPERT,
    AXIS_FSDP,
    AXIS_MODEL,
    AXIS_SEQ,
    BATCH_AXES,
)

Dtype = Any

# Activation sharding: batch over (dcn, data, fsdp), sequence over seq,
# features over model only where the tensor is the "wide" intermediate.
HIDDEN_SPEC = P(BATCH_AXES, AXIS_SEQ, None)
WIDE_SPEC = P(BATCH_AXES, AXIS_SEQ, AXIS_MODEL)


def shard(x: jax.Array, spec: P) -> jax.Array:
    from kubeflow_tpu.parallel.mesh import shard_constraint

    return shard_constraint(x, spec)


def _part(init, names):
    return nn.with_partitioning(init, names)


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """What one layer of the stack is, where the layers differ
    (`TransformerConfig.layer_pattern`)."""

    window: int = 0      # sliding-window attention; 0 = full causal
    rope: bool = True    # rotary embedding on q and k; False = none at all
    moe: bool = False    # a mixture layer (ops/moe.py) or a dense SwiGLU
    latent: bool = False  # latent attention (`LatentAttention`) or K and V heads


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    n_kv_heads: int = 4
    head_dim: int = 64
    d_ff: int = 2048
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    dtype: Dtype = jnp.bfloat16
    attention_impl: str = "auto"   # auto | flash | reference | ring | ulysses
    # Flash kernel tiles (0 = KFTPU_FLASH_BLOCK_Q/K env, else the swept
    # default): explicit here so a measured operating point reproduces
    # from config alone, with no process-global state.
    flash_block_q: int = 0
    flash_block_k: int = 0
    # "auto" stores the decode KV cache in `dtype`; "int8" quantizes it
    # (per-token-head scales) — at long contexts the cache dominates
    # decode HBM traffic and int8 halves it.
    kv_cache_dtype: str = "auto"
    # Sliding-window attention (Mistral-style): keys further than
    # window-1 positions in the past are masked; flash walks a
    # window-sized grid of key blocks, so the blocks left of the window
    # are neither computed nor fetched (MXU work and HBM traffic
    # O(L * window) — see ops/flash_attention.py). 0 = full causal.
    # Supported by every attention path: flash/reference/ring/ulysses
    # in training, and decode masks the cache identically (train/serve
    # parity).
    attention_window: int = 0
    # Bounded decode cache for windowed models: the KV cache holds only
    # the last `attention_window` positions (slot = position % window),
    # so serving memory AND per-step cache bandwidth are O(window), not
    # O(max_seq). Requires attention_window > 0. Exact: token-for-token
    # equal to the full cache under the same window (pinned by tests).
    rolling_kv_cache: bool = False
    # Paged decode KV cache (serving): both > 0 turns the decode cache
    # into a fixed pool of `kv_pages` pages of `kv_page_size` positions
    # each, SHARED across decode slots; callers pass per-slot page
    # tables as a traced `page_table` [B, max_pages] argument
    # (runtime/kvcache.py owns allocation/prefix-sharing on the host).
    # Page 0 is the trash page: idle slots' writes land there so a
    # freed page can be re-owned by another slot without a stale
    # lockstep write corrupting it. Exact: token-for-token equal to
    # the dense cache (pinned by tests).
    kv_pages: int = 0
    kv_page_size: int = 0
    remat: bool = False
    # "full": nothing_saveable — minimum memory, recompute everything.
    # "dots": keep matmul outputs, recompute only elementwise — most of
    # the memory win at a fraction of the recompute tax (the MXU work is
    # NOT redone; usually the right policy for transformers).
    remat_policy: str = "full"
    # MoE: every `moe_every`-th block is a mixture layer (0 = dense only)
    moe_every: int = 0
    n_experts: int = 8
    expert_top_k: int = 2
    # Dispatch implementation (ops/moe.py): "auto" picks the sort+
    # all-to-all sparse path on meshes it covers (fsdp/model/seq/pipe
    # all 1), else the dense one-hot-einsum oracle; "dense"/"sparse"
    # force one.
    moe_impl: str = "auto"
    # Expert capacity = factor * tokens * top_k / n_experts per shard
    # (per batch row in the dense path). Tune against the measured
    # moe_fill / moe_drop step diagnostics: fill << 1 wastes expert
    # GEMM width on padding, drop >> 0 silently zeroes token updates.
    moe_capacity_factor: float = 1.25
    # Width of one expert's SwiGLU (0 = d_ff): fine-grained mixtures
    # publish many narrow experts beside a dense width they never use.
    moe_d_ff: int = 0
    # RMSNorm over head_dim of q and of k (one scale each a layer),
    # before the rotary embedding.
    qk_norm: bool = False
    # Generation by diffusion over blocks (serving/continuous.py, "the
    # block step"): `gen_block` positions are denoised together, and a
    # query sees every key up to the END of its own block, blocks counted
    # from the request's first real token. 0 = one token a step, causal:
    # the programs compiled today. `gen_steps` denoising passes fix
    # gen_block / gen_steps positions each (0 = one position a pass);
    # `gen_mask_id` is the token a position holds until it is fixed.
    gen_block: int = 0
    gen_steps: int = 0
    gen_mask_id: int = 0
    # Pipeline parallelism: split the block stack into this many stages
    # over the `pipe` mesh axis (0/1 = no pipelining).
    pipeline_stages: int = 0
    pp_microbatches: int = 4
    # Every RMSNorm's epsilon.
    norm_eps: float = 1e-6
    # A stack whose layers differ: one LayerSpec a layer (`_build` takes
    # dicts of its fields too). () = the uniform stack `attention_window`
    # and `moe_every` spell, which is what `layers()` then returns.
    layer_pattern: tuple = ()
    # An output gate on attention: o * sigmoid(x W_gate), head by head,
    # before the output projection.
    attn_gate: bool = False
    # RMSNorm on each branch before it joins the residual stream
    # (`ln_attn_out`, `ln_mlp_out`) beside the two on the way in.
    sandwich_norm: bool = False
    # The embedding's rows times this (muP: sqrt(d_model)).
    embed_scale: float = 1.0
    # A mixture layer that holds a share of the experts (ops/moe.py):
    # the router is `n_experts_total` wide (0 = n_experts: every expert
    # is held) and the `n_experts` held are ids `expert_first` and on.
    n_experts_total: int = 0
    expert_first: int = 0
    # "softmax": softmax, the k largest, renormalised. "sigmoid": sigmoid
    # scores, the k largest of score + `expert_bias` (a buffer: selection
    # only), the chosen scores renormalised and times `moe_route_scale`.
    moe_score: str = "softmax"
    moe_route_scale: float = 1.0
    # Shared experts: one SwiGLU of this many experts' width on every
    # token, added to the routed sum.
    moe_shared_experts: int = 0
    # Pages kept by layer kind (runtime/kvcache.py): > 0 gives the layers
    # with a window a pool of their own of this many pages and the second
    # of two page tables, whose pages behind the window the allocator
    # takes back while the request runs. 0: one pool size, one table.
    kv_window_pages: int = 0
    # Latent attention (a layer with `LayerSpec.latent`; `LatentAttention`
    # below): q through a latent of `q_lora_rank` (0 = one projection), k
    # and v through one of `kv_lora_rank`, an RMSNorm on each latent; a
    # head's query and key are `qk_nope_head_dim` values that carry no
    # position beside `qk_rope_head_dim` rotated ones, the key's rotated
    # part one for all heads; a head's value is `v_head_dim` wide.
    # `head_dim` and `n_kv_heads` say nothing of such a layer.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN on a latent layer's rotary part (`yarn_inv_freq`): positions
    # stretched by `rope_factor` (1 = plain rotary) beyond the
    # `rope_original_max` the model was first trained at, dimension by
    # dimension between those that turn `rope_beta_fast` and
    # `rope_beta_slow` times in that many positions; cos and sin times
    # mscale(factor, rope_mscale) / mscale(factor, rope_mscale_all_dim),
    # the softmax scale times mscale(factor, rope_mscale_all_dim) ** 2.
    rope_factor: float = 1.0
    rope_original_max: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # Group-limited routing (ops/moe.py, sigmoid scores): the experts lie
    # in `moe_n_group` groups of consecutive ids and a token chooses inside
    # the `moe_topk_group` groups whose two best scores sum highest.
    # 1 / 1: no groups.
    moe_n_group: int = 1
    moe_topk_group: int = 1

    def layers(self) -> tuple:
        """One LayerSpec a layer: the pattern, or the uniform stack."""
        if self.layer_pattern:
            if len(self.layer_pattern) != self.n_layers:
                raise ValueError(
                    f"layer_pattern has {len(self.layer_pattern)} entries "
                    f"for n_layers={self.n_layers}")
            return self.layer_pattern
        return tuple(
            LayerSpec(window=self.attention_window,
                      moe=self.moe_every > 0 and (i + 1) % self.moe_every == 0)
            for i in range(self.n_layers))


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's frequencies of a rotary embedding over `dim` values, [dim/2]
    float32: theta ** (-2i / dim) where the pair turns more than
    `beta_fast` times in `original_max` positions, that over `factor`
    where it turns fewer than `beta_slow` times, and a linear ramp
    between the two dimensions (floor and ceiling) at which it turns
    exactly so often."""
    def turns_at(rotations):
        return (dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim - 1)
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low)
                   / ((high if high != low else high + 0.001) - low), 0, 1)
    return (plain / factor * ramp + plain * (1 - ramp)).astype(np.float32)


def rope(x: jax.Array, positions: jax.Array, theta: float,
         inv_freq=None, mscale: float = 1.0) -> jax.Array:
    """Rotary position embedding over the last dim. x: [B, L, H, D].
    `inv_freq` [D/2]: other frequencies than theta's own (YaRN), cos and
    sin times `mscale`."""
    d = x.shape[-1]
    half = d // 2
    if inv_freq is None:
        freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    else:
        freqs = jnp.asarray(inv_freq, jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, L, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-6
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        x32 = x.astype(jnp.float32)
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (y * scale).astype(self.dtype)


def _kv_scale_rows(s):
    """[B, S, Hkv, 1] per-(position, head) int8-cache scales -> a layout
    broadcastable against [B, Hkv, G, Lq, S] attention logits/probs (the
    factored-scale decode path applies them there instead of
    dequantizing the cache elementwise)."""
    return s[..., 0].transpose(0, 2, 1)[:, :, None, None, :]


def _split_policy(policy: str) -> tuple[str, int | None]:
    """'slim@12' -> ('slim', 12): apply the named policy to the FIRST
    12 blocks and save everything on the rest — a fractional dial on
    the memory/recompute ladder between whole-model policy rungs. The
    r5 hardware ledger motivated it twice: gpt-760m bs8 slim missed
    fitting by 50MB (slim@15 would fit), and slim measurably BEAT
    no-remat at llama-1b bs8 (byte-bound regime), so the optimum can
    sit strictly between two whole-model policies. Plain names return
    (name, None) = every block."""
    if "@" in policy:
        name, k = policy.split("@", 1)
        if not name or not k.isdigit():
            raise ValueError(
                f"malformed remat_policy {policy!r}: expected "
                "'<dots|full|mlp|slim>@<layer count>' (e.g. slim@12)")
        return name, int(k)
    return policy, None


def _remat_policy(cfg: "TransformerConfig"):
    name, _ = _split_policy(cfg.remat_policy)
    if name == "dots":
        # dot outputs PLUS the flash kernel's named residuals (out, lse —
        # tagged inside its custom_vjp fwd rule, ops/flash_attention.py):
        # pallas_call is not a dot, so plain dots_saveable would replay
        # the whole flash forward in the backward (~6.5% of block MACs at
        # seq 2048) for want of an lse it threw away.
        return jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names("attn_flash"))
    if name == "full":
        return jax.checkpoint_policies.nothing_saveable
    if name == "mlp":
        # Save every block intermediate EXCEPT d_ff-wide ones (gate/up/
        # silu/h). Implemented as a WIDTH predicate on the equation's
        # input avals, not checkpoint_name tags: flax wraps activations
        # like silu in jit, and a name applied after the pjit equation
        # leaves the pjit's own output saveable — round 3 shipped the
        # name-tag version and saved_residuals showed it retaining a
        # full d_ff-wide tensor per layer, which is why "mlp" OOMed at
        # the same batch sizes as no-remat (tools/remat_plan.py).
        # Replay cost: the gate/up matmuls + elementwise, ~2/9 of block
        # MACs — plus the down-projection matmul, whose INPUT is d_ff-
        # wide even though its output is d-wide: an input-aval predicate
        # cannot save it, so its ~1/9 of block MACs replays too (total
        # ~3/9). A width predicate on output avals alone would instead
        # retain the d_ff-wide gate/up outputs and lose the memory win.
        wide = cfg.d_ff

        def mlp_policy(prim, *avals, **params):
            del prim, params
            return not any(
                getattr(a, "shape", None) and a.shape[-1] >= wide
                for a in avals)

        return mlp_policy
    if name == "slim":
        # Whitelist, not blacklist: save ONLY the named d-wide bf16
        # anchors (norm outputs, post-rope q/k/v, pre-o attention
        # context, and the flash kernel's out/lse residuals). "mlp"
        # hardware runs OOMed at bs>=16 because save-everything-except
        # also keeps every unnamed residual the backward touches —
        # including the f32 RMSNorm duplicates, which alone match the
        # entire dropped mlp_wide set in bytes. Replay recomputes
        # gate/up + elementwise (~2/9 of block MACs): most of full
        # remat's memory floor at roughly half its recompute tax, with
        # zero flash-forward replay.
        return jax.checkpoint_policies.save_only_these_names(
            "block_norm", "attn_qkv", "attn_ctx", "attn_flash")
    raise ValueError(
        f"unknown remat_policy {cfg.remat_policy!r} (full|dots|mlp|slim)")


class Attention(nn.Module):
    cfg: TransformerConfig
    layer: Optional[LayerSpec] = None    # None: the uniform stack's

    @property
    def window(self) -> int:
        return (self.cfg.attention_window if self.layer is None
                else self.layer.window)

    def _decode_paged(self, q, k, v, decode_index, pad_len, page_table,
                      block_step=False, fresh=False, hit_below=0):
        """Paged decode: the cache is a pool of [kv_pages, kv_page_size]
        position pages shared across slots; `page_table` [B, MP] maps
        each slot's logical page j (positions j*PS..(j+1)*PS-1) to a
        physical pool page. Where pages are kept by layer kind
        (cfg.kv_window_pages), a layer with a window has a pool of that
        many pages and is handed the window kind's table, in whose rows
        the pages behind the slot's window are TRASH_PAGE again: no
        query of the slot sees a position there any more, the kernel
        never walks them and the gather masks them. Writes scatter the chunk's K/V to
        (table[pos//PS], pos%PS) BEFORE attending (the full-cache
        write-then-attend discipline, so speculative verify chunks
        self-heal identically). Reads take one of two paths, chosen by
        what the call can observe (ops/paged_attention.py:use_kernel): a
        decode tick on a TPU streams the pages that hold what each
        slot's query sees straight out of the pool; every other call
        gathers the slot's pages back into a logical [B, MP*PS] view and
        runs the same masked attention as the dense path — token-for-
        token equal by construction, and the kernel's reference.

        A block model (cfg.gen_block = B) masks by blocks: a query sees
        the keys from its slot's first real position (`pad_len`) to the
        end of its own block, blocks of B counted from there. Prefill
        chunks take that mask on the gather path. `block_step` is the
        caller's word that the chunk IS one block (B queries from the
        block's first position): its rows then all see one range, which
        is what the kernel takes.

        `fresh` is the caller's word that the chunk is a prompt's rung
        which the flash kernel tiles: it takes neither path, but attends
        over its own keys, behind the slot's pages of positions
        [0, `hit_below`) where a prefix hit lies there (`pad_len` <
        `hit_below`: static, the rung's first position where the
        decoder's prefix cache is on, else 0), and only writes pages.

        Why it's safe that the gather sees unallocated (0 = trash-page)
        table entries: the allocator guarantees every position <= the
        slot's current decode index is backed by an owned or shared
        page, so trash content is only ever visible at masked
        (pos > qpos) positions (the kernel never fetches those pages).
        The same holds before the slot's first real position: the
        logical pages that hold left padding only are trash entries
        too (runtime/kvcache.py), masked by `pos >= pad_len`, and what
        a prefill chunk writes at such positions lands in the trash
        page.
        Idle lockstep slots have their whole row zeroed at free time,
        steering their stale writes into the trash page instead of a
        page another slot now owns."""
        cfg = self.cfg
        window = self.window
        b, lq = q.shape[0], q.shape[1]
        hkv, hd = cfg.n_kv_heads, cfg.head_dim
        NP, PS = cfg.kv_pages, cfg.kv_page_size
        if window and cfg.kv_window_pages:
            NP = cfg.kv_window_pages
        MP = page_table.shape[1]
        ck = self.variable("cache", "key_pages",
                           lambda: jnp.zeros((NP, PS, hkv, hd), cfg.dtype))
        cv = self.variable("cache", "value_pages",
                           lambda: jnp.zeros((NP, PS, hkv, hd), cfg.dtype))
        idx = jnp.asarray(decode_index, jnp.int32)
        if idx.ndim == 0:
            idx = jnp.full((b,), idx, jnp.int32)
        pos_q = idx[:, None] + jnp.arange(lq, dtype=jnp.int32)[None, :]
        k_w = k.astype(cfg.dtype)
        v_w = v.astype(cfg.dtype)
        if fresh:
            # The chunk is a prompt's rung, attended through the flash
            # kernel (the caller's word: one token a step, rungs the
            # kernel tiles): no score exists beyond the kernel's tile.
            # Only later ticks read the pages this writes, and those of
            # a window layer that keeps its own see its last `window`
            # positions: the rest is not written.
            from kubeflow_tpu.ops.attention import attention

            keep = min(lq, window + PS) if window and cfg.kv_window_pages \
                else lq
            flat = pos_q[:, lq - keep:].reshape(-1)
            rows = jnp.repeat(jnp.arange(b, dtype=jnp.int32), keep)
            pages, offs = page_table[rows, flat // PS], flat % PS
            for pool, new in ((ck, k_w), (cv, v_w)):
                pool.value = pool.value.at[pages, offs].set(
                    new[:, lq - keep:].reshape(b * keep, hkv, hd))
            attend = functools.partial(
                attention, causal=True, impl=cfg.attention_impl,
                block_q=cfg.flash_block_q, block_k=cfg.flash_block_k,
                window=window)
            real = None if pad_len is None else (
                pos_q >= pad_len[:, None]).astype(jnp.int32)

            def own():
                # nothing real lies before the rung: its own q, k, v, the
                # left padding a segment of its own
                return attend(q, k_w, v_w, segment_ids=real)

            if not hit_below:
                return own()
            pools = ((ck.value, k_w), (cv.value, v_w))

            def behind():
                # a prefix hit lies before the rung: the keys are the
                # slot's pages of positions [0, hit_below) and then the
                # rung's own, the queries end-aligned on them
                prior = page_table[:, :-(-hit_below // PS)]
                k_all, v_all = (
                    jnp.concatenate([pool[prior].reshape(
                        b, -1, hkv, hd)[:, :hit_below], new], axis=1)
                    for pool, new in pools)
                kv_real = (jnp.arange(hit_below + lq)[None, :]
                           >= pad_len[:, None]).astype(jnp.int32)
                return attend(q, k_all, v_all, segment_ids=real,
                              kv_segment_ids=kv_real)

            return jax.lax.cond(jnp.all(pad_len >= hit_below), own, behind)
        # ---- write the chunk, THEN attend ----
        flat = pos_q.reshape(-1)                       # [b*lq] positions
        rows = jnp.repeat(jnp.arange(b, dtype=jnp.int32), lq)
        pages = page_table[rows, flat // PS]
        offs = flat % PS
        ck.value = ck.value.at[pages, offs].set(k_w.reshape(b * lq, hkv, hd))
        cv.value = cv.value.at[pages, offs].set(v_w.reshape(b * lq, hkv, hd))
        from kubeflow_tpu.ops.paged_attention import (
            paged_decode_attention, use_kernel)

        if use_kernel(lq, ck.value.shape, ck.value.dtype,
                      one_range=block_step):
            # on a TPU, one query a slot or the queries of one block:
            # what they see is one range of positions (causality, or the
            # block's end, ends it; the window and the left padding begin
            # it: the mask below, as two integers a slot), and the kernel
            # streams the pages that hold it out of the pool
            last = pos_q[:, -1]
            start = jnp.zeros_like(last)
            if window:
                start = jnp.maximum(start, last - window + 1)
            if pad_len is not None:
                start = jnp.maximum(start, pad_len)
            return paged_decode_attention(
                q, ck.value, cv.value, page_table, start, last)
        # the reference, and the path of prefill and verify chunks and of
        # every backend but the TPU: gather each slot's whole table row
        # into a logical view, then mask
        k_all = ck.value[page_table].reshape(b, MP * PS, hkv, hd)
        v_all = cv.value[page_table].reshape(b, MP * PS, hkv, hd)
        g = cfg.n_heads // hkv
        qg = q.reshape(b, lq, hkv, g, hd)
        logits = jnp.einsum(
            "bqhgd,bshd->bhgqs", qg, k_all,
            preferred_element_type=jnp.float32) * (hd ** -0.5)
        pos = jnp.arange(MP * PS)[None, None, None, None, :]
        qpos = pos_q[:, None, None, :, None]
        if cfg.gen_block:
            # the last position of the query's block
            first = (jnp.zeros((b,), jnp.int32) if pad_len is None
                     else pad_len)[:, None, None, None, None]
            B = cfg.gen_block
            mask = pos <= first + ((qpos - first) // B + 1) * B - 1
        else:
            mask = pos <= qpos
        if window:
            mask = mask & (pos > qpos - window)
        if pad_len is not None:
            mask = mask & (pos >= pad_len[:, None, None, None, None])
        logits = jnp.where(mask, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum(
            "bhgqs,bshd->bqhgd", probs.astype(cfg.dtype), v_all
        ).reshape(b, lq, cfg.n_heads, hd)

    def _decode_rolling(self, q, k, v, decode_index, pad_len):
        """Bounded-window decode: the cache keeps only the last W
        positions (slot = position % W), so memory and per-step cache
        bandwidth are O(W) instead of O(max_seq).

        Clobber-safe ordering: attention runs against the OLD cache (all
        positions < idx) plus the current chunk's keys directly, and the
        chunk is written only afterwards — a chunk write may overwrite
        slot p-W while an earlier chunk row still needs it, so
        write-then-attend (the full-cache path's order) would be wrong
        here. Exact under the same window: pinned against the full-cache
        path by tests/test_generate.py."""
        cfg = self.cfg
        b, lq = q.shape[0], q.shape[1]
        hkv, hd = cfg.n_kv_heads, cfg.head_dim
        W = min(self.window, cfg.max_seq_len)
        quant = cfg.kv_cache_dtype == "int8"
        cache_dt = jnp.int8 if quant else cfg.dtype
        ck = self.variable("cache", "cached_key",
                           lambda: jnp.zeros((b, W, hkv, hd), cache_dt))
        cv = self.variable("cache", "cached_value",
                           lambda: jnp.zeros((b, W, hkv, hd), cache_dt))
        if quant:
            cks = self.variable("cache", "cached_key_scale",
                                lambda: jnp.zeros((b, W, hkv, 1), jnp.float32))
            cvs = self.variable("cache", "cached_value_scale",
                                lambda: jnp.zeros((b, W, hkv, 1), jnp.float32))
            # int8 feeds the matmuls directly; scales factor out of the
            # head_dim contraction (applied to scores / folded into
            # probs below) — see the full-cache path for the r5 ledger
            # evidence that elementwise dequant here costs 3.6x
            k_old = ck.value.astype(cfg.dtype)
            v_old = cv.value.astype(cfg.dtype)
            ksc_b = _kv_scale_rows(cks.value)
            vsc_b = _kv_scale_rows(cvs.value)
        else:
            k_old, v_old = ck.value, cv.value
            ksc_b = vsc_b = None

        idx = jnp.asarray(decode_index, jnp.int32)
        # Quantize the chunk BEFORE attending and attend its dequantized
        # values: the full-cache path writes first and attends from the
        # (dequantized) cache, so token-for-token parity under int8
        # requires the in-chunk term to see the same quantize->dequantize
        # round trip.
        if quant:
            from kubeflow_tpu.ops.quantize import symmetric_int8

            k_w, ks_w = symmetric_int8(k, -1)
            v_w, vs_w = symmetric_int8(v, -1)
            # the in-chunk term sees the same int8 + factored-scale math
            # as a cache read, so a token attends identically now and
            # after it lands in the cache
            k_c = k_w.astype(cfg.dtype)
            v_c = v_w.astype(cfg.dtype)
            ksw_b = _kv_scale_rows(ks_w)
            vsw_b = _kv_scale_rows(vs_w)
        else:
            k_w, v_w = k.astype(cfg.dtype), v.astype(cfg.dtype)
            k_c, v_c = k_w, v_w
            ksw_b = vsw_b = None
        g = cfg.n_heads // hkv
        qg = q.reshape(b, lq, hkv, g, hd)
        scale = hd ** -0.5
        # old-cache term [b,h,g,lq,W] + in-chunk term [b,h,g,lq,lq]
        lc = jnp.einsum("bqhgd,bshd->bhgqs", qg, k_old,
                        preferred_element_type=jnp.float32) * scale
        ls = jnp.einsum("bqhgd,bchd->bhgqc", qg, k_c,
                        preferred_element_type=jnp.float32) * scale
        if quant:
            lc = lc * ksc_b
            ls = ls * ksw_b

        slots = jnp.arange(W, dtype=jnp.int32)
        cols = jnp.arange(lq, dtype=jnp.int32)
        if idx.ndim == 0:
            # scalar start: query row r sits at absolute position idx+r
            qpos = idx + cols                                   # [lq]
            cur_old = idx - 1
            # absolute position currently held by each slot (the largest
            # p <= cur_old with p % W == slot); negative = never written
            pos_abs = cur_old - ((cur_old - slots) % W)         # [W]
            mc = (pos_abs[None, :] >= 0) \
                & (pos_abs[None, :] > qpos[:, None] - W)        # [lq, W]
            mc = jnp.broadcast_to(mc[None], (b, lq, W))
            ms = (cols[None, :] <= cols[:, None]) \
                & (cols[None, :] > cols[:, None] - W)           # [lq, lq]
            ms = jnp.broadcast_to(ms[None], (b, lq, lq))
            if pad_len is not None:
                mc = mc & (pos_abs[None, None, :] >= pad_len[:, None, None])
                ms = ms & ((idx + cols)[None, None, :]
                           >= pad_len[:, None, None])
        else:
            # per-row positions (continuous batching): lq == 1
            if lq != 1:
                raise ValueError(
                    "rolling_kv_cache vector decode is single-token "
                    f"(got chunk width {lq}); speculative/paged chunks "
                    "need the full or paged cache")
            cur_old = idx - 1                                   # [b]
            pos_abs = cur_old[:, None] - (
                (cur_old[:, None] - slots[None, :]) % W)        # [b, W]
            mc = (pos_abs >= 0) & (pos_abs > idx[:, None] - W)
            mc = mc[:, None, :]                                 # [b, 1, W]
            ms = jnp.ones((b, 1, 1), bool)
            if pad_len is not None:
                mc = mc & (pos_abs[:, None, :] >= pad_len[:, None, None])
                ms = ms & (idx[:, None, None] >= pad_len[:, None, None])

        neg = jnp.float32(-1e30)
        lc = jnp.where(mc[:, None, None, :, :], lc, neg)
        ls = jnp.where(ms[:, None, None, :, :], ls, neg)
        probs = jax.nn.softmax(jnp.concatenate([lc, ls], axis=-1), axis=-1)
        pc, ps = probs[..., :W], probs[..., W:]
        if quant:
            pc = pc * vsc_b
            ps = ps * vsw_b
        out = (jnp.einsum("bhgqs,bshd->bqhgd", pc.astype(cfg.dtype), v_old)
               + jnp.einsum("bhgqc,bchd->bqhgd", ps.astype(cfg.dtype), v_c))
        out = out.reshape(b, lq, cfg.n_heads, hd)

        # ---- write the (already-quantized) chunk, AFTER attending ----
        if idx.ndim == 0:
            # only the last W chunk columns survive a wrap; among those
            # the slot map (idx+c) % W is injective
            wslot = (idx + cols) % W                            # [lq]
            alive = cols >= lq - W
            hot = (slots[:, None] == wslot[None, :]) & alive[None, :]
            hit = hot.any(axis=1)                               # [W]

            def wr(old, new):
                upd = jnp.einsum("sc,bc...->bs...", hot.astype(new.dtype),
                                 new).astype(old.dtype)
                keep = jnp.reshape(~hit, (1, W) + (1,) * (old.ndim - 2))
                return jnp.where(keep, old, upd)

            ck.value = wr(ck.value, k_w)
            cv.value = wr(cv.value, v_w)
            if quant:
                cks.value = wr(cks.value, ks_w)
                cvs.value = wr(cvs.value, vs_w)
        else:
            hot = (slots[None, :] == (idx % W)[:, None])[:, :, None, None]
            ck.value = jnp.where(hot, k_w, ck.value)
            cv.value = jnp.where(hot, v_w, cv.value)
            if quant:
                cks.value = jnp.where(hot, ks_w, cks.value)
                cvs.value = jnp.where(hot, vs_w, cvs.value)
        return out

    @nn.compact
    def __call__(self, x, positions, segment_ids=None, decode_index=None,
                 pad_len=None, page_table=None, block_step=False,
                 fresh=False, hit_below=0):
        cfg = self.cfg
        window = self.window
        init = nn.initializers.normal(0.02)
        dense = lambda feats, names, name: nn.DenseGeneral(  # noqa: E731
            feats,
            axis=-1,
            use_bias=False,
            dtype=cfg.dtype,
            kernel_init=_part(init, names),
            name=name,
        )
        # Column-parallel QKV: heads sharded over `model`.
        q = dense((cfg.n_heads, cfg.head_dim), (AXIS_FSDP, AXIS_MODEL, None), "q")(x)
        k = dense((cfg.n_kv_heads, cfg.head_dim), (AXIS_FSDP, AXIS_MODEL, None), "k")(x)
        v = dense((cfg.n_kv_heads, cfg.head_dim), (AXIS_FSDP, AXIS_MODEL, None), "v")(x)
        if cfg.attn_gate:
            z = dense((cfg.n_heads, cfg.head_dim),
                      (AXIS_FSDP, AXIS_MODEL, None), "gate")(x)
        if cfg.qk_norm:
            q = RMSNorm(cfg.norm_eps, cfg.dtype, name="q_norm")(q)
            k = RMSNorm(cfg.norm_eps, cfg.dtype, name="k_norm")(k)
        if self.layer is None or self.layer.rope:
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
        # remat anchors for the "slim" whitelist policy: saving post-rope
        # q/k/v lets the flash backward run without recomputing the
        # projections (its own fwd replay still happens — lse is a
        # custom_vjp residual the policy can't reach)
        q = checkpoint_name(q, "attn_qkv")
        k = checkpoint_name(k, "attn_qkv")
        v = checkpoint_name(v, "attn_qkv")

        if decode_index is not None and page_table is not None:
            if not (cfg.kv_pages and cfg.kv_page_size):
                raise ValueError(
                    "page_table passed but the model was built without "
                    "kv_pages/kv_page_size")
            if cfg.rolling_kv_cache:
                raise ValueError(
                    "paged decode is exclusive with rolling_kv_cache "
                    "(the page pool already bounds cache memory)")
            if cfg.kv_cache_dtype != "auto":
                raise ValueError(
                    "paged decode supports kv_cache_dtype='auto' only "
                    "(int8 page pools are not composed yet)")
            # falls through to the SHARED output projection below, like
            # the rolling path — 'o' must stay single-sited
            out = self._decode_paged(q, k, v, decode_index, pad_len,
                                     page_table, block_step, fresh,
                                     hit_below)
        elif decode_index is not None and cfg.rolling_kv_cache:
            if not window:
                raise ValueError(
                    "rolling_kv_cache requires attention_window > 0")
            if cfg.kv_cache_dtype not in ("auto", "int8"):
                raise ValueError(
                    f"unknown kv_cache_dtype {cfg.kv_cache_dtype!r} "
                    "(auto|int8)")
            # falls through to the SHARED output projection below — the
            # 'o' DenseGeneral must stay single-sited or the two decode
            # paths silently diverge in init/sharding
            out = self._decode_rolling(q, k, v, decode_index, pad_len)
        elif decode_index is not None:
            # KV-cache decode: x is the single new token [B, 1, ...]; write
            # its K/V at decode_index and attend q against the full cache
            # with a <=index mask. Cache layout [B, max_seq, Hkv, D].
            # kv_cache_dtype="int8" stores quantized values + per-token-
            # head scales: at long contexts the cache (not the weights)
            # dominates decode HBM traffic, and int8 halves it.
            b = x.shape[0]
            if cfg.kv_cache_dtype not in ("auto", "int8"):
                # a typo'd value silently running full-precision would
                # report an int8 configuration that never happened
                raise ValueError(
                    f"unknown kv_cache_dtype {cfg.kv_cache_dtype!r} "
                    "(auto|int8)")
            quant = cfg.kv_cache_dtype == "int8"
            cache_dt = jnp.int8 if quant else cfg.dtype
            ck = self.variable(
                "cache", "cached_key",
                lambda: jnp.zeros((b, cfg.max_seq_len, cfg.n_kv_heads,
                                   cfg.head_dim), cache_dt))
            cv = self.variable(
                "cache", "cached_value",
                lambda: jnp.zeros((b, cfg.max_seq_len, cfg.n_kv_heads,
                                   cfg.head_dim), cache_dt))
            if quant:
                cks = self.variable(
                    "cache", "cached_key_scale",
                    lambda: jnp.zeros((b, cfg.max_seq_len, cfg.n_kv_heads,
                                       1), jnp.float32))
                cvs = self.variable(
                    "cache", "cached_value_scale",
                    lambda: jnp.zeros((b, cfg.max_seq_len, cfg.n_kv_heads,
                                       1), jnp.float32))

                from kubeflow_tpu.ops.quantize import symmetric_int8

                k_w, ks_w = symmetric_int8(k, -1)  # per-token-head scale
                v_w, vs_w = symmetric_int8(v, -1)
            else:
                k_w, v_w = k.astype(cfg.dtype), v.astype(cfg.dtype)
            idx = jnp.asarray(decode_index, jnp.int32)
            if idx.ndim == 0:
                dus = jax.lax.dynamic_update_slice
                ck.value = dus(ck.value, k_w, (0, idx, 0, 0))
                cv.value = dus(cv.value, v_w, (0, idx, 0, 0))
                if quant:
                    cks.value = dus(cks.value, ks_w, (0, idx, 0, 0))
                    cvs.value = dus(cvs.value, vs_w, (0, idx, 0, 0))
            elif x.shape[1] == 1:
                # per-row positions (continuous batching: every slot is at
                # its own decode index): one-hot scatter along seq — a
                # [B, S] elementwise select per layer, the static-shape
                # way to write B different positions in one program
                hot = (jnp.arange(cfg.max_seq_len)[None, :]
                       == idx[:, None])[:, :, None, None]
                ck.value = jnp.where(hot, k_w, ck.value)
                cv.value = jnp.where(hot, v_w, cv.value)
                if quant:
                    cks.value = jnp.where(hot, ks_w, cks.value)
                    cvs.value = jnp.where(hot, vs_w, cvs.value)
            else:
                # per-row positions, MULTI-token chunk (lockstep
                # speculative verify: every slot consumes its own
                # [cur, d_1..d_k] chunk at its own position): row c of
                # slot b lands at idx[b] + c. One-hot over (row, seq)
                # folded by an einsum — the [B, lq, S] static-shape
                # scatter; per-slot chunk positions are distinct so the
                # fold never sums two writes
                lw = x.shape[1]
                posw = idx[:, None] + jnp.arange(lw, dtype=jnp.int32)[None, :]
                hotw = (jnp.arange(cfg.max_seq_len)[None, None, :]
                        == posw[:, :, None])
                hitw = hotw.any(axis=1)                          # [B, S]

                def _wr(old, new):
                    upd = jnp.einsum("bls,bl...->bs...",
                                     hotw.astype(new.dtype),
                                     new).astype(old.dtype)
                    keep = jnp.reshape(
                        ~hitw, hitw.shape + (1,) * (old.ndim - 2))
                    return jnp.where(keep, old, upd)

                ck.value = _wr(ck.value, k_w)
                cv.value = _wr(cv.value, v_w)
                if quant:
                    cks.value = _wr(cks.value, ks_w)
                    cvs.value = _wr(cvs.value, vs_w)
            if quant:
                # The int8 cache feeds the matmuls DIRECTLY (int8->bf16
                # convert is exact for [-127,127] and fuses into the
                # operand load). Round 3 dequantized elementwise here,
                # materializing + streaming a full-width copy each tick —
                # measured r5: int8-KV decode 3.6x SLOWER than bf16, the
                # opposite of the feature's point. The per-(position,
                # head) scales factor out of the head_dim contraction:
                #   scores = (q · k_int8) * ks[s]     (scale on scores)
                #   out    = (probs * vs[s]) · v_int8 (scale into probs)
                # so cache traffic is 1 byte/elt and the scale math is
                # head_dim-times smaller than a dequantized cache.
                k_all = ck.value.astype(cfg.dtype)
                v_all = cv.value.astype(cfg.dtype)
                ks_b = _kv_scale_rows(cks.value)
                vs_b = _kv_scale_rows(cvs.value)
            else:
                k_all, v_all = ck.value, cv.value
                ks_b = vs_b = None
            # Grouped-query attention WITHOUT jnp.repeat: expanding K/V
            # to n_heads would materialize (and stream) a G-times-larger
            # bf16 tensor every decode step — the exact traffic the int8
            # cache exists to avoid. Group the query heads instead.
            g = cfg.n_heads // cfg.n_kv_heads
            lq = q.shape[1]
            qg = q.reshape(b, lq, cfg.n_kv_heads, g, cfg.head_dim)
            logits = jnp.einsum(
                "bqhgd,bshd->bhgqs", qg, k_all,
                preferred_element_type=jnp.float32) * (cfg.head_dim ** -0.5)
            if ks_b is not None:
                logits = logits * ks_b
            pos = jnp.arange(cfg.max_seq_len)[None, None, None, None, :]
            if idx.ndim == 0:
                # chunked decode: query row r sits at absolute position
                # idx + r and may attend keys <= that (causal within the
                # chunk; degenerates to pos <= idx at lq == 1)
                qpos = (idx + jnp.arange(lq, dtype=jnp.int32)
                        )[None, None, None, :, None]
            else:
                # vector idx: row c of slot b queries from idx[b] + c
                # (degenerates to the old idx[:,None,...] at lq == 1)
                qpos = (idx[:, None] + jnp.arange(lq, dtype=jnp.int32)
                        [None, :])[:, None, None, :, None]
            mask = pos <= qpos
            if window:
                # same sliding window as training (train/serve parity);
                # this path keeps max_seq cache slots — set
                # rolling_kv_cache for the O(window) bounded cache
                mask = mask & (pos > qpos - window)
            if pad_len is not None:
                # left-padded ragged prompts: positions before each row's
                # real start are pad garbage and must not be attended to
                # (RoPE is relative, so masked left-padding is exact)
                mask = mask & (pos >= pad_len[:, None, None, None, None])
            logits = jnp.where(mask, logits, -1e30)
            probs = jax.nn.softmax(logits, axis=-1)
            if vs_b is not None:
                probs = probs * vs_b
            out = jnp.einsum(
                "bhgqs,bshd->bqhgd", probs.astype(cfg.dtype), v_all
            ).reshape(b, lq, cfg.n_heads, cfg.head_dim)
        elif cfg.attention_impl == "ring":
            from kubeflow_tpu.ops.ring_attention import ring_attention

            out = ring_attention(q, k, v, axis_name=AXIS_SEQ,
                                 segment_ids=segment_ids, window=window)
        elif cfg.attention_impl == "ulysses":
            from kubeflow_tpu.ops.ulysses import ulysses_attention

            out = ulysses_attention(q, k, v, axis_name=AXIS_SEQ,
                                    segment_ids=segment_ids,
                                    block_q=cfg.flash_block_q,
                                    block_k=cfg.flash_block_k,
                                    window=window)
        else:
            from kubeflow_tpu.ops.attention import attention

            out = attention(
                q, k, v, causal=True, impl=cfg.attention_impl,
                segment_ids=segment_ids,
                block_q=cfg.flash_block_q, block_k=cfg.flash_block_k,
                window=window,
            )
        if cfg.attn_gate:
            out = (out.astype(jnp.float32)
                   * jax.nn.sigmoid(z.astype(jnp.float32))).astype(cfg.dtype)
        out = checkpoint_name(out, "attn_ctx")
        # Row-parallel output projection: contraction dim sharded over
        # `model` — GSPMD inserts the all-reduce here.
        out = nn.DenseGeneral(
            x.shape[-1],
            axis=(-2, -1),
            use_bias=False,
            dtype=cfg.dtype,
            kernel_init=_part(init, (AXIS_MODEL, None, AXIS_FSDP)),
            name="o",
        )(out)
        return shard(out, HIDDEN_SPEC)


class LatentAttention(nn.Module):
    """Multi-head latent attention (a layer with `LayerSpec.latent`): the
    keys and values of every head are linear in one latent vector a
    position, `ckv` (cfg.kv_lora_rank, RMS-normed), and the key's rotated
    part `k_pe` (cfg.qk_rope_head_dim) is one for all heads, so the cache
    holds `[ckv | k_pe]` a position and nothing else. One arithmetic in
    two forms, of the same weights:

    - up-projected (a sequence without a cache, and a prompt's rung whose
      attention is the flash kernel's): `[k_nope | v]` a head = `ckv
      W_kv_b`, `k = [k_nope | k_pe]`, attention of cfg.n_heads heads with
      keys of qk_nope + qk_rope values and values of v_head_dim.
    - absorbed (every read of the cache: a tick, a gathered chunk): the
      query goes into the latent's space, `q_lat = q_nope W_kv_b[k part]`,
      scores are `[q_lat | q_pe] . [ckv | k_pe]`, the probabilities weigh
      `ckv` itself and `W_kv_b[v part]` takes the result out again. No
      key or value of a head is ever made of a cached position.

    The softmax scale is (qk_nope + qk_rope) ** -0.5 times YaRN's
    mscale(factor, rope_mscale_all_dim) ** 2. Served through the paged
    cache only (`_decode_paged`): one pool a layer of rows `[ckv | k_pe |
    zeros]`, the row as wide as whole 128-lane tiles hold it
    (`latent_row_width`)."""

    cfg: TransformerConfig
    layer: LayerSpec

    def _up_projected(self, q_nope, q_pe, ckv, k_pe, w, scale, segment_ids):
        """The up-projected form over a sequence's own positions: every
        head's `[k_nope | v]` from the latent through `w` [r, heads,
        nope + v], the rotated key part `k_pe` [b, l, rope] joined to
        each head's, causal attention at keys of nope + rope and values
        of v_head_dim."""
        from kubeflow_tpu.ops.attention import attention

        cfg, dn = self.cfg, self.cfg.qk_nope_head_dim
        kv = jnp.einsum("blr,rhk->blhk", ckv, w)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(
                k_pe[:, :, None, :], kv.shape[:3] + k_pe.shape[-1:])],
            axis=-1)
        return attention(
            jnp.concatenate([q_nope, q_pe], axis=-1), k, kv[..., dn:],
            causal=True, impl=cfg.attention_impl, segment_ids=segment_ids,
            block_q=cfg.flash_block_q, block_k=cfg.flash_block_k,
            scale=scale)

    def _decode_paged(self, q_nope, q_pe, ckv, k_pe, w_kv_b, scale,
                      decode_index, pad_len, page_table, fresh=False):
        """The pool `latent_pages` [kv_pages, kv_page_size, W] behind the
        page table that `Attention._decode_paged` describes: write the
        chunk's rows, then attend. `fresh` (the caller's word: a prompt's
        rung that the flash kernel tiles, nothing real before it): the
        up-projected form over the rung's own positions; only the ticks
        read what it writes. Every other call is the absorbed form over
        the slot's pages: a tick on a TPU through the Pallas kernel that
        streams the pages that hold what each slot's query sees
        (ops/paged_latent_attention.py:use_kernel says, and logs, which),
        anything else gathered and masked, which is the kernel's
        reference."""
        from kubeflow_tpu.ops import paged_latent_attention as pla

        cfg = self.cfg
        b, lq, heads = q_nope.shape[:3]
        dn, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
        PS = cfg.kv_page_size
        W = latent_row_width(cfg)
        pool = self.variable(
            "cache", "latent_pages",
            lambda: jnp.zeros((cfg.kv_pages, PS, W), cfg.dtype))
        idx = jnp.asarray(decode_index, jnp.int32)
        if idx.ndim == 0:
            idx = jnp.full((b,), idx, jnp.int32)
        pos_q = idx[:, None] + jnp.arange(lq, dtype=jnp.int32)[None, :]
        row = jnp.concatenate([ckv, k_pe], axis=-1).astype(cfg.dtype)
        row = jnp.pad(row, ((0, 0), (0, 0), (0, W - row.shape[-1])))
        flat = pos_q.reshape(-1)
        slot = jnp.repeat(jnp.arange(b, dtype=jnp.int32), lq)
        pool.value = pool.value.at[page_table[slot, flat // PS],
                                   flat % PS].set(row.reshape(b * lq, W))
        w = w_kv_b.astype(cfg.dtype)
        if fresh:
            real = None if pad_len is None else (
                pos_q >= pad_len[:, None]).astype(jnp.int32)
            return self._up_projected(q_nope, q_pe, ckv, k_pe, w, scale, real)
        q_abs = jnp.concatenate(
            [jnp.einsum("blhn,rhn->blhr", q_nope, w[..., :dn]), q_pe],
            axis=-1)
        q_abs = jnp.pad(q_abs, ((0, 0),) * 3 + ((0, W - q_abs.shape[-1]),))
        if pla.use_kernel(lq, pool.value.shape, pool.value.dtype):
            last = pos_q[:, -1]
            start = jnp.zeros_like(last) if pad_len is None else pad_len
            o_lat = pla.paged_latent_attention(
                q_abs[:, 0], pool.value, page_table, start, last,
                scale=scale, rank=r)[:, None]
        else:
            rows = pool.value[page_table].reshape(b, -1, W)
            logits = jnp.einsum("bqhw,bsw->bhqs", q_abs, rows,
                                preferred_element_type=jnp.float32) * scale
            pos = jnp.arange(rows.shape[1])[None, None, None, :]
            mask = pos <= pos_q[:, None, :, None]
            if pad_len is not None:
                mask = mask & (pos >= pad_len[:, None, None, None])
            probs = jax.nn.softmax(jnp.where(mask, logits, -1e30), axis=-1)
            o_lat = jnp.einsum("bhqs,bsr->bqhr", probs.astype(cfg.dtype),
                               rows[..., :r])
        return jnp.einsum("blhr,rhv->blhv", o_lat, w[..., dn:])

    @nn.compact
    def __call__(self, x, positions, segment_ids=None, decode_index=None,
                 pad_len=None, page_table=None, block_step=False,
                 fresh=False, hit_below=0):
        cfg = self.cfg
        heads, r = cfg.n_heads, cfg.kv_lora_rank
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        init = nn.initializers.normal(0.02)
        norm = functools.partial(RMSNorm, cfg.norm_eps, cfg.dtype)
        dense = lambda feats, names, name: nn.DenseGeneral(  # noqa: E731
            feats, axis=-1, use_bias=False, dtype=cfg.dtype,
            kernel_init=_part(init, names), name=name)
        if cfg.q_lora_rank:
            cq = norm(name="q_a_norm")(
                dense(cfg.q_lora_rank, (AXIS_FSDP, None), "q_a")(x))
            q = dense((heads, dn + dr), (None, AXIS_MODEL, None), "q_b")(cq)
        else:
            q = dense((heads, dn + dr), (AXIS_FSDP, AXIS_MODEL, None), "q")(x)
        kv_a = dense(r + dr, (AXIS_FSDP, None), "kv_a")(x)
        ckv = norm(name="kv_a_norm")(kv_a[..., :r])
        q_nope, q_pe, k_pe = q[..., :dn], q[..., dn:], kv_a[..., None, r:]
        scale = (dn + dr) ** -0.5
        if self.layer.rope:
            inv_freq, mscale = None, 1.0
            if cfg.rope_factor > 1:
                inv_freq = yarn_inv_freq(
                    dr, cfg.rope_theta, cfg.rope_factor,
                    cfg.rope_original_max, cfg.rope_beta_fast,
                    cfg.rope_beta_slow)
                all_dim = yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
                mscale = yarn_mscale(cfg.rope_factor, cfg.rope_mscale) / all_dim
                scale *= all_dim * all_dim
            q_pe = rope(q_pe, positions, cfg.rope_theta, inv_freq, mscale)
            k_pe = rope(k_pe, positions, cfg.rope_theta, inv_freq, mscale)
        k_pe = k_pe[..., 0, :]
        # [k_nope | v] of every head from the latent: the rung's form
        # multiplies by it whole, the absorbed form by its two parts
        w_kv_b = self.param(
            "kv_b", _part(init, (None, AXIS_MODEL, None)),
            (r, heads, dn + dv), jnp.float32)
        if decode_index is not None:
            if page_table is None or not (cfg.kv_pages and cfg.kv_page_size):
                raise ValueError(
                    "latent attention is served through the paged KV cache "
                    "only (build the model with kv_pages and kv_page_size): "
                    "no dense slot cache of latents is there")
            if cfg.rolling_kv_cache or cfg.kv_cache_dtype != "auto":
                raise ValueError(
                    "latent attention keeps one pool of latents in the "
                    "model's dtype: no rolling_kv_cache, no int8 cache")
            if block_step or cfg.gen_block or hit_below:
                raise ValueError(
                    "latent attention serves one token a step, and a "
                    "prompt's rung over its own positions: no block step, "
                    "no prefix hit's pages behind a flash rung")
            out = self._decode_paged(q_nope, q_pe, ckv, k_pe, w_kv_b, scale,
                                     decode_index, pad_len, page_table, fresh)
        else:
            out = self._up_projected(q_nope, q_pe, ckv, k_pe,
                                     w_kv_b.astype(cfg.dtype), scale,
                                     segment_ids)
        out = checkpoint_name(out, "attn_ctx")
        out = nn.DenseGeneral(
            x.shape[-1], axis=(-2, -1), use_bias=False, dtype=cfg.dtype,
            kernel_init=_part(init, (AXIS_MODEL, None, AXIS_FSDP)),
            name="o")(out)
        return shard(out, HIDDEN_SPEC)


def latent_row_width(cfg: TransformerConfig) -> int:
    """Values a position takes in a latent layer's pool: the latent and
    the key's rotated part, in whole tiles of 128 lanes (512 + 64 -> 640:
    the kernel fetches a page in one piece and the MXU takes whole
    tiles; what that costs is `stats()["kv_latent_row_bytes"]` against
    the 2 x 576 the model needs)."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128


class SwiGLU(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        init = nn.initializers.normal(0.02)
        # Column-parallel up projections. EVERY d_ff-wide tensor carries
        # the "mlp_wide" checkpoint name so remat_policy="mlp" can drop
        # exactly these from the saved residuals. That includes
        # silu(gate): the product's backward consumes it, and round 3
        # shipped it unnamed — saved_residuals showed the "mlp" policy
        # retaining a full d_ff-wide tensor per layer anyway, which is
        # why it OOMed at the same batch sizes as no-remat on hardware
        # (tools/remat_plan.py).
        gate = checkpoint_name(nn.DenseGeneral(
            cfg.d_ff, use_bias=False, dtype=cfg.dtype,
            kernel_init=_part(init, (AXIS_FSDP, AXIS_MODEL)), name="gate",
        )(x), "mlp_wide")
        up = checkpoint_name(nn.DenseGeneral(
            cfg.d_ff, use_bias=False, dtype=cfg.dtype,
            kernel_init=_part(init, (AXIS_FSDP, AXIS_MODEL)), name="up",
        )(x), "mlp_wide")
        sg = checkpoint_name(nn.silu(gate), "mlp_wide")
        h = checkpoint_name(shard(sg * up, WIDE_SPEC), "mlp_wide")
        # Row-parallel down projection (psum on output)
        out = nn.DenseGeneral(
            x.shape[-1], use_bias=False, dtype=cfg.dtype,
            kernel_init=_part(init, (AXIS_MODEL, AXIS_FSDP)), name="down",
        )(h)
        return shard(out, HIDDEN_SPEC)


class LMHead(nn.Module):
    """Vocab projection: bf16 operands, f32 accumulation/output.

    An f32×f32 dot can't ride the MXU's native bf16 datapath — XLA
    decomposes it into multiple passes (~4× the cycles). The head is
    ~6·V·d of the step's FLOPs (7% on gpt-350m), so running it f32 costs
    ~20% of the whole step. bf16 inputs with
    preferred_element_type=float32 keep full-precision logits for the
    softmax at bf16 matmul speed. Param tree path stays
    lm_head/kernel (shape [d_model, vocab])."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        kernel = self.param(
            "kernel",
            _part(nn.initializers.normal(0.02), (AXIS_FSDP, AXIS_MODEL)),
            (cfg.d_model, cfg.vocab_size),
            jnp.float32,
        )
        return jnp.einsum(
            "...d,dv->...v", x.astype(cfg.dtype), kernel.astype(cfg.dtype),
            preferred_element_type=jnp.float32)


class Block(nn.Module):
    """One layer, built from its description (`cfg.layers()`); None: a
    dense layer with the model-wide window (a pipeline stage's)."""

    cfg: TransformerConfig
    layer: Optional[LayerSpec] = None

    @nn.compact
    def __call__(self, x, positions, segment_ids=None, decode_index=None,
                 pad_len=None, page_table=None, block_step=False,
                 live=None, fresh=False, hit_below=0):
        cfg = self.cfg
        norm = functools.partial(RMSNorm, cfg.norm_eps, cfg.dtype)
        # "block_norm" anchors both norm outputs: they are the weight-grad
        # inputs of the q/k/v and gate/up matmuls, so saving these d-wide
        # bf16 tensors (instead of the f32 RMSNorm internals a blacklist
        # policy keeps) is what lets the "slim" replay skip the norms.
        ln1 = checkpoint_name(norm(name="ln_attn")(x), "block_norm")
        attn = (LatentAttention if self.layer is not None
                and self.layer.latent else Attention)
        attn_out = attn(cfg, self.layer, name="attn")(
            ln1, positions, segment_ids, decode_index, pad_len, page_table,
            block_step, fresh, hit_below)
        if cfg.sandwich_norm:
            attn_out = norm(name="ln_attn_out")(attn_out)
        x = x + attn_out
        ln2 = checkpoint_name(norm(name="ln_mlp")(x), "block_norm")
        if self.layer is not None and self.layer.moe:
            from kubeflow_tpu.ops.moe import MoEBlock

            mlp_out = MoEBlock(
                cfg, capacity_factor=cfg.moe_capacity_factor,
                name="moe")(ln2, live)
        else:
            mlp_out = SwiGLU(cfg, name="mlp")(ln2)
        if cfg.sandwich_norm:
            mlp_out = norm(name="ln_mlp_out")(mlp_out)
        return x + mlp_out


class Stage(nn.Module):
    """One pipeline stage: n_layers/pipeline_stages consecutive blocks.

    Takes batch-free 1-D positions (SPMDPipeline's broadcast-input
    contract) and broadcasts them to the microbatch rows itself."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions_1d):
        cfg = self.cfg
        positions = jnp.broadcast_to(positions_1d[None, :], x.shape[:2])
        block = Block
        if cfg.remat:
            if _split_policy(cfg.remat_policy)[1] is not None:
                raise ValueError(
                    f"mixed remat policy {cfg.remat_policy!r} is not "
                    "supported under pipeline parallelism (stages would "
                    "carry unequal activation memory)")
            block = nn.remat(Block, policy=_remat_policy(cfg))
        for p in range(cfg.n_layers // cfg.pipeline_stages):
            x = block(cfg, name=f"block_{p}")(x, positions)
        return x


class TransformerLM(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, train: bool = True, segment_ids=None,
                 decode_index=None, pad_len=None, page_table=None,
                 return_hidden=False, block_step=False, fresh=False,
                 hit_below=0):
        """`page_table`: [B, MP], or where pages are kept by layer kind
        (cfg.kv_window_pages) the pair (held kind's, window kind's), of
        which each layer reads its own. `fresh`: the paged chunk is a
        prompt's rung, attended through the flash kernel
        (`Attention._decode_paged`), and the logits are the last
        position's alone, [B, 1, V]. Its keys are its own q, k, v where
        nothing real lies before it; `hit_below` (static: the rung's
        first position, where the decoder's prefix cache is on) adds the
        one case that is not so, a prefix hit's pages before the rung,
        told in the program by `pad_len` < `hit_below`."""
        cfg = self.cfg
        del train  # no dropout in the speed-run configuration
        specs = cfg.layers()
        norm_f = RMSNorm(cfg.norm_eps, cfg.dtype, name="ln_f")
        emb = self.param(
            "embedding",
            # vocab over (model, fsdp), d unsharded: the gradient of a
            # d-over-fsdp table needs a batch-shard -> feature-shard
            # reshard of dx that the pre-Shardy partitioner can only do
            # as replicate-then-slice ("Involuntary full
            # rematerialization"); vocab-sharding makes both the lookup
            # and the grad scatter the standard ZeRO gather/scatter over
            # the vocab dim instead
            _part(nn.initializers.normal(1.0), ((AXIS_MODEL, AXIS_FSDP), None)),
            (cfg.vocab_size, cfg.d_model),
            jnp.float32,
        )
        x = jnp.asarray(emb, cfg.dtype)[tokens]
        if cfg.embed_scale != 1.0:
            x = x * jnp.asarray(cfg.embed_scale, cfg.dtype)
        x = shard(x, HIDDEN_SPEC)
        if decode_index is not None:
            # KV-cache decode step: tokens [B, Lq] starting at absolute
            # position decode_index (runtime/generate.py drives Lq=1;
            # speculative verify passes a k-token chunk).
            if cfg.pipeline_stages > 1:
                raise ValueError("decode is not supported under pipeline "
                                 "parallelism yet")
            idx = jnp.asarray(decode_index, jnp.int32)
            # scalar: whole batch starting at one position (generate.py's
            # loop and chunked/speculative decode);
            # vector [B]: per-row positions (continuous batching slots,
            # single-token only)
            offs = jnp.arange(tokens.shape[1], dtype=jnp.int32)
            positions = (jnp.broadcast_to(idx + offs, tokens.shape)
                         if idx.ndim == 0 else idx[:, None] + offs[None, :])
            # left padding, and an idle slot's whole chunk (the decoder
            # gives it padding that begins past its position), are no
            # tokens: a mixture layer routes none of them
            live = (None if pad_len is None
                    or not any(s.moe for s in specs)
                    else positions >= pad_len[:, None])
            tables = (page_table if isinstance(page_table, tuple)
                      else (page_table, page_table))
            for i, spec in enumerate(specs):
                of_window = bool(spec.window and cfg.kv_window_pages)
                x = Block(cfg, spec, name=f"layer_{i}")(
                    x, positions, None, decode_index, pad_len,
                    tables[of_window], block_step, live, fresh, hit_below)
            if fresh:
                x = x[:, -1:]
            return LMHead(cfg, name="lm_head")(norm_f(x))
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape
        )
        if cfg.pipeline_stages > 1:
            if cfg.n_layers % cfg.pipeline_stages:
                raise ValueError(
                    f"n_layers={cfg.n_layers} not divisible by "
                    f"pipeline_stages={cfg.pipeline_stages}"
                )
            if (cfg.moe_every or cfg.layer_pattern
                    or cfg.attention_impl in ("ring", "ulysses")
                    or segment_ids is not None):
                raise ValueError("pipeline stages support dense blocks with "
                                 "local attention only (no moe/ring/ulysses/"
                                 "segments yet)")
            from kubeflow_tpu.parallel.pipeline import SPMDPipeline

            x = SPMDPipeline(
                stage_cls=Stage,
                stage_args=(cfg,),
                n_stages=cfg.pipeline_stages,
                n_microbatches=cfg.pp_microbatches,
                name="pipeline",
            )(x, jnp.arange(tokens.shape[1], dtype=jnp.int32))
        else:
            rblock = Block
            k_mix = None
            if cfg.remat:
                _, k_mix = _split_policy(cfg.remat_policy)
                if k_mix is not None and not 0 < k_mix <= cfg.n_layers:
                    raise ValueError(
                        f"remat_policy {cfg.remat_policy!r}: layer count "
                        f"must be in 1..{cfg.n_layers}")
                rblock = nn.remat(Block, policy=_remat_policy(cfg))
            for i, spec in enumerate(specs):
                # mixed policy: first k_mix blocks remat, the rest save
                # everything (remat never changes values, only residuals)
                blk = rblock if (k_mix is None or i < k_mix) else Block
                x = blk(cfg, spec, name=f"layer_{i}")(
                    x, positions, segment_ids)
        x = norm_f(x)
        if return_hidden:
            # Chunked-loss path (ops.xent.chunked_lm_xent): the caller
            # projects through lm_head/kernel chunk-by-chunk so the
            # [B, L, V] logits tensor never materializes. LMHead params
            # still exist (init runs with return_hidden=False).
            return x
        # Untied head, column-parallel over vocab; f32 logits out of a
        # bf16 matmul (see LMHead).
        return LMHead(cfg, name="lm_head")(x)

    def flops_per_token(self, seq_len: int | None = None) -> float:
        """Train FLOPs per token as the work needs them (nothing
        recomputed): 6*N over the weights of the matrix products (the
        layers and the vocabulary head; the embedding is a look-up and
        counts nothing), plus the attention score/value products when
        seq_len is given: per token per layer 12*h*d_head*keys (QK^T +
        PV, fwd+bwd), `keys` the mean number of keys a query of a causal
        sequence of seq_len sees, cut off at cfg.attention_window."""
        cfg = self.cfg
        attn = cfg.d_model * cfg.head_dim * (cfg.n_heads * 2 + cfg.n_kv_heads * 2)
        mlp = 3 * cfg.d_model * cfg.d_ff          # SwiGLU: gate+up+down
        specs = cfg.layers()
        n_moe = sum(s.moe for s in specs)
        n_dense = cfg.n_layers - n_moe
        n_latent = sum(s.latent for s in specs)
        if cfg.attn_gate:
            attn += cfg.d_model * cfg.head_dim * cfg.n_heads
        # a latent layer: q (through its latent, or whole), the kv latent
        # and its up-projection, o
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        latent = (
            (cfg.d_model * cfg.q_lora_rank + cfg.q_lora_rank * cfg.n_heads * qk
             if cfg.q_lora_rank else cfg.d_model * cfg.n_heads * qk)
            + cfg.d_model * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
            + cfg.kv_lora_rank * cfg.n_heads
            * (cfg.qk_nope_head_dim + cfg.v_head_dim)
            + cfg.n_heads * cfg.v_head_dim * cfg.d_model)
        # MoE layer: top_k expert MLPs (of the experts' own width) execute
        # per token (a layer that holds a share computes its share of
        # them), plus the router and the shared experts
        expert = 3 * cfg.d_model * (cfg.moe_d_ff or cfg.d_ff)
        total = cfg.n_experts_total or cfg.n_experts
        moe = (cfg.expert_top_k * expert * cfg.n_experts / total
               + cfg.d_model * total + cfg.moe_shared_experts * expert)
        head = cfg.vocab_size * cfg.d_model
        flops = 6.0 * ((cfg.n_layers - n_latent) * attn + n_latent * latent
                       + n_dense * mlp + n_moe * moe + head)
        if seq_len:
            seen = 0.0
            for s in specs:
                w = s.window
                if w and seq_len > w:   # the first w queries see 1..w keys
                    keys = w * (w + 1) / 2 + (seq_len - w) * w
                else:
                    keys = seq_len * (seq_len + 1) / 2
                # QK^T over the key's size and PV over the value's, which
                # a latent layer's up-projected form has apart
                seen += keys * ((qk + cfg.v_head_dim) / 2 if s.latent
                                else cfg.head_dim)
            flops += 12.0 * cfg.n_heads * seen / seq_len
        return flops


def _build(name: str, **overrides):
    cfg_kw = {}
    model_fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    for k in list(overrides):
        if k in model_fields:
            cfg_kw[k] = overrides.pop(k)
    if overrides:
        raise ValueError(f"unknown transformer kwargs {sorted(overrides)}")
    if cfg_kw.get("layer_pattern"):
        # a configuration's file spells a layer as a dict of its fields
        cfg_kw["layer_pattern"] = tuple(
            LayerSpec(**s) if isinstance(s, dict) else s
            for s in cfg_kw["layer_pattern"])
    cfg = TransformerConfig(**cfg_kw)
    specs = cfg.layers()
    latent = [s for s in specs if s.latent]
    if latent and not (cfg.kv_lora_rank and cfg.qk_nope_head_dim
                       and cfg.qk_rope_head_dim and cfg.v_head_dim):
        raise ValueError(
            "a latent layer needs kv_lora_rank, qk_nope_head_dim, "
            "qk_rope_head_dim and v_head_dim")
    if any(s.window for s in latent):
        raise ValueError("a latent layer with a window is not there")
    if cfg.rope_factor != 1 and not latent:
        raise ValueError("rope_factor (YaRN) is a latent layer's: the "
                         "layers of K and V heads rotate by theta alone")
    if cfg.rope_factor > 1 and not cfg.rope_original_max:
        raise ValueError("rope_factor needs rope_original_max")
    # "auto" is settled here, once per model and in the log, not layer
    # by layer at trace time: over every size of head the stack attends with
    from kubeflow_tpu.ops.attention import resolve_impl

    sizes = ([(cfg.head_dim, cfg.head_dim)] * (len(latent) < len(specs))
             + [(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim,
                 cfg.v_head_dim)] * bool(latent))
    impl = cfg.attention_impl
    for qk_dim, v_dim in sizes:
        impl = resolve_impl(impl, qk_dim, who=name, v_dim=v_dim)
    cfg = dataclasses.replace(cfg, attention_impl=impl)
    return TransformerLM(cfg)


@register_model("transformer-test")
def transformer_test(**kw) -> TransformerLM:
    """Tiny config for unit tests / dryruns."""
    base = dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
                head_dim=16, d_ff=128, max_seq_len=256)
    base.update(kw)
    return _build("transformer-test", **base)


@register_model("gpt-125m")
def gpt_125m(**kw) -> TransformerLM:
    base = dict(d_model=768, n_layers=12, n_heads=12, n_kv_heads=12, head_dim=64, d_ff=3072)
    base.update(kw)
    return _build("gpt-125m", **base)


@register_model("gpt-350m")
def gpt_350m(**kw) -> TransformerLM:
    """GPT-3 Medium shape (d=1024, L=24). With the SwiGLU MLP this lands
    ~430M actual params; the name tracks the family spec, flops_per_token
    tracks the real architecture."""
    base = dict(d_model=1024, n_layers=24, n_heads=16, n_kv_heads=16,
                head_dim=64, d_ff=4096)
    base.update(kw)
    return _build("gpt-350m", **base)


@register_model("gpt-760m")
def gpt_760m(**kw) -> TransformerLM:
    """GPT-3 Large shape, head_dim kept at 64 (24 heads) so attention
    matmuls tile the 128-lane MXU cleanly."""
    base = dict(d_model=1536, n_layers=24, n_heads=24, n_kv_heads=24,
                head_dim=64, d_ff=6144)
    base.update(kw)
    return _build("gpt-760m", **base)


@register_model("llama-1b")
def llama_1b(**kw) -> TransformerLM:
    base = dict(d_model=2048, n_layers=16, n_heads=32, n_kv_heads=8, head_dim=64, d_ff=8192)
    base.update(kw)
    return _build("llama-1b", **base)


@register_model("llama-1b-hd128")
def llama_1b_hd128(**kw) -> TransformerLM:
    """TPU-shaped 1B: identical to llama-1b except 16 heads x head_dim
    128 (GQA 4 kv heads) instead of 32 x 64. The v5e MXU contracts over
    a 128-lane dimension, so head_dim 64 caps the attention matmuls at
    half the systolic array; r5's op microbench measured the flash
    fwd+bwd at ~0.10-0.11 utilization vs ~0.66 for the MLP block,
    making attention the headline-MFU bottleneck. head_dim 128 is the
    established TPU-era choice (Llama-2-7B, Gemma); param count and
    attention FLOPs are unchanged."""
    base = dict(d_model=2048, n_layers=16, n_heads=16, n_kv_heads=4,
                head_dim=128, d_ff=8192)
    base.update(kw)
    return _build("llama-1b-hd128", **base)


@register_model("moe-test")
def moe_test(**kw) -> TransformerLM:
    base = dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
                head_dim=16, d_ff=128, moe_every=2, n_experts=4, expert_top_k=2)
    base.update(kw)
    return _build("moe-test", **base)


@register_model("gpt-moe-8e")
def gpt_moe_8e(**kw) -> TransformerLM:
    """Benchmark-scale MoE: gpt-350m backbone with 8 experts (top-2)
    every second layer — ~1.6B total params, ~550M active per token.
    Single chip measures the dispatch/combine overhead (EP=1, all
    experts local); the `expert` mesh axis shards them across chips."""
    base = dict(d_model=1024, n_layers=24, n_heads=16, n_kv_heads=16,
                head_dim=64, d_ff=4096, moe_every=2, n_experts=8,
                expert_top_k=2)
    base.update(kw)
    return _build("gpt-moe-8e", **base)
