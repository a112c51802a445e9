"""Mixture-of-experts with expert parallelism.

Three dispatch implementations behind one module:

- **dropless** (sort + grouped matmul, one device): the routed (token,
  expert) pairs are sorted by expert into contiguous groups and each of
  gate, up and down is ONE grouped matmul over the groups: no capacity,
  no padding, no one-hot tensors, nothing dropped (`moe_drop` reads 0 by
  construction), and an expert no pair visits is never read. Padding
  rows (`live` false) are routed nowhere. What `moe_impl="auto"` takes
  without a mesh or on a mesh of one device: the path of a served model,
  many narrow experts and few rows a group. The grouped matmul is
  `jax.lax.ragged_dot`, the reference and the CPU's path, or, where
  `ops/grouped_matmul.py:use_kernel` says so from backend, mesh, dtype
  and shape (a TPU, bfloat16, few rows a group), the Pallas kernel of
  that module, which streams each visited expert's matrix through VMEM
  once; the sort, the gates and the rounding are the same.

- **dense** (Switch/GShard one-hot einsums): dispatch/combine are einsums
  against one-hot [b,s,e,c] tensors. Correct on any mesh, runs the whole
  block on the MXU — and materializes capacity-padded tensors whose
  dispatch/combine einsums cost O(s*e*c*d) MACs regardless of how many
  slots are filled, and a capacity that DROPS what overflows it. Kept as
  the oracle (`moe_impl="dense"`) and as the fallback for meshes of
  several devices that the sparse path doesn't cover.

- **sparse** (sort + scatter + explicit all-to-all under shard_map): per
  token-shard, routed (token, slot) pairs are sorted by expert id,
  scattered into per-expert capacity buffers (no one-hot tensors — the
  dispatch is a gather/scatter, not a matmul), exchanged over the
  `expert` mesh axis with jax.lax.all_to_all, run through the local
  experts as one batched GEMM, and returned by the reverse all-to-all.
  This is SURVEY.md §2.5's "all-to-all dispatch over ICI" made explicit
  instead of hoping GSPMD derives it from the einsum. Enabled
  automatically on meshes where tokens are sharded over (dcn, data,
  expert) only (fsdp/model/seq all 1 — the canonical EP regime);
  anything else falls back to dense.

**A layer that holds a share** (cfg.n_experts_total > cfg.n_experts, the
dropless path only): the router is as wide as the model's experts are
many, and this layer holds the `n_experts` of them from id
`cfg.expert_first` on, as one chip of an expert-parallel group does. It
routes over all of them, scores, choice and gate weights alike, and
computes the part of the sum that its own experts give: a pair whose
expert lies elsewhere sorts behind every group, as a padding row's does,
and comes back as zeros. Nothing stands in for the absent chips: no
exchange, no stand-in weights. `moe_pairs`, `moe_expert_visits`,
`moe_load_max` and `moe_kernel_pairs` then count the held pairs, and
`moe_pairs_routed` every pair the live tokens were routed (tokens x k).
Where the call has many rows (`compact_bound`: a prompt's rung, never a
tick) only the held pairs' rows are touched: the sort puts them first,
and the gather, the three grouped matmuls and the gates' sum work on a
static window of that many sorted positions, and on a second window in
the freak call whose held pairs overrun the first (`moe_compact_calls`,
`moe_compact_spills`). Nothing is dropped there either.

Two scoring rules (cfg.moe_score): "softmax" (softmax over the experts,
the k largest, renormalised) and "sigmoid" (sigmoid scores in float32,
the k largest of score + `expert_bias`, a buffer that moves the choice
only, then the chosen scores over their sum and times
cfg.moe_route_scale). Under sigmoid scores cfg.moe_n_group /
cfg.moe_topk_group limit the choice to the best groups of consecutive
experts (a group's score: the sum of its two largest score + bias); 1 / 1
is no groups. cfg.moe_shared_experts adds a plain SwiGLU of
that many experts' width on every token (`shared`) to the routed sum.

Tokens are BATCH-sharded over the `expert` axis outside this block
(parallel/mesh.py BATCH_AXES): the expert axis would otherwise duplicate
every dense layer's compute ep-fold.

Per-step diagnostics are sowed into the "diagnostics" collection:
  moe_fill — filled fraction of expert capacity slots (1 - padding);
  moe_drop — fraction of routed (token, slot) pairs dropped to overflow;
and, as int32 counts of this call (of the router's choice before any
capacity; the dropless path leaves padding rows out):
  moe_pairs — routed pairs of live tokens;
  moe_expert_visits — experts that got at least one pair (the groups the
    grouped matmul reads weights for);
  moe_load_max — the fullest expert's pairs;
  moe_kernel_pairs — the pairs whose grouped matmuls the Pallas kernel
    took (all of `moe_pairs` or 0: the choice is made when the program
    is traced);
  moe_pairs_routed — the pairs of live tokens whether their expert is
    held here or not (`moe_pairs` where every expert is held);
and from a layer that holds a share alone:
  moe_compact_calls — 1 where this call's rows were compacted to the
    held pairs (`compact_bound`: decided when the program is traced);
  moe_compact_spills — 1 where its held pairs overran the window and
    took a second one.

In the device trace the dropless path's expert matmuls are custom calls
whose names start alike: the compiler's own for `ragged_dot`,
`%ragged-dot-none.N = ... custom-call(`, and the Pallas kernel's,
`%ragged-dot-streamed.N = ... custom-call(` (EXPERT_MATMUL_TRACE_NAME
below, `grouped_matmul.KERNEL_NAME`; tests/test_trace_names.py). The
router is a plain matrix product that XLA fuses: a fusion carries no name
of the program's in the trace, and its time is read as part of the step's.

Reference framework has no MoE (SURVEY.md §2.5 "Expert parallelism:
Absent"); this is TPU-native net-new capability.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from kubeflow_tpu.ops import grouped_matmul, moe_combine
from kubeflow_tpu.parallel.mesh import (
    AXIS_DCN,
    AXIS_DATA,
    AXIS_EXPERT,
    AXIS_FSDP,
    AXIS_MODEL,
    AXIS_PIPELINE,
    AXIS_SEQ,
    current_mesh,
)


# What the device trace's names of the dropless path's three grouped
# matmuls start with (XLA:TPU's own kernel for `jax.lax.ragged_dot` and
# `grouped_matmul.KERNEL_NAME`): the benchmark's `moe.expert_roofline.*`
# finds them by it.
EXPERT_MATMUL_TRACE_NAME = "ragged-dot"


def _router(cfg, x, init, bias=None):
    """Top-k routing in float32 over every expert of the model, held
    here or not. Returns (probs [b,s,e], gate_vals [b,s,k], gate_idx
    [b,s,k]). Softmax scores: the k largest, renormalised. Sigmoid
    scores (`bias` [e] given): the k largest of score + bias, the
    chosen scores (not the biased ones) over their sum, times
    cfg.moe_route_scale; with cfg.moe_n_group > 1 the k largest inside
    the best groups (`_within_best_groups`)."""
    router = nn.DenseGeneral(
        cfg.n_experts_total or cfg.n_experts, use_bias=False,
        dtype=jnp.float32,
        kernel_init=nn.with_partitioning(init, (AXIS_FSDP, None)),
        name="router",
    )
    logits = router(x.astype(jnp.float32))
    if bias is None:
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, cfg.expert_top_k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
        return probs, gate_vals, gate_idx
    probs = jax.nn.sigmoid(logits)
    choice = probs + bias
    if getattr(cfg, "moe_n_group", 1) > 1:
        choice = _within_best_groups(choice, cfg.moe_n_group,
                                     cfg.moe_topk_group)
    _, gate_idx = jax.lax.top_k(choice, cfg.expert_top_k)
    gate_vals = jnp.take_along_axis(probs, gate_idx, axis=-1)
    gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-20) \
        * cfg.moe_route_scale
    return probs, gate_vals, gate_idx


def _within_best_groups(choice, n_group: int, topk_group: int):
    """Group-limited choice: `choice` [..., e] with -inf outside the
    `topk_group` groups, of the `n_group` groups of e / n_group
    consecutive experts, whose two largest entries sum highest (the
    first of equals). The k largest of what comes back lie in those
    groups."""
    groups = choice.reshape(choice.shape[:-1] + (n_group, -1))
    score = jax.lax.top_k(groups, 2)[0].sum(-1)           # [..., n_group]
    _, best = jax.lax.top_k(score, topk_group)
    kept = jax.nn.one_hot(best, n_group, dtype=jnp.bool_).any(-2)
    return jnp.where(kept[..., None], groups, -jnp.inf).reshape(choice.shape)


def _expert_mlp(cfg, xin, w_gate, w_up, w_down):
    """Batched SwiGLU over experts: xin [e, t, d] -> [e, t, d]."""
    h = nn.silu(jnp.einsum("etd,edf->etf", xin, w_gate.astype(cfg.dtype))) * \
        jnp.einsum("etd,edf->etf", xin, w_up.astype(cfg.dtype))
    return jnp.einsum("etf,efd->etd", h, w_down.astype(cfg.dtype))


# A window of sorted positions for the held pairs of a layer that holds
# a share: COMPACT_ROOM times their mean, and never under
# COMPACT_MIN_ROWS (under a few hundred rows the gather is nothing, and
# one tick's held share can be anything from none to all).
COMPACT_ROOM = 2
COMPACT_MIN_ROWS = 512


def compact_bound(pairs: int, e: int, e_total: int):
    """The rows `dropless_mlp` works on in the place of all `pairs`
    routed pairs where the layer holds `e` of `e_total` experts, or None
    where it works on all of them: a layer that holds every expert, and
    a call with so few rows that the window would not be smaller (every
    tick). A rule over shapes; nothing sets it."""
    if e >= e_total:
        return None
    mean = -(-COMPACT_ROOM * pairs * e // e_total)
    tile = grouped_matmul.ROW_TILE
    bound = max(COMPACT_MIN_ROWS, -(-mean // tile) * tile)
    return bound if bound < pairs else None


def dropless_mlp(cfg, x, gate_vals, gate_idx, w_gate, w_up, w_down,
                 live=None, streamed=False, first=None, bound=None):
    """Sort by expert, one grouped matmul each for gate, up and down over
    the contiguous groups, combine with the gates. x [t, d] flattened
    tokens, gate_* [t, k], weights [e, ...], `live` [t] bool or None
    (padding rows are routed nowhere and come back as zeros); `streamed`:
    the grouped matmuls are the Pallas kernel's and not `ragged_dot`'s
    (the caller asks `grouped_matmul.use_kernel`); `first`: the weights
    are the experts `first .. first + e - 1` of those `gate_idx` counts
    over (None: all of them), and a pair whose expert is not among them
    comes back as zeros like a dead row's; `bound` (`compact_bound`'s
    answer): the sorted positions a window holds, None for all t * k at
    once. Returns (y [t, d], counts [e]): the pairs each held expert
    got."""
    t, d = x.shape
    k = gate_idx.shape[-1]
    e = w_gate.shape[0]
    eidx = gate_idx.reshape(-1)                      # [t*k]
    if first is not None:
        # an absent expert's pair sorts behind every group, as a dead one
        eidx = eidx - first
        eidx = jnp.where((eidx >= 0) & (eidx < e), eidx, e)
    if live is not None:
        # a dead pair sorts behind every group and belongs to none
        eidx = jnp.where(jnp.repeat(live, k), eidx, e)
    order = jnp.argsort(eidx)                        # stable
    counts = jnp.zeros((e + 1,), jnp.int32).at[eidx].add(1)[:e]
    matmul = (grouped_matmul.grouped_matmul if streamed
              else jax.lax.ragged_dot)
    if bound is not None:
        return _compacted(cfg, x, gate_vals, order, counts, matmul,
                          (w_gate, w_up, w_down), bound), counts
    xs = x[order // k].astype(cfg.dtype)
    wg, wu, wd = (w.astype(cfg.dtype) for w in (w_gate, w_up, w_down))
    out = _experts(matmul, xs, wg, wu, wd, counts)   # [t*k, d], sorted
    # rows behind the last group are no group's: whatever they hold
    out = jnp.where((eidx[order] < e)[:, None], out, 0)
    # back to (token, slot) order, then the gates' weighted sum
    out = out[jnp.argsort(order)].reshape(t, k, d)
    y = jnp.einsum("tkd,tk->td", out.astype(jnp.float32),
                   gate_vals.astype(jnp.float32))
    return y.astype(cfg.dtype), counts


def _experts(matmul, rows, wg, wu, wd, sizes):
    """SwiGLU over rows sorted by expert: three grouped matmuls."""
    h = nn.silu(matmul(rows, wg, sizes)) * matmul(rows, wu, sizes)
    return matmul(h, wd, sizes)


def _compacted(cfg, x, gate_vals, order, counts, matmul, weights,
               bound: int):
    """`dropless_mlp` over the held pairs alone. The stable sort put them
    first, `order[:sum(counts)]`, so a window of `bound` sorted positions
    holds them all but in a freak call, which takes a second window (the
    loop's turns are cdiv(held pairs, bound): every held pair is in one,
    each group's size clipped to the window). A window gathers its rows,
    runs the experts over them and adds each row, weighed by its gate,
    into its token's row of a float32 [t, d] (`ops/moe_combine.py`: its
    kernel or XLA's scatter-add, by its `use_kernel`): the addends of a
    token are the whole-rows path's, and no step touches t * k rows.
    Forward only: a loop of as many turns as the call needs."""
    t, d = x.shape
    k = gate_vals.shape[-1]
    weights = [w.astype(cfg.dtype) for w in weights]
    add = (moe_combine.combine if moe_combine.use_kernel(t, bound, d)
           else moe_combine.scatter_add)
    ends = jnp.cumsum(counts)
    starts, held = ends - counts, ends[-1]
    gates = gate_vals.reshape(-1).astype(jnp.float32)
    # whole windows: a slice that starts inside `order` ends inside it
    order = jnp.pad(order, (0, -(t * k) % bound))

    def window(w, y):
        lo = w * bound
        pair = jax.lax.dynamic_slice(order, (lo,), (bound,))
        sizes = (jnp.clip(ends - lo, 0, bound)
                 - jnp.clip(starts - lo, 0, bound))
        out = _experts(matmul, x[pair // k].astype(cfg.dtype), *weights,
                       sizes)
        # positions behind the last group are no group's, whatever
        # they hold: token t is nobody
        token = jnp.where(lo + jnp.arange(bound) < held, pair // k, t)
        return add(y, out, token, gates[pair])

    y = jax.lax.fori_loop(0, -(-held // bound), window,
                          jnp.zeros((t, d), jnp.float32))
    return y.astype(cfg.dtype)


def sparse_dispatch_mlp(cfg, x_local, gate_vals, gate_idx, w_gate, w_up,
                        w_down, capacity_factor, ep_axis=None):
    """Per-shard sort-based dispatch + expert MLP + combine.

    All arrays are LOCAL (this runs inside shard_map, or directly when
    there is no mesh): x_local [t, d] flattened tokens, gate_* [t, k],
    weights [e_local, ...]. When ep_axis is set, buffers are exchanged
    across it (global experts e = e_local * ep). Returns (y [t, d],
    fill_count, routed_count, slot_count) — slot_count is THIS shard's
    allocated capacity slots (e * cap), the denominator for the fill
    diagnostic (per-shard capacity rounds differently from the dense
    per-row formula, so callers must not recompute it).
    """
    t, d = x_local.shape
    k = gate_idx.shape[-1]
    ep = 1 if ep_axis is None else jax.lax.axis_size(ep_axis)
    e_local = w_gate.shape[0]
    e = e_local * ep
    # per-shard per-expert capacity (same invariant as the dense path's
    # per-row capacity: cf * tokens * k / e)
    cap = max(1, int(capacity_factor * t * k / e))

    # sort routed (token, slot) pairs by expert id -> contiguous groups
    eidx = gate_idx.reshape(-1)                      # [t*k]
    order = jnp.argsort(eidx)                        # stable
    sorted_e = eidx[order]
    sorted_tok = order // k
    # position within each expert's group: running index minus the
    # group's start (exclusive cumsum of per-expert counts)
    counts = jnp.bincount(eidx, length=e)            # [e]
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(t * k) - starts[sorted_e]
    keep = pos < cap
    slot = jnp.where(keep, sorted_e * cap + pos, e * cap)  # overflow -> OOB

    # scatter tokens into capacity buffers [e*cap, d] (OOB rows drop)
    buf = jnp.zeros((e * cap, d), cfg.dtype).at[slot].set(
        x_local[sorted_tok].astype(cfg.dtype), mode="drop")

    if ep_axis is not None and ep > 1:
        # [e, cap, d] -> exchange expert groups so every shard holds ALL
        # shards' buffers for ITS local experts: [ep, e_local, cap, d]
        buf = buf.reshape(ep, e_local * cap, d)
        buf = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=0,
                                 tiled=False)        # [ep, e_local*cap, d]
        xin = buf.reshape(ep, e_local, cap, d).transpose(1, 0, 2, 3) \
                 .reshape(e_local, ep * cap, d)
    else:
        xin = buf.reshape(e_local, cap, d)

    out = _expert_mlp(cfg, xin, w_gate, w_up, w_down)

    if ep_axis is not None and ep > 1:
        out = out.reshape(e_local, ep, cap, d).transpose(1, 0, 2, 3) \
                 .reshape(ep, e_local * cap, d)
        out = jax.lax.all_to_all(out, ep_axis, split_axis=0, concat_axis=0,
                                 tiled=False)
    flat_out = out.reshape(e * cap, d)

    # combine: gather each kept (token, slot) row, weight by its gate
    contrib = flat_out.at[slot].get(mode="fill", fill_value=0)  # [t*k, d]
    w = jnp.where(keep, gate_vals.reshape(-1)[order], 0.0)
    y = jnp.zeros((t, d), jnp.float32).at[sorted_tok].add(
        contrib.astype(jnp.float32) * w[:, None])
    return (y.astype(cfg.dtype), jnp.sum(keep), jnp.asarray(t * k),
            jnp.asarray(e * cap))


class MoEBlock(nn.Module):
    """Drop-in replacement for the dense SwiGLU MLP."""

    cfg: "TransformerConfig"  # noqa: F821 — structural typing, avoids cycle
    capacity_factor: float = 1.25

    def _dropless_ok(self, mesh) -> bool:
        """One device holds every expert and every token: nothing to
        exchange, so nothing needs a capacity."""
        return (getattr(self.cfg, "moe_impl", "auto") == "auto"
                and (mesh is None or mesh.size == 1))

    def _sparse_ok(self, mesh) -> bool:
        impl = getattr(self.cfg, "moe_impl", "auto")
        if impl == "dense":
            return False
        if mesh is None:
            # No mesh context -> dense, even when sparse is forced:
            # init-time traces (jax.eval_shape of model.init) legitimately
            # run outside the mesh context, so raising here would break
            # every forced-sparse config before its first step. Trainer
            # steps always carry the mesh; a truly meshless forced-sparse
            # run therefore measures the DENSE path — single-chip A/Bs
            # must go through the trainer/bench (which always build a
            # mesh) for the label to mean what it says.
            return False
        ep = mesh.shape.get(AXIS_EXPERT, 1)
        # preconditions of the shard_map formulation: tokens sharded over
        # dcn/data/expert only (d and seq unsharded) and experts evenly
        # divisible across the expert axis
        ok = all(mesh.shape.get(a, 1) == 1
                 for a in (AXIS_FSDP, AXIS_MODEL, AXIS_SEQ, AXIS_PIPELINE)) \
            and self.cfg.n_experts % ep == 0
        if impl == "sparse" and not ok:
            # forced sparse on an uncovered mesh would die deep inside
            # shard_map tracing; fail with the config error instead
            raise ValueError(
                f"moe_impl='sparse' requires fsdp/model/seq/pipe mesh axes "
                f"of size 1 and n_experts % expert_axis == 0; got mesh "
                f"{dict(mesh.shape)} with n_experts={self.cfg.n_experts}")
        return ok

    @nn.compact
    def __call__(self, x: jax.Array, live=None) -> jax.Array:
        """`live` [b, s] bool: rows that are tokens (None = all). Only
        the dropless path reads it; the capacity paths serve training
        meshes, whose rows are all tokens."""
        cfg = self.cfg
        b, s, d = x.shape
        e, k = cfg.n_experts, cfg.expert_top_k
        e_all = cfg.n_experts_total or e
        d_ff = cfg.moe_d_ff or cfg.d_ff
        init = nn.initializers.normal(0.02)

        if cfg.moe_score not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown moe_score {cfg.moe_score!r} "
                             "(softmax|sigmoid)")
        n_group = getattr(cfg, "moe_n_group", 1)
        if n_group > 1 and (cfg.moe_score != "sigmoid" or e_all % n_group
                            or e_all < 2 * n_group
                            or cfg.moe_topk_group > n_group):
            raise ValueError(
                f"moe_n_group {n_group} / moe_topk_group "
                f"{cfg.moe_topk_group}: group-limited routing is over "
                f"sigmoid scores, groups of two or more that divide the "
                f"{e_all} experts, and no more groups than there are")
        bias = (self.param("expert_bias", nn.initializers.zeros, (e_all,),
                           jnp.float32)
                if cfg.moe_score == "sigmoid" else None)
        probs, gate_vals, gate_idx = _router(cfg, x, init, bias)

        w_gate = self.param(
            "w_gate", nn.with_partitioning(init, (AXIS_EXPERT, AXIS_FSDP, AXIS_MODEL)),
            (e, d, d_ff), jnp.float32)
        w_up = self.param(
            "w_up", nn.with_partitioning(init, (AXIS_EXPERT, AXIS_FSDP, AXIS_MODEL)),
            (e, d, d_ff), jnp.float32)
        w_down = self.param(
            "w_down", nn.with_partitioning(init, (AXIS_EXPERT, AXIS_MODEL, AXIS_FSDP)),
            (e, d_ff, d), jnp.float32)

        mesh = current_mesh()
        dropless = self._dropless_ok(mesh)
        share = e_all != e
        if share and not dropless:
            raise ValueError(
                f"a layer that holds {e} of {e_all} experts runs the "
                "dropless path only (moe_impl 'auto', one device): the "
                "capacity paths hold every expert")
        use_sparse = not dropless and self._sparse_ok(mesh)
        # one rule for gate, up and down: it asks of k and n what holds
        # for them swapped, and of the rows that exist, the held pairs in
        # the mean
        streamed = dropless and grouped_matmul.use_kernel(
            b * s * k * e // e_all, d, d_ff, e, cfg.dtype)
        bound = compact_bound(b * s * k, e, e_all)
        if dropless:
            y, counts = dropless_mlp(
                cfg, x.reshape(b * s, d), gate_vals.reshape(b * s, k),
                gate_idx.reshape(b * s, k), w_gate, w_up, w_down,
                None if live is None else live.reshape(b * s), streamed,
                cfg.expert_first if share else None, bound)
            y = y.reshape(b, s, d)
            kept = routed = slots = jnp.sum(counts)
        elif use_sparse:
            y, kept, routed, slots = self._sparse(
                x, gate_vals, gate_idx, w_gate, w_up, w_down, mesh)
        else:
            y, kept, routed, slots = self._dense(
                x, gate_vals, gate_idx, w_gate, w_up, w_down)
        if not dropless:
            # the router's choice, before any capacity: the same three
            # counts from every path, so that a program traced without
            # its mesh (init) and one traced under it sow one structure
            counts = jnp.zeros((e,), jnp.int32).at[
                gate_idx.reshape(-1)].add(1)
        pairs = jnp.sum(counts)
        self.sow("diagnostics", "moe_pairs", pairs)
        self.sow("diagnostics", "moe_pairs_routed",
                 pairs if not share else k * (
                     jnp.int32(b * s) if live is None
                     else jnp.sum(live.astype(jnp.int32))))
        self.sow("diagnostics", "moe_expert_visits",
                 jnp.sum((counts > 0).astype(jnp.int32)))
        self.sow("diagnostics", "moe_load_max", jnp.max(counts))
        self.sow("diagnostics", "moe_kernel_pairs",
                 jnp.sum(counts) if streamed else jnp.int32(0))
        if share:
            self.sow("diagnostics", "moe_compact_calls",
                     jnp.int32(bound is not None))
            self.sow("diagnostics", "moe_compact_spills",
                     jnp.int32(0) if bound is None
                     else (pairs > bound).astype(jnp.int32))
        # Ground truth for which dispatch path actually ran (ADVICE r4):
        # _sparse_ok silently falls back to dense on a meshless trace, so
        # a run labeled 'sparse' could measure dense with nothing in the
        # record saying so. 1.0 = sparse all-to-all, 0.0 = dense oracle.
        self.sow("diagnostics", "moe_sparse_dispatch",
                 jnp.float32(1.0 if use_sparse else 0.0))

        # aux load-balancing loss: mean_e (dispatch fraction * prob mass),
        # with the dispatch fraction taken from the router's PRE-capacity
        # top-k assignment — the Switch/T5X convention, and identical in
        # both dispatch paths by construction (it depends only on
        # gate_idx). NOTE round 3's dense path used the post-capacity
        # fraction; the conventions differ only when experts overflow.
        me = probs.mean(axis=(0, 1))                   # [e]
        assign_pre = jax.nn.one_hot(gate_idx, e_all, dtype=jnp.float32)
        ce = assign_pre.sum(axis=2).mean(axis=(0, 1))
        aux = e_all * jnp.sum(me * ce)
        self.sow("losses", "moe_aux", aux)
        # dispatch diagnostics: how much of the capacity
        # buffer is padding, and how much routing overflowed. `slots` is
        # reported by the path that allocated them — the sparse path's
        # per-shard capacity (cf*t_local*k/e) rounds differently from the
        # dense per-row formula, so recomputing it here would let
        # moe_fill exceed 1.
        self.sow("diagnostics", "moe_fill",
                 kept.astype(jnp.float32)
                 / jnp.maximum(slots.astype(jnp.float32), 1.0))
        self.sow("diagnostics", "moe_drop",
                 1.0 - kept.astype(jnp.float32)
                 / jnp.maximum(routed.astype(jnp.float32), 1.0))
        if cfg.moe_shared_experts:
            y = y + self._shared(x, d_ff * cfg.moe_shared_experts, init)
        return y.astype(cfg.dtype)

    def _shared(self, x, width: int, init):
        """The shared experts: one SwiGLU of their summed width on every
        token (padding rows too: plain matrices, nothing to route)."""
        cfg = self.cfg
        dense = lambda feats, names, name: nn.DenseGeneral(  # noqa: E731
            feats, use_bias=False, dtype=cfg.dtype,
            kernel_init=nn.with_partitioning(init, names), name=name)
        h = nn.silu(dense(width, (AXIS_FSDP, AXIS_MODEL), "shared_gate")(x)) \
            * dense(width, (AXIS_FSDP, AXIS_MODEL), "shared_up")(x)
        return dense(x.shape[-1], (AXIS_MODEL, AXIS_FSDP), "shared_down")(h)

    # ---- dense (oracle) path --------------------------------------------

    def _dense(self, x, gate_vals, gate_idx, w_gate, w_up, w_down):
        cfg = self.cfg
        b, s, d = x.shape
        e, k = cfg.n_experts, cfg.expert_top_k
        capacity = int(self.capacity_factor * s * k / e) or 1

        # Tokens arrive sharded over BATCH_AXES, which includes `expert`.
        # The dense dispatch/combine einsums regroup tokens by expert —
        # a transition the pre-Shardy partitioner can only bridge with
        # its replicate-then-repartition fallback ("Involuntary full
        # rematerialization"). Pull the batch off the expert axis
        # explicitly first (one all-gather over expert), and push the
        # output back at the end.
        from kubeflow_tpu.parallel.mesh import shard_constraint

        noexp = (AXIS_DCN, AXIS_DATA, AXIS_FSDP)
        mesh = current_mesh()
        resharded = mesh is not None and mesh.shape.get(AXIS_EXPERT, 1) > 1
        if resharded:
            x = shard_constraint(x, P(noexp, None, None))
            gate_vals = shard_constraint(gate_vals, P(noexp, None, None))
            gate_idx = shard_constraint(gate_idx, P(noexp, None, None))

        assign = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)  # [b,s,k,e]
        flat = assign.reshape(b, s * k, e)
        pos = jnp.cumsum(flat, axis=1) - flat          # arrival order
        pos = pos.reshape(b, s, k, e)
        within_cap = pos < capacity
        assign = assign * within_cap                   # drop overflow
        pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                                dtype=jnp.float32)
        dispatch = jnp.einsum("bske,bskec->bsec", assign, pos_oh)
        combine = jnp.einsum("bsk,bske,bskec->bsec",
                             gate_vals.astype(jnp.float32), assign, pos_oh)

        xin = jnp.einsum("bsec,bsd->ebcd", dispatch.astype(cfg.dtype), x)
        h = nn.silu(jnp.einsum("ebcd,edf->ebcf", xin, w_gate.astype(cfg.dtype))) * \
            jnp.einsum("ebcd,edf->ebcf", xin, w_up.astype(cfg.dtype))
        out = jnp.einsum("ebcf,efd->ebcd", h, w_down.astype(cfg.dtype))
        y = jnp.einsum("bsec,ebcd->bsd", combine.astype(cfg.dtype), out)
        if resharded:
            from kubeflow_tpu.parallel.mesh import BATCH_AXES

            # two-step ladder: pin the einsum output (and, transposed,
            # its backward cotangent) to the expert-free layout FIRST so
            # the only transition at the einsum is an all-gather over
            # `expert`; then restore the full batch sharding for the
            # residual stream
            y = shard_constraint(y, P(noexp, None, None))
            y = shard_constraint(y, P(BATCH_AXES, None, None))
        kept = jnp.sum(assign)
        return (y, kept, jnp.asarray(b * s * k, jnp.float32),
                jnp.asarray(b * e * capacity, jnp.float32))

    # ---- sparse (all-to-all) path ---------------------------------------

    def _sparse(self, x, gate_vals, gate_idx, w_gate, w_up, w_down, mesh):
        from jax import shard_map

        cfg = self.cfg
        b, s, d = x.shape
        tok_axes = (AXIS_DCN, AXIS_DATA, AXIS_EXPERT)
        cf = self.capacity_factor

        def body(xl, gvl, gil, wg, wu, wd):
            bl = xl.shape[0]
            y, fill, routed, slots = sparse_dispatch_mlp(
                cfg, xl.reshape(bl * s, d), gvl.reshape(bl * s, -1),
                gil.reshape(bl * s, -1), wg, wu, wd, cf,
                ep_axis=AXIS_EXPERT)
            # diagnostics are global sums: reduce over the token shards
            fill = jax.lax.psum(fill, tok_axes)
            routed = jax.lax.psum(routed, tok_axes)
            slots = jax.lax.psum(slots, tok_axes)
            return y.reshape(bl, s, d), fill, routed, slots

        tok_spec = P(tok_axes, None, None)
        gate_spec = P(tok_axes, None, None)
        y, fill, routed, slots = shard_map(
            body, mesh=mesh,
            in_specs=(tok_spec, gate_spec, gate_spec,
                      P(AXIS_EXPERT, None, None), P(AXIS_EXPERT, None, None),
                      P(AXIS_EXPERT, None, None)),
            out_specs=(tok_spec, P(), P(), P()),
        )(x, gate_vals, gate_idx, w_gate, w_up, w_down)
        return y, fill, routed, slots
