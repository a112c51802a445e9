"""Readers of a block-diffusion mixture cell: the block step's and the
experts' counters (`SlotDecoder.stats()`: `block_passes`,
`blocks_committed`, `moe_pairs`, `moe_expert_visits`, `moe_load_max`,
`kv_pages_walked`) and the shares of their rooflines of the pass, of the
grouped expert matmuls and of the paged attention kernel. Bytes and
operations come from the cell's architecture (benchmarks/arch/sdar_moe.py),
which takes shapes from the configuration's file only; what was visited
and walked comes from the counters, not from an assumption. A program
without the counters or the kernels (a commit before they existed) reads
as None, never as an error; a share is None, never 0, where it finds
nothing."""

import re

from benchmarks.lib import opcount
from benchmarks.metrics.device import _modules, needs
from benchmarks.metrics.kvwalk import growth_share
from benchmarks.metrics.spans import _delta


def growth_ratio(ctx, part, whole):
    """The growth of `part` over the growth of `whole` between the
    window's two snapshots."""
    share = growth_share(ctx, part, whole)
    return None if share is None else share / 100.0


def load_max_over_mean(ctx):
    """The fullest expert's pairs over the mean expert's, over the
    window's passes and layers: `moe_load_max` sums the first a pass and
    a layer, and the mean expert of a pass and a layer gets its pairs
    over the number of experts."""
    top, pairs = _delta(ctx, "moe_load_max"), _delta(ctx, "moe_pairs")
    if top is None or not pairs:
        return None
    return top * ctx["cell"].dims.experts / pairs


def _traced(ctx, key):
    """A counter's growth over the window, cut to the stretch the
    profiler ran."""
    grown = _delta(ctx, key)
    if grown is None or ctx["trace"] is None:
        return None
    return grown * ctx["trace"]["window_s"] / ctx["window_s"]


def _op_seconds(ctx, pattern):
    if ctx["trace"] is None:
        return 0.0
    rx = re.compile(pattern)
    return sum(s for name, s in ctx["trace"]["op_s"].items()
               if rx.search(name))


@needs("expert_bytes")
def expert_roofline(ctx, ops):
    """The visited experts' bytes over the bandwidth, over the device
    time of the passes' grouped matmuls: those of `ops` whose rows are
    the pass's routed pairs (slots x block x experts a token), which
    leaves out the prefill's."""
    a, d = ctx["cell"].arch, ctx["cell"].dims
    visits = _traced(ctx, "moe_expert_visits")
    rows = ctx["slots"] * d.block * d.top_k
    t = _op_seconds(ctx, rf"^%?{ops}[\w.\-]* = \w+\[{rows},")
    if not visits or t <= 0:
        return None
    least = visits * a.expert_bytes(d) / opcount.peaks(
        ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least / t


@needs("kv_page_bytes")
def paged_attention_roofline(ctx, ops):
    """The pages the passes walked, as bytes over the bandwidth, over the
    device time of the block attention kernel."""
    a, d = ctx["cell"].arch, ctx["cell"].dims
    pages = _traced(ctx, "kv_pages_walked")
    t = _op_seconds(ctx, ops)
    if not pages or t <= 0:
        return None
    page_size = ctx["cell"].config["serve"]["kv_page_size"]
    least = pages * a.kv_page_bytes(d, page_size) / opcount.peaks(
        ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least / t


@needs("dense_pass_bytes", "expert_bytes", "kv_page_bytes", "token_flops",
       "attention_flops")
def pass_roofline(ctx, single, fused, fuse):
    """The least time the traced passes could take over the time they
    took. Bytes: attention, router and head once a pass, the experts
    that were visited (the counter, not 128 a layer), the pages that
    were walked. Operations: every position of every active slot's block
    through the layers' matrices and its attention over the walked
    pages' positions, the head on the positions a denoising pass finds
    masked (half the block and half a pass's share, over a whole
    block's passes)."""
    one, many = _modules(ctx, single), _modules(ctx, fused)
    passes = len(one) + fuse * len(many)
    counted = {k: _traced(ctx, k) for k in (
        "moe_expert_visits", "kv_pages_walked", "block_passes",
        "blocks_committed")}
    if not passes or any(v is None for v in counted.values()):
        return None
    a, d = ctx["cell"].arch, ctx["cell"].dims
    pk = opcount.peaks(ctx["device_kind"])
    page_size = ctx["cell"].config["serve"]["kv_page_size"]
    nbytes = (passes * a.dense_pass_bytes(d)
              + counted["moe_expert_visits"] * a.expert_bytes(d)
              + counted["kv_pages_walked"] * a.kv_page_bytes(d, page_size))
    denoising = counted["block_passes"] - counted["blocks_committed"]
    flops = (counted["block_passes"] * d.block * d.layers * a.token_flops(d)
             + d.layers * a.attention_flops(
                 d, d.block * page_size * counted["kv_pages_walked"])
             + denoising * (d.block + d.per_pass) / 2 * 2 * d.d * d.vocab)
    least = max(nbytes / pk["hbm_bytes_per_s"], flops / pk["bf16_flops"])
    return 100.0 * least / (sum(one) + sum(many))
