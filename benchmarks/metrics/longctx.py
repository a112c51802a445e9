"""Readers of a cell whose model keeps its pages by layer kind and holds
a share of its experts (benchmarks/arch/afmoe.py): the shares of their
rooflines of the decode tick, of the tick's grouped expert matmuls, of
the paged attention kernel and of the prefill's flash kernels. Bytes and
operations come from the cell's architecture, which takes shapes from
the configuration's file only; what was visited and walked comes from
the program's counters (`SlotDecoder.stats()`: `moe_expert_visits`,
`kv_pages_walked`, `kv_pages_walked_window`), not from an assumption. A
program without the counters or the kernels (a commit before they
existed) reads as None, never as an error; a share is None, never 0,
where it finds nothing."""

from benchmarks.lib import opcount
from benchmarks.metrics.blockdiff import _op_seconds, _traced
from benchmarks.metrics.device import _modules, needs


def _walked_bytes(ctx):
    """The bytes of the pages the traced ticks walked, by kind: the held
    kind's table is read by the full layers, the window kind's by the
    sliding ones."""
    a, d = ctx["cell"].arch, ctx["cell"].dims
    walked = _traced(ctx, "kv_pages_walked")
    window = _traced(ctx, "kv_pages_walked_window")
    if walked is None or window is None:
        return None
    page_size = ctx["cell"].config["serve"]["kv_page_size"]
    sliding = sum(d.sliding)
    return ((walked - window) * a.kv_page_bytes(d, page_size,
                                                d.layers - sliding)
            + window * a.kv_page_bytes(d, page_size, sliding))


@needs("weight_bytes", "expert_bytes", "kv_page_bytes", "forward_flops")
def decode_roofline(ctx, single, fused, fuse):
    """The least time the traced ticks could take over the time they
    took. Bytes: every layer's part outside its routed experts and the
    head once a tick, the experts that were visited (the counter, not 32
    a layer), the pages that were walked, by kind. Operations: the
    window's finished requests' decode tokens, cut to the traced
    stretch."""
    one, many = _modules(ctx, single), _modules(ctx, fused)
    ticks = len(one) + fuse * len(many)
    visits, pages = _traced(ctx, "moe_expert_visits"), _walked_bytes(ctx)
    if not ticks or not ctx["requests"] or visits is None or pages is None:
        return None
    a, d = ctx["cell"].arch, ctx["cell"].dims
    pk = opcount.peaks(ctx["device_kind"])
    share = ctx["trace"]["window_s"] / ctx["window_s"]
    flops = share * sum(
        a.forward_flops(d, r["prompt"], r["prompt"] + r["out"] - 1,
                        r["out"] - 1) for r in ctx["requests"])
    nbytes = (ticks * a.weight_bytes(d, 2, 0) + visits * a.expert_bytes(d)
              + pages)
    least = max(nbytes / pk["hbm_bytes_per_s"], flops / pk["bf16_flops"])
    return 100.0 * least / (sum(one) + sum(many))


@needs("expert_bytes")
def expert_roofline(ctx, ops):
    """The visited experts' bytes over the bandwidth, over the device
    time of the ticks' grouped matmuls: those of `ops` whose rows are a
    tick's routed pairs (slots x experts a token), which leaves out the
    prefills', whose rows are a rung's."""
    a, d = ctx["cell"].arch, ctx["cell"].dims
    visits = _traced(ctx, "moe_expert_visits")
    rows = ctx["slots"] * d.top_k
    t = _op_seconds(ctx, rf"^%?{ops}[\w.\-]* = \w+\[{rows},")
    if not visits or t <= 0:
        return None
    least = visits * a.expert_bytes(d) / opcount.peaks(
        ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least / t


@needs("kv_page_bytes")
def paged_attention_roofline(ctx, ops):
    """The pages the ticks walked, by kind, as bytes over the bandwidth,
    over the device time of the paged attention kernel."""
    pages, t = _walked_bytes(ctx), _op_seconds(ctx, ops)
    if not pages or t <= 0:
        return None
    return 100.0 * pages / opcount.peaks(
        ctx["device_kind"])["hbm_bytes_per_s"] / t


@needs("flash_flops")
def prefill_flash_roofline(ctx, ops):
    """The operations the prompts' own attention needs (the real prompts
    of the requests admitted in the traced stretch, each layer by its
    kind, causal and within the window) over the peak, over the device
    time of the prefills' flash kernels. The prompts are the window's
    finished requests', cut to the traced stretch."""
    t = _op_seconds(ctx, ops)
    if t <= 0 or not ctx["requests"] or ctx["trace"] is None:
        return None
    a, d = ctx["cell"].arch, ctx["cell"].dims
    share = ctx["trace"]["window_s"] / ctx["window_s"]
    need = share * a.flash_flops(d, [r["prompt"] for r in ctx["requests"]])
    return 100.0 * need / opcount.peaks(ctx["device_kind"])["bf16_flops"] / t
