"""Readers of a cell whose model attends over latents
(benchmarks/arch/axk1.py): the shares of their rooflines of the decode
tick, of the latent attention kernel, of the tick's grouped expert
matmuls and of the prefill's flash kernels, and how much of a position's
row in the latent pool the model needs. Bytes and operations come from
the cell's architecture, which takes shapes from the configuration's
file only; what was visited and walked comes from the program's counters
(`SlotDecoder.stats()`: `moe_expert_visits`, `kv_pages_walked`, `ticks`,
`kv_latent_row_bytes`), not from an assumption.

What a counter grew by in the window is laid on the traced stretch BY
THE TICKS AND PREFILLS THE STRETCH HELD (the step programs' modules in
the trace), not by its seconds: a stretch of 3 s holds 50 to 90 ticks
and 5 to 15 prefills as its luck has it, and a share of the window's
growth by time read 115% of a roofline in a stretch that held 14
prefills (PR 35's first traced run). A program without the counters or
the kernels (a commit before they existed) reads as None, never as an
error; a share is None, never 0, where it finds nothing."""

import statistics

from benchmarks.lib import opcount
from benchmarks.metrics.blockdiff import _op_seconds
from benchmarks.metrics.device import _modules, needs
from benchmarks.metrics.spans import _delta


def _ticks(ctx, single, fused, fuse):
    """(the ticks the traced stretch held, their device seconds)."""
    one, many = _modules(ctx, single), _modules(ctx, fused)
    return len(one) + fuse * len(many), sum(one) + sum(many)


def _a_tick(ctx, key):
    """A counter's growth over the window, a tick."""
    grown, ticks = _delta(ctx, key), _delta(ctx, "ticks")
    if grown is None or not ticks:
        return None
    return grown / ticks


def _positions_a_tick(ctx):
    """The positions in the pages a tick walks: one walk of a slot's
    table row is every layer's."""
    pages = _a_tick(ctx, "kv_pages_walked")
    if pages is None:
        return None
    return pages * ctx["cell"].config["serve"]["kv_page_size"]


@needs("weight_bytes", "expert_bytes", "latent_bytes", "forward_flops")
def decode_roofline(ctx, single, fused, fuse):
    """The least time the traced ticks could take over the time they
    took. Bytes: every layer's part outside its routed experts and the
    head once a tick, the experts that were visited (the counter, not 12
    a layer), the walked positions' latents in every layer (what the
    model holds of a position, not the pool's wider row). Operations:
    the window's finished requests' decode tokens in the absorbed form,
    a tick."""
    ticks, seconds = _ticks(ctx, single, fused, fuse)
    visits, positions = (_a_tick(ctx, "moe_expert_visits"),
                         _positions_a_tick(ctx))
    if (not ticks or not ctx["requests"] or visits is None
            or positions is None):
        return None
    a, d = ctx["cell"].arch, ctx["cell"].dims
    pk = opcount.peaks(ctx["device_kind"])
    flops = sum(
        a.forward_flops(d, r["prompt"], r["prompt"] + r["out"] - 1,
                        r["out"] - 1) for r in ctx["requests"]
    ) / _delta(ctx, "ticks")
    nbytes = (a.weight_bytes(d, 2, 0) + visits * a.expert_bytes(d)
              + positions * d.layers * a.latent_bytes(d))
    least = max(nbytes / pk["hbm_bytes_per_s"], flops / pk["bf16_flops"])
    return 100.0 * ticks * least / seconds


@needs("latent_bytes", "latent_attention_flops")
def latent_attention_roofline(ctx, ops, single, fused, fuse):
    """The larger of the walked latents' bytes over the bandwidth and the
    absorbed form's operations over them over the peak, over the device
    time of the latent attention kernel (every layer's calls)."""
    ticks, _ = _ticks(ctx, single, fused, fuse)
    positions, t = _positions_a_tick(ctx), _op_seconds(ctx, ops)
    if not ticks or not positions or t <= 0:
        return None
    a, d = ctx["cell"].arch, ctx["cell"].dims
    pk = opcount.peaks(ctx["device_kind"])
    least = ticks * d.layers * max(
        positions * a.latent_bytes(d) / pk["hbm_bytes_per_s"],
        a.latent_attention_flops(d, positions) / pk["bf16_flops"])
    return 100.0 * least / t


@needs("expert_bytes")
def expert_roofline(ctx, ops, single, fused, fuse):
    """The visited experts' bytes over the bandwidth, over the device
    time of the ticks' grouped matmuls: those of `ops` whose rows are a
    tick's routed pairs (slots x experts a token), which leaves out the
    prefills', whose rows are a rung's."""
    a, d = ctx["cell"].arch, ctx["cell"].dims
    ticks, _ = _ticks(ctx, single, fused, fuse)
    visits = _a_tick(ctx, "moe_expert_visits")
    rows = ctx["slots"] * d.top_k
    t = _op_seconds(ctx, rf"^%?{ops}[\w.\-]* = \w+\[{rows},")
    if not ticks or not visits or t <= 0:
        return None
    least = ticks * visits * a.expert_bytes(d) / opcount.peaks(
        ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least / t


@needs("flash_flops")
def prefill_flash_roofline(ctx, ops, module):
    """The operations a prompt's own attention needs (the mean over the
    window's finished requests' real prompts, causal, keys of 192 and
    values of 128, every layer) times the prefills the stretch held, over
    the peak, over the device time of their flash kernels."""
    prefills, t = len(_modules(ctx, module)), _op_seconds(ctx, ops)
    if not prefills or t <= 0 or not ctx["requests"]:
        return None
    a, d = ctx["cell"].arch, ctx["cell"].dims
    need = prefills * statistics.mean(
        a.flash_flops(d, [r["prompt"]]) for r in ctx["requests"])
    return 100.0 * need / opcount.peaks(ctx["device_kind"])["bf16_flops"] / t


@needs("latent_bytes")
def latent_page_fill(ctx):
    """What a position's row in a latent layer's pool holds of what it
    takes: the latent and the key's rotated part over the row in whole
    lane tiles (`kv_latent_row_bytes`), in %."""
    row = (ctx.get("stats1") or {}).get("kv_latent_row_bytes")
    if not row:
        return None
    return 100.0 * ctx["cell"].arch.latent_bytes(ctx["cell"].dims) / row
