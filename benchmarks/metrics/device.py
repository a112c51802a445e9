"""Readers of the device trace: module times, the whole step's share of
the chip's peak, and the kernels' shares of their rooflines. Operations
and bytes come from the cell's architecture (`ctx["cell"].arch`, given
its own sizes `ctx["cell"].dims`), the peaks from benchmarks/lib/opcount.py,
times from the trace."""

import re
import statistics

from benchmarks.lib import opcount


def needs(*counts):
    """Marks a reader with the counts it asks the architecture for, so
    that a metric listed for a cell whose architecture lacks one fails a
    test (test_every_listed_metric_has_a_reader_and_its_cells) and never
    reads 0."""
    def mark(reader):
        reader.counts = counts
        return reader
    return mark


def _modules(ctx, pattern):
    if ctx["trace"] is None:
        return []
    rx = re.compile(pattern)
    return [t for name, ts in ctx["trace"]["module_s"].items()
            if rx.search(name) for t in ts]


def module_ms_p50(ctx, module):
    ts = _modules(ctx, module)
    return 1e3 * statistics.median(ts) if ts else None


def tick_ms(ctx, single, fused, fuse):
    """Device time of a decode tick: both modules' time over the ticks
    they hold."""
    one, many = _modules(ctx, single), _modules(ctx, fused)
    ticks = len(one) + fuse * len(many)
    return 1e3 * (sum(one) + sum(many)) / ticks if ticks else None


@needs("request_flops")
def serve_mfu(ctx):
    """Operations the real tokens of the window's finished requests need,
    forward only, over the window at the chip's bf16 peak."""
    a, d = ctx["cell"].arch, ctx["cell"].dims
    need = sum(a.request_flops(d, r["prompt"], r["out"])
               for r in ctx["requests"])
    peak = opcount.peaks(ctx["device_kind"])["bf16_flops"]
    return 100.0 * need / (ctx["window_s"] * ctx["chips"] * peak)


@needs("train_flops_per_token")
def train_mfu(ctx):
    a, d = ctx["cell"].arch, ctx["cell"].dims
    need = (a.train_flops_per_token(d, ctx["seq_len"])
            * ctx["steps"] * ctx["tokens_per_step"])
    peak = opcount.peaks(ctx["device_kind"])["bf16_flops"]
    return 100.0 * need / (ctx["window_s"] * ctx["chips"] * peak)


@needs("forward_flops")
def prefill_roofline(ctx, module):
    """Mean operations a finished request's real prompt needs, over the
    bf16 peak, over the median prefill's device time."""
    ts = _modules(ctx, module)
    if not ts or not ctx["requests"]:
        return None
    a, d = ctx["cell"].arch, ctx["cell"].dims
    need = statistics.mean(a.forward_flops(d, 0, r["prompt"], 1)
                           for r in ctx["requests"])
    peak = opcount.peaks(ctx["device_kind"])["bf16_flops"]
    return 100.0 * need / peak / statistics.median(ts)


@needs("decode_kv_bytes", "forward_flops", "weight_bytes")
def decode_roofline(ctx, single, fused, fuse, weight_bytes):
    """The least time the traced ticks could take (the larger of their
    operations over the peak and their bytes over the bandwidth: the
    weights once a tick, the cache of the live positions) over the time
    they took. The live positions are the window's mean."""
    one, many = _modules(ctx, single), _modules(ctx, fused)
    ticks = len(one) + fuse * len(many)
    if not ticks or not ctx["requests"]:
        return None
    a, d = ctx["cell"].arch, ctx["cell"].dims
    pk = opcount.peaks(ctx["device_kind"])
    share = ctx["trace"]["window_s"] / ctx["window_s"]
    kv = share * sum(a.decode_kv_bytes(d, r["prompt"], r["out"])
                     for r in ctx["requests"])
    flops = share * sum(
        a.forward_flops(d, r["prompt"], r["prompt"] + r["out"] - 1,
                        r["out"] - 1) for r in ctx["requests"])
    least = max((ticks * a.weight_bytes(d, weight_bytes) + kv)
                / pk["hbm_bytes_per_s"], flops / pk["bf16_flops"])
    return 100.0 * least / (sum(one) + sum(many))


@needs("flash_flops", "flash_bytes")
def flash_roofline(ctx, ops):
    """The attention kernels of the traced train steps (forward, dq,
    dk/dv), found by name among the device operations."""
    if ctx["trace"] is None:
        return None
    rx = re.compile(ops)
    t = sum(s for name, s in ctx["trace"]["op_s"].items() if rx.search(name))
    if t <= 0:
        return None
    a, d = ctx["cell"].arch, ctx["cell"].dims
    pk = opcount.peaks(ctx["device_kind"])
    per_chip = ctx["trace_steps"] / ctx["chips"]
    least = per_chip * max(
        a.flash_flops(d, ctx["batch"], ctx["seq_len"]) / pk["bf16_flops"],
        a.flash_bytes(d, ctx["batch"], ctx["seq_len"])
        / pk["hbm_bytes_per_s"])
    return 100.0 * least / t
