"""Readers of the device trace: module times, the whole step's share of
the chip's peak, and the kernels' shares of their rooflines. Operations
and bytes come from benchmarks/lib/opcount.py, times from the trace."""

import re
import statistics

from benchmarks.lib import opcount


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


def serve_mfu(ctx):
    """Operations the real tokens of the window's finished requests need,
    forward only, over the window at the chip's bf16 peak."""
    d = ctx["cell"].dims
    need = sum(opcount.request_flops(d, r["prompt"], r["out"])
               for r in ctx["requests"])
    peak = opcount.peaks(ctx["device_kind"])["bf16_flops"]
    return 100.0 * need / (ctx["window_s"] * ctx["chips"] * peak)


def train_mfu(ctx):
    d = ctx["cell"].dims
    need = (opcount.train_flops_per_token(d, ctx["seq_len"])
            * ctx["steps"] * ctx["tokens_per_step"])
    peak = opcount.peaks(ctx["device_kind"])["bf16_flops"]
    return 100.0 * need / (ctx["window_s"] * ctx["chips"] * peak)


def prefill_roofline(ctx, module):
    """Mean operations a finished request's real prompt needs, over the
    bf16 peak, over the median prefill's device time."""
    ts = _modules(ctx, module)
    if not ts or not ctx["requests"]:
        return None
    d = ctx["cell"].dims
    need = statistics.mean(opcount.forward_flops(d, 0, r["prompt"], 1)
                           for r in ctx["requests"])
    peak = opcount.peaks(ctx["device_kind"])["bf16_flops"]
    return 100.0 * need / peak / statistics.median(ts)


def decode_roofline(ctx, single, fused, fuse, weight_bytes):
    """The least time the traced ticks could take (the larger of their
    operations over the peak and their bytes over the bandwidth: the
    weights once a tick, the cache of the live positions) over the time
    they took. The live positions are the window's mean."""
    one, many = _modules(ctx, single), _modules(ctx, fused)
    ticks = len(one) + fuse * len(many)
    if not ticks or not ctx["requests"]:
        return None
    d, pk = ctx["cell"].dims, opcount.peaks(ctx["device_kind"])
    share = ctx["trace"]["window_s"] / ctx["window_s"]
    kv = share * sum(opcount.decode_kv_bytes(d, r["prompt"], r["out"])
                     for r in ctx["requests"])
    flops = share * sum(
        opcount.forward_flops(d, r["prompt"], r["prompt"] + r["out"] - 1,
                              r["out"] - 1) for r in ctx["requests"])
    least = max((ticks * opcount.weight_bytes(d, weight_bytes) + kv)
                / pk["hbm_bytes_per_s"], flops / pk["bf16_flops"])
    return 100.0 * least / (sum(one) + sum(many))


def flash_roofline(ctx, ops):
    """The attention kernels of the traced train steps (forward, dq,
    dk/dv), found by name among the device operations."""
    if ctx["trace"] is None:
        return None
    rx = re.compile(ops)
    t = sum(s for name, s in ctx["trace"]["op_s"].items() if rx.search(name))
    if t <= 0:
        return None
    d, pk = ctx["cell"].dims, opcount.peaks(ctx["device_kind"])
    per_chip = ctx["trace_steps"] / ctx["chips"]
    least = per_chip * max(
        opcount.flash_flops(d, ctx["batch"], ctx["seq_len"]) / pk["bf16_flops"],
        opcount.flash_bytes(d, ctx["batch"], ctx["seq_len"])
        / pk["hbm_bytes_per_s"])
    return 100.0 * least / t
