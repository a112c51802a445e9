"""Readers of counts: compilations, the scheduler's and the allocator's
host-truth counters, the load generator's own lateness."""

from benchmarks.lib import harness


def compiles(ctx):
    return ctx["compiles"]


def prefill_tokens_per_request(ctx):
    a, b = ctx["stats0"], ctx["stats1"]
    admitted = b["admitted"] - a["admitted"]
    if admitted <= 0:
        return None
    return (b["prefill_tokens_computed"]
            - a["prefill_tokens_computed"]) / admitted


def occupancy(ctx):
    if not ctx["samples"]:
        return None
    return 100.0 * sum(s[1] for s in ctx["samples"]) / (
        len(ctx["samples"]) * ctx["slots"])


def pages_used_share(ctx):
    total = ctx["stats1"].get("kv_pages_total")
    if not ctx["samples"] or not total:
        return None
    return 100.0 * sum(s[2] for s in ctx["samples"]) / (
        len(ctx["samples"]) * total)


def client_late_p90_ms(ctx):
    late = [r["late_s"] for r in ctx["requests"]]
    return 1e3 * harness.percentile(late, 90) if late else None


def step_ms_p50(ctx):
    gaps = ctx["step_gaps_s"]
    return 1e3 * harness.percentile(gaps, 50) if gaps else None
