"""Readers of what the program measures of itself: the decoder loop's
host phases and per-request waits (`SlotDecoder.stats()`: `phase_s.*`,
`rounds`, `queue_wait_s_sum`, `first_token_s_sum`), the trainer's host
split of a step (attributes of its `train.step` spans in
`kubeflow_tpu.obs.trace.COLLECTOR`), and how much of the device's idle
time the trace can name. A program that has no such counter or span (a
commit before they existed) reads as None, never as an error."""

UNNAMED = ": unattributed"


def _delta(ctx, key):
    """A counter's growth over the window, or None where either snapshot
    lacks it."""
    a, b = ctx.get("stats0") or {}, ctx.get("stats1") or {}
    if key not in a or key not in b:
        return None
    return b[key] - a[key]


def per_event_ms(ctx, seconds, count):
    """Mean milliseconds an event: the growth of the `seconds` counters,
    summed, over the growth of the `count` counter."""
    total = [_delta(ctx, k) for k in seconds]
    n = _delta(ctx, count)
    if n is None or n <= 0 or any(t is None for t in total):
        return None
    return 1e3 * sum(total) / n


def idle_named_share(ctx):
    """Of the seconds in the reduction's longest idle gaps, the share
    whose label names what the host was doing, in %."""
    gaps = (ctx.get("trace") or {}).get("idle_gaps") or []
    total = sum(s for _, s in gaps)
    if total <= 0:
        return None
    named = sum(s for label, s in gaps if not label.endswith(UNNAMED))
    return 100.0 * named / total


def train_step_attr_ms(ctx, attr):
    """Mean of one attribute (seconds) over the window's train.step
    spans: the last `steps` of them that are no compile step."""
    try:
        from kubeflow_tpu.obs.trace import COLLECTOR
    except ImportError:
        return None
    steps = ctx.get("steps") or 0
    spans = [s for s in COLLECTOR.spans()
             if s.name == "train.step" and not s.attrs.get("compile")]
    values = [s.attrs[attr] for s in spans[-steps:] if attr in s.attrs]
    if steps <= 0 or not values:
        return None
    return 1e3 * sum(values) / len(values)
