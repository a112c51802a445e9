"""Readers of what the decoder loop writes down of each round and of the
profiled stretch (`SlotDecoder.stats()`: `tokens_decoded`, `ticks`,
`rounds.<class>`, `round_s.<class>`; the `serve.profiled` span the loop
records from a profiler session's rising edge to its falling one, whose
attributes are what every counter grew by in between; the `serve.request`
spans' `queue_wait_s` and `prompt_tokens`), and of the trainer's
`train.step` spans' own extent.

The readers of the stretch lay the span on the device trace: the span
says what the program dispatched while the session was open (ticks,
admissions, computed positions, walked pages, visited experts, decoded
tokens), the trace what the device took for it. The two can differ by the
dispatch in flight at either end. A tick's counters are scaled by the
ticks the trace holds over the span's. The prefills' are not scaled by
their number: on the chip the trace's extra prefill is a part of one,
cut by the session's start (23 to 166 ms of rungs of 68 to 335 ms in PR
37's runs), as the span's last is cut by its end, and the two parts
make up for each other in the sums where a count would add a whole
prefill's positions for a part of its time. Where the two differ by more
than MAX_PREFILLS_OFF prefills or MAX_TICKS_OFF ticks the bridge has
failed, and every reader of the stretch returns None: a share laid on it
would lie. A program without the counters or the span (a commit before
they existed, a run without `--trace 1`) reads as None, never as 0 and
never as an error."""

import re
import types

from benchmarks.lib import opcount
from benchmarks.metrics.blockdiff import _op_seconds
from benchmarks.metrics.device import _modules, needs
from benchmarks.metrics.spans import _delta

MAX_PREFILLS_OFF = 2
MAX_TICKS_OFF = 16      # two fused dispatches


# -- the whole window, from the counters ---------------------------------------


def per_second(ctx, count):
    """A counter's growth over the window, a second."""
    grown = _delta(ctx, count)
    if not grown or not ctx.get("window_s"):
        return None
    return grown / ctx["window_s"]


def prefill_stall_ms(ctx, top=False):
    """What an admission adds to the round every slot waits on: over the
    ladder's rungs (`top`: the longest alone), the seconds of the rounds
    that held one admission at the rung and one tick, less as many plain
    rounds' mean, over those rounds."""
    plain_n, plain_s = _delta(ctx, "rounds.plain"), _delta(ctx, "round_s.plain")
    rungs = sorted(int(m.group(1)) for m in (
        re.fullmatch(r"rounds\.rung(\d+)", k) for k in ctx.get("stats1") or {})
        if m)
    if not plain_n or plain_s is None or not rungs:
        return None
    n = s = 0
    for rung in rungs[-1:] if top else rungs:
        n += _delta(ctx, f"rounds.rung{rung}") or 0
        s += _delta(ctx, f"round_s.rung{rung}") or 0.0
    if not n:
        return None
    return 1e3 * (s - n * plain_s / plain_n) / n


# -- the profiled stretch: the span laid on the trace --------------------------


def _spans(name):
    try:
        from kubeflow_tpu.obs.trace import COLLECTOR
    except ImportError:
        return []
    return [s for s in COLLECTOR.spans() if s.name == name]


def _stretch(ctx, single, fused, fuse, module):
    """The session's `serve.profiled` span beside the trace's step
    programs: what the counters grew by (`grew`), the trace's ticks and
    their seconds, its prefills' seconds, and the real prompts of the
    requests admitted inside the span. None where either is missing or
    the two disagree."""
    found = _spans("serve.profiled")
    if ctx.get("trace") is None or not found:
        return None
    span = found[-1]
    one, many, prefill = (_modules(ctx, p) for p in (single, fused, module))
    ticks, grew = len(one) + fuse * len(many), span.attrs
    if ("ticks" not in grew or "admitted" not in grew
            or abs(len(prefill) - grew["admitted"]) > MAX_PREFILLS_OFF
            or abs(ticks - grew["ticks"]) > MAX_TICKS_OFF):
        return None
    return types.SimpleNamespace(
        grew=grew, ticks=ticks, tick_s=sum(one) + sum(many),
        prefill_s=sum(prefill), prompts=[
            r.attrs["prompt_tokens"] for r in _spans("serve.request")
            if r.attrs.get("queue_wait_s") is not None
            and span.start <= r.start + r.attrs["queue_wait_s"] <= span.end])


def _by_ticks(st, key):
    """A counter's growth inside the span, as the trace's ticks."""
    if not st.grew["ticks"] or key not in st.grew:
        return None
    return st.grew[key] * st.ticks / st.grew["ticks"]


def prefill_ms_per_ktok(ctx, single, fused, fuse, module):
    """The stretch's prefills' device milliseconds, summed, per 1,000
    positions the span's admissions computed: a sum over a sum, so the
    stretch's mix of rungs moves it by the attention's quadratic part
    only."""
    st = _stretch(ctx, single, fused, fuse, module)
    computed = st and st.grew.get("prefill_tokens_computed")
    if not computed or st.prefill_s <= 0:
        return None
    return 1e6 * st.prefill_s / computed


@needs("forward_flops")
def prefill_roofline_held(ctx, single, fused, fuse, module):
    """The operations the real prompts of the requests admitted inside
    the span need, over the bf16 peak, over the stretch's prefills'
    device time, summed."""
    st = _stretch(ctx, single, fused, fuse, module)
    if st is None or st.prefill_s <= 0 or not st.prompts:
        return None
    a, d = ctx["cell"].arch, ctx["cell"].dims
    need = sum(a.forward_flops(d, 0, n, 1) for n in st.prompts)
    peak = opcount.peaks(ctx["device_kind"])["bf16_flops"]
    return 100.0 * need / peak / st.prefill_s


def _walked_bytes(ctx, st):
    """benchmarks/metrics/longctx.py's, of the span's walk: the held
    kind's table is read by the full layers, the window kind's by the
    sliding ones."""
    a, d = ctx["cell"].arch, ctx["cell"].dims
    walked = _by_ticks(st, "kv_pages_walked")
    window = _by_ticks(st, "kv_pages_walked_window")
    if walked is None or window is None:
        return None
    page_size = ctx["cell"].config["serve"]["kv_page_size"]
    sliding = sum(d.sliding)
    return ((walked - window) * a.kv_page_bytes(d, page_size,
                                                d.layers - sliding)
            + window * a.kv_page_bytes(d, page_size, sliding))


@needs("weight_bytes", "expert_bytes", "kv_page_bytes", "forward_flops")
def decode_roofline_held(ctx, single, fused, fuse, module):
    """longctx.py's `decode_roofline` of the stretch alone: the visited
    experts and the walked pages are the span's, as the trace's ticks;
    the operations are the span's decoded tokens', each at the mean of
    the window's finished requests' decode tokens."""
    st = _stretch(ctx, single, fused, fuse, module)
    if st is None or not st.ticks or not ctx["requests"]:
        return None
    visits, pages = _by_ticks(st, "moe_expert_visits"), _walked_bytes(ctx, st)
    decoded = _by_ticks(st, "tokens_decoded")
    tokens = sum(r["out"] - 1 for r in ctx["requests"])
    if visits is None or pages is None or decoded is None or tokens <= 0:
        return None
    a, d = ctx["cell"].arch, ctx["cell"].dims
    pk = opcount.peaks(ctx["device_kind"])
    flops = decoded / tokens * sum(
        a.forward_flops(d, r["prompt"], r["prompt"] + r["out"] - 1,
                        r["out"] - 1) for r in ctx["requests"])
    nbytes = (st.ticks * a.weight_bytes(d, 2, 0)
              + visits * a.expert_bytes(d) + pages)
    least = max(nbytes / pk["hbm_bytes_per_s"], flops / pk["bf16_flops"])
    return 100.0 * least / st.tick_s


@needs("expert_bytes")
def expert_roofline_held(ctx, ops, single, fused, fuse, module):
    """longctx.py's `expert_roofline` with the span's visits."""
    st = _stretch(ctx, single, fused, fuse, module)
    if st is None:
        return None
    a, d = ctx["cell"].arch, ctx["cell"].dims
    visits = _by_ticks(st, "moe_expert_visits")
    rows = ctx["slots"] * d.top_k
    t = _op_seconds(ctx, rf"^%?{ops}[\w.\-]* = \w+\[{rows},")
    if not visits or t <= 0:
        return None
    least = visits * a.expert_bytes(d) / opcount.peaks(
        ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least / t


@needs("kv_page_bytes")
def paged_attention_roofline_held(ctx, ops, single, fused, fuse, module):
    """longctx.py's `paged_attention_roofline` with the span's walk."""
    st = _stretch(ctx, single, fused, fuse, module)
    if st is None:
        return None
    pages, t = _walked_bytes(ctx, st), _op_seconds(ctx, ops)
    if not pages or t <= 0:
        return None
    return 100.0 * pages / opcount.peaks(
        ctx["device_kind"])["hbm_bytes_per_s"] / t


@needs("flash_flops")
def prefill_flash_roofline_held(ctx, ops, single, fused, fuse, module):
    """longctx.py's `prefill_flash_roofline` with the prompts of the
    requests admitted inside the span."""
    st = _stretch(ctx, single, fused, fuse, module)
    t = _op_seconds(ctx, ops)
    if st is None or t <= 0 or not st.prompts:
        return None
    a, d = ctx["cell"].arch, ctx["cell"].dims
    need = a.flash_flops(d, st.prompts)
    return 100.0 * need / opcount.peaks(ctx["device_kind"])["bf16_flops"] / t


# -- the trainer's own clock ----------------------------------------------------


def train_step_span_ms(ctx):
    """Mean extent (dispatch to `block_until_ready`) of the window's
    train.step spans: the last `steps` of them that are no compile
    step."""
    steps = ctx.get("steps") or 0
    spans = [s for s in _spans("train.step")
             if not s.attrs.get("compile") and s.end is not None]
    if steps <= 0 or not spans:
        return None
    spans = spans[-steps:]
    return 1e3 * sum(s.duration for s in spans) / len(spans)
