"""The readers of what the decoder loop writes down of each round and of
the profiled stretch (benchmarks/metrics/stretch.py), on hand-made `ctx`
and spans: a sound reading each; None (never 0, never an error) on a
program that has no such counter or span, as the commit before they
existed; None where the span and the trace disagree by more than the
dispatches in flight at the stretch's ends."""

import pytest

from benchmarks.lib import opcount, readers, spec
from benchmarks.metrics import stretch

STEP = {"single": "^jit__tick$", "fused": "^jit__step_fused$", "fuse": 8,
        "module": "^jit__paged_prefill_install$"}
NEW = sorted(
    [f"sched.decoded_tok_per_s.{c}"
     for c in ("chat", "blockdiff", "longctx", "latent")]
    + [f"sched.prefill_stall_ms.{c}" for c in ("chat", "longctx", "latent")]
    + [f"sched.prefill_stall_top_ms.{c}" for c in ("longctx", "latent")]
    + [f"{m}.{c}" for m in ("model.prefill_ms_per_ktok",
                            "prefill_roofline_held")
       for c in ("doc", "longctx", "latent")]
    + ["decode_roofline_held.longctx", "moe.expert_roofline_held.longctx",
       "paged_attention_roofline_held.longctx",
       "prefill_flash_roofline_held.longctx", "trainer.step_span_ms.train"])
HELD = {"decode_roofline_held.longctx": stretch.decode_roofline_held,
        "moe.expert_roofline_held.longctx": stretch.expert_roofline_held,
        "paged_attention_roofline_held.longctx":
            stretch.paged_attention_roofline_held,
        "prefill_flash_roofline_held.longctx":
            stretch.prefill_flash_roofline_held,
        "prefill_roofline_held.longctx": stretch.prefill_roofline_held,
        "model.prefill_ms_per_ktok.longctx": stretch.prefill_ms_per_ktok}
RUNGS = (4096, 8192, 12288, 16384)
PROMPTS = (3000, 7000, 11000, 15000)
# what the loop counted in a stretch of 100 single ticks and 5 fused
# rounds over 32 slots behind 4 admissions, one at each rung
GREW = {"ticks": 140, "admitted": 4, "rounds": 105,
        "prefill_tokens_computed": sum(RUNGS), "tokens_decoded": 140 * 31,
        "moe_expert_visits": 140 * 48, "kv_pages_walked": 140 * 20000,
        "kv_pages_walked_window": 140 * 6000}


@pytest.fixture
def collector():
    from kubeflow_tpu.obs.trace import COLLECTOR

    COLLECTOR.clear()
    yield COLLECTOR
    COLLECTOR.clear()


@pytest.fixture(scope="module")
def cell():
    return spec.cell("longctx-saturated")


def trace(ticks=100, fused=5, prefills=(0.07, 0.15, 0.24, 0.33)):
    rows = 32 * 4
    return {"window_s": 3.0, "busy_s": 2.8, "module_s": {
        "jit__tick": [0.0092] * ticks, "jit__step_fused": [0.0736] * fused,
        "jit__paged_prefill_install": list(prefills)}, "op_s": {
        f"%ragged-dot-streamed.3 = bf16[{rows},3072]{{1,0}} custom-call(":
            0.52,
        "%ragged-dot-streamed.9 = bf16[8192,3072]{1,0} custom-call(": 9.0,
        "%paged_decode_attention.2 = bf16[32,48,128]{2,1,0} custom-call(":
            0.5,
        "%local_attention.7 = bf16[48,8192,128]{2,1,0} custom-call(": 0.3}}


def ctx_of(cell, **kw):
    ctx = {"cell": cell, "window_s": 51.0, "trace": trace(),
           "device_kind": "TPU v5 lite", "chips": 1, "slots": 32,
           "requests": [{"prompt": 8000, "out": 400},
                        {"prompt": 2000, "out": 200}],
           "stats0": {}, "stats1": {}}
    ctx.update(kw)
    return ctx


def record_stretch(grew=GREW, prompts=PROMPTS, t0=100.0, t1=103.0):
    from kubeflow_tpu.obs.trace import TRACER

    TRACER.record("serve.profiled", t0, t1, **grew)
    for i, n in enumerate(prompts):      # admitted inside the span
        TRACER.record("serve.request", t0 - 1.0, t1 + 9.0, prompt_tokens=n,
                      queue_wait_s=1.5 + 0.3 * i, outcome="ok")
    # admitted before it, behind it, and never
    TRACER.record("serve.request", t0 - 5.0, t0 + 1.0, prompt_tokens=16000,
                  queue_wait_s=0.5, outcome="ok")
    TRACER.record("serve.request", t1 - 1.0, t1 + 9.0, prompt_tokens=16000,
                  queue_wait_s=2.0, outcome="ok")
    TRACER.record("serve.request", t0, t0 + 1.0, prompt_tokens=16000,
                  queue_wait_s=None, outcome="canceled")


def test_decoded_tokens_a_second_is_a_counters_growth_over_the_window():
    ctx = {"window_s": 50.0, "stats0": {"tokens_decoded": 1000},
           "stats1": {"tokens_decoded": 61000}}
    assert stretch.per_second(ctx, "tokens_decoded") == pytest.approx(1200.0)
    for parent in ({"window_s": 50.0, "stats0": {}, "stats1": {}},
                   dict(ctx, stats1={"tokens_decoded": 1000}), {}):
        assert stretch.per_second(parent, "tokens_decoded") is None


def rounds(plain, *rungs):
    out = {"rounds.plain": plain[0], "round_s.plain": plain[1],
           "rounds.fused": 7, "round_s.fused": 0.5,
           "rounds.other": 1, "round_s.other": 0.9}
    for n, (count, seconds) in zip(RUNGS, rungs):
        out[f"rounds.rung{n}"], out[f"round_s.rung{n}"] = count, seconds
    return out


def test_the_stall_is_a_rungs_round_less_a_plain_one():
    ctx = {"stats0": rounds((100, 1.0), (1, 0.08), (0, 0.0), (2, 0.5),
                            (1, 0.35)),
           "stats1": rounds((1100, 11.0), (51, 4.08), (25, 3.75), (17, 4.25),
                            (11, 3.85))}
    # plain 10 ms; rungs of 80 / 150 / 250 / 350 ms at 50 / 25 / 15 / 10
    assert stretch.prefill_stall_ms(ctx) == pytest.approx(
        (50 * 70 + 25 * 140 + 15 * 240 + 10 * 340) / 100)
    assert stretch.prefill_stall_ms(ctx, top=True) == pytest.approx(340.0)


@pytest.mark.parametrize("ctx", [
    {"stats0": {"rounds": 1}, "stats1": {"rounds": 9}},     # the parent
    {"stats0": rounds((5, 1.0), (1, 0.1)),                  # no plain round
     "stats1": rounds((5, 1.0), (3, 0.3))},
    {"stats0": rounds((5, 1.0), (1, 0.1)),                  # no admission
     "stats1": rounds((9, 2.0), (1, 0.1))},
    {"stats0": {"rounds.plain": 0, "round_s.plain": 0.0},   # a dense decoder
     "stats1": {"rounds.plain": 9, "round_s.plain": 1.0}},
    {},
], ids=["no-such-keys", "no-plain-round", "no-admission", "no-ladder",
        "empty"])
def test_the_stall_reads_none_where_there_is_nothing_to_read(ctx):
    assert stretch.prefill_stall_ms(ctx) is None
    assert stretch.prefill_stall_ms(ctx, top=True) is None


def test_a_sound_stretch_reads_every_metric(cell, collector):
    record_stretch()
    ctx = ctx_of(cell)
    a, d = cell.arch, cell.dims
    pk = opcount.peaks("TPU v5 lite")
    # a sum over a sum: 0.79 s over 40,960 computed positions
    assert stretch.prefill_ms_per_ktok(ctx, **STEP) == pytest.approx(
        1e6 * 0.79 / sum(RUNGS))
    assert stretch.prefill_roofline_held(ctx, **STEP) == pytest.approx(
        100 * sum(a.forward_flops(d, 0, n, 1) for n in PROMPTS)
        / pk["bf16_flops"] / 0.79)
    assert stretch.prefill_flash_roofline_held(
        ctx, ops="local_attention", **STEP) == pytest.approx(
        100 * a.flash_flops(d, list(PROMPTS)) / pk["bf16_flops"] / 0.3)
    visits = GREW["moe_expert_visits"] * a.expert_bytes(d)
    # the tick's grouped matmuls by the tick's 128 rows, not the rung's
    assert stretch.expert_roofline_held(
        ctx, ops="ragged-dot", **STEP) == pytest.approx(
        100 * visits / pk["hbm_bytes_per_s"] / 0.52)
    full = GREW["kv_pages_walked"] - GREW["kv_pages_walked_window"]
    pages = (full * a.kv_page_bytes(d, 16, d.layers - sum(d.sliding))
             + GREW["kv_pages_walked_window"]
             * a.kv_page_bytes(d, 16, sum(d.sliding)))
    assert stretch.paged_attention_roofline_held(
        ctx, ops="paged_decode_attention", **STEP) == pytest.approx(
        100 * pages / pk["hbm_bytes_per_s"] / 0.5)
    tick_s = 100 * 0.0092 + 5 * 0.0736
    nbytes = 140 * a.weight_bytes(d, 2, 0) + visits + pages
    assert stretch.decode_roofline_held(ctx, **STEP) == pytest.approx(
        100 * nbytes / pk["hbm_bytes_per_s"] / tick_s)     # bytes-bound
    for name, reader in HELD.items():
        value = reader(ctx, **spec.metric_files()[name]["args"])
        assert 0 < value <= 100 or "per_ktok" in name, (name, value)


def test_a_dispatch_in_flight_at_an_end_moves_a_reading_by_its_part(
        cell, collector):
    """The trace holds a part of one prefill more than the span (cut by
    the session's start): the prefills' readings take the span's
    positions and prompts as they are and move by that part's seconds
    only, not by a whole prefill's positions. The span holds one fused
    round more than the trace: the ticks' counters are laid on the
    trace's ticks and read what the matching span reads."""
    record_stretch()
    files = spec.metric_files()
    sound = {n: r(ctx_of(cell), **files[n]["args"]) for n, r in HELD.items()}
    ctx = ctx_of(cell, trace=trace(prefills=(0.02, 0.07, 0.15, 0.24, 0.33)))
    for name, part in (("model.prefill_ms_per_ktok.longctx", 0.81 / 0.79),
                       ("prefill_roofline_held.longctx", 0.79 / 0.81)):
        assert HELD[name](ctx, **files[name]["args"]) \
            == pytest.approx(sound[name] * part), name
    collector.clear()
    record_stretch({k: v * 148 / 140 if k in (
        "ticks", "tokens_decoded", "moe_expert_visits", "kv_pages_walked",
        "kv_pages_walked_window") else v for k, v in GREW.items()})
    for name, reader in HELD.items():
        assert reader(ctx_of(cell), **files[name]["args"]) \
            == pytest.approx(sound[name]), name


@pytest.mark.parametrize("grew,tr,reads", [
    (dict(GREW, admitted=7), trace(), False),           # 3 prefills off
    (dict(GREW, admitted=6), trace(), True),
    (GREW, trace(prefills=(0.1,)), False),
    (dict(GREW, ticks=157), trace(), False),            # 17 ticks off
    (dict(GREW, ticks=156), trace(), True),
    (GREW, trace(ticks=83), False),
], ids=["3-prefills-more", "2-prefills-more", "3-prefills-fewer",
        "17-ticks-more", "16-ticks-more", "17-ticks-fewer"])
def test_a_bridge_that_failed_reads_none(cell, collector, grew, tr, reads):
    record_stretch(grew)
    ctx = ctx_of(cell, trace=tr)
    for name, reader in HELD.items():
        value = reader(ctx, **spec.metric_files()[name]["args"])
        assert (value is not None) == reads, (name, value)


def test_no_span_no_trace_or_no_counters_reads_none(cell, collector):
    from kubeflow_tpu.obs.trace import TRACER

    def all_none(ctx):
        return all(r(ctx, **spec.metric_files()[n]["args"]) is None
                   for n, r in HELD.items())

    assert all_none(ctx_of(cell))                       # the parent: no span
    record_stretch()
    assert all_none(ctx_of(cell, trace=None))           # --trace 0
    collector.clear()
    # a span from a program that counts neither ticks nor admissions
    TRACER.record("serve.profiled", 100.0, 103.0, rounds=105)
    assert all_none(ctx_of(cell))
    collector.clear()
    # ticks and admissions, none of the counters a share is made of
    record_stretch({"ticks": 140, "admitted": 4})
    ctx = ctx_of(cell)
    for name, reader in HELD.items():
        value = reader(ctx, **spec.metric_files()[name]["args"])
        assert (value is None) == (name not in (
            "prefill_roofline_held.longctx",
            "prefill_flash_roofline_held.longctx")), name
    collector.clear()
    record_stretch(prompts=())          # no request admitted inside it
    assert stretch.prefill_roofline_held(ctx_of(cell), **STEP) is None
    assert stretch.prefill_flash_roofline_held(
        ctx_of(cell), ops="local_attention", **STEP) is None


def test_the_trainers_step_span_is_the_windows_last_steps(collector):
    from kubeflow_tpu.obs.trace import TRACER

    assert stretch.train_step_span_ms({"steps": 3}) is None
    TRACER.record("train.step", 0.0, 60.0, step=0, compile=True)
    TRACER.record("train.step", 60.0, 69.0, step=1)     # before the window
    for k in range(3):
        TRACER.record("train.step", 70.0 + 2.1 * k, 72.0 + 2.1 * k + 0.01 * k,
                      step=2 + k)
    TRACER.record("serve.request", 0.0, 50.0)
    assert stretch.train_step_span_ms({"steps": 3}) == pytest.approx(2010.0)
    assert stretch.train_step_span_ms({"steps": 0}) is None
    assert stretch.train_step_span_ms({}) is None


def test_the_harness_finds_the_new_metrics_and_leaves_out_what_is_none(
        cell, collector):
    """Through readers.read_all, as a --trace 1 run does: on the parent's
    ctx every new metric of a serving cell is left out of the line, on
    this program's they are in it."""
    bench, files = spec.benchmark(), spec.metric_files()
    declared = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-len(NEW):]] and \
        sorted(m["name"] for m in bench["per_layer"][-len(NEW):]) == NEW
    for name in NEW:
        assert files[name]["reader"].startswith("stretch:")
        for key in ("layer", "unit", "source", "moves", "workloads"):
            assert files[name][key] == declared[name][key], (name, key)
        assert len(declared[name]["workloads"]) == 1
    only_new = dict(bench, per_layer=[declared[n] for n in NEW])
    parent = ctx_of(cell, stats0={"rounds": 1, "admitted": 1},
                    stats1={"rounds": 900, "admitted": 90})
    for name in ("chat-saturated", "doc-qa-paced", "blockdiff-saturated",
                 "longctx-saturated", "latent-saturated"):
        assert readers.read_all(only_new, name, parent) == {}
    record_stretch()
    change = ctx_of(
        cell,
        stats0=dict(rounds((100, 1.0), (1, 0.08), (0, 0.0), (2, 0.5),
                           (1, 0.35)), tokens_decoded=0),
        stats1=dict(rounds((1100, 11.0), (51, 4.08), (25, 3.75), (17, 4.25),
                           (11, 3.85)), tokens_decoded=51 * 1300))
    got = readers.read_all(only_new, "longctx-saturated", change)
    assert sorted(got) == [n for n in NEW if n.endswith(".longctx")]
    assert got["sched.decoded_tok_per_s.longctx"]["value"] \
        == pytest.approx(1300.0)
    assert got["sched.prefill_stall_top_ms.longctx"]["value"] \
        == pytest.approx(340.0)
