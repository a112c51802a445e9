"""The readers of the program's own spans and counters, on hand-made
`ctx`: a reading each, and None (never an error) on a program that has
no such counter or span, as the commit before they existed."""

import pytest

from benchmarks.lib import readers, spec
from benchmarks.metrics import spans

HOST = ["phase_s.admit", "phase_s.prefill", "phase_s.pages", "phase_s.tick",
        "phase_s.complete"]
NEW = ["sched.queue_wait_ms.doc", "sched.first_token_ms.doc",
       "sched.host_ms_per_round.chat", "sched.host_ms_per_round.doc",
       "device.idle_named_share.chat", "device.idle_named_share.doc",
       "trainer.data_wait_ms.train", "trainer.dispatch_ms.train"]


def stats(admitted, rounds, scale):
    out = {"admitted": admitted, "first_tokens": admitted, "rounds": rounds,
           "queue_wait_s_sum": 0.1 * admitted * scale,
           "first_token_s_sum": 0.3 * admitted * scale,
           "phase_s.readback": 99.0, "phase_s.idle": 99.0}
    out.update({k: 0.0005 * rounds * scale for k in HOST})
    return out


@pytest.fixture
def collector():
    from kubeflow_tpu.obs.trace import COLLECTOR

    COLLECTOR.clear()
    yield COLLECTOR
    COLLECTOR.clear()


def test_waits_and_host_time_are_deltas_over_the_window():
    ctx = {"stats0": stats(10, 100, 1.0), "stats1": stats(30, 500, 1.0)}
    assert spans.per_event_ms(ctx, ["queue_wait_s_sum"], "admitted") \
        == pytest.approx(100.0)
    assert spans.per_event_ms(ctx, ["first_token_s_sum"], "first_tokens") \
        == pytest.approx(300.0)
    # five phases of 0.5 ms a round; the blocked and idle ones stay out
    assert spans.per_event_ms(ctx, HOST, "rounds") == pytest.approx(2.5)


@pytest.mark.parametrize("ctx", [
    {"stats0": {"admitted": 1}, "stats1": {"admitted": 9}},    # the parent
    {"stats0": stats(10, 100, 1.0), "stats1": stats(10, 100, 1.0)},
    {},
], ids=["no-such-keys", "nothing-happened", "empty"])
def test_counters_missing_or_still_read_none(ctx):
    assert spans.per_event_ms(ctx, ["queue_wait_s_sum"], "admitted") is None
    assert spans.per_event_ms(ctx, HOST, "rounds") is None


def test_idle_named_share_counts_seconds_not_gaps():
    gaps = [["jit__tick - jit__tick: kftpu.sched.admit", 0.06],
            ["jit__tick - jit__tick: unattributed", 0.03],
            ["jit__tick - jit__tick: np.asarray(jax.Array)", 0.01]]
    assert spans.idle_named_share({"trace": {"idle_gaps": gaps}}) \
        == pytest.approx(70.0)
    assert spans.idle_named_share({"trace": {"idle_gaps": []}}) is None
    assert spans.idle_named_share({"trace": None}) is None


def test_train_step_attrs_are_the_windows_last_steps(collector):
    from kubeflow_tpu.obs.trace import TRACER

    TRACER.record("train.step", 0.0, 1.0, step=0, compile=True,
                  data_wait_s=9.0, dispatch_s=9.0)
    TRACER.record("train.step", 1.0, 2.0, step=1, data_wait_s=5.0,
                  dispatch_s=5.0)                     # before the window
    for k in range(3):
        TRACER.record("train.step", 2.0 + k, 3.0 + k, step=2 + k,
                      data_wait_s=0.001 * (k + 1), dispatch_s=0.02)
    TRACER.record("serve.request", 0.0, 1.0, data_wait_s=7.0)
    ctx = {"steps": 3}
    assert spans.train_step_attr_ms(ctx, "data_wait_s") == pytest.approx(2.0)
    assert spans.train_step_attr_ms(ctx, "dispatch_s") == pytest.approx(20.0)


def test_train_step_attrs_read_none_without_spans_or_attrs(collector):
    from kubeflow_tpu.obs.trace import TRACER

    assert spans.train_step_attr_ms({"steps": 3}, "data_wait_s") is None
    TRACER.record("train.step", 0.0, 1.0, step=1, step_time_s=1.0)  # parent's
    assert spans.train_step_attr_ms({"steps": 3}, "data_wait_s") is None
    assert spans.train_step_attr_ms({"steps": 0}, "data_wait_s") is None


def test_the_harness_finds_the_new_metrics_and_leaves_out_what_is_none(
        collector):
    """Through readers.read_all, as a --trace 1 run does: on the parent's
    ctx the new metrics are left out of the line, on this program's they
    are in it."""
    bench, files = spec.benchmark(), spec.metric_files()
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert name in files and name in declared
        assert files[name]["reader"].startswith("spans:")
        for key in ("layer", "unit", "source", "moves", "workloads"):
            assert files[name][key] == declared[name][key], (name, key)
    only_new = dict(bench, per_layer=[declared[n] for n in NEW])
    parent = {"stats0": {"admitted": 1}, "stats1": {"admitted": 9},
              "trace": {"idle_gaps": []}, "steps": 5}
    for cell in ("chat-saturated", "doc-qa-paced", "ft-8k-1chip"):
        assert readers.read_all(only_new, cell, parent) == {}
    change = {"stats0": stats(10, 100, 1.0), "stats1": stats(30, 500, 1.0),
              "trace": {"idle_gaps": [["a - b: kftpu.sched.admit", 0.1]]}}
    got = readers.read_all(only_new, "doc-qa-paced", change)
    assert {k: v["value"] for k, v in got.items()} == {
        "sched.queue_wait_ms.doc": pytest.approx(100.0),
        "sched.first_token_ms.doc": pytest.approx(300.0),
        "sched.host_ms_per_round.doc": pytest.approx(2.5),
        "device.idle_named_share.doc": pytest.approx(100.0)}
