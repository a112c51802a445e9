import os

import pytest

from benchmarks.lib import xplane

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "small.xplane.pb")


def _trace():
    ms = 1_000_000
    mods = [("jit_step(1)", 0, 4 * ms), ("jit_step(1)", 6 * ms, 10 * ms),
            ("jit_other(2)", 10 * ms + 5_000, 11 * ms)]
    ops = [("fusion.1", 0, 3 * ms), ("copy.2", 2 * ms, 4 * ms),
           ("fusion.1", 6 * ms, 10 * ms), ("fusion.3", 10 * ms + 5_000, 11 * ms)]
    host = [("outer", 0, 20 * ms), ("np.asarray", 4 * ms, 6 * ms)]
    return {"devices": {0: {"modules": mods, "ops": ops}}, "host": host}


def test_union_counts_overlap_once():
    assert xplane.union_ns([(0, 3), (2, 4), (6, 10)]) == 8
    assert xplane.union_ns([]) == 0


def test_reduce_known_busy_idle_and_modules():
    red = xplane.reduce(_trace())
    assert red["window_s"] == pytest.approx(0.011)
    assert red["busy_s"] == pytest.approx(0.008995)
    assert red["module_s"]["jit_step"] == pytest.approx([0.004, 0.004])
    assert red["module_s"]["jit_other"] == pytest.approx([0.000995])
    assert red["device_ops"][0] == ["fusion.1", pytest.approx(0.007)]
    # one gap of 2 ms, under the shortest host event over its middle; the
    # 5 us hole is launch latency and no gap
    assert red["idle_gaps"] == [
        ["jit_step - jit_step: np.asarray", pytest.approx(0.002)]]


def test_reduce_clips_to_the_window():
    red = xplane.reduce(_trace(), lo_ns=1_000_000, hi_ns=7_000_000)
    assert red["window_s"] == pytest.approx(0.006)
    assert red["busy_s"] == pytest.approx(0.004)


def test_module_name_drops_the_program_id():
    assert xplane.module_name("jit__tick(1234567)") == "jit__tick"
    assert xplane.module_name("jit__tick") == "jit__tick"


def test_recorded_trace_from_the_chip():
    """A small trace recorded on a TPU v5e (benchmarks/tests/record_trace.py):
    three calls of one jitted matmul loop, kept beside this test."""
    red = xplane.reduce(xplane.read(RECORDED))
    assert red["chips"] == 1
    calls = red["module_s"]["jit_recorded_step"]
    assert len(calls) == 3
    assert 0 < red["busy_s"] <= red["window_s"]
    assert sum(calls) == pytest.approx(red["busy_s"], rel=0.05)
    assert red["device_ops"] and len(red["idle_gaps"]) <= 10
