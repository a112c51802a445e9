"""The two-counter reader on hand-made `ctx`: growth over the window and
not totals, and None (never an error) where a counter is missing, as on
the commit before the counters existed."""

import pytest

from benchmarks.lib import readers, spec
from benchmarks.metrics import kvwalk

ARGS = {"part": "kv_pages_walked", "whole": "kv_pages_tabled"}
NEW = ["kvcache.pages_walked_share.chat", "kvcache.pages_walked_share.doc"]


def test_share_is_growth_over_the_window_not_totals():
    # before the window the gather-sized walk (100%), inside it a fifth
    ctx = {"stats0": {"kv_pages_walked": 9216, "kv_pages_tabled": 9216},
           "stats1": {"kv_pages_walked": 9216 + 1800,
                      "kv_pages_tabled": 9216 + 9000}}
    assert kvwalk.growth_share(ctx, **ARGS) == pytest.approx(20.0)


@pytest.mark.parametrize("ctx", [
    {"stats0": {"admitted": 1}, "stats1": {"admitted": 9}},     # the parent
    {"stats0": {"kv_pages_tabled": 5}, "stats1": {"kv_pages_tabled": 9}},
    {"stats0": {"kv_pages_walked": 3, "kv_pages_tabled": 5},
     "stats1": {"kv_pages_walked": 3, "kv_pages_tabled": 5}},   # no tick ran
    {},
], ids=["no-such-keys", "one-key", "nothing-happened", "empty"])
def test_missing_or_still_counters_read_none(ctx):
    assert kvwalk.growth_share(ctx, **ARGS) is None


def test_new_metrics_are_declared_found_and_left_out_on_the_parent():
    bench, files = spec.benchmark(), spec.metric_files()
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert files[name]["reader"] == "kvwalk:growth_share"
        assert files[name]["args"] == ARGS
        for key in ("layer", "unit", "source", "moves", "workloads"):
            assert files[name][key] == declared[name][key]
        assert declared[name]["better"] == "lower"
    parent = {"stats0": {"admitted": 1}, "stats1": {"admitted": 9}}
    only = dict(bench, per_layer=[declared[n] for n in NEW])
    assert readers.read_all(only, "chat-saturated", parent) == {}
    walked = {"stats0": {"kv_pages_walked": 0, "kv_pages_tabled": 0},
              "stats1": {"kv_pages_walked": 19, "kv_pages_tabled": 100}}
    assert readers.read_all(only, "doc-qa-paced", walked) == {
        "kvcache.pages_walked_share.doc": {"value": 19.0, "unit": "%"}}
