"""The proof by addition: an architecture, a driver, a configuration, a
mix, a cell and a per-layer metric brought in as new files only (the
fixture directory data/added/, laid out like benchmarks/ and put in front
of it at run time), following benchmarks/README.md's recipes to the
letter. The whole command runs the new cell on the CPU to a result line
with `correct` true and a `model.mfu` made of the new architecture's
counts; an altered token makes `correct` false; no file that was there is
written to."""

import hashlib
import json
import os
import sys
import time

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import harness, opcount, spec
from benchmarks.tests.test_drivers import _alter_tokens

ADDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "added")
CELL = "toy-moe-closed"


def files_that_are_there() -> dict:
    """BENCHMARK.json and every file under benchmarks/ (bytecode apart),
    each with the hash of its bytes."""
    paths = [os.path.join(spec.ROOT, "BENCHMARK.json")]
    for where, dirs, names in os.walk(spec.BENCH_DIR):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        paths += [os.path.join(where, n) for n in names if not n.endswith(".pyc")]
    out = {}
    for p in paths:
        with open(p, "rb") as f:
            out[p] = hashlib.sha256(f.read()).hexdigest()
    return out


@pytest.fixture
def added(monkeypatch):
    """What a PR that adds the fixture's files would find: the same
    harness, with its files beside those that are there."""
    before = files_that_are_there()
    monkeypatch.setattr(spec, "ROOTS", [ADDED, spec.BENCH_DIR])
    monkeypatch.setattr(
        spec, "benchmark", lambda: spec.load_json(os.path.join(ADDED, "bench.json")))
    real_devices, real_window = harness.devices_for, harness.TraceWindow
    monkeypatch.setattr(harness, "devices_for",
                        lambda chips, require_tpu=True: real_devices(chips, False))
    # a CPU capture has no device plane to reduce: the readers that need
    # none still run, as on a chip with the profiler off
    monkeypatch.setattr(harness, "TraceWindow", lambda enabled: real_window(False))
    # a share of a peak needs a peak: the CPU borrows the chip's, here only
    monkeypatch.setitem(opcount.PEAKS, "cpu", opcount.PEAKS["TPU v5 lite"])
    yield
    assert files_that_are_there() == before


def test_names_are_the_fixtures(added):
    cell = spec.cell(CELL)
    assert cell.arch.__file__ == os.path.join(ADDED, "arch", "toy_moe.py")
    assert (cell.dims.experts, cell.dims.top_k) == (4, 2)
    kw = cell.arch.model_kwargs(cell)
    assert (kw["moe_every"], kw["n_experts"], kw["moe_capacity_factor"]) == (1, 4, 2.0)
    assert spec.driver(cell).__module__ == "benchmarks_added.drivers.serve_closed_twin"
    assert "model.mfu.toy-moe" in spec.metric_files()
    assert "model.mfu.chat" in spec.metric_files()     # beside, not instead


def test_whole_command_on_the_added_cell(added, capsys):
    rc = bench_run.main(["--workload", CELL, "--seed", str(2**31 + 11),
                         "--seconds", "1", "--trace", "1"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    line = json.loads(out[-1])
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    # the new comparison's names, the judged one beside the mix's limit
    assert line["checks"]["moe_logit_gap"]["limit"] == 0.05
    assert line["checks"]["requests_compared"]["value"] == 4.0
    assert "served_logit_gap" not in line["checks"]
    # the per-layer metric, read by the reader that is there from the
    # counts of the architecture that was not
    arch = sys.modules["benchmarks_added.arch.toy_moe"]
    driver = sys.modules["benchmarks_added.drivers.serve_closed_twin"]
    assert driver.RUNS[-1] == CELL
    assert line["metrics"]["model.mfu.toy-moe"]["value"] > 0
    assert len([c for c in arch.CALLS if c[0] == "request_flops"]) >= line["attempted"]
    cell = spec.cell(CELL)
    assert (arch.request_flops(cell.dims, 100, 10)
            != spec.load_module("arch", "dense_gqa").request_flops(cell.dims, 100, 10))


def test_altered_token_on_the_added_cell_is_not_correct(added):
    cell = spec.cell(CELL)
    res = spec.driver(cell)(cell, 21, 0.5, False, time.monotonic(),
                            require_tpu=False, break_served=_alter_tokens)
    assert res["correct"] is False
    gap = {n: (v, lim) for n, v, lim in res["checks"]}["moe_logit_gap"]
    assert gap[0] > gap[1]


def test_a_reader_whose_count_the_architecture_lacks_is_found_out(added):
    """`device:decode_roofline` needs `weight_bytes`, which toy_moe does
    not count: listing it for the added cell has to fail the check that
    test_every_listed_metric_has_a_reader_and_its_cells makes."""
    from benchmarks.lib import readers

    cell = spec.cell(CELL)
    reader = readers.reader_of({"reader": "device:decode_roofline"})
    missing = [c for c in reader.counts if not callable(getattr(cell.arch, c, None))]
    assert "weight_bytes" in missing
    ok = readers.reader_of(spec.metric_files()["model.mfu.toy-moe"])
    assert all(callable(getattr(cell.arch, c, None)) for c in ok.counts)
