"""The whole command at a toy size for each driver, on the CPU, and the
faults that `correct` has to catch. The look for a chip is skipped; the
rest of a run is the one the chip sees."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import run as bench_run
from benchmarks.drivers import serve_closed
from benchmarks.lib import harness, readers, spec, train
from benchmarks.tests import toy

ROOT = spec.ROOT


@pytest.fixture
def toy_command(monkeypatch, capsys):
    """benchmarks/run.py's main on the toy cells, the chip not asked for."""
    real = harness.devices_for
    monkeypatch.setattr(harness, "devices_for",
                        lambda chips, require_tpu=True: real(chips, False))
    monkeypatch.setattr(spec, "benchmark", toy.bench)
    monkeypatch.setattr(spec, "cell", lambda name, bench=None: toy.cell(name))

    def command(*argv):
        rc = bench_run.main(list(argv))
        captured = capsys.readouterr()
        return rc, captured.out.strip().splitlines(), captured.err
    return command


@pytest.mark.parametrize("workload,metric", [
    ("toy-closed", "out_tok_per_s"),
    ("toy-open", "req_latency_p50_s"),
    ("toy-train", "train_tok_per_s"),
])
def test_whole_command(toy_command, workload, metric):
    rc, out, err = toy_command("--workload", workload, "--seed",
                               str(2**31 + 5), "--seconds", "1", "--trace", "0")
    assert rc == 0
    line = json.loads(out[-1])
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"][metric]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    assert line["device"]["platform"] == "cpu"   # named, never hidden
    # every compared number stands beside its limit on stderr too
    for name, c in line["checks"].items():
        assert f"check {name}: value {c['value']!r} limit {c['limit']!r}" in err


def test_no_chip_no_number(capsys):
    """The real look for a chip: on this CPU it exits 69 with no result."""
    rc = bench_run.main(["--workload", "chat-saturated", "--seed", "1",
                         "--seconds", "1", "--trace", "0"])
    assert rc == harness.EX_NO_DEVICE
    assert capsys.readouterr().out == ""


def test_mesh_cell_on_four_virtual_devices():
    """train_fit under fsdp=2 x model=2 against the unsharded reference;
    a process of its own, because the device count is fixed at start-up."""
    code = (
        "import json, sys, time; t = time.monotonic()\n"
        f"sys.path.insert(0, {ROOT!r})\n"
        "from benchmarks.lib import train\n"
        "from benchmarks.tests import toy\n"
        "res = train.run(toy.cell('toy-train-mesh'), 11, 0.5, False, t,\n"
        "                require_tpu=False)\n"
        "print(json.dumps({'correct': res['correct'], 'checks': res['checks'],\n"
        "                  'count': res['device']['count']}))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                          capture_output=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["count"] == 4
    assert got["correct"] is True, got["checks"]


# -- faults planted under the timed path: `correct` has to come out false ----

def _alter_tokens(served):
    """A token altered where it is produced: every tick's sampled tokens
    come out of the decoder's programs shifted by one."""
    dec = served.decoder
    vocab = served.cell.dims.vocab

    def shifted(fn):
        def step(*args):
            st = fn(*args)
            return st[:4] + ((st[4] + 1) % vocab,) + st[5:]
        return step

    dec._step, dec._step_fused = shifted(dec._step), shifted(dec._step_fused)


def test_altered_token_is_not_correct():
    import time

    res = serve_closed.run(toy.cell("toy-closed"), 21, 0.5, False,
                           time.monotonic(), require_tpu=False,
                           break_served=_alter_tokens)
    assert res["correct"] is False
    gap = dict((n, (v, lim)) for n, v, lim in res["checks"])["served_logit_gap"]
    assert gap[0] > gap[1]


def test_int4_program_is_not_correct():
    """The serving control, at toy size: the program's own int4 path in
    the place of the int8 the configuration states."""
    import time

    res = serve_closed.run(toy.cell("toy-closed"), 23, 0.5, False,
                           time.monotonic(), overrides={"param_dtype": "int4"},
                           require_tpu=False)
    assert res["correct"] is False
    checks = {n: (v, lim) for n, v, lim in res["checks"]}
    assert checks["served_logit_gap"][0] > checks["served_logit_gap"][1]
    assert checks["failed_requests"][0] == 0


def _state_unchanged(trainer):
    """A step that returns its state unchanged (only the step counter
    moves on, or fit would never end)."""
    import jax
    import jax.numpy as jnp

    real = trainer._train_step

    def step(state, batch):
        new, m = real(jax.tree.map(jnp.copy, state), batch)
        return state.replace(step=new.step), m

    trainer._train_step = step


def _half_batch(trainer):
    """Half of the batch left out, the mean taken over the rest."""
    import jax.numpy as jnp

    real = trainer._train_step

    def step(state, batch):
        half = {k: jnp.concatenate([v[:v.shape[0] // 2]] * 2)
                for k, v in batch.items()}
        return real(state, half)

    trainer._train_step = step


@pytest.mark.parametrize("fault,caught_by", [
    (_state_unchanged, "grad_norm_gap"),
    (_half_batch, "grad_norm_gap"),
])
def test_broken_step_is_not_correct(fault, caught_by):
    import time

    res = train.run(toy.cell("toy-train"), 31, 0.3, False, time.monotonic(),
                    require_tpu=False, break_trainer=fault)
    assert res["correct"] is False
    checks = {n: (v, lim) for n, v, lim in res["checks"]}
    assert checks[caught_by][0] > checks[caught_by][1], checks


def test_lower_precision_reference_is_not_correct():
    """The control, at toy size: the reference put in the program's place
    and computed in float8 where the configuration states bfloat16."""
    import jax.numpy as jnp

    cell = toy.cell("toy-train")
    ref = train.run_reference(cell, 41)
    low = train.run_reference(cell, 41, lowp=jnp.float8_e4m3fn)
    assert not harness.judge(train.compare(cell, low, ref))
    assert harness.judge(train.compare(cell, ref, ref))


def test_every_listed_metric_has_a_reader_and_its_cells():
    """BENCHMARK.json against the files: each per-layer metric has a file
    whose reader imports, moves an end-to-end metric that its cells
    report, and finds every count it needs in the architecture of each of
    its cells; every cell keeps setup_s, one more end-to-end metric and
    one per-layer metric, and its mix names a driver that is there."""
    bench = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    files = spec.metric_files()
    cells = {w["name"]: spec.cell(w["name"], bench)   # configuration and mix
             for w in bench["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells)) for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        reader = readers.reader_of(files[m["name"]])
        assert callable(reader)
        assert set(m["workloads"]) <= e2e[m["moves"]], m["name"]
        assert set(m["workloads"]) <= set(cells)
        for w in m["workloads"]:
            for count in getattr(reader, "counts", ()):
                assert callable(getattr(cells[w].arch, count, None)), (
                    f"{m['name']} needs {count} of {cells[w].arch.__name__}")
    for w, cell in cells.items():
        assert sum(w in ws for ws in e2e.values()) >= 2
        assert any(w in m["workloads"] for m in bench["per_layer"])
        assert callable(spec.driver(cell))
