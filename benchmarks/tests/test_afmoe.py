"""`afmoe` at the published widths (the counts of ISSUE 33's arithmetic,
the configuration against the catalog's keys), the new cell's files
against BENCHMARK.json, and the whole command on a toy cell of that
architecture on the CPU (the fixture directory data/longctx/, laid out
like benchmarks/ and put in front of it at run time)."""

import json
import os
import time

import pytest

from benchmarks import run as bench_run
from benchmarks.arch import afmoe
from benchmarks.lib import harness, opcount, readers, spec
from benchmarks.tests.test_drivers import _alter_tokens

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "longctx")
CELL, TOY = "longctx-saturated", "toy-longctx"
REDUCED = ["layer_types", "num_dense_layers", "num_experts",
           "num_hidden_layers", "vocab_size"]


def real():
    return spec.cell(CELL, spec.benchmark())


def test_names_resolve():
    from benchmarks.drivers import serve_closed

    cell = real()
    d = cell.dims
    assert cell.arch is afmoe and cell.config["arch"] == "afmoe"
    assert (d.vocab, d.layers, d.dense_layers) == (25024, 5, 1)
    assert (d.experts, d.experts_total, d.top_k, d.shared) == (32, 256, 4, 1)
    assert (d.d, d.d_expert, d.d_dense, d.window) == (3072, 3072, 12288, 4096)
    assert d.sliding == (True, True, True, True, False)
    assert spec.driver(cell) is serve_closed.run
    kw = cell.arch.model_kwargs(cell)
    assert (kw["n_experts"], kw["n_experts_total"]) == (32, 256)
    assert kw["layer_pattern"][0] == dict(window=4096, rope=True, moe=False)
    assert kw["layer_pattern"][4] == dict(window=0, rope=False, moe=True)
    assert kw["embed_scale"] == pytest.approx(3072 ** 0.5)


def test_configuration_holds_the_published_keys():
    """Every key of the published config.json (as the catalog beside the
    `model-configs` guide has it) under its name, those apart that
    `reduced` names, with the published values beside; no width is cut."""
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 3072,
        "intermediate_size": 12288, "load_balance_coeff": 5e-05,
        "max_position_embeddings": 262144, "model_type": "afmoe",
        "moe_intermediate_size": 3072, "mup_enabled": True, "n_group": 1,
        "num_attention_heads": 48, "num_dense_layers": 6,
        "num_expert_groups": 1, "num_experts": 256,
        "num_experts_per_tok": 4, "num_hidden_layers": 60,
        "num_key_value_heads": 8, "num_limited_groups": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
        "route_scale": 2.448, "score_func": "sigmoid",
        "sliding_window": 4096, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192,
        "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 15}
    cfg = real().config
    differs = sorted(k for k, v in published.items() if cfg.get(k, "-") != v)
    assert differs == sorted(cfg["reduced"]) == REDUCED
    assert {k: cfg["published"][k] for k in REDUCED if k != "layer_types"} \
        == {k: published[k] for k in REDUCED if k != "layer_types"}
    assert cfg["layer_types"] == ["sliding_attention"] * 4 + ["full_attention"]
    assert cfg["share"]["chips_a_layer"] == 8
    assert "one of eight chips" in cfg["deployment"]
    entry = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == "trinity-large-serve")
    assert sorted(entry["reduced"]) == REDUCED
    assert cfg["serve"]["prefix_cache"] is False
    assert cfg["serve"]["kv_pages"] == 32 * 1072 + 1


def test_counts_at_the_published_widths():
    """The issue's arithmetic: a layer outside its routed experts 92.0 M,
    an expert 28.31 M, a mixture layer here 998 M, the dense layer
    176.2 M (the gate counted), 4,322 M = 8.64 GB held."""
    d = real().dims
    assert afmoe.expert_params(d) == 28_311_552
    assert round(afmoe.layer_dense_params(d, 1) / 1e6, 1) == 92.0
    assert round(afmoe.layer_dense_params(d, 0) / 1e6, 1) == 176.2
    held = (sum(afmoe.layer_dense_params(d, i) for i in range(d.layers))
            + afmoe.moe_layers(d) * d.experts * afmoe.expert_params(d)
            + 2 * d.vocab * d.d)
    assert round(held / 1e6) == 4322
    assert afmoe.weight_bytes(d, 2) == pytest.approx(
        2 * (held - d.vocab * d.d), rel=1e-9)
    # a tick that visits 12.7 experts a mixture layer
    tick = afmoe.weight_bytes(d, 2, 4 * 12.7)
    assert 3.9e9 < tick < 4.3e9
    # by kind: a sliding layer's live positions stop at the window
    kv = afmoe.decode_kv_bytes(d, 16000, 2)
    assert kv == 2 * 2 * 8 * 128 * (16001 + 4 * 4096)
    # a token's operations: the held share of its 4 experts is half an
    # expert in the mean
    assert afmoe.token_flops(d) == pytest.approx(
        2 * (176.16e6 + 4 * 92.0e6 + 4 * 0.5 * 28.31e6), rel=2e-3)
    assert afmoe.request_flops(d, 8000, 400) > 8400 * afmoe.token_flops(d)


def test_every_listed_metric_of_the_cell_has_a_file_that_agrees():
    bench, files = spec.benchmark(), spec.metric_files()
    mine = [m for m in bench["per_layer"] if m["workloads"] == [CELL]]
    assert len(mine) == 20
    for m in mine:
        meta = files[m["name"]]
        assert {k: meta[k] for k in ("layer", "unit", "source", "moves",
                                     "workloads")} == \
            {k: m[k] for k in ("layer", "unit", "source", "moves",
                               "workloads")}
        fn = readers.reader_of(meta)
        for count in getattr(fn, "counts", ()):
            assert hasattr(afmoe, count), (m["name"], count)
    out = next(e for e in bench["end_to_end"] if e["name"] == "out_tok_per_s")
    assert CELL in out["workloads"]


def test_the_roofline_readers_read_nothing_where_there_is_nothing():
    """A program without the counters (the parent) or a run without a
    trace: None, never 0 and never an error."""
    from benchmarks.metrics import longctx

    cell = real()
    ctx = {"cell": cell, "trace": None, "stats0": {}, "stats1": {},
           "requests": [], "slots": 32, "window_s": 51.0,
           "device_kind": "TPU v5 lite"}
    tick = dict(single="^jit__tick$", fused="^jit__step_fused$", fuse=8)
    assert longctx.decode_roofline(ctx, **tick) is None
    assert longctx.expert_roofline(ctx, "ragged-dot") is None
    assert longctx.paged_attention_roofline(ctx, "paged_decode_attention") is None
    assert longctx.prefill_flash_roofline(ctx, "local_attention") is None
    # a trace of a program that has the modules and not the counters
    ctx["trace"] = {"module_s": {"jit__tick": [0.01]}, "op_s": {
        "%paged_decode_attention.1 = bf16[32,48,128] custom-call(": 0.001},
        "window_s": 3.0}
    ctx["requests"] = [{"prompt": 5000, "out": 300}]
    assert longctx.decode_roofline(ctx, **tick) is None
    assert longctx.paged_attention_roofline(ctx, "paged_decode_attention") is None


def test_a_fault_in_one_slot_is_not_spread_over_the_others():
    import numpy as np

    def request(n, wide=0):
        gap = np.full(n, 0.01, np.float32)
        gap[:wide] = 1.5
        return gap

    got = afmoe.judged([request(700) for _ in range(5)] + [request(500, 10)])
    assert got["served_wide_share"] == pytest.approx(2.0)
    assert got["served_logit_gap"] == pytest.approx(0.01)   # the narrow ones
    assert got["served_worst_gap"] == pytest.approx(1.5)
    shifted = afmoe.judged([request(500) + 0.02])
    assert shifted["served_logit_gap"] == pytest.approx(0.03)
    assert set(got) == set(afmoe.JUDGED)


# -- the whole command on a toy cell of the architecture ----------------------

@pytest.fixture
def toy(monkeypatch):
    monkeypatch.setattr(spec, "ROOTS", [DATA, spec.BENCH_DIR])
    monkeypatch.setattr(
        spec, "benchmark", lambda: spec.load_json(os.path.join(DATA, "bench.json")))
    real_devices, real_window = harness.devices_for, harness.TraceWindow
    monkeypatch.setattr(harness, "devices_for",
                        lambda chips, require_tpu=True: real_devices(chips, False))
    monkeypatch.setattr(harness, "TraceWindow", lambda enabled: real_window(False))
    monkeypatch.setitem(opcount.PEAKS, "cpu", opcount.PEAKS["TPU v5 lite"])


def test_whole_command_on_a_toy_cell(toy, capsys):
    rc = bench_run.main(["--workload", TOY, "--seed", str(2**31 + 11),
                         "--seconds", "1", "--trace", "1"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    line = json.loads(out[-1])
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["checks"]) == {
        "malformed_answers", "served_logit_gap", "served_wide_share",
        "served_worst_gap", "failed_requests"}
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["server.compiles.longctx"] == 0
    assert 0 < m["kvcache.window_pages_held_share.longctx"] < 100
    assert 10 < m["moe.held_pairs_share.longctx"] < 45      # 8 of 32 held
    assert m["moe.pairs_per_visit.longctx"] >= 1.0
    assert m["moe.load_max_over_mean.longctx"] >= 1.0
    assert m["moe.kernel_pairs_share.longctx"] == 0         # no TPU here
    assert m["model.mfu.longctx"] > 0
    assert 0 < m["kvcache.pages_walked_share.longctx"] <= 100
    assert 0 < m["kvcache.pages_used_share.longctx"] <= 100
    # no device trace on the CPU: the shares are left out, not 0
    assert not [k for k in m if "roofline" in k]


def test_altered_token_on_the_toy_cell_is_not_correct(toy):
    cell = spec.cell(TOY)
    res = spec.driver(cell)(cell, 21, 0.5, False, time.monotonic(),
                            require_tpu=False, break_served=_alter_tokens)
    assert res["correct"] is False
    # every token is off by far: the share of wide tokens and the worst
    # one say so (the mean is over the tokens that are not wide)
    read = {n: (v, lim) for n, v, lim in res["checks"]}
    assert read["served_wide_share"][0] > read["served_wide_share"][1]
    assert read["served_worst_gap"][0] > 2.0
