"""`axk1` at the published widths (the counts of ISSUE 35's arithmetic,
the configuration against the catalog's keys), the new cell's files
against BENCHMARK.json, the readers that are new, and the whole command
on a toy cell of that architecture on the CPU (the fixture directory
data/latent/, laid out like benchmarks/ and put in front of it at run
time)."""

import json
import os
import time

import pytest

from benchmarks import run as bench_run
from benchmarks.arch import axk1
from benchmarks.lib import harness, opcount, readers, spec
from benchmarks.tests.test_drivers import _alter_tokens

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "latent")
CELL, TOY = "latent-saturated", "toy-latent"
REDUCED = ["n_routed_experts", "num_hidden_layers", "vocab_size"]
# the catalog row's `config` (the model-configs guide's architectures.jsonl)
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "axk1", "moe_intermediate_size": 2048, "moe_layer_freq": 1,
    "n_group": 8, "n_routed_experts": 192, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 61,
    "num_key_value_heads": 64, "q_lora_rank": 1536, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 4, "topk_method": "none",
    "v_head_dim": 128, "vocab_size": 163840}


def real():
    return spec.cell(CELL, spec.benchmark())


def test_names_resolve():
    from benchmarks.drivers import serve_closed

    cell = real()
    d = cell.dims
    assert cell.arch is axk1 and cell.config["arch"] == "axk1"
    assert (d.vocab, d.layers, d.dense_layers) == (20480, 5, 1)
    assert (d.experts, d.experts_total, d.top_k, d.shared) == (12, 192, 8, 1)
    assert (d.n_group, d.topk_group, d.route_scale) == (8, 4, 2.5)
    assert (d.d, d.d_expert, d.d_dense, d.heads) == (7168, 2048, 18432, 64)
    assert (d.q_rank, d.kv_rank, d.nope, d.rope, d.v_dim) \
        == (1536, 512, 128, 64, 128)
    assert d.yarn == (32.0, 4096, 32.0, 1.0, 1.0, 1.0)
    assert spec.driver(cell) is serve_closed.run
    kw = cell.arch.model_kwargs(cell)
    assert (kw["n_experts"], kw["n_experts_total"]) == (12, 192)
    assert (kw["moe_n_group"], kw["moe_topk_group"]) == (8, 4)
    assert kw["layer_pattern"][0] == dict(latent=True, moe=False)
    assert kw["layer_pattern"][4] == dict(latent=True, moe=True)
    assert (kw["rope_factor"], kw["rope_original_max"]) == (32.0, 4096)


def test_configuration_holds_the_published_keys():
    """Every key of the published config.json (as the catalog beside the
    `model-configs` guide has it) under its name, those apart that
    `reduced` names, with the published values beside; no width is cut."""
    cfg = real().config
    differs = sorted(k for k, v in PUBLISHED.items() if cfg.get(k, "-") != v)
    assert differs == sorted(cfg["reduced"]) == REDUCED
    assert cfg["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert cfg["share"]["chips_a_layer"] == 16
    assert "one of sixteen chips" in cfg["deployment"]
    entry = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == "ax-k1-serve")
    assert sorted(entry["reduced"]) == REDUCED
    assert entry["source"] == cfg["source"] \
        == "https://huggingface.co/skt/A.X-K1/blob/main/config.json"
    serve = cfg["serve"]
    assert serve["prefix_cache"] is False
    assert (serve["decode_slots"], serve["kv_pages"]) == (64, 64 * 560 + 1)
    assert (serve["prompt_len"], serve["max_new_tokens"]) == (8192, 768)
    mix = real().traffic
    assert (mix["clients"], mix["prompt_min"], mix["prompt_max"],
            mix["answer_min"], mix["answer_max"]) == (128, 1024, 8192, 128, 768)
    assert mix["blocks"] >= 12 and mix["check_requests"] == 6
    assert set(mix["limits"]["ax-k1-serve"]) == set(axk1.JUDGED)


def test_counts_at_the_published_widths():
    """The issue's arithmetic: attention 101.1 M, a layer outside its
    routed experts 146.5 M, an expert 44.04 M, the dense layer 497.5 M, a
    mixture layer here 675.0 M, 3,491 M = 6.98 GB held; a position's
    latent 1,152 bytes a layer."""
    d = real().dims
    assert round(axk1.attn_params(d) / 1e6, 1) == 101.1
    assert axk1.expert_params(d) == 44_040_192
    assert round(axk1.layer_dense_params(d, 1) / 1e6, 1) == 146.5
    assert round(axk1.layer_dense_params(d, 0) / 1e6, 1) == 497.5
    here = axk1.layer_dense_params(d, 1) + d.experts * axk1.expert_params(d)
    assert round(here / 1e6, 1) == 675.0
    held = (sum(axk1.layer_dense_params(d, i) for i in range(d.layers))
            + axk1.moe_layers(d) * d.experts * axk1.expert_params(d)
            + 2 * d.vocab * d.d)
    assert round(held / 1e6) == 3491
    assert axk1.weight_bytes(d, 2) == pytest.approx(
        2 * (held - d.vocab * d.d), rel=1e-9)
    assert axk1.latent_bytes(d) == 1152
    assert axk1.kv_page_bytes(d, 16) == 5 * 16 * 1152
    assert axk1.decode_kv_bytes(d, 8000, 2) == 5 * 1152 * 8001
    # the two forms: a prompt's own attention at keys of 192 and values of
    # 128, a cached position's at 576 + 512
    assert axk1.attention_flops(d, 1) == 2 * 64 * 320
    assert axk1.latent_attention_flops(d, 1) == 2 * 64 * 1088
    assert axk1.flash_flops(d, [4096]) == 5 * 2 * 64 * 320 * 4096 * 4097 // 2
    # a token's operations: the held share of its 8 experts is half an
    # expert in the mean
    assert axk1.token_flops(d) == pytest.approx(
        2 * (497.5e6 + 4 * 146.5e6 + 4 * 0.5 * 44.04e6), rel=2e-3)
    assert axk1.request_flops(d, 5000, 400) == pytest.approx(
        axk1.forward_flops(d, 0, 5000, 1)
        + axk1.forward_flops(d, 5000, 5399, 399))
    # behind a cache the attention is the absorbed form's
    assert (axk1.forward_flops(d, 5000, 5001, 0) - axk1.token_flops(d)
            == 5 * 2 * 64 * 1088 * 5001)


def test_every_listed_metric_of_the_cell_has_a_file_that_agrees():
    bench, files = spec.benchmark(), spec.metric_files()
    mine = [m for m in bench["per_layer"] if m["workloads"] == [CELL]]
    assert len(mine) == 22
    assert {m["name"].rsplit(".", 1)[1] for m in mine} == {"latent"}
    for m in mine:
        meta = files[m["name"]]
        assert {k: meta[k] for k in ("layer", "unit", "source", "moves",
                                     "workloads")} == \
            {k: m[k] for k in ("layer", "unit", "source", "moves",
                               "workloads")}
        fn = readers.reader_of(meta)
        for count in getattr(fn, "counts", ()):
            assert hasattr(axk1, count), (m["name"], count)
    out = next(e for e in bench["end_to_end"] if e["name"] == "out_tok_per_s")
    assert CELL in out["workloads"]
    # the share of the whole step's peak, under its name, and every
    # roofline of the accepted benchmark that the cell's metric moves
    names = {m["name"] for m in mine}
    assert {"model.mfu.latent", "decode_roofline.latent",
            "prefill_roofline.latent", "moe.expert_roofline.latent",
            "prefill_flash_roofline.latent",
            "latent_attention_roofline.latent"} <= names


def test_the_new_readers_read_what_is_there_and_nothing_where_nothing_is():
    """A program without the counters (the parent) or a run without a
    trace: None, never 0 and never an error. With them: the needed bytes
    of the ticks the stretch held over the kernels' time, whatever share
    of the window's seconds the stretch's ticks were."""
    from benchmarks.metrics import latent

    cell = real()
    ctx = {"cell": cell, "trace": None, "stats0": {}, "stats1": {},
           "requests": [], "slots": 64, "window_s": 51.0,
           "device_kind": "TPU v5 lite"}
    tick = dict(single="^jit__tick$", fused="^jit__step_fused$", fuse=8)
    kernel = dict(ops="paged_latent_attention", **tick)
    flash = dict(ops="local_attention", module="^jit__paged_prefill_install$")
    assert latent.decode_roofline(ctx, **tick) is None
    assert latent.latent_attention_roofline(ctx, **kernel) is None
    assert latent.expert_roofline(ctx, "ragged-dot", **tick) is None
    assert latent.prefill_flash_roofline(ctx, **flash) is None
    assert latent.latent_page_fill(ctx) is None
    # a trace of a program that has the modules and not the counters:
    # two single ticks and a fused round, two prefills
    ctx["trace"] = {
        "module_s": {"jit__tick": [0.015, 0.015], "jit__step_fused": [0.12],
                     "jit__paged_prefill_install": [0.1, 0.2]},
        "op_s": {
            "%paged_latent_attention.1 = bf16[64,64,512] custom-call(": 0.04,
            "%ragged-dot-streamed.3 = bf16[512,2048]{1,0} custom-call(": 0.05,
            "%ragged-dot-none.3 = bf16[32768,2048]{1,0} custom-call(": 0.9,
            "%local_attention.2 = (bf16[64,4096,128]) custom-call(": 0.1},
        "window_s": 3.0}
    ctx["requests"] = [{"prompt": 5000, "out": 300}, {"prompt": 3000, "out": 200}]
    assert latent.decode_roofline(ctx, **tick) is None
    assert latent.latent_attention_roofline(ctx, **kernel) is None
    assert latent.expert_roofline(ctx, "ragged-dot", **tick) is None
    # 1,000 ticks in the window, each of 64 slots at 5,000 positions (313
    # pages a slot) that visits 40 layer-experts
    ctx["stats0"] = {"kv_pages_walked": 0, "moe_expert_visits": 0, "ticks": 0}
    ctx["stats1"] = {"kv_pages_walked": 64 * 313 * 1000, "ticks": 1000,
                     "moe_expert_visits": 40 * 1000,
                     "kv_latent_row_bytes": 1280}
    positions = 64 * 313 * 16
    got = latent.latent_attention_roofline(ctx, **kernel)
    assert got == pytest.approx(100 * 10 * 5 * positions * 1152 / 819e9 / 0.04)
    assert 30 < got < 100
    assert latent.expert_roofline(ctx, "ragged-dot", **tick) == pytest.approx(
        100 * 10 * 40 * 88_080_384 / 819e9 / 0.05)
    assert latent.prefill_flash_roofline(ctx, **flash) == pytest.approx(
        100 * 2 * (axk1.flash_flops(cell.dims, [5000])
                   + axk1.flash_flops(cell.dims, [3000])) / 2 / 197e12 / 0.1)
    assert latent.latent_page_fill(ctx) == pytest.approx(90.0)
    nbytes = (axk1.weight_bytes(cell.dims, 2, 0) + 40 * 88_080_384
              + 5 * positions * 1152)
    assert latent.decode_roofline(ctx, **tick) == pytest.approx(
        100 * 10 * nbytes / 819e9 / 0.15)


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
    assert m["server.compiles.latent"] == 0
    assert m["attn.latent_kernel_share.latent"] == 0        # no TPU here
    assert m["sched.prefill_flash_share.latent"] == 0
    assert m["kvcache.latent_page_fill.latent"] == pytest.approx(
        100 * 40 / 128)
    assert 10 < m["moe.held_pairs_share.latent"] < 45       # 8 of 32 held
    assert m["moe.pairs_per_visit.latent"] >= 1.0
    assert m["moe.load_max_over_mean.latent"] >= 1.0
    assert m["moe.kernel_pairs_share.latent"] == 0
    assert m["model.mfu.latent"] > 0
    assert 0 < m["kvcache.pages_walked_share.latent"] <= 100
    assert 0 < m["kvcache.pages_used_share.latent"] <= 100
    # no device trace on the CPU: the shares are left out, not 0
    assert not [k for k in m if "roofline" in k]


def test_altered_token_on_the_toy_cell_is_not_correct(toy):
    cell = spec.cell(TOY)
    res = spec.driver(cell)(cell, 21, 0.5, False, time.monotonic(),
                            require_tpu=False, break_served=_alter_tokens)
    assert res["correct"] is False
    read = {n: (v, lim) for n, v, lim in res["checks"]}
    assert read["served_wide_share"][0] > read["served_wide_share"][1]
    assert read["served_worst_gap"][0] > 2.0


@pytest.fixture(scope="module")
def sound_sample():
    """Two prompts and the sound reference's own greedy answers to them,
    at the toy cell's sizes (one padded length: one program)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib.weights import seed_key

    d = axk1.sizes(spec.load_json(os.path.join(
        DATA, "configs", "toy-ax-k1-serve.json")))
    logits = jax.jit(lambda t: axk1.sequence_logits(d, seed_key(5), t))
    rng = np.random.default_rng(0)
    sample = []
    for n in (200, 60):
        prompt = rng.integers(1, d.vocab, n).tolist()
        toks = np.zeros(256, np.int32)
        toks[:n] = prompt
        for i in range(n, n + 24):
            toks[i] = int(np.argmax(np.asarray(logits(jnp.asarray(toks)))[i - 1]))
        sample.append({"prompt": prompt, "prediction": toks[n:n + 24].tolist()})
    return sample


@pytest.mark.parametrize("fault", [
    dict(lowp="float8_e4m3fn"), dict(top_k=3), dict(no_groups=True),
    dict(no_yarn=True), dict(no_mscale=True), dict(no_kv_norm=True)],
    ids=lambda f: next(iter(f)))
def test_a_planted_fault_moves_a_judged_number_at_toy_size(
        toy, sound_sample, fault):
    """The six controls of `controls_latent_on_chip.py`, through
    `compare_served` itself: the reference with the fault in it judges
    what the sound reference chose, and at least one judged number moves
    by far more than the sound reading (0 against itself)."""
    import jax.numpy as jnp

    cell = spec.cell(TOY)
    sound, _ = axk1.compare_served(cell, 5, sound_sample)
    assert sound["served_worst_gap"] <= 1e-4
    if "lowp" in fault:
        fault = dict(lowp=getattr(jnp, fault["lowp"]))
    judged, _ = axk1.compare_served(cell, 5, sound_sample, **fault)
    assert judged["served_worst_gap"] > 0.02, judged
