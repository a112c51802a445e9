"""`sdar_moe` at the published widths (the counts of ISSUE 29's
arithmetic, the first weights), the new cell's files against
BENCHMARK.json, and the whole command on a toy cell of that architecture
on the CPU (the fixture directory data/blockdiff/, laid out like
benchmarks/ and put in front of it at run time, as test_added.py does)."""

import json
import os
import time

import pytest

from benchmarks import run as bench_run
from benchmarks.arch import sdar_moe
from benchmarks.lib import harness, opcount, readers, spec
from benchmarks.tests.test_drivers import _alter_tokens

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "blockdiff")
CELL, TOY = "blockdiff-saturated", "toy-blockdiff"


def real():
    return spec.cell(CELL, spec.benchmark())


def test_names_resolve():
    from benchmarks.drivers import serve_closed

    cell = real()
    assert cell.arch is sdar_moe and cell.config["arch"] == "sdar_moe"
    assert (cell.dims.vocab, cell.dims.layers) == (151936, 6)
    assert (cell.dims.experts, cell.dims.top_k, cell.dims.d_expert) == (128, 8, 768)
    assert (cell.dims.block, cell.dims.steps, cell.dims.per_pass) == (4, 4, 1)
    assert spec.driver(cell) is serve_closed.run
    kw = cell.arch.model_kwargs(cell)
    assert (kw["d_ff"], kw["moe_d_ff"]) == (6144, 768)
    assert (kw["qk_norm"], kw["gen_block"]) == (True, 4)
    assert kw["gen_mask_id"] == 151669 and kw["moe_every"] == 1


def test_configuration_holds_the_published_keys():
    """Every number of the catalog's entry under its key, the layers
    apart, which `reduced` names with the published 48 beside."""
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    cfg = real().config
    differs = sorted(k for k, v in published.items() if cfg.get(k, "-") != v)
    assert differs == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 48}
    for key in ("generation.block_length", "generation.denoising_steps",
                "generation.remasking", "generation.mask_token_id",
                "generation.prediction", "weights"):
        assert key in cfg["assumed"], key
    assert "eight pipeline stages" in cfg["deployment"]
    assert cfg["serve"] == {
        "prompt_len": 1024, "max_new_tokens": 1024,
        "continuous_batching": True, "decode_slots": 64, "kv_pages": 8193,
        "kv_page_size": 16, "prefix_cache": False, "param_dtype": "bfloat16",
        "temperature": 0.0}


def test_counts_at_the_published_widths():
    """ISSUE 29's arithmetic: 623.1 M a layer, 4.361 B held, 8.72 GB in
    bfloat16; a pass over 64 slots that visits every expert reads 8.10 GB
    but for the cache; a page of 16 positions is 196,608 B."""
    d = real().dims
    assert sdar_moe.attn_params(d) == 18_874_368
    assert sdar_moe.expert_params(d) == 4_718_592
    assert sdar_moe.layer_params(d) == 623_116_288
    assert sdar_moe.held_params(d) == 4_361_027_584
    assert sdar_moe.kv_page_bytes(d, 16) == 196_608
    every = d.layers * d.experts
    assert sdar_moe.pass_bytes(d, every, 0, 16) == 8_099_725_312
    assert sdar_moe.expert_bytes(d) * every == 7_247_757_312
    # nine tenths of a pass's bytes are experts
    assert 0.89 < sdar_moe.expert_bytes(d) * every / 8_099_725_312 < 0.90
    assert sdar_moe.token_flops(d) == 2 * (18_874_368 + 262_144 + 8 * 4_718_592)
    # a request: the prompt's whole blocks once, then 5 passes a whole block
    one_block = sdar_moe.request_flops(d, 8, 4) - sdar_moe.request_flops(d, 8, 0)
    body = 5 * (4 * d.layers * sdar_moe.token_flops(d)
                + d.layers * sdar_moe.attention_flops(d, 4 * 12))
    head = (4 + 3 + 2 + 1) * 2 * d.d * d.vocab
    assert one_block == body + head
    assert sdar_moe.request_flops(d, 8, 0) == sdar_moe.forward_flops(d, 0, 8, 0)
    # the prompt's tail opens the first block: 2 masked, 2 + 1 passes
    tail = sdar_moe.request_flops(d, 10, 2) - sdar_moe.forward_flops(d, 0, 8, 0)
    assert tail == 3 * (4 * d.layers * sdar_moe.token_flops(d) + d.layers
                        * sdar_moe.attention_flops(d, 4 * 12)) \
        + (2 + 1) * 2 * d.d * d.vocab


def test_first_weights():
    """Seed 0, layer 1: the first three values of four leaves and of the
    head, as bfloat16 holds them (one on a seed past 2**31)."""
    import jax
    import numpy as np

    from benchmarks.lib.weights import seed_key

    d = real().dims
    first = jax.jit(lambda k: {
        n: v.reshape(-1)[:3].astype("float32")
        for n, v in sdar_moe.layer_leaves(d, k, 1).items()
        if n in ("q", "w_down", "router", "q_norm")})(seed_key(0))
    got = {n: np.asarray(v).tolist() for n, v in first.items()}
    assert got == FIRST_WEIGHTS, got
    head = jax.jit(lambda k: sdar_moe.top_leaf(d, k, "lm_head")
                   .reshape(-1)[:3].astype("float32"))(seed_key(2**31 + 5))
    assert np.asarray(head).tolist() == FIRST_HEAD
    leaves = jax.eval_shape(lambda k: sdar_moe.program_params(d, k), seed_key(0))
    assert {str(x.dtype) for x in jax.tree.leaves(leaves)} == {"bfloat16"}
    assert sum(x.size for x in jax.tree.leaves(leaves)) - sdar_moe.held_params(d) \
        == d.layers * (2 * d.d + 2 * d.head_dim) + d.d      # the norms' scales


FIRST_WEIGHTS = {
    "q": [0.0235595703125, -0.000774383544921875, 0.040283203125],
    "q_norm": [1.0703125, 0.9921875, 1.0],
    "router": [-0.0196533203125, -0.025390625, 0.01409912109375],
    "w_down": [0.037109375, -0.0020904541015625, 0.040283203125]}
FIRST_HEAD = [0.01007080078125, -0.0291748046875, 0.009521484375]


def test_every_listed_metric_of_the_cell_has_a_file_that_agrees():
    bench, files = spec.benchmark(), spec.metric_files()
    mine = [m for m in bench["per_layer"] if m["workloads"] == [CELL]]
    assert len(mine) == 16
    for m in mine:
        f = files[m["name"]]
        assert (f["layer"], f["unit"], f["source"], f["moves"], f["workloads"]) \
            == (m["layer"], m["unit"], m["source"], "out_tok_per_s", [CELL])
        reader = readers.reader_of(f)
        for count in getattr(reader, "counts", ()):
            assert callable(getattr(sdar_moe, count, None)), (m["name"], count)
    shares = [m["name"] for m in mine if "roofline" in m["name"]]
    assert sorted(shares) == [
        "decode_roofline.blockdiff", "moe.expert_roofline.blockdiff",
        "paged_attention_roofline.blockdiff"]
    e2e = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}
    assert CELL in e2e["out_tok_per_s"] and e2e["setup_s"] is None
    mix = real().traffic
    assert set(mix["limits"]["sdar-30b-a3b-serve"]) == {
        "served_logit_gap", "served_order_gap", "served_wide_share",
        "served_worst_gap"}
    assert (mix["answer_min"], mix["answer_max"]) == (256, 1024)
    assert (mix["clients"], mix["block"], mix["blocks"]) == (128, 64, 14)


def test_the_roofline_readers_read_nothing_where_there_is_nothing():
    """A program without the counters or the kernels (the parent commit):
    None, never an error and never 0."""
    from benchmarks.metrics import blockdiff

    ctx = {"cell": real(), "trace": None, "stats0": {"rounds": 1},
           "stats1": {"rounds": 2}, "window_s": 1.0, "slots": 64,
           "device_kind": "TPU v5 lite"}
    mods = {"single": "^jit__tick$", "fused": "^jit__step_fused$", "fuse": 8}
    assert blockdiff.growth_ratio(ctx, "block_passes", "blocks_committed") is None
    assert blockdiff.load_max_over_mean(ctx) is None
    assert blockdiff.expert_roofline(ctx, "ragged-dot") is None
    assert blockdiff.paged_attention_roofline(ctx, "paged_block_attention") is None
    assert blockdiff.pass_roofline(ctx, **mods) is None
    red = {"window_s": 0.5, "module_s": {"jit__tick": [0.01, 0.01]},
           "op_s": {"%fusion.1 = bf16[8]{0} fusion(%x)": 0.01}}
    assert blockdiff.pass_roofline(dict(ctx, trace=red), **mods) is None
    # with the counters and the kernels: shares of what was counted
    st1 = {"rounds": 2, "moe_expert_visits": 4 * 768, "kv_pages_walked": 4000,
           "block_passes": 4 * 64, "blocks_committed": 50, "moe_pairs": 8192,
           "moe_load_max": 4 * 6 * 30}
    st0 = dict.fromkeys(st1, 0)
    red["op_s"] = {
        "%ragged-dot-none.1 = bf16[2048,768]{1,0} custom-call(%a)": 0.012,
        "%ragged-dot-none.9 = bf16[8192,768]{1,0} custom-call(%a)": 0.5,
        "%paged_block_attention.3 = bf16[64,128,128]{2,1,0} custom-call(%q)": 0.002}
    ctx = dict(ctx, trace=red, stats0=st0, stats1=st1)
    d = real().dims
    visits = 4 * 768 * 0.5         # half the window was traced
    assert blockdiff.expert_roofline(ctx, "ragged-dot") == pytest.approx(
        100 * visits * sdar_moe.expert_bytes(d) / 819e9 / 0.012)
    assert blockdiff.paged_attention_roofline(
        ctx, "paged_block_attention") == pytest.approx(
        100 * 2000 * 196608 / 819e9 / 0.002)
    least = (2 * sdar_moe.dense_pass_bytes(d) + visits * sdar_moe.expert_bytes(d)
             + 2000 * 196608) / 819e9
    assert blockdiff.pass_roofline(ctx, **mods) == pytest.approx(100 * least / 0.02)
    assert blockdiff.load_max_over_mean(ctx) == pytest.approx(720 * 128 / 8192)


def test_a_fault_in_one_slot_is_not_spread_over_the_others():
    """`judged` takes the largest over the checked requests, and the wide
    share counts states where the mean dilutes them: five sound requests
    and one with a fiftieth of its states off by 1.5 read a share of 2%,
    a mean of 0.04 and the worst state, whatever the others read."""
    import numpy as np

    def request(n, wide=0):
        gap = np.full(n, 0.01, np.float32)
        gap[:wide] = 1.5
        return {"gap": gap, "order": np.zeros(n, np.float32),
                "judged": np.ones(n, bool)}

    got = sdar_moe.judged([request(800) for _ in range(5)] + [request(500, 10)])
    assert got["served_wide_share"] == pytest.approx(2.0)
    assert got["served_logit_gap"] == pytest.approx((490 * 0.01 + 15) / 500)
    assert got["served_worst_gap"] == pytest.approx(1.5)
    assert got["served_order_gap"] == 0.0
    assert sdar_moe.judged([request(800)])["served_wide_share"] == 0.0
    assert set(got) == set(sdar_moe.JUDGED)


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
        "malformed_answers", "served_logit_gap", "served_order_gap",
        "served_wide_share", "served_worst_gap", "reference_control_gap",
        "failed_requests"}
    # bfloat16 against float32 at toy widths: under the toy mix's limits;
    # the 4-bit control is reported beside them and never judged
    assert line["checks"]["reference_control_gap"]["value"] > 0
    assert line["checks"]["reference_control_gap"]["limit"] == 1e30
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["server.compiles.blockdiff"] == 0
    assert 2.0 <= m["diffusion.passes_per_block.blockdiff"] <= 5.0
    assert m["moe.pairs_per_visit.blockdiff"] >= 1.0
    assert m["moe.load_max_over_mean.blockdiff"] >= 1.0
    assert m["model.mfu.blockdiff"] > 0
    assert 0 < m["kvcache.pages_walked_share.blockdiff"] <= 100
    # no device trace on the CPU: the shares are left out, not 0
    assert not [k for k in m if "roofline" in k]


def test_altered_token_on_the_toy_cell_is_not_correct(toy):
    cell = spec.cell(TOY)
    res = spec.driver(cell)(cell, 21, 0.5, False, time.monotonic(),
                            require_tpu=False, break_served=_alter_tokens)
    assert res["correct"] is False
    gap = {n: (v, lim) for n, v, lim in res["checks"]}["served_logit_gap"]
    assert gap[0] > gap[1]
