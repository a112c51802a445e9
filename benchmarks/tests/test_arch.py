"""An architecture and a driver are modules found by name: the names
resolve, a name nothing has is an error that says which file gave it, and
`dense_gqa` reads on both configuration files what the parent's
benchmarks/lib/opcount.py and weights.py read (the literals are the
parent's, commit ad8aec4, taken before the move)."""

import json
import os

import pytest

from benchmarks.arch import dense_gqa
from benchmarks.lib import spec
from benchmarks.tests import toy

BENCH = spec.benchmark()


def test_names_resolve():
    for w in BENCH["workloads"]:
        cell = spec.cell(w["name"], BENCH)
        assert cell.arch is dense_gqa
        assert cell.config["arch"] == "dense_gqa"
        assert (cell.dims.vocab, cell.dims.layers) == (32000, 8)
        run = spec.driver(cell)
        assert run.__module__.startswith("benchmarks.")
        assert callable(run)
    from benchmarks.drivers import serve_closed, serve_open, train_fit

    by_mix = {w["traffic"]: spec.driver(spec.cell(w["name"], BENCH))
              for w in BENCH["workloads"]}
    assert by_mix == {"chat-saturated": serve_closed.run,
                      "doc-qa-paced": serve_open.run, "ft-8k": train_fit.run}


def _toy_with(tmp_path, config_edit=None, traffic_edit=None):
    """toy-closed with its configuration or its mix edited, as files of
    a root of their own in front of the toy's."""
    bench = toy.bench()
    config = spec.load_json(os.path.join(toy.DATA, "configs", "toy-serve.json"))
    traffic = spec.load_json(os.path.join(toy.DATA, "traffic", "toy-closed.json"))
    (config_edit or (lambda c: None))(config)
    (traffic_edit or (lambda t: None))(traffic)
    os.makedirs(tmp_path / "traffic")
    (tmp_path / "toy-serve.json").write_text(json.dumps(config))
    (tmp_path / "traffic" / "toy-closed.json").write_text(json.dumps(traffic))
    for c in bench["configs"]:
        if c["name"] == "toy-serve":
            c["file"] = str(tmp_path / "toy-serve.json")
    return bench, [str(tmp_path), toy.DATA, spec.BENCH_DIR]


def test_configuration_without_arch_is_an_error_naming_the_file(tmp_path):
    bench, roots = _toy_with(tmp_path, lambda c: c.pop("arch"))
    with pytest.raises(KeyError) as e:
        spec.cell("toy-closed", bench, roots)
    assert "toy-serve.json" in str(e.value) and "has no" in str(e.value)
    assert "dense_gqa" in str(e.value)       # what it could have said


def test_unknown_arch_is_an_error_naming_the_file(tmp_path):
    bench, roots = _toy_with(tmp_path, lambda c: c.update(arch="latent_moe"))
    with pytest.raises(KeyError) as e:
        spec.cell("toy-closed", bench, roots)
    assert "toy-serve.json" in str(e.value) and "latent_moe" in str(e.value)


def test_unknown_driver_is_an_error_naming_the_file(tmp_path):
    bench, roots = _toy_with(
        tmp_path, traffic_edit=lambda t: t.update(driver="serve_blocks"))
    cell = spec.cell("toy-closed", bench, roots)
    with pytest.raises(KeyError) as e:
        spec.driver(cell)
    assert "toy-closed.json" in str(e.value) and "serve_blocks" in str(e.value)
    assert "serve_closed" in str(e.value)


@pytest.mark.parametrize("workload", ["chat-saturated", "ft-8k-1chip"])
def test_dense_gqa_counts_are_the_parents(workload):
    d = spec.cell(workload, BENCH).dims
    assert dense_gqa.train_flops_per_token(d, 8192) == 12463472640.0   # 12.46 G
    assert dense_gqa.weight_bytes(d, 1) == 1875902464
    assert dense_gqa.weight_bytes(d, 2) == 3751804928
    assert dense_gqa.request_flops(d, 757, 215) == 3506675384320
    assert dense_gqa.forward_flops(d, 0, 2960, 1) == 10904052695040
    assert dense_gqa.decode_kv_bytes(d, 757, 215) == 6062178304
    assert dense_gqa.flash_flops(d, 2, 8192) == 19792819912704
    assert dense_gqa.flash_bytes(d, 2, 8192) == 8053063680


def test_dense_gqa_weights_are_the_parents():
    """Seed 0, layer 1 of mistral-7b-train: the first three values of
    three leaves, and of two top leaves (one on a seed past 2**31)."""
    import jax
    import numpy as np

    from benchmarks.lib.weights import seed_key

    d = spec.cell("ft-8k-1chip", BENCH).dims
    first = jax.jit(lambda k: {
        n: v.reshape(-1)[:3]
        for n, v in dense_gqa.layer_leaves(d, k, 1).items()})(seed_key(0))
    want = {
        "q": [0.021293962374329567, -0.0006994300638325512,
              0.036360129714012146],
        "down": [-0.01782446913421154, -0.02292921207845211,
                 0.012782365083694458],
        "ln_mlp": [1.0677320957183838, 0.9905164837837219, 1.003163456916809],
    }
    for n, values in want.items():
        assert np.asarray(first[n]).tolist() == values, n
    head = jax.jit(lambda k: dense_gqa.top_leaf(d, k, "lm_head")
                   .reshape(-1)[:3])(seed_key(0))
    assert np.asarray(head).tolist() == [
        -0.009219649247825146, 0.009284495376050472, -0.012111450545489788]
    emb = jax.jit(lambda k: dense_gqa.top_leaf(d, k, "embedding")
                  .reshape(-1)[:3])(seed_key(2**31 + 5))
    assert np.asarray(emb).tolist() == [
        -1.4843329191207886, 1.1299010515213013, -0.9611269235610962]


def test_program_keywords_merge_the_files_model_kwargs_on_both_paths():
    train = toy.cell("toy-train")
    kw = train.arch.model_kwargs(train, max_seq_len=64)
    assert kw["attention_impl"] == train.config["program"]["model_kwargs"][
        "attention_impl"]
    assert kw["max_seq_len"] == 64 and kw["d_ff"] == 128
    serve = toy.cell("toy-closed")
    assert serve.arch.model_kwargs(serve) == serve.dims.model_kwargs()


def test_a_judged_number_without_a_limit_is_an_error():
    """The driver looks every judged name up in the mix's limits."""
    import dataclasses
    import types

    from benchmarks.lib import schedule, serve

    cell = toy.cell("toy-closed")
    arch = types.SimpleNamespace(
        __name__="an_arch", compare_served=lambda cell, seed, sample: (
            {"served_logit_gap": 0.0, "block_order_gap": 0.0}, {}))
    cell = dataclasses.replace(cell, arch=arch)
    req = schedule.Request(index=0, block=0, prompt=(1, 2, 3), max_new=2,
                           due_s=None)
    measured = [{"ok": True, "req": req, "tokens": [4, 5],
                 "prediction": [4, 5]}]
    with pytest.raises(KeyError) as e:
        serve.check_answers(cell, 1, measured)
    assert "block_order_gap" in str(e.value)
    assert "toy-closed.json" in str(e.value)
