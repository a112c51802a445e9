"""A layer that holds a share of the experts works on the held pairs'
rows alone (ops/moe.py: `compact_bound`, `dropless_mlp(bound=...)`): the
compacted call against the whole-rows one at toy sizes on the CPU, the
calls that overrun the window, the rule over the benchmark's own shapes,
and the two counters a rung hands the decoder."""

import importlib
import json
import pathlib
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
D, F, K, E, T = 64, 32, 4, 4, 256


def layer(share: int, live: bool, idx=None, seed: int = 0):
    """The arguments of `dropless_mlp` for a layer that holds experts
    E .. 2E - 1 of E x `share`, T tokens of K pairs; `idx` maps the
    router's choice to another."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (T, D), jnp.float32)
    scores = jax.nn.softmax(jax.random.normal(keys[1], (T, E * share)))
    gate_vals, gate_idx = jax.lax.top_k(scores, K)
    if idx is not None:
        gate_idx = idx(gate_idx)
    w = [jax.random.normal(key, shape, jnp.float32) * 0.1
         for key, shape in zip(keys[2:5], ((E, D, F), (E, D, F), (E, F, D)))]
    alive = jax.random.uniform(keys[5], (T,)) < 0.7 if live else None
    return (x, gate_vals, gate_idx, *w, alive)


def both(args, bound, dtype):
    """(y, counts) of the whole-rows call and of the one compacted to
    windows of `bound`, as float32 arrays."""
    import jax

    from kubeflow_tpu.ops.moe import dropless_mlp

    cfg = SimpleNamespace(dtype=dtype)
    return [[np.asarray(a, np.float32) for a in jax.jit(
        lambda *args, b=b: dropless_mlp(cfg, *args, False, E, b))(*args)]
        for b in (None, bound)]


# -- the compacted call against the whole-rows one -------------------------------------

@pytest.mark.parametrize("live", [False, True], ids=["all-live", "padded"])
@pytest.mark.parametrize("share", [8, 16])
def test_compacted_rows_give_the_whole_rows_result(share, live):
    """Shares of 1/8 and 1/16, with and without padding rows: the same
    counts, and y equal to bfloat16's rounding (the same addends in
    float32, in another order)."""
    import jax.numpy as jnp

    from kubeflow_tpu.ops.moe import compact_bound

    bound = compact_bound(T * K, E, E * share)
    assert bound == 512 < T * K
    (whole, counts), (got, counts_c) = both(
        layer(share, live), bound, jnp.bfloat16)
    assert (counts == counts_c).all() and 0 < counts.sum() < bound
    assert np.abs(whole).max() > 0.05
    assert np.abs(got - whole).max() <= 2.0 ** -8 * np.abs(whole).max()
    # in float32 the two agree to the last bits: nothing is left out
    (whole, _), (got, _) = both(layer(share, live), bound, jnp.float32)
    assert np.abs(got - whole).max() <= 1e-6


@pytest.mark.parametrize("case", ["overrun", "every-pair-held"])
def test_held_pairs_that_overrun_the_bound_are_all_computed(case):
    """Twice the window's pairs (three windows), and a call whose every
    pair is held (eight windows): the result is the whole-rows one, no
    pair dropped, no count clipped."""
    import jax.numpy as jnp

    # the router's choice folded onto two, or one, share's worth of ids
    fold = {"overrun": 2 * E, "every-pair-held": E}[case]
    args = layer(8, False, idx=lambda i: E + i % fold)
    bound = 128
    (whole, counts), (got, counts_c) = both(args, bound, jnp.float32)
    held = int(counts.sum())
    assert (counts == counts_c).all()
    assert 2 * bound < held < T * K if case == "overrun" else held == T * K
    assert np.abs(got - whole).max() <= 1e-6
    assert np.abs(whole).max() > 0.05


def test_a_call_that_holds_no_pair_gives_zeros():
    import jax.numpy as jnp

    args = layer(8, False, idx=lambda i: i % E)     # ids 0 .. E - 1: absent
    (whole, counts), (got, _) = both(args, 128, jnp.float32)
    assert counts.sum() == 0 and not whole.any() and not got.any()


# -- the layer's two counters ------------------------------------------------------------

def moe_block(share: int, held: str):
    """A MoEBlock that holds E of E x share experts and its parameters;
    `held` "mean": the experts the router's scores give it, "all": a
    selection bias that sends every pair to the held ones."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    from kubeflow_tpu.models.transformer import TransformerConfig
    from kubeflow_tpu.ops.moe import MoEBlock

    cfg = TransformerConfig(
        d_model=D, d_ff=F, moe_d_ff=F, n_experts=E, n_experts_total=E * share,
        expert_first=E, expert_top_k=K, moe_score="sigmoid",
        dtype=jnp.float32)
    block = MoEBlock(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, T, D), jnp.float32)
    params = meta.unbox(block.init(jax.random.PRNGKey(4), x)["params"])
    if held == "all":
        params = dict(params, expert_bias=jnp.zeros(
            (E * share,)).at[E:2 * E].set(2.0))
    return block, params, x


@pytest.mark.parametrize("held,spills", [("mean", 0), ("all", 1)])
def test_the_layer_counts_its_compacted_calls_and_their_spills(held, spills):
    """`moe_compact_calls` 1 where the rule engaged, `moe_compact_spills`
    1 where the held pairs overran the bound (every pair held: T x K over
    a bound of 512), and the layer's result is the whole-rows one's."""
    from kubeflow_tpu.ops import moe

    block, params, x = moe_block(8, held)
    y, mut = block.apply({"params": params}, x, mutable=["diagnostics"])
    diag = {k: int(v[0]) for k, v in mut["diagnostics"].items()
            if k.startswith("moe_") and v[0].dtype.kind == "i"}
    assert diag["moe_compact_calls"] == 1
    assert diag["moe_compact_spills"] == spills
    assert diag["moe_pairs_routed"] == T * K
    assert (diag["moe_pairs"] == T * K) == (held == "all")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(moe, "compact_bound", lambda *a: None)
        want, mut = block.apply({"params": params}, x,
                                mutable=["diagnostics"])
    assert int(mut["diagnostics"]["moe_compact_calls"][0]) == 0
    assert int(mut["diagnostics"]["moe_compact_spills"][0]) == 0
    assert np.abs(np.asarray(y) - np.asarray(want)).max() <= 1e-6
    assert np.abs(np.asarray(want)).max() > 1e-3


def test_a_layer_that_holds_every_expert_sows_neither_counter():
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.transformer import TransformerConfig
    from kubeflow_tpu.ops.moe import MoEBlock

    cfg = TransformerConfig(d_model=D, d_ff=F, moe_d_ff=F, n_experts=E,
                            expert_top_k=2, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, T, D), jnp.float32)
    block = MoEBlock(cfg)
    _, mut = block.apply({"params": block.init(jax.random.PRNGKey(1), x)[
        "params"]}, x, mutable=["diagnostics"])
    assert not [k for k in mut["diagnostics"] if "compact" in k]


# -- the rule, over the benchmark's own shapes -------------------------------------------

def cells():
    return [w["name"] for w in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("cell", cells())
def test_the_rule_over_the_cells_own_shapes(cell):
    """Each tick and the block pass work on every row; each rung of the
    two configurations that hold a share is compacted, to a window that
    is no tick's rows (`moe.expert_roofline.*` finds a tick's grouped
    matmuls by those); a configuration without a share never is."""
    from kubeflow_tpu.ops.moe import compact_bound
    from kubeflow_tpu.runtime.kvcache import prefill_ladder

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    work = next(w for w in bench["workloads"] if w["name"] == cell)
    config = json.loads((ROOT / next(
        c["file"] for c in bench["configs"]
        if c["name"] == work["config"])).read_text())
    kw = importlib.import_module(
        f"benchmarks.arch.{config['arch']}").sizes(config).model_kwargs()
    e, k = kw.get("n_experts", 0), kw.get("expert_top_k", 0)
    e_all = kw.get("n_experts_total", 0) or e
    share = e_all > e
    assert share == (work["config"] in ("trinity-large-serve", "ax-k1-serve"))
    if "serve" not in config:       # the training cell: no mixture layer
        assert not e
        return
    serve = config["serve"]
    tick = serve["decode_slots"] * (kw.get("gen_block", 0) or 1) * k
    assert compact_bound(tick, e, e_all) is None
    rungs = prefill_ladder(serve["prompt_len"], serve["kv_page_size"])
    bounds = [compact_bound(n * k, e, e_all) for n in rungs]
    if not share:
        assert bounds == [None] * len(rungs)
        return
    assert len(rungs) == 4
    # twice the mean of the held pairs, in whole row tiles
    assert bounds == [2 * n * k * e // e_all for n in rungs]
    assert all(b % 128 == 0 and b < n * k for b, n in zip(bounds, rungs))
    assert tick not in bounds


def test_the_bound_has_a_floor_and_engages_only_where_it_is_smaller():
    from kubeflow_tpu.ops.moe import compact_bound

    assert compact_bound(4096, 8, 8) is None        # every expert held
    assert compact_bound(512, 12, 192) is None      # a tick: 512 rows
    assert compact_bound(513, 12, 192) == 512       # the floor
    assert compact_bound(8192, 12, 192) == 1024
    assert compact_bound(8200, 12, 192) == 1152     # whole row tiles
    assert compact_bound(1024, 4, 8) is None        # twice a half: all


# -- the decoder's counters --------------------------------------------------------------

def test_a_rung_hands_its_counters_to_the_decoder():
    """A toy of `trinity-large-serve` (8 of 32 experts, 4 a token) behind
    `SlotDecoder`: the 128 rung works on every row and the 256 to 512
    rungs are compacted, so `moe_compact_calls` grows by the mixture
    layers of an admission at those rungs alone; nothing overran; the
    ticks' counters mean ticks as before."""
    import dataclasses

    from kubeflow_tpu.ops.moe import compact_bound
    from kubeflow_tpu.serving.continuous import SlotDecoder, window_pages_for
    from test_afmoe import PAGE, SEED, arch, toy_model

    a, d = arch()
    p, n = 512, 4
    model = toy_model(kv_pages=2 * 140 + 1, kv_page_size=PAGE,
                      max_seq_len=p + n)
    model = model.clone(cfg=dataclasses.replace(
        model.cfg, kv_window_pages=window_pages_for(
            model.cfg, 2, p, n, prefix_cache=False)))
    moe_layers = d.layers - d.dense_layers
    assert [compact_bound(r * d.top_k, 8, 32) for r in (128, 256, 384, 512)] \
        == [None, 512, 768, 1024]
    dec = SlotDecoder(model, {"params": a.make_program_params(d, SEED)},
                      slots=2, prompt_len=p, max_new_tokens=n,
                      prefix_cache=False)
    try:
        assert dec.stats()["moe_compact_calls"] == 0
        rng = np.random.default_rng(5)
        calls = []
        for length in (100, 300, 500):
            got = dec.submit(rng.integers(1, d.vocab, length).tolist(), n)
            assert len(got) == n
            calls.append(dec.stats()["moe_compact_calls"])
        st = dec.stats()
    finally:
        dec.close()
    assert calls == [0, moe_layers, 2 * moe_layers]
    assert st["moe_compact_spills"] == 0
    assert st["moe_pairs_routed"] == 3 * n * d.top_k * moe_layers
