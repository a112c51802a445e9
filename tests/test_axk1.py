"""Latent attention (MLA) through the one scheduler loop and a latent page
pool, a group-limited sigmoid router and one chip's share of the experts,
against the plain reference of benchmarks/arch/axk1.py on seeded weights,
on the CPU at toy widths: a prompt's rung and then ticks through the
latent pages against the reference's one full forward, the absorbed and
the up-projected form of one position, the Pallas kernel (interpreted)
against its gather form, the grouped choice against a hand-written loop,
YaRN's frequencies against the formula, the sixteen shares against the
uncut layer, the planted faults, the rules and the refusals.

The program runs in float32 here, so that it and the reference agree to
rounding of the last bits and a fault of any size shows; on the chip it
runs in bfloat16 against the limits of the mix's file (PERF.md)."""

import math
import threading
import time

import numpy as np
import pytest

CONFIG = dict(
    hidden_size=64, num_hidden_layers=3, first_k_dense_replace=1,
    num_attention_heads=4, q_lora_rank=24, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, n_routed_experts=8,
    published=dict(n_routed_experts=32), share=dict(expert_first=8),
    num_experts_per_tok=4, n_shared_experts=1, n_group=8, topk_group=4,
    norm_topk_prob=True, routed_scaling_factor=2.5, scoring_func="sigmoid",
    vocab_size=128, rope_theta=1e4, rms_norm_eps=1e-6,
    rope_scaling=dict(type="yarn", factor=32, beta_fast=32, beta_slow=1,
                      mscale=1, mscale_all_dim=1,
                      original_max_position_embeddings=32))
SEED = 13
P, N, PAGE, SLOTS = 96, 40, 4, 3
# (prompt length, tokens asked for): slots of unequal length, a prompt
# that fills the row; six requests on three slots, so that every slot is
# freed and admitted again while the others tick
REQUESTS = [(90, 40), (20, 12), (96, 33), (61, 40), (7, 25), (75, 9)]
# float32 against float32, the absorbed form against the up-projected:
# the two round differently in the last bits of a 32-wide latent
GAP = 3e-4


def arch(**over):
    from benchmarks.arch import axk1

    return axk1, axk1.sizes(dict(CONFIG, **over))


def toy_model(d=None, **kw):
    import jax.numpy as jnp

    from kubeflow_tpu.models.registry import get_model

    d = d or arch()[1]
    return get_model("transformer-test", **{
        **d.model_kwargs(), "max_seq_len": P + N, "dtype": jnp.float32, **kw})


def paged_model(**kw):
    return toy_model(kv_pages=SLOTS * 34 + 1, kv_page_size=PAGE, **kw)


def decoder(model, a, d, **kw):
    from kubeflow_tpu.serving.continuous import SlotDecoder

    return SlotDecoder(model, {"params": a.make_program_params(d, SEED)},
                       slots=SLOTS, prompt_len=P, max_new_tokens=N,
                       **{"prefix_cache": False, **kw})


def prompts_of(d):
    rng = np.random.default_rng(0)
    return [rng.integers(1, d.vocab, n).tolist() for n, _ in REQUESTS]


def serve(dec, prompts):
    got = {}

    def go(i):
        time.sleep(0.02 * i)        # arrivals spread over the others' ticks
        got[i] = dec.submit(prompts[i], REQUESTS[i][1])

    threads = [threading.Thread(target=go, args=(i,))
               for i in range(len(REQUESTS))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    return got


@pytest.fixture(scope="module")
def served():
    """The six requests through one SlotDecoder of three slots over the
    latent pool: each request's prompt and what came back, the counts."""
    a, d = arch()
    dec = decoder(paged_model(), a, d)
    assert not dec._fresh       # the CPU's attention is the reference's
    prompts = prompts_of(d)
    got = serve(dec, prompts)
    stats = dec.stats()
    dec.alloc.check()
    cache = dec.state[0]
    dec.close()
    return {"prompts": prompts, "got": got, "stats": stats,
            "pools": {k: v["attn"]["latent_pages"].shape
                      for k, v in cache.items()}}


def gaps(served, i, **fault):
    a, d = arch()
    return a.served_gaps(d, SEED, served["prompts"][i], served["got"][i],
                         192, N, **fault)


# -- the system against the reference ----------------------------------------------

@pytest.mark.parametrize("i", range(len(REQUESTS)),
                         ids=[f"L{n}-new{m}" for n, m in REQUESTS])
def test_program_agrees_with_the_reference_at_every_token(served, i):
    """Each served token is the reference's first at its position: the
    rung (gathered here, so absorbed), then every tick in the absorbed
    form through the latent pages, against one up-projected forward."""
    assert len(served["got"][i]) == REQUESTS[i][1]
    assert float(gaps(served, i).max()) <= GAP


def test_the_cache_is_one_latent_pool_a_layer_and_the_counters_say_so(served):
    """[kv_pages, page size, the latent and the rotated part in whole
    lane tiles], nothing of K or V; `stats()` has the bytes a position
    takes, the ticks and the rule's choice (gather off the
    TPU), and the mixture's counters of a share."""
    _, d = arch()
    st = served["stats"]
    assert served["pools"] == {
        f"layer_{i}": (SLOTS * 34 + 1, PAGE, 128) for i in range(d.layers)}
    assert st["kv_latent_row_bytes"] == 4 * 128
    ticks = sum(m for _, m in REQUESTS)
    assert st["ticks"] >= max(m for _, m in REQUESTS)
    assert st["attn_latent_kernel_ticks"] == 0
    assert st["completed"] == len(REQUESTS) and st["kv_pages_used"] == 0
    assert 0 < st["kv_pages_walked"] < st["kv_pages_tabled"]
    moe_layers = d.layers - d.dense_layers
    assert st["moe_pairs_routed"] == ticks * d.top_k * moe_layers
    # 8 of 32 experts, two of the eight groups, four of which a token
    # chooses in: a quarter in the mean
    assert 0.1 < st["moe_pairs"] / st["moe_pairs_routed"] < 0.45
    assert st["moe_kernel_pairs"] == 0


# -- faults the comparison must fail -----------------------------------------------

FAULTS = {
    "float8-reference": dict(lowp="float8_e4m3fn"),
    "three-experts-of-four": dict(top_k=3),
    "plain-top-k-for-the-grouped-choice": dict(no_groups=True),
    "yarn-left-out": dict(no_yarn=True),
    "mscale-left-out-of-the-scale": dict(no_mscale=True),
    "kv-latent-norm-left-out": dict(no_kv_norm=True),
    # the float32 control the chip cannot tell (under bfloat16's step)
    "bias-in-the-weight": dict(bias_in_weight=True),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_planted_fault_fails_the_comparison(served, fault):
    """The controls of the chip run at toy size: the reference with the
    fault in it judges the sound program's answers, and the gap leaves
    the room that rounding needs by orders of magnitude."""
    import jax.numpy as jnp

    kw = dict(FAULTS[fault])
    if "lowp" in kw:
        kw["lowp"] = getattr(jnp, kw["lowp"])
    worst = max(float(gaps(served, i, **kw).max())
                for i in range(len(REQUESTS)))
    # (the bias is a hundredth of a score, in two layers' held quarter of
    # the pairs: the smallest of the faults, eight times the rounding)
    assert worst > (5 if fault == "bias-in-the-weight" else 100) * GAP, worst


def test_the_reference_in_bfloat16_passes_where_float8_fails(served):
    import jax.numpy as jnp

    a, _ = arch()
    read = {name: a.judged([gaps(served, i, lowp=getattr(jnp, name))
                            for i in range(len(REQUESTS))])
            for name in ("bfloat16", "float8_e4m3fn")}
    assert read["float8_e4m3fn"]["served_logit_gap"] \
        > 5 * read["bfloat16"]["served_logit_gap"]
    assert read["bfloat16"]["served_logit_gap"] < 0.02
    assert read["float8_e4m3fn"]["served_worst_gap"] > 0.1


# -- the two forms of one arithmetic ------------------------------------------------

def _rung(model, params, d, fresh, lq=128, pad=37, mp=40):
    import jax.numpy as jnp

    from kubeflow_tpu.runtime import kvcache

    toks = jnp.asarray(np.random.default_rng(1).integers(
        1, d.vocab, (1, lq)), jnp.int32).at[0, :pad].set(0)
    table = jnp.arange(1, mp + 1, dtype=jnp.int32)[None]
    return model.apply(
        params | {"cache": kvcache.init_paged_cache(model, mp)}, toks,
        train=False, decode_index=jnp.zeros((1,), jnp.int32),
        mutable=["cache"], pad_len=jnp.asarray([pad], jnp.int32),
        page_table=table, fresh=fresh)


@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_the_absorbed_and_the_up_projected_form_agree(impl):
    """One rung, left-padded: the up-projected form over its own keys of
    24 and values of 16 (`fresh`: XLA's attention, and the flash kernel
    interpreted, which takes the pair as it is) and the absorbed form
    gathered from the pages it has just written give the same logits at
    the last position, and both write the same latents."""
    a, d = arch()
    model = paged_model(attention_impl=impl, max_seq_len=256)
    params = {"params": a.make_program_params(d, SEED)}
    (gather, gmut), (fresh, fmut) = (
        _rung(model, params, d, f) for f in (False, True))
    assert fresh.shape == (1, 1, d.vocab) and gather.shape[1] == 128
    assert float(np.abs(fresh[0, 0] - gather[0, -1]).max()) <= GAP
    from benchmarks.lib.weights import seed_key

    want = np.asarray(a.sequence_logits(
        d, seed_key(SEED),
        np.random.default_rng(1).integers(1, d.vocab, (1, 128))[0, 37:]))
    assert float(np.abs(np.asarray(fresh[0, 0]) - want[-1]).max()) <= GAP
    for i in range(d.layers):
        g, f = (np.asarray(m["cache"][f"layer_{i}"]["attn"]["latent_pages"])
                for m in (gmut, fmut))
        pos = np.arange(37, 128)        # the real positions' rows
        pages, offs = 1 + pos // PAGE, pos % PAGE
        assert np.abs(g[pages, offs] - f[pages, offs]).max() <= GAP
        # the row: the latent, the rotated part, zeros
        assert np.abs(g[pages, offs, :d.kv_rank + d.rope]).min() > 0
        assert not g[:, :, d.kv_rank + d.rope:].any()


def test_a_plain_forward_is_the_reference(served):
    """Without a cache (the trainer's path) the layer is the up-projected
    form, through `attention` with the softmax scale YaRN gives."""
    import jax.numpy as jnp

    from benchmarks.lib.weights import seed_key

    a, d = arch()
    toks = np.random.default_rng(4).integers(1, d.vocab, (1, 64))
    got = toy_model().apply(
        {"params": a.make_program_params(d, SEED)}, jnp.asarray(toks),
        train=False)
    want = a.sequence_logits(d, seed_key(SEED), toks[0])
    assert float(np.abs(np.asarray(got[0]) - np.asarray(want)).max()) <= GAP


# -- the kernel against its gather form --------------------------------------------

KERNEL_CASES = {
    # (slots, heads, width, rank, page size, pool, row, [start], [last])
    "ragged-ends": (3, 4, 128, 96, 4, 64, 12, [3, 0, 9], [40, 17, 5]),
    "one-page-and-a-full-row": (2, 8, 256, 128, 8, 40, 6, [17, 0], [20, 47]),
    "an-idle-slot-between": (4, 16, 128, 64, 4, 96, 20,
                             [0, 9, 0, 30], [70, 3, 0, 79]),
}


@pytest.mark.parametrize("name", list(KERNEL_CASES))
def test_kernel_matches_its_gather_form(name, monkeypatch):
    """The Pallas kernel, interpreted: every head against the streamed
    rows, whole as keys and their first `rank` values as values, over
    start..last of each slot's pages, in blocks of a few pages so that a
    walk has first, middle and last blocks; zeros for an idle slot."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.ops import paged_latent_attention as pla

    b, heads, w, rank, ps, pool_n, mp, start, last = KERNEL_CASES[name]
    monkeypatch.setattr(pla, "PAGES_PER_BLOCK", 4)
    rng = np.random.default_rng(5)
    pool = jnp.asarray(rng.normal(size=(pool_n, ps, w)), jnp.bfloat16)
    pool = pool.at[0].set(1e4)      # the trash page must never be read
    q = jnp.asarray(rng.normal(size=(b, heads, w)), jnp.bfloat16)
    table = jnp.asarray(rng.permutation(np.arange(1, pool_n))[:b * mp]
                        .reshape(b, mp), jnp.int32)
    start, last = jnp.asarray(start, jnp.int32), jnp.asarray(last, jnp.int32)
    got = np.asarray(pla.paged_latent_attention(
        q, pool, table, start, last, scale=0.11, rank=rank), np.float32)
    rows = pool[table].reshape(b, mp * ps, w).astype(jnp.float32)
    s = jnp.einsum("bhw,bsw->bhs", q.astype(jnp.float32), rows) * 0.11
    pos = jnp.arange(mp * ps)[None, None]
    seen = (pos >= start[:, None, None]) & (pos <= last[:, None, None])
    p = jax.nn.softmax(jnp.where(seen, s, -1e30), -1)
    want = np.asarray(jnp.einsum(
        "bhs,bsr->bhr", p.astype(jnp.bfloat16).astype(jnp.float32),
        rows[..., :rank]))
    idle = np.asarray(start > last)
    assert not got[idle].any()
    # bfloat16's step on values of a few units
    assert np.abs(got - want)[~idle].max() <= 2 ** -6 * np.abs(want).max()


def test_a_tick_through_the_kernel_is_the_tick_through_the_gather(monkeypatch):
    """In the model: the rule steered to the kernel (interpreted), a tick
    of three slots at unequal positions, one idle, gives the logits the
    gather gives."""
    import jax.numpy as jnp

    from kubeflow_tpu.ops import paged_latent_attention as pla
    from kubeflow_tpu.runtime import kvcache

    a, d = arch()
    model = paged_model()
    params = {"params": a.make_program_params(d, SEED)}
    rng = np.random.default_rng(6)
    mp = 34
    table = jnp.asarray(np.arange(1, 3 * mp + 1).reshape(3, mp), jnp.int32)
    cache = kvcache.init_paged_cache(model, mp)
    cache = {k: {"attn": {"latent_pages": jnp.asarray(
        rng.normal(size=v["attn"]["latent_pages"].shape), jnp.float32)}}
        for k, v in cache.items()}
    args = dict(train=False, decode_index=jnp.asarray([57, 9, 120], jnp.int32),
                mutable=["cache"], page_table=table,
                pad_len=jnp.asarray([11, 10, 0], jnp.int32))   # slot 1 idle
    toks = jnp.asarray(rng.integers(1, d.vocab, (3, 1)), jnp.int32)
    want, _ = model.apply(params | {"cache": cache}, toks, **args)
    monkeypatch.setattr(pla, "use_kernel", lambda lq, *_: lq == 1)
    got, _ = model.apply(params | {"cache": cache}, toks, **args)
    live = [0, 2]
    assert float(np.abs(np.asarray(got - want))[live].max()) <= 1e-3


# -- the grouped choice --------------------------------------------------------------

def _by_hand(c, n_group, topk_group, k):
    """The choice as one would write it down: python loops over a row."""
    out = []
    for row in np.asarray(c, np.float64):
        size = len(row) // n_group
        score = [sum(sorted(row[g * size:(g + 1) * size])[-2:])
                 for g in range(n_group)]
        best = sorted(range(n_group), key=lambda g: -score[g])[:topk_group]
        allowed = [e for g in best for e in range(g * size, (g + 1) * size)]
        out.append(sorted(sorted(allowed, key=lambda e: -row[e])[:k]))
    return out


def test_the_grouped_choice_is_the_hand_written_loop():
    """The reference's `choose` and the program's router pick, for every
    row, the 8 largest inside the 4 of 8 groups of 24 whose two largest
    sum highest (the published sizes); a plain top-8 picks otherwise."""
    import jax.numpy as jnp

    from kubeflow_tpu.ops.moe import _within_best_groups

    a, d = arch(n_routed_experts=192, published={}, n_group=8, topk_group=4,
                num_experts_per_tok=8)
    c = np.random.default_rng(7).uniform(0, 1, (200, 192)).astype(np.float32)
    want = _by_hand(c, 8, 4, 8)
    ref = np.sort(np.asarray(a.choose(d, jnp.asarray(c))), -1).tolist()
    import jax

    prog = np.sort(np.asarray(jax.lax.top_k(
        _within_best_groups(jnp.asarray(c), 8, 4), 8)[1]), -1).tolist()
    assert ref == want and prog == want
    plain = np.sort(np.asarray(a.choose(d, jnp.asarray(c), no_groups=True)),
                    -1).tolist()
    assert sum(p != w for p, w in zip(plain, want)) > 50


def test_one_group_is_todays_router():
    """`moe_n_group` 1 / `moe_topk_group` 1 traces the program the router
    was (the pinned jaxprs of tests/test_block_diffusion.py and
    tests/test_afmoe.py hold the step programs to it), and a mixture
    layer gives what it gave: the same output as a layer whose every
    expert lies in one group that is always kept."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.transformer import TransformerConfig
    from kubeflow_tpu.ops.moe import MoEBlock

    kw = dict(d_model=32, d_ff=48, moe_d_ff=16, n_experts=8, expert_top_k=2,
              moe_score="sigmoid", moe_route_scale=2.5, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 32), jnp.float32)
    block = MoEBlock(TransformerConfig(**kw))
    params = block.init(jax.random.PRNGKey(2), x)
    params = jax.tree.map(lambda p: p, params)
    params["params"]["expert_bias"] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(3), (8,))
    one = block.apply(params, x)
    text = str(jax.make_jaxpr(lambda p: block.apply(p, x))(params))
    for n_group, topk_group in ((1, 1), (2, 2), (4, 4)):
        cfg = TransformerConfig(**kw, moe_n_group=n_group,
                                moe_topk_group=topk_group)
        np.testing.assert_array_equal(
            np.asarray(MoEBlock(cfg).apply(params, x)), np.asarray(one))
    same = MoEBlock(TransformerConfig(**kw, moe_n_group=1, moe_topk_group=1))
    assert str(jax.make_jaxpr(lambda p: same.apply(p, x))(params)) == text
    # and groups that limit the choice do change it
    cut = MoEBlock(TransformerConfig(**kw, moe_n_group=4, moe_topk_group=1))
    assert np.abs(np.asarray(cut.apply(params, x)) - np.asarray(one)).max() \
        > 1e-3
    with pytest.raises(ValueError, match="group-limited routing"):
        MoEBlock(TransformerConfig(**dict(kw, moe_score="softmax"),
                                   moe_n_group=2)).apply(params, x)


# -- YaRN ----------------------------------------------------------------------------

def test_yarn_frequencies_are_the_formula():
    """At the published sizes (64 rotary values, theta 1e4, factor 32 over
    4,096, beta 32 and 1): pairs 0..10 keep theta's own frequency, pairs
    23 and on are divided by 32, a linear ramp between; the program's and
    the reference's, each written on its own, agree to float32; the
    softmax scale is 192 ** -0.5 x (0.1 ln 32 + 1) ** 2."""
    from kubeflow_tpu.models.transformer import yarn_inv_freq, yarn_mscale

    a, d = arch(qk_rope_head_dim=64, qk_nope_head_dim=128, rope_scaling=dict(
        CONFIG["rope_scaling"], original_max_position_embeddings=4096))
    prog = yarn_inv_freq(64, 1e4, 32.0, 4096, 32.0, 1.0)
    ref = a.inv_freq(d)
    plain = 1e4 ** (-np.arange(32) / 32.0)
    low = 64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(1e4))
    high = 64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(1e4))
    assert (math.floor(low), math.ceil(high)) == (10, 23)
    want = np.array([
        plain[i] * (1 - t) + plain[i] / 32 * t
        for i in range(32) for t in [min(1.0, max(0.0, (i - 10) / 13))]])
    np.testing.assert_allclose(prog, want, rtol=1e-6)
    np.testing.assert_allclose(ref, want, rtol=1e-6)
    np.testing.assert_allclose(prog[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(prog[23:], plain[23:] / 32, rtol=1e-6)
    assert a.softmax_scale(d) == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(32) + 1) ** 2)
    assert yarn_mscale(32.0, 1.0) == pytest.approx(0.1 * math.log(32) + 1)
    assert yarn_mscale(1.0, 1.0) == 1.0
    # no rope_scaling: plain rotary, plain scale
    _, bare = arch(rope_scaling=None)
    np.testing.assert_allclose(a.inv_freq(bare),
                               1e4 ** (-np.arange(4) / 4.0), rtol=1e-6)
    assert a.softmax_scale(bare) == pytest.approx(24 ** -0.5)


# -- the share -----------------------------------------------------------------------

def test_sixteen_shares_of_twelve_experts_sum_to_the_uncut_layer():
    """16 chips x 12 experts of a 192-expert layer in 8 groups of 24 (a
    chip holds half a group, as the cell's does): the parts of the result
    that the shares give, with the shared expert (which every chip
    computes alike) counted once, add up to what the uncut reference
    gives for the whole layer; each share is what the reference gives for
    the same share; every routed pair lands on exactly one chip."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.transformer import TransformerConfig
    from kubeflow_tpu.ops.moe import MoEBlock

    sizes = dict(n_group=8, topk_group=4, num_experts_per_tok=8)
    a, whole = arch(n_routed_experts=192, published={},
                    share=dict(expert_first=0), **sizes)
    key = jax.random.PRNGKey(5)
    w = {k: v.astype(jnp.float32)
         for k, v in a.layer_leaves(whole, key, 1).items()}
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 20, whole.d), jnp.float32)
    rows = x.reshape(-1, whole.d)
    want = a.mixture(whole, rows, w)
    shared = a.swiglu(rows, w["shared_gate"], w["shared_up"], w["shared_down"])
    live = jnp.ones((2, 20), bool).at[0, :3].set(False)
    keep = np.asarray(live).reshape(-1)
    parts, pairs = [], 0
    for chip in range(16):
        _, d = arch(n_routed_experts=12,
                    published=dict(n_routed_experts=192),
                    share=dict(expert_first=12 * chip), **sizes)
        kw = {k: v for k, v in d.model_kwargs().items()
              if k not in ("layer_pattern", "n_layers")}
        held = slice(12 * chip, 12 * chip + 12)
        params = {
            "router": {"kernel": w["router"]}, "expert_bias": w["expert_bias"],
            "w_gate": w["w_gate"][held], "w_up": w["w_up"][held],
            "w_down": w["w_down"][held],
            **{n: {"kernel": w[n]}
               for n in ("shared_gate", "shared_up", "shared_down")}}
        got, mut = MoEBlock(TransformerConfig(dtype=jnp.float32, **kw)).apply(
            {"params": params}, x, live, mutable=["diagnostics"])
        got = np.asarray(got).reshape(-1, whole.d)
        ref = a.mixture(whole, rows, dict(
            w, w_gate=w["w_gate"][held], w_up=w["w_up"][held],
            w_down=w["w_down"][held]), held=(12 * chip, 12))
        assert np.abs(got - np.asarray(ref))[keep].max() <= 1e-5
        parts.append(got - np.asarray(shared))
        diag = {k: int(v[0]) for k, v in mut["diagnostics"].items()
                if k.startswith("moe_pairs")}
        assert diag["moe_pairs_routed"] == keep.sum() * whole.top_k
        pairs += diag["moe_pairs"]
    total = np.asarray(shared) + sum(parts)
    assert np.abs(total - np.asarray(want))[keep].max() <= 1e-5
    assert pairs == keep.sum() * whole.top_k


# -- the flash prefill through the decoder -------------------------------------------

def test_flash_prefill_through_the_decoder():
    """A decoder whose prefix cache is off and whose attention is the
    flash kernel's (interpreted here) takes the rung's own attention in
    the up-projected form, keys of 24 and values of 16, writes latents,
    and its ticks read them absorbed: every token is the reference's."""
    from kubeflow_tpu.serving.continuous import SlotDecoder

    a, d = arch()
    p, n = 512, 16
    model = toy_model(kv_pages=2 * 140 + 1, kv_page_size=PAGE,
                      attention_impl="flash", max_seq_len=p + n)
    dec = SlotDecoder(model, {"params": a.make_program_params(d, SEED)},
                      slots=2, prompt_len=p, max_new_tokens=n,
                      prefix_cache=False)
    try:
        assert dec._fresh
        rng = np.random.default_rng(2)

        def ask(length):
            prompt = rng.integers(1, d.vocab, length).tolist()
            got = dec.submit(prompt, n)
            return float(a.served_gaps(d, SEED, prompt, got, 1024, n).max())

        assert max(ask(300), ask(120)) <= GAP
        dec.alloc.check()
        assert dec.stats()["prefill_flash"] == 2
    finally:
        dec.close()


# -- the rules and the refusals -------------------------------------------------------

def test_the_rules_say_what_runs_and_refuse_what_cannot(caplog):
    import logging

    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.registry import get_model
    from kubeflow_tpu.ops import paged_latent_attention as pla
    from kubeflow_tpu.ops.attention import resolve_impl
    from kubeflow_tpu.serving.continuous import (SlotDecoder,
                                                 _fresh_prefill_rule)
    from kubeflow_tpu.serving.server import serve_lm_generator

    a, d = arch()
    variables = {"params": a.make_program_params(d, SEED)}
    # the kernel's rule, from backend, chunk, row width and dtype
    with caplog.at_level(logging.INFO, logger=pla.log.name):
        assert not pla.use_kernel(1, (9, 16, 640), jnp.bfloat16)
        assert not pla.use_kernel(128, (9, 16, 640), jnp.bfloat16)
    assert "latent attention -> gather (default backend is 'cpu'" \
        in caplog.text
    assert "a chunk of 128 queries a slot" in caplog.text
    # the flash rule: keys of 192 and values of 128 are a pair it takes
    assert resolve_impl("flash", 192, v_dim=128) == "flash"
    assert resolve_impl("auto", 192, v_dim=128) == "reference"   # the CPU
    cfg = toy_model(kv_pages=281, kv_page_size=PAGE,
                    attention_impl="flash").cfg
    assert _fresh_prefill_rule(cfg, 512, prefix_cache=False, draft=False)[0]
    use, why = _fresh_prefill_rule(cfg, 512, prefix_cache=True, draft=False)
    assert not use and "latent attention with the prefix cache on" in why
    # what cannot run says so
    with pytest.raises(ValueError, match="paged KV cache on one device"):
        SlotDecoder(toy_model(), variables, slots=2, prompt_len=P,
                    max_new_tokens=N)
    draft = get_model("transformer-test", vocab_size=d.vocab, max_seq_len=256)
    dvars = draft.init(jax.random.PRNGKey(0), np.zeros((1, 1), np.int32),
                       train=False)
    with pytest.raises(ValueError, match="no\\s+draft_model"):
        SlotDecoder(paged_model(), variables, slots=2, prompt_len=P,
                    max_new_tokens=N, draft_model=draft,
                    draft_variables=dvars)
    with pytest.raises(ValueError, match="latent attention"):
        SlotDecoder(paged_model(gen_block=4, gen_mask_id=1), variables,
                    slots=2, prompt_len=P, max_new_tokens=N)
    for more, what in ((dict(kv_cache_dtype="int8"), "no int8 cache"),
                       (dict(rolling_kv_cache=True), "rolling_kv_cache")):
        with pytest.raises(ValueError, match=what):
            _rung(paged_model(max_seq_len=256, **more), variables, d, False)
    with pytest.raises(ValueError, match="exclusive with rolling_kv_cache"):
        serve_lm_generator(
            "m", "transformer-test", prompt_len=P, max_new_tokens=N,
            continuous_batching=True, kv_pages=99, kv_page_size=PAGE,
            **dict(d.model_kwargs(), rolling_kv_cache=True))
    with pytest.raises(ValueError, match="latent layer with a window"):
        toy_model(layer_pattern=[dict(latent=True, window=8)] * d.layers)
    with pytest.raises(ValueError, match="is a latent layer's"):
        get_model("transformer-test", rope_factor=4.0, rope_original_max=64)


def test_the_prefix_cache_on_serves_the_same_tokens_through_the_gather():
    """With the prefix cache on the rung gathers (the rule's answer) and
    a second request that shares a prompt's pages reads the hit's latents
    absorbed: the same tokens as without a cache."""
    a, d = arch()
    rng = np.random.default_rng(8)
    shared = rng.integers(1, d.vocab, 64).tolist()
    prompts = [shared + rng.integers(1, d.vocab, 20).tolist()
               for _ in range(2)]
    out = {}
    for prefix_cache in (False, True):
        dec = decoder(paged_model(), a, d, prefix_cache=prefix_cache)
        try:
            out[prefix_cache] = [dec.submit(p, 12) for p in prompts]
            hits = dec.stats()["prefix_hit_pages"]
        finally:
            dec.close()
    assert out[True] == out[False] and hits > 0
    for p, got in zip(prompts, out[True]):
        assert float(a.served_gaps(d, SEED, p, got, 192, N).max()) <= GAP


def test_server_serves_the_latent_model_over_http_path():
    """`serve_lm_generator` -> SlotDecoder -> TokenStep -> the one
    PageAllocator -> TransformerLM with latent layers, by the model's
    keywords alone."""
    import jax.numpy as jnp

    from kubeflow_tpu.serving.server import serve_lm_generator

    _, d = arch()
    prompt = np.random.default_rng(3).integers(1, d.vocab, 70).tolist()
    sm = serve_lm_generator(
        "latent", "transformer-test", prompt_len=P, max_new_tokens=N,
        continuous_batching=True, decode_slots=2, kv_pages=2 * 34 + 1,
        kv_page_size=PAGE, prefix_cache=False, seed=SEED,
        dtype=jnp.float32, **d.model_kwargs())
    out = np.asarray(sm.predict_fn(
        {"tokens": [prompt], "max_new_tokens": [N]})).tolist()
    assert len(out[0]) == N


def test_the_latent_programs_keep_the_names_the_benchmark_reads():
    import re

    import jax
    import jax.numpy as jnp

    a, d = arch()
    dec = decoder(paged_model(), a, d)
    try:
        assert sorted(dec._prefill_at) == [24, 48, 72, 96]
        for length, program in dec._prefill_at.items():
            name = re.search(r"HloModule (\w+)", program.as_text()).group(1)
            assert name == "jit__paged_prefill_install", (length, name)
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype),
            (dec._params, dec.state, dec._tables()))
        for jitted, want in ((dec._step, "jit__tick"),
                             (dec._step_fused, "jit__step_fused")):
            text = jitted.lower(*shapes).as_text()
            assert re.search(r"module @(\w+)", text).group(1) == want
        assert dec.state[8].shape == (5,)    # the mixture's counters
    finally:
        dec.close()


def test_flops_per_token_counts_a_latent_layer():
    """The trainer's MFU gauge reads the model's own count: a latent
    layer's five matrices, and attention at keys of nope + rope and
    values of v_head_dim, against the benchmark's count of the same."""
    a, d = arch(n_routed_experts=32, published={})
    model = toy_model(d)
    # forward only, one token, no attention term
    want = a.token_flops(d) + 2.0 * d.d * d.vocab
    assert model.flops_per_token() / 3 == pytest.approx(want)
    seq = 64
    attn = a.attention_flops(d, seq * (seq + 1) // 2) * d.layers / seq
    assert (model.flops_per_token(seq) - model.flops_per_token()) / 3 \
        == pytest.approx(attn)
