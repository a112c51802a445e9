"""A stack whose layers differ (window and full attention, dense and
mixture MLPs, a share of the experts, a gate on attention, four norms)
through the one scheduler loop and the two-kind paged cache, against the
plain reference of benchmarks/arch/afmoe.py on seeded weights, on the CPU
at toy widths: prefill at a rung and then ticks with window pages
released mid-request, the share of the experts against the uncut layer,
the planted faults, the flash prefill against the gather, the counters.

The program runs in float32 here, so that it and the reference agree to
rounding of the last bits and a fault of any size shows; on the chip it
runs in bfloat16 against the limits of the mix's file (PERF.md)."""

import threading
import time

import numpy as np
import pytest

WINDOW = 32
CONFIG = dict(
    hidden_size=64, num_hidden_layers=5, num_dense_layers=1,
    layer_types=["sliding_attention"] * 4 + ["full_attention"],
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, num_experts=8,
    published=dict(num_experts=32), share=dict(expert_first=8),
    num_experts_per_tok=4, num_shared_experts=1, vocab_size=128,
    rope_theta=1e4, rms_norm_eps=1e-5, sliding_window=WINDOW,
    score_func="sigmoid", route_norm=True, route_scale=2.448,
    mup_enabled=True, n_group=1, topk_group=1)
SEED = 11
P, N, PAGE, SLOTS = 96, 40, 4, 3
# (prompt length, tokens asked for): contexts of 100 and more on a window
# of 32, slots of unequal length, a prompt shorter than the window, one
# that fills the row; seven requests on three slots, so that every slot
# is freed and admitted again while the others tick
REQUESTS = [(90, 40), (20, 12), (96, 33), (61, 40), (7, 25), (75, 9),
            (33, 40)]
GAP = 2e-4       # float32 against float32: the last bits


def arch(**over):
    from benchmarks.arch import afmoe

    return afmoe, afmoe.sizes(dict(CONFIG, **over))


def toy_model(d=None, **kw):
    import jax.numpy as jnp

    from kubeflow_tpu.models.registry import get_model

    d = d or arch()[1]
    return get_model("transformer-test", **{
        **d.model_kwargs(), "max_seq_len": P + N, "dtype": jnp.float32, **kw})


def decoder(model, a, d, **kw):
    from kubeflow_tpu.serving.continuous import SlotDecoder

    return SlotDecoder(model, {"params": a.make_program_params(d, SEED)},
                       slots=SLOTS, prompt_len=P, max_new_tokens=N,
                       prefix_cache=False, **kw)


def two_kind_model(**kw):
    """The toy model as `serve_lm_generator` builds it with the prefix
    cache off: the window layers' pool by the decoder's own rule."""
    import dataclasses

    from kubeflow_tpu.serving.continuous import window_pages_for

    model = toy_model(kv_pages=SLOTS * 34 + 1, kv_page_size=PAGE, **kw)
    return model.clone(cfg=dataclasses.replace(
        model.cfg, kv_window_pages=window_pages_for(
            model.cfg, SLOTS, P, N, prefix_cache=False)))


def prompts_of(d):
    rng = np.random.default_rng(0)
    return [rng.integers(1, d.vocab, n).tolist() for n, _ in REQUESTS]


def serve(dec, prompts, watch=None):
    got = {}

    def go(i):
        time.sleep(0.02 * i)        # arrivals spread over the others' ticks
        got[i] = dec.submit(prompts[i], REQUESTS[i][1])

    threads = [threading.Thread(target=go, args=(i,))
               for i in range(len(REQUESTS))]
    for t in threads:
        t.start()
    while any(t.is_alive() for t in threads):
        if watch:
            watch()
        time.sleep(0.002)
    for t in threads:
        t.join(timeout=600)
    return got


@pytest.fixture(scope="module")
def served():
    """The seven requests through one SlotDecoder of three slots over the
    two-kind paged cache: each request's prompt and what came back, the
    decoder's counts, and the most window pages any slot ever held."""
    from kubeflow_tpu.obs import trace as obs_trace

    a, d = arch()
    model = two_kind_model()
    assert model.cfg.kv_window_pages == SLOTS * 34 + 1   # the gather: whole
    dec = decoder(model, a, d)
    assert dec.alloc.window == WINDOW and not dec._fresh
    prompts = prompts_of(d)
    got = serve(dec, prompts)
    stats = dec.stats()
    dec.alloc.check()
    spans = [s for s in obs_trace.COLLECTOR.spans()
             if s.name == "serve.request"
             and "window_pages_released" in s.attrs]
    dec.close()
    return {"prompts": prompts, "got": got, "stats": stats, "spans": spans}


def gaps(served, i, d=None, **fault):
    a, d0 = arch()
    return a.served_gaps(d or d0, SEED, served["prompts"][i],
                         served["got"][i], 192, N, **fault)


# -- (a) the system against the reference ------------------------------------------

@pytest.mark.parametrize("i", range(len(REQUESTS)),
                         ids=[f"L{n}-new{m}" for n, m in REQUESTS])
def test_program_agrees_with_the_reference_at_every_token(served, i):
    """Each served token is the reference's first at its position: the
    prefill at its rung, then every tick through both kinds of page."""
    assert len(served["got"][i]) == REQUESTS[i][1]
    assert float(gaps(served, i).max()) <= GAP


def test_window_pages_were_released_while_requests_ran(served):
    st = served["stats"]
    assert st["completed"] == len(REQUESTS)
    # every page behind the window of the first query of a request's last
    # round, which began at most a fused round's 8 ticks before its end
    def behind(ticks_before_end):
        total = 0
        for n, m in REQUESTS:
            pad, query = P - n, P + m - ticks_before_end
            low = max(pad // PAGE, (query - WINDOW + 1) // PAGE)
            total += max(0, low - pad // PAGE)
        return total

    released = st["kv_window_pages_released"]
    assert 50 < behind(8) <= released <= behind(1)
    assert sum(s.attrs["window_pages_released"]
               for s in served["spans"]) == released
    # nothing is held at the end, of either kind
    assert st["kv_pages_used"] == st["kv_pages_used_window"] == 0
    assert st["kv_pages_total"] == \
        st["kv_pages_total_held"] + st["kv_pages_total_window"]
    # over the rounds a slot held fewer window pages than its context covers
    assert 0 < st["kv_window_pages_held_sum"] \
        < 0.6 * st["kv_window_pages_covered_sum"]


def test_mixture_counters_of_a_share(served):
    """TokenStep carries the mixture's counters: every tick routes top_k
    pairs a live token and a mixture layer, of which the held experts
    get their share."""
    _, d = arch()
    st = served["stats"]
    ticks = sum(m for _, m in REQUESTS)
    moe_layers = d.layers - d.dense_layers
    assert st["moe_pairs_routed"] == ticks * d.top_k * moe_layers
    assert 0 < st["moe_pairs"] < st["moe_pairs_routed"]
    share = st["moe_pairs"] / st["moe_pairs_routed"]
    assert 0.1 < share < 0.45           # 8 of 32 experts: a quarter
    assert st["moe_expert_visits"] <= st["moe_pairs"]
    assert st["moe_load_max"] >= st["moe_pairs"] / (
        st["rounds"] * moe_layers * d.experts)
    assert st["moe_kernel_pairs"] == 0      # off the TPU: ragged_dot


# -- (d) faults the comparison must fail -------------------------------------------

FAULTS = {
    "float8-reference": dict(lowp="float8_e4m3fn"),
    "three-experts-of-four": dict(top_k=3),
    "window-off": dict(no_window=True),
    "rotary-on-the-full-layer": dict(rope_full=True),
    "gate-left-out": dict(no_gate=True),
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
    assert worst > 100 * GAP, worst


def test_the_reference_in_bfloat16_passes_where_float8_fails(served):
    """The nearest precision below the stated one must fail and the
    stated one pass, by the judged numbers' own reduction."""
    import jax.numpy as jnp

    a, _ = arch()
    read = {}
    for name in ("bfloat16", "float8_e4m3fn"):
        read[name] = a.judged([gaps(served, i, lowp=getattr(jnp, name))
                               for i in range(len(REQUESTS))])
    assert read["float8_e4m3fn"]["served_logit_gap"] \
        > 5 * read["bfloat16"]["served_logit_gap"]
    assert read["bfloat16"]["served_logit_gap"] < 0.02
    assert read["float8_e4m3fn"]["served_worst_gap"] > 0.1


def test_a_window_page_released_one_page_early_fails(monkeypatch):
    """The allocator's bound is tight: with every release one page ahead
    of the window, a query reads a page that is no longer its slot's."""
    from kubeflow_tpu.runtime import kvcache

    a, d = arch()
    real = kvcache.PageAllocator._window_low
    monkeypatch.setattr(
        kvcache.PageAllocator, "_window_low",
        lambda self, first, reads_from: real(self, first, reads_from) + 1
        if reads_from >= P else real(self, first, reads_from))
    dec = decoder(two_kind_model(), a, d)
    prompts = prompts_of(d)
    try:
        got = serve(dec, prompts)
    finally:
        dec.close()
    worst = max(float(a.served_gaps(d, SEED, prompts[i], got[i], 192, N).max())
                for i in range(len(REQUESTS)))
    assert worst > 100 * GAP, worst


# -- (b) the share ------------------------------------------------------------------

def test_eight_shares_of_four_experts_sum_to_the_uncut_layer():
    """8 chips x 4 experts of a 32-expert layer: the parts of the result
    that the shares give, with the shared expert (which every chip
    computes alike) counted once, add up to what the uncut reference
    gives for the whole layer; and each share is what the reference
    gives for the same share."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.transformer import TransformerConfig
    from kubeflow_tpu.ops.moe import MoEBlock

    a, whole = arch(num_experts=32, share=dict(expert_first=0))
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
    for chip in range(8):
        _, d = arch(num_experts=4, share=dict(expert_first=4 * chip))
        kw = {k: v for k, v in d.model_kwargs().items()
              if k not in ("layer_pattern", "n_layers")}
        held = slice(4 * chip, 4 * chip + 4)
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
            w_down=w["w_down"][held]), held=(4 * chip, 4))
        assert np.abs(got - np.asarray(ref))[keep].max() <= 1e-5
        parts.append(got - np.asarray(shared))
        diag = {k: int(v[0]) for k, v in mut["diagnostics"].items()
                if k.startswith("moe_pairs")}
        assert diag["moe_pairs_routed"] == keep.sum() * whole.top_k
        pairs += diag["moe_pairs"]
    total = np.asarray(shared) + sum(parts)
    assert np.abs(total - np.asarray(want))[keep].max() <= 1e-5
    assert pairs == keep.sum() * whole.top_k    # every pair lands on one chip


# -- (e) the flash prefill against the gather ---------------------------------------

def test_flash_prefill_agrees_with_the_gather_on_the_same_rung():
    """The rung's own attention (the flash kernel, interpreted here) and
    the gather over the slot's pages: the same logits at the last
    position and the same pages for the ticks, with left padding."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.runtime import kvcache

    a, d = arch()
    params = {"params": a.make_program_params(d, SEED)}
    lq, pad, mp = 128, 37, 40
    model = two_kind_model(attention_impl="flash")
    toks = jnp.asarray(np.random.default_rng(1).integers(
        1, d.vocab, (1, lq)), jnp.int32).at[0, :pad].set(0)
    table = jnp.arange(1, mp + 1, dtype=jnp.int32)[None]
    cache = kvcache.init_paged_cache(model, mp)
    out = {}
    for fresh in (False, True):
        out[fresh] = model.apply(
            params | {"cache": cache}, toks, train=False,
            decode_index=jnp.zeros((1,), jnp.int32), mutable=["cache"],
            pad_len=jnp.asarray([pad], jnp.int32), page_table=(table, table),
            fresh=fresh)
    (gather, gmut), (flash, fmut) = out[False], out[True]
    assert flash.shape == (1, 1, d.vocab) and gather.shape == (1, lq, d.vocab)
    assert float(jnp.abs(flash[0, 0] - gather[0, -1]).max()) <= 2e-4
    # the pages the ticks will read: the full layer's every real position,
    # a window layer's last `window` (what lies before is not written)
    for layer, sliding in enumerate(d.sliding):
        for name in ("key_pages", "value_pages"):
            g = np.asarray(gmut["cache"][f"layer_{layer}"]["attn"][name])
            f = np.asarray(fmut["cache"][f"layer_{layer}"]["attn"][name])
            pos = np.arange(lq - WINDOW if sliding else pad, lq)
            pages, offs = 1 + pos // PAGE, pos % PAGE
            assert np.abs(g[pages, offs] - f[pages, offs]).max() <= 2e-4, (
                layer, name)


def test_flash_prefill_through_the_decoder():
    """A decoder whose prefix cache is off and whose attention is the
    flash kernel's takes the rung's own attention (its rule says so),
    claims no window page behind the first tick's window, and serves
    what the reference puts first."""
    a, d = arch()
    model = two_kind_model(attention_impl="flash")
    # the rungs of 96 by 4 are no multiples of 128: the rule says gather
    assert not decoder(model, a, d)._fresh
    from kubeflow_tpu.serving.continuous import (SlotDecoder,
                                                 window_pages_for)
    import dataclasses

    p, n = 512, 16
    model = toy_model(kv_pages=2 * 140 + 1, kv_page_size=PAGE,
                      attention_impl="flash", max_seq_len=p + n)
    model = model.clone(cfg=dataclasses.replace(
        model.cfg, kv_window_pages=window_pages_for(
            model.cfg, 2, p, n, prefix_cache=False)))
    # a ring a slot, not a sequence: window + a fused round + a page
    assert model.cfg.kv_window_pages == 2 * (WINDOW // PAGE + 2 + 1) + 1
    dec = SlotDecoder(model, {"params": a.make_program_params(d, SEED)},
                      slots=2, prompt_len=p, max_new_tokens=n,
                      prefix_cache=False)
    try:
        assert dec._fresh
        rng = np.random.default_rng(2)
        peak = 0

        def ask(length):
            nonlocal peak
            prompt = rng.integers(1, d.vocab, length).tolist()
            got = dec.submit(prompt, n)
            peak = max(peak, dec.stats()["kv_pages_used_window"])
            return float(a.served_gaps(d, SEED, prompt, got, 1024, n).max())

        assert max(ask(300), ask(120)) <= GAP
        dec.alloc.check()
        st = dec.stats()
        assert st["kv_pages_used"] == 0
        # 284 and 104 real pages of prompt and answer beside rings of 11
        assert st["kv_window_pages_held_sum"] \
            < 0.2 * st["kv_window_pages_covered_sum"]
    finally:
        dec.close()


# sha256 of `jax.make_jaxpr` of a two-kind model's paged prefill-install
# on the own-keys path (prefix cache off) at the 128 and the 512 rung of
# 512, addresses struck out, at commit 24075f6: the parent of the PR that
# gave the prefix cache its case inside the flash path. With the prefix
# cache off `longctx-saturated` compiles what it compiled.
FRESH_PREFILL_JAXPRS_AT_PARENT = {
    128: "123d2e58c582ac0aa0b83ddeae1971ed478cc9448e76c0561c7d9491d63d4afc",
    512: "c74da6480aee7339d55e2dec0e7491ec7f80eb21f99b79e6021899e8f1056637",
}
# The 512 rung since PR 36: its mixture layers (8 of 32 experts, 2,048
# pairs) work on a window of 1,024 sorted positions (ops/moe.py:
# `compact_bound`) and the program hands out the rung's two counters; the
# 128 rung (512 pairs: the rule's floor) is the program it was. With the
# rule switched off the 512 rung hashes to the value above, and the two
# programs' logits and pages agree to rounding (the test below).
FRESH_PREFILL_JAXPR_COMPACTED = {
    512: "72d6b3f6fde08f4221e6e05bb45d642a7a6b1d0dce23cf21b7de595eb5b0e9fa",
}


def fresh_prefill_step():
    """The toy two-kind model's step with the flash prefill, and the
    arguments of its 512-position program after `params` and `state`."""
    import dataclasses

    import jax.numpy as jnp

    from kubeflow_tpu.runtime.kvcache import pages_for
    from kubeflow_tpu.serving import steps
    from kubeflow_tpu.serving.continuous import window_pages_for

    a, d = arch()
    p, n = 512, 16
    model = toy_model(kv_pages=2 * 140 + 1, kv_page_size=PAGE,
                      attention_impl="flash", max_seq_len=p + n)
    model = model.clone(cfg=dataclasses.replace(
        model.cfg, kv_window_pages=window_pages_for(
            model.cfg, 2, p, n, prefix_cache=False)))
    params = {"params": a.make_program_params(d, SEED)}
    mp = pages_for(p + n, PAGE)
    step = steps.TokenStep(model, params, 2, p, n, mp, fresh_prefill=True)
    row = jnp.zeros((1, mp), jnp.int32)

    def args(rung, toks=None, table=(row, row)):
        return (jnp.zeros((1, rung), jnp.int32) if toks is None else toks,
                jnp.zeros((1,), jnp.int32), table,
                jnp.zeros((1,), jnp.int32), jnp.int32(0), jnp.int32(1))

    return d, params, step, args


def jaxpr_sha(fn, *args):
    import hashlib
    import re

    import jax

    text = str(jax.make_jaxpr(fn)(*args))
    return hashlib.sha256(
        re.sub(r"0x[0-9a-f]+", "0x", text).encode()).hexdigest()


@pytest.mark.parametrize("rung", list(FRESH_PREFILL_JAXPRS_AT_PARENT))
def test_the_fresh_prefill_without_a_prefix_cache_is_what_it_was(
        rung, monkeypatch):
    from kubeflow_tpu.ops import moe

    _, params, step, args = fresh_prefill_step()
    got = jaxpr_sha(step._paged_prefill_install, params, step.state,
                    *args(rung))
    assert got == FRESH_PREFILL_JAXPR_COMPACTED.get(
        rung, FRESH_PREFILL_JAXPRS_AT_PARENT[rung])
    # the rule apart, the program is the parent's at every rung
    monkeypatch.setattr(moe, "compact_bound", lambda *a: None)
    _, params, step, args = fresh_prefill_step()
    assert jaxpr_sha(step._paged_prefill_install, params, step.state,
                     *args(rung)) == FRESH_PREFILL_JAXPRS_AT_PARENT[rung]


def test_the_compacted_rung_gives_the_logits_and_pages_it_gave(monkeypatch):
    """The 512 rung's re-pinned program against the program it was (the
    rule switched off), on a padded prompt: the same last logits and the
    same pages to float32's rounding, the mixture layers counted."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.ops import moe

    out = {}
    for compacted in (True, False):
        if not compacted:
            monkeypatch.setattr(moe, "compact_bound", lambda *a: None)
        d, params, step, args = fresh_prefill_step()
        toks = jnp.asarray(np.random.default_rng(3).integers(
            1, d.vocab, (1, 512)), jnp.int32).at[0, :37].set(0)
        table = jnp.arange(1, step.mp + 1, dtype=jnp.int32)[None]
        got = step._paged_prefill_install(
            params, step.state, *args(512, toks, (table, table))[:3],
            jnp.asarray([37], jnp.int32), jnp.int32(0), jnp.int32(1))
        state, counts = got if compacted else (got, None)
        out[compacted] = (np.asarray(state[1][0]), jax.tree.map(
            np.asarray, state[0]), counts)
    logits, pages, counts = out[True]
    assert np.asarray(counts).tolist() == [d.layers - d.dense_layers, 0]
    assert np.abs(logits).max() > 0.1
    assert np.abs(logits - out[False][0]).max() <= GAP
    for got, want in zip(jax.tree.leaves(pages), jax.tree.leaves(out[False][1])):
        assert np.abs(got - want).max() <= GAP


# -- the rules and the refusals ------------------------------------------------------

def test_which_layers_release_is_a_rule_over_what_is_there():
    import dataclasses

    from kubeflow_tpu.serving.continuous import SlotDecoder, window_pages_for
    from kubeflow_tpu.serving.server import serve_lm_generator

    a, d = arch()
    cfg = toy_model(kv_pages=99, kv_page_size=PAGE).cfg
    # the prefix cache on: every layer holds its pages, as ever
    assert window_pages_for(cfg, SLOTS, P, N, prefix_cache=True) == 0
    assert window_pages_for(cfg, SLOTS, P, N, prefix_cache=False) \
        == SLOTS * 34 + 1
    # a window that covers the whole sequence, a draft: nothing to release
    wide = dataclasses.replace(cfg, layer_pattern=tuple(
        dataclasses.replace(s, window=5 * s.window) for s in cfg.layers()))
    assert window_pages_for(wide, SLOTS, P, N, prefix_cache=False) == 0
    assert window_pages_for(cfg, SLOTS, P, N, prefix_cache=False,
                            draft=True) == 0
    with pytest.raises(ValueError, match="need prefix_cache=False"):
        SlotDecoder(two_kind_model(),
                    {"params": a.make_program_params(d, SEED)}, slots=SLOTS,
                    prompt_len=P, max_new_tokens=N, prefix_cache=True)
    with pytest.raises(ValueError, match="keeps a window layer's pages"):
        serve_lm_generator(
            "m", "transformer-test", prompt_len=P, max_new_tokens=N,
            continuous_batching=True, kv_pages=99, kv_page_size=PAGE,
            **dict(d.model_kwargs(), rolling_kv_cache=True))


def test_server_serves_the_pattern_over_http_path():
    """`serve_lm_generator` -> SlotDecoder -> TokenStep -> the one
    PageAllocator -> TransformerLM: the prefix cache off makes the window
    layers release, on makes every layer hold; both serve the reference's
    first tokens."""
    import jax.numpy as jnp

    from kubeflow_tpu.serving.server import serve_lm_generator

    a, d = arch()
    prompt = np.random.default_rng(3).integers(1, d.vocab, 70).tolist()
    out = {}
    for prefix_cache in (False, True):
        sm = serve_lm_generator(
            "pattern", "transformer-test", prompt_len=P, max_new_tokens=N,
            continuous_batching=True, decode_slots=2, kv_pages=2 * 34 + 1,
            kv_page_size=PAGE, prefix_cache=prefix_cache, seed=SEED,
            dtype=jnp.float32, **d.model_kwargs())
        # the seed's own initialiser, not the benchmark's weights: the two
        # decoders hold the same ones and must serve the same tokens
        out[prefix_cache] = np.asarray(sm.predict_fn(
            {"tokens": [prompt], "max_new_tokens": [N]})).tolist()
    assert out[False] == out[True] and len(out[True][0]) == N


def test_the_two_kind_programs_keep_the_names_the_benchmark_reads():
    """The step programs of a decoder that keeps pages by layer kind take
    a pair of tables and are the modules they were by name: the metric
    files of the cell find them as they find the others'."""
    import re

    import jax
    import jax.numpy as jnp

    a, d = arch()
    dec = decoder(two_kind_model(), a, d)
    try:
        assert sorted(dec._prefill_at) == [24, 48, 72, 96]
        for length, program in dec._prefill_at.items():
            name = re.search(r"HloModule (\w+)", program.as_text()).group(1)
            assert name == "jit__paged_prefill_install", (length, name)
        tables = dec._tables()
        assert isinstance(tables, tuple) and len(tables) == 2
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype),
            (dec._params, dec.state, tables))
        for jitted, want in ((dec._step, "jit__tick"),
                             (dec._step_fused, "jit__step_fused")):
            text = jitted.lower(*shapes).as_text()
            assert re.search(r"module @(\w+)", text).group(1) == want
        # the state's ninth leaf: the mixture's counters
        assert dec.state[8].shape == (5,)
    finally:
        dec.close()
