"""The paged prefill of a decoder whose prefix cache is on, through the
flash kernel (interpreted here): a rung attends over its own keys, and
behind the slot's pages before the rung where a prefix hit lies there
(`Attention._decode_paged(fresh=True, hit_below=...)`), both in the
rung's one program. Held to the gather of the slot's pages, the
reference, on a dense model with a window at toy widths in float32; and
the decoder's rule (`serving/continuous.py:_fresh_prefill_rule`) to its
outcomes and reasons."""

import dataclasses

import numpy as np
import pytest

P, N, PAGE, WINDOW, VOCAB = 512, 8, 4, 160, 64
GAP = 2e-4      # float32 against float32: the last bits


def toy_model(**kw):
    import jax.numpy as jnp

    from kubeflow_tpu.models.registry import get_model

    return get_model("transformer-test", **{
        "vocab_size": VOCAB, "max_seq_len": P + N, "dtype": jnp.float32,
        "attention_window": WINDOW, "attention_impl": "flash",
        "kv_pages": 2 * 130 + 1, "kv_page_size": PAGE, **kw})


def variables_of(model):
    """The seed's initialiser with the attention's matrices eight times
    as large: scores of order one, so that a key that is missed or seen
    twice moves the logits by far more than rounding does."""
    import jax

    variables = model.init(jax.random.PRNGKey(3), np.zeros((1, 1), np.int32),
                           train=False)
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x * 8 if "attn" in jax.tree_util.keystr(path) else x,
        variables)


# (the rung, the left padding, the positions an earlier gather prefill
# has left in the slot's pages: a prefix hit's)
CASES = {
    "longest-rung-no-hit": (512, 37, 0),
    "short-rung-behind-padding": (256, 300, 0),
    "short-rung-behind-a-hit": (128, 100, 384),
}


@pytest.mark.parametrize("case", list(CASES))
def test_flash_prefill_agrees_with_the_gather(case):
    """The same logits at the last position and the same pages for the
    ticks, whichever keys the rung's program attends over."""
    import jax.numpy as jnp

    from kubeflow_tpu.runtime import kvcache

    lq, pad, held = CASES[case]
    start = P - lq
    model = toy_model()
    params = variables_of(model)
    mp = kvcache.pages_for(P + N, PAGE)
    toks = jnp.asarray(np.random.default_rng(1).integers(
        1, VOCAB, (1, P)), jnp.int32).at[0, :pad].set(0)
    table = jnp.arange(1, mp + 1, dtype=jnp.int32)[None]

    def prefill(cache, lo, hi, **fresh):
        return model.apply(
            params | {"cache": cache}, toks[:, lo:hi], train=False,
            decode_index=jnp.asarray([lo], jnp.int32), mutable=["cache"],
            pad_len=jnp.asarray([pad], jnp.int32), page_table=table, **fresh)

    cache = kvcache.init_paged_cache(model, mp)
    if held:
        cache = prefill(cache, 0, held)[1]["cache"]
    gather, gmut = prefill(cache, start, P)
    flash, fmut = prefill(cache, start, P, fresh=True, hit_below=start)
    assert flash.shape == (1, 1, VOCAB) and gather.shape == (1, lq, VOCAB)
    assert float(jnp.abs(flash[0, 0] - gather[0, -1]).max()) <= GAP
    # the prefix cache is on: every layer holds every real position
    pos = np.arange(pad, P)
    pages, offs = 1 + pos // PAGE, pos % PAGE
    for layer in range(model.cfg.n_layers):
        for name in ("key_pages", "value_pages"):
            g = np.asarray(gmut["cache"][f"layer_{layer}"]["attn"][name])
            f = np.asarray(fmut["cache"][f"layer_{layer}"]["attn"][name])
            assert np.abs(g[pages, offs] - f[pages, offs]).max() <= GAP, (
                layer, name)
    if held:
        # and the case did read the pages: without them the rung's
        # queries lose the keys their window reaches before it
        blind, _ = prefill(cache, start, P, fresh=True)
        assert float(jnp.abs(blind[0, 0] - gather[0, -1]).max()) > 100 * GAP


def test_a_second_request_hits_and_attends_behind_the_hit():
    """Two requests that share a prefix through `SlotDecoder(prefix_cache=
    True)`: the second takes a rung behind its hit, and both are served
    token for token what the gather decoder serves."""
    from kubeflow_tpu.serving.continuous import SlotDecoder

    rng = np.random.default_rng(2)
    prefix = rng.integers(1, VOCAB, 300).tolist()
    prompts = [prefix + rng.integers(1, VOCAB, 100).tolist() for _ in "ab"]
    got = {}
    for impl in ("reference", "flash"):
        model = toy_model(attention_impl=impl)
        dec = SlotDecoder(model, variables_of(model), slots=2, prompt_len=P,
                          max_new_tokens=N, prefix_cache=True)
        try:
            assert dec._fresh == (impl == "flash")
            got[impl] = [dec.submit(p) for p in prompts]
            st = dec.stats()
            dec.alloc.check()
        finally:
            dec.close()
        # pad 112: pages 28-102 hit (300 shared tokens end at 412), and
        # the rung of 128 starts at 384, behind them
        assert st["prefix_hit_pages"] == (384 - 112) // PAGE
        assert st["prefill_tokens_computed"] == 512 + 128
        assert st["admitted"] == 2
        flash = impl == "flash"
        assert (st["prefill_flash"], st["prefill_behind_hit"]) == (
            (2, 1) if flash else (0, 0))
    assert got["flash"] == got["reference"]
    assert all(len(t) == N for t in got["flash"])


def rule_cfg(**kw):
    return dataclasses.replace(toy_model().cfg, **kw)


# (the model's config, prompt_len, prefix cache, draft) -> (flash?, why)
RULE = {
    "prefix-cache-off": (
        {}, 512, False, False, True, "prefix cache off"),
    "prefix-cache-on": (
        {}, 512, True, False, True, "where a hit lies there"),
    "block-model": (
        dict(gen_block=4), 512, False, False, False, "a block model"),
    "draft": (
        {}, 512, True, True, False, "or a draft"),
    "reference-attention": (
        dict(attention_impl="reference"), 512, True, False, False,
        "'reference', not flash"),
    "rung-no-multiple-of-128": (
        {}, 96, True, False, False, r"\[24, 48, 72, 96\] is no multiple"),
}


@pytest.mark.parametrize("case", list(RULE))
def test_which_prefill_attends_through_the_flash_kernel_is_a_rule(case):
    import re

    from kubeflow_tpu.serving.continuous import _fresh_prefill_rule

    kw, prompt_len, prefix_cache, draft, flash, why = RULE[case]
    got = _fresh_prefill_rule(rule_cfg(**kw), prompt_len,
                              prefix_cache=prefix_cache, draft=draft)
    assert got[0] is flash and re.search(why, got[1]), got
