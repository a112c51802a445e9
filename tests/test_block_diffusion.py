"""A block-diffusion mixture model through the one scheduler loop, against
the plain reference of benchmarks/arch/sdar_moe.py on seeded weights, on
the CPU at toy widths: prefill plus block steps through the paged cache
in every replayed state, the dropless experts against the plain loop,
the q/k norm, the planted faults, the counters' arithmetic, the
refusals, and what a one-token model's programs still are.

The program runs in float32 here, so that it and the reference agree to
rounding of the last bits and a fault of any size shows; on the chip it
runs in bfloat16 against the limits of the mix's file (PERF.md)."""

import functools
import hashlib
import re
import threading
import time

import numpy as np
import pytest
from conftest import BLOCK_MODEL

CONFIG = dict(
    hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, num_experts=8, num_experts_per_tok=2,
    intermediate_size=96, moe_intermediate_size=32, vocab_size=128,
    rope_theta=1e6, rms_norm_eps=1e-6,
    generation=dict(block_length=4, denoising_steps=4, mask_token_id=127))
SEED = 7
P, N, PAGE = 16, 24, 4
# (prompt length, tokens asked for): every `L mod 4`, a prompt that fills
# the padded row, one token, answers that end inside a block; six
# requests on three slots, so that slots are admitted while the others
# sit at every step of their blocks. One prompt holds the MASK id.
REQUESTS = [(5, 24), (8, 7), (15, 20), (10, 1), (3, 9), (16, 12)]
GAP, ORDER = 1e-4, 1e-3      # float32 against float32: the last bits


def arch():
    from benchmarks.arch import sdar_moe

    return sdar_moe, sdar_moe.sizes(CONFIG)


def toy_model(**kw):
    import jax.numpy as jnp

    from kubeflow_tpu.models.registry import get_model

    _, d = arch()
    return get_model("transformer-test", max_seq_len=P + N + d.block,
                     dtype=jnp.float32, **dict(d.model_kwargs(), **kw))


@pytest.fixture(scope="module")
def served():
    """The six requests through one SlotDecoder of three slots: each
    request's prompt and what came back, and the decoder's counts."""
    from kubeflow_tpu.obs import trace as obs_trace
    from kubeflow_tpu.serving.continuous import SlotDecoder

    a, d = arch()
    model = toy_model(kv_pages=41, kv_page_size=PAGE)
    dec = SlotDecoder(model, {"params": a.make_program_params(d, SEED)},
                      slots=3, prompt_len=P, max_new_tokens=N,
                      prefix_cache=False)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, d.vocab - 1, n).tolist() for n, _ in REQUESTS]
    prompts[2][3] = d.mask_id       # a prompt may hold the MASK id
    got = {}

    def go(i):
        time.sleep(0.02 * i)        # arrivals spread over the others' passes
        got[i] = dec.submit(prompts[i], REQUESTS[i][1])

    threads = [threading.Thread(target=go, args=(i,))
               for i in range(len(REQUESTS))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    stats = dec.stats()
    dec.alloc.check()
    spans = [s for s in obs_trace.COLLECTOR.spans()
             if s.name == "serve.request" and "blocks" in s.attrs]
    dec.close()
    return {"prompts": prompts, "got": got, "stats": stats, "spans": spans}


def gaps(served, i, **fault):
    a, d = arch()
    r = served["got"][i]
    return a.request_gaps(d, SEED, served["prompts"][i], r["tokens"],
                          r["fixed_at"], 44, 64, **fault)


@pytest.mark.parametrize("i", range(len(REQUESTS)),
                         ids=[f"L{n}-mod{n % 4}-new{m}" for n, m in REQUESTS])
def test_program_agrees_with_the_reference_in_every_state(served, i):
    """Each token fixed is the reference's first at its position in the
    state it was fixed in, and the position fixed is the reference's
    most confident there."""
    a, d = arch()
    n, m = REQUESTS[i]
    r = served["got"][i]
    assert len(r["tokens"]) == len(r["fixed_at"]) == m
    sts = a.states_of(d, served["prompts"][i], r["tokens"], r["fixed_at"])
    blocks = -(-(n % 4 + m) // 4)
    assert len({s["start"] for s in sts}) == blocks
    assert sts[0]["start"] == n - n % 4
    assert sum(sts[0]["masked"]) == 4 - n % 4      # the prompt's tail is fixed
    out = gaps(served, i)
    assert len(out["gap"]) == len(sts)
    assert float(out["gap"].max()) <= GAP
    assert float(out["order"].max()) <= ORDER


FAULTS = {
    "float8-reference": dict(lowp="float8_e4m3fn"),
    "one-expert-less": dict(top_k=1),
    "causal-mask": dict(causal=True),
    "commit-pass-left-out": dict(no_commit=True),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_planted_fault_fails_the_comparison(served, fault):
    """The controls of the chip run at toy size: the reference with the
    fault in it judges the sound program's answers, and one of the two
    numbers leaves the room that rounding needs by orders of magnitude."""
    import jax.numpy as jnp

    kw = dict(FAULTS[fault])
    if "lowp" in kw:
        kw["lowp"] = getattr(jnp, kw["lowp"])
    worst = {"gap": 0.0, "order": 0.0}
    for i in range(len(REQUESTS)):
        out = gaps(served, i, **kw)
        for k in worst:
            worst[k] = max(worst[k], float(out[k].max()))
    assert worst["gap"] > 100 * GAP or worst["order"] > 100 * ORDER, worst


def test_naive_replay_of_whole_sequences_agrees_with_the_layered_one(served):
    """The reference takes the committed keys and values from its own
    pass over the finished sequence; replaying every state as a whole
    sequence from scratch gives the same numbers."""
    import jax
    import jax.numpy as jnp

    from benchmarks.lib.weights import seed_key

    a, d = arch()
    i = 2
    prompt, r = served["prompts"][i], served["got"][i]
    sts = a.states_of(d, prompt, r["tokens"], r["fixed_at"])
    fast = gaps(served, i)
    whole = list(prompt) + r["tokens"]
    logits = jax.jit(lambda k, t: a.sequence_logits(d, k, t))
    known = [j for j, st in enumerate(sts) if any(st["picked"])]
    # the served tokens are the reference's own first: judge other tokens
    # too, so that the two replays are held to numbers that are not 0
    for j in (known[0], known[1], known[len(known) // 2], known[-1]):
        st = sts[j]
        toks = np.asarray(whole[:st["start"]] + st["tok"], np.int32)
        rows = np.asarray(logits(seed_key(SEED), jnp.asarray(toks)))[-d.block:]
        best = rows.max(axis=-1)
        picked, masked = np.asarray(st["picked"]), np.asarray(st["masked"])
        gap = (best - rows[np.arange(d.block), st["served"]])[picked].max()
        assert abs(gap - float(fast["gap"][j])) <= 1e-4
        lc = -np.log(np.exp(rows - best[:, None]).sum(-1))
        rest = masked & ~picked
        order = max(0.0, (lc[rest].max() if rest.any() else -np.inf)
                    - lc[picked].min())
        assert abs(order - float(fast["order"][j])) <= 1e-4
    shifted = a.request_gaps(
        d, SEED, prompt, [(t + 1) % d.vocab for t in r["tokens"]],
        r["fixed_at"], 44, 64)
    st = sts[known[0]]
    toks = np.asarray(whole[:st["start"]] + st["tok"], np.int32)
    rows = np.asarray(logits(seed_key(SEED), jnp.asarray(toks)))[-d.block:]
    pos = int(np.argmax(st["picked"]))
    want = rows[pos].max() - rows[pos, (st["served"][pos] + 1) % d.vocab]
    assert want > 0.01
    assert abs(want - float(shifted["gap"][known[0]])) <= 1e-4


def test_a_step_the_schedule_cannot_have_produced_is_refused():
    a, d = arch()
    prompt = [3, 4, 5, 6, 7]                         # tail 1: 3 masked first
    ok = a.states_of(d, prompt, [9] * 7, [2, 1, 3, 1, 4, 2, 3])
    assert [s["start"] for s in ok] == [4, 4, 4, 8, 8, 8, 8]
    for bad in ([2, 2, 3, 1, 4, 2, 3],               # step 2 twice in a block
                [2, 1, 4, 1, 4, 2, 3],               # step 4 of 3 masked
                [1, 2, 3, 1, 2, 5, 3]):              # no step 5
        with pytest.raises(a.Impossible):
            a.states_of(d, prompt, [9] * 7, bad)
    # the cut last block: replayed as far as its states are known
    cut = a.states_of(d, prompt, [9] * 5, [2, 1, 3, 3, 1])
    assert [s["start"] for s in cut] == [4, 4, 4, 8, 8]
    assert a.answer_tokens({"tokens": [1, 2], "fixed_at": [1, 0]}) is None
    assert a.answer_tokens([1, 2]) is None
    assert a.answer_tokens({"tokens": [1, 2], "fixed_at": [1, 2]}) == [1, 2]


def test_counters_arithmetic(served):
    """`block_passes`, `blocks_committed`, `kv_pages_walked` and the
    experts' counts follow from the requests' lengths alone: one position
    fixed a denoising pass, one committing pass a block."""
    _, d = arch()
    st = served["stats"]
    blocks = fixed = walked = 0
    for n, m in REQUESTS:
        tail, pad = n % 4, P - n
        for b in range(-(-(tail + m) // 4)):
            masked = 4 - tail if b == 0 else 4
            end = P - tail + 4 * b + 3
            blocks += 1
            fixed += masked
            walked += (masked + 1) * (end // PAGE - pad // PAGE + 1)
    assert st["blocks_committed"] == blocks
    assert st["block_passes"] == fixed + blocks
    assert st["kv_pages_walked"] == walked
    assert st["moe_pairs"] == st["block_passes"] * 4 * d.top_k * d.layers
    # a pass and a layer visit at least top_k experts and at most all
    layer_passes = st["rounds"] * d.layers      # no round was fused here
    assert d.top_k * layer_passes <= st["moe_expert_visits"] \
        <= d.experts * layer_passes
    assert st["moe_load_max"] * d.experts >= st["moe_pairs"]
    # off the TPU the rule leaves every grouped matmul to `ragged_dot`
    assert st["moe_kernel_pairs"] == 0
    assert st["completed"] == len(REQUESTS)
    by_tokens = {s.attrs["new_tokens"]: s.attrs for s in served["spans"]}
    for n, m in REQUESTS:
        assert by_tokens[m]["blocks"] == -(-(n % 4 + m) // 4)
        assert by_tokens[m]["passes"] >= by_tokens[m]["blocks"] * 2


# -- the experts ------------------------------------------------------------------

def test_dropless_experts_match_the_plain_loop_where_capacity_drops():
    """A routing so uneven that the capacity path drops: every token's
    first choice is expert 0. The dropless layer agrees with the plain
    loop over all experts and drops nothing."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.transformer import TransformerConfig
    from kubeflow_tpu.ops.moe import MoEBlock

    a, d = arch()
    w = a.served_weights(a.layer_leaves(d, jax.random.PRNGKey(1), 0), None)
    w["router"] = w["router"].at[:, 0].set(0.0)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, d.d), jnp.float32)
    x = x.at[..., 0].set(20.0)
    w["router"] = w["router"].at[0, 0].set(1.0)     # logit of expert 0: 20
    params = {"router": {"kernel": w["router"]}, "w_gate": w["w_gate"],
              "w_up": w["w_up"], "w_down": w["w_down"]}
    kw = dict(d_model=d.d, d_ff=d.d_dense, moe_d_ff=d.d_expert,
              n_experts=d.experts, expert_top_k=d.top_k, dtype=jnp.float32)
    live = jnp.ones((2, 24), bool).at[1, :5].set(False)   # five rows of padding
    got, mut = MoEBlock(TransformerConfig(**kw)).apply(
        {"params": params}, x, live, mutable=["diagnostics"])
    want = a.experts(d, x.reshape(-1, d.d), w).reshape(x.shape)
    keep = np.asarray(live)
    assert np.abs(np.asarray(got - want))[keep].max() <= 1e-5
    assert not np.asarray(got)[~keep].any()     # padding is routed nowhere
    diag = {k: float(v[0]) for k, v in mut["diagnostics"].items()}
    assert diag["moe_drop"] == 0.0
    assert diag["moe_pairs"] == keep.sum() * d.top_k
    assert diag["moe_load_max"] == keep.sum()   # every live row chose expert 0
    assert diag["moe_expert_visits"] <= d.experts
    # the capacity path on the same rows drops
    _, mut = MoEBlock(TransformerConfig(moe_impl="dense", **kw)).apply(
        {"params": params}, x, mutable=["diagnostics"])
    assert float(mut["diagnostics"]["moe_drop"][0]) > 0.2


@pytest.mark.parametrize("qk_norm", [True, False], ids=["on", "off"])
def test_qk_norm(qk_norm):
    """On: an RMSNorm over head_dim of q and of k, one scale each, before
    the rotary embedding, as the reference has it. Off: no such leaves,
    and the projections go to the rotary embedding as they are."""
    import jax
    import jax.numpy as jnp

    from benchmarks.lib.reference import mm, rms_norm, rope
    from kubeflow_tpu.models.transformer import Attention, TransformerConfig

    a, d = arch()
    cfg = TransformerConfig(
        d_model=d.d, n_heads=d.heads, n_kv_heads=d.kv_heads,
        head_dim=d.head_dim, rope_theta=d.rope_theta, qk_norm=qk_norm,
        dtype=jnp.float32, attention_impl="reference", max_seq_len=32)
    w = a.served_weights(a.layer_leaves(d, jax.random.PRNGKey(3), 0), None)
    params = {n: {"kernel": w[n]} for n in ("q", "k", "v", "o")}
    if qk_norm:
        params.update(q_norm={"scale": w["q_norm"]},
                      k_norm={"scale": w["k_norm"]})
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 12, d.d), jnp.float32)
    pos = jnp.arange(12)[None]
    init = Attention(cfg).init(jax.random.PRNGKey(0), x, pos)["params"]
    assert ("q_norm" in init) == ("k_norm" in init) == qk_norm
    got = Attention(cfg).apply({"params": params}, x, pos)[0]
    q, k, v = (mm("nd,dhk->nhk", x[0], w[n]) for n in "qkv")
    if qk_norm:
        q = rms_norm(q, w["q_norm"], d.norm_eps)
        k = rms_norm(k, w["k_norm"], d.norm_eps)
    q, k = (rope(t, pos[0], d.rope_theta) for t in (q, k))
    causal = jnp.arange(12)[None, :] <= jnp.arange(12)[:, None]
    want = mm("nhk,hkd->nd", a._attend(d, q, k, v, causal, None), w["o"])
    assert float(jnp.abs(got - want).max()) <= 2e-4


# -- what the programs still are ------------------------------------------------------

# sha256 of `jax.make_jaxpr` of a paged transformer-test decoder's `_tick`
# at commit 32e321a (the parent of the PR that brought the block step),
# addresses struck out: gen_block = 0 compiles the program it compiled.
TICK_JAXPR_AT_PARENT = (
    "c8a1309ec2f2ec039005c61b4835b49f8eb097a300eaef2849e76cc4db11236c")
# The same of the other step programs, at commit aa28e3e (the parent of
# the PR that moved them out of SlotDecoder.__init__ into
# serving/steps.py): equal jaxprs are equal XLA modules under the same
# names, equal compile-cache keys, and so equal set-up and device time.
# The block model's three were taken again, once, in the PR that sowed
# `moe_kernel_pairs` beside `moe_pairs` (ops/moe.py) and counted it in
# BLOCK_COUNTERS: with that sow and that counter taken out, its tree
# hashed to the three values of aa28e3e, and the four dense programs'
# never moved.
JAXPRS_AT_PARENT = {
    "paged tick": TICK_JAXPR_AT_PARENT,
    "paged fused":
        "2ff8ce2e9ee4c968ddbc4ebf37e7230d7b7d0fcf34f2d10e88cb391cc7567f66",
    "paged prefill-install":
        "288e9ad20e847f9109d175efbb5917d34ae5bb4ecf6109bf55f747a25184fe4a",
    "block tick":
        "605ff13fd2855e6bcf5cb1b189b20dc0c1419cc8aacb89446b2f04e7dfa694b6",
    "block fused":
        "361c7a5f4b4088608473d22a6086a74a565e4f6219ff8117aab5fa89551a0742",
    "block prefill-install":
        "ff6b7863e55219e13653cc8fea46355d6eefb0547e86e2bf6fcd716e27a0cae4",
    "dense tick":
        "d6b2e876980d9585d33e6208711e4bd472b9c60ced0e578de6a2dc70464c0bad",
}


@functools.lru_cache(maxsize=None)
def program_jaxprs(cache: str) -> dict:
    """The hashes of one decoder's step programs: `cache` is "paged",
    "block" (a block model over the paged cache) or "dense"."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.registry import get_model
    from kubeflow_tpu.serving.continuous import SlotDecoder

    def sha(fn, *args):
        text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*args)))
        return hashlib.sha256(text.encode()).hexdigest()

    more = {} if cache == "dense" else dict(kv_pages=25, kv_page_size=4)
    if cache == "block":
        more.update(BLOCK_MODEL)
    model = get_model("transformer-test", vocab_size=64, max_seq_len=24,
                      **more)
    variables = model.init(jax.random.PRNGKey(0), np.zeros((1, 1), np.int32),
                           train=False)
    dec = SlotDecoder(model, variables, slots=3, prompt_len=8,
                      max_new_tokens=8 if cache == "block" else 6)
    try:
        assert dec.B == (4 if cache == "block" else 0)
        assert ("block_passes" in dec.stats()) == (cache == "block")
        if cache == "dense":
            return {"dense tick": sha(dec._step, dec._params, dec.state)}
        table = jnp.asarray(dec.alloc.table)
        opening = ((jnp.zeros((4,), jnp.int32), jnp.int32(1)),) \
            if cache == "block" else ()
        return {
            f"{cache} tick": sha(dec._step, dec._params, dec.state, table),
            f"{cache} fused": sha(dec._step_fused, dec._params, dec.state,
                                  table),
            f"{cache} prefill-install": sha(
                dec.step._paged_prefill_install, dec._params, dec.state,
                jnp.zeros((1, 8), jnp.int32), jnp.zeros((1,), jnp.int32),
                table[:1], jnp.zeros((1,), jnp.int32), jnp.int32(0),
                jnp.int32(1), *opening)}
    finally:
        dec.close()


@pytest.mark.parametrize("program", list(JAXPRS_AT_PARENT))
def test_the_step_programs_are_what_they_were(program):
    """Its first case was `test_gen_block_zero_leaves_the_dense_tick_what_
    it_was`: the paged one-token tick, with the hash it had."""
    assert program_jaxprs(program.split()[0])[program] \
        == JAXPRS_AT_PARENT[program]


# -- the refusals -------------------------------------------------------------------

def test_block_model_is_refused_where_it_cannot_run():
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.registry import get_model
    from kubeflow_tpu.runtime.generate import generate
    from kubeflow_tpu.serving.continuous import SlotDecoder
    from kubeflow_tpu.serving.server import cast_params, serve_lm_generator

    a, d = arch()
    variables = {"params": a.make_program_params(d, SEED)}
    dense_cache = toy_model()
    with pytest.raises(ValueError, match="paged KV cache only"):
        SlotDecoder(dense_cache, variables, slots=2, prompt_len=P,
                    max_new_tokens=N)
    with pytest.raises(ValueError, match="one token a step"):
        generate(dense_cache, variables, jnp.zeros((1, 8), jnp.int32),
                 max_new_tokens=4)
    paged = toy_model(kv_pages=41, kv_page_size=PAGE)
    draft = get_model("transformer-test", vocab_size=d.vocab, max_seq_len=64)
    dvars = draft.init(jax.random.PRNGKey(0), np.zeros((1, 1), np.int32),
                       train=False)
    with pytest.raises(ValueError, match="takes no draft_model"):
        SlotDecoder(paged, variables, slots=2, prompt_len=P, max_new_tokens=N,
                    draft_model=draft, draft_variables=dvars)
    with pytest.raises(ValueError, match="decoded greedily"):
        SlotDecoder(paged, variables, slots=2, prompt_len=P, max_new_tokens=N,
                    temperature=0.7)
    with pytest.raises(ValueError, match="cannot draft"):
        SlotDecoder(draft, dvars, slots=2, prompt_len=P, max_new_tokens=N,
                    draft_model=dense_cache, draft_variables=variables)
    with pytest.raises(ValueError, match="slot decoder over the paged"):
        serve_lm_generator("m", "transformer-test", prompt_len=P,
                           max_new_tokens=N, **d.model_kwargs())
    # bfloat16 leaves served as bfloat16 are the arrays they were
    leaf = variables["params"]["lm_head"]["kernel"]
    assert leaf.dtype == jnp.bfloat16
    assert cast_params(variables, "bfloat16")["params"]["lm_head"][
        "kernel"] is leaf


def test_server_returns_tokens_and_steps():
    """`serve_lm_generator` over a block model: a prediction is the
    tokens and the step each was fixed at."""
    from kubeflow_tpu.serving.server import serve_lm_generator

    _, d = arch()
    sm = serve_lm_generator(
        "blocks", "transformer-test", prompt_len=P, max_new_tokens=8,
        continuous_batching=True, decode_slots=2, kv_pages=21,
        kv_page_size=PAGE, prefix_cache=False, param_dtype="bfloat16",
        **d.model_kwargs())
    try:
        preds = sm.predict([{"tokens": [5, 6, 7], "max_new_tokens": 6},
                            {"tokens": [9] * 8, "max_new_tokens": 6}])
    finally:
        sm.close()
    assert len(preds) == 2
    for p in preds:
        assert sorted(p) == ["fixed_at", "tokens"]
        assert len(p["tokens"]) == len(p["fixed_at"]) == 6
        assert all(1 <= s <= 4 for s in p["fixed_at"])


def test_flops_per_token_takes_the_experts_own_width():
    """`TransformerLM.flops_per_token` counts a mixture layer's experts at
    `moe_d_ff`, as the benchmark's count of the same model does."""
    a, d = arch()
    model = toy_model()
    want = 3 * (d.layers * a.token_flops(d) + 2 * d.d * d.vocab)
    assert model.flops_per_token() == want
    wide = toy_model(moe_d_ff=0)      # 0: experts of the dense width
    assert wide.flops_per_token() - want == (
        6 * d.layers * d.top_k * 3 * d.d * (d.d_dense - d.d_expert))
