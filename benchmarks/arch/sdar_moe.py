"""`sdar_moe`: the published block-diffusion mixture decoder (JetLM
SDAR-30B-A3B-Chat, `model_type` `sdar_moe`): pre-RMSNorm, grouped-query
attention with an RMSNorm over head_dim of q and of k before the rotary
embedding (half-split convention), a block-causal mask, and in every
layer a mixture of many narrow SwiGLU experts (softmax router, the k
largest renormalised, no shared expert, nothing dropped), untied head.
It generates by diffusion over blocks: a block of B positions starts as
MASK, each denoising pass fixes the B / steps masked positions whose
argmax token has the highest softmax probability, and a last pass over
the clean block commits its keys and values.

The layer, for input x of one sequence whose first real token is at
position 0 (the program left-pads; rotary embeddings are relative):

    a = rms(x) ; q, k, v = a Wq, a Wk, a Wv ; q = rms_hd(q) g_q ;
    k = rms_hd(k) g_k ; rope(q), rope(k) ;
    query i sees key j iff j // B <= i // B ;  h = x + attn Wo
    m = rms(h) ; p = softmax(m Wr) ; the k largest p_e over their sum ;
    y = sum_e g_e (silu(m Wg_e) * (m Wu_e)) Wd_e ; out = h + y

This module is everything in the harness that knows that shape: the
sizes, the program's keywords, the weights from the seed, the plain
reference with the comparison that decides `correct`, and the counts.
The reference is jax.numpy in float32 at `highest` matmul precision over
weights rounded to bfloat16 (as the configuration states them), the
experts a plain loop over all of them with a gate that is zero for those
a token did not choose, no cache between calls: a layer's weights are
remade from the seed where they are used, so the 17 GB they would fill
in float32 never exist at once. It imports nothing of the program."""

from __future__ import annotations

import dataclasses
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib.reference import mm, quantize, rms_norm, rope
from benchmarks.lib.weights import NORM_STD, W_STD, normal, seed_key

# Every norm's scale is 1 + NORM_STD N(0, 1), the q and k norms' too: a
# query's scores over random keys then have deviation 1. With those two
# scales at 2 (deviation 4: a query weighs a few keys) the bfloat16
# program and the float32 reference put another token first in half of
# all states (mean gap 0.13-0.65 a request against 0.000-0.006 at 1; my
# chip run, PR 29), and no limit could tell a fault from rounding.

STATE_CHUNK = 64     # replayed block states attended at once
HEAD_ROWS = 512      # rows of logits that exist at once


# -- the sizes ------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    experts: int
    top_k: int
    d_expert: int
    d_dense: int     # the published `intermediate_size`: no layer has it
    vocab: int
    rope_theta: float
    norm_eps: float
    block: int       # B: positions denoised together
    steps: int       # denoising passes a whole block takes
    mask_id: int

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        gen = cfg["generation"]
        if cfg.get("decoder_sparse_step", 1) != 1 or cfg.get("mlp_only_layers"):
            raise ValueError("sdar_moe reads a mixture in every layer")
        if not cfg.get("norm_topk_prob", True):
            raise ValueError("sdar_moe renormalises the chosen gates")
        return cls(
            d=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
            heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
            d_expert=cfg["moe_intermediate_size"],
            d_dense=cfg["intermediate_size"], vocab=cfg["vocab_size"],
            rope_theta=float(cfg["rope_theta"]),
            norm_eps=float(cfg["rms_norm_eps"]),
            block=gen["block_length"], steps=gen["denoising_steps"],
            mask_id=gen["mask_token_id"])

    @property
    def per_pass(self) -> int:
        return self.block // self.steps

    def model_kwargs(self) -> dict:
        """The keyword overrides models/transformer.py takes: the widths
        under the names they are published by (`d_ff` the dense
        `intermediate_size`, which no layer of this model has; `moe_d_ff`
        the experts')."""
        return dict(
            d_model=self.d, n_layers=self.layers, n_heads=self.heads,
            n_kv_heads=self.kv_heads, head_dim=self.head_dim,
            d_ff=self.d_dense, moe_d_ff=self.d_expert, moe_every=1,
            n_experts=self.experts, expert_top_k=self.top_k,
            vocab_size=self.vocab, rope_theta=self.rope_theta, qk_norm=True,
            gen_block=self.block, gen_steps=self.steps,
            gen_mask_id=self.mask_id)


sizes = Dims.from_config


def model_kwargs(cell, **more) -> dict:
    return dict(cell.dims.model_kwargs(), **more,
                **cell.config["program"].get("model_kwargs", {}))


# -- the weights ----------------------------------------------------------------

# leaf ids: stable numbers folded into the key, never reordered
_LEAF = {"ln_attn": 0, "q": 1, "k": 2, "v": 3, "o": 4, "q_norm": 5,
         "k_norm": 6, "ln_mlp": 7, "router": 8, "w_gate": 9, "w_up": 10,
         "w_down": 11, "embedding": 12, "ln_f": 13, "lm_head": 14}
TOP_LEAVES = ("embedding", "ln_f", "lm_head")


def _bf16(x):
    """The value as the configuration holds it: rounded to bfloat16."""
    return x.astype(jnp.bfloat16)


def layer_leaves(d: Dims, key, i) -> dict:
    """Layer i's weights as they are served: bfloat16. `i` may be traced."""
    def w(name, shape, std, mean=0.0):
        return _bf16(normal(key, i, _LEAF[name], shape, std, mean))

    # a matrix's deviation is fan_in ** -0.5: a layer's output then has
    # the size of its input at any width, the tests' and the published
    # (where it is 0.022, 0.016 and 0.036 for fan-ins of 2,048, 4,096
    # and 768: about the family's 0.02)
    e, f = d.experts, d.d_expert
    in_d, in_o, in_f = d.d ** -0.5, (d.heads * d.head_dim) ** -0.5, f ** -0.5
    return {
        "ln_attn": w("ln_attn", (d.d,), NORM_STD, 1.0),
        "q": w("q", (d.d, d.heads, d.head_dim), in_d),
        "k": w("k", (d.d, d.kv_heads, d.head_dim), in_d),
        "v": w("v", (d.d, d.kv_heads, d.head_dim), in_d),
        "o": w("o", (d.heads, d.head_dim, d.d), in_o),
        "q_norm": w("q_norm", (d.head_dim,), NORM_STD, 1.0),
        "k_norm": w("k_norm", (d.head_dim,), NORM_STD, 1.0),
        "ln_mlp": w("ln_mlp", (d.d,), NORM_STD, 1.0),
        "router": w("router", (d.d, e), in_d),
        "w_gate": w("w_gate", (e, d.d, f), in_d),
        "w_up": w("w_up", (e, d.d, f), in_d),
        "w_down": w("w_down", (e, f, d.d), in_f),
    }


def top_leaf(d: Dims, key, name: str):
    """embedding [V, d], ln_f [d] or lm_head [d, V], bfloat16."""
    shape, std, mean = {
        # the family's initialiser gives the embedding the matrices'
        # 0.02: the residual stream then carries what the layers add,
        # and not the token's own row beside a little else, so a fault
        # in a layer (an expert left out, another mask) moves the logits
        "embedding": ((d.vocab, d.d), W_STD, 0.0),
        "ln_f": ((d.d,), NORM_STD, 1.0),
        "lm_head": ((d.d, d.vocab), d.d ** -0.5, 0.0)}[name]
    return _bf16(normal(key, d.layers, _LEAF[name], shape, std, mean))


def program_layer(w: dict) -> dict:
    """One layer's leaves in the layout of models/transformer.py."""
    attn = {n: {"kernel": w[n]} for n in ("q", "k", "v", "o")}
    attn["q_norm"] = {"scale": w["q_norm"]}
    attn["k_norm"] = {"scale": w["k_norm"]}
    return {
        "ln_attn": {"scale": w["ln_attn"]}, "attn": attn,
        "ln_mlp": {"scale": w["ln_mlp"]},
        "moe": {"router": {"kernel": w["router"]}, "w_gate": w["w_gate"],
                "w_up": w["w_up"], "w_down": w["w_down"]},
    }


def program_params(d: Dims, key) -> dict:
    tree = {f"layer_{i}": program_layer(layer_leaves(d, key, i))
            for i in range(d.layers)}
    tree["embedding"] = top_leaf(d, key, "embedding")
    tree["ln_f"] = {"scale": top_leaf(d, key, "ln_f")}
    tree["lm_head"] = {"kernel": top_leaf(d, key, "lm_head")}
    return tree


def make_program_params(d: Dims, seed: int, shardings=None):
    """One jitted call; every leaf leaves it as bfloat16."""
    fn = jax.jit(lambda k: program_params(d, k), out_shardings=shardings)
    return fn(seed_key(seed))


def served_weights(leaves: dict, bits: int | None) -> dict:
    """Leaves widened to float32, or (the control) quantized to `bits`:
    matrices with one scale for each index of the last axis, an expert
    at a time, a row at a time for the embedding; norm scales exact."""
    out = {}
    for k, v in leaves.items():
        v = v.astype(jnp.float32)
        if bits and v.ndim >= 2:
            if k in ("w_gate", "w_up", "w_down"):
                v = jax.vmap(lambda w: quantize(w, bits))(v)
            else:
                v = quantize(v, bits, k == "embedding")
        out[k] = v
    return out


# -- the plain reference: one layer --------------------------------------------

def _qkv(d: Dims, x, pos, w, lowp):
    """x [n, d] at positions pos [n] -> q [n, H, hd], k, v [n, Hkv, hd]."""
    a = rms_norm(x, w["ln_attn"], d.norm_eps)
    q = mm("nd,dhk->nhk", a, w["q"], lowp)
    k = mm("nd,dhk->nhk", a, w["k"], lowp)
    v = mm("nd,dhk->nhk", a, w["v"], lowp)
    q = rope(rms_norm(q, w["q_norm"], d.norm_eps), pos, d.rope_theta)
    k = rope(rms_norm(k, w["k_norm"], d.norm_eps), pos, d.rope_theta)
    return q, k, v


def _attend(d: Dims, q, k, v, ok, lowp):
    """q [n, H, hd] over k, v [m, Hkv, hd] where ok [n, m]."""
    n = q.shape[0]
    g = d.heads // d.kv_heads
    qg = q.reshape(n, d.kv_heads, g, d.head_dim)

    def head(args):                       # one kv head at a time
        qh, kh, vh = args                 # [n, g, hd], [m, hd], [m, hd]
        s = mm("ngd,md->gnm", qh, kh, lowp) * (d.head_dim ** -0.5)
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return mm("gnm,md->ngd", p, vh, lowp)

    out = jax.lax.map(head, (qg.transpose(1, 0, 2, 3), k.transpose(1, 0, 2),
                             v.transpose(1, 0, 2)))       # [Hkv, n, g, hd]
    return out.transpose(1, 0, 2, 3).reshape(n, d.heads, d.head_dim)


def experts(d: Dims, m, w, lowp=None, top_k: int | None = None):
    """The mixture over rows m [n, d]: every expert in turn, over every
    row, weighted by a gate that is 0 where the row did not choose it."""
    p = jax.nn.softmax(mm("nd,de->ne", m, w["router"], lowp), axis=-1)
    vals, idx = jax.lax.top_k(p, top_k or d.top_k)
    vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(idx, d.experts) * vals[..., None], axis=1)

    def one(e, y):
        h = (jax.nn.silu(mm("nd,df->nf", m, w["w_gate"][e], lowp))
             * mm("nd,df->nf", m, w["w_up"][e], lowp))
        return y + gate[:, e, None] * mm("nf,fd->nd", h, w["w_down"][e], lowp)

    return jax.lax.fori_loop(0, d.experts, one, jnp.zeros_like(m))


def layer(d: Dims, x, w, lowp=None, top_k=None, causal=False):
    """One layer over one sequence x [n, d], positions 0..n-1, under the
    block-causal mask (or, a planted fault, the causal one)."""
    n = x.shape[0]
    pos = jnp.arange(n)
    q, k, v = _qkv(d, x, pos, w, lowp)
    blk = pos if causal else pos // d.block
    a = _attend(d, q, k, v, blk[None, :] <= blk[:, None], lowp)
    h = x + mm("nhk,hkd->nd", a, w["o"], lowp)
    return h + experts(d, rms_norm(h, w["ln_mlp"], d.norm_eps), w, lowp, top_k)


def head_logits(d: Dims, x, top, lowp=None):
    return mm("nd,dv->nv", rms_norm(x, top["ln_f"], d.norm_eps),
              top["lm_head"], lowp)


def sequence_logits(d: Dims, key, tokens, bits=None, lowp=None):
    """Logits [n, V] of one whole sequence from scratch: the naive replay
    that the tests hold `replay` to. n is a multiple of the block."""
    top = served_weights({n: top_leaf(d, key, n) for n in TOP_LEAVES}, bits)
    x = top["embedding"][tokens]
    for i in range(d.layers):
        x = layer(d, x, served_weights(layer_leaves(d, key, i), bits), lowp)
    return head_logits(d, x, top, lowp)


# -- the plain reference: a finished request's block states -------------------

def _map_chunks(fn, size: int, *arrays):
    """fn over chunks of `size` leading rows of the arrays (all rows at
    once where `size` does not divide them), the chunks laid end to end."""
    n = arrays[0].shape[0]
    c = size if n % size == 0 else n
    out = jax.lax.map(fn, tuple(a.reshape((n // c, c) + a.shape[1:])
                                for a in arrays))
    return jax.tree.map(lambda v: v.reshape((n,) + v.shape[2:]), out)


def _state_attend(d: Dims, qs, ks, vs, kc, vc, start, lowp):
    """Each replayed state's B queries qs [S, B, H, hd] over the clean
    keys before its block (kc, vc [T, Hkv, hd], positions < start [S])
    and its own block's ks, vs [S, B, Hkv, hd], all of which it sees."""
    t = kc.shape[0]

    def one(args):
        q, k, v, s0 = args
        ok = jnp.concatenate([jnp.broadcast_to(jnp.arange(t) < s0, (d.block, t)),
                              jnp.ones((d.block, d.block), bool)], axis=1)
        return _attend(d, q, jnp.concatenate([kc, k]),
                       jnp.concatenate([vc, v]), ok, lowp)

    return _map_chunks(jax.vmap(one), STATE_CHUNK, qs, ks, vs, start)


def replay_hidden(d: Dims, key, seq, st_tok, st_start, bits=None, lowp=None,
                  top_k=None, causal=False):
    """The final hidden rows [S, B, d] of every replayed state. `seq` [T]
    is the finished sequence (prompt, then answer; T a multiple of B,
    zeros behind the last whole block), `st_tok` [S, B] the tokens each
    state's block holds (MASK where nothing is fixed yet) and `st_start`
    [S] its block's first position. The committed keys and values a
    state sees are those of the clean sequence's own pass, a layer at a
    time: no position sees a later block, so they are what the passes
    that committed those blocks wrote."""
    top = served_weights({"embedding": top_leaf(d, key, "embedding")}, bits)
    xc = top["embedding"][seq]
    xs = top["embedding"][st_tok]
    s, b = st_tok.shape
    pos_c = jnp.arange(seq.shape[0])
    pos_s = (st_start[:, None] + jnp.arange(b)[None, :]).reshape(-1)

    def body(i, carry):
        xc, xs = carry
        w = served_weights(layer_leaves(d, key, i), bits)
        qc, kc, vc = _qkv(d, xc, pos_c, w, lowp)
        qs, ks, vs = _qkv(d, xs.reshape(s * b, -1), pos_s, w, lowp)
        blk = pos_c if causal else pos_c // d.block
        ac = _attend(d, qc, kc, vc, blk[None, :] <= blk[:, None], lowp)
        shape = lambda a: a.reshape((s, b) + a.shape[1:])   # noqa: E731
        a_s = _state_attend(d, shape(qs), shape(ks), shape(vs), kc, vc,
                            st_start, lowp)
        h = jnp.concatenate([xc, xs.reshape(s * b, -1)]) + mm(
            "nhk,hkd->nd", jnp.concatenate([ac, a_s.reshape(
                s * b, d.heads, d.head_dim)]), w["o"], lowp)
        out = h + experts(d, rms_norm(h, w["ln_mlp"], d.norm_eps), w, lowp,
                          top_k)
        return out[:xc.shape[0]], out[xc.shape[0]:].reshape(xs.shape)

    return jax.lax.fori_loop(0, d.layers, body, (xc, xs))[1]


def _row_stats(d: Dims, key, hidden, tokens, bits=None, other=None,
               lowp=None):
    """For rows hidden [n, d]: the best logit, the log-confidence (best
    less the log-sum-exp) and the logit of `tokens` [n]; with `other`
    (hidden rows of another forward pass, and the bits its head is held
    at) also the logit of the token that pass would put first."""
    top = served_weights({n: top_leaf(d, key, n)
                          for n in ("ln_f", "lm_head")}, bits)
    if other is not None:
        o_rows, o_bits = other
        o_top = served_weights({n: top_leaf(d, key, n)
                                for n in ("ln_f", "lm_head")}, o_bits)

    def chunk(args):
        h, tok, oh = args
        logits = head_logits(d, h, top, lowp)
        best = jnp.max(logits, axis=-1)
        out = {"best": best,
               "logconf": best - jax.nn.logsumexp(logits, axis=-1),
               "at_token": jnp.take_along_axis(logits, tok[:, None], -1)[:, 0]}
        if other is not None:
            first = jnp.argmax(head_logits(d, oh, o_top), axis=-1)
            out["at_other"] = jnp.take_along_axis(
                logits, first[:, None], -1)[:, 0]
        return out

    return _map_chunks(chunk, HEAD_ROWS, hidden, tokens,
                       hidden if other is None else o_rows)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _replay_gaps(d: Dims, ctrl_bits, lowp, top_k, causal,
                 key, seq, st_tok, st_start, st_picked, st_masked, st_served):
    """One finished request against the reference. Per replayed state:
    `gap`, how far the reference's logit of each token fixed in that
    state lies under its best there (the largest of the state);
    `order`, how far the log-confidence of the least confident position
    that was fixed lies under the most confident masked position that
    was not; with `ctrl_bits`, `control_gap`: the gap of the token a
    forward pass at that many bits would have fixed in its place.
    `lowp`, `top_k` and `causal` plant faults in the reference itself
    (the controls): they have to fail a limit."""
    s, b = st_tok.shape
    hid = replay_hidden(d, key, seq, st_tok, st_start, None, lowp, top_k,
                        causal).reshape(s * b, -1)
    other = None
    if ctrl_bits:
        other = (replay_hidden(d, key, seq, st_tok, st_start, ctrl_bits)
                 .reshape(s * b, -1), ctrl_bits)
    st = _row_stats(d, key, hid, st_served.reshape(-1), None, other, lowp)
    st = {k: v.reshape(s, b) for k, v in st.items()}
    gap = jnp.max(jnp.where(st_picked, st["best"] - st["at_token"], 0.0), -1)
    lc = st["logconf"]
    rest = st_masked & ~st_picked
    order = jnp.maximum(
        0.0, jnp.max(jnp.where(rest, lc, -jnp.inf), -1)
        - jnp.min(jnp.where(st_picked, lc, jnp.inf), -1))
    judged = st_picked.any(-1)       # a state in which a known token was fixed
    out = {"gap": gap, "order": jnp.where(judged, order, 0.0),
           "judged": judged}
    if ctrl_bits:
        out["control_gap"] = jnp.max(
            jnp.where(st_picked, st["best"] - st["at_other"], 0.0), -1)
    return out


# -- a prediction, and the states it says its blocks went through --------------

def answer_tokens(prediction):
    """A prediction of the server as the list of tokens that
    `malformed_answers` and `out_tok_per_s` count. A block model
    answers `{"tokens": [...], "fixed_at": [...]}`; None where it is
    nothing of the kind (a step no schedule has is judged with the
    request's own lengths, in `states_of`)."""
    if not isinstance(prediction, dict):
        return None
    toks, at = prediction.get("tokens"), prediction.get("fixed_at")
    if not (isinstance(toks, list) and isinstance(at, list)
            and len(toks) == len(at)
            and all(isinstance(a, int) and a >= 1 for a in at)):
        return None
    return toks


class Impossible(ValueError):
    """A `fixed_at` that the schedule cannot have produced."""


def states_of(d: Dims, prompt, tokens, fixed_at) -> list[dict]:
    """The block states a finished request went through, from the step at
    which each of its tokens was fixed: one for each denoising pass whose
    input is known, as `{"start", "tok" [B], "masked" [B], "picked" [B],
    "served" [B]}`. The prompt's last `len(prompt) % B` tokens open the
    first block as fixed. A pass fixes `per_pass` masked positions (all
    that are left, if fewer), so the steps of a block's positions are
    1, 2, ... each `per_pass` times: anything else raises Impossible.
    The answer's last block may be cut: of its positions behind the
    last token asked for nothing is known, so its states are replayed
    as far as every earlier pass fixed known positions only."""
    b, per = d.block, d.per_pass
    tail = len(prompt) % b
    first = len(prompt) - tail
    toks = list(prompt[first:]) + list(tokens)
    at = [0] * tail + list(fixed_at)            # 0: fixed by the prompt
    out = []
    for lo in range(0, len(toks), b):
        blk_t, blk_a = toks[lo:lo + b], at[lo:lo + b]
        known = len(blk_t)                       # < b: the cut last block
        n_masked = b - sum(a == 0 for a in blk_a)
        steps = -(-n_masked // per)
        if any(a and not 1 <= a <= steps for a in blk_a):
            raise Impossible(f"block at {first + lo}: steps {blk_a} of "
                             f"{n_masked} masked positions")
        for s in range(1, steps + 1):
            picked = [a == s for a in blk_a]
            want = min(per, n_masked - per * (s - 1))
            if sum(picked) > want or (known == b and sum(picked) != want):
                raise Impossible(f"block at {first + lo}: step {s} fixed "
                                 f"{sum(picked)} positions, not {want}")
            pad = [False] * (b - known)
            out.append({
                "start": first + lo,
                "tok": [t if a < s else d.mask_id
                        for t, a in zip(blk_t, blk_a)] + [d.mask_id] * (b - known),
                "masked": [a >= s for a in blk_a] + [True] * (b - known),
                "picked": picked + pad,
                "served": blk_t + [0] * (b - known)})
            if sum(picked) < want:
                break    # a position behind the answer's end was fixed here
    return out


def request_gaps(d: Dims, seed: int, prompt, tokens, fixed_at, t_pad: int,
                 s_pad: int, ctrl_bits=None, lowp=None, top_k=None,
                 causal=False, no_commit=False) -> dict:
    """Host entry: one finished request against the reference, padded to
    `t_pad` positions and `s_pad` states so that one program serves every
    request of a cell. `lowp`, `top_k`, `causal` and `no_commit` plant a
    fault in the reference (the controls); `no_commit` is the cache of a
    program that never commits a block: the keys and values each block's
    last denoising pass wrote, the positions fixed by that pass still
    MASK."""
    sts = states_of(d, prompt, tokens, fixed_at)
    b = d.block
    whole = (len(prompt) + len(tokens)) // b * b
    seq = np.zeros(t_pad, np.int32)
    seq[:whole] = (list(prompt) + list(tokens))[:whole]
    if no_commit:
        at = np.zeros(whole, np.int32)
        at[len(prompt):] = list(fixed_at)[:whole - len(prompt)]
        last = at.reshape(-1, b).max(axis=1, keepdims=True)
        seq[:whole][((at.reshape(-1, b) == last) & (last > 0)).reshape(-1)] = \
            d.mask_id
    arr = {k: np.zeros((s_pad, b), dt) for k, dt in (
        ("tok", np.int32), ("masked", bool), ("picked", bool),
        ("served", np.int32))}
    start = np.zeros(s_pad, np.int32)
    for i, st in enumerate(sts):
        start[i] = st["start"]
        for k in arr:
            arr[k][i] = st[k]
    out = _replay_gaps(d, ctrl_bits, lowp, top_k, causal, seed_key(seed), jnp.asarray(seq),
                       jnp.asarray(arr["tok"]), jnp.asarray(start),
                       jnp.asarray(arr["picked"]), jnp.asarray(arr["masked"]),
                       jnp.asarray(arr["served"]))
    return {k: np.asarray(v)[:len(sts)] for k, v in out.items()}


def _pad_to(n: int, m: int) -> int:
    return -(-n // m) * m


# A state whose fixed token lies this far under the reference's best is
# counted as wide. Of 11,329 states of three sound runs 271 read over 0.1,
# 61 over 0.2, 12 over 0.3, one over 0.4 and none over 0.5; with one
# expert of eight left out 422 read over 0.3 (my chip runs, PR 29;
# PERF.md section 6).
WIDE_GAP = 0.3
JUDGED = ("served_logit_gap", "served_order_gap", "served_wide_share",
          "served_worst_gap")


def judged(outs: list[dict]) -> dict:
    """The numbers a run is judged by, from `request_gaps` of each checked
    request; each is the largest over the requests, so that a fault in
    one slot is not spread over the others. `served_logit_gap` and
    `served_order_gap`: a request's MEAN over the states in which a
    token of it was fixed. `served_wide_share`: the share (%) of those
    states whose logit gap exceeds WIDE_GAP. `served_worst_gap`: the
    widest logit gap of any one state. The means are what tells a fault
    in every state from rounding: with 128 experts, 8 a token, the
    bfloat16 program and the float32 reference route a token or two of
    a state to another eighth expert, which moves single logits by
    tenths at sound and faulty runs alike (PERF.md section 6). The
    share and the worst state are there for a fault in few states, which
    a mean dilutes: the share for some states off by much, the worst
    state for one plainly wrong token."""
    per = []
    for out in outs:
        n = max(1, int(out["judged"].sum()))
        per.append((out["gap"].sum() / n, out["order"].sum() / n,
                    100.0 * (out["gap"] > WIDE_GAP).sum() / n,
                    out["gap"].max()))
    return {name: float(max(col)) for name, col in zip(JUDGED, zip(*per))}


def compare_served(cell, seed: int, sample: list, **fault) -> tuple[dict, dict]:
    """The comparison of a serving cell: every block state of each
    sampled request goes through the reference, and `judged` reduces
    them. A request whose `fixed_at` no schedule can have produced makes
    every number infinite, which no limit admits. Beside them:
    `reference_control_gap`, the mean gap of the tokens the first (the
    longest) request would have been given by the reference at the mix's
    `reference_control_bits`. `fault` plants one in the reference (the
    controls)."""
    d, serve_cfg = cell.dims, cell.config["serve"]
    ctrl_bits = cell.traffic.get("reference_control_bits")
    n_max = serve_cfg["max_new_tokens"]
    t_pad = _pad_to(serve_cfg["prompt_len"] + n_max + d.block, d.block)
    s_pad = _pad_to((n_max // d.block + 2) * d.steps, STATE_CHUNK)
    outs = []
    for j, m in enumerate(sample):
        pred = m["prediction"]
        try:
            outs.append(request_gaps(
                d, seed, m["prompt"], pred["tokens"], pred["fixed_at"],
                t_pad, s_pad, ctrl_bits if j == 0 else None, **fault))
        except Impossible as e:
            print(f"sdar_moe: {e}", file=sys.stderr)
            return dict.fromkeys(JUDGED, float("inf")), {}
    beside = {}
    if ctrl_bits:
        first = outs[0]
        beside["reference_control_gap"] = float(
            first["control_gap"].sum() / max(1, int(first["judged"].sum())))
    return judged(outs), beside


# -- the counts -----------------------------------------------------------------
# The operations and bytes the work needs, from the configuration's shapes
# alone: 8 experts a token, `steps + 1` passes a block, the head where a
# position is still masked. Padding, idle slots, the head on positions
# already fixed and experts read for nothing are the program's cost.

def attn_params(d: Dims) -> int:
    """q, k, v, o of one layer."""
    return d.d * d.head_dim * (2 * d.heads + 2 * d.kv_heads)


def expert_params(d: Dims) -> int:
    """gate, up, down of one expert."""
    return 3 * d.d * d.d_expert


def layer_params(d: Dims) -> int:
    """The matrices one layer holds: attention, router, every expert."""
    return attn_params(d) + d.d * d.experts + d.experts * expert_params(d)


def held_params(d: Dims) -> int:
    """Every layer here, the embedding and the head."""
    return d.layers * layer_params(d) + 2 * d.vocab * d.d


def token_flops(d: Dims) -> int:
    """One token through one layer's matrices: attention, router, and
    the `top_k` experts it chose; multiply and add counted apart."""
    return 2 * (attn_params(d) + d.d * d.experts + d.top_k * expert_params(d))


def attention_flops(d: Dims, keys: int) -> int:
    """QK^T and PV of one layer for queries seeing `keys` keys in all."""
    return 4 * d.heads * d.head_dim * keys


def forward_flops(d: Dims, start: int, stop: int, head_tokens: int) -> int:
    """One pass over the whole blocks at positions start..stop-1 (both
    multiples of the block) under the block-causal mask, the head on
    `head_tokens` of them."""
    n = stop - start
    keys = sum(p // d.block * d.block + d.block for p in range(start, stop))
    return (d.layers * (n * token_flops(d) + attention_flops(d, keys))
            + 2 * d.d * d.vocab * head_tokens)


def request_flops(d: Dims, prompt: int, out: int) -> int:
    """A served request as the published procedure runs it: the prompt's
    whole blocks once, no head; then for each block of the answer one
    denoising pass a step, the head on the positions still masked, and
    one committing pass, no head."""
    b, per = d.block, d.per_pass
    tail = prompt % b
    total = forward_flops(d, 0, prompt - tail, 0)
    masked_first = b - tail
    for i in range(-(-(tail + out) // b)):
        lo = prompt - tail + i * b
        masked = masked_first if i == 0 else b
        while masked > 0:
            total += forward_flops(d, lo, lo + b, masked)
            masked -= per
        total += forward_flops(d, lo, lo + b, 0)
    return total


def dense_pass_bytes(d: Dims) -> int:
    """What every pass reads whatever it routes: attention and router of
    each layer, and the head (embedding rows are a look-up); bfloat16."""
    return 2 * (d.layers * (attn_params(d) + d.d * d.experts)
                + d.d * d.vocab)


def expert_bytes(d: Dims) -> int:
    """One expert of one layer, bfloat16."""
    return 2 * expert_params(d)


def kv_page_bytes(d: Dims, page_size: int) -> int:
    """Keys and values of one page of positions, every layer, bfloat16."""
    return 2 * 2 * d.layers * d.kv_heads * d.head_dim * page_size


def pass_bytes(d: Dims, visits: int, pages: int, page_size: int) -> int:
    """One pass: the dense part, `visits` layer-experts, `pages` pages."""
    return (dense_pass_bytes(d) + visits * expert_bytes(d)
            + pages * kv_page_bytes(d, page_size))
