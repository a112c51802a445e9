"""`afmoe`: the published Arcee Trinity decoder (`model_type` `afmoe`;
Trinity-Large-Preview): grouped-query attention with an RMSNorm over
head_dim of q and of k, an output gate, sliding-window layers that
rotate q and k beside full-attention layers that carry no position
embedding at all, four norms a layer, leading dense SwiGLU layers and
then mixtures of many SwiGLU experts under a sigmoid router with a
selection bias, beside a shared expert; muP embedding scale, untied
head. One chip's share: the router, the bias, the choice and the gate
weights are over every published expert; the sum is over the shared
expert and the experts held here.

The layer, for input x of one sequence whose first real token is at
position 0 (the program left-pads; rotary embeddings are relative):

    x_0 = E[t] * sqrt(d)                                       (mup_enabled)
    a = rms(x; g1) ; q, k, v, z = a Wq, a Wk, a Wv, a Wz
    q = rms_hd(q) gq ; k = rms_hd(k) gk
    sliding: rope(q), rope(k) (halves rotated); i sees j iff i - W < j <= i
    full:    no rotary embedding;               i sees j iff j <= i
    o = softmax(q k^T / sqrt(hd)) v * sigmoid(z)
    h = x + rms(o Wo; g2) ; m = rms(h; g3)
    dense layer (l < num_dense_layers): f = (silu(m Wg) * (m Wu)) Wd
    mixture: s = sigmoid(m Wr), float32 ; chosen = the k largest of s + b
             w_e = s_e / (sum of the chosen s + 1e-20) * route_scale
             f = shared(m) + sum over chosen e HELD HERE of w_e expert_e(m)
    x' = h + rms(f; g4) ; logits = rms(x_L; gf) Wh

This module is everything in the harness that knows that shape: the
sizes, the program's keywords, the weights from the seed, the plain
reference with the comparison that decides `correct`, and the counts.
The reference is jax.numpy in float32 at `highest` matmul precision over
weights rounded to bfloat16 (as the configuration states them), the held
experts a plain loop over all of them with a gate that is zero for those
a token did not choose, no cache, no kernel, the queries in blocks so
that 17k positions fit. It imports nothing of the program."""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib.opcount import visible_keys_sum
from benchmarks.lib.reference import Q_BLOCK, chunks, mm, rms_norm, rope
from benchmarks.lib.weights import NORM_STD, W_STD, normal, seed_key

BIAS_STD = 0.01      # expert_bias ~ N(0, BIAS_STD): choice and weight differ


# -- the sizes ------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    d_dense: int
    d_expert: int
    experts: int          # held here: `num_experts` of the file
    experts_total: int    # the router's width: `published.num_experts`
    expert_first: int     # the first held expert's id
    top_k: int
    shared: int           # shared experts, each of the experts' width
    vocab: int
    rope_theta: float
    window: int
    norm_eps: float
    route_scale: float
    embed_scale: float
    sliding: tuple        # a layer: window and rotary (True) or full (False)
    dense_layers: int     # leading layers whose MLP is dense

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        if cfg.get("score_func") != "sigmoid" or not cfg.get("route_norm"):
            raise ValueError("afmoe reads sigmoid scores, renormalised")
        if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
            raise ValueError("afmoe reads no grouped selection")
        types = cfg["layer_types"]
        if len(types) != cfg["num_hidden_layers"]:
            raise ValueError("layer_types does not name every layer")
        share = cfg.get("share", {})
        return cls(
            d=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
            heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            d_dense=cfg["intermediate_size"],
            d_expert=cfg["moe_intermediate_size"],
            experts=cfg["num_experts"],
            experts_total=cfg.get("published", {}).get(
                "num_experts", cfg["num_experts"]),
            expert_first=share.get("expert_first", 0),
            top_k=cfg["num_experts_per_tok"],
            shared=cfg["num_shared_experts"], vocab=cfg["vocab_size"],
            rope_theta=float(cfg["rope_theta"]),
            window=int(cfg["sliding_window"]),
            norm_eps=float(cfg["rms_norm_eps"]),
            route_scale=float(cfg["route_scale"]),
            embed_scale=(float(cfg["hidden_size"]) ** 0.5
                         if cfg.get("mup_enabled") else 1.0),
            sliding=tuple(t == "sliding_attention" for t in types),
            dense_layers=cfg["num_dense_layers"])

    def is_moe(self, i: int) -> bool:
        return i >= self.dense_layers

    def model_kwargs(self) -> dict:
        """The keyword overrides models/transformer.py takes."""
        return dict(
            d_model=self.d, n_layers=self.layers, n_heads=self.heads,
            n_kv_heads=self.kv_heads, head_dim=self.head_dim,
            d_ff=self.d_dense, moe_d_ff=self.d_expert, moe_every=0,
            n_experts=self.experts, n_experts_total=self.experts_total,
            expert_first=self.expert_first, expert_top_k=self.top_k,
            moe_score="sigmoid", moe_route_scale=self.route_scale,
            moe_shared_experts=self.shared, vocab_size=self.vocab,
            rope_theta=self.rope_theta, norm_eps=self.norm_eps,
            qk_norm=True, attn_gate=True, sandwich_norm=True,
            embed_scale=self.embed_scale,
            layer_pattern=[
                dict(window=self.window if s else 0, rope=s,
                     moe=self.is_moe(i))
                for i, s in enumerate(self.sliding)])


sizes = Dims.from_config


def model_kwargs(cell, **more) -> dict:
    return dict(cell.dims.model_kwargs(), **more,
                **cell.config["program"].get("model_kwargs", {}))


# -- the weights ----------------------------------------------------------------

# leaf ids: stable numbers folded into the key, never reordered
_LEAF = {"ln_attn": 0, "q": 1, "k": 2, "v": 3, "o": 4, "z": 5, "q_norm": 6,
         "k_norm": 7, "ln_attn_out": 8, "ln_mlp": 9, "ln_mlp_out": 10,
         "gate": 11, "up": 12, "down": 13, "router": 14, "expert_bias": 15,
         "w_gate": 16, "w_up": 17, "w_down": 18, "shared_gate": 19,
         "shared_up": 20, "shared_down": 21,
         "embedding": 22, "ln_f": 23, "lm_head": 24}
TOP_LEAVES = ("embedding", "ln_f", "lm_head")
NORMS = ("ln_attn", "q_norm", "k_norm", "ln_attn_out", "ln_mlp",
         "ln_mlp_out", "ln_f")


def _bf16(x):
    """The value as the configuration holds it: rounded to bfloat16."""
    return x.astype(jnp.bfloat16)


def layer_leaves(d: Dims, key, i: int) -> dict:
    """Layer i's weights as they are served: bfloat16, the selection
    bias (a buffer of the checkpoint) too. Every norm's scale is 1 + NORM_STD N(0, 1),
    the q and k norms' too (benchmarks/arch/sdar_moe.py says why); a
    matrix's deviation is fan_in ** -0.5. `i` is a Python integer: which
    leaves a layer has depends on it."""
    def w(name, shape, std, mean=0.0):
        return _bf16(normal(key, i, _LEAF[name], shape, std, mean))

    hd = d.heads * d.head_dim
    in_d, in_o = d.d ** -0.5, hd ** -0.5
    out = {n: w(n, (d.head_dim if n in ("q_norm", "k_norm") else d.d,),
                NORM_STD, 1.0) for n in NORMS[:-1]}
    out.update(
        q=w("q", (d.d, d.heads, d.head_dim), in_d),
        k=w("k", (d.d, d.kv_heads, d.head_dim), in_d),
        v=w("v", (d.d, d.kv_heads, d.head_dim), in_d),
        z=w("z", (d.d, d.heads, d.head_dim), in_d),
        o=w("o", (d.heads, d.head_dim, d.d), in_o))
    if not d.is_moe(i):
        f = d.d_dense
        out.update(gate=w("gate", (d.d, f), in_d), up=w("up", (d.d, f), in_d),
                   down=w("down", (f, d.d), f ** -0.5))
        return out
    e, f, fs = d.experts, d.d_expert, d.shared * d.d_expert
    out.update(
        router=w("router", (d.d, d.experts_total), in_d),
        expert_bias=w("expert_bias", (d.experts_total,), BIAS_STD),
        w_gate=w("w_gate", (e, d.d, f), in_d),
        w_up=w("w_up", (e, d.d, f), in_d),
        w_down=w("w_down", (e, f, d.d), f ** -0.5),
        shared_gate=w("shared_gate", (d.d, fs), in_d),
        shared_up=w("shared_up", (d.d, fs), in_d),
        shared_down=w("shared_down", (fs, d.d), fs ** -0.5))
    return out


def top_leaf(d: Dims, key, name: str):
    """embedding [V, d], ln_f [d] or lm_head [d, V], bfloat16. The
    embedding's rows have the matrices' 0.02 before the muP scale."""
    shape, std, mean = {
        "embedding": ((d.vocab, d.d), W_STD, 0.0),
        "ln_f": ((d.d,), NORM_STD, 1.0),
        "lm_head": ((d.d, d.vocab), d.d ** -0.5, 0.0)}[name]
    return _bf16(normal(key, d.layers, _LEAF[name], shape, std, mean))


def program_layer(d: Dims, w: dict, i: int) -> dict:
    """One layer's leaves in the layout of models/transformer.py."""
    attn = {n: {"kernel": w[n]} for n in ("q", "k", "v", "o")}
    attn["gate"] = {"kernel": w["z"]}
    attn["q_norm"] = {"scale": w["q_norm"]}
    attn["k_norm"] = {"scale": w["k_norm"]}
    out = {n: {"scale": w[n]}
           for n in ("ln_attn", "ln_attn_out", "ln_mlp", "ln_mlp_out")}
    out["attn"] = attn
    if not d.is_moe(i):
        out["mlp"] = {n: {"kernel": w[n]} for n in ("gate", "up", "down")}
        return out
    out["moe"] = {
        "router": {"kernel": w["router"]}, "expert_bias": w["expert_bias"],
        "w_gate": w["w_gate"], "w_up": w["w_up"], "w_down": w["w_down"],
        **{n: {"kernel": w[n]}
           for n in ("shared_gate", "shared_up", "shared_down")}}
    return out


def program_params(d: Dims, key) -> dict:
    tree = {f"layer_{i}": program_layer(d, layer_leaves(d, key, i), i)
            for i in range(d.layers)}
    tree["embedding"] = top_leaf(d, key, "embedding")
    tree["ln_f"] = {"scale": top_leaf(d, key, "ln_f")}
    tree["lm_head"] = {"kernel": top_leaf(d, key, "lm_head")}
    return tree


def make_program_params(d: Dims, seed: int, shardings=None):
    """One jitted call; every leaf leaves it as the program serves it."""
    fn = jax.jit(lambda k: program_params(d, k), out_shardings=shardings)
    return fn(seed_key(seed))


# -- the plain reference --------------------------------------------------------
# `fault` plants one in the reference itself, for the controls and the
# tests: "lowp" (a dtype: every matrix product on operands rounded to it),
# "top_k" (experts a token), "no_window" (the sliding layers see every
# earlier key), "rope_full" (the full layers rotate too), "no_gate" (the
# attention's output gate left out), "bias_in_weight" (the selection bias
# stays in the gate weights).

def _f32(w: dict, *names):
    return (w[n].astype(jnp.float32) for n in names)


def attention(d: Dims, q, k, v, window: int, lowp=None):
    """q [n, H, hd], k, v [n, Hkv, hd] at positions 0..n-1, causal and
    (window > 0) within the window; a block of Q_BLOCK queries and one
    KV head at a time. n is a multiple of Q_BLOCK, or less."""
    n = q.shape[0]
    g = d.heads // d.kv_heads
    qb = min(Q_BLOCK, n)
    if n % qb:
        raise ValueError(f"sequence {n} is no multiple of {qb}")
    # a block's queries see the keys of its own positions and, under a
    # window, of the `window` before: a slice of that many, which begins
    # before position 0 for the first blocks (masked below)
    span = min(n, window + qb) if window else n
    pad = span - qb if window else 0
    kp = jnp.pad(k, ((pad, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((pad, 0), (0, 0), (0, 0)))
    qg = q.reshape(n // qb, qb, d.kv_heads, g, d.head_dim)

    def block(args):
        qblk, b = args
        q0 = b * qb
        qpos = q0 + jnp.arange(qb)
        # window: keys q0 - pad .. q0 + qb - 1; full: all of 0 .. n - 1
        k0 = q0 - pad if window else 0
        kpos = k0 + jnp.arange(span)
        ks = jax.lax.dynamic_slice_in_dim(kp, k0 + pad, span)
        vs = jax.lax.dynamic_slice_in_dim(vp, k0 + pad, span)
        ok = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] >= 0)
        if window:
            ok = ok & (kpos[None, :] > qpos[:, None] - window)

        def head(hargs):                  # one kv head at a time
            qh, kh, vh = hargs            # [qb, g, hd], [span, hd] x 2
            s = mm("qgd,kd->gqk", qh, kh, lowp) * (d.head_dim ** -0.5)
            p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
            return mm("gqk,kd->qgd", p, vh, lowp)

        out = jax.lax.map(head, (qblk.transpose(1, 0, 2, 3),
                                 ks.transpose(1, 0, 2), vs.transpose(1, 0, 2)))
        return out.transpose(1, 0, 2, 3)              # [qb, Hkv, g, hd]

    out = jax.lax.map(block, (qg, jnp.arange(n // qb)))
    return out.reshape(n, d.heads, d.head_dim)


def swiglu(m, gate, up, down, lowp=None):
    return mm("nf,fd->nd", jax.nn.silu(mm("nd,df->nf", m, gate, lowp))
              * mm("nd,df->nf", m, up, lowp), down, lowp)


def route(d: Dims, m, w, lowp=None, top_k=None, bias_in_weight=False):
    """The gate of every published expert for rows m [n, d]: [n, E],
    zero where a row did not choose the expert."""
    s = jax.nn.sigmoid(mm("nd,de->ne", m, w["router"].astype(jnp.float32),
                          lowp))
    biased = s + w["expert_bias"]
    _, idx = jax.lax.top_k(biased, top_k or d.top_k)
    vals = jnp.take_along_axis(biased if bias_in_weight else s, idx, -1)
    vals = vals / (jnp.sum(vals, -1, keepdims=True) + 1e-20) * d.route_scale
    return jnp.sum(jax.nn.one_hot(idx, d.experts_total) * vals[..., None], 1)


def mixture(d: Dims, m, w, lowp=None, top_k=None, bias_in_weight=False,
            held=None):
    """The shared expert on every row, and of the routed sum the part of
    the experts `held` = (first, count): every held expert in turn, over
    every row, weighted by a gate that is 0 where the row did not choose
    it. `w["w_*"]` are the held experts' matrices."""
    first, count = held or (d.expert_first, d.experts)
    gate = route(d, m, w, lowp, top_k, bias_in_weight)

    def one(e, y):
        ge, ue, de = (w[n][e].astype(jnp.float32)
                      for n in ("w_gate", "w_up", "w_down"))
        return y + gate[:, first + e, None] * swiglu(m, ge, ue, de, lowp)

    y = swiglu(m, *_f32(w, "shared_gate", "shared_up", "shared_down"), lowp)
    return jax.lax.fori_loop(0, count, one, y)


def layer(d: Dims, x, w, i: int, lowp=None, top_k=None, no_window=False,
          rope_full=False, no_gate=False, bias_in_weight=False):
    """Layer i over one sequence x [n, d], positions 0..n-1; n a multiple
    of Q_BLOCK, or less."""
    n = x.shape[0]
    pos = jnp.arange(n)
    sliding = d.sliding[i]
    a = rms_norm(x, w["ln_attn"].astype(jnp.float32), d.norm_eps)
    wq, wk, wv, wz, wo = _f32(w, "q", "k", "v", "z", "o")
    q = rms_norm(mm("nd,dhk->nhk", a, wq, lowp),
                 w["q_norm"].astype(jnp.float32), d.norm_eps)
    k = rms_norm(mm("nd,dhk->nhk", a, wk, lowp),
                 w["k_norm"].astype(jnp.float32), d.norm_eps)
    v = mm("nd,dhk->nhk", a, wv, lowp)
    if sliding or rope_full:
        q, k = rope(q, pos, d.rope_theta), rope(k, pos, d.rope_theta)
    o = attention(d, q, k, v, d.window if sliding and not no_window else 0,
                  lowp)
    if not no_gate:
        o = o * jax.nn.sigmoid(mm("nd,dhk->nhk", a, wz, lowp))
    h = x + rms_norm(mm("nhk,hkd->nd", o, wo, lowp),
                     w["ln_attn_out"].astype(jnp.float32), d.norm_eps)
    m = rms_norm(h, w["ln_mlp"].astype(jnp.float32), d.norm_eps)
    if d.is_moe(i):
        f = chunks(lambda mc: mixture(d, mc, w, lowp, top_k, bias_in_weight),
                   m)
    else:
        f = chunks(lambda mc: swiglu(
            mc, *_f32(w, "gate", "up", "down"), lowp), m)
    return h + rms_norm(f, w["ln_mlp_out"].astype(jnp.float32), d.norm_eps)


def hidden(d: Dims, key, tokens, **fault):
    """The last layer's output [n, d] of one whole sequence from scratch."""
    x = top_leaf(d, key, "embedding").astype(jnp.float32)[tokens] \
        * d.embed_scale
    for i in range(d.layers):
        x = layer(d, x, layer_leaves(d, key, i), i, **fault)
    return x


def head_logits(d: Dims, key, x, lowp=None):
    return mm("nd,dv->nv",
              rms_norm(x, top_leaf(d, key, "ln_f").astype(jnp.float32),
                       d.norm_eps),
              top_leaf(d, key, "lm_head").astype(jnp.float32), lowp)


def sequence_logits(d: Dims, key, tokens, **fault):
    """Logits [n, V] of one whole sequence: what the tests hold the
    program's prefill and ticks to."""
    return head_logits(d, key, hidden(d, key, tokens, **fault),
                       fault.get("lowp"))


# -- serving: one request's logits at its served positions --------------------

@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _served_gaps(d: Dims, n_out_max: int, fault: tuple,
                 key, tokens, n_prompt, n_out):
    """tokens [T]: the real prompt, then the served tokens, then padding
    (causal: what lies behind changes nothing before it). For each served
    token j < n_out, how far its reference logit lies under the
    reference's best at its position."""
    fault = dict(fault)
    rows = n_prompt - 1 + jnp.arange(n_out_max)
    logits = head_logits(d, key, hidden(d, key, tokens, **fault)[rows],
                         fault.get("lowp"))
    served = tokens[n_prompt + jnp.arange(n_out_max)]
    gap = jnp.max(logits, axis=-1) - jnp.take_along_axis(
        logits, served[:, None], -1)[:, 0]
    return jnp.where(jnp.arange(n_out_max) < n_out, gap, 0.0)


def served_gaps(d: Dims, seed: int, prompt, served, n_pad_to: int,
                n_out_max: int, **fault) -> np.ndarray:
    """Host entry: one finished request against the reference."""
    toks = np.zeros(n_pad_to, np.int32)
    toks[:len(prompt)] = prompt
    toks[len(prompt):len(prompt) + len(served)] = served
    out = _served_gaps(d, n_out_max, tuple(sorted(fault.items())),
                       seed_key(seed), jnp.asarray(toks),
                       jnp.int32(len(prompt)), jnp.int32(len(served)))
    return np.asarray(out)[:len(served)]


def answer_tokens(prediction):
    """A prediction of the server as the list of tokens that
    `malformed_answers` and `out_tok_per_s` count: the server returns
    that list itself. None where it is nothing of the kind."""
    return prediction if isinstance(prediction, list) else None


# A served token whose reference logit lies this far under the
# reference's best is counted as wide (PERF.md section 6, PR 33, says
# what sound runs and the controls read on either side of it).
WIDE_GAP = 0.3
JUDGED = ("served_logit_gap", "served_wide_share", "served_worst_gap")


def judged(gaps: list) -> dict:
    """The numbers a run is judged by, from `served_gaps` of each checked
    request; each is the largest over the requests, so that a fault in
    one slot is not spread over the others. `served_logit_gap`: a
    request's MEAN gap over its served tokens whose gap is no wider than
    WIDE_GAP; `served_wide_share`: the share (%) of its tokens whose gap
    is wider; `served_worst_gap`: the widest gap of any one token. The
    mean is what tells a fault in every token from rounding. It leaves
    the wide tokens to the other two because the bfloat16 program and
    the float32 reference give a token's fourth expert to another now
    and then, which moves single logits by tenths in sound and faulty
    runs alike (PERF.md section 6, PR 29): in an answer of 128 tokens one
    such token of 1.3 is a hundredth in a mean over all of them, twice
    what a sound request reads (PR 33), and a limit over it would be a
    limit on luck."""
    per = []
    for g in gaps:
        wide = g > WIDE_GAP
        per.append((g[~wide].mean() if (~wide).any() else 0.0,
                    100.0 * wide.mean(), g.max()))
    return {name: float(max(col)) for name, col in zip(JUDGED, zip(*per))}


def compare_served(cell, seed: int, sample: list, **fault) -> tuple[dict, dict]:
    """The comparison of a serving cell: each sampled request (`prompt`,
    and `prediction` whole as the server returned it) goes once through
    the reference's full forward over prompt and answer, padded to one
    length so that one program serves every request of a cell, and
    `judged` reduces the gaps of its served tokens. `fault` plants one in
    the reference (the controls)."""
    d, serve_cfg = cell.dims, cell.config["serve"]
    n_max = serve_cfg["max_new_tokens"]
    pad_to = -(-(serve_cfg["prompt_len"] + n_max) // Q_BLOCK) * Q_BLOCK
    return judged([
        served_gaps(d, seed, m["prompt"], answer_tokens(m["prediction"]),
                    pad_to, n_max, **fault) for m in sample]), {}


# -- the counts -----------------------------------------------------------------
# The operations and bytes the work needs on this chip, from the
# configuration's shapes alone: of the routed experts the share held here
# (`top_k * experts / experts_total` a token in the mean). Padding, idle
# slots, rungs longer than the prompt and experts read for nothing are
# the program's cost.

def attn_params(d: Dims) -> int:
    """q, k, v, o and the gate of one layer."""
    return d.d * d.head_dim * (3 * d.heads + 2 * d.kv_heads)


def expert_params(d: Dims) -> int:
    """gate, up, down of one expert."""
    return 3 * d.d * d.d_expert


def layer_dense_params(d: Dims, i: int) -> int:
    """What every token reads of layer i whatever it routes: attention,
    and the dense MLP or the router and the shared experts."""
    mlp = (d.d * d.experts_total + d.shared * expert_params(d)
           if d.is_moe(i) else 3 * d.d * d.d_dense)
    return attn_params(d) + mlp


def moe_layers(d: Dims) -> int:
    return d.layers - d.dense_layers


def token_flops(d: Dims) -> float:
    """One token through every layer's matrices: multiply and add counted
    apart; of the routed experts the share held here, in the mean."""
    held = d.top_k * d.experts / d.experts_total
    return 2.0 * (sum(layer_dense_params(d, i) for i in range(d.layers))
                  + moe_layers(d) * held * expert_params(d))


def attention_flops(d: Dims, keys: int) -> int:
    """QK^T and PV of one layer for queries seeing `keys` keys in all."""
    return 4 * d.heads * d.head_dim * keys


def forward_flops(d: Dims, start: int, stop: int, head_tokens: int) -> float:
    """Forward pass over the tokens at positions start..stop-1 of one
    sequence, the head on `head_tokens` of them; each layer's attention
    over the keys its kind sees."""
    attn = sum(attention_flops(d, visible_keys_sum(
        start, stop, d.window if s else 0)) for s in d.sliding)
    return ((stop - start) * token_flops(d) + attn
            + 2.0 * d.d * d.vocab * head_tokens)


def request_flops(d: Dims, prompt: int, out: int) -> float:
    """A served request: its real prompt, then `out` tokens one by one;
    the head once for each output token."""
    return forward_flops(d, 0, prompt + out - 1, out)


def expert_bytes(d: Dims) -> int:
    """One expert of one layer, bfloat16."""
    return 2 * expert_params(d)


def weight_bytes(d: Dims, bytes_per_weight: float,
                 visits: float | None = None) -> float:
    """What one decode tick has to read of the weights: every layer's
    part outside its routed experts and the head once (embedding rows
    are a look-up), and the experts that were visited: `visits`
    layer-experts a tick, as `moe_expert_visits` counted them (None:
    every held expert of every mixture layer)."""
    if visits is None:
        visits = moe_layers(d) * d.experts
    return bytes_per_weight * (
        sum(layer_dense_params(d, i) for i in range(d.layers))
        + d.d * d.vocab + visits * expert_params(d))


def kv_page_bytes(d: Dims, page_size: int, layers: int = 1) -> int:
    """Keys and values of one page of positions in `layers` layers,
    bfloat16."""
    return 2 * 2 * layers * d.kv_heads * d.head_dim * page_size


def decode_kv_bytes(d: Dims, prompt: int, out: int) -> int:
    """Cache a request's decode ticks have to read, by layer kind: tick i
    sees the positions before it, within the window in a sliding layer."""
    return sum(2 * 2 * d.kv_heads * d.head_dim * visible_keys_sum(
        prompt, prompt + out - 1, d.window if s else 0) for s in d.sliding)


def flash_flops(d: Dims, lengths) -> int:
    """The prompts' own attention (QK^T and PV, every layer by its kind)
    of prefills over real prompts of `lengths`."""
    return sum(attention_flops(d, visible_keys_sum(0, n, d.window if s else 0))
               for n in lengths for s in d.sliding)
