"""`axk1`: the published SK Telecom A.X-K1 decoder (`model_type` `axk1`,
of the DeepSeek-V3 family): multi-head latent attention (low-rank q and
kv projections with an RMSNorm on each latent, a rotary part of the key
shared by all heads, YaRN frequencies), one leading dense SwiGLU layer
and then mixtures of many SwiGLU experts under a sigmoid router whose
choice is limited to the best groups, beside a shared expert; two norms
a layer, untied head. One chip's share: the router, the bias, the
grouped choice and the gate weights are over every published expert; the
sum is over the shared expert and the experts held here.

The layer, for input x of one sequence whose first real token is at
position 0 (the program left-pads; rotary embeddings are relative):

    a = rms(x; g1)
    cq = rms(a Wq_a; gq) ; q = cq Wq_b -> heads of [q_nope | q_pe]
    [ckv | k_pe] = a Wkv_a ; ckv = rms(ckv; gkv)
    q_pe, k_pe = rope(q_pe), rope(k_pe): halves rotated, YaRN's
        frequencies, k_pe one for all heads
    [k_nope | v] of a head = ckv Wkv_b ; k = [k_nope | k_pe]
    o = softmax(q k^T * scale) v, causal ; scale = (nope + rope) ** -0.5
        * mscale(factor, mscale_all_dim) ** 2
    h = x + o Wo ; m = rms(h; g2)
    dense layer (l < first_k_dense_replace): f = (silu(m Wg) * (m Wu)) Wd
    mixture: s = sigmoid(m Wr), float32 ; c = s + b
             a group's score = the sum of its two largest c ; the
             topk_group best groups stay ; chosen = the k largest c there
             w_e = s_e / (sum of the chosen s + 1e-20) * routed_scaling_factor
             f = shared(m) + sum over chosen e HELD HERE of w_e expert_e(m)
    x' = h + f ; logits = rms(x_L; gf) Wh

The reference computes this up-projected form only: no cache, no
absorbed query, no kernel. It is jax.numpy in float32 at `highest`
matmul precision over weights rounded to bfloat16 (as the configuration
states them), the held experts a plain loop over all of them with a gate
that is zero for those a token did not choose, the queries in blocks so
that 9k positions fit. It imports nothing of the program; the judged
numbers and the shared arithmetic are `afmoe`'s and `lib/`'s."""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.arch.afmoe import (JUDGED, _bf16, _f32,  # noqa: F401
                                   answer_tokens, judged, swiglu)
from benchmarks.lib.opcount import visible_keys_sum
from benchmarks.lib.reference import Q_BLOCK, chunks, mm, rms_norm
from benchmarks.lib.weights import NORM_STD, W_STD, normal, seed_key

BIAS_STD = 0.01      # e_score_correction_bias ~ N(0, BIAS_STD)


# -- the sizes ------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    layers: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int             # a head's query and key values without position
    rope: int             # and those that are rotated (the key's: shared)
    v_dim: int
    d_dense: int
    d_expert: int
    experts: int          # held here: `n_routed_experts` of the file
    experts_total: int    # the router's width: `published.n_routed_experts`
    expert_first: int
    top_k: int
    n_group: int
    topk_group: int
    shared: int
    vocab: int
    rope_theta: float
    yarn: tuple           # (factor, original_max, beta_fast, beta_slow,
                          #  mscale, mscale_all_dim), or () = plain rotary
    norm_eps: float
    route_scale: float
    dense_layers: int

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        if cfg.get("scoring_func") != "sigmoid" or not cfg.get("norm_topk_prob"):
            raise ValueError("axk1 reads sigmoid scores, renormalised")
        if cfg.get("moe_layer_freq", 1) != 1:
            raise ValueError("axk1 reads a mixture in every layer behind "
                             "the leading dense ones")
        rs = cfg.get("rope_scaling") or {}
        if rs and rs.get("type") != "yarn":
            raise ValueError(f"axk1 reads YaRN or plain rotary, not {rs!r}")
        total = cfg.get("published", {}).get(
            "n_routed_experts", cfg["n_routed_experts"])
        if total % cfg["n_group"]:
            raise ValueError("n_group does not divide the experts")
        return cls(
            d=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
            heads=cfg["num_attention_heads"], q_rank=cfg["q_lora_rank"],
            kv_rank=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
            rope=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
            d_dense=cfg["intermediate_size"],
            d_expert=cfg["moe_intermediate_size"],
            experts=cfg["n_routed_experts"], experts_total=total,
            expert_first=cfg.get("share", {}).get("expert_first", 0),
            top_k=cfg["num_experts_per_tok"], n_group=cfg["n_group"],
            topk_group=cfg["topk_group"], shared=cfg["n_shared_experts"],
            vocab=cfg["vocab_size"], rope_theta=float(cfg["rope_theta"]),
            yarn=(float(rs["factor"]),
                  int(rs["original_max_position_embeddings"]),
                  float(rs["beta_fast"]), float(rs["beta_slow"]),
                  float(rs["mscale"]), float(rs["mscale_all_dim"]))
            if rs else (),
            norm_eps=float(cfg["rms_norm_eps"]),
            route_scale=float(cfg["routed_scaling_factor"]),
            dense_layers=cfg["first_k_dense_replace"])

    def is_moe(self, i: int) -> bool:
        return i >= self.dense_layers

    def model_kwargs(self) -> dict:
        """The keyword overrides models/transformer.py takes."""
        yarn = {}
        if self.yarn:
            yarn = dict(zip(("rope_factor", "rope_original_max",
                             "rope_beta_fast", "rope_beta_slow",
                             "rope_mscale", "rope_mscale_all_dim"),
                            self.yarn))
        return dict(
            d_model=self.d, n_layers=self.layers, n_heads=self.heads,
            q_lora_rank=self.q_rank, kv_lora_rank=self.kv_rank,
            qk_nope_head_dim=self.nope, qk_rope_head_dim=self.rope,
            v_head_dim=self.v_dim, d_ff=self.d_dense,
            moe_d_ff=self.d_expert, moe_every=0, n_experts=self.experts,
            n_experts_total=self.experts_total,
            expert_first=self.expert_first, expert_top_k=self.top_k,
            moe_score="sigmoid", moe_route_scale=self.route_scale,
            moe_n_group=self.n_group, moe_topk_group=self.topk_group,
            moe_shared_experts=self.shared, vocab_size=self.vocab,
            rope_theta=self.rope_theta, norm_eps=self.norm_eps, **yarn,
            layer_pattern=[dict(latent=True, moe=self.is_moe(i))
                           for i in range(self.layers)])


sizes = Dims.from_config


def model_kwargs(cell, **more) -> dict:
    return dict(cell.dims.model_kwargs(), **more,
                **cell.config["program"].get("model_kwargs", {}))


# -- YaRN -----------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def inv_freq(d: Dims, plain: bool = False) -> np.ndarray:
    """The frequencies of the rotary part, [rope / 2]: theta ** (-2i /
    rope), and under YaRN that over `factor` for the pairs that turn fewer
    than beta_slow times in original_max positions, itself for those that
    turn more than beta_fast times, and a linear ramp between the two
    dimensions at which a pair turns exactly so often."""
    dim = d.rope
    base = d.rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if plain or not d.yarn:
        return base.astype(np.float32)
    factor, original, fast, slow = d.yarn[:4]

    def dim_of(turns):
        return (dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(d.rope_theta)))

    low, high = max(math.floor(dim_of(fast)), 0), min(
        math.ceil(dim_of(slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return (base / factor * ramp + base * (1 - ramp)).astype(np.float32)


def softmax_scale(d: Dims, no_mscale: bool = False) -> float:
    scale = (d.nope + d.rope) ** -0.5
    if d.yarn and not no_mscale:
        scale *= yarn_mscale(d.yarn[0], d.yarn[5]) ** 2
    return scale


def rotate(x, positions, freqs, mscale: float = 1.0):
    """x [n, heads, rope]; pairs are (i, i + rope / 2)."""
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(freqs)
    cos = jnp.cos(ang)[:, None, :] * mscale
    sin = jnp.sin(ang)[:, None, :] * mscale
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# -- the weights ----------------------------------------------------------------

# leaf ids: stable numbers folded into the key, never reordered
_LEAF = {"ln_attn": 0, "q_a": 1, "q_a_norm": 2, "q_b": 3, "kv_a": 4,
         "kv_a_norm": 5, "kv_b": 6, "o": 7, "ln_mlp": 8, "gate": 9, "up": 10,
         "down": 11, "router": 12, "expert_bias": 13, "w_gate": 14,
         "w_up": 15, "w_down": 16, "shared_gate": 17, "shared_up": 18,
         "shared_down": 19, "embedding": 20, "ln_f": 21, "lm_head": 22}
TOP_LEAVES = ("embedding", "ln_f", "lm_head")


def layer_leaves(d: Dims, key, i: int) -> dict:
    """Layer i's weights as they are served: bfloat16, the selection bias
    (a buffer of the checkpoint) too. Every norm's scale is 1 + NORM_STD
    N(0, 1); a matrix's deviation is fan_in ** -0.5. `i` is a Python
    integer: which leaves a layer has depends on it."""
    def w(name, shape, std, mean=0.0):
        return _bf16(normal(key, i, _LEAF[name], shape, std, mean))

    in_d = d.d ** -0.5
    out = dict(
        ln_attn=w("ln_attn", (d.d,), NORM_STD, 1.0),
        ln_mlp=w("ln_mlp", (d.d,), NORM_STD, 1.0),
        q_a=w("q_a", (d.d, d.q_rank), in_d),
        q_a_norm=w("q_a_norm", (d.q_rank,), NORM_STD, 1.0),
        q_b=w("q_b", (d.q_rank, d.heads, d.nope + d.rope), d.q_rank ** -0.5),
        kv_a=w("kv_a", (d.d, d.kv_rank + d.rope), in_d),
        kv_a_norm=w("kv_a_norm", (d.kv_rank,), NORM_STD, 1.0),
        kv_b=w("kv_b", (d.kv_rank, d.heads, d.nope + d.v_dim),
               d.kv_rank ** -0.5),
        o=w("o", (d.heads, d.v_dim, d.d), (d.heads * d.v_dim) ** -0.5))
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
    """embedding [V, d], ln_f [d] or lm_head [d, V], bfloat16."""
    shape, std, mean = {
        "embedding": ((d.vocab, d.d), W_STD, 0.0),
        "ln_f": ((d.d,), NORM_STD, 1.0),
        "lm_head": ((d.d, d.vocab), d.d ** -0.5, 0.0)}[name]
    return _bf16(normal(key, d.layers, _LEAF[name], shape, std, mean))


def program_layer(d: Dims, w: dict, i: int) -> dict:
    """One layer's leaves in the layout of models/transformer.py."""
    attn = {n: {"kernel": w[n]} for n in ("q_a", "q_b", "kv_a", "o")}
    attn["q_a_norm"] = {"scale": w["q_a_norm"]}
    attn["kv_a_norm"] = {"scale": w["kv_a_norm"]}
    attn["kv_b"] = w["kv_b"]
    out = {"ln_attn": {"scale": w["ln_attn"]},
           "ln_mlp": {"scale": w["ln_mlp"]}, "attn": attn}
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
# "top_k" (experts a token), "no_groups" (a plain choice of the k largest
# over all experts), "no_yarn" (theta's own frequencies), "no_mscale" (the
# softmax scale without YaRN's factor), "no_kv_norm" (the kv latent's
# RMSNorm left out), "bias_in_weight" (the selection bias stays in the
# gate weights).

def attention(q, k, v, scale: float, lowp=None):
    """q, k [n, H, dk], v [n, H, dv] at positions 0..n-1, causal; a block
    of Q_BLOCK queries and one head at a time. n is a multiple of
    Q_BLOCK, or less."""
    n, heads, dk = q.shape
    qb = min(Q_BLOCK, n)
    if n % qb:
        raise ValueError(f"sequence {n} is no multiple of {qb}")
    kt, vt = k.transpose(1, 0, 2), v.transpose(1, 0, 2)

    def block(args):
        qblk, b = args
        ok = jnp.arange(n)[None, :] <= (b * qb + jnp.arange(qb))[:, None]

        def head(hargs):
            qh, kh, vh = hargs            # [qb, dk], [n, dk], [n, dv]
            s = mm("qd,kd->qk", qh, kh, lowp) * scale
            p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
            return mm("qk,kd->qd", p, vh, lowp)

        return jax.lax.map(head, (qblk.transpose(1, 0, 2), kt, vt)
                           ).transpose(1, 0, 2)

    out = jax.lax.map(block, (q.reshape(n // qb, qb, heads, dk),
                              jnp.arange(n // qb)))
    return out.reshape(n, heads, v.shape[-1])


def choose(d: Dims, c, top_k=None, no_groups=False):
    """The ids [n, k] of the experts each row chooses from its scores for
    the choice c [n, E]: the k largest inside the topk_group groups (of E
    / n_group consecutive experts) whose two largest entries sum highest."""
    if not no_groups and d.n_group > 1:
        g = c.reshape(c.shape[0], d.n_group, -1)
        score = jnp.sum(jax.lax.top_k(g, 2)[0], -1)
        _, best = jax.lax.top_k(score, d.topk_group)
        kept = jnp.sum(jax.nn.one_hot(best, d.n_group), 1) > 0
        c = jnp.where(kept[:, :, None], g, -jnp.inf).reshape(c.shape)
    return jax.lax.top_k(c, top_k or d.top_k)[1]


def route(d: Dims, m, w, lowp=None, top_k=None, no_groups=False,
          bias_in_weight=False):
    """The gate of every published expert for rows m [n, d]: [n, E],
    zero where a row did not choose the expert."""
    s = jax.nn.sigmoid(mm("nd,de->ne", m, w["router"].astype(jnp.float32),
                          lowp))
    biased = s + w["expert_bias"]
    idx = choose(d, biased, top_k, no_groups)
    vals = jnp.take_along_axis(biased if bias_in_weight else s, idx, -1)
    vals = vals / (jnp.sum(vals, -1, keepdims=True) + 1e-20) * d.route_scale
    return jnp.sum(jax.nn.one_hot(idx, d.experts_total) * vals[..., None], 1)


def mixture(d: Dims, m, w, lowp=None, held=None, **choice):
    """The shared expert on every row, and of the routed sum the part of
    the experts `held` = (first, count): every held expert in turn, over
    every row, weighted by a gate that is 0 where the row did not choose
    it. `w["w_*"]` are the held experts' matrices."""
    first, count = held or (d.expert_first, d.experts)
    gate = route(d, m, w, lowp, **choice)

    def one(e, y):
        ge, ue, de = (w[n][e].astype(jnp.float32)
                      for n in ("w_gate", "w_up", "w_down"))
        return y + gate[:, first + e, None] * swiglu(m, ge, ue, de, lowp)

    y = swiglu(m, *_f32(w, "shared_gate", "shared_up", "shared_down"), lowp)
    return jax.lax.fori_loop(0, count, one, y)


def attend(d: Dims, x, w, lowp=None, no_yarn=False, no_mscale=False,
           no_kv_norm=False):
    """The attention branch of one layer over x [n, d], before it joins
    the stream: the up-projected form, from scratch."""
    pos = jnp.arange(x.shape[0])
    a = rms_norm(x, w["ln_attn"].astype(jnp.float32), d.norm_eps)
    wqa, wqb, wkva, wkvb, wo = _f32(w, "q_a", "q_b", "kv_a", "kv_b", "o")
    cq = rms_norm(mm("nd,dr->nr", a, wqa, lowp),
                  w["q_a_norm"].astype(jnp.float32), d.norm_eps)
    q = mm("nr,rhk->nhk", cq, wqb, lowp)
    kv = mm("nd,dr->nr", a, wkva, lowp)
    ckv = kv[:, :d.kv_rank]
    if not no_kv_norm:
        ckv = rms_norm(ckv, w["kv_a_norm"].astype(jnp.float32), d.norm_eps)
    freqs = inv_freq(d, plain=no_yarn)
    ms = (yarn_mscale(d.yarn[0], d.yarn[4]) / yarn_mscale(d.yarn[0], d.yarn[5])
          if d.yarn and not no_yarn else 1.0)
    q_pe = rotate(q[..., d.nope:], pos, freqs, ms)
    k_pe = rotate(kv[:, None, d.kv_rank:], pos, freqs, ms)
    kvb = mm("nr,rhk->nhk", ckv, wkvb, lowp)
    k = jnp.concatenate(
        [kvb[..., :d.nope],
         jnp.broadcast_to(k_pe, (x.shape[0], d.heads, d.rope))], -1)
    o = attention(jnp.concatenate([q[..., :d.nope], q_pe], -1), k,
                  kvb[..., d.nope:], softmax_scale(d, no_mscale), lowp)
    return mm("nhk,hkd->nd", o, wo, lowp)


def layer(d: Dims, x, w, i: int, lowp=None, top_k=None, no_groups=False,
          bias_in_weight=False, **attn_fault):
    """Layer i over one sequence x [n, d], positions 0..n-1; n a multiple
    of Q_BLOCK, or less."""
    h = x + attend(d, x, w, lowp, **attn_fault)
    m = rms_norm(h, w["ln_mlp"].astype(jnp.float32), d.norm_eps)
    if d.is_moe(i):
        f = chunks(lambda mc: mixture(
            d, mc, w, lowp, top_k=top_k, no_groups=no_groups,
            bias_in_weight=bias_in_weight), m)
    else:
        f = chunks(lambda mc: swiglu(
            mc, *_f32(w, "gate", "up", "down"), lowp), m)
    return h + f


def hidden(d: Dims, key, tokens, **fault):
    """The last layer's output [n, d] of one whole sequence from scratch."""
    x = top_leaf(d, key, "embedding").astype(jnp.float32)[tokens]
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


def compare_served(cell, seed: int, sample: list, **fault) -> tuple[dict, dict]:
    """The comparison of a serving cell: each sampled request (`prompt`,
    and `prediction` whole as the server returned it) goes once through
    the reference's full forward over prompt and answer, padded to one
    length so that one program serves every request of a cell, and
    `afmoe.judged` reduces the gaps of its served tokens: what a rung in
    the up-projected form and then ticks in the absorbed form through the
    latent pages served is held to one plain forward. `fault` plants one
    in the reference (the controls)."""
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
# slots, rungs longer than the prompt, the pool's rows wider than a latent
# and experts read for nothing are the program's cost.

def attn_params(d: Dims) -> int:
    """q_a, q_b, kv_a, kv_b and o of one layer."""
    return (d.d * d.q_rank + d.q_rank * d.heads * (d.nope + d.rope)
            + d.d * (d.kv_rank + d.rope)
            + d.kv_rank * d.heads * (d.nope + d.v_dim)
            + d.heads * d.v_dim * d.d)


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
    """QK^T and PV of one layer's up-projected form (a prompt's own
    attention) for queries seeing `keys` keys in all: keys of nope + rope
    values, values of v_dim."""
    return 2 * d.heads * (d.nope + d.rope + d.v_dim) * keys


def latent_bytes(d: Dims) -> int:
    """What one position holds in one layer's cache: the latent and the
    key's rotated part, bfloat16."""
    return 2 * (d.kv_rank + d.rope)


def latent_attention_flops(d: Dims, keys: int) -> int:
    """The absorbed form of one layer (a read of the cache) for queries
    seeing `keys` cached positions in all: scores over the latent and the
    rotated part, the weighted sum over the latent."""
    return 2 * d.heads * (d.kv_rank + d.rope + d.kv_rank) * keys


def forward_flops(d: Dims, start: int, stop: int, head_tokens: int) -> float:
    """Forward pass over the tokens at positions start..stop-1 of one
    sequence, the head on `head_tokens` of them. From position 0 it is a
    prompt's own attention, up-projected; behind a cache (start > 0) the
    earlier positions are latents and the attention is the absorbed
    form's, which never makes a cached position's keys and values."""
    keys = visible_keys_sum(start, stop, 0)
    attn = d.layers * (latent_attention_flops(d, keys) if start
                       else attention_flops(d, keys))
    return ((stop - start) * token_flops(d) + attn
            + 2.0 * d.d * d.vocab * head_tokens)


def request_flops(d: Dims, prompt: int, out: int) -> float:
    """A served request: its real prompt up-projected, then `out` tokens
    one by one over the latents; the head once for each output token."""
    return (forward_flops(d, 0, prompt, 1)
            + forward_flops(d, prompt, prompt + out - 1, out - 1))


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


def kv_page_bytes(d: Dims, page_size: int, layers: int | None = None) -> int:
    """The latents of one page of positions in `layers` layers (None:
    every layer; one walk of the table is every layer's)."""
    return latent_bytes(d) * page_size * (d.layers if layers is None
                                          else layers)


def decode_kv_bytes(d: Dims, prompt: int, out: int) -> int:
    """Cache a request's decode ticks have to read: tick i sees the
    positions before it, in every layer."""
    return d.layers * latent_bytes(d) * visible_keys_sum(
        prompt, prompt + out - 1, 0)


def flash_flops(d: Dims, lengths) -> int:
    """The prompts' own attention (QK^T and PV at nope + rope and v_dim,
    every layer) of prefills over real prompts of `lengths`."""
    return sum(d.layers * attention_flops(d, visible_keys_sum(0, n, 0))
               for n in lengths)
