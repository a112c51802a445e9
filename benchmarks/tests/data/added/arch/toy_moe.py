"""`toy_moe`: a second architecture, kept with the tests and never a
benchmark configuration. It is what benchmarks/README.md's recipe "an
architecture" asks of a new file and nothing more: a decoder whose every
layer's MLP is a mixture of experts (top-k of a softmax router,
renormalised, nothing dropped), as the program's `moe_every=1` stack
runs it at a capacity factor of experts / top-k.

It differs from `dense_gqa` in all that an architecture owns: the sizes
(two more published keys), the program's keywords, the parameter tree
(a router and three stacked expert leaves in the place of three
matrices), the counts (top-k experts' operations a token; every expert's
bytes a tick) and the comparison (the name of its judged number, and
weights held in bfloat16 and not int8). Attention, norms and the head are
`dense_gqa`'s: an architecture may build on another."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.arch import dense_gqa as base
from benchmarks.lib.opcount import visible_keys_sum
from benchmarks.lib.reference import Q_BLOCK, mm, rms_norm, rope
from benchmarks.lib.weights import W_STD, normal, seed_key

CALLS: list = []     # the counts the readers asked for: the test looks


@dataclasses.dataclass(frozen=True)
class Dims(base.Dims):
    experts: int = 0
    top_k: int = 0

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        return cls(**dataclasses.asdict(base.Dims.from_config(cfg)),
                   experts=cfg["num_local_experts"],
                   top_k=cfg["num_experts_per_tok"])


sizes = Dims.from_config


def model_kwargs(cell, **more) -> dict:
    d = cell.dims
    return dict(d.model_kwargs(), moe_every=1, n_experts=d.experts,
                expert_top_k=d.top_k,
                moe_capacity_factor=d.experts / d.top_k,   # drops nothing
                **more, **cell.config["program"].get("model_kwargs", {}))


# -- the weights: dense_gqa's ids for what is shared, new ids for the rest ----

_LEAF = {"router": 12, "w_gate": 13, "w_up": 14, "w_down": 15}
_ATTN = ("ln_attn", "q", "k", "v", "o", "ln_mlp")
top_leaf = base.top_leaf


def layer_leaves(d: Dims, key, i) -> dict:
    shared = base.layer_leaves(d, key, i)
    e, f = d.experts, d.d_ff
    return {
        **{n: shared[n] for n in _ATTN},
        "router": normal(key, i, _LEAF["router"], (d.d, e), W_STD),
        "w_gate": normal(key, i, _LEAF["w_gate"], (e, d.d, f), W_STD),
        "w_up": normal(key, i, _LEAF["w_up"], (e, d.d, f), W_STD),
        "w_down": normal(key, i, _LEAF["w_down"], (e, f, d.d), W_STD),
    }


def program_params(d: Dims, key) -> dict:
    tree = {}
    for i in range(d.layers):
        w = layer_leaves(d, key, i)
        tree[f"layer_{i}"] = {
            "ln_attn": {"scale": w["ln_attn"]},
            "attn": {n: {"kernel": w[n]} for n in ("q", "k", "v", "o")},
            "ln_mlp": {"scale": w["ln_mlp"]},
            "moe": {"router": {"kernel": w["router"]}, "w_gate": w["w_gate"],
                    "w_up": w["w_up"], "w_down": w["w_down"]},
        }
    tree["embedding"] = top_leaf(d, key, "embedding")
    tree["ln_f"] = {"scale": top_leaf(d, key, "ln_f")}
    tree["lm_head"] = {"kernel": top_leaf(d, key, "lm_head")}
    return tree


def make_program_params(d: Dims, seed: int, shardings=None):
    fn = jax.jit(lambda k: program_params(d, k), out_shardings=shardings)
    return fn(seed_key(seed))


# -- the plain reference ------------------------------------------------------

def layer(d: Dims, x, w):
    n = x.shape[0]
    pos = jnp.arange(n)
    h = rms_norm(x, w["ln_attn"], d.norm_eps)
    q = rope(mm("nd,dhk->nhk", h, w["q"]), pos, d.rope_theta)
    k = rope(mm("nd,dhk->nhk", h, w["k"]), pos, d.rope_theta)
    v = mm("nd,dhk->nhk", h, w["v"])
    x = x + mm("nhk,hkd->nd", base.attention(d, q, k, v), w["o"])
    h = rms_norm(x, w["ln_mlp"], d.norm_eps)
    probs = jax.nn.softmax(mm("nd,de->ne", h, w["router"]), axis=-1)
    top, _ = jax.lax.top_k(probs, d.top_k)
    gates = jnp.where(probs >= top[:, -1:], probs, 0.0)
    gates = gates / jnp.sum(gates, -1, keepdims=True)
    # every expert on every token, weighted by its gate (nought for the
    # experts a token did not choose): plain, and exact at a toy size
    up = mm("nd,edf->enf", h, w["w_up"])
    act = jax.nn.silu(mm("nd,edf->enf", h, w["w_gate"])) * up
    return x + mm("ne,end->nd", gates, mm("enf,efd->end", act, w["w_down"]))


def _bf16(leaves: dict) -> dict:
    """Weights as a bfloat16 server holds them."""
    return {k: v.astype(jnp.bfloat16).astype(jnp.float32)
            for k, v in leaves.items()}


def _served_gap(d: Dims, key, tokens, n_prompt, n_out, n_out_max):
    top = _bf16({n: top_leaf(d, key, n) for n in base.TOP_LEAVES})
    x = top["embedding"][tokens]
    for i in range(d.layers):
        x = layer(d, x, _bf16(layer_leaves(d, key, i)))
    rows = n_prompt - 1 + jnp.arange(n_out_max)
    logits = base.head_logits(d, x[rows], top["ln_f"], top["lm_head"])
    served = tokens[n_prompt + jnp.arange(n_out_max)]
    gap = jnp.max(logits, -1) - jnp.take_along_axis(
        logits, served[:, None], -1)[:, 0]
    return jnp.max(jnp.where(jnp.arange(n_out_max) < n_out, gap, 0.0))


_served_gap_jit = jax.jit(_served_gap, static_argnums=(0, 5))


def answer_tokens(prediction):
    return prediction if isinstance(prediction, list) else None


def compare_served(cell, seed: int, sample: list) -> tuple[dict, dict]:
    d, serve_cfg = cell.dims, cell.config["serve"]
    n_max = serve_cfg["max_new_tokens"]
    pad_to = -(-(serve_cfg["prompt_len"] + n_max) // Q_BLOCK) * Q_BLOCK
    gap = 0.0
    for m in sample:
        served = answer_tokens(m["prediction"])
        toks = np.zeros(pad_to, np.int32)
        toks[:len(m["prompt"])] = m["prompt"]
        toks[len(m["prompt"]):len(m["prompt"]) + len(served)] = served
        gap = max(gap, float(_served_gap_jit(
            d, seed_key(seed), jnp.asarray(toks), jnp.int32(len(m["prompt"])),
            jnp.int32(len(served)), n_max)))
    return {"moe_logit_gap": gap}, {"requests_compared": float(len(sample))}


# -- the counts ---------------------------------------------------------------

def layer_matmul_params(d: Dims, experts: int) -> int:
    """Weights of one layer's matrix products with `experts` experts in
    them: a token's operations take top_k, a tick's bytes take all."""
    attn = d.d * d.head_dim * (2 * d.heads + 2 * d.kv_heads)
    return attn + d.d * d.experts + experts * 3 * d.d * d.d_ff


def request_flops(d: Dims, prompt: int, out: int) -> int:
    CALLS.append(("request_flops", prompt, out))
    n = prompt + out - 1
    body = 2 * d.layers * layer_matmul_params(d, d.top_k) * n
    attn = d.layers * base.attention_flops(d, visible_keys_sum(0, n, d.window))
    return body + attn + 2 * d.d * d.vocab * out
