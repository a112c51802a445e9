"""`dense_gqa`: the published dense decoder (pre-RMSNorm, rotary
embeddings in the half-split convention, grouped-query causal attention
with an optional sliding window, SwiGLU, untied head), as Mistral-7B and
the Llama family publish it.

An architecture is everything in the harness that knows the shape of a
model, behind the name in the configuration's `arch` key: the sizes read
from the published keys, the program's keywords, the weights from the
seed (in the program's layout and leaf by leaf), the plain reference in
jax.numpy and float32 at `highest` matmul precision with the comparison
that decides `correct`, and the operations and bytes the work needs.
What no architecture owns it takes from benchmarks/lib: the fold-in rule
of the weights, the reference's shared arithmetic, the window's count of
visible keys. It imports nothing of the program.

The drivers ask for `sizes`, `model_kwargs`, `make_program_params`,
`answer_tokens`, `compare_served`, `leaf_names`, `change_norms` and
`train_reference`; the readers of benchmarks/metrics/device.py for the
counts. benchmarks/README.md says which calls which."""

from __future__ import annotations

import dataclasses
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import reference
from benchmarks.lib.opcount import visible_keys_sum
from benchmarks.lib.reference import (Q_BLOCK, chunk_size, chunks, mm,
                                      quantize, rms_norm, rope)
from benchmarks.lib.weights import NORM_STD, W_STD, normal, seed_key


# -- the sizes ------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Dims:
    """The model sizes the counts, the weights and the reference need,
    read from a configuration file's published (Hugging Face) keys."""

    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    window: int       # 0 = full causal
    norm_eps: float

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        return cls(
            d=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
            heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
            rope_theta=float(cfg["rope_theta"]),
            window=int(cfg.get("sliding_window") or 0),
            norm_eps=float(cfg["rms_norm_eps"]))

    def model_kwargs(self) -> dict:
        """The keyword overrides models/transformer.py takes."""
        return dict(
            d_model=self.d, n_layers=self.layers, n_heads=self.heads,
            n_kv_heads=self.kv_heads, head_dim=self.head_dim,
            d_ff=self.d_ff, vocab_size=self.vocab,
            rope_theta=self.rope_theta, attention_window=self.window)


sizes = Dims.from_config


def model_kwargs(cell, **more) -> dict:
    """What `serve_lm_generator` and `TrainConfig.model_kwargs` are given
    for this cell: every size, then the driver's own (`more`), then the
    `program.model_kwargs` of the configuration's file."""
    return dict(cell.dims.model_kwargs(), **more,
                **cell.config["program"].get("model_kwargs", {}))


# -- the weights ----------------------------------------------------------------

# leaf ids: stable numbers folded into the key, never reordered
_LEAF = {"ln_attn": 0, "q": 1, "k": 2, "v": 3, "o": 4, "ln_mlp": 5,
         "gate": 6, "up": 7, "down": 8,
         "embedding": 9, "ln_f": 10, "lm_head": 11}
TOP_LEAVES = ("embedding", "ln_f", "lm_head")


def _normal(key, layer, name, shape, std, mean=0.0):
    return normal(key, layer, _LEAF[name], shape, std, mean)


def layer_leaves(d: Dims, key, i) -> dict:
    """Layer i's weights, float32, keyed as the reference names them.
    `i` may be traced: one compiled maker serves every layer."""
    return {
        "ln_attn": _normal(key, i, "ln_attn", (d.d,), NORM_STD, 1.0),
        "q": _normal(key, i, "q", (d.d, d.heads, d.head_dim), W_STD),
        "k": _normal(key, i, "k", (d.d, d.kv_heads, d.head_dim), W_STD),
        "v": _normal(key, i, "v", (d.d, d.kv_heads, d.head_dim), W_STD),
        "o": _normal(key, i, "o", (d.heads, d.head_dim, d.d), W_STD),
        "ln_mlp": _normal(key, i, "ln_mlp", (d.d,), NORM_STD, 1.0),
        "gate": _normal(key, i, "gate", (d.d, d.d_ff), W_STD),
        "up": _normal(key, i, "up", (d.d, d.d_ff), W_STD),
        "down": _normal(key, i, "down", (d.d_ff, d.d), W_STD),
    }


def top_leaf(d: Dims, key, name: str):
    """embedding [V, d], ln_f [d] or lm_head [d, V]."""
    if name == "embedding":
        return _normal(key, d.layers, name, (d.vocab, d.d), 1.0)
    if name == "ln_f":
        return _normal(key, d.layers, name, (d.d,), NORM_STD, 1.0)
    if name == "lm_head":
        return _normal(key, d.layers, name, (d.d, d.vocab), W_STD)
    raise KeyError(name)


def program_layer(leaves: dict) -> dict:
    """One layer's leaves in the layout of models/transformer.py."""
    return {
        "ln_attn": {"scale": leaves["ln_attn"]},
        "attn": {n: {"kernel": leaves[n]} for n in ("q", "k", "v", "o")},
        "ln_mlp": {"scale": leaves["ln_mlp"]},
        "mlp": {n: {"kernel": leaves[n]} for n in ("gate", "up", "down")},
    }


def program_params(d: Dims, key) -> dict:
    """The whole parameter tree the program's TransformerLM takes."""
    tree = {f"layer_{i}": program_layer(layer_leaves(d, key, i))
            for i in range(d.layers)}
    tree["embedding"] = top_leaf(d, key, "embedding")
    tree["ln_f"] = {"scale": top_leaf(d, key, "ln_f")}
    tree["lm_head"] = {"kernel": top_leaf(d, key, "lm_head")}
    return tree


def make_program_params(d: Dims, seed: int, shardings=None):
    """One jitted call; `shardings` lays the leaves out as the program's
    state is laid out (a tree like the result, or None for one device)."""
    fn = jax.jit(lambda k: program_params(d, k), out_shardings=shardings)
    return fn(seed_key(seed))


def leaf_names(params: dict) -> dict:
    """The program's parameter tree (or a tree of its shape, such as an
    optimizer's statistics) flattened to the reference's names."""
    out = {}
    for top, sub in params.items():
        if top.startswith("layer_"):
            out[f"{top}/ln_attn"] = sub["ln_attn"]["scale"]
            out[f"{top}/ln_mlp"] = sub["ln_mlp"]["scale"]
            for n in ("q", "k", "v", "o"):
                out[f"{top}/{n}"] = sub["attn"][n]["kernel"]
            for n in ("gate", "up", "down"):
                out[f"{top}/{n}"] = sub["mlp"][n]["kernel"]
        elif top == "embedding":
            out[top] = sub
        else:
            out[top] = sub.get("scale", sub.get("kernel"))
    return out


def change_norms(d: Dims, seed: int, params: dict) -> dict:
    """Norm of each leaf's change since the seed's first weights, by the
    reference's names, a layer at a time so that two copies of the model
    never exist. `params` is the program's tree."""
    # the key is an argument, never a constant closed over: a constant
    # would make each seed a new program, compiled inside set-up
    key = seed_key(seed)
    params = leaf_names(params)
    layer = jax.jit(lambda key, i, now: {
        k: jnp.sqrt(jnp.sum((now[k] - v) ** 2))
        for k, v in layer_leaves(d, key, i).items()})
    out = {}
    for i in range(d.layers):
        now = {k.split("/")[1]: v for k, v in params.items()
               if k.startswith(f"layer_{i}/")}
        out.update({f"layer_{i}/{k}": float(v)
                    for k, v in layer(key, jnp.int32(i), now).items()})
    for n in TOP_LEAVES:
        out[n] = float(jax.jit(lambda key, now, n=n: jnp.sqrt(jnp.sum(
            (now - top_leaf(d, key, n)) ** 2)))(key, params[n]))
    return out


# -- the plain reference --------------------------------------------------------

def attention(d: Dims, q, k, v, lowp=None):
    """q [n, H, hd], k, v [n, Hkv, hd] at positions 0..n-1; causal, and
    within the window where there is one. n is a multiple of Q_BLOCK, or less."""
    n = q.shape[0]
    g = d.heads // d.kv_heads
    qg = q.reshape(n, d.kv_heads, g, d.head_dim)
    kpos = jnp.arange(n)

    def block(args):
        qb, qpos = args
        s = mm("qhgd,khd->hgqk", qb, k, lowp) * (d.head_dim ** -0.5)
        ok = kpos[None, :] <= qpos[:, None]
        if d.window:
            ok = ok & (kpos[None, :] > qpos[:, None] - d.window)
        s = jnp.where(ok[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm("hgqk,khd->qhgd", p, v, lowp)

    qb = min(Q_BLOCK, n)
    if n % qb:
        raise ValueError(f"sequence {n} is no multiple of {qb}")
    out = jax.lax.map(
        jax.checkpoint(block),
        (qg.reshape(n // qb, qb, d.kv_heads, g, d.head_dim),
         kpos.reshape(n // qb, qb)))
    return out.reshape(n, d.heads, d.head_dim)


def layer(d: Dims, x, w, lowp=None):
    """One decoder layer over one sequence x [n, d_model], positions
    0..n-1; n a multiple of Q_BLOCK, or less."""
    n = x.shape[0]
    pos = jnp.arange(n)
    h = rms_norm(x, w["ln_attn"], d.norm_eps)
    q = rope(mm("nd,dhk->nhk", h, w["q"], lowp), pos, d.rope_theta)
    k = rope(mm("nd,dhk->nhk", h, w["k"], lowp), pos, d.rope_theta)
    v = mm("nd,dhk->nhk", h, w["v"], lowp)
    a = attention(d, q, k, v, lowp)
    x = x + mm("nhk,hkd->nd", a, w["o"], lowp)

    def mlp(hc):
        gate = mm("nd,df->nf", hc, w["gate"], lowp)
        up = mm("nd,df->nf", hc, w["up"], lowp)
        return mm("nf,fd->nd", jax.nn.silu(gate) * up, w["down"], lowp)

    h = rms_norm(x, w["ln_mlp"], d.norm_eps)
    return x + chunks(mlp, h)


def head_logits(d: Dims, x, ln_f, lm_head, lowp=None):
    return mm("nd,dv->nv", rms_norm(x, ln_f, d.norm_eps), lm_head, lowp)


def mean_xent(d: Dims, x, ln_f, lm_head, targets, lowp=None):
    """Mean cross-entropy over every position of x [B, T, d]."""
    b, t, _ = x.shape

    def chunk(args):
        xc, yc = args
        logits = head_logits(d, xc, ln_f, lm_head, lowp)
        lse = jax.nn.logsumexp(logits, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(
            logits, yc[:, None], axis=-1)[:, 0])

    size = chunk_size(t)
    xs = x.reshape(b * t // size, size, -1)
    ys = targets.reshape(b * t // size, size)
    return jnp.sum(jax.lax.map(jax.checkpoint(chunk), (xs, ys))) / (b * t)


def served_weights(leaves: dict, bits: int | None) -> dict:
    """A tree of leaves as a weight-only quantized server holds them:
    matrices quantized, norm scales exact."""
    if not bits:
        return leaves
    return {k: (v if v.ndim < 2 else quantize(v, bits, k == "embedding"))
            for k, v in leaves.items()}


# -- serving: one request's logits at its served positions --------------------

@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _served_gaps(d: Dims, n_out_max: int, bits, ctrl_bits,
                 key, tokens, n_prompt, n_out):
    """tokens [T]: the real prompt, then the served tokens, then padding.
    Returns for each served token j < n_out the gap by which its reference
    logit lies below the reference's best, and the same gap for the token
    a forward pass at `ctrl_bits` weights would have put first."""

    def forward(b):
        top = served_weights(
            {n: top_leaf(d, key, n)
             for n in ("embedding", "ln_f", "lm_head")}, b)
        x = top["embedding"][tokens]

        def body(i, x):
            return layer(d, x, served_weights(layer_leaves(d, key, i), b))

        x = jax.lax.fori_loop(0, d.layers, body, x)
        rows = n_prompt - 1 + jnp.arange(n_out_max)
        return head_logits(d, x[rows], top["ln_f"], top["lm_head"])

    logits = forward(bits)
    served = tokens[n_prompt + jnp.arange(n_out_max)]
    live = jnp.arange(n_out_max) < n_out
    best = jnp.max(logits, axis=-1)
    gap = best - jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
    out = {"gap": jnp.where(live, gap, 0.0)}
    if ctrl_bits:
        first = jnp.argmax(forward(ctrl_bits), axis=-1)
        cgap = best - jnp.take_along_axis(logits, first[:, None], -1)[:, 0]
        out["control_gap"] = jnp.where(live, cgap, 0.0)
    return out


def served_gaps(d: Dims, seed: int, bits, prompt, served, n_pad_to: int,
                n_out_max: int, ctrl_bits=None) -> dict:
    """Host entry: one finished request against the reference."""
    toks = np.zeros(n_pad_to, np.int32)
    toks[:len(prompt)] = prompt
    toks[len(prompt):len(prompt) + len(served)] = served
    out = _served_gaps(d, n_out_max, bits, ctrl_bits, seed_key(seed),
                       jnp.asarray(toks), jnp.int32(len(prompt)),
                       jnp.int32(len(served)))
    return {k: np.asarray(v)[:len(served)] for k, v in out.items()}




def answer_tokens(prediction):
    """A prediction of the server as the list of tokens that
    `malformed_answers` and `out_tok_per_s` count: here the server
    returns that list itself. None where it is nothing of the kind."""
    return prediction if isinstance(prediction, list) else None


def compare_served(cell, seed: int, sample: list) -> tuple[dict, dict]:
    """The comparison of a serving cell: each sampled request (`prompt`,
    and `prediction` whole as the server returned it) goes once through
    the reference, which holds its weights as the configuration states
    them, whatever an override (the control) made the program do.
    Returns the judged numbers, each of which needs a limit in the mix's
    file, and the numbers reported beside them and never judged."""
    d, serve_cfg = cell.dims, cell.config["serve"]
    bits = {"int8": 8, "int4": 4}.get(serve_cfg.get("param_dtype"))
    ctrl_bits = cell.traffic.get("reference_control_bits")
    n_max = serve_cfg["max_new_tokens"]
    pad_to = -(-(serve_cfg["prompt_len"] + n_max) // Q_BLOCK) * Q_BLOCK
    gap, cgap = 0.0, 0.0
    for m in sample:
        out = served_gaps(d, seed, bits, m["prompt"],
                          answer_tokens(m["prediction"]), pad_to, n_max,
                          ctrl_bits)
        gap = max(gap, float(out["gap"].max()))
        if ctrl_bits:
            cgap = max(cgap, float(out["control_gap"].max()))
    beside = {"reference_control_gap": cgap} if ctrl_bits else {}
    return {"served_logit_gap": gap}, beside


def train_reference(cell, seed: int, lowp=None, rows=None):
    """The follower of the trainer's first steps (`step`, `change_norms`,
    `close`): the shared outline with this module's layer and leaves."""
    return reference.TrainReference(
        sys.modules[__name__], cell.dims, cell.config["trainer"], seed,
        lowp=lowp, rows=rows)


# -- the counts -----------------------------------------------------------------
# The operations and bytes the work needs, from the configuration's shapes
# alone. They read the same whatever the program does to get the work done:
# padding, recomputation and gathers of more than is needed are the
# program's cost, not needed work.

def layer_matmul_params(d: Dims) -> int:
    """Weights of one layer's matrix products (q, k, v, o, gate, up, down)."""
    attn = d.d * d.head_dim * (2 * d.heads + 2 * d.kv_heads)
    return attn + 3 * d.d * d.d_ff


def attention_flops(d: Dims, keys: int) -> int:
    """Forward QK^T and PV of one layer for queries seeing `keys` keys in
    all: 2 products, multiply and add counted apart."""
    return 4 * d.heads * d.head_dim * keys


def forward_flops(d: Dims, start: int, stop: int, head_tokens: int) -> int:
    """Forward pass over the tokens at positions start..stop-1 of one
    sequence, with the vocabulary head on `head_tokens` of them. The
    embedding is a look-up and counts nothing."""
    n = stop - start
    body = 2 * d.layers * layer_matmul_params(d) * n
    attn = d.layers * attention_flops(d, visible_keys_sum(start, stop, d.window))
    return body + attn + 2 * d.d * d.vocab * head_tokens


def train_flops_per_token(d: Dims, seq_len: int) -> float:
    """Forward and backward (twice the forward), nothing recomputed."""
    return 3.0 * forward_flops(d, 0, seq_len, seq_len) / seq_len


def request_flops(d: Dims, prompt: int, out: int) -> int:
    """A served request: its real prompt, then `out` tokens one by one.
    The head runs once for each output token (the first on the prompt's
    last position); the last output token is never fed back."""
    return forward_flops(d, 0, prompt + out - 1, out)


def weight_bytes(d: Dims, bytes_per_weight: float) -> float:
    """What one decode tick has to read of the weights: every layer and
    the head once (embedding rows are a look-up)."""
    return bytes_per_weight * (d.layers * layer_matmul_params(d)
                               + d.d * d.vocab)


def kv_bytes(d: Dims, keys: int, bytes_per_value: int = 2) -> int:
    """Keys and values of `keys` cached positions, all layers."""
    return 2 * d.layers * d.kv_heads * d.head_dim * bytes_per_value * keys


def decode_kv_bytes(d: Dims, prompt: int, out: int) -> int:
    """Cache a request's decode ticks have to read: tick i (token i+1 of
    `out`, i >= 1) sees the positions before it within the window."""
    return kv_bytes(d, visible_keys_sum(prompt, prompt + out - 1, d.window))


def flash_flops(d: Dims, batch: int, seq_len: int) -> int:
    """Attention kernels of one train step over all layers: forward (2
    products) and backward (dq: 2 products incl. the recomputed scores,
    dk/dv: 3), i.e. 7 products of 2*keys*head_dim per query and head where
    the forward has 2. Counted as needed: forward 2, backward 4 (the
    score recomputation inside the backward kernels is not needed work)."""
    keys = visible_keys_sum(0, seq_len, d.window)
    fwd = attention_flops(d, keys)
    return batch * d.layers * 3 * fwd


def flash_bytes(d: Dims, batch: int, seq_len: int,
                bytes_per_value: int = 2) -> int:
    """Least traffic of those kernels: forward reads q, k, v and writes
    out; backward reads q, k, v, out, dout and writes dq, dk, dv."""
    q = batch * seq_len * d.heads * d.head_dim
    kv = batch * seq_len * d.kv_heads * d.head_dim
    fwd = 2 * q + 2 * kv
    bwd = 4 * q + 4 * kv
    return d.layers * bytes_per_value * (fwd + bwd)
