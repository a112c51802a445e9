"""The plain reference: the published decoder (pre-RMSNorm, rotary
embeddings in the half-split convention, grouped-query causal attention
with an optional sliding window, SwiGLU, untied head) in jax.numpy and
float32 at `highest` matmul precision. No kernels, no cache, no batching.
It imports nothing of the program and takes its weights from
benchmarks/lib/weights.py, leaf by leaf, so that it fits beside nothing.

`lowp` computes every matrix product on operands rounded to a lower
type: the control that `correct` has to fail (see PERF.md)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import weights as W
from benchmarks.lib.spec import Dims

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512       # queries attended at once
ROW_CHUNK = 2048    # positions of the MLP and of the loss at once


def _mm(spec, a, b, lowp=None):
    if lowp is not None:
        a = a.astype(lowp).astype(jnp.float32)
        b = b.astype(lowp).astype(jnp.float32)
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """x [n, heads, head_dim]; pairs are (i, i + head_dim/2)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _chunk_size(n: int) -> int:
    """The largest of ROW_CHUNK, its halves down to Q_BLOCK, or n itself,
    that divides n."""
    size = ROW_CHUNK
    while size >= Q_BLOCK:
        if n % size == 0:
            return size
        size //= 2
    return n


def _chunks(fn, x):
    """fn over row chunks of x [n, ...]; nothing of a chunk is kept for
    the backward pass but its input."""
    n = x.shape[0]
    size = _chunk_size(n)
    xs = x.reshape((n // size, size) + x.shape[1:])
    ys = jax.lax.map(jax.checkpoint(fn), xs)
    return ys.reshape((n,) + ys.shape[2:])


def attention(d: Dims, q, k, v, lowp=None):
    """q [n, H, hd], k, v [n, Hkv, hd] at positions 0..n-1; causal, and
    within the window where there is one. n is a multiple of Q_BLOCK, or less."""
    n = q.shape[0]
    g = d.heads // d.kv_heads
    qg = q.reshape(n, d.kv_heads, g, d.head_dim)
    kpos = jnp.arange(n)

    def block(args):
        qb, qpos = args
        s = _mm("qhgd,khd->hgqk", qb, k, lowp) * (d.head_dim ** -0.5)
        ok = kpos[None, :] <= qpos[:, None]
        if d.window:
            ok = ok & (kpos[None, :] > qpos[:, None] - d.window)
        s = jnp.where(ok[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("hgqk,khd->qhgd", p, v, lowp)

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
    q = rope(_mm("nd,dhk->nhk", h, w["q"], lowp), pos, d.rope_theta)
    k = rope(_mm("nd,dhk->nhk", h, w["k"], lowp), pos, d.rope_theta)
    v = _mm("nd,dhk->nhk", h, w["v"], lowp)
    a = attention(d, q, k, v, lowp)
    x = x + _mm("nhk,hkd->nd", a, w["o"], lowp)

    def mlp(hc):
        gate = _mm("nd,df->nf", hc, w["gate"], lowp)
        up = _mm("nd,df->nf", hc, w["up"], lowp)
        return _mm("nf,fd->nd", jax.nn.silu(gate) * up, w["down"], lowp)

    h = rms_norm(x, w["ln_mlp"], d.norm_eps)
    return x + _chunks(mlp, h)


def head_logits(d: Dims, x, ln_f, lm_head, lowp=None):
    return _mm("nd,dv->nv", rms_norm(x, ln_f, d.norm_eps), lm_head, lowp)


def mean_xent(d: Dims, x, ln_f, lm_head, targets, lowp=None):
    """Mean cross-entropy over every position of x [B, T, d]."""
    b, t, _ = x.shape

    def chunk(args):
        xc, yc = args
        logits = head_logits(d, xc, ln_f, lm_head, lowp)
        lse = jax.nn.logsumexp(logits, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(
            logits, yc[:, None], axis=-1)[:, 0])

    size = _chunk_size(t)
    xs = x.reshape(b * t // size, size, -1)
    ys = targets.reshape(b * t // size, size)
    return jnp.sum(jax.lax.map(jax.checkpoint(chunk), (xs, ys))) / (b * t)


# -- weights as the configuration states them --------------------------------

def quantize(w, bits: int, per_row: bool = False):
    """Symmetric round-to-nearest weight quantization, returned already
    multiplied back: one scale for each index of the last axis (each row
    for a table that is looked up), scale = largest magnitude / (2**(bits-1) - 1)."""
    top = float(2 ** (bits - 1) - 1)
    axes = tuple(range(1, w.ndim)) if per_row else tuple(range(w.ndim - 1))
    amax = jnp.max(jnp.abs(w), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / top, 1.0)
    return jnp.clip(jnp.round(w / scale), -top, top) * scale


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
            {n: W.top_leaf(d, key, n)
             for n in ("embedding", "ln_f", "lm_head")}, b)
        x = top["embedding"][tokens]

        def body(i, x):
            return layer(d, x, served_weights(W.layer_leaves(d, key, i), b))

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
    out = _served_gaps(d, n_out_max, bits, ctrl_bits, W.seed_key(seed),
                       jnp.asarray(toks), jnp.int32(len(prompt)),
                       jnp.int32(len(served)))
    return {k: np.asarray(v)[:len(served)] for k, v in out.items()}


# -- training: the first steps, leaf by leaf ----------------------------------

def make_tx(train: dict):
    """The optimizer the configuration states, from optax (a library, not
    the program): warm-up and cosine decay of the learning rate, then
    adafactor (scaled by the parameter's RMS) or adamw (b1 0.9, b2 0.95)."""
    import optax

    sched = optax.warmup_cosine_decay_schedule(
        0.0, train["learning_rate"], train["warmup_steps"],
        max(train["total_steps"], train["warmup_steps"] + 1))
    if train["optimizer"] == "adafactor":
        return optax.adafactor(
            learning_rate=sched, multiply_by_parameter_scale=True,
            weight_decay_rate=train["weight_decay"] or None)
    if train["optimizer"] == "adamw":
        return optax.adamw(sched, b1=0.9, b2=0.95,
                           weight_decay=train["weight_decay"])
    raise ValueError(f"reference has no optimizer {train['optimizer']!r}")


class TrainReference:
    """Follows the program's first steps on one device. Holds the float32
    parameters and each leaf's optimizer state; gradients exist one layer
    at a time and are applied as soon as they are whole."""

    def __init__(self, d: Dims, train: dict, seed: int, lowp=None,
                 rows=None):
        self.d, self.lowp, self.rows = d, lowp, rows
        # the key is an argument of every compiled maker, never a constant
        # closed over: a constant would make each seed a new program
        self.tx = make_tx(train)
        self._make_layer = jax.jit(lambda key, i: W.layer_leaves(d, key, i))
        self._make_top = {n: jax.jit(lambda key, n=n: W.top_leaf(d, key, n))
                          for n in ("embedding", "ln_f", "lm_head")}
        key = W.seed_key(seed)
        self.layers = [self._make_layer(key, jnp.int32(i))
                       for i in range(d.layers)]
        self.top = {n: make(key) for n, make in self._make_top.items()}
        self._init = jax.jit(self.tx.init)
        self.opt = {k: self._init(v) for k, v in self.flat().items()}

        def update(g, st, p):
            import optax
            u, st = self.tx.update(g, st, p)
            return optax.apply_updates(p, u), st, jnp.sum(g * g)

        self._update = jax.jit(update, donate_argnums=(0, 2))
        lp = lowp
        self._fwd = jax.jit(lambda x, w: jax.vmap(
            lambda r: layer(d, r, w, lp))(x))

        def row_vjp(x, w, dy):
            _, vjp = jax.vjp(lambda x, w: layer(d, x, w, lp), x, w)
            return vjp(dy)

        self._row_vjp = jax.jit(row_vjp)
        self._top = jax.jit(jax.value_and_grad(
            lambda x, ln_f, head, y: mean_xent(d, x, ln_f, head, y, lp),
            argnums=(0, 1, 2)))
        self._emb_grad = jax.jit(
            lambda dx, tok, v: jnp.zeros((v, dx.shape[-1]), jnp.float32)
            .at[tok.reshape(-1)].add(dx.reshape(-1, dx.shape[-1])),
            static_argnums=2)

    def flat(self) -> dict:
        out = {f"layer_{i}/{k}": v for i, lw in enumerate(self.layers)
               for k, v in lw.items()}
        out.update(self.top)
        return out

    def _apply(self, name, grad, holder, key, norms):
        # holder[key] is donated: keep no other reference to it
        p = holder[key]
        holder[key] = None
        holder[key], self.opt[name], ss = self._update(
            grad, self.opt[name], p)
        norms[name] = ss

    def step(self, tokens: np.ndarray, targets: np.ndarray) -> dict:
        """One optimizer step; returns the loss and each leaf's gradient
        norm. `rows` (a fault for the tests) trains on those rows only."""
        if self.rows is not None:
            tokens, targets = tokens[self.rows], targets[self.rows]
        tok, tgt = jnp.asarray(tokens), jnp.asarray(targets)
        xs = [self.top["embedding"][tok]]
        for w in self.layers:
            xs.append(self._fwd(xs[-1], w))
        loss, (dx, g_ln, g_head) = self._top(
            xs.pop(), self.top["ln_f"], self.top["lm_head"], tgt)
        norms: dict = {}
        self._apply("ln_f", g_ln, self.top, "ln_f", norms)
        self._apply("lm_head", g_head, self.top, "lm_head", norms)
        del g_ln, g_head
        for i in reversed(range(self.d.layers)):
            x, w = xs.pop(), self.layers[i]
            dxs, gw = [], None
            for r in range(x.shape[0]):
                dxr, gr = self._row_vjp(x[r], w, dx[r])
                dxs.append(dxr)
                gw = gr if gw is None else jax.tree.map(jnp.add, gw, gr)
            dx = jnp.stack(dxs)
            for k in list(w):
                self._apply(f"layer_{i}/{k}", gw.pop(k), w, k, norms)
        g_emb = self._emb_grad(dx, tok, self.d.vocab)
        self._apply("embedding", g_emb, self.top, "embedding", norms)
        return {"loss": float(loss),
                "grad_norm": {k: float(np.sqrt(v)) for k, v in norms.items()}}

    def change_norms(self, seed: int) -> dict:
        """Norm of each leaf's change since the seed's first weights."""
        key = W.seed_key(seed)
        diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum((a - b) ** 2)))
        out = {}
        for i, w in enumerate(self.layers):
            w0 = self._make_layer(key, jnp.int32(i))
            for k in w:
                out[f"layer_{i}/{k}"] = float(diff(w[k], w0[k]))
        for n in self.top:
            out[n] = float(diff(self.top[n], self._make_top[n](key)))
        return out

    def close(self) -> None:
        """Give the device memory back now: the compiled closures hold
        this object in a cycle, so waiting for the collector is not enough
        where a second reference has to fit."""
        for leaf in jax.tree.leaves((self.layers, self.top, self.opt)):
            if hasattr(leaf, "delete") and not leaf.is_deleted():
                leaf.delete()
