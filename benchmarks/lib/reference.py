"""What every architecture's plain reference shares: matrix products in
float32 at `highest` precision (or, for the control, on operands rounded
to a lower type), RMS norm, rotary embeddings, the chunking that keeps a
long sequence inside the memory, the weight-only quantization rule, the
optimizer as a configuration states it, and the leaf-by-leaf follower of
a trainer's first steps. No kernels, no cache, no batching; nothing of
the program is imported. The equations of a model are its
architecture's: benchmarks/arch/<name>.py.

`lowp` computes every matrix product on operands rounded to a lower
type: the control that `correct` has to fail (see PERF.md)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512       # queries attended at once
ROW_CHUNK = 2048    # positions of the MLP and of the loss at once


def mm(spec, a, b, lowp=None):
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


def chunk_size(n: int) -> int:
    """The largest of ROW_CHUNK, its halves down to Q_BLOCK, or n itself,
    that divides n."""
    size = ROW_CHUNK
    while size >= Q_BLOCK:
        if n % size == 0:
            return size
        size //= 2
    return n


def chunks(fn, x):
    """fn over row chunks of x [n, ...]; nothing of a chunk is kept for
    the backward pass but its input."""
    n = x.shape[0]
    size = chunk_size(n)
    xs = x.reshape((n // size, size) + x.shape[1:])
    ys = jax.lax.map(jax.checkpoint(fn), xs)
    return ys.reshape((n,) + ys.shape[2:])


# -- weights as a quantized server holds them --------------------------------

def quantize(w, bits: int, per_row: bool = False):
    """Symmetric round-to-nearest weight quantization, returned already
    multiplied back: one scale for each index of the last axis (each row
    for a table that is looked up), scale = largest magnitude / (2**(bits-1) - 1)."""
    top = float(2 ** (bits - 1) - 1)
    axes = tuple(range(1, w.ndim)) if per_row else tuple(range(w.ndim - 1))
    amax = jnp.max(jnp.abs(w), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / top, 1.0)
    return jnp.clip(jnp.round(w / scale), -top, top) * scale


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
    at a time and are applied as soon as they are whole.

    The outline is a stack of `d.layers` layers between an embedding
    look-up and a normed head with a mean cross-entropy; what a layer
    computes and which leaves it has are the architecture's. `model` is
    its module: `layer(d, x, w, lowp)`, `mean_xent(d, x, ln_f, lm_head,
    targets, lowp)`, `layer_leaves(d, key, i)`, `top_leaf(d, key, name)`
    and `TOP_LEAVES` (embedding, final norm, head, in that order). An
    architecture of another outline brings a follower of its own."""

    def __init__(self, model, d, train: dict, seed: int, lowp=None,
                 rows=None):
        self.m, self.d, self.lowp, self.rows = model, d, lowp, rows
        self.emb, self.ln_f, self.head = model.TOP_LEAVES
        # the key is an argument of every compiled maker, never a constant
        # closed over: a constant would make each seed a new program
        self.tx = make_tx(train)
        self._make_layer = jax.jit(lambda key, i: model.layer_leaves(d, key, i))
        self._make_top = {n: jax.jit(lambda key, n=n: model.top_leaf(d, key, n))
                          for n in model.TOP_LEAVES}
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
            lambda r: model.layer(d, r, w, lp))(x))

        def row_vjp(x, w, dy):
            _, vjp = jax.vjp(lambda x, w: model.layer(d, x, w, lp), x, w)
            return vjp(dy)

        self._row_vjp = jax.jit(row_vjp)
        self._top = jax.jit(jax.value_and_grad(
            lambda x, ln_f, head, y: model.mean_xent(d, x, ln_f, head, y, lp),
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
        xs = [self.top[self.emb][tok]]
        for w in self.layers:
            xs.append(self._fwd(xs[-1], w))
        loss, (dx, g_ln, g_head) = self._top(
            xs.pop(), self.top[self.ln_f], self.top[self.head], tgt)
        norms: dict = {}
        self._apply(self.ln_f, g_ln, self.top, self.ln_f, norms)
        self._apply(self.head, g_head, self.top, self.head, norms)
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
        self._apply(self.emb, g_emb, self.top, self.emb, norms)
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
