"""The benchmark's own weights, made on the device from --seed.

One rule, used twice: to fill the program's parameter tree (one jitted
call, laid out as the program wants it) and, leaf by leaf, by the plain
reference. The program's own initialiser is never used, so the reference
needs nothing the program has made."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.lib.spec import Dims

# leaf ids: stable numbers folded into the key, never reordered
_LEAF = {"ln_attn": 0, "q": 1, "k": 2, "v": 3, "o": 4, "ln_mlp": 5,
         "gate": 6, "up": 7, "down": 8,
         "embedding": 9, "ln_f": 10, "lm_head": 11}
W_STD = 0.02       # matrix weights
NORM_STD = 0.05    # norm scales are 1 + this * N(0, 1): not all alike


def seed_key(seed: int):
    """A key from any whole number up to well past 2**31."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _normal(key, layer, name, shape, std, mean=0.0):
    k = jax.random.fold_in(jax.random.fold_in(key, layer), _LEAF[name])
    return mean + std * jax.random.normal(k, shape, jnp.float32)


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
