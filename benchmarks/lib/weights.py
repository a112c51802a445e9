"""The rule by which the benchmark's own weights are made on the device
from --seed. Every architecture (benchmarks/arch/<name>.py) uses it for
its own leaves, twice: to fill the program's parameter tree (one jitted
call, laid out as the program wants it) and, leaf by leaf, for the plain
reference. The program's own initialiser is never used, so the reference
needs nothing the program has made."""

from __future__ import annotations

import jax
import jax.numpy as jnp

W_STD = 0.02       # matrix weights
NORM_STD = 0.05    # norm scales are 1 + this * N(0, 1): not all alike


def seed_key(seed: int):
    """A key from any whole number up to well past 2**31."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def normal(key, layer, leaf_id: int, shape, std, mean=0.0):
    """One leaf: the key folded with the layer's number (which may be
    traced), then with the leaf's id. An architecture gives each of its
    leaves a stable id, never reordered."""
    k = jax.random.fold_in(jax.random.fold_in(key, layer), leaf_id)
    return mean + std * jax.random.normal(k, shape, jnp.float32)
