"""Autoregressive generation with KV-cache decode.

The reference's serving story is TF-Serving REST over exported models;
for LM families the TPU build needs actual decoding. This is the
jit-compiled loop: prefill writes the prompt into each layer's KV cache
in GEMM-shaped position chunks (PREFILL_CHUNK wide; cache-correct by
construction), then the sampling scan feeds each new token back in.
Every decode step is the model's `decode_index` path — [B, 1] tokens
against the cached K/V, so cost per token is O(L) attention reads
instead of O(L^2) recompute.

Sampling: greedy (temperature=0), temperature softmax, optional top-k
truncation. Everything is static-shaped: prompts are right-aligned by
the caller padding to a fixed length; `prompt_len` may be a traced
scalar.
"""

from __future__ import annotations

import functools
import os
from typing import Any

import jax
import jax.numpy as jnp


def init_cache(model, batch: int) -> Any:
    """Zero KV caches shaped for `batch` rows (eval_shape: no FLOPs).
    Shapes come from the model config alone, never from live params."""
    tok1 = jnp.zeros((batch, 1), jnp.int32)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tok1, decode_index=0)
    )
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        shapes.get("cache", {}))


def check_decode_geometry(model, prompt_len: int, max_new_tokens: int) -> None:
    """Decode past max_seq_len is silent garbage (the scalar cache write
    clamps; the vector one-hot write drops) — refuse the geometry up
    front, identically for generate() and the slot decoder."""
    limit = model.cfg.max_seq_len
    if prompt_len + max_new_tokens > limit:
        raise ValueError(
            f"prompt_len + max_new_tokens = {prompt_len + max_new_tokens} "
            f"exceeds the model's max_seq_len {limit}")


# Prefill chunk width: each tick feeds this many positions through the
# model's chunked decode path. Per-token prefill is a GEMV that
# re-streams the full weights once PER POSITION; 128-wide chunks make
# every projection a real GEMM and cut the weight stream ~128x — the
# dominant term of served prompt latency.
PREFILL_CHUNK = 128


def prefill_scan(model, params, cache, prompts, pad_len, chunk=0):
    """Run a [B, P] prompt through the KV cache in position chunks
    (cache-correct by construction: each chunk writes its K/V before
    attending, and the causal mask covers within-chunk order); returns
    (cache, last_logits [B, V]). Full-width chunks scan; a remainder
    chunk (P % width) runs as one extra apply, so EVERY prompt length
    gets GEMM-shaped prefill — never a per-token GEMV tail. The ONE
    prefill implementation — generate(), the slot decoder, and
    speculative decode must never drift apart here.

    `chunk` is the static chunk width (0 = KFTPU_PREFILL_CHUNK env, else
    PREFILL_CHUNK). NOTE the env var is read at TRACE time: jitted
    callers bake it into their compiled program and changing it later in
    the same process has no effect (the jit cache key does not include
    it) — pass `chunk` explicitly for in-process A/Bs; the env hook is
    for per-process sweeps like tools/serve_bench.py."""
    b, lp = prompts.shape
    width = chunk or int(os.environ.get("KFTPU_PREFILL_CHUNK", PREFILL_CHUNK))
    c = min(max(width, 1), lp)
    n_full, rem = (lp // c, lp % c) if c else (0, 0)
    logits = jnp.zeros((b, model.cfg.vocab_size), jnp.float32)
    pad_kw = {} if pad_len is None else {"pad_len": pad_len}

    def chunk_apply(cache, toks, start):
        out, mut = model.apply(
            params | {"cache": cache}, toks, train=False,
            decode_index=start, mutable=["cache"], **pad_kw)
        return mut["cache"], out[:, -1]

    if n_full:
        def tick(carry, xs):
            cache, _ = carry
            toks, start = xs
            return chunk_apply(cache, toks, start), None

        (cache, logits), _ = jax.lax.scan(
            tick, (cache, logits),
            (prompts[:, :n_full * c].reshape(b, n_full, c).swapaxes(0, 1),
             jnp.arange(n_full, dtype=jnp.int32) * c))
    if rem:
        cache, logits = chunk_apply(
            cache, prompts[:, n_full * c:], jnp.int32(n_full * c))
    return cache, logits


def prefill_per_token(model, params, cache, prompts, pad_len):
    """The original one-position-per-tick prefill, kept as the
    differential-test oracle for the chunked implementation."""
    b, lp = prompts.shape

    def tick(carry, xs):
        cache, _ = carry
        tok_col, idx = xs
        out, mut = model.apply(
            params | {"cache": cache}, tok_col[:, None], train=False,
            decode_index=idx, mutable=["cache"],
            **({} if pad_len is None else {"pad_len": pad_len}))
        return (mut["cache"], out[:, 0]), None

    (cache, logits), _ = jax.lax.scan(
        tick,
        (cache, jnp.zeros((b, model.cfg.vocab_size), jnp.float32)),
        (prompts.T, jnp.arange(lp)))
    return cache, logits


def _sample(logits, temperature: float, top_k: int, rng):
    """logits [B, V] -> token ids [B]."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -1e30, logits)
    return jax.random.categorical(rng, logits).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("model", "max_new_tokens",
                                             "temperature", "top_k"))
def generate(model, variables, prompt: jax.Array, *,
             max_new_tokens: int, temperature: float = 0.0, top_k: int = 0,
             seed: int | jax.Array = 0, pad_len: jax.Array | None = None
             ) -> jax.Array:
    """Generate `max_new_tokens` continuations.

    prompt: [B, Lp] int32 (full prompt; all rows same length). For
    ragged batches, LEFT-pad each row to Lp and pass `pad_len` [B] (the
    number of pad positions per row): padded positions are masked out of
    decode attention, and RoPE being relative makes masked left-padding
    exact. `seed` may be a traced scalar (vary per call for independent
    samples). Returns [B, Lp + N].
    """
    b, lp = prompt.shape
    if getattr(model.cfg, "gen_block", 0):
        raise ValueError(
            "generate() decodes one token a step under a causal mask; a "
            "block model (gen_block > 0) is served by "
            "serving/continuous.py:SlotDecoder over the paged KV cache")
    check_decode_geometry(model, lp, max_new_tokens)
    params = {"params": variables["params"]}
    cache = init_cache(model, b)

    # kwarg only when needed: models without ragged-prompt support keep
    # their existing apply signature
    pad_kw = {} if pad_len is None else {"pad_len": pad_len}

    def step(cache, tok_col, idx):
        out, mut = model.apply(
            params | {"cache": cache},
            tok_col[:, None],
            train=False,
            decode_index=idx,
            mutable=["cache"],
            **pad_kw,
        )
        return mut["cache"], out[:, 0]                 # logits [B, V]

    cache, logits = prefill_scan(model, params, cache, prompt, pad_len)

    # decode: sample, feed back
    rng = jax.random.PRNGKey(seed)

    def decode_tick(carry, i):
        cache, logits, rng = carry
        rng, sub = jax.random.split(rng)
        tok = _sample(logits, temperature, top_k, sub)
        cache, logits = step(cache, tok, lp + i)
        return (cache, logits, rng), tok

    (_, _, _), toks = jax.lax.scan(
        decode_tick, (cache, logits, rng), jnp.arange(max_new_tokens))
    return jnp.concatenate([prompt, toks.T], axis=1)
