"""The chip's published peaks, and the operations and bytes the work
needs, from the configuration's shapes alone. They read the same whatever
the program does to get the work done: padding, recomputation and gathers
of more than is needed are the program's cost, not needed work."""

from __future__ import annotations

from benchmarks.lib.spec import Dims

# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}. Add it with its source, never a default.")
    return PEAKS[device_kind]


def layer_matmul_params(d: Dims) -> int:
    """Weights of one layer's matrix products (q, k, v, o, gate, up, down)."""
    attn = d.d * d.head_dim * (2 * d.heads + 2 * d.kv_heads)
    return attn + 3 * d.d * d.d_ff


def visible_keys(position: int, window: int) -> int:
    """Keys a query at 0-based `position` attends to (itself included)."""
    n = position + 1
    return min(n, window) if window else n


def visible_keys_sum(start: int, stop: int, window: int) -> int:
    """Sum of visible_keys over positions start..stop-1, in closed form."""
    def upto(n):  # positions 0..n-1
        if not window or n <= window:
            return n * (n + 1) // 2
        return window * (window + 1) // 2 + (n - window) * window
    return upto(stop) - upto(start)


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
