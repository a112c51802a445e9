"""The chip's published peaks, and the count of keys a query sees under a
window: what no architecture owns of the operations and bytes the work
needs. The counts themselves (a request's operations, a tick's bytes) are
each architecture's: benchmarks/arch/<name>.py."""

from __future__ import annotations

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


def train_flops_per_token(d, seq_len: int) -> float:
    """The `dense_gqa` count under its old address, for
    tests/test_flops.py, which holds the trainer's own MFU gauge to it
    and which a benchmark PR may not edit (PERF.md section 7). Nothing in
    the benchmark calls it."""
    from benchmarks.arch import dense_gqa

    return dense_gqa.train_flops_per_token(d, seq_len)
