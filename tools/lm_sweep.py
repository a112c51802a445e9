"""LM perf sweep runner: measure every queued operating point, record
EVERY outcome (including OOMs) to tools/lm_sweep.log, promote the best.

Replaces the original lm_sweep.sh loop, whose `2>/dev/null | tail -1`
silently dropped failed points: `bench.py --workload lm` re-raises on
failure (bench.py main: workload=="lm" has no error-JSON fallback), so an
OOM produced no stdout and the log recorded nothing — the round-2 queue
looked "unrun" when in fact most points had failed. Here each point
appends one JSON line: bench's own output on success, or
{"point": ..., "rc": ..., "oom": ..., "error": <stderr tail>} on failure,
so the ledger distinguishes "didn't fit" from "never measured".

Usage: python tools/lm_sweep.py [--log PATH] [--timeout SECS]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

# The queue. Ordered so the validation points (do the round-3 model/remat
# changes reproduce and beat the round-2 ledger?) run before the
# larger-model frontier, and kernel block tuning runs last on a known-
# good config. Every point uses adafactor: round 2 established that
# adamw's 8 bytes/param optimizer state is what OOMs larger-than-350m
# models on one 16 GB v5e.
POINTS: list[dict] = [
    # -- validation: round-2 best, now with the bf16-matmul LM head
    dict(model="gpt-350m", batch=8),
    # -- bigger batch via selective remat (d_ff-wide tensors dropped)
    dict(model="gpt-350m", batch=16, remat="mlp"),
    dict(model="gpt-350m", batch=32, remat="mlp"),
    # -- gpt-760m frontier: arithmetic intensity grows with d_model
    dict(model="gpt-760m", batch=8, remat="mlp"),
    dict(model="gpt-760m", batch=16, remat="mlp"),
    dict(model="gpt-760m", batch=16, remat="full"),
    dict(model="gpt-760m", batch=32, remat="full"),
    # -- llama-1b: the judge's round-3 target class
    dict(model="llama-1b", batch=8, remat="mlp"),
    dict(model="llama-1b", batch=16, remat="mlp"),
    dict(model="llama-1b", batch=16, remat="full"),
    dict(model="llama-1b", batch=32, remat="full"),
]

# Phase 2 (--phase2): chunked head cross-entropy (ops/xent.py). Phase-1
# hardware showed every batch>=16 point OOMs on the [B, L, V] logits +
# dlogits pair (4.2 GB at bs16) — chunking removes exactly that tensor,
# so these re-run the failed frontier with xent_chunks=8.
PHASE2_POINTS: list[dict] = [
    dict(model="gpt-350m", batch=16, remat="mlp", xent_chunks=8),
    dict(model="gpt-350m", batch=32, remat="mlp", xent_chunks=8),
    dict(model="gpt-350m", batch=16, xent_chunks=8),
    dict(model="gpt-760m", batch=16, remat="mlp", xent_chunks=8),
    dict(model="gpt-760m", batch=32, remat="mlp", xent_chunks=8),
    dict(model="llama-1b", batch=16, remat="mlp", xent_chunks=8),
    dict(model="llama-1b", batch=32, remat="mlp", xent_chunks=8),
    dict(model="llama-1b", batch=32, remat="full", xent_chunks=8),
]

# Phase 3 (--phase3): gradient accumulation. Full remat (phase-1 best,
# 0.467 MFU) re-runs the whole forward in backward — a 2N/8N recompute
# tax. Accumulating over small microbatches keeps per-microbatch
# activations small enough for the cheap "mlp" policy (or none), so the
# tax drops to ~2/9 of block MACs (or zero) while the optimizer still
# sees the full global batch.
PHASE3_POINTS: list[dict] = [
    dict(model="llama-1b", batch=16, grad_accum=4, remat="mlp", xent_chunks=8),
    dict(model="llama-1b", batch=32, grad_accum=8, remat="mlp", xent_chunks=8),
    dict(model="llama-1b", batch=16, grad_accum=4, xent_chunks=8),
    dict(model="gpt-760m", batch=16, grad_accum=4, remat="mlp", xent_chunks=8),
    dict(model="gpt-760m", batch=16, grad_accum=2, remat="mlp", xent_chunks=8),
    dict(model="gpt-350m", batch=16, grad_accum=2, remat="mlp", xent_chunks=8),
    dict(model="gpt-350m", batch=32, grad_accum=4, remat="mlp", xent_chunks=8),
    # diagnostics: how much of the block win transfers to the small model
    dict(model="gpt-350m", batch=8, xent_chunks=8),
    dict(model="gpt-760m", batch=8, xent_chunks=8),
]

# Phase 4 (--phase4): the post-0.49 frontier. Chunked CE + the 512
# block defaults opened configs phases 1-3 never measured: mid-size
# batches under full remat, gpt-760m (which OOMed unchunked), and the
# small-model diagnostic.
PHASE4_POINTS: list[dict] = [
    dict(model="llama-1b", batch=16, remat="full", xent_chunks=8),
    dict(model="gpt-350m", batch=16, remat="full", xent_chunks=8),
    dict(model="gpt-350m", batch=16, remat="mlp", xent_chunks=16),
    dict(model="gpt-760m", batch=8, remat="mlp", xent_chunks=8),
    dict(model="gpt-760m", batch=8, remat="full", xent_chunks=8),
    dict(model="gpt-760m", batch=16, remat="full", xent_chunks=8),
    dict(model="gpt-125m", batch=16, xent_chunks=8),
    # EP story: measured MoE dispatch overhead on one chip (experts
    # local); ~1.6B total / ~550M active params with adafactor
    dict(model="gpt-moe-8e", batch=8, remat="mlp", xent_chunks=8),
    dict(model="gpt-moe-8e", batch=8, remat="full", xent_chunks=8),
]

# Phase 5 (--phase5): feature-cost ledger for the round-3 additions —
# sliding-window attention A/B at the measured operating points, plus a
# reconfirmation of the promoted best under the current code.
PHASE5_POINTS: list[dict] = [
    dict(model="gpt-350m", batch=8, xent_chunks=8),
    dict(model="gpt-350m", batch=8, xent_chunks=8, window=512),
    dict(model="gpt-350m", batch=8, xent_chunks=8, window=1024),
    dict(model="llama-1b", batch=32, remat="full", xent_chunks=8),
    dict(model="llama-1b", batch=32, remat="full", xent_chunks=8,
         window=512),
]

# Flash-attention block grid, applied to the best point found above.
# Phase-1 hardware: 128/128 0.227 < 256/256 0.368 < 256/512 0.434 <
# 512/512 0.467 (llama-1b bs16) — monotone in block area so far, so the
# grid now probes past the new 512/512 default.
BLOCK_GRID = [(512, 1024), (1024, 512), (1024, 1024), (512, 2048),
              (2048, 2048)]


def bench_cmd(point: dict) -> list[str]:
    cmd = [sys.executable, "bench.py", "--workload", "lm",
           "--lm-model", point["model"],
           "--lm-batch", str(point["batch"]),
           "--lm-optimizer", point.get("optimizer", "adafactor")]
    if point.get("remat"):
        cmd += ["--lm-remat", "--lm-remat-policy", point["remat"]]
    if point.get("xent_chunks"):
        cmd += ["--lm-xent-chunks", str(point["xent_chunks"])]
    if point.get("grad_accum"):
        cmd += ["--lm-grad-accum", str(point["grad_accum"])]
    if point.get("window"):
        cmd += ["--lm-window", str(point["window"])]
    return cmd


def run_point(point: dict, log, timeout: float, env=None) -> dict | None:
    """Run one bench point; append its outcome line; return the lm dict
    on success."""
    t0 = time.monotonic()
    try:
        proc = subprocess.run(
            bench_cmd(point), cwd=REPO, timeout=timeout,
            capture_output=True, text=True,
            env={**os.environ, **(env or {})})
        rc, out, err = proc.returncode, proc.stdout, proc.stderr
    except subprocess.TimeoutExpired as e:
        # TimeoutExpired carries BYTES even under text=True
        def _s(x):
            return x.decode(errors="replace") if isinstance(x, bytes) else (x or "")

        rc, out = -1, _s(e.stdout)
        err = _s(e.stderr) + f"\n[timeout after {timeout:.0f}s]"
    secs = round(time.monotonic() - t0, 1)
    last = out.strip().splitlines()[-1] if out.strip() else ""
    record: dict | None = None
    if rc == 0 and last.startswith("{"):
        try:
            record = json.loads(last)
        except ValueError:
            record = None
    if record is not None:
        record["sweep_secs"] = secs
        log.write(json.dumps(record) + "\n")
        log.flush()
        return record.get("lm")
    # Allocation-dump markers too: the backend's OOM detail can be pages
    # long and the canonical keyword scrolls out of any fixed tail.
    oom = any(m in err for m in (
        "RESOURCE_EXHAUSTED", "Out of memory", "Allocation type: HLO temp",
        "exceeds the memory available", "scoped vmem limit"))
    # keep both tails: a failure may say why on either stream
    log.write(json.dumps({
        "point": point, "rc": rc, "secs": secs, "oom": oom,
        "error": err.strip()[-400:] or out.strip()[-400:],
    }) + "\n")
    log.flush()
    return None


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--log", default=os.path.join(HERE, "lm_sweep.log"))
    ap.add_argument("--timeout", type=float, default=900.0)
    ap.add_argument("--skip-blocks", action="store_true",
                    help="skip the flash block grid stage")
    phase = ap.add_mutually_exclusive_group()
    phase.add_argument("--phase2", action="store_true",
                       help="run the chunked-xent PHASE2_POINTS queue instead")
    phase.add_argument("--phase3", action="store_true",
                       help="run the grad-accum PHASE3_POINTS queue instead")
    phase.add_argument("--phase4", action="store_true",
                       help="run the post-0.49-frontier PHASE4_POINTS queue")
    phase.add_argument("--phase5", action="store_true",
                       help="run the feature-cost PHASE5_POINTS queue")
    args = ap.parse_args()

    best: dict | None = None
    best_point: dict | None = None
    with open(args.log, "a") as log:
        log.write(json.dumps({"sweep_start": time.strftime(
            "%Y-%m-%d %H:%M:%S", time.gmtime())}) + "\n")
        queue = POINTS
        if args.phase2:
            queue = PHASE2_POINTS
        elif args.phase3:
            queue = PHASE3_POINTS
        elif args.phase4:
            queue = PHASE4_POINTS
        elif args.phase5:
            queue = PHASE5_POINTS
        for point in queue:
            print("point:", point, flush=True)
            lm = run_point(point, log, args.timeout)
            print("  ->", (f"mfu={lm['mfu']:.4f} {lm['tokens_per_sec']} tok/s"
                           if lm else "FAILED (see log)"), flush=True)
            # windowed points do less attention work than the MFU
            # accounting assumes (same invariant as promote_best.py):
            # they must not win the block-grid slot either
            if (lm and not point.get("window")
                    and (best is None or lm["mfu"] > best["mfu"])):
                best, best_point = lm, point
        if best_point is not None and not args.skip_blocks:
            for bq, bk in BLOCK_GRID:
                print(f"blocks q={bq} k={bk} on {best_point}", flush=True)
                lm = run_point(best_point, log, args.timeout, env={
                    "KFTPU_FLASH_BLOCK_Q": str(bq),
                    "KFTPU_FLASH_BLOCK_K": str(bk)})
                print("  ->", (f"mfu={lm['mfu']:.4f}" if lm else "FAILED"),
                      flush=True)
        log.write(json.dumps({"sweep_done": time.strftime(
            "%Y-%m-%d %H:%M:%S", time.gmtime())}) + "\n")
    rc = subprocess.call([sys.executable,
                          os.path.join(HERE, "promote_best.py"), args.log])
    return rc


if __name__ == "__main__":
    sys.exit(main())
