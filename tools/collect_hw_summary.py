"""Render a round's hardware ledger as markdown tables.

Reads the watcher's stage outputs (tools/r{N}_stages/*.out — each holds a
bench.py or serve_bench.py JSON line) plus the promoted
serve_table.json, and prints markdown: one LM
table (model / batch / policy / MFU / tok/s), one ResNet row set, one
serving table. Stages that never ran or failed are listed as such, so
the ledger distinguishes "didn't fit / didn't run" from "never
measured" — the same honesty rule as lm_sweep's failure records.

Usage: python tools/collect_hw_summary.py [STAGE_DIR]
"""

from __future__ import annotations

import glob
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def stage_records(stage_dir):
    for out in sorted(glob.glob(os.path.join(stage_dir, "*.out"))):
        name = os.path.basename(out)[:-4]
        doc = None
        for line in open(out, errors="replace"):
            line = line.strip()
            if line.startswith("{"):
                try:
                    doc = json.loads(line)
                except ValueError:
                    continue
        done = os.path.exists(os.path.join(stage_dir, name + ".done"))
        skip = os.path.exists(os.path.join(stage_dir, name + ".skip"))
        yield name, doc, done, skip


def _latest_stage_dir() -> str:
    """Newest r{N}_stages dir — defaulting to a hardcoded round would
    silently render a STALE ledger as if it were current."""
    import re

    dirs = glob.glob(os.path.join(HERE, "r*_stages"))
    dirs = [d for d in dirs if re.search(r"r(\d+)_stages$", d)]
    dirs.sort(key=lambda d: int(re.search(r"r(\d+)_stages$", d).group(1)))
    return dirs[-1] if dirs else os.path.join(HERE, "r4_stages")


def main() -> int:
    stage_dir = sys.argv[1] if len(sys.argv) > 1 else _latest_stage_dir()
    if not os.path.isdir(stage_dir):
        print(f"no stage dir at {stage_dir}; nothing measured yet")
        return 0

    lm_rows, rn_rows, serve_rows, pending = [], [], [], []
    for name, doc, done, skip in stage_records(stage_dir):
        if doc is None or not done:
            pending.append((name, "skipped (failed twice)" if skip
                            else "no parseable result"))
            continue
        lm = doc.get("lm") if isinstance(doc.get("lm"), dict) else None
        if lm and isinstance(lm.get("mfu"), (int, float)):
            lm_rows.append(
                (name, lm.get("model"), lm.get("global_batch"),
                 lm.get("seq_len"), lm.get("remat_policy")
                 if lm.get("remat") else "none",
                 lm.get("window") or "-", lm["mfu"],
                 lm.get("tokens_per_sec")))
        elif doc.get("metric", "").startswith("resnet") and doc.get("value"):
            rn_rows.append((name, doc.get("resnet_remat") or "none",
                            doc["value"], doc.get("images_per_sec"),
                            doc.get("fraction_of_roofline")))
        elif doc.get("mode") == "continuous":
            serve_rows.append(
                (name, doc.get("model"), doc.get("param_dtype"),
                 doc.get("kv_cache_dtype", "native"),
                 doc.get("attention_window", "-"),
                 "roll" if doc.get("rolling_kv_cache") else "full",
                 doc.get("tokens_per_sec"), doc.get("p50_ms"),
                 doc.get("p99_ms")))

    if lm_rows:
        print("### LM training (measured, 1x v5e)\n")
        print("| stage | model | bs | seq | remat | window | MFU | tok/s |")
        print("|---|---|---|---|---|---|---|---|")
        for r in sorted(lm_rows, key=lambda r: -r[6]):
            print("| " + " | ".join(str(x) for x in r) + " |")
        print()
    if rn_rows:
        print("### ResNet-50 (measured, 1x v5e)\n")
        print("| stage | remat | MFU | img/s | frac of roofline |")
        print("|---|---|---|---|---|")
        for r in rn_rows:
            print("| " + " | ".join(str(x) for x in r) + " |")
        print()
    if serve_rows:
        print("### Serving, continuous batching (measured, 1x v5e)\n")
        print("| stage | model | weights | kv | window | cache | tok/s "
              "| p50 ms | p99 ms |")
        print("|---|---|---|---|---|---|---|---|---|")
        for r in serve_rows:
            print("| " + " | ".join(str(x) for x in r) + " |")
        print()
    if pending:
        print("### Not measured\n")
        for name, why in pending:
            print(f"- {name}: {why}")
    if not (lm_rows or rn_rows or serve_rows or pending):
        print("stage dir empty; nothing measured yet")
    return 0


if __name__ == "__main__":
    sys.exit(main())
