"""Promote the serving sweep's best measured operating point.

Same promotion discipline as promote_best.py, for the decode side
(serving numbers as a first-class ledger): parse files of
serve_bench.py JSON lines, keep the best CONTINUOUS-mode point per
(model, max_new_tokens, slots, param_dtype, kv_cache_dtype) config in
tools/serve_table.json (the A/B ledger), and write the best
DEFAULT-GEOMETRY (gpt-350m) point to tools/serve_best.json — bench.py
attaches it (and, budget permitting, re-measures) so the driver-recorded
BENCH json carries a serving field. Only measured numbers are promoted;
a failed sweep changes nothing; non-default geometries never compete for
(or raise the floor of) the headline slot.

Usage: python tools/promote_serve_best.py LOG [LOG...]
"""

from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def candidates(paths):
    for path in paths:
        if not os.path.exists(path):
            continue
        for line in open(path):
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                doc = json.loads(line)
            except ValueError:
                continue
            if doc.get("mode") == "continuous" and \
                    isinstance(doc.get("tokens_per_sec"), (int, float)) and \
                    doc["tokens_per_sec"] > 0:
                yield doc


def _config_key(doc) -> str:
    return "|".join(str(doc.get(k)) for k in (
        "model", "max_new_tokens", "slots", "param_dtype",
        "kv_cache_dtype", "attention_window", "rolling_kv_cache"))


def main() -> int:
    paths = sys.argv[1:] or [os.path.join(HERE, "serve_sweep.log")]
    best_path = os.path.join(HERE, "serve_best.json")
    table_path = os.path.join(HERE, "serve_table.json")
    floor = 0.0
    if os.path.exists(best_path):
        try:
            floor = json.load(open(best_path)).get("tokens_per_sec", 0.0)
        except (ValueError, OSError):
            pass
    # per-config bests (every measured geometry/dtype keeps its own row —
    # the A/B ledger)
    table: dict = {}
    if os.path.exists(table_path):
        try:
            table = json.load(open(table_path))
        except (ValueError, OSError):
            table = {}
    best = None
    for doc in candidates(paths):
        key = _config_key(doc)
        if doc["tokens_per_sec"] > table.get(key, {}).get(
                "tokens_per_sec", 0.0):
            table[key] = doc
        # serve_best.json pins ONLY the default headline geometry —
        # cross-config competition (e.g. a llama-1b long-prompt point)
        # must neither win the slot nor raise the floor against future
        # default-geometry measurements
        if doc.get("model") != "gpt-350m":
            continue
        if doc["tokens_per_sec"] > floor and (
                best is None
                or doc["tokens_per_sec"] > best["tokens_per_sec"]):
            best = doc
    if table:
        tmp = table_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(table, f, indent=1)
        os.replace(tmp, table_path)
        print(f"serving table: {len(table)} config(s) -> {table_path}")
    if best is None:
        print(f"no default-geometry point beat {floor:.1f} tok/s; "
              "serve_best.json unchanged")
        return 0
    best["promoted_at"] = time.strftime("%Y-%m-%d %H:%M:%S", time.gmtime())
    tmp = best_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(best, f, indent=1)
    os.replace(tmp, best_path)
    print(f"promoted serving point {best['model']} "
          f"{best['param_dtype']}/{best.get('kv_cache_dtype', 'native')} "
          f"{best['tokens_per_sec']} tok/s -> {best_path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
