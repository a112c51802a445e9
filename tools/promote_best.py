"""Promote the LM sweep's best measured operating point to the bench default.

Parses tools/lm_sweep.log (JSON lines appended by lm_sweep.py, each the
output of `bench.py --workload lm ...` whose `lm` dict is self-describing)
and writes tools/lm_best.json when a config beats BOTH the current
promotion file and the hard floor of the last hand-verified default
(gpt-350m + adafactor). bench.py's `--lm-best auto` then runs the
headline LM at that point — so a sweep that completes unattended still
upgrades the headline bench with zero human steps. Only measured numbers
are ever promoted; a failed/partial sweep changes nothing.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
FLOOR_MFU = 0.202  # the hand-verified default's measured MFU


def candidates(log_path: str):
    for line in open(log_path):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        lm = doc.get("lm") or {}
        if lm.get("window"):
            # sliding-window points do LESS attention work than the MFU
            # accounting assumes — their "MFU" is inflated and must never
            # compete with full-causal points for the headline default
            continue
        if isinstance(lm.get("mfu"), (int, float)) and lm["mfu"] > 0:
            yield lm


def main() -> int:
    log_path = sys.argv[1] if len(sys.argv) > 1 else \
        os.path.join(HERE, "lm_sweep.log")
    best_path = os.path.join(HERE, "lm_best.json")
    if not os.path.exists(log_path):
        print(f"no sweep log at {log_path}; nothing to promote")
        return 0
    floor = FLOOR_MFU
    if os.path.exists(best_path):
        try:
            floor = max(floor, json.load(open(best_path)).get("mfu", 0))
        except (ValueError, OSError):
            pass
    best = None
    for lm in candidates(log_path):
        if lm["mfu"] > floor and (best is None or lm["mfu"] > best["mfu"]):
            best = lm
    if best is None:
        print(f"no sweep point beat mfu={floor:.3f}; defaults unchanged")
        return 0
    best = dict(best)
    if not best.get("remat"):
        # ledger hygiene: record only knobs actually
        # in effect — "remat_policy" next to remat=false invites reading
        # the point as remat-verified when the policy never ran
        best.pop("remat_policy", None)
    # atomic replace: a bench.py starting concurrently must never read a
    # half-written file
    tmp = best_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(best, f, indent=1)
    os.replace(tmp, best_path)
    print(f"promoted {best['model']} ({best['optimizer']}"
          f"{', remat=' + best.get('remat_policy', '') if best.get('remat') else ''}) "
          f"mfu={best['mfu']:.3f} -> {best_path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
