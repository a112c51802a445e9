"""Finds each per-layer metric's reader by the metric's file
(benchmarks/metrics/<name>.json: {"reader": "<module>:<function>",
"args": {...}}) and runs it on what the driver gathered. A reader that
finds nothing to read returns None and the metric is left out."""

from __future__ import annotations

import importlib

from benchmarks.lib import spec


def read_all(bench: dict, workload: str, ctx: dict) -> dict:
    files = spec.metric_files()
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        meta = files[m["name"]]
        mod, _, fn = meta["reader"].partition(":")
        reader = getattr(importlib.import_module(f"benchmarks.metrics.{mod}"), fn)
        value = reader(ctx, **meta.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
