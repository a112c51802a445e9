"""Finds each per-layer metric's reader by the metric's file
(benchmarks/metrics/<name>.json: {"reader": "<module>:<function>",
"args": {...}}) and runs it on what the driver gathered. A reader that
finds nothing to read returns None and the metric is left out."""

from __future__ import annotations

from benchmarks.lib import spec


def reader_of(meta: dict):
    """The function a metric's file names: `<module>:<function>` under
    a `metrics/` directory."""
    mod, _, fn = meta["reader"].partition(":")
    module = spec.load_module("metrics", mod)
    if module is None:
        raise KeyError(f"no metrics/{mod}.py for the reader {meta['reader']!r}")
    return getattr(module, fn)


def read_all(bench: dict, workload: str, ctx: dict) -> dict:
    files = spec.metric_files()
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        meta = files[m["name"]]
        value = reader_of(meta)(ctx, **meta.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
