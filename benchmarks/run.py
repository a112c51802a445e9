#!/usr/bin/env python3
"""Runs one cell of BENCHMARK.json once, in one process that owns the
chip(s): load, warm up, measure, check, print the result line.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

With no TPU (or fewer chips than the cell asks for) it exits 69 and
prints no result: a CPU number is never a benchmark number.
"""

import time

T_START = time.monotonic()   # set-up counts from here

import argparse  # noqa: E402
import json      # noqa: E402
import os        # noqa: E402
import sys       # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def parse_overrides(pairs) -> dict:
    out = {}
    for pair in pairs:
        k, _, v = pair.partition("=")
        try:
            out[k] = json.loads(v)
        except ValueError:
            out[k] = v
    return out


def result_line(cell, bench: dict, res: dict, trace_on: bool) -> dict:
    """The contract's last line from a driver's result."""
    from benchmarks.lib import readers

    if trace_on:
        metrics = readers.read_all(bench, cell.name, res["ctx"])
    else:
        metrics = res["metrics"]
    device = dict(res["device"], memory_peak_bytes=res["memory_peak_bytes"])
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    if trace_on and res["trace"] is not None:
        line["breakdown"] = {"device_ops": res["trace"]["device_ops"],
                             "idle_gaps": res["trace"]["idle_gaps"]}
    line["checks"] = res["checks"]
    return line


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--override", action="append", default=[],
                   help="key=value over the configuration's serve/trainer "
                        "keys, traffic.key=value over the mix's: for the "
                        "controls and the rate sweep of PERF.md, never for "
                        "a benchmark run")
    args = p.parse_args(argv)

    from benchmarks.lib import harness, spec

    bench = spec.benchmark()
    cell = spec.cell(args.workload, bench)
    overrides = parse_overrides(args.override)
    mix = {k[len("traffic."):]: overrides.pop(k) for k in list(overrides)
           if k.startswith("traffic.")}
    if mix:   # the rate sweep that finds an open-loop cell's knee
        import dataclasses

        cell = dataclasses.replace(cell, traffic=dict(cell.traffic, **mix))
    run = spec.driver(cell)     # benchmarks/drivers/<the mix's driver>.py
    try:
        res = run(cell, args.seed, args.seconds, bool(args.trace), T_START,
                  overrides=overrides)
    except harness.NoDevice as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return harness.EX_NO_DEVICE
    harness.emit(result_line(cell, bench, res, bool(args.trace)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
