"""Finds a cell's files by the names in BENCHMARK.json and reads them:
the configuration, the mix, and by the names those two carry the
architecture (`arch` in the configuration's file) and the driver
(`driver` in the mix's file), each a module of its own, found as
lib/readers.py finds a metric's reader. Nothing here knows the shape of
a model.

What a driver may ask of an architecture: `cell.dims.vocab` and
`cell.dims.layers` of its sizes, and the functions benchmarks/README.md
lists (`model_kwargs`, `make_program_params`, `answer_tokens`,
`compare_served`, `leaf_names`, `change_norms`, `train_reference`). The
readers of benchmarks/metrics ask it for its counts. Every other size is
the architecture's own business."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import types
from typing import Any

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
# Directories laid out like benchmarks/ (arch/, drivers/, traffic/,
# metrics/), searched in this order. The tests put a fixture directory in
# front; the benchmark itself has the one.
ROOTS = [BENCH_DIR]


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def find(kind: str, filename: str, roots=None) -> str | None:
    """The first `<root>/<kind>/<filename>` that is there."""
    for root in roots or ROOTS:
        path = os.path.join(root, kind, filename)
        if os.path.isfile(path):
            return path
    return None


def known(kind: str, ext: str, roots=None) -> list[str]:
    names = set()
    for root in roots or ROOTS:
        d = os.path.join(root, kind)
        if os.path.isdir(d):
            names |= {f[:-len(ext)] for f in os.listdir(d)
                      if f.endswith(ext) and not f.startswith("_")}
    return sorted(names)


def load_module(kind: str, name: str, roots=None) -> types.ModuleType | None:
    """The module `<root>/<kind>/<name>.py`: `benchmarks.<kind>.<name>`
    where it lies under benchmarks/, and imported from its file under a
    name of its own where a test keeps it elsewhere. None where no root
    has it."""
    path = find(kind, name + ".py", roots)
    if path is None:
        return None
    if os.path.dirname(os.path.dirname(path)) == BENCH_DIR:
        return importlib.import_module(f"benchmarks.{kind}.{name}")
    modname = f"benchmarks_added.{kind}.{name}"
    if modname in sys.modules and sys.modules[modname].__file__ == path:
        return sys.modules[modname]
    mspec = importlib.util.spec_from_file_location(modname, path)
    module = importlib.util.module_from_spec(mspec)
    sys.modules[modname] = module
    mspec.loader.exec_module(module)
    return module


def architecture(config: dict, config_file: str, roots=None):
    """The module the configuration's `arch` names. No default: a file
    without the key, or with a name no module has, is an error."""
    name = config.get("arch")
    if not name:
        raise KeyError(
            f"{config_file} has no \"arch\": name the module under "
            f"benchmarks/arch/ that knows this model's shape; known: "
            f"{known('arch', '.py', roots)}. Never a default.")
    module = load_module("arch", name, roots)
    if module is None:
        raise KeyError(
            f"{config_file} names the architecture {name!r}, and there is "
            f"no arch/{name}.py; known: {known('arch', '.py', roots)}")
    return module


def driver(cell: "Cell"):
    """The `run` function of the module the mix's `driver` names."""
    name = cell.traffic.get("driver")
    module = load_module("drivers", name) if name else None
    if module is None:
        raise KeyError(
            f"{cell.traffic_file} names the driver {name!r}, and there is "
            f"no drivers/{name}.py; known: {known('drivers', '.py')}")
    return module.run


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of `workloads` with its files read. `arch` is the
    architecture's module and `dims` its reading of the configuration's
    sizes: a driver asks `dims` for `vocab` and `layers` and nothing
    else, and the module for the functions the README lists."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    arch: types.ModuleType
    dims: Any
    traffic_file: str = ""


def cell(workload: str, bench: dict | None = None, roots=None) -> Cell:
    """`bench` and `roots` default to BENCHMARK.json and benchmarks/; the
    tests point them at toy files."""
    bench = bench or benchmark()
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(by_name)}")
    w = by_name[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic_file = find("traffic", w["traffic"] + ".json", roots)
    if traffic_file is None:
        raise KeyError(f"no traffic/{w['traffic']}.json for the cell "
                       f"{workload!r}; known: {known('traffic', '.json', roots)}")
    arch = architecture(config, cfg_entry["file"], roots)
    return Cell(name=workload, chips=int(w["chips"]),
                config_name=w["config"], config=config,
                traffic_name=w["traffic"], traffic=load_json(traffic_file),
                arch=arch, dims=arch.sizes(config),
                traffic_file=os.path.relpath(traffic_file, ROOT))


def metric_files() -> dict[str, dict]:
    """Every per-layer metric the `metrics/` directories hold, by name."""
    out = {}
    for root in reversed(ROOTS):
        mdir = os.path.join(root, "metrics")
        if not os.path.isdir(mdir):
            continue
        for fn in sorted(os.listdir(mdir)):
            if fn.endswith(".json"):
                out[fn[:-5]] = load_json(os.path.join(mdir, fn))
    return out
