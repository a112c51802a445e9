"""Finds a cell's files by the names in BENCHMARK.json and reads them."""

from __future__ import annotations

import dataclasses
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


@dataclasses.dataclass(frozen=True)
class Dims:
    """The model sizes the counts, the weights and the reference need,
    read from a configuration file's published (Hugging Face) keys."""

    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    window: int       # 0 = full causal
    norm_eps: float

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        return cls(
            d=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
            heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
            rope_theta=float(cfg["rope_theta"]),
            window=int(cfg.get("sliding_window") or 0),
            norm_eps=float(cfg["rms_norm_eps"]))

    def model_kwargs(self) -> dict:
        """The keyword overrides models/transformer.py takes."""
        return dict(
            d_model=self.d, n_layers=self.layers, n_heads=self.heads,
            n_kv_heads=self.kv_heads, head_dim=self.head_dim,
            d_ff=self.d_ff, vocab_size=self.vocab,
            rope_theta=self.rope_theta, attention_window=self.window)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    dims: Dims


def cell(workload: str, bench: dict | None = None,
         traffic_dir: str | None = None) -> Cell:
    """`bench` and `traffic_dir` default to BENCHMARK.json and
    benchmarks/traffic; the tests point them at toy files."""
    bench = bench or benchmark()
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(by_name)}")
    w = by_name[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(
        traffic_dir or os.path.join(BENCH_DIR, "traffic"),
        w["traffic"] + ".json"))
    return Cell(name=workload, chips=int(w["chips"]),
                config_name=w["config"], config=config,
                traffic_name=w["traffic"], traffic=traffic,
                dims=Dims.from_config(config))


def metric_files() -> dict[str, dict]:
    """Every per-layer metric the directory holds, by name."""
    out = {}
    mdir = os.path.join(BENCH_DIR, "metrics")
    for fn in sorted(os.listdir(mdir)):
        if fn.endswith(".json"):
            out[fn[:-5]] = load_json(os.path.join(mdir, fn))
    return out
