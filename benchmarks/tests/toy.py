"""Toy cells for the tests: the real drivers and the `dense_gqa`
architecture on files under data/."""

import os

from benchmarks.lib import spec

_CELL = spec.cell     # the tests replace spec.cell with cell() below
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def bench() -> dict:
    return spec.load_json(os.path.join(DATA, "toy_bench.json"))


def cell(name: str) -> spec.Cell:
    return _CELL(name, bench(), [DATA, spec.BENCH_DIR])
