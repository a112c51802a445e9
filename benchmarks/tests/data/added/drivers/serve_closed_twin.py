"""`serve_closed_twin`: a driver under another name, kept with the tests:
the closed loop of benchmarks/lib/serve.py, and a note that it was this
module that ran."""

from benchmarks.lib import serve

RUNS: list = []


def run(cell, seed, seconds, trace_on, t_start, **kw) -> dict:
    RUNS.append(cell.name)
    return serve.run(cell, seed, seconds, trace_on, t_start, closed=True, **kw)
