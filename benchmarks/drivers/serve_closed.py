"""`serve_closed`: a closed loop. `clients` clients over HTTP, each
sending its next request when the last returns, on a mix of `prompt_min/max`,
`answer_min/max` in `blocks` blocks of `block`; the window opens once a
slot's worth of requests has completed. Reports `out_tok_per_s`."""

from benchmarks.lib import serve


def run(cell, seed, seconds, trace_on, t_start, **kw) -> dict:
    return serve.run(cell, seed, seconds, trace_on, t_start, closed=True, **kw)
