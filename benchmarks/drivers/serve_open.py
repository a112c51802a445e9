"""`serve_open`: an open loop. One request due in each slot of 1/`rate`
seconds whatever the server does, `lead_in_requests` before the window and
`tail_requests` behind it; latency counts from the due time. Reports
`req_latency_p50_s` and `req_latency_p90_s`."""

from benchmarks.lib import serve


def run(cell, seed, seconds, trace_on, t_start, **kw) -> dict:
    return serve.run(cell, seed, seconds, trace_on, t_start, closed=False, **kw)
