"""What the two serving drivers share (benchmarks/drivers/serve_closed.py:
clients that each wait for their answer; serve_open.py: arrivals on a
schedule). Both drive the HTTP predict route of a ModelServer built with
the calls that serving/server.py:main makes, in this process, so that the
profiler sees the device. What knows the model's shape (the weights, the
program's keywords, how a prediction reads as tokens, the reference and
its comparison) is asked of the cell's architecture."""

from __future__ import annotations

import concurrent.futures as cf
import gc
import json
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np

from benchmarks.lib import harness, schedule
from benchmarks.lib.spec import Cell

MODEL = "bench"
TRACE_SECONDS = 3.0    # the profiler runs for this much of the window
WARM_LENGTHS = (7, 9)  # real lengths of the warm-up prompts, under any mix's


def _decoders() -> list:
    """The program's live objects that can do what the driver asks of a
    decoder (`stats()`, `active_slots`; `state`, `_params` and
    `variables` to free), whatever their class. The side door of ROADMAP
    D7: `serve_lm_generator` keeps its decoder in a closure and gives no
    accessor yet."""
    return [o for o in gc.get_objects()
            if str(getattr(type(o), "__module__", "")).startswith("kubeflow_tpu.")
            and callable(getattr(type(o), "stats", None))
            and hasattr(type(o), "active_slots")]


class Served:
    """A live server with the benchmark's weights, and its decoder."""

    def __init__(self, cell: Cell, seed: int, devices, overrides: dict):
        import jax

        from kubeflow_tpu.runtime import checkpoint
        from kubeflow_tpu.serving import server

        self.cell, self.devices = cell, devices
        earlier = {id(o) for o in _decoders()}
        self.serve_cfg = dict(cell.config["serve"], **overrides)
        d = cell.dims

        # The server restores its weights through restore_variables; the
        # benchmark stands in for the checkpoint store, so that the
        # weights are its own, made on the device from the seed, and
        # nothing is written to disk. Quantization stays the server's.
        # (The other side door of D7: `serve_lm_generator` takes no
        # `variables=` yet.)
        def restore(directory, step=None):
            params = cell.arch.make_program_params(d, seed)
            return {"params": params}, 0

        real = checkpoint.restore_variables
        checkpoint.restore_variables = restore
        try:
            self.server = server.ModelServer()
            self.server.register(server.serve_lm_generator(
                MODEL, cell.config["program"]["model"],
                checkpoint_dir="benchmark-seeded-weights",
                **self.serve_cfg, **cell.arch.model_kwargs(cell)))
        finally:
            checkpoint.restore_variables = real
        self.svc = self.server.serve(host="127.0.0.1", port=0)
        self.svc.serve_background()
        port = self.svc._server.server_address[1]
        self.url = f"http://127.0.0.1:{port}/v1/models/{MODEL}:predict"
        # the first request builds the decoder and compiles the prefill,
        # the single tick and (20 new tokens, nothing waiting) the fused
        rng = np.random.default_rng(seed)
        for n in WARM_LENGTHS:
            toks = rng.integers(1, d.vocab, n).tolist()
            ok, got = self.ask(toks, min(20, self.serve_cfg["max_new_tokens"]))
            if not ok:
                raise RuntimeError(f"warm-up request failed: {got}")
        found = [o for o in _decoders() if id(o) not in earlier]
        if len(found) != 1:
            raise RuntimeError(f"expected one new decoder, found {len(found)}")
        self.decoder = found[0]
        jax.block_until_ready(self.decoder.state)

    def ask(self, tokens, max_new: int, timeout: float = 120.0):
        """(ok, the prediction as the server returned it or what went
        wrong). Never raises."""
        body = json.dumps({"instances": [
            {"tokens": list(tokens), "max_new_tokens": int(max_new)}]}).encode()
        req = urllib.request.Request(
            self.url, data=body, headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return True, json.loads(r.read())["predictions"][0]
        except urllib.error.HTTPError as e:
            return False, f"HTTP {e.code}: {e.read()[:200]!r}"
        except (urllib.error.URLError, OSError, ValueError, KeyError) as e:
            return False, repr(e)

    def close_and_free(self) -> None:
        """Stop the server and give the device memory back, so that the
        reference runs beside nothing."""
        import logging

        import jax

        # what is still out belongs to no window; its failure is no news
        logging.getLogger("kubeflow_tpu").setLevel(logging.CRITICAL)
        self.server.close()   # fails what is still out, so handlers end
        self.svc.shutdown()
        dec = self.decoder
        for leaf in jax.tree.leaves((dec.state, dec._params, dec.variables)):
            if hasattr(leaf, "delete") and not leaf.is_deleted():
                leaf.delete()
        self.decoder = None
        gc.collect()


class Sampler(threading.Thread):
    """Reads the decoder's host-truth counts at 20 Hz."""

    def __init__(self, decoder):
        super().__init__(daemon=True, name="bench-sampler")
        self.decoder, self.rows, self._halt = decoder, [], threading.Event()

    def run(self):
        while not self._halt.wait(0.05):
            st = self.decoder.stats()
            self.rows.append((time.monotonic(), self.decoder.active_slots,
                              st.get("kv_pages_used", 0)))

    def stop(self):
        self._halt.set()
        self.join(timeout=5)


def _record(rec: dict, served: Served, r: schedule.Request) -> None:
    rec["sent"] = time.monotonic()
    ok, got = served.ask(r.prompt, r.max_new)
    rec["done"] = time.monotonic()
    rec["ok"] = ok
    rec["prediction"] = got if ok else None
    # the tokens that count, as the architecture reads the prediction
    rec["tokens"] = served.cell.arch.answer_tokens(got) if ok else None
    rec["error"] = None if ok else got


def _window(served: Served, seconds: float, trace: harness.TraceWindow,
            before_close, compiles: harness.CompileCounter) -> dict:
    """The measured stretch itself: counters before and after, the sampler
    and the profiler's short stretch; `before_close` waits out the rest."""
    dec = served.decoder
    sampler = Sampler(dec)
    c0, n0 = dec.stats(), compiles.n
    t0 = time.monotonic()
    sampler.start()
    trace.start()
    if trace.enabled:
        time.sleep(min(TRACE_SECONDS, seconds))
        trace.stop()
    time.sleep(max(0.0, t0 + seconds - time.monotonic()))
    t1 = time.monotonic()
    c1, n1 = dec.stats(), compiles.n
    sampler.stop()
    before_close()
    return {"t0": t0, "t1": t1, "stats0": c0, "stats1": c1,
            "compiles": n1 - n0,
            "samples": [s for s in sampler.rows if t0 <= s[0] <= t1]}


def _closed(served: Served, reqs, seconds, trace, compiles) -> tuple:
    traffic = served.cell.traffic
    recs = [{"req": r} for r in reqs]
    nxt, lock, halt = iter(range(len(reqs))), threading.Lock(), threading.Event()

    def client():
        while not halt.is_set():
            with lock:
                i = next(nxt, None)
            if i is None:
                return time.monotonic()     # the mix ran out
            _record(recs[i], served, reqs[i])
        return None

    pool = cf.ThreadPoolExecutor(traffic["clients"], "bench-client")
    futs = [pool.submit(client) for _ in range(traffic["clients"])]
    slots = served.serve_cfg["decode_slots"]
    base = served.decoder.stats()["completed"]
    while served.decoder.stats()["completed"] - base < slots:
        if any(f.done() for f in futs):
            break
        time.sleep(0.05)

    def stop_clients():
        # what is still out belongs to no window: the server is closed
        # under it, and its clients come back with an error at once
        halt.set()

    win = _window(served, seconds, trace, stop_clients, compiles)
    return recs, win, pool, futs


def _open(served: Served, reqs, sizes, seconds, trace, compiles) -> tuple:
    traffic = served.cell.traffic
    recs = [{"req": r} for r in reqs]
    lead, n_win, _tail = sizes
    pool = cf.ThreadPoolExecutor(traffic["max_in_flight"], "bench-client")
    start = time.monotonic() + 0.2
    all_back = threading.Event()

    def dispatch():
        for i, r in enumerate(reqs):
            if i >= lead + n_win and all_back.is_set():
                return
            delay = start + r.due_s - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            recs[i]["due"] = start + r.due_s
            recs[i]["fut"] = pool.submit(_record, recs[i], served, r)

    disp = threading.Thread(target=dispatch, daemon=True, name="bench-dispatch")
    disp.start()
    t_window = start + lead / traffic["rate"]
    time.sleep(max(0.0, t_window - time.monotonic()))

    def wait_measured():
        # the schedule goes on behind the window until every measured
        # request is back, so none is measured against a draining server
        deadline = time.monotonic() + traffic["answer_cap_s"]
        for rec in recs[lead:lead + n_win]:
            while "fut" not in rec and time.monotonic() < deadline:
                time.sleep(0.01)
            if "fut" in rec:
                try:
                    rec["fut"].result(timeout=max(0.0, deadline - time.monotonic()))
                except cf.TimeoutError:
                    pass
        all_back.set()

    win = _window(served, n_win / traffic["rate"], trace, wait_measured,
                  compiles)
    win["t0"], win["t1"] = t_window, t_window + n_win / traffic["rate"]
    disp.join(timeout=traffic["answer_cap_s"])
    return recs, win, pool, []


def check_answers(cell: Cell, seed: int, measured: list) -> list:
    """The comparison that decides `correct`: a sample of the finished
    requests, drawn from the seed, with the longest in it, each given to
    the architecture's comparison with its prompt and its prediction
    whole. Every number that comes back judged needs a limit in the mix's
    file: a name without one is an error, never a pass."""
    limits = cell.traffic["limits"][cell.config_name]
    vocab = cell.dims.vocab
    done = [m for m in measured if m["ok"]]

    def whole(m):
        return m["tokens"] is not None and len(m["tokens"]) == m["req"].max_new

    bad = sum(1 for m in done if not whole(m) or not all(
        isinstance(t, int) and 0 <= t < vocab for t in m["tokens"]))
    checks = [("malformed_answers", float(bad), 0.0)]
    done = [m for m in done if whole(m)]
    if not done:
        return checks + [(name, float("nan"), lim)
                         for name, lim in limits.items()]
    rng = np.random.default_rng(seed)
    longest = max(done, key=lambda m: len(m["req"].prompt) + m["req"].max_new)
    rest = [m for m in done if m is not longest]
    k = min(cell.traffic["check_requests"] - 1, len(rest))
    sample = [longest] + [rest[i] for i in rng.choice(len(rest), k, False)]
    judged, beside = cell.arch.compare_served(cell, seed, [
        {"prompt": m["req"].prompt, "prediction": m["prediction"]}
        for m in sample])
    for name, value in judged.items():
        if name not in limits:
            raise KeyError(
                f"{cell.arch.__name__} compares {name!r}, and "
                f"{cell.traffic_file} gives it no limit under "
                f"limits[{cell.config_name!r}]")
        checks.append((name, value, limits[name]))
    # reported beside the judged numbers, never judged themselves
    checks += [(name, value, 1e30) for name, value in beside.items()]
    return checks


def run(cell: Cell, seed: int, seconds: float, trace_on: bool, t_start: float,
        closed: bool, overrides: dict | None = None, require_tpu: bool = True,
        break_served=None) -> dict:
    """One run of a serving cell, `closed` loop or open. `break_served`
    is for the tests: it is given the live Served before any traffic, to
    plant a fault."""
    devices = harness.devices_for(cell.chips, require_tpu)
    harness.configure_cache()
    compiles = harness.CompileCounter()
    traffic, d = cell.traffic, cell.dims
    served = Served(cell, seed, devices, overrides or {})
    if break_served:
        break_served(served)
    trace = harness.TraceWindow(trace_on)
    if closed:
        sizes = [traffic["block"]] * traffic["blocks"]
    else:
        sizes = [traffic["lead_in_requests"],
                 max(1, round(traffic["rate"] * seconds)),
                 traffic["tail_requests"]]
    reqs = schedule.make_requests(traffic, d.vocab, seed, sizes)
    if closed:
        recs, win, pool, futs = _closed(served, reqs, seconds, trace, compiles)
        measured = [r for r in recs if "done" in r
                    and win["t0"] <= r["done"] <= win["t1"]]
    else:
        recs, win, pool, futs = _open(served, reqs, sizes, seconds, trace,
                                      compiles)
        measured = recs[sizes[0]:sizes[0] + sizes[1]]
        for m in measured:
            m.setdefault("ok", False)
    setup_s = win["t0"] - t_start
    mem = harness.memory_peak_bytes(devices)
    window_s = win["t1"] - win["t0"]
    served.close_and_free()
    pool.shutdown(wait=True, cancel_futures=True)
    for f in futs:
        if f.result() is not None and f.result() < win["t1"]:
            raise RuntimeError("the mix ran out of requests inside the "
                               "window: give its file more blocks")
    ok = [m for m in measured if m["ok"]]
    failed = len(measured) - len(ok)
    out_tokens = sum(len(m["tokens"] or ()) for m in ok)
    metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
    if closed:
        metrics["out_tok_per_s"] = {"value": out_tokens / window_s,
                                    "unit": "tokens/s"}
    else:
        cap = traffic["answer_cap_s"]
        lat = [(m["done"] - m["due"]) if m["ok"] else cap for m in measured]
        metrics["req_latency_p50_s"] = {
            "value": harness.percentile(lat, 50), "unit": "s"}
        metrics["req_latency_p90_s"] = {
            "value": harness.percentile(lat, 90), "unit": "s"}
    red = trace.reduce()
    t_ref = time.monotonic()
    checks = check_answers(cell, seed, measured)
    print(f"reference: {time.monotonic() - t_ref:.1f} s", file=sys.stderr)
    checks.append(("failed_requests", float(failed), 0.0))
    ctx = {
        "cell": cell, "window_s": window_s, "trace": red,
        "device_kind": devices[0].device_kind, "chips": len(devices),
        "compiles": win["compiles"], "stats0": win["stats0"],
        "stats1": win["stats1"], "samples": win["samples"],
        "slots": served.serve_cfg["decode_slots"],
        "requests": [{"prompt": len(m["req"].prompt),
                      "out": len(m["tokens"] or ()),
                      "late_s": m["sent"] - m.get("due", m["sent"])}
                     for m in ok],
        "padded_prompt": served.serve_cfg["prompt_len"],
    }
    return {"correct": harness.judge(checks), "attempted": len(measured),
            "failed": failed, "metrics": metrics,
            "device": harness.device_line(devices, red),
            "memory_peak_bytes": mem, "ctx": ctx, "checks": checks,
            "trace": red}
