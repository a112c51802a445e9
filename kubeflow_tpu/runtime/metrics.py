"""Hot-loop instrumentation the reference never had.

The reference's observability is Prometheus on the control plane only
(bootstrap/cmd/bootstrap/app/server.go:68-132, notebook-controller
pkg/metrics/metrics.go) — per-step training metrics don't exist. Here
every worker exports step time, throughput, and MFU in Prometheus text
exposition format, scrapeable at :9100/metrics, with zero third-party
dependencies (stdlib http.server on a daemon thread).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

# Peak dense bf16 FLOP/s per chip, by jax device_kind. Source: public Cloud
# TPU docs tables (v4: 275T, v5e: 197T, v5p: 459T, v6e "Trillium": 918T).
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}

# Peak HBM bandwidth per chip (bytes/s), same doc tables (v4: 1.2TB/s,
# v5e: 819GB/s, v5p: 2.77TB/s, v6e: 1.64TB/s). Drives the roofline
# fields bench.py reports next to MFU.
PEAK_HBM_BW = {
    "TPU v4": 1.2e12,
    "TPU v5 lite": 819e9,
    "TPU v5": 2.77e12,
    "TPU v5p": 2.77e12,
    "TPU v6 lite": 1.64e12,
    "TPU v6e": 1.64e12,
}


class UnknownDeviceError(LookupError):
    """A device_kind with no entry in the peak tables. A utilization
    against some other chip's peak is not a utilization."""


def _lookup(table: dict, device_kind: str) -> float:
    for prefix, val in sorted(table.items(), key=lambda kv: -len(kv[0])):
        if device_kind.startswith(prefix):
            return val
    raise UnknownDeviceError(
        f"no published peak for device_kind {device_kind!r} "
        f"(known: {sorted(table)})")


def peak_flops(device_kind: str) -> float:
    return _lookup(PEAK_FLOPS, device_kind)


def peak_hbm_bw(device_kind: str) -> float:
    return _lookup(PEAK_HBM_BW, device_kind)


def device_info(devices=None) -> dict:
    """`devices` (default: every device this process sees) as JAX names
    them — stamped on every summary and result so a number can never be
    read apart from the platform that produced it."""
    if devices is None:
        import jax

        devices = jax.devices()
    devs = list(devices)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class StepMeter:
    """Tracks step wall time, examples/sec and MFU over a sliding window.

    With ``tracer`` set (an ``obs.trace.Tracer``), each start/stop pair
    additionally emits a ``train.step`` span under the ambient trace
    context — this is what links worker step timing back to the gang
    scheduler's admission span (one timeline, job submit → step)."""

    def __init__(self, flops_per_step: float, n_chips: int, device_kind: str = "", window: int = 20,
                 tracer=None, span_name: str = "train.step", step_base: int = 0):
        self.flops_per_step = float(flops_per_step)
        self.n_chips = max(1, n_chips)
        # no peak (mfu = nan) for a device the tables do not know: the CPU
        # of a test run, or a chip nobody has entered yet
        try:
            self.peak = peak_flops(device_kind) * self.n_chips
        except UnknownDeviceError:
            self.peak = None
        self._times: deque[float] = deque(maxlen=window)
        self._t0: float | None = None
        self.steps = 0
        self._tracer = tracer
        self._span_name = span_name
        # span step attr = step_base + metered count, so a trainer that
        # meters from global step N (compile step excluded) labels its
        # spans with the true global step indices
        self.step_base = step_base
        self._span = None

    def start(self) -> None:
        if self._tracer is not None:
            if self._span is not None:
                # the previous step never reached stop() (it raised):
                # close its span as ERROR so the failed step — the one
                # an operator most wants to see — still exports
                self._span.status = "ERROR"
                self._tracer.finish(self._span)
            self._span = self._tracer.begin(
                self._span_name, step=self.step_base + self.steps)
        self._t0 = time.perf_counter()

    def stop(self, **attrs) -> float:
        """`attrs` go onto the step's span beside `step_time_s` (the
        trainer's host split of the step)."""
        assert self._t0 is not None, "stop() without start()"
        dt = time.perf_counter() - self._t0
        self._times.append(dt)
        self.steps += 1
        self._t0 = None
        if self._span is not None:
            self._span.attrs.update(attrs, step_time_s=round(dt, 6))
            self._tracer.finish(self._span)
            self._span = None
        return dt

    def close(self) -> None:
        """Finish a still-open step span as ERROR. Call when the loop
        unwinds between start() and stop() (a step raised): the failing
        step's span must still export — there is no later start() to
        self-heal it."""
        if self._span is not None:
            self._span.status = "ERROR"
            self._tracer.finish(self._span)
            self._span = None

    @property
    def step_time(self) -> float:
        return sum(self._times) / len(self._times) if self._times else float("nan")

    def throughput(self, examples_per_step: int) -> float:
        return examples_per_step / self.step_time

    @property
    def achieved_flops(self) -> float:
        return self.flops_per_step / self.step_time

    @property
    def mfu(self) -> float:
        if not self.peak:
            return float("nan")
        return self.achieved_flops / self.peak


# Default latency buckets (seconds) — controller-runtime's reconcile
# histogram range: sub-ms reconciles up to minute-scale stalls.
DEFAULT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                   1.0, 2.5, 5.0, 10.0, 30.0, 60.0)


class _Histogram:
    """Cumulative-bucket histogram state for one label set."""

    __slots__ = ("buckets", "counts", "sum", "count")

    def __init__(self, buckets):
        self.buckets = tuple(sorted(float(b) for b in buckets))
        self.counts = [0] * len(self.buckets)
        self.sum = 0.0
        self.count = 0

    def observe(self, value: float) -> None:
        for i, le in enumerate(self.buckets):
            if value <= le:
                self.counts[i] += 1
                break
        self.sum += value
        self.count += 1


def _escape_label(value) -> str:
    """Prometheus text-format label-value escaping: backslash, quote and
    newline must be escaped or the exposition is unscrapeable."""
    return (str(value).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _escape_help(text: str) -> str:
    return text.replace("\\", "\\\\").replace("\n", "\\n")


def _label_str(key: tuple, extra: tuple = ()) -> str:
    return ",".join(f'{k}="{_escape_label(v)}"' for k, v in (*key, *extra))


class MetricsRegistry:
    """Minimal Prometheus registry: gauges, counters and native
    histograms, text format 0.0.4."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._metrics: dict[str, tuple[str, str, dict[tuple, object]]] = {}

    def _set(self, kind: str, name: str, help_: str, value: float, labels: dict | None):
        key = tuple(sorted((labels or {}).items()))
        with self._lock:
            _, _, series = self._metrics.setdefault(name, (kind, help_, {}))
            series[key] = value

    def gauge(self, name: str, value: float, help_: str = "", **labels) -> None:
        self._set("gauge", name, help_, value, labels)

    def counter_inc(self, name: str, help_: str = "", by: float = 1.0, **labels) -> None:
        key = tuple(sorted(labels.items()))
        with self._lock:
            _, _, series = self._metrics.setdefault(name, ("counter", help_, {}))
            series[key] = series.get(key, 0.0) + by

    def histogram(self, name: str, value: float, help_: str = "",
                  buckets=DEFAULT_BUCKETS, **labels) -> None:
        """Observe ``value`` into a cumulative-bucket histogram. Renders
        as ``name_bucket{le=...}`` / ``name_sum`` / ``name_count`` —
        the native type the scheduler's hand-rolled ``_sum``/``_count``
        counter pair predated."""
        key = tuple(sorted(labels.items()))
        with self._lock:
            _, _, series = self._metrics.setdefault(
                name, ("histogram", help_, {}))
            hist = series.get(key)
            if not isinstance(hist, _Histogram):
                hist = series[key] = _Histogram(buckets)
            hist.observe(float(value))

    @staticmethod
    def _render_histogram(out: list, name: str, key: tuple,
                          hist: _Histogram) -> None:
        cum = 0
        for le, n in zip(hist.buckets, hist.counts):
            cum += n
            out.append(f"{name}_bucket{{"
                       f"{_label_str(key, (('le', le),))}}} {cum}")
        out.append(f"{name}_bucket{{{_label_str(key, (('le', '+Inf'),))}}} "
                   f"{hist.count}")
        suffix = f"{{{_label_str(key)}}}" if key else ""
        out.append(f"{name}_sum{suffix} {hist.sum}")
        out.append(f"{name}_count{suffix} {hist.count}")

    def series(self, name: str) -> list[tuple[dict, float]]:
        """Structured read of one scalar metric's samples as
        ``(labels, value)`` pairs — the in-process fast path for
        consumers like the JAXService autoscaler's ``RegistrySignals``
        (parsing the full text exposition per signal read would cost
        O(total series) per reconcile). Histogram samples are skipped;
        read those through ``render()``."""
        out: list[tuple[dict, float]] = []
        with self._lock:
            entry = self._metrics.get(name)
            if entry is None:
                return out
            _, _, samples = entry
            for key, value in samples.items():
                if isinstance(value, _Histogram):
                    continue
                out.append((dict(key), float(value)))
        return out

    def render(self) -> str:
        out = []
        with self._lock:
            for name, (kind, help_, series) in sorted(self._metrics.items()):
                if help_:
                    out.append(f"# HELP {name} {_escape_help(help_)}")
                out.append(f"# TYPE {name} {kind}")
                for key in sorted(series):
                    value = series[key]
                    if isinstance(value, _Histogram):
                        self._render_histogram(out, name, key, value)
                    elif key:
                        out.append(f"{name}{{{_label_str(key)}}} {value}")
                    else:
                        out.append(f"{name} {value}")
        return "\n".join(out) + "\n"


REGISTRY = MetricsRegistry()

# -- prometheus_client interop ------------------------------------------------

_PROM_METRICS: dict[str, object] = {}


def prom_metric(name: str, kind, doc: str, **kw):
    """Process-global memoized prometheus_client metric: registering a
    name twice raises in prometheus_client, and several subsystems
    (serving server, control plane, router) share one process in tests
    and benches. The ONE spelling of that guard — the per-module copies
    in serving/server.py and control/jaxjob/controller.py delegate
    here."""
    if name not in _PROM_METRICS:
        _PROM_METRICS[name] = kind(name, doc, **kw)
    return _PROM_METRICS[name]


class _Handler(BaseHTTPRequestHandler):
    registry: MetricsRegistry = REGISTRY

    def do_GET(self):  # noqa: N802
        if self.path.rstrip("/") in ("", "/metrics"):
            body = self.registry.render().encode()
            self.send_response(200)
            self.send_header("Content-Type", "text/plain; version=0.0.4")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        elif self.path == "/healthz":
            self.send_response(200)
            self.end_headers()
            self.wfile.write(b"ok")
        else:
            self.send_response(404)
            self.end_headers()

    def log_message(self, *a):  # silence per-request lines
        pass


def serve_metrics(port: int = 9100, registry: MetricsRegistry = REGISTRY) -> ThreadingHTTPServer:
    """Start the /metrics endpoint on a daemon thread; returns the server
    (caller may .shutdown()). Port 0 picks a free port (tests)."""
    handler = type("Handler", (_Handler,), {"registry": registry})
    srv = ThreadingHTTPServer(("0.0.0.0", port), handler)
    t = threading.Thread(target=srv.serve_forever, name="metrics", daemon=True)
    t.start()
    return srv
