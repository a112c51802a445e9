"""What every driver shares: the look for the chip, the compile cache,
the count of compilations, the profiler window, the peak memory and the
result line."""

from __future__ import annotations

import json
import math
import os
import shutil
import sys
import threading
import time

from benchmarks.lib import spec

EX_NO_DEVICE = 69


class NoDevice(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def devices_for(chips: int, require_tpu: bool = True):
    """The chips the cell runs on, or NoDevice. A CPU never stands in."""
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"JAX's default platform is {devs[0].platform!r}, "
                       "not a TPU: a benchmark number comes only from a chip")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def configure_cache() -> str:
    """The persistent compilation cache at the program's fixed place in
    the checkout (or where JAX_COMPILATION_CACHE_DIR says), with every
    program kept, however quickly it compiled."""
    import jax

    from kubeflow_tpu.utils import compile_cache

    where = compile_cache.configure()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


class CompileCounter:
    """Counts XLA compilations (cache hits included: a hit inside the
    window still stalls it) through jax.monitoring."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            with self._lock:
                self.n += 1


class TraceWindow:
    """Starts and stops jax.profiler around a short stretch of the
    measured window and reduces what it wrote."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir = os.path.join(spec.ROOT, ".bench_trace")
        self.t0 = self.t1 = None

    def start(self):
        if not self.enabled:
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = time.monotonic()

    def stop(self):
        if not self.enabled or self.t0 is None or self.t1 is not None:
            return
        import jax

        jax.profiler.stop_trace()
        self.t1 = time.monotonic()

    def reduce(self) -> dict | None:
        if not self.enabled:
            return None
        from benchmarks.lib import xplane

        red = xplane.reduce(xplane.read(xplane.find_xplane(self.dir)))
        keep = os.environ.get("BENCH_KEEP_OPS")
        if keep:   # to look at one trace by hand: every operation's time
            with open(keep, "w") as f:
                json.dump({"op_s": red["op_s"], "module_s": red["module_s"]}, f)
        shutil.rmtree(self.dir, ignore_errors=True)
        return red


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def device_line(devices, trace_red: dict | None) -> dict:
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if trace_red is not None:
        out["busy_s"] = trace_red["busy_s"]
        out["window_s"] = trace_red["window_s"]
    return out


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of all the values (no interpolation)."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, math.ceil(q / 100.0 * len(s)) - 1))]


def emit(result: dict) -> None:
    """The compared numbers beside their limits as the last lines on
    standard error, then the one result line on standard output, with
    the same numbers under `checks`, last."""
    checks = result.pop("checks")
    for name, value, limit in checks:
        print(f"check {name}: value {value!r} limit {limit!r}",
              file=sys.stderr)
    sys.stderr.flush()
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in checks}
    print(json.dumps(result), flush=True)


def judge(checks) -> bool:
    """Every compared number at or under its limit (and a real number)."""
    return all(v == v and v <= lim for _, v, lim in checks)
