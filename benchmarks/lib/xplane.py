"""From the profiler's `.xplane.pb` to numbers: device busy time as the
union of the intervals in which an operation ran, XLA module and operation
durations, and the idle gaps between modules, each named by the modules
on either side and by the shortest host event that covers its middle."""

from __future__ import annotations

import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
MIN_GAP_NS = 20_000      # shorter holes are launch latency, not a gap


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def module_name(event_name: str) -> str:
    """'jit__tick(123456789)' -> 'jit__tick'."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_label(event_name: str, width: int = 96) -> str:
    """A device operation's HLO text cut to its kind and what it makes:
    '%fusion.2 = bf16[8,128]{1,0:T(8,128)} fusion(...)' -> 'fusion
    bf16[8,128]'. Operations of one kind and shape add up under one name."""
    m = re.match(r"^%?([\w.\-]+) = (.*?) ([\w\-]+)\(", event_name)
    if not m:
        return event_name[:width]
    made = re.sub(r"\{[^{}]*\}", "", m.group(2))
    return f"{m.group(3)} {made}"[:width]


def union_ns(intervals) -> float:
    """Total length covered by (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _clip(events, lo, hi):
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield name, s, e


def read(path: str) -> dict:
    """{'devices': {ordinal: {'modules': [(name, start, end)], 'ops':
    [...]}}, 'host': [(name, start, end)]} with times in ns."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = out["devices"].setdefault(
                int(m.group(1)), {"modules": [], "ops": []})
            for line in plane.lines:
                key = {MODULE_LINE: "modules", OP_LINE: "ops"}.get(line.name)
                if key:
                    dev[key].extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out["host"].extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.duration_ns > 0)
    if not out["devices"]:
        raise ValueError(f"{path}: no device plane; planes are "
                         f"{[p.name for p in data.planes]}")
    return out


def reduce(trace: dict, lo_ns: float | None = None,
           hi_ns: float | None = None, top: int = 10) -> dict:
    """The reduction every metric reader starts from. The window is
    [lo_ns, hi_ns], by default the first module's start to the last
    module's end on any device."""
    devs = trace["devices"]
    every = [ev for d in devs.values() for ev in (d["modules"] or d["ops"])]
    if not every:
        raise ValueError("the trace holds no device operation")
    lo = min(s for _, s, _ in every) if lo_ns is None else lo_ns
    hi = max(e for _, _, e in every) if hi_ns is None else hi_ns
    busy, module_ns, op_ns, full_ns, gaps = [], {}, {}, {}, {}
    host = trace["host"]
    h_start = np.array([hs for _, hs, _ in host], dtype=np.float64)
    h_end = np.array([he for _, _, he in host], dtype=np.float64)
    for ordinal in sorted(devs):
        d = devs[ordinal]
        ops = list(_clip(d["ops"] or d["modules"], lo, hi))
        busy.append(union_ns((s, e) for _, s, e in ops))
        mods = sorted(_clip(d["modules"], lo, hi), key=lambda ev: ev[1])
        for name, s, e in mods:
            module_ns.setdefault(module_name(name), []).append(e - s)
        if ordinal == min(devs):     # one device's view of ops and gaps
            for name, s, e in ops:
                label = op_label(name)
                op_ns[label] = op_ns.get(label, 0.0) + (e - s)
                full_ns[name] = full_ns.get(name, 0.0) + (e - s)
            for (n0, _, e0), (n1, s1, _) in zip(mods, mods[1:]):
                if s1 - e0 >= MIN_GAP_NS:
                    mid = (e0 + s1) / 2
                    over = np.flatnonzero((h_start <= mid) & (mid <= h_end))
                    what = (host[over[np.argmin(
                        (h_end - h_start)[over])]][0] if over.size
                        else "unattributed")
                    label = (f"{module_name(n0)} - {module_name(n1)}: "
                             f"{what}")
                    gaps[label] = gaps.get(label, 0.0) + (s1 - e0)

    def ranked(table):
        return [[k, v / 1e9] for k, v in sorted(
            table.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "module_s": {k: [x / 1e9 for x in v] for k, v in module_ns.items()},
        "op_s": {k: v / 1e9 for k, v in full_ns.items()},
        "device_ops": ranked(op_ns),
        "idle_gaps": ranked(gaps),
        "chips": len(devs),
    }
