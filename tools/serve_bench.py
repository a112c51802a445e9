#!/usr/bin/env python3
"""Serving benchmarks: single-replica decode modes AND the JAXService
serving plane.

Two families share this tool:

1. **Decode modes** (the original ledger): drives the in-process
   serving stack (no HTTP overhead) with an open-loop arrival stream of
   pre-tokenized prompts and reports ONE JSON line per mode — `micro`
   (MicroBatcher + whole-batch generate) vs `continuous` (slot
   decoder). Run on real TPU for numbers that matter.

     python tools/serve_bench.py --model gpt-350m --param-dtype bfloat16 \\
         --prompt-len 512 --max-new-tokens 64 --requests 64 --concurrency 16

2. **The serving plane** (`--router`, ISSUE 8): a DETERMINISTIC
   virtual-time benchmark of the token router + JAXService controller —
   manual clock, seeded arrival trace, stub replicas with a fixed
   tokens/sec service rate, zero wall-clock dependence, so every
   latency/throughput number and every autoscaling decision replays
   identically per seed. Two arms share one trace:

   - ``single`` — replicas pinned at 1 (the pre-JAXService shape);
   - ``multi``  — autoscaling 1..4 on router queue depth + tokens/sec,
     WITH the scripted drills: a replica kill mid-load (the router must
     shed its in-flight requests to survivors with zero drops and the
     controller must re-provision) and a full scale-up/scale-down cycle
     (cordon -> drain -> delete proven on the virtual clock).

   Banked as BENCH_SERVE_r01.json; ``--check`` reruns the banked config
   and gates on regression (the sched_bench.py ratchet mold):
   any dropped request, a changed decision fingerprint (determinism),
   or multi-arm throughput below 75% of the banked number fails CI.

     python tools/serve_bench.py --router          # run + bank
     python tools/serve_bench.py --check           # CI gate

3. **The per-replica decode path** (``--decode``, ISSUE 9): a
   deterministic counter benchmark of the paged KV cache, prefix
   reuse, and speculative lockstep decode on the tiny test
   transformer — dense-vs-paged concurrency at the same cache bytes,
   prefill tokens saved by the prefix cache, tokens per target
   forward under speculation, all token-identical across arms. Banked
   as BENCH_SERVE_r02.json; ``--check`` gates BOTH banks.

     python tools/serve_bench.py --decode          # run + bank r02

4. **Request-level resilience** (``--resilience``, ISSUE 14): the same
   deterministic virtual-time harness pointed at the resilience layer —
   three stub replicas, a 1-of-3 BROWNOUT (10x slower, not dead) with an
   overload wave inside it, then a flapping replica. Two arms share one
   seeded trace of banded requests with a 4s deadline: ``resilient``
   (deadlines + hedging + breakers + band shedding on) vs ``control``
   (resilience=None — the legacy router; goodput still judged against
   the same deadline). Banked as BENCH_SERVE_r03.json; ``--check``
   gates critical-band goodput during the brownout, hedge rescues, the
   breaker round-trip, the decision fingerprint, and the zero-KV-leak
   cancel drill.

     python tools/serve_bench.py --resilience      # run + bank r03
"""

from __future__ import annotations

import argparse
import heapq
import json
import math
import os
import random
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROUTER_OUT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_SERVE_r01.json")
DECODE_OUT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_SERVE_r02.json")
RESILIENCE_OUT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_SERVE_r03.json")


def run_mode(mode: str, args) -> dict:
    from kubeflow_tpu.serving.server import serve_lm_generator

    served = serve_lm_generator(
        "bench", args.model, prompt_len=args.prompt_len,
        max_new_tokens=args.max_new_tokens,
        continuous_batching=(mode == "continuous"),
        decode_slots=args.slots,
        **({"kv_pages": args.kv_pages, "kv_page_size": args.kv_page_size}
           if args.kv_pages and mode == "continuous" else {}),
        batch_window_ms=(args.window_ms if mode == "micro" else 0.0),
        param_dtype=args.param_dtype or None,
        mesh=args.mesh or None,
        vocab_size=args.vocab_size,
        **({"kv_cache_dtype": args.kv_cache_dtype}
           if args.kv_cache_dtype else {}),
        **({"attention_window": args.attention_window}
           if args.attention_window else {}),
        **({"rolling_kv_cache": True} if args.rolling_kv_cache else {}))
    try:
        rng = __import__("random").Random(0)
        prompts = [[rng.randrange(1, args.vocab_size)
                    for _ in range(rng.randrange(4, args.prompt_len))]
                   for _ in range(args.requests)]
        # warmup: compile every program the measured window can hit —
        # micro-batching dispatches pow2-padded GROUPS, so warm each
        # pow2 batch size up to the concurrency cap (otherwise first-
        # compile latencies pollute the percentiles)
        k = 1
        while k <= max(1, args.concurrency):
            served.predict([{"tokens": prompts[i % len(prompts)]}
                            for i in range(k)])
            k *= 2

        latencies: list[float] = []
        lat_lock = threading.Lock()
        sem = threading.Semaphore(args.concurrency)
        threads = []

        def one(p):
            t0 = time.perf_counter()
            served.predict([{"tokens": p}])
            dt = time.perf_counter() - t0
            with lat_lock:
                latencies.append(dt)
            sem.release()

        t_start = time.perf_counter()
        for p in prompts:
            sem.acquire()  # closed-loop at `concurrency` outstanding
            th = threading.Thread(target=one, args=(p,))
            th.start()
            threads.append(th)
        for th in threads:
            th.join()
        wall = time.perf_counter() - t_start
        latencies.sort()

        def pct(q):
            return round(
                latencies[min(len(latencies) - 1,
                              int(q * len(latencies)))] * 1e3, 1)

        return {
            "mode": mode,
            "requests": args.requests,
            "concurrency": args.concurrency,
            "slots": args.slots,
            "tokens_per_sec": round(
                args.requests * args.max_new_tokens / wall, 1),
            "requests_per_sec": round(args.requests / wall, 2),
            "p50_ms": pct(0.50),
            "p95_ms": pct(0.95),
            "p99_ms": pct(0.99),
            "wall_s": round(wall, 2),
            "model": args.model,
            "max_new_tokens": args.max_new_tokens,
            "param_dtype": args.param_dtype or "f32",
            **({"kv_cache_dtype": args.kv_cache_dtype}
               if args.kv_cache_dtype else {}),
            **({"attention_window": args.attention_window,
                "rolling_kv_cache": bool(args.rolling_kv_cache)}
               if args.attention_window else {}),
        }
    finally:
        served.close()


# ---------------------------------------------------------------------------
# The deterministic per-replica decode benchmark (--decode / --check,
# ISSUE 9): dense-vs-paged KV cache density, prefix-cache prefill
# savings, greedy-vs-speculative tokens per target forward — all on the
# tiny test transformer with seeded prompts, so every claim is a
# COUNTER (array shapes, allocator stats, prefill/accept totals) that
# replays identically per seed. CPU wall seconds are banked alongside
# for context but never gated (the TPU backend is unavailable in this
# image; ROADMAP bench policy).


DECODE_CONFIG = {
    "seed": 0,
    "model": "transformer-test",
    "vocab_size": 64,
    "prompt_len": 32,          # 4 full pages of prompt
    "max_new_tokens": 16,      # server-wide ceiling
    "req_new": 8,              # per-request budget (density arms)
    "page_size": 8,
    "dense_slots": 4,
    "paged_slots": 8,
    "requests": 8,
    "shared_prefix": 24,       # 3 pages shared across all 8 prompts
    "draft_k": 4,
    "spec_requests": 4,
}


def _decode_prompts(cfg: dict, rng: random.Random) -> list[list[int]]:
    """Full-length (no padding) prompts sharing a page-aligned system
    prefix — the workload the prefix cache exists for."""
    pre = [rng.randrange(1, cfg["vocab_size"])
           for _ in range(cfg["shared_prefix"])]
    tail = cfg["prompt_len"] - cfg["shared_prefix"]
    return [pre + [rng.randrange(1, cfg["vocab_size"]) for _ in range(tail)]
            for _ in range(cfg["requests"])]


def _drive_burst(dec, prompts, max_new) -> tuple[list, float]:
    """Queue every request while admission is held, then release: the
    decoder sees one deterministic FIFO burst (admission order == list
    order), which pins prefix-hit and peak-concurrency counters."""
    results: list = [None] * len(prompts)
    held, dec._free = dec._free, []
    threads = [threading.Thread(
        target=lambda i=i: results.__setitem__(
            i, dec.submit(prompts[i], max_new)))
        for i in range(len(prompts))]
    t0 = time.perf_counter()
    for th in threads:
        th.start()
    time.sleep(0.4)  # queue fully populated while no slot is "free"
    dec._free = held
    dec._wake.set()
    for th in threads:
        th.join()
    return results, time.perf_counter() - t0


def _arm_stats(dec, wall: float) -> dict:
    keep = ("admitted", "completed", "peak_active",
            "prefill_tokens_computed", "prompt_tokens_submitted",
            "cache_bytes", "spec_rounds", "spec_tokens_emitted",
            "spec_tokens_accepted", "spec_drafted", "kv_pages_total",
            "kv_page_size", "prefix_hit_pages", "prefix_hit_tokens",
            "cow_clones", "mode")
    st = dec.stats()
    out = {k: st[k] for k in keep if k in st}
    out["wall_s"] = round(wall, 2)
    return out


def run_decode_bench(cfg: dict) -> dict:
    import hashlib

    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass  # backend already initialized (tests force cpu themselves)
    import numpy as np

    from kubeflow_tpu.models.registry import get_model
    from kubeflow_tpu.serving.continuous import SlotDecoder

    P, N, PS = cfg["prompt_len"], cfg["max_new_tokens"], cfg["page_size"]
    dense_seq = P + N
    # SAME cache-byte budget by construction: pool positions (pages x
    # page_size, trash page included) == dense positions (slots x P+N)
    kv_pages = cfg["dense_slots"] * dense_seq // PS
    rng = random.Random(cfg["seed"])
    prompts = _decode_prompts(cfg, rng)

    dense_m = get_model(cfg["model"], vocab_size=cfg["vocab_size"],
                        max_seq_len=dense_seq)
    variables = dense_m.init(jax.random.PRNGKey(cfg["seed"]),
                             np.zeros((1, 1), np.int32), train=False)

    # -- density: dense S_d slots vs paged pool at the same bytes ------
    dd = SlotDecoder(dense_m, variables, slots=cfg["dense_slots"],
                     prompt_len=P, max_new_tokens=N)
    try:
        dense_out, dense_wall = _drive_burst(dd, prompts, cfg["req_new"])
        dense = _arm_stats(dd, dense_wall)
    finally:
        dd.close()
    paged_m = get_model(cfg["model"], vocab_size=cfg["vocab_size"],
                        max_seq_len=dense_seq, kv_pages=kv_pages,
                        kv_page_size=PS)
    pd = SlotDecoder(paged_m, variables, slots=cfg["paged_slots"],
                     prompt_len=P, max_new_tokens=N)
    try:
        paged_out, paged_wall = _drive_burst(pd, prompts, cfg["req_new"])
        paged = _arm_stats(pd, paged_wall)
    finally:
        pd.close()

    # -- prefix reuse: the same paged pool with the cache disabled -----
    po = SlotDecoder(paged_m, variables, slots=cfg["paged_slots"],
                     prompt_len=P, max_new_tokens=N, prefix_cache=False)
    try:
        off_out, off_wall = _drive_burst(po, prompts, cfg["req_new"])
        off = _arm_stats(po, off_wall)
    finally:
        po.close()

    # -- speculative lockstep: draft == target weights (a perfectly
    #    agreeing draft — the tokens-per-forward ceiling) vs greedy ----
    k = cfg["draft_k"]
    spec_m = get_model(cfg["model"], vocab_size=cfg["vocab_size"],
                       max_seq_len=P + N + k)
    sprompts = prompts[:cfg["spec_requests"]]
    gd = SlotDecoder(spec_m, variables, slots=cfg["spec_requests"],
                     prompt_len=P, max_new_tokens=N)
    try:
        greedy_out, greedy_wall = _drive_burst(gd, sprompts, N)
        greedy = _arm_stats(gd, greedy_wall)
    finally:
        gd.close()
    sd = SlotDecoder(spec_m, variables, slots=cfg["spec_requests"],
                     prompt_len=P, max_new_tokens=N,
                     draft_model=spec_m, draft_variables=variables,
                     draft_k=k)
    try:
        spec_out, spec_wall = _drive_burst(sd, sprompts, N)
        spec = _arm_stats(sd, spec_wall)
    finally:
        sd.close()

    fingerprint = hashlib.sha256(json.dumps(
        [dense_out, paged_out, off_out, greedy_out, spec_out],
        sort_keys=True).encode()).hexdigest()
    tokens_per_forward = (spec["spec_tokens_emitted"]
                          / max(spec["spec_rounds"], 1))
    saving_pct = round(100.0 * (1 - paged["prefill_tokens_computed"]
                                / max(off["prefill_tokens_computed"], 1)), 1)
    return {
        "config": dict(cfg),
        "density": {
            "dense": dense, "paged": paged,
            "identical_tokens": paged_out == dense_out,
            "same_cache_bytes":
                paged["cache_bytes"] == dense["cache_bytes"],
            "concurrency_x": round(paged["peak_active"]
                                   / max(dense["peak_active"], 1), 2),
        },
        "prefix": {
            "off": off,
            "identical_tokens": off_out == paged_out,
            "prefill_tokens_with_cache": paged["prefill_tokens_computed"],
            "prefill_tokens_without": off["prefill_tokens_computed"],
            "saving_pct": saving_pct,
        },
        "speculative": {
            "greedy": greedy, "spec": spec,
            "identical_tokens": spec_out == greedy_out,
            "tokens_per_forward": round(tokens_per_forward, 2),
        },
        "fingerprint": fingerprint,
    }


def check_decode_bench(banked_path: str) -> int:
    """CI ratchet over BENCH_SERVE_r02: rerun the banked config and
    fail on any broken invariant (tokens diverging between arms, the
    paged pool admitting < 2x dense at the same bytes, prefix savings
    below 40%, speculative <= 1 token per target forward) or on a
    changed deterministic fingerprint."""
    with open(banked_path) as fh:
        banked = json.load(fh)
    section = banked.get("decode")
    if not section:
        print(f"check: no decode section in {banked_path}", file=sys.stderr)
        return 2
    now = run_decode_bench(dict(section["config"]))
    ok = True
    if not (now["density"]["identical_tokens"]
            and now["prefix"]["identical_tokens"]
            and now["speculative"]["identical_tokens"]):
        print("check: decode regression — arms no longer token-identical",
              file=sys.stderr)
        ok = False
    if not now["density"]["same_cache_bytes"]:
        print("check: decode regression — cache byte budgets diverged",
              file=sys.stderr)
        ok = False
    if now["density"]["concurrency_x"] < 2.0:
        print(f"check: decode regression — paged admits only "
              f"{now['density']['concurrency_x']}x dense (< 2x)",
              file=sys.stderr)
        ok = False
    if now["prefix"]["saving_pct"] < 40.0:
        print(f"check: decode regression — prefix cache saves only "
              f"{now['prefix']['saving_pct']}% prefill tokens (< 40%)",
              file=sys.stderr)
        ok = False
    if now["speculative"]["tokens_per_forward"] <= 1.0:
        print("check: decode regression — speculative emits <= 1 token "
              "per target forward", file=sys.stderr)
        ok = False
    if now["fingerprint"] != section["fingerprint"]:
        print("check: decode regression — deterministic token "
              "fingerprint diverged from the bank", file=sys.stderr)
        ok = False
    print(json.dumps({"check": "ok" if ok else "REGRESSED",
                      "concurrency_x": now["density"]["concurrency_x"],
                      "saving_pct": now["prefix"]["saving_pct"],
                      "tokens_per_forward":
                          now["speculative"]["tokens_per_forward"]},
                     indent=2))
    return 0 if ok else 1


def decode_main(args) -> int:
    if args.check:
        return check_decode_bench(args.decode_out)
    cfg = dict(DECODE_CONFIG)
    cfg["seed"] = args.seed
    result = {"bench": "serve_bench", "round": "r02",
              "decode": run_decode_bench(cfg)}
    with open(args.decode_out, "w") as fh:
        json.dump(result, fh, indent=2, sort_keys=True)
        fh.write("\n")
    d = result["decode"]
    print(json.dumps({"out": args.decode_out,
                      "concurrency_x": d["density"]["concurrency_x"],
                      "saving_pct": d["prefix"]["saving_pct"],
                      "tokens_per_forward":
                          d["speculative"]["tokens_per_forward"],
                      "identical": d["density"]["identical_tokens"]
                      and d["prefix"]["identical_tokens"]
                      and d["speculative"]["identical_tokens"]},
                     indent=2))
    return 0


# ---------------------------------------------------------------------------
# The deterministic serving-plane benchmark (--router / --check)


class ManualClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance_to(self, t: float) -> None:
        self.t = max(self.t, t)


# Virtual-time workload: (duration_s, arrivals_per_s) phases — a ramp
# from a trickle into ~3x one replica's capacity (30 req/s x ~64 tokens
# ~= 1900 tokens/s vs 600), then a lull so the scale-down half of the
# cycle runs inside the measured window. The single arm queues the
# whole overload and drains it for ~45 extra virtual seconds; the multi
# arm scales to 4 and absorbs it.
PHASES = ((5.0, 2.0), (20.0, 30.0), (25.0, 1.0))
ROUTER_CONFIG = {
    "seed": 0,
    "tokens_lo": 32, "tokens_hi": 96,      # per-request new tokens
    "replica_tokens_per_sec": 600.0,       # stub service rate
    "replica_token_budget": 256,           # router queues beyond this
    "max_queue": 2048,
    "max_replicas": 4,
    "target_queue_depth": 4,
    "target_tokens_per_sec": 450.0,
    "up_stabilization_s": 1.0,
    "down_stabilization_s": 8.0,
    "control_tick_s": 0.25,                # reconcile + endpoint sync cadence
    "kill_at_s": 15.0,                     # multi arm: replica-1 dies here
}


def build_trace(cfg: dict, rng: random.Random) -> list[tuple[float, int]]:
    """Seeded open-loop arrival trace: (time, tokens) per request."""
    out = []
    t = 0.0
    for duration, rate in PHASES:
        end = t + duration
        while True:
            t += rng.expovariate(rate)
            if t >= end:
                t = end
                break
            out.append((t, rng.randrange(cfg["tokens_lo"],
                                         cfg["tokens_hi"])))
    return out


def run_router_arm(arm: str, cfg: dict) -> dict:
    """One virtual-time run: the REAL JAXService controller against a
    FakeCluster, the REAL token router, stub replicas modeled as
    fixed-rate FIFO servers. Single-threaded event loop — every
    transition is an explicit call, so decisions replay per seed."""
    from kubeflow_tpu.control.jaxservice import types as T
    from kubeflow_tpu.control.jaxservice.controller import build_controller
    from kubeflow_tpu.control.k8s import objects as ob
    from kubeflow_tpu.control.k8s.fake import FakeCluster
    from kubeflow_tpu.control.k8s.kubelet import FakeKubelet
    from kubeflow_tpu.control.runtime import seed_controller
    from kubeflow_tpu.runtime.metrics import MetricsRegistry
    from kubeflow_tpu.serving.router import (
        RegistrySignals, RouterBusy, TokenRouter, parse_endpoints,
    )

    rng = random.Random(cfg["seed"])
    trace = build_trace(cfg, rng)
    clock = ManualClock()
    cluster = FakeCluster(history_limit=65536)
    registry = MetricsRegistry()
    signals = RegistrySignals(registry)
    ctl = seed_controller(build_controller(
        cluster, record_events=False, registry=registry, signals=signals,
        clock=clock))
    kubelet = FakeKubelet(cluster)
    max_replicas = 1 if arm == "single" else cfg["max_replicas"]
    cluster.create(T.new_jaxservice(
        "bench", model="gpt-125m", min_replicas=1,
        max_replicas=max_replicas,
        target_queue_depth=cfg["target_queue_depth"],
        target_tokens_per_sec=cfg["target_tokens_per_sec"],
        up_stabilization_s=cfg["up_stabilization_s"],
        down_stabilization_s=cfg["down_stabilization_s"]))
    router = TokenRouter(
        service="bench", namespace="default", clock=clock,
        registry=registry, prom_sink=False,
        max_queue=cfg["max_queue"],
        replica_token_budget=cfg["replica_token_budget"])

    free_at: dict[str, float] = {}
    seq: dict[int, int] = {}          # ticket id -> dispatch generation
    events: list[tuple] = []          # (due, order, kind, payload)
    order = [0]

    def push(due: float, kind: str, payload) -> None:
        order[0] += 1
        heapq.heappush(events, (due, order[0], kind, payload))

    def schedule(ticket) -> None:
        name = ticket.member.name
        due = max(clock.t, free_at.get(name, 0.0)) \
            + ticket.tokens / cfg["replica_tokens_per_sec"]
        free_at[name] = due
        seq[id(ticket)] = seq.get(id(ticket), 0) + 1
        push(due, "complete", (ticket, name, seq[id(ticket)]))

    latencies: list[float] = []
    tokens_done = 0
    # peak-demand window (the overload phase): where capacity, not the
    # workload, bounds throughput — the multi-vs-single scaling claim
    ramp_start = PHASES[0][0]
    ramp_end = ramp_start + PHASES[1][0]
    ramp_tokens = 0
    completed = rejected = shed_redispatches = 0
    decisions: list[list] = []
    kill_done = {"t": None, "restart_seen": False}

    def control_tick() -> None:
        nonlocal shed_redispatches
        for _ in range(4):
            if ctl.run_until_idle(max_rounds=1000,
                                  advance_delayed=True) == 0:
                break
            kubelet.step()
        svc = cluster.get(T.API_VERSION, T.KIND, "bench", "default")
        target = (svc.get("status") or {}).get("targetReplicas", 1)
        if not decisions or decisions[-1][1] != target:
            # a list, not a tuple: the fingerprint must compare equal
            # after a JSON round-trip through the banked file
            decisions.append([round(clock.t, 2), target])
        eps = parse_endpoints(svc)
        live = {e["name"] for e in eps}
        for name in list(free_at):
            if name not in live:
                free_at.pop(name)
        redispatched = router.sync_endpoints(eps)
        shed_redispatches += len(redispatched)
        for t in redispatched:
            schedule(t)
        if (svc.get("status") or {}).get("restarts", 0) > 0:
            kill_done["restart_seen"] = True

    def kill_replica() -> None:
        pod = cluster.get_or_none("v1", "Pod", "bench-replica-1",
                                  "default")
        if pod is None:
            return
        pod.setdefault("status", {})["phase"] = "Failed"
        pod["status"]["reason"] = "Evicted"
        cluster.update_status(pod)
        free_at.pop("bench-replica-1", None)
        kill_done["t"] = clock.t

    # seed the event heap
    for t_arr, tokens in trace:
        push(t_arr, "arrive", tokens)
    tick = 0.0
    horizon = sum(d for d, _ in PHASES) + 120.0
    while tick < horizon:
        push(tick, "tick", None)
        tick += cfg["control_tick_s"]
    if arm == "multi":
        push(cfg["kill_at_s"], "kill", None)

    submitted: dict[int, float] = {}  # ticket id -> arrival time
    pending = len(trace)
    while events:
        due, _, kind, payload = heapq.heappop(events)
        clock.advance_to(due)
        if kind == "tick":
            control_tick()
            if pending == 0 and router.queue_depth() == 0 \
                    and router.inflight_tokens() == 0:
                # drained: let the scale-down tail keep running a bit,
                # then stop once no completion events remain
                if not any(k == "complete" for _, _, k, _ in events):
                    break
        elif kind == "arrive":
            try:
                t = router.submit(payload)
            except RouterBusy:
                rejected += 1
                pending -= 1
                continue
            submitted[id(t)] = clock.t
            if t.member is not None:
                schedule(t)
        elif kind == "kill":
            kill_replica()
        elif kind == "complete":
            ticket, name, gen = payload
            if ticket.member is None or ticket.member.name != name \
                    or seq.get(id(ticket)) != gen:
                continue  # stale: the ticket was shed and rescheduled
            latencies.append(clock.t - submitted.pop(id(ticket), clock.t))
            tokens_done += ticket.tokens
            if ramp_start <= clock.t <= ramp_end:
                ramp_tokens += ticket.tokens
            completed += 1
            pending -= 1
            for t in router.complete(ticket):
                schedule(t)

    svc = cluster.get(T.API_VERSION, T.KIND, "bench", "default")
    status = svc.get("status") or {}
    latencies.sort()

    def pct(q: float) -> float:
        if not latencies:
            return 0.0
        return round(latencies[min(len(latencies) - 1,
                                   int(q * len(latencies)))], 3)

    dropped = len(trace) - completed - rejected
    return {
        "arm": arm,
        "requests": len(trace),
        "completed": completed,
        "rejected": rejected,
        "dropped": dropped,
        "tokens_done": tokens_done,
        "virtual_makespan_s": round(clock.t, 2),
        "tokens_per_sec": round(tokens_done / clock.t, 1) if clock.t else 0,
        "peak_tokens_per_sec": round(
            ramp_tokens / (ramp_end - ramp_start), 1),
        "p50_s": pct(0.50),
        "p95_s": pct(0.95),
        "p99_s": pct(0.99),
        "max_target": max((t for _, t in decisions), default=1),
        "final_target": decisions[-1][1] if decisions else 1,
        "scales": status.get("scales", 0),
        "replica_restarts": status.get("restarts", 0),
        "shed_redispatches": shed_redispatches,
        "kill_at_s": kill_done["t"],
        "decisions": decisions,
    }


def run_router_bench(cfg: dict) -> dict:
    single = run_router_arm("single", cfg)
    multi = run_router_arm("multi", cfg)
    replay = run_router_arm("multi", cfg)  # determinism self-check
    identical = (multi["decisions"] == replay["decisions"]
                 and multi["tokens_done"] == replay["tokens_done"]
                 and multi["p95_s"] == replay["p95_s"])
    return {
        "config": dict(cfg),
        "single": single,
        "multi": multi,
        "comparison": {
            "tokens_per_sec_x": round(
                multi["tokens_per_sec"]
                / max(single["tokens_per_sec"], 1e-9), 2),
            "peak_tokens_per_sec_x": round(
                multi["peak_tokens_per_sec"]
                / max(single["peak_tokens_per_sec"], 1e-9), 2),
            "p95_speedup_x": round(
                single["p95_s"] / max(multi["p95_s"], 1e-9), 2),
            "zero_dropped": single["dropped"] == 0
            and multi["dropped"] == 0,
            "kill_drill_survived": multi["replica_restarts"] >= 1
            and multi["dropped"] == 0,
            "scale_cycle_complete": multi["max_target"] > 1
            and multi["final_target"] < multi["max_target"],
            "decisions_replay_identical": identical,
        },
    }


def check_router_bench(banked_path: str) -> int:
    """CI ratchet: rerun the banked config; fail on any dropped
    request, a broken drill, a changed decision fingerprint, or
    multi-arm throughput below 75% of the banked number."""
    with open(banked_path) as fh:
        banked = json.load(fh)
    section = banked.get("router")
    if not section:
        print(f"check: no router section in {banked_path}",
              file=sys.stderr)
        return 2
    now = run_router_bench(dict(section["config"]))
    ok = True
    cmp_ = now["comparison"]
    if not cmp_["zero_dropped"] or not cmp_["kill_drill_survived"]:
        print("check: drill regression — dropped requests or the kill "
              "drill failed", file=sys.stderr)
        ok = False
    if not cmp_["decisions_replay_identical"]:
        print("check: determinism regression — same-seed replay "
              "diverged", file=sys.stderr)
        ok = False
    if now["multi"]["decisions"] != section["multi"]["decisions"]:
        print("check: autoscaling decisions diverged from the banked "
              "fingerprint", file=sys.stderr)
        ok = False
    floor = section["multi"]["tokens_per_sec"] * 0.75
    if now["multi"]["tokens_per_sec"] < floor:
        print(f"check: multi tokens_per_sec "
              f"{now['multi']['tokens_per_sec']} below budget "
              f"{floor:.1f} (banked "
              f"{section['multi']['tokens_per_sec']})", file=sys.stderr)
        ok = False
    print(json.dumps({"check": "ok" if ok else "REGRESSED",
                      "multi_tokens_per_sec":
                          now["multi"]["tokens_per_sec"],
                      "comparison": cmp_}, indent=2))
    return 0 if ok else 1


def router_main(args) -> int:
    if args.check:
        return check_router_bench(args.out)
    cfg = dict(ROUTER_CONFIG)
    cfg["seed"] = args.seed
    result = {"bench": "serve_bench", "round": "r01",
              "router": run_router_bench(cfg)}
    with open(args.out, "w") as fh:
        json.dump(result, fh, indent=2, sort_keys=True)
        fh.write("\n")
    print(json.dumps({"out": args.out,
                      "comparison": result["router"]["comparison"],
                      "single_tokens_per_sec":
                          result["router"]["single"]["tokens_per_sec"],
                      "multi_tokens_per_sec":
                          result["router"]["multi"]["tokens_per_sec"]},
                     indent=2))
    return 0


# ---------------------------------------------------------------------------
# The deterministic resilience benchmark (--resilience / --check,
# ISSUE 14): router core only — no controller, membership is static.
# Three stub replicas modeled as fixed-rate FIFO servers on the manual
# clock; the drills are a brownout (slow, not dead), an overload wave
# inside it, and a fail-fast flap. Every router decision (sheds,
# deadline drops, hedges, breaker transitions) is tapped via
# on_decision and fingerprinted, so the whole run replays byte-identical
# per seed.


# (start_s, end_s, arrivals_per_s) — warmup builds the latency samples
# hedging needs, then the brownout window [6, 30) holds an overload
# wave [8, 28), then the flap window [30, 36) and a cooldown tail.
RES_PHASES = ((0.0, 6.0, 8.0), (6.0, 8.0, 10.0), (8.0, 28.0, 40.0),
              (28.0, 30.0, 10.0), (30.0, 36.0, 8.0), (36.0, 44.0, 6.0))
RES_CONFIG = {
    "seed": 0,
    "tokens_lo": 32, "tokens_hi": 96,
    "replica_tokens_per_sec": 600.0,
    "replica_token_budget": 256,
    "max_queue": 24,
    "replicas": 3,
    "deadline_s": 4.0,
    # band mix: P(critical), P(critical)+P(default) thresholds on one
    # uniform draw per arrival
    "band_split": (0.2, 0.8),
    "brownout": (6.0, 30.0),          # r0 serves at rate/brownout_x here
    "brownout_x": 10.0,
    "brownout_replica": "r0",
    "flap": (30.0, 36.0),             # r1 fails fast here (breaker drill)
    "flap_replica": "r1",
    "fail_latency_s": 0.02,           # a fast error, not a timeout
}


def build_res_trace(cfg: dict, rng: random.Random) -> list[tuple]:
    """Seeded open-loop trace of (time, tokens, band) arrivals."""
    from kubeflow_tpu.serving.router import (
        BAND_CRITICAL, BAND_DEFAULT, BAND_SHEDDABLE,
    )

    p_crit, p_def = cfg["band_split"]
    out = []
    for start, end, rate in RES_PHASES:
        t = start
        while True:
            t += rng.expovariate(rate)
            if t >= end:
                break
            tokens = rng.randrange(cfg["tokens_lo"], cfg["tokens_hi"])
            u = rng.random()
            band = (BAND_CRITICAL if u < p_crit
                    else BAND_DEFAULT if u < p_def else BAND_SHEDDABLE)
            out.append((t, tokens, band))
    return out


def run_resilience_arm(arm: str, cfg: dict,
                       trace: list[tuple]) -> dict:
    """One virtual-time run over the shared trace. ``resilient`` turns
    the full layer on (deadlines reach the router, hedge checks fire,
    in-flight work is canceled at its deadline — modeling the replica-
    side slot cancel); ``control`` is the legacy router, with goodput
    still judged against the same per-request deadline."""
    import hashlib

    from kubeflow_tpu.serving.router import (
        BAND_RANK, Member, ResilienceConfig, RouterBusy, TokenRouter,
    )

    resilient = arm == "resilient"
    clock = ManualClock()
    decisions: list[dict] = []
    router = TokenRouter(
        service="bench", namespace="default", clock=clock,
        prom_sink=False, max_queue=cfg["max_queue"],
        replica_token_budget=cfg["replica_token_budget"],
        resilience=ResilienceConfig() if resilient else None,
        on_decision=decisions.append if resilient else None)
    names = [f"r{i}" for i in range(cfg["replicas"])]
    router.set_members([Member(name=n) for n in names])

    bo_start, bo_end = cfg["brownout"]
    fl_start, fl_end = cfg["flap"]

    def rate_of(name: str, at: float) -> float:
        r = cfg["replica_tokens_per_sec"]
        if name == cfg["brownout_replica"] and bo_start <= at < bo_end:
            return r / cfg["brownout_x"]
        return r

    free_at: dict[str, float] = {}
    seq: dict[int, int] = {}
    finished: set[int] = set()
    events: list[tuple] = []
    order = [0]
    # id(t) keys (arrivals/seq/finished) are only stable while the
    # ticket object is alive — hold every admitted ticket so CPython
    # never reuses an id mid-run (a recycled id would alias a new
    # ticket onto a finished one and silently drop its events)
    hold: list = []
    arrivals: dict[int, tuple] = {}   # ticket id -> (t_arr, band, tokens)
    done_at: dict[int, float] = {}
    per_band = {b: {"arrivals": 0, "rejected": 0} for b in BAND_RANK}
    hedge_wins = 0
    deadline_cancels = 0

    def push(due: float, kind: str, payload) -> None:
        order[0] += 1
        heapq.heappush(events, (due, order[0], kind, payload))

    def svc_time(name: str, tokens: int, at: float) -> float:
        return tokens / rate_of(name, at)

    def on_dispatch(t) -> None:
        """Model the dispatched leg: a flapping replica errors fast;
        everyone else serves FIFO at its current rate. The resilient arm
        also arms the deadline cancel and the hedge check."""
        name = t.member.name
        now = clock.t
        seq[id(t)] = seq.get(id(t), 0) + 1
        gen = seq[id(t)]
        if name == cfg["flap_replica"] and fl_start <= now < fl_end:
            push(now + cfg["fail_latency_s"], "fail", (t, name, gen))
            return
        svc = svc_time(name, t.tokens, now)
        due = max(now, free_at.get(name, 0.0)) + svc
        free_at[name] = due
        if resilient and t.deadline is not None and due > t.deadline:
            # the replica cancels the slot AT the deadline (frees its
            # pages); the leg never produces a completion
            push(t.deadline, "cancel", (t, name, gen, svc))
            delay = router.hedge_delay()
            if delay is not None and now + delay < t.deadline:
                push(now + delay, "hedge", (t, name, gen))
            return
        push(due, "complete", (t, name, gen, svc))
        if resilient:
            delay = router.hedge_delay()
            if delay is not None and now + delay < due \
                    and (t.deadline is None or now + delay < t.deadline):
                push(now + delay, "hedge", (t, name, gen))

    def refund(name: str, svc: float) -> None:
        """A canceled leg frees its replica early (the slot-cancel /
        hedge-loser path): pull the FIFO horizon back by its share."""
        if name in free_at:
            free_at[name] = max(clock.t, free_at[name] - svc)

    for t_arr, tokens, band in trace:
        push(t_arr, "arrive", (tokens, band))

    while events:
        due, _, kind, payload = heapq.heappop(events)
        clock.advance_to(due)
        if kind == "arrive":
            tokens, band = payload
            per_band[band]["arrivals"] += 1
            try:
                if resilient:
                    t = router.submit(
                        tokens, band=band,
                        deadline=clock.t + cfg["deadline_s"])
                else:
                    t = router.submit(tokens)
            except RouterBusy:
                per_band[band]["rejected"] += 1
                continue
            hold.append(t)
            arrivals[id(t)] = (clock.t, band, tokens)
            if t.member is not None:
                on_dispatch(t)
        elif kind == "complete":
            t, name, gen, svc = payload
            if id(t) in finished or seq.get(id(t)) != gen \
                    or t.member is None or t.member.name != name:
                continue
            finished.add(id(t))
            done_at[id(t)] = clock.t
            if t.hedge_member is not None:
                refund(t.hedge_member.name, svc_time(
                    t.hedge_member.name, t.tokens, t._hedge_at))
            for nt in router.complete(t):
                on_dispatch(nt)
        elif kind == "hcomplete":
            t, hname, svc = payload
            if id(t) in finished or t.hedge_member is None \
                    or t.hedge_member.name != hname:
                continue
            finished.add(id(t))
            done_at[id(t)] = clock.t
            hedge_wins += 1
            if t.member is not None:
                refund(t.member.name, svc_time(
                    t.member.name, t.tokens, t._dispatched_at))
            for nt in router.complete(t, winner=hname):
                on_dispatch(nt)
        elif kind == "hedge":
            t, name, gen = payload
            if id(t) in finished or seq.get(id(t)) != gen \
                    or t.member is None or t.member.name != name:
                continue
            m = router.try_hedge(t)
            if m is None:
                continue
            svc = svc_time(m.name, t.tokens, clock.t)
            hdue = max(clock.t, free_at.get(m.name, 0.0)) + svc
            free_at[m.name] = hdue
            if t.deadline is None or hdue <= t.deadline:
                push(hdue, "hcomplete", (t, m.name, svc))
            else:
                push(t.deadline, "hcancel", (t, m.name, svc))
        elif kind == "hcancel":
            t, hname, svc = payload
            if id(t) in finished or t.hedge_member is None \
                    or t.hedge_member.name != hname:
                continue
            refund(hname, svc)
        elif kind == "cancel":
            t, name, gen, svc = payload
            if id(t) in finished or seq.get(id(t)) != gen \
                    or t.member is None or t.member.name != name:
                continue
            finished.add(id(t))
            deadline_cancels += 1
            refund(name, svc)
            if t.hedge_member is not None:
                refund(t.hedge_member.name, svc_time(
                    t.hedge_member.name, t.tokens, t._hedge_at))
            # fail() sees the elapsed deadline and drops with
            # dropped_reason="deadline" (the shell's 504)
            for nt in router.fail(t, requeue=True):
                on_dispatch(nt)
        elif kind == "fail":
            t, name, gen = payload
            if id(t) in finished or seq.get(id(t)) != gen \
                    or t.member is None or t.member.name != name:
                continue
            for nt in router.fail(t, requeue=True):
                on_dispatch(nt)
            if t.member is None and t.dropped_reason is not None:
                finished.add(id(t))

    # goodput per band over the brownout-window arrivals: completed
    # within the deadline / arrived, resilience on or off
    goodput = {}
    for band in BAND_RANK:
        window = [tid for tid, (ta, b, _tok) in arrivals.items()
                  if b == band and bo_start <= ta < bo_end]
        hits = sum(1 for tid in window
                   if tid in done_at
                   and done_at[tid] - arrivals[tid][0] <= cfg["deadline_s"])
        total = sum(1 for t_arr, _tok, b in trace
                    if b == band and bo_start <= t_arr < bo_end)
        goodput[band] = round(hits / total, 4) if total else 1.0
    fingerprint = hashlib.sha256(json.dumps(
        decisions, sort_keys=True).encode()).hexdigest()
    breaker_kinds = [d for d in decisions if d["kind"] == "breaker"]
    completed = len(done_at)
    return {
        "arm": arm,
        "requests": len(trace),
        "completed": completed,
        "rejected": {b: per_band[b]["rejected"] for b in per_band},
        "arrivals": {b: per_band[b]["arrivals"] for b in per_band},
        "brownout_goodput": goodput,
        "hedge_wins": hedge_wins,
        "deadline_cancels": deadline_cancels,
        "sheds": {b: sum(1 for d in decisions
                         if d["kind"] == "shed" and d.get("band") == b)
                  for b in BAND_RANK},
        "deadline_drops": sum(
            1 for d in decisions if d["kind"] == "deadline"),
        "breaker_opened": any(d.get("state") == "open"
                              for d in breaker_kinds),
        "breaker_reclosed": any(d.get("state") == "closed"
                                for d in breaker_kinds),
        "decisions": len(decisions),
        "decision_fingerprint": fingerprint,
        "virtual_makespan_s": round(clock.t, 2),
    }


def run_kv_cancel_drill(seed: int) -> dict:
    """Host-only proof of the zero-leak contract: drive a PageAllocator
    through admit / append / mid-flight frees (the deadline-cancel and
    hedge-loser paths) and assert the refcount invariant plus a fully
    recovered freelist. No jax involved — this is the allocator the
    slot decoder's ``_cancel_expired`` calls ``free()`` on."""
    from kubeflow_tpu.runtime.kvcache import PageAllocator

    rng = random.Random(seed)
    page, slots = 8, 8
    # prefix_cache off: the LRU prefix index legitimately retains
    # prompt pages across frees, which is reuse — not the leak this
    # drill exists to catch on the cancel path
    alloc = PageAllocator(num_pages=64, page_size=page, slots=slots,
                          max_pages_per_slot=12, prefix_cache=False)
    live: dict[int, tuple[int, int]] = {}   # slot -> (position, total)
    frees = admits = 0
    for step in range(400):
        op = rng.random()
        free_slots = [s for s in range(slots) if s not in live]
        if op < 0.5 and free_slots:
            row = [rng.randrange(1, 50) for _ in range(32)]
            total = 32 + rng.randrange(8, 33)
            if alloc.can_admit(row, 0, total):
                s = free_slots[0]
                alloc.admit(s, row, 0, total)
                live[s] = (32, total)
                admits += 1
        elif op < 0.8 and live:
            s = sorted(live)[rng.randrange(len(live))]
            pos, total = live[s]
            pos = min(pos + rng.randrange(1, 9), total)
            live[s] = (pos, total)
            alloc.append(s, pos)
        elif live:
            # the cancel path: a deadline or a lost hedge frees the
            # slot MID-GENERATION, pages and all
            s = sorted(live)[rng.randrange(len(live))]
            alloc.free(s)
            live.pop(s)
            frees += 1
        alloc.check()
    for s in list(live):
        alloc.free(s)
    alloc.check()
    clean = alloc.free_pages == alloc.num_pages - 1  # page 0 is trash
    return {"admits": admits, "mid_flight_frees": frees,
            "pages_recovered": clean, "invariant_clean": True}


def run_resilience_bench(cfg: dict) -> dict:
    rng = random.Random(cfg["seed"])
    trace = build_res_trace(cfg, rng)
    resilient = run_resilience_arm("resilient", cfg, trace)
    control = run_resilience_arm("control", cfg, trace)
    replay = run_resilience_arm("resilient", cfg, trace)
    return {
        "config": dict(cfg),
        "resilient": resilient,
        "control": control,
        "kv_drill": run_kv_cancel_drill(cfg["seed"]),
        "comparison": {
            "critical_goodput_resilient":
                resilient["brownout_goodput"]["critical"],
            "critical_goodput_control":
                control["brownout_goodput"]["critical"],
            "hedge_wins": resilient["hedge_wins"],
            "critical_sheds": resilient["sheds"].get("critical", 0)
            if resilient["sheds"] else 0,
            "breaker_round_trip": resilient["breaker_opened"]
            and resilient["breaker_reclosed"],
            "replay_identical":
                resilient["decision_fingerprint"]
                == replay["decision_fingerprint"]
                and resilient["completed"] == replay["completed"],
        },
    }


def check_resilience_bench(banked_path: str) -> int:
    """CI ratchet over BENCH_SERVE_r03: rerun the banked config; fail
    when the resilience layer stops earning its keep — critical-band
    goodput through the brownout below 90% (or the control arm NOT
    degrading, which means the drill lost its teeth), zero hedge
    rescues, a critical-band shed, a broken breaker round-trip, a
    decision-fingerprint change, or a KV page leak in the cancel
    drill."""
    with open(banked_path) as fh:
        banked = json.load(fh)
    section = banked.get("resilience")
    if not section:
        print(f"check: no resilience section in {banked_path}",
              file=sys.stderr)
        return 2
    now = run_resilience_bench(dict(section["config"]))
    ok = True
    cmp_ = now["comparison"]
    if cmp_["critical_goodput_resilient"] < 0.9:
        print(f"check: resilience regression — critical goodput "
              f"{cmp_['critical_goodput_resilient']} < 0.9 through the "
              "brownout", file=sys.stderr)
        ok = False
    if cmp_["critical_goodput_control"] >= 0.7:
        print(f"check: drill regression — the control arm no longer "
              f"degrades ({cmp_['critical_goodput_control']} >= 0.7); "
              "the brownout drill lost its teeth", file=sys.stderr)
        ok = False
    if cmp_["hedge_wins"] < 1:
        print("check: resilience regression — zero hedge rescues",
              file=sys.stderr)
        ok = False
    if cmp_["critical_sheds"] != 0:
        print(f"check: resilience regression — "
              f"{cmp_['critical_sheds']} critical-band requests shed",
              file=sys.stderr)
        ok = False
    if not cmp_["breaker_round_trip"]:
        print("check: resilience regression — breaker never completed "
              "open -> half-open -> closed", file=sys.stderr)
        ok = False
    if not cmp_["replay_identical"]:
        print("check: determinism regression — same-seed replay "
              "diverged", file=sys.stderr)
        ok = False
    if now["resilient"]["decision_fingerprint"] \
            != section["resilient"]["decision_fingerprint"]:
        print("check: decision fingerprint diverged from the banked "
              "run", file=sys.stderr)
        ok = False
    drill = now["kv_drill"]
    if not (drill["pages_recovered"] and drill["invariant_clean"]
            and drill["mid_flight_frees"] > 0):
        print("check: KV cancel drill regression — pages leaked or no "
              "mid-flight frees exercised", file=sys.stderr)
        ok = False
    print(json.dumps({"check": "ok" if ok else "REGRESSED",
                      "comparison": cmp_}, indent=2))
    return 0 if ok else 1


def resilience_main(args) -> int:
    if args.check:
        return check_resilience_bench(args.resilience_out)
    cfg = dict(RES_CONFIG)
    cfg["seed"] = args.seed
    result = {"bench": "serve_bench", "round": "r03",
              "resilience": run_resilience_bench(cfg)}
    with open(args.resilience_out, "w") as fh:
        json.dump(result, fh, indent=2, sort_keys=True)
        fh.write("\n")
    print(json.dumps({"out": args.resilience_out,
                      "comparison": result["resilience"]["comparison"],
                      "resilient_goodput":
                          result["resilience"]["resilient"]
                          ["brownout_goodput"],
                      "control_goodput":
                          result["resilience"]["control"]
                          ["brownout_goodput"]}, indent=2))
    return 0


def main() -> int:
    p = argparse.ArgumentParser("serve_bench")
    p.add_argument("--model", default="gpt-350m")
    p.add_argument("--vocab-size", type=int, default=32000)
    p.add_argument("--prompt-len", type=int, default=512)
    p.add_argument("--max-new-tokens", type=int, default=64)
    p.add_argument("--requests", type=int, default=64)
    p.add_argument("--concurrency", type=int, default=16)
    p.add_argument("--slots", type=int, default=16)
    p.add_argument("--window-ms", type=float, default=5.0,
                   help="micro-batching window for the micro mode")
    p.add_argument("--param-dtype", default="bfloat16",
                   choices=["bfloat16", "float32", "int8", "int4", ""])
    p.add_argument("--kv-pages", type=int, default=0,
                   help="paged KV cache pool size for the continuous "
                        "mode (0 = dense per-slot cache)")
    p.add_argument("--kv-page-size", type=int, default=0)
    p.add_argument("--attention-window", type=int, default=0,
                   help="sliding-window width for the served model "
                        "(0 = full causal)")
    p.add_argument("--rolling-kv-cache", action="store_true",
                   help="bound the KV cache to the window (O(window) "
                        "memory + per-step cache stream)")
    p.add_argument("--kv-cache-dtype", default="",
                   choices=["", "auto", "int8"],
                   help="int8 quantizes the decode KV cache (per-token-"
                        "head scales) — the long-context decode lever")
    p.add_argument("--mesh", default="",
                   help="axis=n[,axis=n...] to shard the served params")
    p.add_argument("--modes", default="micro,continuous")
    p.add_argument("--router", action="store_true",
                   help="run the deterministic JAXService router+"
                        "autoscaler benchmark and bank BENCH_SERVE_r01")
    p.add_argument("--decode", action="store_true",
                   help="run the deterministic per-replica decode "
                        "benchmark (dense-vs-paged KV cache, prefix "
                        "reuse, speculative lockstep) and bank "
                        "BENCH_SERVE_r02")
    p.add_argument("--resilience", action="store_true",
                   help="run the deterministic request-resilience "
                        "benchmark (brownout + overload + flap drills, "
                        "deadline/hedge/breaker/band-shed layer vs the "
                        "legacy router) and bank BENCH_SERVE_r03")
    p.add_argument("--check", action="store_true",
                   help="CI gate: rerun every banked config and fail on "
                        "drops/divergence/counter regression (with "
                        "--router or --decode: gate only that bank)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default=ROUTER_OUT)
    p.add_argument("--decode-out", default=DECODE_OUT)
    p.add_argument("--resilience-out", default=RESILIENCE_OUT)
    args = p.parse_args()
    if args.check:
        if args.decode:
            return check_decode_bench(args.decode_out)
        if args.router:
            return check_router_bench(args.out)
        if args.resilience:
            return check_resilience_bench(args.resilience_out)
        rc = 0
        if os.path.exists(args.out):
            rc = max(rc, check_router_bench(args.out))
        if os.path.exists(args.decode_out):
            rc = max(rc, check_decode_bench(args.decode_out))
        if os.path.exists(args.resilience_out):
            rc = max(rc, check_resilience_bench(args.resilience_out))
        return rc
    if args.decode:
        return decode_main(args)
    if args.router:
        return router_main(args)
    if args.resilience:
        return resilience_main(args)
    if args.mesh:
        args.mesh = {k: int(v) for k, v in
                     (kv.split("=", 1) for kv in args.mesh.split(","))}
    for mode in args.modes.split(","):
        print(json.dumps(run_mode(mode.strip(), args)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
