"""Continuous batching for LM serving: slot-based lockstep decode.

The MicroBatcher coalesces concurrent requests into one `generate()`
call — but then the whole group decodes together: a request arriving one
step later waits for the ENTIRE previous generation, and every request
in a group pays the longest member's latency. Continuous batching is the
transformer-serving answer: a fixed pool of S slots decodes in lockstep,
requests JOIN at any step boundary (prefilled off to the side, then
scattered into a free slot's cache rows) and LEAVE independently when
their token budget is done. Throughput stays at batched-decode levels
while p50 latency drops to ~arrival + own-length.

TPU-shaped by construction: the decode step is ONE compiled program of
static shape [S, 1] forever — no per-arrival recompiles — with per-slot
positions (models/transformer.py vector `decode_index`), one-hot cache
scatters instead of dynamic shapes, and masked sampling for idle slots.

Three per-replica speed levers compose on top of the slot machinery
(docs/serving.md "Per-replica decode path"):

- **Paged KV cache** (model built with cfg.kv_pages/kv_page_size): the
  dense [S, P+N] cache becomes a fixed page pool shared across slots;
  admission is gated on PAGE availability (runtime/kvcache.py), so a
  request holds only the pages its actual prompt + its own token
  budget needs and short requests stop reserving P+N positions of HBM
  for their whole life.
- **Prefix reuse**: page-granular chained prompt hashes map to
  read-only shared pages (copy-on-write on divergence), so a fleet of
  requests sharing a system prompt skips most prefill compute.
- **Speculative lockstep decode** (draft_model): greedy slots draft k
  tokens and the target verifies every slot's whole chunk in ONE
  [S, k+1] forward; output stays token-for-token equal to plain greedy
  decode.

Two rules over what is there, each logged once at the build with its
reason, and no option (`window_pages_for`, `_fresh_prefill_rule`):

- **Pages kept by layer kind** (runtime/kvcache.py): where the prefix
  cache is off, a layer with a sliding window keeps its pages in a pool
  and a table of its own, and the allocator takes back, in the loop's
  `pages` phase, the pages that have fallen behind the slot's window. With
  the prefix cache on, a prompt page may be handed to a later request
  whole, so every layer holds its pages for the slot's life, as ever.
- **The prompt's attention through the flash kernel**: a causal
  one-token model whose attention is the flash kernel's, at rungs the
  kernel tiles, attends over the rung's own keys and writes the pages
  for the ticks; no score exists beyond a tile. Without a prefix cache
  that is the whole of it: every real token of a prompt is in the one
  rung. With it a rung may start behind a hit, and where one does
  (`pad_len` below the rung's first position, which the program sees)
  the keys are the slot's pages before the rung and then the rung's own,
  in the same program. Every other decoder (a block model, a draft's
  verify chunk, another backend's attention, a rung that is no multiple
  of 128) gathers the slot's pages and masks: the reference.

What a round does is its **kind of step** (serving/steps.py): one token
a slot; a speculative chunk; or, for a model that generates by diffusion
over blocks (cfg.gen_block > 0), one pass over every slot's block. The
kind is read from the models' configs; the one scheduler loop, admission
and the page allocator are the same whatever it is.

Single-host scheduler; the decode/prefill programs themselves run under
whatever mesh the variables are sharded over.
"""

from __future__ import annotations

import collections
import contextlib
import queue
import threading
import time
from typing import Any

from kubeflow_tpu.obs import trace as obs_trace
from kubeflow_tpu.runtime.metrics import REGISTRY as METRICS_REGISTRY
# the ONE spelling of the 504 across the serving plane (router.py is
# jax-free, so this import costs nothing)
from kubeflow_tpu.serving.router import DeadlineExceeded

log = __import__("logging").getLogger("kubeflow_tpu.serving.continuous")


def _prom(name, kind, doc, **kw):
    from kubeflow_tpu.runtime.metrics import prom_metric

    return prom_metric(name, kind, doc, **kw)


def window_pages_for(cfg, slots: int, prompt_len: int, max_new_tokens: int,
                     *, prefix_cache: bool, draft: bool = False) -> int:
    """The window kind's pool (`kv_window_pages` of the model's config),
    by the first rule of the module docstring: 0 (every layer holds its
    pages) unless the prefix cache is off, the model is served a token a
    step without a draft, and some layer has a window shorter than a
    slot's longest sequence. Else every slot's ring and the trash page:
    the window, what a fused round writes ahead, a page for where the
    window begins; or a whole sequence where the prompt's attention
    reads the pages (the gather) and holds them until the first tick."""
    from kubeflow_tpu.runtime.kvcache import pages_for
    from kubeflow_tpu.serving.steps import TokenStep

    ps = cfg.kv_page_size
    total = prompt_len + max_new_tokens
    window = max((s.window for s in cfg.layers()), default=0)
    if not ps:
        why = "no paged cache"
    elif not window:
        why = "no layer has a window"
    elif prefix_cache:
        why = "the prefix cache is on: a prompt page is handed on whole"
    elif cfg.gen_block or draft:
        why = "a block model or a draft"
    elif window >= total:
        why = f"the window {window} covers a slot's {total} positions"
    else:
        why = ""
    if why:
        log.info("pages by layer kind: every layer holds its pages (%s)", why)
        return 0
    ring = pages_for(window + TokenStep.FUSE, ps) + 1
    if not _fresh_prefill_rule(cfg, prompt_len, prefix_cache=False,
                               draft=False)[0]:
        ring = max(ring, pages_for(total, ps))
    log.info("pages by layer kind: window layers keep %d pages a slot of "
             "%d positions a page and release behind the window of %d "
             "(prefix cache off)", ring, ps, window)
    return slots * ring + 1


def _fresh_prefill_rule(cfg, prompt_len: int, *, prefix_cache: bool,
                        draft: bool) -> tuple:
    """(whether a paged prefill attends through the flash kernel, why):
    the second rule of the module docstring."""
    from kubeflow_tpu.runtime.kvcache import prefill_ladder

    ladder = prefill_ladder(prompt_len, cfg.kv_page_size)
    if cfg.gen_block or draft:
        return False, "a block model or a draft: no causal one-token prefill"
    if cfg.attention_impl != "flash":
        return False, f"attention impl is {cfg.attention_impl!r}, not flash"
    if any(n % 128 for n in ladder):
        return False, f"a rung of {list(ladder)} is no multiple of 128"
    if prefix_cache and _has_latent(cfg):
        return False, ("latent attention with the prefix cache on: a hit's "
                       "pages hold latents, which the absorbed form reads")
    if prefix_cache:
        return True, ("causal, flash attention; the prefix cache is on: the "
                      "pages before a rung where a hit lies there")
    return True, "prefix cache off, causal, flash attention"


def _flash_block_pairs(cfg, rung: int, start: int, pad: int,
                       behind: bool) -> tuple:
    """(run, reachable): the flash forward kernel's block pairs in one
    admission's rung (`ops/flash_attention.py:block_pairs`), summed over
    the model's layers and heads, at the keys and the first real key
    the rung's program hands it (`Attention._decode_paged(fresh=True)`,
    `LatentAttention._decode_paged`): behind a hit the keys are the
    prompt's positions from 0 and the padding ends at `pad`, else the
    rung's own from `start`. The tiles as `local_attention` asks."""
    from kubeflow_tpu.ops.attention import flash_blocks
    from kubeflow_tpu.ops.flash_attention import block_pairs

    bq, bk = flash_blocks(cfg.flash_block_q, cfg.flash_block_k)
    lk, kv_start = ((start + rung, pad) if behind
                    else (rung, min(max(pad - start, 0), rung)))
    run = reachable = 0
    for spec in cfg.layers():
        r, a = block_pairs(rung, lk, bq, bk, window=spec.window,
                           kv_start=kv_start)
        run, reachable = run + r, reachable + a
    return run * cfg.n_heads, reachable * cfg.n_heads


def _has_latent(cfg) -> bool:
    """Whether some layer of the model attends over latents
    (models/transformer.py `LatentAttention`)."""
    return hasattr(cfg, "layers") and any(s.latent for s in cfg.layers())


class _DecodeMeter:
    """Per-replica decode-path signals, exported to BOTH sinks (the
    PR 4 convention): the MetricsRegistry text the control plane
    scrapes and prometheus_client for dashboards. Catalogued in
    docs/observability.md."""

    def __init__(self, model: str, registry=METRICS_REGISTRY):
        self.model = model
        self.registry = registry

    def pages(self, free: int, used: int) -> None:
        import prometheus_client as prom

        self.registry.gauge(
            "serving_kv_pages_free", free,
            help_="KV-cache pages available for admission", model=self.model)
        self.registry.gauge(
            "serving_kv_pages_used", used,
            help_="KV-cache pages held by live or cached-prefix sequences",
            model=self.model)
        _prom("serving_kv_pages_free", prom.Gauge,
              "KV-cache pages available for admission",
              labelnames=("model",)).labels(self.model).set(free)
        _prom("serving_kv_pages_used", prom.Gauge,
              "KV-cache pages held by live or cached-prefix sequences",
              labelnames=("model",)).labels(self.model).set(used)

    def prefix_hits(self, pages: int) -> None:
        # inc-by-zero on a miss keeps the series visible from the
        # first admission
        import prometheus_client as prom

        self.registry.counter_inc(
            "serving_prefix_cache_hits_total", by=float(pages),
            help_="prompt pages served from the shared prefix cache "
                  "(each hit skips page_size positions of prefill)",
            model=self.model)
        _prom("serving_prefix_cache_hits_total", prom.Counter,
              "prompt pages served from the shared prefix cache",
              labelnames=("model",)).labels(self.model).inc(pages)

    def prefill_tokens(self, n: int) -> None:
        if n <= 0:
            return
        import prometheus_client as prom

        self.registry.counter_inc(
            "serving_prefill_tokens_total", by=float(n),
            help_="prompt positions actually computed by prefill "
                  "(prefix reuse drives this below tokens submitted)",
            model=self.model)
        _prom("serving_prefill_tokens_total", prom.Counter,
              "prompt positions actually computed by prefill",
              labelnames=("model",)).labels(self.model).inc(n)

    def request_waits(self, queue_wait_s: float, first_token_s: float) -> None:
        """Once a request, from the thread that submitted it."""
        import prometheus_client as prom

        self.registry.histogram(
            "serving_queue_wait_seconds", queue_wait_s,
            help_="submit to admission (the request's prefill dispatched)",
            model=self.model)
        _prom("serving_queue_wait_seconds", prom.Histogram,
              "submit to admission (the request's prefill dispatched)",
              labelnames=("model",)).labels(self.model).observe(queue_wait_s)
        self.registry.histogram(
            "serving_first_token_seconds", first_token_s,
            help_="submit to the first read-back after admission: the "
                  "first moment a token could have been streamed",
            model=self.model)
        _prom("serving_first_token_seconds", prom.Histogram,
              "submit to the first read-back after admission",
              labelnames=("model",)).labels(self.model).observe(first_token_s)

    def spec_round(self, slots: int, accepted: int) -> None:
        import prometheus_client as prom

        self.registry.counter_inc(
            "serving_spec_rounds_total", by=float(slots),
            help_="speculative verify forwards, one per active slot "
                  "per round (tokens emitted / rounds = tokens per "
                  "target forward)", model=self.model)
        _prom("serving_spec_rounds_total", prom.Counter,
              "speculative verify forwards (slot-rounds)",
              labelnames=("model",)).labels(self.model).inc(slots)
        # inc-by-zero keeps the series visible: a disagreeing draft
        # shows an explicit 0, not a missing metric
        self.registry.counter_inc(
            "serving_spec_tokens_accepted_total", by=float(accepted),
            help_="draft tokens accepted by the target verify",
            model=self.model)
        _prom("serving_spec_tokens_accepted_total", prom.Counter,
              "draft tokens accepted by the target verify",
              labelnames=("model",)).labels(self.model).inc(accepted)


# The scheduler loop's host phases: `kftpu.sched.<phase>` in the
# profiler's trace, `phase_s.<phase>` in stats(). The loop thread is
# always in one of them.
SCHED_PHASES = ("admit", "prefill", "pages", "tick", "readback",
                "complete", "idle")

# A request's stamps, and the waits summed from them, are observability
# payload on the spans' clock; no decision reads them (deadlines run on
# the injectable self.clock).
_stamp = time.perf_counter


class _Request:
    """One submitted request: what the loop needs to serve it, and its
    four stamps on perf_counter. The submitting thread makes it and
    stamps `t_submit`; the loop thread writes the other numbers (plain
    floats and ints, never a span) and fires `ev` last, after which the
    submitting thread reads them."""

    __slots__ = ("prompt", "pad", "req", "ev", "result", "attrs", "deadline",
                 "t_submit", "t_admit", "t_first", "t_done", "slot",
                 "prefill_tokens", "released")

    def __init__(self, prompt, pad: int, req: int, deadline):
        self.prompt, self.pad, self.req = prompt, pad, req
        self.deadline = deadline
        self.ev = threading.Event()
        self.result, self.attrs = None, {}
        self.t_submit = _stamp()
        self.t_admit = self.t_first = self.t_done = 0.0
        self.slot = -1
        self.prefill_tokens = 0
        self.released = 0      # window-kind pages given back while it ran

    def finish(self, result, attrs=None) -> None:
        """The one exit: the answer as its step gives it (or the error),
        what the step adds to the request's span (a block model's
        `blocks` and `passes`), the done stamp, then wake the submitter."""
        self.result, self.attrs = result, attrs or {}
        self.t_done = _stamp()
        self.ev.set()

    def outcome(self) -> str:
        if isinstance(self.result, Exception):
            return ("canceled" if isinstance(self.result, DeadlineExceeded)
                    else "failed")
        return "ok"


def _of_step(name: str) -> property:
    """What lives in the decoder's step under the decoder's own name:
    the benchmark's harness blocks on `state` and frees it, tests assign
    it, and planted faults wrap the two tick programs."""
    return property(lambda self: getattr(self.step, name),
                    lambda self, value: setattr(self.step, name, value))


class SlotDecoder:
    """S-slot continuous decoder over a KV-cache LM.

    Host API: ``submit(tokens, max_new=None) -> list[int]`` blocks the
    calling thread until that request's continuation is done; many
    threads may submit concurrently. A background loop admits pending
    requests into free slots at step boundaries and advances all
    active slots one step per round: the loop is the decoder's, the
    device state and the round's programs are `self.step`'s.

    Modes (the module docstring says what each is for):

    - dense (default) or paged (the model built with cfg.kv_pages): the
      slot cache; paged, prefill computes the shortest rung of a fixed
      ladder that covers the real tokens no prefix hit covers, never at
      a length that was not compiled when the decoder was built.
    - speculative (draft_model given): greedy only, dense or paged.
    - block (the model's cfg.gen_block > 0; paged, greedy, no draft):
      `submit` returns ``{"tokens": [...], "fixed_at": [...]}``, the
      step of its block at which each token was fixed.
    """

    def __init__(self, model, variables, *, slots: int = 8,
                 prompt_len: int = 128, max_new_tokens: int = 32,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 mesh=None, prefix_cache: bool = True,
                 draft_model=None, draft_variables=None, draft_k: int = 4,
                 metrics_name: str | None = None, clock=None):
        import jax

        from kubeflow_tpu.runtime.generate import check_decode_geometry
        from kubeflow_tpu.runtime.kvcache import (
            PageAllocator, pages_for, prefill_ladder)
        from kubeflow_tpu.serving import steps

        self.model = model
        self.variables = variables
        self.S = slots
        self.P = prompt_len
        self.N = max_new_tokens
        self.mesh = mesh
        self._ctx = mesh or contextlib.nullcontext()   # around every program
        # deadline clock (injectable for deterministic cancel tests);
        # submit deadlines are ABSOLUTE values on this clock
        self.clock = clock if clock is not None else time.monotonic
        self._jnp = jax.numpy
        self.spec = draft_model is not None
        self.paged = bool(getattr(model.cfg, "kv_pages", 0))
        # a block model's step (cfg.gen_block > 0): B positions a slot
        self.B = B = int(getattr(model.cfg, "gen_block", 0) or 0)
        if B:
            steps_ = getattr(model.cfg, "gen_steps", 0) or B
            if B % steps_:
                raise ValueError(f"gen_block {B} is no multiple of "
                                 f"gen_steps {steps_}")
            if not self.paged:
                raise ValueError(
                    "a block model (gen_block > 0) is served through the "
                    "paged KV cache only: its block-causal mask lives in "
                    "the paged decode path (build it with kv_pages and "
                    "kv_page_size)")
            if self.spec:
                raise ValueError(
                    "a block model (gen_block > 0) takes no draft_model: "
                    "speculative verify is a causal chunk, a block step "
                    "is not")
            if temperature != 0.0:
                raise ValueError(
                    "a block model (gen_block > 0) is decoded greedily "
                    "(temperature must be 0): sampling the fixed tokens "
                    "is not there yet")
            if getattr(model.cfg, "attention_window", 0):
                raise ValueError(
                    "a block model (gen_block > 0) with attention_window: "
                    "the queries of a block would see different ranges")
        elif self.spec and getattr(draft_model.cfg, "gen_block", 0):
            raise ValueError("a block model (gen_block > 0) cannot draft "
                             "for a one-token model")
        if _has_latent(model.cfg) and (B or self.spec or not self.paged
                                       or mesh is not None):
            raise ValueError(
                "latent attention is served a token a step through the "
                "paged KV cache on one device (build the model with "
                "kv_pages and kv_page_size): no block model, no "
                "draft_model, no dense slot cache, no mesh")
        # positions a slot may touch past its last token: the verify
        # chunk's overhang, or the rest of the answer's last block
        overhang = B or (draft_k if self.spec else 0)
        check_decode_geometry(model, prompt_len, max_new_tokens + overhang)
        if self.spec:
            if temperature != 0.0:
                raise ValueError("speculative lockstep decode is "
                                 "greedy-only (temperature must be 0)")
            if draft_k < 1:
                raise ValueError("draft_k must be >= 1")
            for name, m in (("target", model), ("draft", draft_model)):
                if getattr(m.cfg, "rolling_kv_cache", False):
                    raise ValueError(
                        f"speculative decoding requires the full or "
                        f"paged KV cache; {name} has rolling_kv_cache")
            if getattr(draft_model.cfg, "kv_pages", 0):
                raise ValueError("the draft model keeps a dense cache "
                                 "(build it without kv_pages)")
            check_decode_geometry(draft_model, prompt_len,
                                  max_new_tokens + draft_k)
        self._cfg = model.cfg
        if self.paged:
            cfg = model.cfg
            self.page_size = cfg.kv_page_size
            # a slot's worst-case sequence: prompt + its budget + the
            # overhang past the last token
            self._mp = pages_for(prompt_len + max_new_tokens + overhang,
                                 self.page_size)
            usable = cfg.kv_pages - 1  # page 0 is trash
            if usable < self._mp:
                raise ValueError(
                    f"kv_pages={cfg.kv_pages} cannot hold even one "
                    f"sequence ({self._mp} pages of {self.page_size} "
                    "needed, page 0 is trash)")
            wpages = getattr(cfg, "kv_window_pages", 0)
            if wpages and (B or self.spec):
                raise ValueError(
                    "kv_window_pages (pages kept by layer kind) is for a "
                    "one-token model without a draft")
            self.alloc = PageAllocator(
                cfg.kv_pages, self.page_size, slots, self._mp,
                prefix_cache=prefix_cache,
                window=(max(s.window for s in cfg.layers()) if wpages else 0),
                window_pages=wpages, window_ahead=steps.TokenStep.FUSE)
            # the suffix lengths prefill runs at (runtime/kvcache.py)
            self._ladder = prefill_ladder(prompt_len, self.page_size)
            self._fresh, why = _fresh_prefill_rule(
                cfg, prompt_len, prefix_cache=prefix_cache, draft=self.spec)
            log.info("paged prefill: %s (%s)",
                     "the rung's keys through the flash kernel"
                     if self._fresh else "gathers the slot's pages", why)
        else:
            self.alloc = None
            self._fresh = False
        self.meter = _DecodeMeter(metrics_name) if metrics_name else None
        self._pages_published = -1   # free pages at the last publish

        # host-truth counters (stats(); the meter mirrors into sinks)
        self._counters = {
            "admitted": 0, "completed": 0, "peak_active": 0,
            # per admission: the positions prefill computed, the prompt's
            # real tokens (prompt_len less the padding), and prompt_len
            "prefill_tokens_computed": 0, "prompt_tokens_real": 0,
            "prompt_tokens_submitted": 0,
            # admissions whose prompt attended through the flash kernel
            # (`_fresh_prefill_rule`: all of a decoder's or none), and
            # those of them that read a prefix hit's pages before the rung
            "prefill_flash": 0, "prefill_behind_hit": 0,
            # of their rungs' flash kernels: the block pairs that ran,
            # and those the causal / window rule alone reaches (the
            # difference lies wholly in the left padding)
            "prefill_flash_pairs_run": 0, "prefill_flash_pairs_reachable": 0,
            "spec_rounds": 0, "spec_tokens_emitted": 0,
            "spec_tokens_accepted": 0, "spec_drafted": 0,
            "deadline_canceled": 0,
            # passes of the loop that dispatched a step program, those of
            # them dispatched while the round before was still unread
            # (`_loop`), the ticks they dispatched (a fused round counts
            # FUSE; a block model's are its passes), and the tokens of
            # answers their read-backs found fixed (a canceled request's
            # stay counted)
            "rounds": 0, "rounds_ahead": 0, "ticks": 0, "tokens_decoded": 0,
            # per request: submit to admission, submit to the first
            # read-back after it (seconds, summed; `admitted` and
            # `first_tokens` are the counts)
            "queue_wait_s_sum": 0.0, "first_token_s_sum": 0.0,
            "first_tokens": 0,
        }
        if self.paged:
            # how much of the page table the one-token step's ticks walk
            # (steps.py `TokenStep._counts`)
            self._counters.update(kv_pages_walked=0, kv_pages_tabled=0)
            if self.alloc.window:
                # summed a round over the slots that hold a request: the
                # window-kind pages held, and the pages their contexts
                # cover (what would be held if nothing were released)
                self._counters.update(kv_window_pages_held_sum=0,
                                      kv_window_pages_covered_sum=0,
                                      kv_pages_walked_window=0)
        if B:
            # counted on the device and read back with `remaining`
            self._counters.update(dict.fromkeys(steps.BLOCK_COUNTERS, 0))
        # a round by what it held (`_round_class`): how many, and their
        # wall seconds: those of the passes from the end of the last
        # read-back to the end of its own `complete` (`_read_round`)
        self._round_keys = {
            cls: (f"rounds.{cls}", f"round_s.{cls}") for cls in (
                "plain", "fused",
                *(f"rung{n}" for n in (self._ladder if self.paged else ())),
                "other")}
        for n_key, s_key in self._round_keys.values():
            self._counters.update({n_key: 0, s_key: 0.0})
        # the loop's host phases: phase_s.* in stats(), and kftpu.sched.*
        # annotations in the profiler's trace
        self._phase = obs_trace.PhaseClock(
            "sched", SCHED_PHASES, self._counters)
        # the suffixes admitted in the loop's current pass
        self._pass_rungs: list = []
        # while a profiler session is open: (when the loop saw it open,
        # the counters as they stood)
        self._profiled: tuple | None = None
        # the top of the first pass whose seconds no round has taken yet
        self._t_charge: float | None = None

        # (jit arguments, never closure captures: steps.py says why)
        self._params = {"params": variables["params"]}
        geometry = (slots, prompt_len, max_new_tokens,
                    self._mp if self.paged else 0)
        # the kind of step, from what the models say of themselves
        if B:
            self.step = steps.BlockStep(model, self._params, *geometry,
                                        seed=seed)
        elif self.spec:
            self.step = steps.SpecStep(
                model, self._params, draft_model,
                {"params": draft_variables["params"]}, draft_k, *geometry)
        else:
            self.step = steps.TokenStep(
                model, self._params, *geometry, temperature=temperature,
                top_k=top_k, seed=seed, fresh_prefill=self._fresh,
                prefix_hits=prefix_cache)
            self._counters.update(dict.fromkeys(self.step.counted, 0))
            if self.step.latent_row_bytes:
                # of `ticks`, those whose latent attention was the Pallas
                # kernel's (its rule's choice: all of a decoder's or none)
                self._counters["attn_latent_kernel_ticks"] = 0
        # what the rungs of a model that holds a share of its experts
        # count (a draft's admission is its own program, and counts none)
        self._counters.update(dict.fromkeys(
            getattr(self.step, "rung_counted", ()), 0))
        if self.paged:
            t0 = _stamp()
            self._prefill_at = self.step.prefill_programs(self._ladder, mesh)
            log.info("paged prefill ready at %s positions in %.2f s",
                     list(self._ladder), _stamp() - t0)
        # the suffix lengths dispatched since the build
        self._prefill_lengths: set = set()
        # bytes the decode cache holds on-device (shape truth: the
        # density claims in tools/serve_bench.py --decode assert on it)
        self._cache_bytes = sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(self.step.state[0]))
        self._free: list[int] = list(range(self.S))
        self._pending: "queue.Queue[tuple]" = queue.Queue()
        self._carry: tuple | None = None  # page-gated head of the queue
        # guards the _stop flag vs submit(): an enqueue must strictly
        # precede the shutdown drain or the caller waits forever
        self._lock = threading.Lock()
        self._active = 0  # host-side mirror (device state is donated)
        self._wake = threading.Event()
        self._stop = False
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="slot-decoder")
        self._thread.start()

    state, _step, _step_fused = map(
        _of_step, ("state", "_step", "_step_fused"))

    # -- host API ----------------------------------------------------------

    def submit(self, tokens: list[int], max_new: int | None = None,
               deadline: float | None = None) -> "list[int] | dict":
        """Block until the continuation for this prompt is decoded: the
        list of its tokens, or for a block model ``{"tokens": [...],
        "fixed_at": [...]}``.
        `max_new` caps THIS request's budget below the decoder-wide
        max_new_tokens (a paged decoder then reserves fewer pages).
        `deadline` is an ABSOLUTE time on self.clock: past it the
        request is canceled wherever it is (queued, carried, or
        mid-decode — its slot and KV pages return to the pool) and the
        caller sees DeadlineExceeded."""
        row = [int(t) for t in tokens][-self.P:]
        pad = self.P - len(row)
        return self.submit_padded([0] * pad + row, pad, max_new, deadline)

    def submit_padded(self, padded_row, pad: int,
                      max_new: int | None = None,
                      deadline: float | None = None) -> "list[int] | dict":
        """Pre-padded variant for callers that already align rows."""
        import numpy as np

        req = self.N if max_new is None else int(max_new)
        if not 1 <= req <= self.N:
            raise ValueError(f"max_new must be in 1..{self.N}, got {req}")
        r = _Request(np.asarray(padded_row, dtype=np.int32), pad, req,
                     deadline)
        with self._lock:  # enqueue-before-drain or fail fast, atomically
            if self._stop:
                raise RuntimeError("decoder shut down")
            self._pending.put(r)
        self._wake.set()
        if deadline is None:
            # the loop fires ev on EVERY exit path (complete, cancel,
            # fail_all, shutdown drain), so the unbounded park is safe
            r.ev.wait()  # tpulint: disable=NET501  loop guarantees ev.set
        else:
            # bounded wait: the loop cancels the slot at the next round
            # boundary; the grace poll only guards a wedged loop thread
            while not r.ev.wait(timeout=0.25):
                if self.clock() >= deadline + 30.0:
                    raise DeadlineExceeded(
                        "decoder unresponsive past request deadline")
        self._note_request(r)
        if isinstance(r.result, Exception):
            raise r.result
        return r.result

    def _note_request(self, r: _Request) -> None:
        """From the submitting thread, once woken: the request's
        `serve.request` span (submit to done, under the submitter's
        ambient context) and its two waits to the meter. None where the
        request never got that far."""
        outcome = r.outcome()
        wait = r.t_admit - r.t_submit if r.t_admit else None
        first = r.t_first - r.t_submit if r.t_first else None
        obs_trace.TRACER.record(
            "serve.request", r.t_submit, r.t_done,
            queue_wait_s=wait, first_token_s=first,
            prompt_tokens=self.P - r.pad,
            prefill_tokens_computed=r.prefill_tokens,
            new_tokens=r.req if outcome == "ok" else 0, slot=r.slot,
            outcome=outcome, **r.attrs,
            **({"window_pages_released": r.released}
               if self.alloc is not None and self.alloc.window else {}))
        if self.meter and first is not None:
            self.meter.request_waits(wait, first)

    def close(self) -> None:
        with self._lock:
            self._stop = True
        self._wake.set()
        self._thread.join(timeout=10)

    @property
    def active_slots(self) -> int:
        # host-side mirror: reading self.state from another thread races
        # the loop's buffer donation (donate_argnums)
        return self._active

    def stats(self) -> dict:
        """Host-truth counters (deterministic; what serve_bench banks)."""
        out = dict(self._counters)
        out["mode"] = "paged" if self.paged else "dense"
        out["speculative"] = self.spec
        out["cache_bytes"] = self._cache_bytes
        if self.paged:
            a = self.alloc
            if a.window:
                # by kind; `kv_pages_used` / `kv_pages_total` below are
                # then of both pools together
                out.update(
                    kv_pages_used_held=a.used_pages,
                    kv_pages_total_held=a.num_pages - 1,
                    kv_pages_used_window=a.window_used_pages,
                    kv_pages_total_window=a.window_pages - 1,
                    kv_window_pages_released=a.window_released)
            out.update(
                kv_pages_total=(a.num_pages - 1      # sans trash
                                + max(0, a.window_pages - 1)),
                kv_page_size=self.page_size,
                kv_pages_free=a.free_pages,
                kv_pages_used=a.used_pages + a.window_used_pages,
                prefix_hit_pages=self.alloc.prefix_hit_pages,
                prefix_hit_tokens=self.alloc.prefix_hit_tokens,
                cow_clones=self.alloc.cow_clones,
                # distinct suffix lengths prefill ran at since the build:
                # the ladder's size at the most
                prefill_shapes=len(self._prefill_lengths),
            )
            row = getattr(self.step, "latent_row_bytes", 0)
            if row:     # what a position takes in one latent layer's pool
                out["kv_latent_row_bytes"] = row
        return out

    # -- the loop's pieces ---------------------------------------------------

    def _note_active(self, owners) -> None:
        self._active = len(owners)
        if len(owners) > self._counters["peak_active"]:
            self._counters["peak_active"] = len(owners)

    def _publish_pages(self) -> None:
        """The page gauges, where the pool moved since they were last
        set (admission, completion, cancel, a sequence crossing a page
        boundary): four writes through two sinks are not worth a
        round's time for nothing."""
        if self.meter and self.paged:
            free = self.alloc.free_pages
            if free != self._pages_published:
                self._pages_published = free
                self.meter.pages(free, self.alloc.used_pages)

    def _tables(self, slot: int | None = None):
        """What a program is given as its page table: the allocator's
        (one slot's row of it), or where pages are kept by layer kind the
        pair (held kind's, window kind's)."""
        rows = slice(None) if slot is None else slice(slot, slot + 1)
        # copies: the allocator rewrites entries that a program still in
        # flight reads, and the device may take the host's array as it is
        held = self.alloc.table[rows].copy()
        if not self.alloc.window:
            return self._jnp.asarray(held) if slot is None else held
        return (self._jnp.asarray(held),
                self._jnp.asarray(self.alloc.window_table[rows].copy()))

    def _note_window_pages(self, owners) -> None:
        if self.alloc.window:
            c, a = self._counters, self.alloc
            c["kv_window_pages_held_sum"] += sum(map(a.window_held, owners))
            c["kv_window_pages_covered_sum"] += sum(
                map(a.window_covered, owners))

    def _note_profiler(self) -> None:
        """Immediately before every dispatch of a step program, and in
        every idle pass: whether a profiler session is open, against what
        the loop saw last. At most one round is in flight there, so what
        is dispatched behind an open session runs inside it: from the
        rising edge to the falling one the counters' growth is what the
        session's device trace holds, to the one round in flight at
        either end (a fused one at the most), and goes out as one
        `serve.profiled` span."""
        if obs_trace.profiling():
            if self._profiled is None:
                self._profiled = (_stamp(), dict(self._counters))
        elif self._profiled is not None:
            self._end_profiled()

    def _end_profiled(self) -> None:
        t_on, before = self._profiled
        self._profiled = None
        obs_trace.TRACER.record(
            "serve.profiled", t_on, _stamp(),
            **{k: v - before[k] for k, v in self._counters.items()})

    def _round_class(self, ticks: int) -> str:
        """What the pass that is about to dispatch `ticks` held: `plain`
        (a single tick, no admission), `fused` (a fused dispatch, no
        admission), `rung<R>` (a single tick behind exactly one admission
        whose suffix is the ladder's R), `other` (the rest: several
        admissions, an admission before a fused dispatch, a dense
        decoder's batch)."""
        rungs = self._pass_rungs
        if not rungs:
            return "fused" if ticks > 1 else "plain"
        cls = f"rung{rungs[0]}"
        if len(rungs) == 1 and ticks == 1 and cls in self._round_keys:
            return cls
        return "other"

    def _cow_copy(self, copies) -> None:
        """[(src, dst)] page clones, applied before a program writes:
        the ONE conversion every COW-apply site shares."""
        if copies:
            jnp = self._jnp
            self.step.copy_pages(
                jnp.asarray([c[0] for c in copies], jnp.int32),
                jnp.asarray([c[1] for c in copies], jnp.int32))

    def _drain_shutdown(self, owners: dict) -> None:
        err = RuntimeError("decoder shut down")
        for r in list(owners.values()):
            r.finish(err)
        if self._carry is not None:
            self._carry.finish(err)
            self._carry = None
        while not self._pending.empty():
            self._pending.get_nowait().finish(err)

    def _next_pending(self):
        """FIFO head: the page-gated carry first, then the queue."""
        if self._carry is not None:
            item, self._carry = self._carry, None
            return item
        if not self._pending.empty():
            return self._pending.get_nowait()
        return None

    def _validate(self, r: _Request) -> bool:
        """Row-shape validation; a malformed row fails ONLY its caller
        and never reaches a slot. Also the queue-side deadline gate: a
        request that expired while waiting (or carried at the page gate)
        is shed here, BEFORE it costs a prefill."""
        if r.deadline is not None and self.clock() >= r.deadline:
            r.finish(DeadlineExceeded("deadline elapsed before admission"))
            self._counters["deadline_canceled"] += 1
            return False
        if r.prompt.shape != (self.P,):
            r.finish(ValueError(
                f"padded row must have length {self.P}, "
                f"got {r.prompt.shape}"))
            return False
        return True

    def _note_admitted(self, owners: dict, r: _Request, slot: int,
                       prefill_tokens: int, first, plan=None) -> None:
        """Admission's bookkeeping, once the request's prefill has been
        dispatched and the slot has its owner: the stamp, the counters. Where the
        install left a first token on the device (`first`: a speculative
        prefill's own), the host blocks on it here, and a budget of one
        token is then already met."""
        phase = self._phase
        with phase("admit"):
            r.t_admit = _stamp()
            r.slot, r.prefill_tokens = slot, prefill_tokens
            self._pass_rungs.append(prefill_tokens)
            c = self._counters
            c["admitted"] += 1
            c["queue_wait_s_sum"] += r.t_admit - r.t_submit
            c["prefill_tokens_computed"] += prefill_tokens
            c["prompt_tokens_real"] += self.P - r.pad
            c["prompt_tokens_submitted"] += self.P
            self._prefill_lengths.add(prefill_tokens)
            if self._fresh:
                c["prefill_flash"] += 1
                # what the program's own test says (`_decode_paged`)
                behind = r.pad < plan.compute_start
                c["prefill_behind_hit"] += behind
                run, reachable = _flash_block_pairs(
                    self._cfg, prefill_tokens, plan.compute_start, r.pad,
                    behind)
                c["prefill_flash_pairs_run"] += run
                c["prefill_flash_pairs_reachable"] += reachable
            if self.meter:
                self.meter.prefill_tokens(prefill_tokens)
                if plan is not None:
                    self.meter.prefix_hits(plan.shared_pages)
            if first is None:
                self._publish_pages()
                return
        with phase("readback"):
            met = self.step.first_token(slot, r, first)
        with phase("admit"):
            c["tokens_decoded"] += 1     # the install's own first token
            self._note_first_tokens([r])
            if met:
                self._complete(owners, slot)
            self._publish_pages()

    def _note_first_tokens(self, requests) -> None:
        """The first read-back after an admission has just ended: the
        first moment a token of these requests could have been sent."""
        now = _stamp()
        c = self._counters
        for r in requests:
            if not r.t_first:
                r.t_first = now
                c["first_token_s_sum"] += now - r.t_submit
                c["first_tokens"] += 1

    def _complete(self, owners: dict, slot: int) -> None:
        """A finished slot: the slot and (paged) its pages back to the
        pool, then the answer to its waiter, who may read `stats()`."""
        r = owners.pop(slot)
        answer = self.step.answer(slot, r)
        self._free.append(slot)
        self._counters["completed"] += 1
        if self.paged:
            self.alloc.free(slot)
        self._note_active(owners)
        r.finish(*answer)

    def _read_round(self, owners: dict, flown: tuple) -> None:
        """Read back the oldest round in flight and complete what it
        finished. `flown`: the round's seconds key and when it was
        dispatched. The passes since the last read-back are its
        seconds: where the loop runs ahead, the pass that read it back
        and, for the first round ahead, the pass that dispatched it."""
        phase, c = self._phase, self._counters
        s_key, t_sent = flown
        with phase("readback"):
            done, counts = self.step.readback(owners)
            for name, n in counts.items():
                c[name] += n
        with phase("complete"):
            # the requests admitted before the round was dispatched: it
            # decoded their tokens
            self._note_first_tokens(
                [r for r in owners.values() if r.t_admit <= t_sent])
            for s_ in done:
                self._complete(owners, s_)
            if self.meter and "spec_rounds" in counts:
                self.meter.spec_round(
                    counts["spec_rounds"], counts["spec_tokens_accepted"])
            self._publish_pages()
        c[s_key] += _stamp() - self._t_charge
        self._t_charge = None

    def _cancel_expired(self, owners: dict) -> None:
        """Cancel the mid-decode slots whose deadline has passed, at the
        round boundary: the waiter gets DeadlineExceeded, the slot and
        (paged) its KV pages go back to the pool. Zero-leak is the
        contract — alloc.check() stays clean after any cancel."""
        now = self.clock()
        expired = [s_ for s_, r in owners.items()
                   if r.deadline is not None and now >= r.deadline]
        if not expired:
            return
        self.step.cancel(expired)
        gone = [owners.pop(slot) for slot in expired]
        for slot in expired:
            self._free.append(slot)
            self._counters["deadline_canceled"] += 1
            if self.paged:
                self.alloc.free(slot)
        self._publish_pages()
        self._note_active(owners)
        # the waiters last: each may read `stats()` once woken
        for r in gone:
            r.finish(DeadlineExceeded("deadline exceeded during decode"))

    # -- the scheduler loop: one, whatever the kind of step ----------------

    def _loop(self) -> None:
        phase, step = self._phase, self.step
        owners: dict[int, _Request] = {}   # slot -> the request it serves
        # the rounds dispatched and not read back, oldest first: each
        # one's seconds key and when it was dispatched
        flight: collections.deque = collections.deque()

        def fail_all(err, batch=()):
            """Poison every waiter and REBUILD device state: continuing
            on a failed donated call's dead buffers would turn the decoder
            into a zombie that errors every future request while still
            accepting submits."""
            for r in (*batch, *owners.values()):
                r.finish(err)
            owners.clear()
            flight.clear()
            self._t_charge = None
            self._free = list(range(self.S))
            if self.alloc is not None:
                self.alloc.reset()
            step.fresh()

        admit = self._admit_paged if self.paged else self._admit_dense
        c = self._counters
        while not self._stop:
            try:
                if self._t_charge is None:
                    self._t_charge = _stamp()
                self._pass_rungs.clear()
                admit(owners, fail_all)
                with phase("admit"):
                    self._cancel_expired(owners)
                    self._note_active(owners)
                # Run ahead: dispatch this round before the last one is
                # read back, so the host's work between the two runs
                # behind the device's. Where the step's next round needs
                # nothing the read-back gives, and every slot is owned:
                # no waiter could then enter sooner. Else the round in
                # flight is read back first, in a pass of its own.
                # A round in flight writes no page that its predecessor's
                # read-back gives away, but for a slot that finished
                # there: that slot idles, and its ticks write at its
                # frozen position, one past its answer: into the trash
                # page or a page of its own answer (the row's last, where
                # the position lies past the row), never a prompt page
                # that `_complete` keeps for the prefix cache. Whatever
                # is dispatched later, an install into that page among
                # it, consumes the state the round in flight hands on,
                # so the device runs it after that write.
                ahead = step.runs_ahead and not self._free
                if flight and not ahead:
                    self._read_round(owners, flight.popleft())
                    continue
                if not owners:
                    with phase("idle"):
                        self._note_profiler()
                        self._wake.wait(timeout=0.05)
                        self._wake.clear()
                    self._t_charge = None
                    continue
                with phase("pages"):
                    # fuse ticks when the step has a full window left
                    # in every active slot AND no waiter could be admitted
                    # any sooner by single-stepping: no slot can complete
                    # inside the window, so when the decoder is SATURATED
                    # (no free slot) a queued request loses zero ticks to
                    # fusion — exactly the latency-bound regime the
                    # fusion exists for
                    waiting = (self._carry is not None
                               or not self._pending.empty())
                    ticks = (step.ticks(owners)
                             if not waiting or not self._free else 1)
                    if self.paged:
                        # a round's writes march forward: take back the
                        # window-kind pages behind its first query's
                        # window, hand out the pages it will cross
                        # (reserved at admission) and run the COW
                        # barrier over the write range
                        for s_, r in owners.items():
                            start, end = step.writes(s_, r, ticks)
                            r.released += self.alloc.release(s_, start)
                            self.alloc.append(s_, end)
                            self._cow_copy(
                                self.alloc.write_barrier(s_, start, end))
                        self._note_window_pages(owners)
                    table = self._tables() if self.paged else None
                    n_key, s_key = self._round_keys[self._round_class(ticks)]
                with phase("tick", fused=int(ticks > 1)), self._ctx:
                    self._note_profiler()
                    step.dispatch(owners, ticks, table)
                c["rounds"] += 1
                c[n_key] += 1
                c["ticks"] += ticks
                c["rounds_ahead"] += bool(flight)
                flight.append((s_key, _stamp()))
                if len(flight) > 1 or not ahead:
                    self._read_round(owners, flight.popleft())
            except Exception as e:  # a broken step: poison + rebuild
                log.exception("slot-decoder loop failed")
                fail_all(e)
                self._active = 0
        # shutdown: the round in flight is read back, so what it finished
        # is answered; then fail any stragglers; a profiled stretch still
        # open ends here
        try:
            while flight:
                if self._t_charge is None:
                    self._t_charge = _stamp()
                self._read_round(owners, flight.popleft())
        except Exception:
            log.exception("slot-decoder read-back at shutdown failed")
        self._drain_shutdown(owners)
        if self._profiled is not None:
            self._end_profiled()

    # -- admission: dense (batched idle-burst prefill) ---------------------

    def _admit_dense(self, owners, fail_all) -> None:
        phase = self._phase
        if not (self._free and not self._pending.empty()):
            return
        with phase("admit"):
            # admit pending requests into free slots (step boundary).
            # Idle decoder: take a BATCH of waiting prompts so an idle
            # burst prefills together. Anything mid-generation:
            # admit at most ONE per tick — a burst must not stall
            # in-flight decodes.
            want = 1 if owners else len(self._free)
            batch = []
            while len(batch) < want and not self._pending.empty():
                batch.append(self._pending.get_nowait())
            # validate rows FIRST; a wrong-length row (the submit_padded
            # caller's bug) fails THAT caller only and never enters the
            # batch, so row indices stay aligned with the prefill's
            batch = [r for r in batch if self._validate(r)]
            if not batch:
                return
            slots = [self._free.pop() for _ in range(len(batch))]
        try:
            with phase("prefill"), self._ctx:
                self._note_profiler()
                firsts = self.step.install_dense(batch, slots, self._free)
        except Exception as e:
            self._free.extend(slots)
            fail_all(e, batch)
            return
        for s_, r, first in zip(slots, batch, firsts):
            owners[s_] = r
            self._note_admitted(owners, r, s_, self.P, first)

    # -- admission: paged (per-request suffix prefill, page-gated) ---------

    def _admit_paged(self, owners, fail_all) -> None:
        phase, step = self._phase, self.step
        want = 1 if owners else self.S
        admitted = 0
        while admitted < want and self._free:
            with phase("admit"):
                r = self._next_pending()
                if r is None:
                    return
                if not self._validate(r):
                    continue
                # (the allocator reads the row's real pages only)
                row, total = r.prompt, step.end(r)
                # (the window kind, so no prefix cache:) a prefill that
                # attends over its own keys leaves the pages to the
                # ticks, the first of which is at prompt_len
                reads_from = self.P if self._fresh else None
                if not self.alloc.can_admit(row, r.pad, total, reads_from):
                    # head-of-line page gate: FIFO order is preserved (no
                    # bypass) — the request waits for completions to free
                    # pages, and everything behind it waits too
                    self._carry = r
                    return
                slot = self._free.pop()
            try:
                with phase("admit"):
                    plan = self.alloc.admit(slot, row, r.pad, total,
                                            reads_from)
                    # the suffix is a rung of the ladder: compiled when
                    # the decoder was built
                    suffix = self.P - plan.compute_start
                with phase("prefill"), self._ctx:
                    self._note_profiler()
                    self._cow_copy(plan.copies)
                    first = step.install_paged(
                        self._prefill_at[suffix], r, slot,
                        plan.compute_start, self._tables(slot))
            except Exception as e:
                # the slot's PAGES go back before the slot id does —
                # recycling the slot while the allocator still holds
                # its admission leaks every page it claimed (tpulint
                # RES701); free() is a no-op when admit itself raised
                self.alloc.free(slot)
                self._free.append(slot)
                fail_all(e, [r])
                return
            owners[slot] = r    # whose completion or cancel frees the pages
            self._note_admitted(owners, r, slot, suffix, first, plan)
            admitted += slot in owners
