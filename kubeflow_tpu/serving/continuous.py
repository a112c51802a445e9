"""Continuous batching for LM serving: slot-based lockstep decode.

The MicroBatcher coalesces concurrent requests into one `generate()`
call — but then the whole group decodes together: a request arriving one
step later waits for the ENTIRE previous generation, and every request
in a group pays the longest member's latency. Continuous batching is the
transformer-serving answer (beyond anything the reference's TF-Serving
story had): a fixed pool of S slots decodes in lockstep, requests JOIN
at any step boundary (prefilled off to the side, then scattered into a
free slot's cache rows) and LEAVE independently when their token budget
is done. Throughput stays at batched-decode levels while p50 latency
drops to ~arrival + own-length.

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
  tokens (runtime/speculative.py lockstep_propose) and the target
  verifies every slot's whole chunk in ONE [S, k+1] forward; per-slot
  variable accept lengths ride the same masking discipline the tick
  already uses, and output stays token-for-token equal to plain
  greedy decode.

Beside the three levers, **the block step**: a model that generates by
diffusion over blocks (cfg.gen_block = B > 0, models/transformer.py)
takes another kind of step in the same loop. Each slot holds one block
of B positions, MASK where nothing is fixed yet; a tick is one pass over
every slot's block against the committed cache (write, then attend,
under the block-causal mask), which fixes the most confident masked
positions, and a pass over a block with no MASK left commits it: its
tokens go to the output, the slot moves B positions on and opens the
next block. Slots sit at different steps of their blocks in one lockstep
program. What kind of step a model takes is read from its config; the
scheduler, admission and the page allocator are the same.

Single-host scheduler; the decode/prefill programs themselves run under
whatever mesh the variables are sharded over.
"""

from __future__ import annotations

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

# What a block model's pass counts on the device, in this order, over the
# slots that hold a request (serving/continuous.py `_block_pass`):
# passes x active slots; blocks committed; the pages from each slot's
# first real position to its block's end (what the pass's attention
# walks: `kv_pages_walked`); and from the mixture layers (ops/moe.py),
# summed over layers: the routed pairs, the experts that got at least one
# (each a group whose weights the grouped matmul reads) and the fullest
# expert's pairs.
BLOCK_COUNTERS = ("block_passes", "blocks_committed", "kv_pages_walked",
                  "moe_pairs", "moe_expert_visits", "moe_load_max")

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

    __slots__ = ("prompt", "pad", "req", "ev", "sink", "deadline",
                 "t_submit", "t_admit", "t_first", "t_done", "slot",
                 "prefill_tokens", "fixed_at", "blocks", "passes")

    def __init__(self, prompt, pad: int, req: int, deadline):
        self.prompt, self.pad, self.req = prompt, pad, req
        self.deadline = deadline
        self.ev = threading.Event()
        self.sink: list = []
        self.t_submit = _stamp()
        self.t_admit = self.t_first = self.t_done = 0.0
        self.slot = -1
        self.prefill_tokens = 0
        # a block model's: the denoising step at which each token was
        # fixed, the blocks the answer lies in and the passes it took
        self.fixed_at: list | None = None
        self.blocks = self.passes = 0

    def finish(self, result) -> None:
        """The one exit: tokens or the error into the sink, the done
        stamp, then wake the submitter."""
        if isinstance(result, Exception):
            self.sink.append(result)
        else:
            self.sink.extend(result)
        self.t_done = _stamp()
        self.ev.set()

    def outcome(self) -> str:
        if self.sink and isinstance(self.sink[0], Exception):
            return ("canceled" if isinstance(self.sink[0], DeadlineExceeded)
                    else "failed")
        return "ok"


class SlotDecoder:
    """S-slot continuous decoder over a KV-cache LM.

    Host API: ``submit(tokens, max_new=None) -> list[int]`` blocks the
    calling thread until that request's continuation is done; many
    threads may submit concurrently. A background loop admits pending
    requests into free slots at step boundaries and advances all
    active slots one token (or one speculative chunk) per tick.

    Modes (orthogonal where meaningful):

    - dense (default): per-slot [S, max_seq] cache rows, batched
      idle-burst prefill — the original shape.
    - paged: the model was built with cfg.kv_pages/kv_page_size; a
      PageAllocator gates admission on page availability, prompts
      reuse shared prefix pages, and per-request prefill computes the
      shortest rung of a fixed ladder of lengths that covers the real
      tokens no hit covers: never the padding before them, and never
      at a length that was not compiled when the decoder was built.
    - speculative (draft_model given): greedy-only lockstep
      propose/verify rounds; composes with dense or paged target.
    - block (the model's cfg.gen_block > 0; paged, greedy, no draft):
      a tick is one denoising or committing pass over every slot's
      block of gen_block positions, and `submit` returns
      ``{"tokens": [...], "fixed_at": [...]}``, the step of its block
      at which each token was fixed.
    """

    def __init__(self, model, variables, *, slots: int = 8,
                 prompt_len: int = 128, max_new_tokens: int = 32,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 mesh=None, prefix_cache: bool = True,
                 draft_model=None, draft_variables=None, draft_k: int = 4,
                 metrics_name: str | None = None, clock=None):
        import jax
        import jax.numpy as jnp

        from kubeflow_tpu.runtime.generate import (
            check_decode_geometry, init_cache, prefill_scan)
        from kubeflow_tpu.runtime.kvcache import (
            PageAllocator, init_paged_cache, pages_for, prefill_ladder)

        self.model = model
        self.variables = variables
        self.S = slots
        self.P = prompt_len
        self.N = max_new_tokens
        self.mesh = mesh
        # deadline clock (injectable for deterministic cancel tests);
        # submit deadlines are ABSOLUTE values on this clock
        self.clock = clock if clock is not None else time.monotonic
        self._jnp = jnp
        self._jax = jax
        cfg_vocab = model.cfg.vocab_size
        self.spec = draft_model is not None
        self.draft_k = draft_k if self.spec else 0
        self.paged = bool(getattr(model.cfg, "kv_pages", 0))
        # a block model's step (cfg.gen_block > 0): B positions a slot,
        # `per` of them fixed a pass
        self.B = B = int(getattr(model.cfg, "gen_block", 0) or 0)
        if B:
            steps = getattr(model.cfg, "gen_steps", 0) or B
            if B % steps:
                raise ValueError(f"gen_block {B} is no multiple of "
                                 f"gen_steps {steps}")
            self._per = B // steps
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
        # positions a slot may touch past its last token: the verify
        # chunk's overhang, or the rest of the answer's last block
        self._overhang = B if B else self.draft_k
        check_decode_geometry(model, prompt_len,
                              max_new_tokens + self._overhang)
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
        # a slot's worst-case sequence: prompt + its budget + the
        # overhang past the last token
        self._total_len = prompt_len + max_new_tokens + self._overhang
        if self.paged:
            cfg = model.cfg
            self.page_size = cfg.kv_page_size
            self._mp = pages_for(self._total_len, self.page_size)
            usable = cfg.kv_pages - 1  # page 0 is trash
            if usable < self._mp:
                raise ValueError(
                    f"kv_pages={cfg.kv_pages} cannot hold even one "
                    f"sequence ({self._mp} pages of {self.page_size} "
                    "needed, page 0 is trash)")
            self.alloc = PageAllocator(
                cfg.kv_pages, self.page_size, slots, self._mp,
                prefix_cache=prefix_cache)
            # the suffix lengths prefill runs at (runtime/kvcache.py)
            self._ladder = prefill_ladder(prompt_len, self.page_size)
        else:
            self.alloc = None
        self.meter = _DecodeMeter(metrics_name) if metrics_name else None
        self._pages_published = -1   # free pages at the last publish

        # host-truth counters (stats(); the meter mirrors into sinks)
        self._counters = {
            "admitted": 0, "completed": 0, "peak_active": 0,
            # per admission: the positions prefill computed, the prompt's
            # real tokens (prompt_len less the padding), and prompt_len
            "prefill_tokens_computed": 0, "prompt_tokens_real": 0,
            "prompt_tokens_submitted": 0,
            "spec_rounds": 0, "spec_tokens_emitted": 0,
            "spec_tokens_accepted": 0, "spec_drafted": 0,
            "deadline_canceled": 0,
            # passes of the loop that dispatched a step program
            "rounds": 0,
            # per request: submit to admission, submit to the first
            # read-back after it (seconds, summed; `admitted` and
            # `first_tokens` are the counts)
            "queue_wait_s_sum": 0.0, "first_token_s_sum": 0.0,
            "first_tokens": 0,
        }
        if self.paged:
            # how much of the page table the plain loop's decode ticks
            # walk (one tick = one query a slot): the pages that hold
            # what each active slot's query sees, summed over slots and
            # ticks, beside ticks x every entry of the table, which is
            # what gathering the table touches
            self._counters.update(kv_pages_walked=0, kv_pages_tabled=0)
        if B:
            # the block step, counted on the device and read back with
            # `remaining` (BLOCK_COUNTERS says what each counts)
            self._counters.update(dict.fromkeys(BLOCK_COUNTERS, 0))
        # the loop's host phases: phase_s.* in stats(), and kftpu.sched.*
        # annotations in the profiler's trace
        self._phase = obs_trace.PhaseClock(
            "sched", SCHED_PHASES, self._counters)

        # Params are jit ARGUMENTS everywhere below, never closure
        # captures: a closed-over weight tree is serialized into the
        # program as inline constants — a gpt-350m continuous decoder
        # carries ~700MB of MLIR to the compiler and into the compile
        # cache key — and every weight swap becomes a full retrace.
        # server.py's predict path (fwd(params, x)) does the same.
        self._params = {"params": variables["params"]}
        if self.spec:
            self._d_params = {"params": draft_variables["params"]}
            self.draft = draft_model

        # -- compiled: batch-K prefill (the ONE prefill implementation,
        #    shared with generate(): runtime/generate.py prefill_scan).
        #    K is a static batch size — one compile per size in
        #    _PREFILL_SIZES, so an idle-decoder burst prefills together
        #    instead of paying burst_size serial scans. ------------------
        def _prefill(params, prompts_kp, pad_lens_k):
            cache_k = init_cache(model, prompts_kp.shape[0])
            return prefill_scan(model, params, cache_k, prompts_kp,
                                pad_lens_k)

        self._prefill = jax.jit(_prefill)

        # -- compiled: install K prefilled rows into K slots in ONE
        #    program (K static, unrolled; slot ids traced) --------------
        def _install(state, cache_k, logits_k, slots_k, pads_k, news_k):
            cache, last, pos, remaining, out, pads, req, rng = state
            k = logits_k.shape[0]
            for i in range(k):  # static unroll: K is a compile-time size
                si = slots_k[i]
                cache = jax.tree.map(
                    lambda big, kk, i=i, si=si: jax.lax.dynamic_update_slice(
                        big, kk[i:i + 1].astype(big.dtype),
                        (si,) + (0,) * (big.ndim - 1)),
                    cache, cache_k)
                last = jax.lax.dynamic_update_slice(
                    last, logits_k[i][None], (si, 0))
                pos = _set1(jnp, pos, si, self.P)
                remaining = _set1(jnp, remaining, si, news_k[i])
                out = jax.lax.dynamic_update_slice(
                    out, jnp.zeros((1, self.N), jnp.int32), (si, 0))
                pads = _set1(jnp, pads, si, pads_k[i])
                req = _set1(jnp, req, si, news_k[i])
            return (cache, last, pos, remaining, out, pads, req, rng)

        self._install = jax.jit(_install, donate_argnums=(0,))

        # -- compiled: deactivate slots (dummy prefill targets) ----------
        def _clear_slots(state, slots_k):
            cache, last, pos, remaining, out, pads, req, rng = state
            clear = (jnp.arange(self.S)[:, None]
                     == slots_k[None, :]).any(axis=1)
            remaining = jnp.where(clear, 0, remaining)
            return (cache, last, pos, remaining, out, pads, req, rng)

        self._clear_slots = jax.jit(_clear_slots, donate_argnums=(0,))

        # -- compiled: paged prefill of ONE request's prompt suffix +
        #    install. The suffix is the shortest rung of self._ladder
        #    that covers the real tokens no prefix hit covers (the
        #    allocator's plan), so the function is traced at the ladder's
        #    lengths and at no other. The function's name is a contract
        #    at every rung: the benchmark finds the XLA module
        #    `jit__paged_prefill_install` by it
        #    (benchmarks/metrics/*.json; tests/test_trace_names.py) ------
        def _paged_prefill_install(params, state, toks, start, pt_row,
                                   pad, slot, req_n, block=None):
            cache, last, pos, remaining, out, pads, req, rng = state
            logits, mut = model.apply(
                params | {"cache": cache}, toks, train=False,
                decode_index=start, mutable=["cache"], pad_len=pad,
                page_table=pt_row)
            cache = mut["cache"]
            if block is None:
                last = jax.lax.dynamic_update_slice(
                    last, logits[:, -1], (slot, 0))
                first_pos = self.P
            else:
                # a block model: the prompt's whole blocks are committed
                # by this pass (no position sees a later block); the
                # tokens behind them (`tail` [B], the first `n_tail`
                # real) open the slot's first block as fixed, and the
                # block steps write those positions anew
                tail, n_tail = block
                mine = jnp.arange(self.S) == slot
                blk = dict(last)
                blk["tok"] = jnp.where(mine[:, None], tail[None, :],
                                       blk["tok"])
                blk["fixed"] = jnp.where(
                    mine[:, None], (jnp.arange(B) < n_tail)[None, :],
                    blk["fixed"])
                blk["at"] = jnp.where(mine[:, None], 0, blk["at"])
                blk["step"] = jnp.where(mine, 0, blk["step"])
                blk["out_at"] = jnp.where(mine[:, None], 0, blk["out_at"])
                last = blk
                first_pos = self.P - n_tail
            pos = _set1(jnp, pos, slot, first_pos)
            remaining = _set1(jnp, remaining, slot, req_n)
            out = jax.lax.dynamic_update_slice(
                out, jnp.zeros((1, self.N), jnp.int32), (slot, 0))
            pads = _set1(jnp, pads, slot, pad[0])
            req = _set1(jnp, req, slot, req_n)
            return (cache, last, pos, remaining, out, pads, req, rng)

        self._paged_prefill_install = jax.jit(
            _paged_prefill_install, donate_argnums=(1,))
        # the suffix lengths dispatched since the build
        self._prefill_lengths: set = set()

        # -- compiled: apply COW page clones before a program writes ----
        def _apply_copies(state, src, dst):
            from kubeflow_tpu.runtime.kvcache import copy_pages

            return (copy_pages(state[0], src, dst),) + tuple(state[1:])

        self._apply_copies = jax.jit(_apply_copies, donate_argnums=(0,))

        # -- compiled: one lockstep decode tick for all S slots. Its name
        #    is a contract too: the paged decoder's module is `jit__tick`
        #    in the device trace, and the benchmark reads it by that ------
        def _tick(params, state, page_table=None):
            cache, last, pos, remaining, out, pads, req, rng = state
            from kubeflow_tpu.runtime.generate import _sample

            active = remaining > 0
            rng, sub = jax.random.split(rng)
            tok = _sample(last, temperature, top_k, sub)
            # record the sampled token at each active slot's next column
            # (column index = tokens generated so far = req - remaining)
            ncol = req - remaining
            hot = (jnp.arange(self.N)[None, :] == ncol[:, None]) \
                & active[:, None]
            out = jnp.where(hot, tok[:, None], out)
            # advance the model one position for every slot (idle slots
            # compute too — lockstep static shape — but their state is
            # frozen by the masks below; their cache writes land in
            # their own dead rows (dense) or the trash page (paged)).
            # An idle slot's query is made to see nothing, by padding
            # that begins past its position: the paged attention kernel
            # then fetches no page for it.
            logits_next, mut = model.apply(
                params | {"cache": cache}, tok[:, None], train=False,
                decode_index=pos, mutable=["cache"],
                pad_len=jnp.where(active, pads, pos + 1),
                **({"page_table": page_table}
                   if page_table is not None else {}))
            pos = jnp.where(active, pos + 1, pos)
            remaining = jnp.where(active, remaining - 1, remaining)
            last = jnp.where(active[:, None], logits_next[:, 0], last)
            return (mut["cache"], last, pos, remaining, out, pads, req, rng)

        # -- compiled: a block model's tick, one pass over every slot's
        #    block. It takes the place of `_tick` under the same names
        #    (`jit__tick`, `jit__step_fused` in the device trace) -------
        def _block_pass(params, state, page_table):
            cache, blk, pos, remaining, out, pads, req, rng = state
            active = remaining > 0
            masked = ~blk["fixed"]                              # [S, B]
            # no MASK going in: this pass runs the clean block, and the
            # keys and values it writes are the committed ones
            commit = active & ~masked.any(axis=1)
            toks = jnp.where(masked, jnp.int32(model.cfg.gen_mask_id),
                             blk["tok"])
            logits, mut = model.apply(
                params | {"cache": cache}, toks, train=False,
                decode_index=pos, mutable=["cache", "diagnostics"],
                pad_len=jnp.where(active, pads, pos + B),
                page_table=page_table, block_step=True)
            logits = logits.astype(jnp.float32)                 # [S, B, V]
            cand = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            # confidence: the softmax probability of the argmax token
            conf = jnp.exp(jnp.max(logits, axis=-1)
                           - jax.nn.logsumexp(logits, axis=-1))
            # fix the `per` masked positions of highest confidence (the
            # first of equals); fewer where fewer are masked
            _, pick = jax.lax.top_k(jnp.where(masked, conf, -1.0),
                                    self._per)
            chosen = ((jnp.arange(B)[None, None, :] == pick[:, :, None])
                      .any(axis=1) & masked & active[:, None])
            step = blk["step"] + (active & ~commit)
            tok = jnp.where(chosen, cand, blk["tok"])
            fixed = blk["fixed"] | chosen
            at = jnp.where(chosen, step[:, None], blk["at"])
            # commit: the block's tokens to their output columns (the
            # first block's prompt tokens have none, and the last block
            # is cut to the tokens asked for), B positions on, and the
            # next block opens all MASK
            cols = pos[:, None] + jnp.arange(B)[None, :] - self.P
            emit = commit[:, None] & (cols >= 0) & (cols < req[:, None])
            rows = jnp.broadcast_to(jnp.arange(self.S)[:, None], cols.shape)
            cols = jnp.where(emit, cols, self.N)    # out of range: dropped
            out = out.at[rows, cols].set(tok, mode="drop")
            out_at = blk["out_at"].at[rows, cols].set(at, mode="drop")
            remaining = remaining - emit.sum(axis=1).astype(jnp.int32)
            last_pos = pos + B - 1
            pos = jnp.where(commit, pos + B, pos)
            keep = ~commit[:, None]
            counted = [
                active.sum(), commit.sum(),
                jnp.where(active, last_pos // self.page_size
                          - pads // self.page_size + 1, 0).sum(),
                *(_diag_sum(jax, mut.get("diagnostics", {}), n)
                  for n in BLOCK_COUNTERS[3:])]
            blk = {"tok": jnp.where(keep, tok, 0),
                   "fixed": fixed & keep,
                   "at": jnp.where(keep, at, 0),
                   "step": jnp.where(commit, 0, step),
                   "out_at": out_at,
                   "ctr": blk["ctr"] + jnp.stack(
                       [jnp.asarray(c, jnp.int32) for c in counted])}
            return (mut["cache"], blk, pos, remaining, out, pads, req, rng)

        def _counted_from_zero(state):
            """A dispatched program counts from zero: the host adds each
            round's counts to its own, which never wrap."""
            blk = dict(state[1], ctr=jnp.zeros_like(state[1]["ctr"]))
            return (state[0], blk) + tuple(state[2:])

        if B:
            tick_once = _block_pass

            def _tick(params, state, page_table):    # noqa: F811
                return _block_pass(params, _counted_from_zero(state),
                                   page_table)
        else:
            tick_once = _tick

        if self.paged:
            self._step = jax.jit(_tick, donate_argnums=(1,))
        else:
            # dense signature stays (params, state): the trace spies in
            # tests and the fused scan below rely on it
            self._step = jax.jit(lambda params, state: _tick(params, state),
                                 donate_argnums=(1,))

        # -- compiled: FUSE ticks in one dispatched program. Each
        #    dispatch costs a host round-trip (launch, the readback of
        #    `remaining`, the loop's bookkeeping); where that exceeds the
        #    tick's own compute, decode is bound by the host. Fusing
        #    amortizes the round-trip FUSE-fold. Measured on a v5e (PERF.md
        #    section 5, `sched.host_ms_per_round.*`): without an admission
        #    the host's share of a round is about 1.3 ms (page bookkeeping
        #    and the table's upload 0.6-0.7, the dispatch 0.5-0.6, completion
        #    0.1) against a tick of 3.7-5.2 ms of an 8-layer Mistral-7B,
        #    so the round-trip is a quarter to a third of a single tick
        #    and a thirtieth of a fused round; an admission adds 6-15 ms
        #    of host time. Correctness is
        #    unchanged — the tick body masks on remaining>0, so a slot
        #    finishing mid-window just idles until the window ends; the
        #    cost is admission/completion latency bounded at FUSE ticks,
        #    which is why the loop only fuses when nothing is waiting
        #    and every active slot has >= FUSE tokens to go. ------------
        FUSE = 8

        # (`jit__step_fused` in the device trace: read by the benchmark)
        def _step_fused(params, state, page_table=None):
            def body(st, _):
                return tick_once(params, st, page_table), None

            if B:
                state = _counted_from_zero(state)
            st, _ = jax.lax.scan(body, state, None, length=FUSE)
            return st

        if self.paged:
            self._step_fused = jax.jit(_step_fused, donate_argnums=(1,))
        else:
            self._step_fused = jax.jit(
                lambda params, state: _step_fused(params, state),
                donate_argnums=(1,))
        self._fuse = FUSE
        # the most tokens a slot can finish in a fused round: a block
        # takes a denoising pass and a committing one at the least
        self._fuse_tokens = B * -(-FUSE // 2) if B else FUSE

        # -- compiled: speculative admission (prefill target + draft,
        #    install into slot rows, return the first greedy token) ----
        if self.spec:
            draft = draft_model

            def _row_install(big_tree, row_tree, slot):
                return jax.tree.map(
                    lambda big, kk: jax.lax.dynamic_update_slice(
                        big, kk.astype(big.dtype),
                        (slot,) + (0,) * (big.ndim - 1)),
                    big_tree, row_tree)

            def _spec_admit_dense(t_params, d_params, t_cache, d_cache,
                                  prompt, pad, slot):
                tc1, tlogits = prefill_scan(
                    model, t_params, init_cache(model, 1), prompt, pad)
                dc1, _ = prefill_scan(
                    draft, d_params, init_cache(draft, 1), prompt, pad)
                t_cache = _row_install(t_cache, tc1, slot)
                d_cache = _row_install(d_cache, dc1, slot)
                first = jnp.argmax(tlogits[0], axis=-1).astype(jnp.int32)
                return t_cache, d_cache, first

            self._spec_admit_dense = jax.jit(
                _spec_admit_dense, donate_argnums=(2, 3))

            def _spec_admit_paged(t_params, d_params, t_cache, d_cache,
                                  toks, start, pt_row, prompt, pad, slot):
                logits, mut = model.apply(
                    t_params | {"cache": t_cache}, toks, train=False,
                    decode_index=start, mutable=["cache"], pad_len=pad,
                    page_table=pt_row)
                t_cache = mut["cache"]
                dc1, _ = prefill_scan(
                    draft, d_params, init_cache(draft, 1), prompt, pad)
                d_cache = _row_install(d_cache, dc1, slot)
                first = jnp.argmax(logits[0, -1], axis=-1).astype(jnp.int32)
                return t_cache, d_cache, first

            self._spec_admit_paged = jax.jit(
                _spec_admit_paged, donate_argnums=(2, 3))

        # -- device state (rebuildable: a failed donated call leaves the
        #    old buffers dead, so recovery re-creates from scratch) ------
        def _fresh_cache():
            if self.paged:
                return init_paged_cache(model, self._mp)
            return init_cache(model, self.S)

        def _fresh_block():
            """What a block model's next pass starts from, in the place
            of the one-token model's last logits: each slot's block (its
            tokens, which are fixed, the step each was fixed at, the
            denoising passes so far), the step of every output token,
            and the counts of BLOCK_COUNTERS since the last dispatch.
            Maskedness is `fixed`, never a comparison with the MASK id:
            a prompt may hold that id."""
            return {"tok": jnp.zeros((self.S, B), jnp.int32),
                    "fixed": jnp.zeros((self.S, B), bool),
                    "at": jnp.zeros((self.S, B), jnp.int32),
                    "step": jnp.zeros((self.S,), jnp.int32),
                    "out_at": jnp.zeros((self.S, self.N), jnp.int32),
                    "ctr": jnp.zeros((len(BLOCK_COUNTERS),), jnp.int32)}

        def _fresh_state():
            return (
                _fresh_cache(),
                (_fresh_block() if B else
                 jnp.zeros((self.S, cfg_vocab), jnp.float32)),
                jnp.zeros((self.S,), jnp.int32),            # pos
                jnp.zeros((self.S,), jnp.int32),            # remaining
                jnp.zeros((self.S, self.N), jnp.int32),     # out
                jnp.zeros((self.S,), jnp.int32),            # pad_len
                jnp.zeros((self.S,), jnp.int32),            # req budget
                jax.random.PRNGKey(seed),
            )

        self._fresh_cache = _fresh_cache
        self._fresh_state = _fresh_state
        if self.spec:
            self.t_cache = _fresh_cache()
            self.d_cache = init_cache(draft_model, self.S)
            self._fresh_d_cache = lambda: init_cache(draft_model, self.S)
        else:
            self.state = _fresh_state()
            if self.paged:
                self._compile_prefills()
        # bytes the decode cache holds on-device (shape truth: the
        # density claims in tools/serve_bench.py --decode assert on it)
        self._cache_bytes = sum(
            leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(
                self.t_cache if self.spec else self.state[0]))
        # prefill batch sizes we're willing to compile (smallest >= the
        # waiting count is used; idle bursts prefill together)
        self._PREFILL_SIZES = tuple(sorted(
            {n for n in (1, 2, 4, 8, 16, 32) if n < self.S} | {self.S}))
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
            target=self._loop_spec if self.spec else self._loop,
            daemon=True, name="slot-decoder")
        self._thread.start()

    def _compile_prefills(self) -> None:
        """self._prefill_at: suffix length -> the prefill compiled (or
        loaded from the compile cache) for it from abstract shapes, every
        rung of the ladder, before the first request: a request never
        meets a compilation, whatever its length. This thread traces and
        lowers one rung after another (threads would only pass the
        interpreter lock around: side by side the rungs took longer on a
        v5e's host than one after another), and each lowered program
        compiles or loads in the pool meanwhile. Nothing runs on the
        device here."""
        import concurrent.futures as cf

        jax, jnp = self._jax, self._jnp

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        block = ((i32(self.B), i32()),) if self.B else ()

        def lowered(length):
            return self._paged_prefill_install.lower(
                self._params, self.state, i32(1, length), i32(1),
                i32(1, self._mp), i32(1), i32(), i32(), *block)

        t0 = _stamp()
        with cf.ThreadPoolExecutor(len(self._ladder)) as pool, \
                (self.mesh or contextlib.nullcontext()):
            jobs = [pool.submit(lowered(n).compile) for n in self._ladder]
        self._prefill_at = dict(zip(self._ladder,
                                    (job.result() for job in jobs)))
        log.info("paged prefill compiled at %s positions in %.2f s",
                 list(self._ladder), _stamp() - t0)

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
        if r.sink and isinstance(r.sink[0], Exception):
            raise r.sink[0]
        if self.B:
            return {"tokens": r.sink, "fixed_at": r.fixed_at}
        return r.sink

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
            new_tokens=len(r.sink) if outcome == "ok" else 0, slot=r.slot,
            outcome=outcome,
            **({"blocks": r.blocks, "passes": r.passes} if self.B else {}))
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
            out.update(
                kv_pages_total=self.alloc.num_pages - 1,  # sans trash
                kv_page_size=self.page_size,
                kv_pages_free=self.alloc.free_pages,
                kv_pages_used=self.alloc.used_pages,
                prefix_hit_pages=self.alloc.prefix_hit_pages,
                prefix_hit_tokens=self.alloc.prefix_hit_tokens,
                cow_clones=self.alloc.cow_clones,
                # distinct suffix lengths prefill ran at since the build:
                # the ladder's size at the most
                prefill_shapes=len(self._prefill_lengths),
            )
        return out

    # -- shared loop pieces ------------------------------------------------

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

    def _cow_arrays(self, copies):
        """[(src, dst)] page clones -> traced index arrays; the ONE
        conversion every COW-apply site shares."""
        jnp = self._jnp
        return (jnp.asarray([c[0] for c in copies], jnp.int32),
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

    def _note_admitted(self, r: _Request, slot: int, prefill_tokens: int,
                       owners: dict) -> None:
        """Admission's bookkeeping, once the request's prefill has been
        dispatched: the stamp, the counters, the slot's owner."""
        r.t_admit = _stamp()
        r.slot, r.prefill_tokens = slot, prefill_tokens
        owners[slot] = r
        c = self._counters
        c["admitted"] += 1
        c["queue_wait_s_sum"] += r.t_admit - r.t_submit
        c["prefill_tokens_computed"] += prefill_tokens
        c["prompt_tokens_real"] += self.P - r.pad
        c["prompt_tokens_submitted"] += self.P
        self._prefill_lengths.add(prefill_tokens)

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

    def _pages_seen(self, pad: int, pos: int) -> int:
        """Pages that hold what a query at `pos` sees behind `pad`
        positions of left padding: the range `_decode_paged` hands the
        paged attention kernel (models/transformer.py)."""
        window = self.model.cfg.attention_window
        first = max(pad, pos - window + 1) if window else pad
        return pos // self.page_size - first // self.page_size + 1

    # -- a block model's geometry (all zero or empty for B = 0) -----------

    def _tail(self, r: _Request) -> int:
        """The prompt's last tokens that fill no whole block: they open
        the request's first block as fixed."""
        return (self.P - r.pad) % self.B if self.B else 0

    def _block_end(self, r: _Request) -> int:
        """One past the last position of the block the answer ends in:
        every position the request's passes write."""
        tail = self._tail(r)
        return self.P - tail + -(-(tail + r.req) // self.B) * self.B

    def _first_block(self, r: _Request) -> tuple:
        """The last argument of the prefill of a block model's request:
        its first block's tokens and how many of them the prompt fixed.
        Nothing for a one-token model."""
        if not self.B:
            return ()
        import numpy as np

        tail = self._tail(r)
        r.blocks = -(-(tail + r.req) // self.B)
        toks = np.zeros(self.B, np.int32)
        toks[:tail] = r.prompt[self.P - tail:]
        return ((self._jnp.asarray(toks), self._jnp.int32(tail)),)

    def _expired_slots(self, owners: dict) -> list[int]:
        """Active slots whose request deadline has passed."""
        now = self.clock()
        return [s_ for s_, r in owners.items()
                if r.deadline is not None and now >= r.deadline]

    def _cancel_slot(self, owners: dict, slot: int) -> None:
        """Cancel ONE mid-decode slot: waiter gets DeadlineExceeded, the
        slot and (paged) its KV pages go back to the pool. Zero-leak is
        the contract — alloc.check() stays clean after any cancel."""
        owners.pop(slot).finish(
            DeadlineExceeded("deadline exceeded during decode"))
        self._free.append(slot)
        self._counters["deadline_canceled"] += 1
        if self.paged:
            self.alloc.free(slot)

    # -- scheduler loop (plain greedy/sampled decode) ----------------------

    def _loop(self) -> None:
        import numpy as np

        jnp = self._jnp
        phase = self._phase
        owners: dict[int, _Request] = {}   # slot -> the request it serves
        ctx = self.mesh if self.mesh is not None else None

        def fail_all(err, batch=()):
            """Poison every waiter and REBUILD device state: after a
            failed donated call the old buffers are dead — continuing on
            them would turn the decoder into a zombie that errors every
            future request while still accepting submits."""
            for r in (*batch, *owners.values()):
                r.finish(err)
            owners.clear()
            self._free = list(range(self.S))
            if self.alloc is not None:
                self.alloc.reset()
            self.state = self._fresh_state()

        last_rem = np.zeros(self.S, np.int64)  # host mirror of remaining
        last_pos = np.zeros(self.S, np.int64)  # host mirror of pos
        while not self._stop:
            try:
                if self.paged:
                    self._admit_paged(owners, fail_all, last_rem, last_pos)
                else:
                    self._admit_dense(owners, fail_all, last_rem)
                with phase("admit"):
                    # cancel expired slots at the round boundary: zero
                    # their remaining (the masked step then treats them
                    # as idle) and return slot + pages to the pool before
                    # the next admission pass can want them
                    expired = self._expired_slots(owners)
                    if expired:
                        self.state = self._clear_slots(
                            self.state, jnp.asarray(expired, jnp.int32))
                        for s_ in expired:
                            self._cancel_slot(owners, s_)
                            last_rem[s_] = 0
                        self._publish_pages()
                    self._note_active(owners)
                if not owners:
                    with phase("idle"):
                        self._wake.wait(timeout=0.05)
                        self._wake.clear()
                    continue
                with phase("pages"):
                    # fuse ticks when every active slot has a full window
                    # of tokens left AND no waiter could be admitted any
                    # sooner by single-stepping: with all remaining >=
                    # FUSE no slot can complete inside the window, so
                    # when the decoder is SATURATED (no free slot) a
                    # queued request loses zero ticks to fusion — that
                    # saturated case is exactly the latency-bound regime
                    # the fusion exists for (host-side remaining mirror:
                    # last readback, req for fresh installs)
                    waiting = (self._carry is not None
                               or not self._pending.empty())
                    fuse = ((not waiting or not self._free)
                            and all(int(last_rem[s_]) >= self._fuse_tokens
                                    for s_ in owners))
                    ticks = self._fuse if fuse else 1
                    if self.paged:
                        # decode writes march forward: hand out the pages
                        # the window will cross (reserved at admission)
                        # and run the COW barrier over the write range.
                        # A block model's pass writes its block's B
                        # positions, and a round of `ticks` passes can
                        # commit every other pass: the pages are there
                        # before a block's first pass.
                        for s_, r in owners.items():
                            start = int(last_pos[s_])
                            if self.B:
                                r.passes += ticks
                                end = min(start + self.B * (1 + ticks // 2),
                                          self._block_end(r))
                            else:
                                end = start + ticks
                            self.alloc.append(s_, end)
                            copies = self.alloc.write_barrier(s_, start, end)
                            if copies:
                                self.state = self._apply_copies(
                                    self.state, *self._cow_arrays(copies))
                            if not self.B:   # (a block pass counts its own)
                                self._counters["kv_pages_walked"] += sum(
                                    self._pages_seen(r.pad, pos)
                                    for pos in range(start, start + ticks))
                        self._counters["kv_pages_tabled"] += (
                            ticks * self.alloc.table.size)
                        pt = jnp.asarray(self.alloc.table)
                        args = (self._params, self.state, pt)
                    else:
                        args = (self._params, self.state)
                with phase("tick", fused=int(fuse)), \
                        (ctx or contextlib.nullcontext()):
                    self.state = (self._step_fused if fuse else
                                  self._step)(*args)
                self._counters["rounds"] += 1
                with phase("readback"):
                    # the host blocks here until the device has caught up
                    remaining = np.asarray(self.state[3])
                    # writable copies: admission writes fresh slots' mirrors
                    last_rem = np.array(remaining)
                    last_pos = np.array(self.state[2])
                    done = [s_ for s_ in owners if remaining[s_] <= 0]
                    # one readback of the tokens per round, and only
                    # where a slot finished
                    out = np.asarray(self.state[4]) if done else None
                    if self.B:
                        # the round's counts, from the same read-back
                        for name, n in zip(BLOCK_COUNTERS, np.asarray(
                                self.state[1]["ctr"]).tolist()):
                            self._counters[name] += n
                        out_at = (np.asarray(self.state[1]["out_at"])
                                  if done else None)
                with phase("complete"):
                    self._note_first_tokens(owners.values())
                    for s_ in done:
                        r = owners.pop(s_)
                        if self.B:
                            r.fixed_at = [int(t) for t in out_at[s_][:r.req]]
                        r.finish(int(t) for t in out[s_][:r.req])
                        self._free.append(s_)
                        self._counters["completed"] += 1
                        if self.paged:
                            self.alloc.free(s_)
                    self._publish_pages()
                    self._note_active(owners)
            except Exception as e:  # a broken step: poison + rebuild
                log.exception("slot-decoder loop failed")
                fail_all(e)
                self._active = 0
        # shutdown: fail any stragglers
        self._drain_shutdown(owners)

    # -- admission: dense (batched idle-burst prefill) ---------------------

    def _admit_dense(self, owners, fail_all, last_rem) -> None:
        import numpy as np

        jnp = self._jnp
        phase = self._phase
        ctx = self.mesh if self.mesh is not None else None
        if not (self._free and not self._pending.empty()):
            return
        with phase("admit"):
            # admit pending requests into free slots (step boundary).
            # Idle decoder: take a BATCH of waiting prompts (padded
            # up to the next supported prefill size) so an idle
            # burst prefills together. Anything mid-generation:
            # admit at most ONE per tick — a burst must not stall
            # in-flight decodes.
            want = 1 if owners else len(self._free)
            batch = []
            while len(batch) < want and not self._pending.empty():
                batch.append(self._pending.get_nowait())
            # validate rows FIRST; a wrong-length row (the submit_padded
            # caller's bug) fails THAT caller only and never enters the
            # batch, so row indices below stay aligned with the prefill
            # outputs
            batch = [r for r in batch if self._validate(r)]
            if not batch:
                return
            k = next(n for n in self._PREFILL_SIZES if n >= len(batch))
            prompts = np.zeros((k, self.P), np.int32)
            pads = np.zeros((k,), np.int32)
            news = np.zeros((k,), np.int32)
            for i, r in enumerate(batch):
                prompts[i] = r.prompt
                pads[i] = r.pad
                news[i] = r.req
            slots = [self._free.pop() for _ in range(len(batch))]
            # dummy rows (k > len(batch)) target REMAINING free slots:
            # they hold no generation, and any future real install fully
            # overwrites the row. Idle admission guarantees enough free
            # slots (batch <= free == S >= k); active admission is always
            # k == batch == 1.
            dummies = self._free[:k - len(slots)]
            pad_slots = slots + dummies
            assert len(pad_slots) == k, (k, slots, dummies)
        try:
            with phase("prefill"), (ctx or contextlib.nullcontext()):
                cache_k, logits_k = self._prefill(
                    self._params, jnp.asarray(prompts), jnp.asarray(pads))
                new_state = self._install(
                    self.state, cache_k, logits_k,
                    jnp.asarray(pad_slots, jnp.int32),
                    jnp.asarray(pads), jnp.asarray(news))
        except Exception as e:
            self._free.extend(slots)
            fail_all(e, batch)
            return
        with phase("admit"):
            self.state = new_state
            # dummy installs left remaining>0 on their free slots: zero
            # them so the step loop never decodes an unowned slot
            if dummies:
                self.state = self._clear_slots(
                    self.state, jnp.asarray(dummies, jnp.int32))
            if self.meter:
                self.meter.prefill_tokens(len(batch) * self.P)
            for s_, r in zip(slots, batch):
                self._note_admitted(r, s_, self.P, owners)
                last_rem[s_] = r.req

    # -- admission: paged (per-request suffix prefill, page-gated) ---------

    def _admit_paged(self, owners, fail_all, last_rem, last_pos) -> None:
        jnp = self._jnp
        phase = self._phase
        ctx = self.mesh if self.mesh is not None else None
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
                row = r.prompt
                total = (self._block_end(r) if self.B
                         else self.P + r.req + self.draft_k)
                if not self.alloc.can_admit(row, r.pad, total):
                    # head-of-line page gate: FIFO order is preserved (no
                    # bypass) — the request waits for completions to free
                    # pages, and everything behind it waits too
                    self._carry = r
                    return
                slot = self._free.pop()
            try:
                with phase("admit"):
                    plan = self.alloc.admit(slot, row, r.pad, total)
                    # a rung of the ladder: compiled when the decoder
                    # was built
                    suffix = row[plan.compute_start:]
                with phase("prefill"), (ctx or contextlib.nullcontext()):
                    if plan.copies:
                        self.state = self._apply_copies(
                            self.state, *self._cow_arrays(plan.copies))
                    self.state = self._prefill_at[len(suffix)](
                        self._params, self.state, suffix[None, :],
                        jnp.asarray([plan.compute_start], jnp.int32),
                        jnp.asarray(self.alloc.table[slot:slot + 1]),
                        jnp.asarray([r.pad], jnp.int32),
                        jnp.int32(slot), jnp.int32(r.req),
                        *self._first_block(r))
            except Exception as e:
                # the slot's PAGES go back before the slot id does —
                # recycling the slot while the allocator still holds
                # its admission leaks every page it claimed (tpulint
                # RES701); free() is a no-op when admit itself raised
                self.alloc.free(slot)
                self._free.append(slot)
                fail_all(e, [r])
                return
            with phase("admit"):
                self._note_admitted(r, slot, len(suffix), owners)
                last_rem[slot] = r.req
                last_pos[slot] = self.P - self._tail(r)
                if self.meter:
                    self.meter.prefill_tokens(len(suffix))
                    self.meter.prefix_hits(plan.shared_pages)
                self._publish_pages()
                admitted += 1

    # -- scheduler loop (speculative lockstep) -----------------------------

    def _loop_spec(self) -> None:
        import numpy as np

        from kubeflow_tpu.runtime.speculative import (
            greedy_accept, lockstep_propose, lockstep_verify)

        jnp = self._jnp
        phase = self._phase
        k = self.draft_k
        K1 = k + 1
        owners: dict[int, _Request] = {}  # slot -> the request it serves
        out_h: dict[int, list] = {}      # slot -> emitted tokens
        ebuf: dict[int, list] = {}       # slot -> last round's emissions
        pos_h = np.zeros(self.S, np.int64)   # position of each cur token
        rem_h = np.zeros(self.S, np.int64)
        pads_h = np.zeros(self.S, np.int32)
        ctx = self.mesh if self.mesh is not None else None

        def fail_all(err, batch=()):
            for r in (*batch, *owners.values()):
                r.finish(err)
            owners.clear()
            out_h.clear()
            ebuf.clear()
            self._free = list(range(self.S))
            if self.alloc is not None:
                self.alloc.reset()
            self.t_cache = self._fresh_cache()
            self.d_cache = self._fresh_d_cache()

        def complete(slot) -> None:
            owners.pop(slot).finish(out_h.pop(slot))
            ebuf.pop(slot, None)
            self._free.append(slot)
            self._counters["completed"] += 1
            if self.paged:
                self.alloc.free(slot)
            self._publish_pages()

        def admit() -> None:
            want = 1 if owners else self.S
            admitted = 0
            while admitted < want and self._free:
                with phase("admit"):
                    r = self._next_pending()
                    if r is None:
                        return
                    if not self._validate(r):
                        continue
                    row = r.prompt
                    total = self.P + r.req + k
                    if self.paged:
                        if not self.alloc.can_admit(row, r.pad, total):
                            self._carry = r
                            return
                    slot = self._free.pop()
                try:
                    with phase("prefill"), (ctx or contextlib.nullcontext()):
                        if self.paged:
                            plan = self.alloc.admit(slot, row, r.pad, total)
                            if plan.copies:
                                from kubeflow_tpu.runtime.kvcache import \
                                    copy_pages
                                self.t_cache = copy_pages(
                                    self.t_cache,
                                    *self._cow_arrays(plan.copies))
                            suffix = row[plan.compute_start:]
                            self.t_cache, self.d_cache, first = \
                                self._spec_admit_paged(
                                    self._params, self._d_params,
                                    self.t_cache, self.d_cache,
                                    suffix[None, :],
                                    jnp.asarray([plan.compute_start],
                                                jnp.int32),
                                    jnp.asarray(
                                        self.alloc.table[slot:slot + 1]),
                                    jnp.asarray(row[None, :]),
                                    jnp.asarray([r.pad], jnp.int32),
                                    jnp.int32(slot))
                            n_pref = len(suffix)
                            hits = plan.shared_pages
                        else:
                            self.t_cache, self.d_cache, first = \
                                self._spec_admit_dense(
                                    self._params, self._d_params,
                                    self.t_cache, self.d_cache,
                                    jnp.asarray(row[None, :]),
                                    jnp.asarray([r.pad], jnp.int32),
                                    jnp.int32(slot))
                            n_pref = self.P
                            hits = 0
                except Exception as e:
                    self._free.append(slot)
                    fail_all(e, [r])
                    return
                self._note_admitted(r, slot, n_pref, owners)
                with phase("readback"):
                    # the prefill's own first token: the host blocks on it
                    cur = int(first)
                with phase("admit"):
                    self._note_first_tokens([r])
                    out_h[slot] = [cur]
                    ebuf[slot] = [cur]
                    pos_h[slot] = self.P
                    rem_h[slot] = r.req - 1
                    pads_h[slot] = r.pad
                    if self.meter:
                        self.meter.prefill_tokens(n_pref)
                        if self.paged:
                            self.meter.prefix_hits(hits)
                    self._publish_pages()
                    if rem_h[slot] <= 0:
                        # the prefill logits already satisfied a 1-token
                        # budget
                        complete(slot)
                    else:
                        admitted += 1

        while not self._stop:
            try:
                admit()
                with phase("admit"):
                    # round-boundary deadline sweep: the canceled slot's
                    # host mirrors are dropped, so the next round simply
                    # never emits for it (caches hold only dead rows)
                    expired = self._expired_slots(owners)
                    if expired:
                        for s_ in expired:
                            self._cancel_slot(owners, s_)
                            out_h.pop(s_, None)
                            ebuf.pop(s_, None)
                            rem_h[s_] = 0
                        self._publish_pages()
                    self._note_active(owners)
                if not owners:
                    with phase("idle"):
                        self._wake.wait(timeout=0.05)
                        self._wake.clear()
                    continue
                # ---- one propose/verify round over every active slot
                with phase("pages"):
                    order = sorted(owners)
                    emitted = np.zeros((self.S, K1), np.int32)
                    starts = np.zeros(self.S, np.int32)
                    elen = np.ones(self.S, np.int32)
                    curv = np.zeros(self.S, np.int32)
                    for s_ in order:
                        e = ebuf[s_]
                        emitted[s_, :len(e)] = e
                        starts[s_] = pos_h[s_] - len(e) + 1
                        elen[s_] = len(e)
                        curv[s_] = e[-1]
                        if self.paged:
                            # verify rewrites positions pos..pos+k
                            self.alloc.append(s_, int(pos_h[s_]) + K1)
                            copies = self.alloc.write_barrier(
                                s_, int(pos_h[s_]), int(pos_h[s_]) + K1)
                            if copies:
                                from kubeflow_tpu.runtime.kvcache import \
                                    copy_pages
                                self.t_cache = copy_pages(
                                    self.t_cache, *self._cow_arrays(copies))
                    pads_dev = jnp.asarray(pads_h)
                # the draft's proposals are read back between the two
                # dispatches: the verify chunk is built from them
                with phase("tick", fused=0), \
                        (ctx or contextlib.nullcontext()):
                    self.d_cache, props = lockstep_propose(
                        self.draft, self._d_params, self.d_cache,
                        jnp.asarray(emitted), jnp.asarray(starts),
                        jnp.asarray(elen), k=k, pad_len=pads_dev)
                    props_h = np.asarray(props)
                    chunk = np.zeros((self.S, K1), np.int32)
                    chunk[:, 0] = curv
                    chunk[:, 1:] = props_h
                    self.t_cache, y = lockstep_verify(
                        self.model, self._params, self.t_cache,
                        jnp.asarray(chunk),
                        jnp.asarray(pos_h, np.int32), pad_len=pads_dev,
                        **({"page_table": jnp.asarray(self.alloc.table)}
                           if self.paged else {}))
                self._counters["rounds"] += 1
                with phase("readback"):
                    y_h = np.asarray(y)
                with phase("complete"):
                    round_slots = 0
                    round_accepted = 0
                    for s_ in order:
                        a = greedy_accept(props_h[s_], y_h[s_], k)
                        emit = [int(t) for t in props_h[s_][:a]]
                        emit.append(int(y_h[s_][a]))
                        take = min(len(emit), int(rem_h[s_]))
                        emit = emit[:take]
                        out_h[s_].extend(emit)
                        ebuf[s_] = emit
                        pos_h[s_] += take
                        rem_h[s_] -= take
                        round_slots += 1
                        round_accepted += min(a, take)
                        self._counters["spec_rounds"] += 1
                        self._counters["spec_tokens_emitted"] += take
                        self._counters["spec_tokens_accepted"] += min(a, take)
                        self._counters["spec_drafted"] += k
                        if rem_h[s_] <= 0:
                            complete(s_)
                    if self.meter:
                        self.meter.spec_round(round_slots, round_accepted)
                    self._note_active(owners)
            except Exception as e:
                log.exception("speculative slot-decoder loop failed")
                fail_all(e)
                self._active = 0
        self._drain_shutdown(owners)


def _diag_sum(jax, diagnostics, name: str):
    """The sum of every layer's sow of `name` in a "diagnostics"
    collection (0 where no layer sowed it)."""
    from jax.tree_util import tree_flatten_with_path

    return sum((v for path, v in tree_flatten_with_path(diagnostics)[0]
                if any(getattr(p, "key", None) == name for p in path)), 0)


def _set1(jnp, vec, i, val):
    """vec[i] = val with a dynamic index (static-shape scatter)."""
    return jnp.where(jnp.arange(vec.shape[0]) == i,
                     jnp.asarray(val, vec.dtype), vec)
