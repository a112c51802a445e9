"""The three kinds of step that the slot decoder's one loop drives
(serving/continuous.py): one token a slot (`TokenStep`), one pass over
a block of a model that generates by diffusion over blocks
(`BlockStep`), one speculative propose-and-verify chunk (`SpecStep`).

A step owns the device state and the host's mirrors of it, the programs
of a round, and what a round writes and yields. It is handed requests
(their prompt, padding and budget), a plan and a page table; it knows
nothing of the queue, of a request's event, of the allocator's books,
of the meter or of the clock. What the loop calls, on every kind:

    runs_ahead              whether the next round may be dispatched before
                            this one is read back (its programs need
                            nothing the host reads of a round)
    end(r)                  one past the last position r may ever write
    prefill_programs(ladder, mesh)    the paged install at each rung
    install_paged(...), install_dense(...)    a first token still on the
                            device (then `first_token`), or None
    copy_pages(src, dst)    copy-on-write clones, before a program writes
    ticks(owners)           how many ticks the next round may fuse
    writes(slot, r, ticks)  the positions [start, end) the round writes
    dispatch(owners, ticks, table)    the round's programs
    readback(owners)        the oldest round not read back: (the slots
                            that finished, the round's counts,
                            `tokens_decoded` among them: the tokens of
                            answers it fixed)
    answer(slot, r)         (what `submit` returns, the span's extras)
    cancel(slots); fresh()  zero slots; rebuild after a failed round

A kind that runs ahead hands the host what it reads of a round in a
report beside the state (`TokenStep._handed_out`), whose transfer starts at
the dispatch, and the answers' tokens in a buffer no later program is
handed: the state itself goes on to the next program, donated. Its
mirrors of `remaining` and `pos` stand at the last read-back, and
`ticks` and `writes` count the rounds still in flight on top of them.
"""

from __future__ import annotations

import collections
import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.runtime import kvcache
from kubeflow_tpu.runtime.generate import _sample, init_cache, prefill_scan
from kubeflow_tpu.runtime.speculative import (
    greedy_accept, lockstep_propose, lockstep_verify)

# What a block model's pass counts on the device, in this order, over the
# slots that hold a request (`BlockStep._tick_once`):
# passes x active slots; blocks committed; the pages from each slot's
# first real position to its block's end (what the pass's attention
# walks: `kv_pages_walked`); and from the mixture layers (ops/moe.py),
# summed over layers: the routed pairs, the experts that got at least one
# (each a group whose weights the grouped matmul reads), the fullest
# expert's pairs, and the pairs whose grouped matmuls were the Pallas
# kernel's (ops/grouped_matmul.py: all of them or none).
BLOCK_COUNTERS = ("block_passes", "blocks_committed", "kv_pages_walked",
                  "moe_pairs", "moe_expert_visits", "moe_load_max",
                  "moe_kernel_pairs")
# What a one-token tick of a model with mixture layers counts on the
# device, over the active slots and summed over the layers: the mixture's
# four above (of the pairs whose experts this chip holds) and every pair
# the tokens were routed, held here or not (ops/moe.py).
MOE_COUNTERS = BLOCK_COUNTERS[3:] + ("moe_pairs_routed",)
# What a prompt's rung counts on the device where its mixture layers hold
# a share of the experts and compact their rows to the held pairs
# (ops/moe.py:compact_bound): the layers that did, and those of them
# whose held pairs overran the window. The rung's program hands them out
# beside the state: the ticks' counters are zeroed at every dispatch and
# mean ticks only.
RUNG_COUNTERS = ("moe_compact_calls", "moe_compact_spills")


class TokenStep:
    """One token a slot a tick, over the dense slot cache or (the model
    built with kv_pages) the paged one. State: (cache, last logits, pos,
    remaining, out, pads, req, rng), donated to every program; a model
    with mixture layers adds a ninth, the ticks' MOE_COUNTERS since the
    last dispatch. Where the model keeps pages by layer kind
    (cfg.kv_window_pages) a page table is the pair (held kind's, window
    kind's). `fresh_prefill`: the paged prefill attends through the flash
    kernel (the decoder's rule, serving/continuous.py) over the rung's
    own keys, and where the decoder's prefix cache is on (`prefix_hits`)
    behind the pages before the rung if a hit lies there: both cases in
    the rung's one program, told apart by `pad` against the rung's first
    position."""

    # -- FUSE ticks in one dispatched program. Each dispatch costs a
    #    round of the host: the launch, the read-back, the loop's
    #    bookkeeping. By the loop's own books on a v5e (PERF.md section
    #    5, an 8-layer Mistral-7B over 32 slots), a plain round took
    #    8.3-8.6 ms of wall time around a tick of 5.2 ms: about 3 ms of
    #    the host's a round, and `sched.host_ms_per_round.chat` reads
    #    4.1-4.7 ms over every round; an admission adds 9-10 ms. Fusing
    #    pays that once for FUSE ticks; where the loop keeps a round in
    #    flight (a full decoder, a kind that runs ahead) the host's round
    #    runs behind the device's instead. Correctness is unchanged: the
    #    tick body masks on remaining>0, so a slot finishing mid-window
    #    just idles until the window ends; the cost is admission and
    #    completion latency bounded at FUSE ticks, which is why the loop
    #    only fuses when nothing is waiting (or no slot is free) and
    #    every active slot has >= FUSE tokens to go. ------------------
    FUSE = 8
    # a round's programs take nothing from the host but the page table,
    # so the next one may be dispatched before this one is read back
    runs_ahead = True
    _opening_shapes = ()    # of `_opening`'s arguments to the paged install
    # what a tick of a model with mixture layers counts in the state's
    # ninth leaf (a block model's pass counts them among its own)
    _tick_counters = MOE_COUNTERS

    def __init__(self, model, params, slots: int, prompt_len: int,
                 max_new_tokens: int, pages_per_row: int = 0, *,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 fresh_prefill: bool = False, prefix_hits: bool = False):
        self.model, self.params = model, params
        self.S, self.P, self.N = slots, prompt_len, max_new_tokens
        # a paged cache's table row, in pages; 0: the dense slot cache
        self.mp = pages_per_row
        self.page_size = model.cfg.kv_page_size if pages_per_row else 0
        cfg = model.cfg
        specs = cfg.layers() if hasattr(cfg, "layers") else ()
        self.two_kinds = bool(pages_per_row
                              and getattr(cfg, "kv_window_pages", 0))
        # what a tick's attention walks, a table at a time: the windows
        # of the layers that read it (`writes`)
        self._walks = sorted({(int(bool(s.window and self.two_kinds)),
                               s.window) for s in specs}) or [(0, 0)]
        # the mixture's counters ride the state of a one-token model too
        self.counted = (self._tick_counters
                        if any(s.moe for s in specs) else ())
        # the rungs' own counters, where a mixture layer holds a share
        self.rung_counted = (
            RUNG_COUNTERS if any(s.moe for s in specs)
            and getattr(cfg, "n_experts_total", 0) > cfg.n_experts else ())
        self._fresh_prefill, self._prefix_hits = fresh_prefill, prefix_hits
        # a model with latent layers: the bytes a position takes in a
        # layer's pool, and whether a tick's latent attention is the
        # Pallas kernel's (the rule's own answer, asked once more than
        # the layers ask it)
        self.latent_row_bytes, self._latent_kernel = 0, False
        if pages_per_row and any(s.latent for s in specs):
            from kubeflow_tpu.models.transformer import latent_row_width
            from kubeflow_tpu.ops import paged_latent_attention as pla

            width = latent_row_width(cfg)
            self.latent_row_bytes = width * jnp.dtype(cfg.dtype).itemsize
            self._latent_kernel = pla.use_kernel(
                1, (cfg.kv_pages, cfg.kv_page_size, width), cfg.dtype)
        self.temperature, self.top_k, self.seed = temperature, top_k, seed
        # the most tokens a slot can finish in a fused round
        self.fuse_tokens = self.FUSE
        # prefill batch sizes we're willing to compile (smallest >= the
        # waiting count is used; idle bursts prefill together)
        self._PREFILL_SIZES = tuple(sorted(
            {n for n in (1, 2, 4, 8, 16, 32) if n < slots} | {slots}))

        # Params are jit ARGUMENTS everywhere below, never closure
        # captures: a closed-over weight tree is serialized into the
        # program as inline constants — a gpt-350m continuous decoder
        # carries ~700MB of MLIR to the compiler and into the compile
        # cache key — and every weight swap becomes a full retrace.
        # server.py's predict path (fwd(params, x)) does the same.

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
            cache, last, pos, remaining, out, pads, req, rng, *more = state
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
                pos = _set1(pos, si, self.P)
                remaining = _set1(remaining, si, news_k[i])
                out = jax.lax.dynamic_update_slice(
                    out, jnp.zeros((1, self.N), jnp.int32), (si, 0))
                pads = _set1(pads, si, pads_k[i])
                req = _set1(req, si, news_k[i])
            return (cache, last, pos, remaining, out, pads, req, rng, *more)

        self._install = jax.jit(_install, donate_argnums=(0,))

        # -- compiled: deactivate slots (dummy prefill targets, cancels) -
        def _clear_slots(state, slots_k):
            cache, last, pos, remaining, out, pads, req, rng, *more = state
            clear = (jnp.arange(self.S)[:, None]
                     == slots_k[None, :]).any(axis=1)
            remaining = jnp.where(clear, 0, remaining)
            return (cache, last, pos, remaining, out, pads, req, rng, *more)

        self._clear_slots = jax.jit(_clear_slots, donate_argnums=(0,))

        # -- compiled: paged prefill of ONE request's prompt suffix +
        #    install. The suffix is the shortest rung of the decoder's
        #    ladder that covers the real tokens no prefix hit covers (the
        #    allocator's plan), so the function is traced at the ladder's
        #    lengths and at no other. The function's name is a contract
        #    at every rung: the benchmark finds the XLA module
        #    `jit__paged_prefill_install` by it
        #    (benchmarks/metrics/*.json; tests/test_trace_names.py).
        #    `block`: what a block model's request opens with ------------
        def _paged_prefill_install(params, state, toks, start, pt_row,
                                   pad, slot, req_n, *block):
            cache, last, pos, remaining, out, pads, req, rng, *more = state
            fresh = {}
            if self._fresh_prefill:
                fresh["fresh"] = True
                # a rung ends at prompt_len: where it starts is static
                if self._prefix_hits and toks.shape[1] < self.P:
                    fresh["hit_below"] = self.P - toks.shape[1]
            compacts = self._compacts(toks.shape[1])
            logits, mut = model.apply(
                params | {"cache": cache}, toks, train=False,
                decode_index=start,
                mutable=["cache", "diagnostics"] if compacts else ["cache"],
                pad_len=pad, page_table=pt_row, **fresh)
            cache = mut["cache"]
            last, first_pos = self._open(last, logits, slot, *block)
            pos = _set1(pos, slot, first_pos)
            remaining = _set1(remaining, slot, req_n)
            out = jax.lax.dynamic_update_slice(
                out, jnp.zeros((1, self.N), jnp.int32), (slot, 0))
            pads = _set1(pads, slot, pad[0])
            req = _set1(req, slot, req_n)
            state = (cache, last, pos, remaining, out, pads, req, rng, *more)
            if compacts:
                return state, jnp.stack([
                    jnp.asarray(_diag_sum(mut["diagnostics"], n), jnp.int32)
                    for n in RUNG_COUNTERS])
            return state

        self._paged_prefill_install = jax.jit(
            _paged_prefill_install, donate_argnums=(1,))

        # -- compiled: apply COW page clones before a program writes ----
        def _apply_copies(state, src, dst):
            return (kvcache.copy_pages(state[0], src, dst),) + tuple(state[1:])

        self._apply_copies = jax.jit(_apply_copies, donate_argnums=(0,))

        # -- compiled: one lockstep decode tick for all S slots, and FUSE
        #    of them in one program. The names are a contract too: the
        #    modules are `jit__tick` and `jit__step_fused` in the device
        #    trace, and the benchmark reads them by that (a block model's
        #    pass takes the tick's place under the same names). Each
        #    hands out the state, then the round's report and its answers'
        #    tokens (`_handed_out`) ------------------------------------
        def _tick(params, state, page_table=None):
            return self._handed_out(state, self._tick_once(
                params, self._counted_from_zero(state), page_table))

        def _step_fused(params, state, page_table=None):
            def body(st, _):
                return self._tick_once(params, st, page_table), None

            st, _ = jax.lax.scan(body, self._counted_from_zero(state), None,
                                 length=self.FUSE)
            return self._handed_out(state, st)

        if self.mp:
            self._step = jax.jit(_tick, donate_argnums=(1,))
            self._step_fused = jax.jit(_step_fused, donate_argnums=(1,))
        else:
            # dense signature stays (params, state): the trace spies in
            # tests rely on it
            self._step = jax.jit(lambda params, state: _tick(params, state),
                                 donate_argnums=(1,))
            self._step_fused = jax.jit(
                lambda params, state: _step_fused(params, state),
                donate_argnums=(1,))
        self.fresh()

    # -- what the programs are made of (a block model's differ) ----------

    def _tick_once(self, params, state, page_table):
        cache, last, pos, remaining, out, pads, req, rng, *more = state
        active = remaining > 0
        rng, sub = jax.random.split(rng)
        tok = _sample(last, self.temperature, self.top_k, sub)
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
        logits_next, mut = self.model.apply(
            params | {"cache": cache}, tok[:, None], train=False,
            decode_index=pos,
            mutable=["cache", "diagnostics"] if self.counted else ["cache"],
            pad_len=jnp.where(active, pads, pos + 1),
            **({"page_table": page_table}
               if page_table is not None else {}))
        pos = jnp.where(active, pos + 1, pos)
        remaining = jnp.where(active, remaining - 1, remaining)
        last = jnp.where(active[:, None], logits_next[:, 0], last)
        if self.counted:
            more = [more[0] + jnp.stack([
                jnp.asarray(_diag_sum(mut.get("diagnostics", {}), n),
                            jnp.int32) for n in self.counted])]
        return (mut["cache"], last, pos, remaining, out, pads, req, rng,
                *more)

    def _counted_from_zero(self, state):
        """A dispatched program counts from zero: the host adds each
        round's counts to its own, which never wrap."""
        if self.counted:
            return tuple(state[:8]) + (jnp.zeros_like(state[8]),)
        return state

    def _handed_out(self, before, after) -> tuple:
        """What a round's program returns: the state it leaves, which the
        next program is handed and consumes; then the report, all the
        host reads of every round in one array (each slot's `remaining`,
        then its `pos`, then the tokens of answers the round fixed, then
        what it counted on the device); then the answers' tokens, which
        no later program is handed, read where a slot finished."""
        report = jnp.concatenate([
            after[3], after[2], jnp.sum(before[3] - after[3])[None],
            self._device_counts(after)])
        return (*after, report, self._answers(after))

    def _device_counts(self, state):
        return state[8] if self.counted else jnp.zeros((0,), jnp.int32)

    def _answers(self, state):
        return state[4]

    def _compacts(self, length: int) -> bool:
        """Whether the rung of `length` positions compacts its mixture
        layers' rows (the layers' own rule): its program then hands out
        RUNG_COUNTERS beside the state."""
        from kubeflow_tpu.ops.moe import compact_bound

        cfg = self.model.cfg
        return bool(self.rung_counted) and compact_bound(
            length * cfg.expert_top_k, cfg.n_experts,
            cfg.n_experts_total) is not None

    def _open(self, last, logits, slot):
        """The paged install's part that is the step's own: the slot's
        entry in `last`, and the position its first tick writes."""
        return (jax.lax.dynamic_update_slice(
            last, logits[:, -1], (slot, 0)), self.P)

    def _fresh_last(self):
        return jnp.zeros((self.S, self.model.cfg.vocab_size), jnp.float32)

    def _opening(self, slot: int, r) -> tuple:
        """The paged install's last arguments, and where the request's
        first write lands. Nothing, and prompt_len, for one token."""
        return (), self.P

    # -- what the loop calls ----------------------------------------------

    def fresh(self) -> None:
        """Device state (a failed donated call leaves the old buffers
        dead) and the host's mirrors of `remaining` and `pos`."""
        self.state = (
            (kvcache.init_paged_cache(self.model, self.mp) if self.mp
             else init_cache(self.model, self.S)),
            self._fresh_last(),
            jnp.zeros((self.S,), jnp.int32),            # pos
            jnp.zeros((self.S,), jnp.int32),            # remaining
            jnp.zeros((self.S, self.N), jnp.int32),     # out
            jnp.zeros((self.S,), jnp.int32),            # pad_len
            jnp.zeros((self.S,), jnp.int32),            # req budget
            jax.random.PRNGKey(self.seed),
            *((jnp.zeros((len(self.counted),), jnp.int32),)
              if self.counted else ()),
        )
        # last readback; admission writes fresh slots' mirrors
        self.rem = np.zeros(self.S, np.int64)
        self.pos = np.zeros(self.S, np.int64)
        self._walked = self._walked_window = 0
        # the admitted rungs' counts, on the device until the read-back
        # of the round dispatched behind them
        self._rung_counts: list = []
        # the rounds dispatched and not read back, oldest first
        self._flight: collections.deque = collections.deque()

    def end(self, r) -> int:
        return self.P + r.req

    def prefill_programs(self, ladder, mesh) -> dict:
        """Suffix length -> the prefill compiled (or
        loaded from the compile cache) for it from abstract shapes, every
        rung of the ladder, before the first request: a request never
        meets a compilation, whatever its length. This thread traces and
        lowers one rung after another (threads would only pass the
        interpreter lock around: side by side the rungs took longer on a
        v5e's host than one after another), and each lowered program
        compiles or loads in the pool meanwhile. Nothing runs on the
        device here."""
        import concurrent.futures as cf

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        row = i32(1, self.mp)

        def lowered(length):
            return self._paged_prefill_install.lower(
                self.params, self.state, i32(1, length), i32(1),
                (row, row) if self.two_kinds else row, i32(1), i32(), i32(),
                *self._opening_shapes)

        with cf.ThreadPoolExecutor(len(ladder)) as pool, \
                (mesh or contextlib.nullcontext()):
            jobs = [pool.submit(lowered(n).compile) for n in ladder]
        return dict(zip(ladder, (job.result() for job in jobs)))

    def install_paged(self, program, r, slot: int, start: int, table_row):
        block, first_pos = self._opening(slot, r)
        self.state = program(
            self.params, self.state, r.prompt[None, start:],
            jnp.asarray([start], jnp.int32),
            jax.tree.map(jnp.asarray, table_row),
            jnp.asarray([r.pad], jnp.int32), jnp.int32(slot),
            jnp.int32(r.req), *block)
        if self._compacts(self.P - start):
            self.state, counts = self.state
            self._rung_counts.append(counts)
        self.rem[slot], self.pos[slot] = r.req, first_pos
        self._leave([slot])

    def install_dense(self, batch, slots, spare) -> list:
        """One batched prefill and one install for an idle burst, padded
        up to the next compiled size."""
        k = next(n for n in self._PREFILL_SIZES if n >= len(batch))
        prompts = np.zeros((k, self.P), np.int32)
        pads = np.zeros((k,), np.int32)
        news = np.zeros((k,), np.int32)
        for i, r in enumerate(batch):
            prompts[i], pads[i], news[i] = r.prompt, r.pad, r.req
        # dummy rows (k > len(batch)) target REMAINING free slots:
        # they hold no generation, and any future real install fully
        # overwrites the row. Idle admission guarantees enough free
        # slots (batch <= free == S >= k); active admission is always
        # k == batch == 1.
        dummies = spare[:k - len(slots)]
        assert len(slots) + len(dummies) == k, (k, slots, dummies)
        cache_k, logits_k = self._prefill(
            self.params, jnp.asarray(prompts), jnp.asarray(pads))
        self.state = self._install(
            self.state, cache_k, logits_k,
            jnp.asarray(slots + dummies, jnp.int32),
            jnp.asarray(pads), jnp.asarray(news))
        # dummy installs left remaining>0 on their free slots: zero
        # them so the step loop never decodes an unowned slot
        if dummies:
            self.cancel(dummies)
        for s_, r in zip(slots, batch):
            self.rem[s_] = r.req
        self._leave(slots)
        return [None] * len(batch)

    def copy_pages(self, src, dst) -> None:
        self.state = self._apply_copies(self.state, src, dst)

    def _leave(self, slots) -> None:
        """The slots' requests have gone (an install put another in, or
        a cancel none): the rounds in flight no longer serve them."""
        for rnd in self._flight:
            rnd.slots.difference_update(slots)

    def _most(self, ticks: int) -> int:
        """The most tokens a slot can finish in a round of `ticks`."""
        return ticks

    def _flying(self, slot: int) -> int:
        """The ticks of the rounds in flight that serve the slot's
        request."""
        return sum(rnd.ticks for rnd in self._flight if slot in rnd.slots)

    def _left(self, slot: int) -> int:
        """The least the slot's request can have to go when the next
        round starts: its mirror, less the most the rounds in flight can
        take (exactly what they take, a token a tick)."""
        return max(int(self.rem[slot]) - self._most(self._flying(slot)), 0)

    def ticks(self, owners) -> int:
        return (self.FUSE if all(self._left(s_) >= self.fuse_tokens
                                 for s_ in owners) else 1)

    def writes(self, slot: int, r, ticks: int) -> tuple:
        # the rounds in flight moved the slot on a position a token; one
        # that finished there idles, and its ticks write only its
        # frozen position (past the answer's end: its own last page or
        # the trash page)
        left = self._left(slot)
        start = int(self.pos[slot]) + int(self.rem[slot]) - left
        end = start + min(ticks, left)
        # walked: the pages that hold what a query at `pos` sees behind
        # r.pad positions of left padding, the range `_decode_paged` hands
        # the paged attention kernel (models/transformer.py); where the
        # layers' windows differ, once a window
        for kind, window in self._walks:
            pages = 0
            for pos in range(start, end):
                first = max(r.pad, pos - window + 1) if window else r.pad
                pages += pos // self.page_size - first // self.page_size + 1
            self._walked += pages
            self._walked_window += kind * pages
        return start, end

    def dispatch(self, owners, ticks: int, table) -> None:
        n = len(self.state)
        handed = (self._step_fused if ticks > 1 else self._step)(
            self.params, self.state, *(() if table is None else (table,)))
        self.state, (report, answers) = tuple(handed[:n]), handed[n:]
        report.copy_to_host_async()
        self._flight.append(_Round(owners, ticks, report, answers,
                                   self._host_counts(ticks, table),
                                   self._rung_counts))
        self._rung_counts = []

    def _host_counts(self, ticks: int, table) -> dict:
        """How much of the page table the round's ticks walk (one tick
        = one query a slot): the pages that hold what each active slot's
        query sees, beside ticks x every entry of the table, which is
        what gathering the table touches; counted by `writes`."""
        counts = {}
        if self.mp:
            counts = {"kv_pages_walked": self._walked,
                      "kv_pages_tabled": ticks * len(self._walks) * (
                          table[0] if self.two_kinds else table).size}
        if self.two_kinds:      # of `walked`, the window kind's table's part
            counts["kv_pages_walked_window"] = self._walked_window
        self._walked = self._walked_window = 0
        if self.latent_row_bytes:   # of the round's ticks, the kernel's
            counts["attn_latent_kernel_ticks"] = (
                ticks if self._latent_kernel else 0)
        return counts

    def readback(self, owners) -> tuple:
        rnd = self._flight.popleft()
        # the round's one transfer, started at its dispatch: the host
        # blocks here until the device has caught up
        report = np.asarray(rnd.report)
        S = self.S
        remaining, pos = report[:S], report[S:2 * S]
        # the slots whose requests the round served and still hold them
        held = [s_ for s_ in owners if s_ in rnd.slots]
        self.rem[held], self.pos[held] = remaining[held], pos[held]
        done = [s_ for s_ in held if remaining[s_] <= 0]
        # the tokens are read in a round where a slot finished, only
        self._out = np.asarray(rnd.answers) if done else None
        self._served(held, rnd.ticks)
        counts = dict(rnd.counts, tokens_decoded=int(report[2 * S]))
        counts.update(zip(self._device_counted,
                          report[2 * S + 1:].tolist()))
        counts.update(self._rungs_read(rnd.rungs))
        return done, counts

    @property
    def _device_counted(self) -> tuple:
        return self.counted

    def _served(self, slots, ticks: int) -> None:
        """The read-back found these slots' requests were served by a
        round of `ticks` (they were active at its start)."""

    def _rungs_read(self, rungs) -> dict:
        """RUNG_COUNTERS of the rungs admitted before a round's
        dispatch, whose programs it ran behind."""
        total = np.zeros(len(self.rung_counted), np.int64)
        for counts in rungs:
            total += np.asarray(counts)
        return dict(zip(self.rung_counted, total.tolist()))

    def answer(self, slot: int, r) -> tuple:
        return [int(t) for t in self._out[slot][:r.req]], {}

    def cancel(self, slots) -> None:
        """Zero the slots' remaining: the masked tick then treats them
        as idle."""
        self.state = self._clear_slots(self.state,
                                       jnp.asarray(slots, jnp.int32))
        self.rem[list(slots)] = 0
        self._leave(slots)


class _Round:
    """A round from its dispatch to its read-back: the slots whose
    requests it serves (`_leave` takes out those that go before it is
    read), its ticks, what its program handed out beside the state (the
    report, whose transfer started at the dispatch, and the answers'
    tokens), what the host counted of it, and the counts of the rungs
    admitted before it."""

    __slots__ = ("slots", "ticks", "report", "answers", "counts", "rungs")

    def __init__(self, owners, ticks, report, answers, counts, rungs):
        self.slots = set(owners)
        self.ticks, self.report, self.answers = ticks, report, answers
        self.counts, self.rungs = counts, rungs


class BlockStep(TokenStep):
    """A block model's step (cfg.gen_block = B > 0; paged, greedy): each
    slot holds one block of B positions, and a tick is one denoising or
    committing pass over every slot's block, which fixes `per` masked
    positions. In the place of the last logits the state holds the
    blocks (`_fresh_last`); an answer is its tokens and the step of its
    block at which each was fixed."""

    _tick_counters = ()     # BLOCK_COUNTERS, in `blk["ctr"]`

    def __init__(self, model, params, *geometry, seed: int = 0):
        self.B = model.cfg.gen_block
        self.per = self.B // (getattr(model.cfg, "gen_steps", 0) or self.B)
        self.passes: dict = {}   # slot -> the passes its request has been through
        super().__init__(model, params, *geometry, seed=seed)
        # a block takes a denoising pass and a committing one at the least
        self.fuse_tokens = self.B * -(-self.FUSE // 2)
        self._opening_shapes = ((jax.ShapeDtypeStruct((self.B,), jnp.int32),
                                 jax.ShapeDtypeStruct((), jnp.int32)),)

    def _tick_once(self, params, state, page_table):
        B, model = self.B, self.model
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
        _, pick = jax.lax.top_k(jnp.where(masked, conf, -1.0), self.per)
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
            *(_diag_sum(mut.get("diagnostics", {}), n)
              for n in BLOCK_COUNTERS[3:])]
        blk = {"tok": jnp.where(keep, tok, 0),
               "fixed": fixed & keep,
               "at": jnp.where(keep, at, 0),
               "step": jnp.where(commit, 0, step),
               "out_at": out_at,
               "ctr": blk["ctr"] + jnp.stack(
                   [jnp.asarray(c, jnp.int32) for c in counted])}
        return (mut["cache"], blk, pos, remaining, out, pads, req, rng)

    def _counted_from_zero(self, state):
        """A dispatched program counts from zero: the host adds each
        round's counts to its own, which never wrap."""
        blk = dict(state[1], ctr=jnp.zeros_like(state[1]["ctr"]))
        return (state[0], blk) + tuple(state[2:])

    def _open(self, last, logits, slot, block):
        # the prompt's whole blocks are committed by the prefill's pass
        # (no position sees a later block); the tokens behind them
        # (`tail` [B], the first `n_tail` real) open the slot's first
        # block as fixed, and the block steps write those positions anew
        tail, n_tail = block
        mine = jnp.arange(self.S) == slot
        blk = dict(last)
        blk["tok"] = jnp.where(mine[:, None], tail[None, :], blk["tok"])
        blk["fixed"] = jnp.where(
            mine[:, None], (jnp.arange(self.B) < n_tail)[None, :],
            blk["fixed"])
        blk["at"] = jnp.where(mine[:, None], 0, blk["at"])
        blk["step"] = jnp.where(mine, 0, blk["step"])
        blk["out_at"] = jnp.where(mine[:, None], 0, blk["out_at"])
        return blk, self.P - n_tail

    def _fresh_last(self):
        """What the next pass starts from: each slot's block (its
        tokens, which are fixed, the step each was fixed at, the
        denoising passes so far), the step of every output token,
        and the counts of BLOCK_COUNTERS since the last dispatch.
        Maskedness is `fixed`, never a comparison with the MASK id:
        a prompt may hold that id."""
        S, B = self.S, self.B
        return {"tok": jnp.zeros((S, B), jnp.int32),
                "fixed": jnp.zeros((S, B), bool),
                "at": jnp.zeros((S, B), jnp.int32),
                "step": jnp.zeros((S,), jnp.int32),
                "out_at": jnp.zeros((S, self.N), jnp.int32),
                "ctr": jnp.zeros((len(BLOCK_COUNTERS),), jnp.int32)}

    def _tail(self, r) -> int:
        """The prompt's last tokens that fill no whole block: they open
        the request's first block as fixed."""
        return (self.P - r.pad) % self.B

    def end(self, r) -> int:
        """One past the last position of the block the answer ends in:
        every position the request's passes write."""
        tail = self._tail(r)
        return self.P - tail + -(-(tail + r.req) // self.B) * self.B

    def _opening(self, slot: int, r) -> tuple:
        """The request's first block: its tokens and how many of them
        the prompt fixed."""
        tail = self._tail(r)
        toks = np.zeros(self.B, np.int32)
        toks[:tail] = r.prompt[self.P - tail:]
        self.passes[slot] = 0
        return ((jnp.asarray(toks), jnp.int32(tail)),), self.P - tail

    def _most(self, ticks: int) -> int:
        # a block is a denoising pass at the least and a committing one:
        # every other pass can commit B tokens
        return self.B * -(-ticks // 2)

    def writes(self, slot: int, r, ticks: int) -> tuple:
        # a pass writes its block's B positions, and a round of `ticks`
        # passes can commit every other pass: the pages are there
        # before a block's first pass, and before that of every block
        # the rounds in flight may have opened
        start = int(self.pos[slot])
        ahead = self._most(self._flying(slot))
        return start, min(start + ahead + self.B * (1 + ticks // 2),
                          self.end(r))

    def _device_counts(self, state):
        return state[1]["ctr"]

    @property
    def _device_counted(self) -> tuple:
        return BLOCK_COUNTERS

    def _answers(self, state):
        # the tokens and the step each was fixed at, in one buffer
        return jnp.stack([state[4], state[1]["out_at"]])

    def _host_counts(self, ticks: int, table) -> dict:
        # (the pages a pass walks are counted on the device)
        return {"kv_pages_tabled": ticks * len(self._walks) * table.size}

    def _served(self, slots, ticks: int) -> None:
        for s_ in slots:
            self.passes[s_] += ticks

    def answer(self, slot: int, r) -> tuple:
        tokens, fixed_at = self._out[:, slot, :r.req].tolist()
        return ({"tokens": tokens, "fixed_at": fixed_at},
                {"blocks": -(-(self._tail(r) + r.req) // self.B),
                 "passes": int(self.passes[slot])})


class SpecStep:
    """Greedy lockstep propose-and-verify (runtime/speculative.py): the
    draft proposes k tokens a slot and the target verifies every slot's
    chunk in ONE [S, k+1] forward. State: the target's cache (dense or
    paged) and the draft's dense one; the positions, budgets and tokens
    live on the host, which reads every round's proposals and targets."""

    # the verify chunk is built from what the host reads of the round
    # before: each round is read back before the next is dispatched
    runs_ahead = False

    def __init__(self, model, params, draft, d_params, k: int,
                 slots: int, prompt_len: int, max_new_tokens: int,
                 pages_per_row: int = 0):
        self.model, self.params = model, params
        self.draft, self.d_params, self.k = draft, d_params, k
        self.S, self.P, self.N = slots, prompt_len, max_new_tokens
        self.mp = pages_per_row

        # -- compiled: speculative admission (prefill target + draft,
        #    install into slot rows, return the first greedy token) ----
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
        self.fresh()

    def fresh(self) -> None:
        self.state = ((kvcache.init_paged_cache(self.model, self.mp) if self.mp
                       else init_cache(self.model, self.S)),
                      init_cache(self.draft, self.S))
        self.out_h: dict[int, list] = {}     # slot -> emitted tokens
        self.ebuf: dict[int, list] = {}      # slot -> last round's emissions
        self.pos_h = np.zeros(self.S, np.int64)  # position of each cur token
        self.rem_h = np.zeros(self.S, np.int64)
        self.pads_h = np.zeros(self.S, np.int32)

    def end(self, r) -> int:
        # the verify chunk's overhang past the last token
        return self.P + r.req + self.k

    def prefill_programs(self, ladder, mesh) -> dict:
        """One jitted admission: a rung compiles when a request first
        computes it."""
        return dict.fromkeys(ladder, self._spec_admit_paged)

    def install_paged(self, program, r, slot: int, start: int, table_row):
        t_cache, d_cache, first = program(
            self.params, self.d_params, *self.state, r.prompt[None, start:],
            jnp.asarray([start], jnp.int32), jnp.asarray(table_row),
            jnp.asarray(r.prompt[None, :]), jnp.asarray([r.pad], jnp.int32),
            jnp.int32(slot))
        self.state = (t_cache, d_cache)
        return first

    def install_dense(self, batch, slots, spare) -> list:
        firsts = []
        for s_, r in zip(slots, batch):
            t_cache, d_cache, first = self._spec_admit_dense(
                self.params, self.d_params, *self.state,
                jnp.asarray(r.prompt[None, :]),
                jnp.asarray([r.pad], jnp.int32), jnp.int32(s_))
            self.state = (t_cache, d_cache)
            firsts.append(first)
        return firsts

    def first_token(self, slot: int, r, first) -> bool:
        """The prefill's own first token, which the host blocks on; true
        where it already met a budget of one."""
        cur = int(first)
        self.out_h[slot] = [cur]
        self.ebuf[slot] = [cur]
        self.pos_h[slot] = self.P
        self.rem_h[slot] = r.req - 1
        self.pads_h[slot] = r.pad
        return r.req <= 1

    def copy_pages(self, src, dst) -> None:
        self.state = (kvcache.copy_pages(self.state[0], src, dst),
                      self.state[1])

    def ticks(self, owners) -> int:
        return 1

    def writes(self, slot: int, r, ticks: int) -> tuple:
        # verify rewrites positions pos..pos+k
        start = int(self.pos_h[slot])
        return start, start + self.k + 1

    def dispatch(self, owners, ticks: int, table) -> None:
        K1 = self.k + 1
        self._order = sorted(owners)
        emitted = np.zeros((self.S, K1), np.int32)
        chunk = np.zeros((self.S, K1), np.int32)
        starts = np.zeros(self.S, np.int32)
        elen = np.ones(self.S, np.int32)
        for s_ in self._order:
            e = self.ebuf[s_]
            emitted[s_, :len(e)] = e
            starts[s_] = self.pos_h[s_] - len(e) + 1
            elen[s_] = len(e)
            chunk[s_, 0] = e[-1]
        pads = jnp.asarray(self.pads_h)
        t_cache, d_cache = self.state
        d_cache, props = lockstep_propose(
            self.draft, self.d_params, d_cache, jnp.asarray(emitted),
            jnp.asarray(starts), jnp.asarray(elen), k=self.k, pad_len=pads)
        self.state = (t_cache, d_cache)
        # the draft's proposals are read back between the two
        # dispatches: the verify chunk is built from them
        self._props = np.asarray(props)
        chunk[:, 1:] = self._props
        t_cache, self._y = lockstep_verify(
            self.model, self.params, t_cache, jnp.asarray(chunk),
            jnp.asarray(self.pos_h, np.int32), pad_len=pads,
            **({} if table is None else {"page_table": table}))
        self.state = (t_cache, d_cache)

    def readback(self, owners) -> tuple:
        y_h = np.asarray(self._y)
        counts = {"spec_rounds": len(self._order), "spec_tokens_emitted": 0,
                  "spec_tokens_accepted": 0,
                  "spec_drafted": self.k * len(self._order)}
        done = []
        for s_ in self._order:
            a = greedy_accept(self._props[s_], y_h[s_], self.k)
            emit = [int(t) for t in self._props[s_][:a]]
            emit.append(int(y_h[s_][a]))
            take = min(len(emit), int(self.rem_h[s_]))
            emit = emit[:take]
            self.out_h[s_].extend(emit)
            self.ebuf[s_] = emit
            self.pos_h[s_] += take
            self.rem_h[s_] -= take
            counts["spec_tokens_emitted"] += take
            counts["spec_tokens_accepted"] += min(a, take)
            if self.rem_h[s_] <= 0:
                done.append(s_)
        # (an install's own first token is the loop's to count)
        counts["tokens_decoded"] = counts["spec_tokens_emitted"]
        return done, counts

    def answer(self, slot: int, r) -> tuple:
        self.ebuf.pop(slot, None)
        return self.out_h.pop(slot), {}

    def cancel(self, slots) -> None:
        """The slot's host mirrors are dropped, so the next round simply
        never emits for it (caches hold only dead rows)."""
        for s_ in slots:
            self.out_h.pop(s_, None)
            self.ebuf.pop(s_, None)
            self.rem_h[s_] = 0


def _diag_sum(diagnostics, name: str):
    """The sum of every layer's sow of `name` in a "diagnostics"
    collection (0 where no layer sowed it)."""
    return sum((v for path, v in
                jax.tree_util.tree_flatten_with_path(diagnostics)[0]
                if any(getattr(p, "key", None) == name for p in path)), 0)


def _set1(vec, i, val):
    """vec[i] = val with a dynamic index (static-shape scatter)."""
    return jnp.where(jnp.arange(vec.shape[0]) == i,
                     jnp.asarray(val, vec.dtype), vec)
