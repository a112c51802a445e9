"""Paged KV cache: host-side page allocator + prefix reuse + COW.

The dense decode cache reserves ``P + N`` positions of HBM per slot for
the slot's whole life — a short request in a long-budget decoder wastes
almost all of it. The paged cache replaces that with a fixed pool of
``num_pages`` pages of ``page_size`` positions each (static shapes —
TPU-friendly) shared across all slots: a request holds only the pages
its actual prompt + its OWN token budget needs, prompt pages whose
content matches an earlier request are shared read-only (prefix reuse),
and admission is gated on page availability instead of slot count.

Split of responsibilities:

- THIS module is pure host-side bookkeeping over numpy page tables —
  freelist, refcounts, chained prompt-page hashing, copy-on-write
  barriers — with no jax dependency in the allocator itself, so the
  property tests can drive millions of admit/append/free transitions
  cheaply. Device work is returned as DATA (page ids to copy) for the
  caller to apply.
- models/transformer.py owns the traced side: cache variables become
  the ``[num_pages, page_size, Hkv, D]`` pool and a traced
  ``page_table`` [B, MP] maps each slot's logical page j (positions
  ``j*PS .. (j+1)*PS-1``) to a physical page.
- serving/continuous.py drives both: allocator at admission/append/
  free, page table passed into every compiled prefill/tick.

Page 0 is the TRASH page: no slot ever owns it, freed slots' table
rows are zeroed so their stale lockstep writes land there instead of a
page another slot now owns, and gathers through unallocated table
entries read it only at masked positions.

A page with no real position is no page: a prompt is left-padded to the
decoder's fixed ``prompt_len``, and the logical pages wholly inside the
padding keep TRASH_PAGE in the slot's row. They are not allocated, not
counted, not hashed, not registered and not freed; every reader masks
positions before the pad length, and what prefill writes there lands in
the trash page.

Prefix reuse hashes CHAINS, not pages in isolation: a page's K/V at
layer > 0 depend on every earlier position (attention), so page j is
shareable only under an identical full prefix — ``h_j =
H(h_{j-1} || tokens_j)`` from the page that holds the first real token,
with the pad length folded into the root. Only COMPLETE prompt pages
are ever registered (a partially-filled page will be written by decode
and can never be shared safely).

What prefill computes is a suffix of the prompt whose length is a rung
of ``prefill_ladder``: the shortest that covers the real tokens the
index does not have. The compiled prefill is traced at those lengths
and at no other, with or without hits; a hit that would leave a length
between two rungs is cut back to the rung, and the pages behind the cut
are computed again into private pages.

Pages kept by layer kind. A model whose layers differ keeps two kinds of
page in this one allocator (``window`` and ``window_pages`` given): the
HELD kind is everything above, a page held for the slot's whole life,
and is the kind of the layers that attend to the whole context; the
WINDOW kind is the kind of the layers with a sliding window. Each kind
has its pool (on the device: its own ``[pages, PS, Hkv, D]`` arrays in
each layer of that kind), its free list and its table (``table``,
``window_table``: one row a slot, logical page j at entry j in both). A
window page goes back to its free list while the request runs, in the
round in which its last position falls behind every later query's
window (``release``: position <= reads_from - window, `reads_from` the
lowest position whose query will still read the slot's pages); its
entry is TRASH_PAGE again, which no reader reaches: the paged kernel
walks only ``start // PS .. last // PS`` and the gather masks the rest.
So a window layer holds ``window / PS + 1`` pages a slot (plus what a
round writes ahead) however long the context, and a prefill whose
attention does not read the pages (``reads_from`` = the position of the
first tick) never claims more. Admission is gated on both kinds. The
window kind keeps no prefix index and needs none off: a prompt page of
the index is handed to a later request whole, every layer's part of it,
so with prefix reuse on every layer is of the held kind (the decoder's
rule, serving/continuous.py).

Copy-on-write: any write into a page that is shared (referenced by
another slot or by the prefix index) first clones it to a fresh page —
``write_barrier`` returns the (src, dst) copies for the caller to apply
on-device BEFORE dispatching the program that writes. Reachable at
admission where the computed suffix starts inside a claimed page (a
``prompt_len`` that is no whole number of pages), and in decode where a
block model's first block rewrites the prompt's tail.
"""

from __future__ import annotations

import functools
import hashlib
import heapq
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import NamedTuple

import numpy as np

TRASH_PAGE = 0


def pages_for(length: int, page_size: int) -> int:
    """Number of pages covering `length` positions."""
    return -(-length // page_size)


@functools.lru_cache(maxsize=None)
def prefill_ladder(prompt_len: int, page_size: int) -> tuple[int, ...]:
    """The suffix lengths a paged prefill computes, ascending:
    ``prompt_len`` x {1/4, 1/2, 3/4, 1} in whole pages (for 4,096 by 16:
    1,024, 2,048, 3,072, 4,096). An admission takes the shortest rung
    that covers what it must compute, so the prefill is compiled once a
    rung, when the decoder is built, and a prompt above the first rung
    pays for at most twice its own length (three halves above half of
    ``prompt_len``). One rule for every decoder: nothing sets it. Four
    rungs and not more because a rung is paid for at every start: on a
    v5e's host one more trace, lowering and load of an 8-layer
    Mistral-7B's prefill is 0.6 to 0.9 s of set-up (PERF.md section 6,
    PR 30)."""
    return tuple(sorted({
        min(prompt_len,
            pages_for(-(-prompt_len * n // 4), page_size) * page_size)
        for n in (1, 2, 3, 4)}))


class _Layout(NamedTuple):
    """How an admission lies in its slot's row (``_layout``)."""

    first: int            # logical page of the first real position
    hashes: list          # chain hash of complete page first + i
    hits: list            # the index's pages for the leading hashes
    compute_start: int    # first prompt position prefill computes
    claimed: int          # leading hits taken as shared pages
    need: int             # pages to claim now and through decode


@dataclass
class AdmitPlan:
    """What one admission did: where prefill must start computing and
    which device-side page copies must run before it."""

    slot: int
    total_len: int
    prompt_len: int
    cached_positions: int          # positions covered by shared pages
    compute_start: int             # first prompt position to compute
    copies: list = field(default_factory=list)   # [(src, dst)] clones
    shared_pages: int = 0          # pages claimed from the prefix index


class PageAllocator:
    """Freelist + refcount + prefix-index bookkeeping for the pool.

    Single-threaded by design: the one decoder scheduler thread drives
    every transition (admission, per-tick appends/barriers, frees), so
    there is no lock to take and LOCK201 has nothing to track here.

    Refcount invariant: ``ref[p]`` == number of slot-table references
    to p + (1 if p is held by the prefix index). Pages with ref 0 are
    exactly the freelist. ``check()`` asserts this after any sequence
    of operations (the property test calls it per step).
    """

    def __init__(self, num_pages: int, page_size: int, slots: int,
                 max_pages_per_slot: int, prefix_cache: bool = True,
                 window: int = 0, window_pages: int = 0,
                 window_ahead: int = 1):
        """`window`, `window_pages`: the second kind of page (both or
        neither): a pool of `window_pages` for the layers whose window
        is `window` positions. `window_ahead`: the most positions one
        round writes past its first query (a fused round's ticks, a
        verify chunk), which a slot's window pages must also cover."""
        if num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is trash)")
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        if bool(window) != bool(window_pages):
            raise ValueError("window and window_pages go together "
                             f"(got {window}, {window_pages})")
        if window and prefix_cache:
            raise ValueError(
                "pages kept by layer kind need prefix_cache=False: a "
                "prompt page of the prefix index is handed to a later "
                "request whole, a window layer's released part with it")
        if window and window_pages < 2:
            raise ValueError("window_pages must be >= 2 (page 0 is trash)")
        self.num_pages = num_pages
        self.page_size = page_size
        self.slots = slots
        self.max_pages_per_slot = max_pages_per_slot
        self.prefix_enabled = prefix_cache
        # traced into every compiled program; int32 row per slot
        self.table = np.zeros((slots, max_pages_per_slot), np.int32)
        self._free: list[int] = list(range(1, num_pages))  # heap, asc ids
        heapq.heapify(self._free)
        self._ref = np.zeros(num_pages, np.int64)
        # per-slot extent: logical pages [_slot_first, _slot_len) are
        # allocated (the pages before hold padding only and stay trash),
        # _slot_total is what the slot may grow to
        self._slot_first: list[int] = [0] * slots
        self._slot_len: list[int] = [0] * slots
        self._slot_total: list[int] = [0] * slots   # reserved total pages
        self._reserved = 0                          # unallocated-yet pages
        # prefix index: chain hash -> page id (LRU via move_to_end)
        self._prefix: "OrderedDict[bytes, int]" = OrderedDict()
        self._page_key: dict[int, bytes] = {}
        # counters (host truth; the decoder mirrors them into metrics)
        self.prefix_lookups = 0
        self.prefix_hit_pages = 0
        self.prefix_hit_tokens = 0
        self.cow_clones = 0
        self.admits = 0
        self.evictions = 0
        # the window kind (none where window == 0): its table, its free
        # list, and for each slot the first logical page it still holds
        # (it holds [_wlow, _slot_len)) and the most it may hold from now
        # on (`_wquota`: what admission keeps free for it)
        self.window = window
        self.window_pages = window_pages
        # the most a slot holds between two releases: the window, what a
        # round writes ahead, and a page for where the window begins
        self._wring = pages_for(window + window_ahead, page_size) + 1
        self.window_table = (np.zeros((slots, max_pages_per_slot), np.int32)
                             if window else None)
        self._wfree: list[int] = list(range(1, window_pages))
        self._wlow: list[int] = [0] * slots
        self._wquota: list[int] = [0] * slots
        self.window_released = 0      # pages returned while a request ran

    # -- introspection ----------------------------------------------------

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return (self.num_pages - 1) - len(self._free)

    @property
    def window_used_pages(self) -> int:
        return max(0, self.window_pages - 1) - len(self._wfree)

    def window_held(self, slot: int) -> int:
        """Window-kind pages the slot holds now."""
        return self._slot_len[slot] - self._wlow[slot] if self.window else 0

    def window_covered(self, slot: int) -> int:
        """Pages the slot's context covers: what it would hold of the
        window kind if nothing were released."""
        return self._slot_len[slot] - self._slot_first[slot]

    def available(self) -> int:
        """Pages an admission may still claim: free + evictable prefix
        pages, minus what in-flight slots have reserved for decode."""
        evictable = sum(1 for p in self._prefix.values()
                        if self._ref[p] == 1)
        return len(self._free) + evictable - self._reserved

    # -- hashing ----------------------------------------------------------

    def _chain_hashes(self, row, pad: int, first: int) -> list[bytes]:
        """Chained hashes of the COMPLETE pages of `row` from logical
        page `first` on (one hash per full page; the pad length salts
        the root because left-pad masking changes every position's
        attention output, and because the first page's leading
        positions may be padding)."""
        ps = self.page_size
        toks = np.asarray(row, np.int32)
        h = hashlib.blake2b(f"pad={pad}".encode(), digest_size=16).digest()
        out = []
        for j in range(first, len(toks) // ps):
            h = hashlib.blake2b(
                h + toks[j * ps:(j + 1) * ps].tobytes(),
                digest_size=16).digest()
            out.append(h)
        return out

    # -- allocation core --------------------------------------------------

    def _evict_one(self) -> bool:
        """Drop the least-recently-hit prefix page nobody references."""
        for key, page in self._prefix.items():
            if self._ref[page] == 1:
                del self._prefix[key]
                del self._page_key[page]
                self._ref[page] = 0
                heapq.heappush(self._free, page)
                self.evictions += 1
                return True
        return False

    def _alloc_page(self) -> int:
        if not self._free and not self._evict_one():
            raise RuntimeError("page pool exhausted (caller must gate "
                               "admission on available())")
        page = heapq.heappop(self._free)
        self._ref[page] = 1
        return page

    # -- admission --------------------------------------------------------

    def _layout(self, row, pad: int, total_len: int) -> _Layout:
        """Where the admission's pages begin, what the index has of
        them, and the suffix prefill computes: the shortest rung of the
        ladder that covers the real positions no hit covers (one at the
        least: the first decode token needs the last position's
        logits). Hits behind the rung's start are not claimed."""
        ps = self.page_size
        prompt_len = len(row)
        real_from = min(pad, prompt_len - 1)
        first = real_from // ps
        hashes = (self._chain_hashes(row, pad, first)
                  if self.prefix_enabled else [])
        hits = []
        for h in hashes:
            page = self._prefix.get(h)
            if page is None:
                break
            hits.append(page)
        covered = max(real_from, (first + len(hits)) * ps)
        todo = max(1, prompt_len - covered)
        compute_start = prompt_len - next(
            n for n in prefill_ladder(prompt_len, ps) if n >= todo)
        claimed = min(len(hits),
                      max(0, pages_for(compute_start, ps) - first))
        # the suffix starts inside the last claimed page: prefill's write
        # copy-on-writes it, one page more
        cow = 1 if claimed and compute_start % ps else 0
        need = pages_for(total_len, ps) - first - claimed + cow
        return _Layout(first, hashes, hits, compute_start, claimed, need)

    def plan(self, row, pad: int, total_len: int) -> tuple[int, int]:
        """(pages_to_claim, cached_positions) for an admission. Gate
        with can_admit(), not `need <= available()`: available() counts
        every unreferenced prefix page as evictable, including the very
        pages THIS admission would hit — claiming them pins them, so
        the naive comparison over-admits and exhausts the pool
        mid-decode."""
        lay = self._layout(row, pad, total_len)
        return lay.need, lay.claimed * self.page_size

    def _window_low(self, first: int, reads_from: int) -> int:
        """The first logical page of the window kind that a query at
        `reads_from` or later still sees: every position of an earlier
        page is <= reads_from - window."""
        return max(first, (reads_from - self.window + 1) // self.page_size)

    def plan_window(self, row, pad: int, total_len: int,
                    reads_from: int | None = None) -> int:
        """Window-kind pages an admission must find free: what it holds
        at once (the prompt's pages a query at `reads_from` or later
        sees; None: the prefill's own first query) or, if that is more,
        the most it holds later (the ring). 0 without a window kind."""
        if not self.window:
            return 0
        lay = self._layout(row, pad, total_len)
        return self._window_claim(lay, len(row), total_len, reads_from)[1]

    def _window_claim(self, lay: _Layout, prompt_len: int, total_len: int,
                      reads_from: int | None) -> tuple[int, int]:
        """(the first logical page an admission holds of the window kind,
        the most pages it holds at once or later: its quota)."""
        n_prompt = pages_for(prompt_len, self.page_size)
        low = min(n_prompt, self._window_low(
            lay.first,
            lay.compute_start if reads_from is None else reads_from))
        return low, max(n_prompt - low, self._window_cap(
            lay.first, pages_for(total_len, self.page_size)))

    def _window_cap(self, first: int, n_total: int) -> int:
        """The most window pages a slot holds once a round has released
        behind its window: the ring, or its whole sequence if shorter."""
        return min(self._wring, n_total - first)

    def _window_spare(self) -> int:
        """Free window pages that no live slot's quota still counts on."""
        return len(self._wfree) - sum(
            max(0, q - self.window_held(s))
            for s, q in enumerate(self._wquota) if q)

    def can_admit(self, row, pad: int, total_len: int,
                  reads_from: int | None = None) -> bool:
        """True when the admission can claim every page it needs NOW
        and lazily through decode, of both kinds: free pages plus prefix
        pages that are genuinely evictable (unreferenced AND not this
        admission's own claimed hits), minus what live slots have
        reserved; and of the window kind `plan_window` of the free pages
        that no live slot's quota counts on."""
        lay = self._layout(row, pad, total_len)
        hitset = set(lay.hits[:lay.claimed])
        evictable = sum(1 for p in self._prefix.values()
                        if self._ref[p] == 1 and p not in hitset)
        if lay.need > len(self._free) + evictable - self._reserved:
            return False
        return not self.window or self.plan_window(
            row, pad, total_len, reads_from) <= self._window_spare()

    def admit(self, slot: int, row, pad: int, total_len: int,
              reads_from: int | None = None) -> AdmitPlan:
        """`reads_from` (the window kind only): the lowest position
        whose query will read this slot's pages, the prefill's own first
        query by default; a prefill that attends over its own keys gives
        the position of the first tick, and the window kind then claims
        no page behind that query's window.

        Claim pages for a request: none for the logical pages that
        hold padding only (TRASH_PAGE stays in the row), shared prompt
        pages from the prefix index (refcounted, read-only) up to where
        the computed suffix starts, fresh pages for the rest of the
        prompt; decode pages are RESERVED but appended lazily
        (``append``). Returns the plan — including any copy-on-write
        clones the caller must apply on-device before prefill runs —
        and registers the slot's newly computed complete prompt pages
        for future reuse."""
        prompt_len = len(row)
        if prompt_len < 1 or total_len < prompt_len:
            raise ValueError(f"bad admit geometry ({prompt_len=}, "
                             f"{total_len=})")
        n_total = pages_for(total_len, self.page_size)
        if n_total > self.max_pages_per_slot:
            raise ValueError(
                f"total_len {total_len} needs {n_total} pages > "
                f"max_pages_per_slot {self.max_pages_per_slot}")
        if self._slot_total[slot]:
            raise RuntimeError(f"slot {slot} already admitted")
        ps = self.page_size
        lay = self._layout(row, pad, total_len)
        first, k = lay.first, lay.claimed
        self.prefix_lookups += 1
        for i, page in enumerate(lay.hits[:k]):
            self.table[slot, first + i] = page
            self._ref[page] += 1
            self._prefix.move_to_end(lay.hashes[i])   # LRU touch
        self.prefix_hit_pages += k
        self.prefix_hit_tokens += k * ps
        # the slot's extent is set before any page is drawn, so that
        # free() gives back whatever was claimed if the pool runs dry
        n_prompt = pages_for(prompt_len, ps)
        self._slot_first[slot] = first
        self._slot_len[slot] = n_prompt
        self._slot_total[slot] = n_total
        self._reserved += n_total - n_prompt
        # private pages for the computed prompt suffix
        for j in range(first + k, n_prompt):
            self.table[slot, j] = self._alloc_page()
        if self.window:
            low, quota = self._window_claim(lay, prompt_len, total_len,
                                            reads_from)
            self._wlow[slot], self._wquota[slot] = low, quota
            for j in range(low, n_prompt):
                self.window_table[slot, j] = self._alloc_window_page()
        self.admits += 1
        plan = AdmitPlan(slot=slot, total_len=total_len,
                         prompt_len=prompt_len, cached_positions=k * ps,
                         compute_start=lay.compute_start, shared_pages=k)
        # prefill WRITES [compute_start, prompt_len): COW the claimed
        # page the suffix starts inside, if it does
        plan.copies = self.write_barrier(slot, lay.compute_start, prompt_len)
        # register newly computed COMPLETE prompt pages for reuse
        if self.prefix_enabled:
            for j in range(first + k, prompt_len // ps):
                page = int(self.table[slot, j])
                key = lay.hashes[j - first]
                if key in self._prefix or page in self._page_key:
                    continue  # duplicate content (computed again, a clone)
                self._prefix[key] = page
                self._page_key[page] = key
                self._ref[page] += 1
        return plan

    # -- decode-time operations -------------------------------------------

    def append(self, slot: int, upto_position: int) -> None:
        """Make sure pages covering positions < `upto_position` exist
        (decode/speculative writes march forward; pages appear as the
        sequence crosses page boundaries, drawn from the reservation
        made at admission)."""
        need = pages_for(upto_position, self.page_size)
        if need > self._slot_total[slot]:
            raise ValueError(
                f"slot {slot}: position {upto_position} beyond reserved "
                f"{self._slot_total[slot]} pages")
        while self._slot_len[slot] < need:
            j = self._slot_len[slot]
            self.table[slot, j] = self._alloc_page()
            if self.window:
                self.window_table[slot, j] = self._alloc_window_page()
            self._slot_len[slot] = j + 1
            self._reserved -= 1

    def _alloc_window_page(self) -> int:
        if not self._wfree:
            raise RuntimeError("window page pool exhausted (caller must "
                               "gate admission on can_admit())")
        return heapq.heappop(self._wfree)

    def release(self, slot: int, reads_from: int) -> int:
        """Give back the slot's window-kind pages that no query at
        `reads_from` or later sees (every position <= reads_from -
        window): their entries are TRASH_PAGE again. Before the round's
        `append`, so that a round claims no more than it returned.
        Returns how many went back."""
        if not self.window or not self._slot_total[slot]:
            return 0
        low = min(self._window_low(self._slot_first[slot], reads_from),
                  self._slot_len[slot])
        gone = low - self._wlow[slot]
        if gone <= 0:
            return 0
        for j in range(self._wlow[slot], low):
            heapq.heappush(self._wfree, int(self.window_table[slot, j]))
            self.window_table[slot, j] = TRASH_PAGE
        self._wlow[slot] = low
        self._wquota[slot] = max(self.window_held(slot), self._window_cap(
            self._slot_first[slot], self._slot_total[slot]))
        self.window_released += gone
        return gone

    def write_barrier(self, slot: int, start: int, end: int) -> list:
        """Copy-on-write guard: every page overlapping positions
        [start, end) that is shared (another slot's table or the prefix
        index also references it) is replaced by a fresh private clone.
        Returns [(src, dst)] page copies the caller MUST apply to the
        device pool before any program writes the range."""
        if end <= start:
            return []
        copies = []
        ps = self.page_size
        for j in range(start // ps, pages_for(end, ps)):
            if j >= self._slot_len[slot]:
                break  # not allocated yet; append() hands out fresh pages
            page = int(self.table[slot, j])
            shared = self._ref[page] > 1 or page in self._page_key
            if page != TRASH_PAGE and shared:
                clone = self._alloc_page()
                self._ref[page] -= 1
                self.table[slot, j] = clone
                copies.append((page, clone))
                self.cow_clones += 1
        return copies

    def free(self, slot: int) -> None:
        """Release the slot: deref every page (shared prompt pages
        survive in the prefix index for future hits), zero the table
        row so the idle slot's lockstep writes land in the trash page,
        drop the unallocated reservation."""
        for j in range(self._slot_first[slot], self._slot_len[slot]):
            page = int(self.table[slot, j])
            if page == TRASH_PAGE:   # (an admission the pool cut short)
                continue
            self._ref[page] -= 1
            if self._ref[page] == 0:
                heapq.heappush(self._free, page)
        self._reserved -= self._slot_total[slot] - self._slot_len[slot]
        if self.window:
            for j in range(self._wlow[slot], self._slot_len[slot]):
                page = int(self.window_table[slot, j])
                if page != TRASH_PAGE:   # (an admission the pool cut short)
                    heapq.heappush(self._wfree, page)
            self.window_table[slot, :] = TRASH_PAGE
            self._wlow[slot] = self._wquota[slot] = 0
        self.table[slot, :] = TRASH_PAGE
        self._slot_first[slot] = 0
        self._slot_len[slot] = 0
        self._slot_total[slot] = 0

    def reset(self) -> None:
        """Forget everything (the decoder's fail_all path: device state
        is rebuilt from scratch, so cached prefix pages are garbage)."""
        self.table[:, :] = TRASH_PAGE
        self._free = list(range(1, self.num_pages))
        heapq.heapify(self._free)
        self._ref[:] = 0
        self._slot_first = [0] * self.slots
        self._slot_len = [0] * self.slots
        self._slot_total = [0] * self.slots
        self._reserved = 0
        self._prefix.clear()
        self._page_key.clear()
        if self.window:
            self.window_table[:, :] = TRASH_PAGE
            self._wfree = list(range(1, self.window_pages))
            self._wlow = [0] * self.slots
            self._wquota = [0] * self.slots

    # -- invariants (the property test's oracle) --------------------------

    def check(self) -> None:
        refs = np.zeros(self.num_pages, np.int64)
        for s in range(self.slots):
            first, end = self._slot_first[s], self._slot_len[s]
            row = self.table[s, first:end]
            for page in row:
                assert page != TRASH_PAGE, (s, row)
                refs[page] += 1
            assert (self.table[s, :first] == TRASH_PAGE).all()
            assert (self.table[s, end:] == TRASH_PAGE).all()
        for page in self._prefix.values():
            refs[page] += 1
        assert (refs == self._ref).all(), "refcount drift"
        free = set(self._free)
        assert len(free) == len(self._free), "freelist duplicates"
        assert TRASH_PAGE not in free
        for page in range(1, self.num_pages):
            in_free = page in free
            assert in_free == (refs[page] == 0), (page, refs[page], in_free)
        assert set(self._page_key) == set(self._prefix.values())
        assert self._reserved == sum(
            t - l for t, l in zip(self._slot_total, self._slot_len))
        assert self._reserved >= 0
        if not self.window:
            return
        # the window kind: every page free or in exactly one slot's row,
        # a slot's pages exactly at [_wlow, _slot_len), within its quota
        owned: list[int] = []
        for s in range(self.slots):
            low, end = self._wlow[s], self._slot_len[s]
            assert self._slot_first[s] <= low <= max(end, low), (s, low, end)
            row = self.window_table[s]
            assert (row[:low] == TRASH_PAGE).all(), (s, row)
            assert (row[max(end, low):] == TRASH_PAGE).all(), (s, row)
            assert (row[low:end] != TRASH_PAGE).all(), (s, row)
            owned.extend(int(p) for p in row[low:end])
            assert self.window_held(s) <= self._wquota[s], (
                s, self.window_held(s), self._wquota[s])
        wfree = set(self._wfree)
        assert len(wfree) == len(self._wfree), "window freelist duplicates"
        assert len(set(owned)) == len(owned), "a window page in two owners"
        assert not wfree & set(owned), "a window page both free and owned"
        assert wfree | set(owned) == set(range(1, self.window_pages)), \
            "a window page lost"
        assert self._window_spare() >= 0


# ---------------------------------------------------------------------------
# device-side helpers (the only jax in this module)


def init_paged_cache(model, max_pages_per_slot: int):
    """Zero page-pool caches for a model built with cfg.kv_pages /
    kv_page_size (eval_shape: no FLOPs). The pool shape comes from the
    config alone; max_pages_per_slot only shapes the probe table."""
    import jax
    import jax.numpy as jnp

    tok1 = jnp.zeros((1, 1), jnp.int32)
    pt = jnp.zeros((1, max_pages_per_slot), jnp.int32)
    if getattr(model.cfg, "kv_window_pages", 0):
        pt = (pt, pt)       # one table a kind
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tok1,
                           decode_index=jnp.zeros((1,), jnp.int32),
                           page_table=pt))
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        shapes.get("cache", {}))


def copy_pages(cache, src, dst):
    """Apply COW clones on-device: pool[dst] = pool[src] for every
    leaf of the paged cache pytree. src/dst are [m] int32 page ids;
    jit at the call site (one compile per clone-batch size m). (Clones
    come of shared pages, which a decoder that keeps pages by layer
    kind never has: its prefix cache is off.)"""
    import jax

    return jax.tree.map(lambda pool: pool.at[dst].set(pool[src]), cache)
