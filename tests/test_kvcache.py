"""Paged KV cache (runtime/kvcache.py + the paged/speculative
SlotDecoder modes): allocator invariants under random transitions,
prefix-reuse COW correctness, and the pinned token-for-token
equalities — paged == dense and speculative == plain greedy."""

import random
import threading

import numpy as np
import pytest

from kubeflow_tpu.runtime.kvcache import (
    TRASH_PAGE,
    PageAllocator,
    pages_for,
)


@pytest.fixture(scope="module")
def lm():
    import jax

    from kubeflow_tpu.models.registry import get_model

    model = get_model("transformer-test", vocab_size=64, max_seq_len=24)
    tok = np.zeros((1, 1), np.int32)
    variables = model.init(jax.random.PRNGKey(0), tok, train=False)
    return model, variables


def paged_model(**kw):
    from kubeflow_tpu.models.registry import get_model

    base = dict(vocab_size=64, max_seq_len=24)
    base.update(kw)
    return get_model("transformer-test", **base)


def reference_generate(model, variables, tokens, prompt_len=8, max_new=4):
    import jax.numpy as jnp

    from kubeflow_tpu.runtime.generate import generate

    row = [int(t) for t in tokens][-prompt_len:]
    pad = prompt_len - len(row)
    prompt = jnp.asarray([[0] * pad + row], jnp.int32)
    out = generate(model, variables, prompt, max_new_tokens=max_new,
                   pad_len=jnp.asarray([pad], jnp.int32))
    return [int(t) for t in np.asarray(out)[0, prompt_len:]]


def padded(real, prompt_len=32, first=1):
    """A left-padded row of `real` distinct tokens from `first` on, and
    its pad length."""
    pad = prompt_len - real
    return [0] * pad + list(range(first, first + real)), pad


class TestPrefillLadder:
    @pytest.mark.parametrize("prompt_len,page,want", [
        (4096, 16, (1024, 2048, 3072, 4096)),
        (1024, 16, (256, 512, 768, 1024)),
        (32, 4, (8, 16, 24, 32)),
        (8, 4, (4, 8)),
        (10, 4, (4, 8, 10)),    # whole pages, cut at the prompt's end
        (8, 3, (3, 6, 8)),
        (1, 16, (1,)),
    ])
    def test_rungs(self, prompt_len, page, want):
        from kubeflow_tpu.runtime.kvcache import prefill_ladder

        assert prefill_ladder(prompt_len, page) == want

    @pytest.mark.parametrize("prompt_len", [1, 7, 64, 100, 1000, 8192])
    @pytest.mark.parametrize("page", [1, 4, 16, 128])
    def test_shape_of_any_ladder(self, prompt_len, page):
        from kubeflow_tpu.runtime.kvcache import prefill_ladder

        rungs = prefill_ladder(prompt_len, page)
        assert 1 <= len(rungs) <= 8
        assert list(rungs) == sorted(set(rungs)) and rungs[-1] == prompt_len
        assert all(n % page == 0 for n in rungs[:-1])
        # a prompt pays for at most twice its own length, above a rung
        for real in range(rungs[0], prompt_len + 1):
            assert next(n for n in rungs if n >= real) < 2 * real + page


class TestPageAllocator:
    def test_admit_shares_the_prefix_up_to_a_rung(self):
        """Pages of 8, prompts of 32: the ladder is 8, 16, 24, 32, so a
        hit is claimed up to where the shortest covering rung starts."""
        a = PageAllocator(num_pages=40, page_size=8, slots=6,
                          max_pages_per_slot=6)
        row = list(range(1, 33))                    # 4 full pages
        p0 = a.admit(0, row, 0, 40)
        assert p0.shared_pages == 0 and p0.compute_start == 0
        a.check()
        # identical prompt: every full page hits, and the last rung (one
        # page here) is computed again for the first token's logits,
        # into a private page: nothing to copy
        need, cached = a.plan(row, 0, 40)
        assert (need, cached) == (2, 24)
        p1 = a.admit(1, row, 0, 40)
        assert p1.shared_pages == 3 and p1.compute_start == 24
        assert not p1.copies and a.cow_clones == 0
        assert a.table[1, 3] not in a.table[0]
        a.check()
        # page-aligned divergence: 3 shared pages
        p2 = a.admit(2, row[:24] + [9] * 8, 0, 40)
        assert p2.shared_pages == 3 and p2.compute_start == 24
        assert not p2.copies
        a.check()
        # mid-page divergence: the divergent page hash misses entirely
        p3 = a.admit(3, row[:28] + [9] * 4, 0, 40)
        assert p3.shared_pages == 3 and p3.compute_start == 24
        a.check()
        # two pages hit, 16 to compute: a rung
        p4 = a.admit(4, row[:16] + [9] * 16, 0, 40)
        assert p4.shared_pages == 2 and p4.compute_start == 16
        a.check()
        # one page and a half hit: 24 to compute
        p5 = a.admit(5, row[:12] + [9] * 20, 0, 40)
        assert p5.shared_pages == 1 and p5.compute_start == 8
        assert a.prefix_hit_pages == 12 and a.prefix_hit_tokens == 96
        a.check()

    def test_a_rung_that_starts_inside_a_claimed_page_cows_it(self):
        """A prompt length that is no whole number of pages (10 by 4:
        rungs 4, 8, 10): the full hit computes the last 4 positions from
        6 on, inside the second shared page, which is cloned first."""
        a = PageAllocator(num_pages=24, page_size=4, slots=3,
                          max_pages_per_slot=3)
        row = list(range(1, 11))
        assert a.admit(0, row, 0, 12).compute_start == 0
        assert a.plan(row, 0, 12) == (2, 8)     # the third page + the clone
        p1 = a.admit(1, row, 0, 12)
        assert p1.shared_pages == 2 and p1.compute_start == 6
        assert len(p1.copies) == 1 and a.cow_clones == 1
        src, dst = p1.copies[0]
        assert src == a.table[0, 1] and dst == a.table[1, 1] and src != dst
        assert a.table[1, 0] == a.table[0, 0]
        a.check()
        a.free(0)
        a.free(1)
        a.check()

    @pytest.mark.parametrize("page,length,total,want", [
        (4, 8, 8, (1, 4)),      # a rung of one page computed again
        (4, 10, 12, (2, 8)),    # the unaligned tail's page + the COW clone
        (4, 8, 16, (3, 4)),     # ... and the decode pages behind it
    ])
    def test_plan_accounts_for_every_page_of_a_full_hit(
            self, page, length, total, want):
        a = PageAllocator(num_pages=12, page_size=page, slots=2,
                          max_pages_per_slot=4)
        row = list(range(1, length + 1))
        a.admit(0, row, 0, total)
        assert a.plan(row, 0, total) == want
        free = a.free_pages
        a.admit(1, row, 0, total)
        a.append(1, total)
        assert free - a.free_pages == want[0]
        a.check()

    def test_free_returns_pages_and_zeroes_the_table_row(self):
        a = PageAllocator(num_pages=16, page_size=4, slots=2,
                          max_pages_per_slot=4, prefix_cache=False)
        a.admit(0, list(range(1, 9)), 0, 16)
        a.append(0, 16)
        assert a.used_pages == 4
        a.free(0)
        a.check()
        assert a.used_pages == 0
        assert (a.table[0] == TRASH_PAGE).all()

    def test_pool_exhaustion_is_an_error_not_corruption(self):
        a = PageAllocator(num_pages=4, page_size=4, slots=2,
                          max_pages_per_slot=3, prefix_cache=False)
        a.admit(0, list(range(1, 9)), 0, 12)        # 3 of 3 usable pages
        with pytest.raises(RuntimeError, match="exhausted"):
            a.admit(1, list(range(10, 18)), 0, 12)

    def test_property_random_transitions_hold_invariants(self):
        """Random admit/append/write_barrier/free sequences never
        double-allocate or leak a page: refcounts, freelist, table and
        prefix-index invariants checked after EVERY transition."""
        rng = random.Random(20260804)
        a = PageAllocator(num_pages=48, page_size=4, slots=8,
                          max_pages_per_slot=12)
        live: dict[int, tuple] = {}    # slot -> (total_len, cur_len)
        admits = 0
        for _step in range(6000):
            op = rng.random()
            if op < 0.40 and len(live) < a.slots:
                slot = next(s for s in range(a.slots) if s not in live)
                plen = rng.randrange(1, 25)
                row = [rng.randrange(0, 2) for _ in range(plen)]
                total = plen + rng.randrange(0, 16)
                if pages_for(total, a.page_size) > a.max_pages_per_slot:
                    continue
                pad = rng.choice([0, 0, 1, rng.randrange(0, plen + 1)])
                if a.can_admit(row, pad, total):
                    a.admit(slot, row, pad, total)
                    live[slot] = (total, plen)
                    admits += 1
            elif op < 0.80 and live:
                slot = rng.choice(sorted(live))
                total, cur = live[slot]
                if cur < total:
                    step = min(total - cur, rng.randrange(1, 4))
                    a.append(slot, cur + step)
                    a.write_barrier(slot, cur, cur + step)
                    live[slot] = (total, cur + step)
            elif live:
                slot = rng.choice(sorted(live))
                a.free(slot)
                del live[slot]
            a.check()
        assert admits > 100   # the run actually exercised admission
        # ... and prefix hits, and the clones of write_barrier
        assert a.prefix_hit_pages > 100 and a.cow_clones > 50
        for slot in sorted(live):
            a.free(slot)
            a.check()
        # nothing leaked: only prefix-index pages may remain resident
        assert a.used_pages == len(a._prefix)

    def test_can_admit_never_counts_its_own_hits_as_evictable(self):
        """The admission gate must not plan on evicting the very prefix
        pages the admission is about to claim: with 2 free pages and a
        4-token budget left only via this prompt's own cached pages,
        admission must WAIT, or append() exhausts the pool mid-decode
        and fails every in-flight request."""
        a = PageAllocator(num_pages=7, page_size=4, slots=2,
                          max_pages_per_slot=7)
        a.admit(0, list(range(1, 9)), 0, 8)    # chain A: 2 prefix pages
        a.admit(1, list(range(20, 28)), 0, 8)  # chain B: 2 prefix pages
        a.free(0)
        a.free(1)
        a.check()
        assert a.free_pages == 2               # 4 pages live in the index
        row = list(range(1, 9))
        # rungs 4 and 8: of the two hits the first is claimed, the second
        # computed again. total_len 28 needs 7 pages - 1 claimed = 6,
        # obtainable = free(2) + evictables that are NOT claimed (3) = 5:
        # the naive `need <= free + all evictables (2 + 4)` gate would
        # admit and starve; the correct gate refuses. 24 (need 5) fits
        # exactly.
        assert a.can_admit(row, 0, 24) is True
        assert a.can_admit(row, 0, 28) is False
        a.admit(0, row, 0, 24)
        a.append(0, 24)                          # never raises
        a.check()

    def test_reset_forgets_everything(self):
        a = PageAllocator(num_pages=16, page_size=4, slots=2,
                          max_pages_per_slot=4)
        a.admit(0, list(range(1, 9)), 0, 12)
        a.reset()
        a.check()
        assert a.free_pages == 15 and a.used_pages == 0


class TestPadPagesAreNoPages:
    """Prompts of 32 in pages of 4 (rungs 8, 16, 24, 32), left-padded:
    a page that holds no real position is no page."""

    P, PS, TOTAL = 32, 4, 40

    def alloc(self, **kw):
        return PageAllocator(num_pages=64, page_size=self.PS, slots=4,
                             max_pages_per_slot=10, **kw)

    @pytest.mark.parametrize("real", [1, 3, 4, 5, 13, 31, 32])
    def test_pad_pages_are_trash_and_cost_nothing(self, real):
        a = self.alloc()
        row, pad = padded(real)
        pad_pages = pad // self.PS
        need, cached = a.plan(row, pad, self.TOTAL)
        assert need == 10 - pad_pages and cached == 0
        assert a.can_admit(row, pad, self.TOTAL)
        plan = a.admit(0, row, pad, self.TOTAL)
        assert (a.table[0, :pad_pages] == TRASH_PAGE).all()
        assert (a.table[0, pad_pages:8] != TRASH_PAGE).all()
        assert a.used_pages == 8 - pad_pages
        # the index holds the complete pages from the first real one on
        assert len(a._prefix) == 8 - pad_pages
        assert TRASH_PAGE not in a._prefix.values()
        assert plan.shared_pages == 0 and not plan.copies
        a.check()
        a.append(0, self.TOTAL)
        assert a.used_pages == need
        a.write_barrier(0, 0, self.TOTAL)       # trash is never cloned
        assert (a.table[0, :pad_pages] == TRASH_PAGE).all()
        a.check()
        a.free(0)
        a.check()
        assert (a.table[0] == TRASH_PAGE).all()
        assert a.used_pages == len(a._prefix) == 8 - pad_pages

    def test_a_pool_of_real_pages_admits_what_padding_would_refuse(self):
        """4 usable pages hold a prompt of 5 real tokens and 4 new ones
        (3 pages), whatever the 27 positions of padding before them."""
        a = PageAllocator(num_pages=5, page_size=4, slots=1,
                          max_pages_per_slot=9, prefix_cache=False)
        row, pad = padded(5)
        assert a.plan(row, pad, 36) == (3, 0)
        assert a.can_admit(row, pad, 36)
        a.admit(0, row, pad, 36)
        a.append(0, 36)
        a.check()

    def test_an_empty_prompt_still_computes_a_rung(self):
        a = self.alloc()
        plan = a.admit(0, [0] * self.P, self.P, self.TOTAL)
        assert plan.compute_start == self.P - 8
        assert (a.table[0, :7] == TRASH_PAGE).all() and a.table[0, 7]
        a.check()

    @pytest.mark.parametrize("hit", [False, True], ids=["miss", "hit"])
    @pytest.mark.parametrize("real", range(1, 33))
    def test_the_computed_suffix_is_a_rung(self, real, hit):
        """Every real length, alone and behind a prompt of the same pad
        that shares its leading tokens (all but the last three)."""
        from kubeflow_tpu.runtime.kvcache import prefill_ladder

        a = self.alloc()
        row, pad = padded(real)
        shared = 0
        if hit:
            other = list(row)
            other[-3:] = [60, 61, 62][-min(3, real):]
            a.admit(1, other, pad, self.TOTAL)
            shared = max(0, (self.P - 3) // self.PS - pad // self.PS)
        plan = a.admit(0, row, pad, self.TOTAL)
        length = self.P - plan.compute_start
        rungs = prefill_ladder(self.P, self.PS)
        assert length in rungs
        # the shortest rung that covers what no claimed page holds
        todo = self.P - max(pad, (pad // self.PS + shared) * self.PS)
        assert length == next(n for n in rungs if n >= max(1, todo))
        assert plan.shared_pages == max(
            0, min(shared, plan.compute_start // self.PS - pad // self.PS))
        assert not plan.copies
        a.check()

    @pytest.mark.parametrize("real", [1, 5, 12, 20])
    def test_one_real_length_and_other_tokens_share_nothing(self, real):
        """The allocator's trap (PERF.md section 4 before PR 30): two
        left-padded prompts of one real length shared their all-zero pad
        pages, a prefix hit that left a suffix of a new length."""
        a = self.alloc()
        row_a, pad = padded(real, first=1)
        row_b, _ = padded(real, first=40)
        pa = a.admit(0, row_a, pad, self.TOTAL)
        pb = a.admit(1, row_b, pad, self.TOTAL)
        assert pb.shared_pages == 0 and a.prefix_hit_pages == 0
        assert pb.compute_start == pa.compute_start
        assert not set(a.table[0][a.table[0] > 0]) & set(
            a.table[1][a.table[1] > 0])
        a.check()


class TestPagedDecode:
    """The paged SlotDecoder against its dense twin: same weights, same
    tokens, byte for byte."""

    def test_paged_matches_dense_exactly(self, lm):
        from kubeflow_tpu.serving.continuous import SlotDecoder

        model, variables = lm
        pm = paged_model(kv_pages=17, kv_page_size=4)
        dec = SlotDecoder(pm, variables, slots=4, prompt_len=8,
                          max_new_tokens=4)
        try:
            prompts = [[1, 2, 3], [4, 5, 6, 7, 8], [9], [10, 11]]
            want = [reference_generate(model, variables, p)
                    for p in prompts]
            assert [dec.submit(p) for p in prompts] == want
            st = dec.stats()
            assert st["mode"] == "paged" and st["completed"] == 4
            assert st["kv_pages_free"] + st["kv_pages_used"] == 16
        finally:
            dec.close()

    def test_concurrent_staggered_paged_stays_exact(self, lm):
        from kubeflow_tpu.serving.continuous import SlotDecoder

        model, variables = lm
        pm = paged_model(kv_pages=25, kv_page_size=4)
        dec = SlotDecoder(pm, variables, slots=3, prompt_len=8,
                          max_new_tokens=6)
        try:
            prompts = [[i + 1, i + 2, i + 3] for i in range(7)]
            want = {tuple(p): reference_generate(
                model, variables, p, max_new=6) for p in prompts}
            results: dict = {}
            errs: list = []

            def go(p):
                try:
                    results[tuple(p)] = dec.submit(p)
                except Exception as e:  # pragma: no cover
                    errs.append(e)

            threads = [threading.Thread(target=go, args=(p,))
                       for p in prompts]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not errs, errs
            assert results == want
        finally:
            dec.close()

    @pytest.mark.parametrize("page,hits,clones", [(4, 2, 0), (3, 4, 2)])
    def test_prefix_reuse_cow_does_not_corrupt_the_sharer(
            self, lm, page, hits, clones):
        """Three live slots share prompt pages; the full-hit admissions
        compute their last rung again, into a private page (pages of 4:
        rungs 4, 8) or from inside the last shared page, which they
        COW-clone first (pages of 3: rungs 3, 6, 8). Every decode must
        still equal the no-sharing reference — a clone that mutated the
        shared original would corrupt its sharers' tokens."""
        from kubeflow_tpu.serving.continuous import SlotDecoder

        model, variables = lm
        pm = paged_model(kv_pages=25, kv_page_size=page)
        dec = SlotDecoder(pm, variables, slots=4, prompt_len=8,
                          max_new_tokens=6)
        try:
            prompt = [3, 1, 4, 1, 5, 9, 2, 6]    # full 8 = 2 whole pages
            want = reference_generate(model, variables, prompt, max_new=6)
            held, dec._free = dec._free, []      # admit as one burst
            results: list = [None] * 3
            threads = [threading.Thread(
                target=lambda i=i: results.__setitem__(
                    i, dec.submit(prompt))) for i in range(3)]
            for t in threads:
                t.start()
            import time as _time

            _time.sleep(0.3)
            dec._free = held
            dec._wake.set()
            for t in threads:
                t.join(timeout=120)
            assert results == [want] * 3
            st = dec.stats()
            assert st["prefix_hit_pages"] == hits  # sharing really happened
            assert st["cow_clones"] == clones      # and the COW path ran
            assert st["prefill_tokens_computed"] == 8 + 2 * page
            assert st["prompt_tokens_real"] == 24
            dec.alloc.check()
        finally:
            dec.close()

    @pytest.mark.parametrize("real,at_once", [(6, 2), (2, 3)])
    def test_admission_gates_on_pages_not_slots(self, lm, real, at_once):
        """A pool sized for 2 or 3 live sequences with 6 slots: requests
        queue on page availability and all complete as pages free. A
        sequence holds the pages of its real tokens and its answer: 3
        for 6 + 4 tokens, 2 for 2 + 4 behind a page of padding."""
        from kubeflow_tpu.serving.continuous import SlotDecoder

        model, variables = lm
        pm = paged_model(kv_pages=8, kv_page_size=4)  # 7 usable pages
        dec = SlotDecoder(pm, variables, slots=6, prompt_len=8,
                          max_new_tokens=4, prefix_cache=False)
        try:
            prompts = [list(range(i + 1, i + 1 + real)) for i in range(6)]
            want = [reference_generate(model, variables, p)
                    for p in prompts]
            results: list = [None] * 6
            threads = [threading.Thread(
                target=lambda i=i: results.__setitem__(
                    i, dec.submit(prompts[i]))) for i in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert results == want
            # 7 usable pages / 3 (or 2) pages per sequence
            assert dec.stats()["peak_active"] <= at_once
        finally:
            dec.close()

    def test_per_request_budget_frees_pages_early(self, lm):
        from kubeflow_tpu.serving.continuous import SlotDecoder

        model, variables = lm
        pm = paged_model(kv_pages=17, kv_page_size=4)
        dec = SlotDecoder(pm, variables, slots=4, prompt_len=8,
                          max_new_tokens=6)
        try:
            p = [1, 2, 3]
            full = reference_generate(model, variables, p, max_new=6)
            assert dec.submit(p, max_new=2) == full[:2]
            assert dec.submit(p, max_new=6) == full
            with pytest.raises(ValueError, match="max_new"):
                dec.submit(p, max_new=7)
            st = dec.stats()
            assert st["completed"] == 2   # the out-of-range cap never ran
            # completed sequences hold nothing; only prefix-index pages
            # stay resident for future reuse
            assert st["kv_pages_used"] < st["kv_pages_total"]
        finally:
            dec.close()

    def test_pool_too_small_for_one_sequence_refused(self, lm):
        from kubeflow_tpu.serving.continuous import SlotDecoder

        model, variables = lm
        pm = paged_model(kv_pages=3, kv_page_size=4)
        with pytest.raises(ValueError, match="kv_pages"):
            SlotDecoder(pm, variables, slots=2, prompt_len=8,
                        max_new_tokens=4)


class TestPagesWalkedCounters:
    """`kv_pages_walked` / `kv_pages_tabled` in stats(): the pages that
    hold what each active slot's query sees, tick by tick, against ticks
    x the whole table. Pages of 4, prompt_len 8, a window of 16, 2 slots
    with rows of 7 pages (28 positions): 14 table entries a tick."""

    # (real prompt tokens, new tokens, pages walked, rounds), by hand. A
    # request alone in the decoder fuses 8 ticks while 8 or more remain.
    # pad 3, positions 8..27; a query at p sees max(3, p - 15)..p:
    #   8-11: pages 0-2 = 3 each; 12-15: 4; 16-18: 5; 19 (from 4): 4;
    #   20-22: 5; 23 (from 8): 4; 24-26: 5; 27 (from 12): 4
    #   = 12 + 16 + 15 + 4 + 15 + 4 + 15 + 4 = 85; rounds 8 + 8 + 4 x 1
    # pad 0, positions 8..10: pages 0-2 = 3 each = 9; three single ticks
    # pad 6, positions 8..16 from 6 (page 1): 8-11: 2; 12-15: 3; 16: 4
    #   = 8 + 12 + 4 = 24; rounds 8 + 1
    SCHEDULE = [(5, 20, 85, 6), (8, 3, 9, 3), (2, 9, 24, 2)]

    def test_three_request_schedule_matches_the_hand_count(self, lm):
        from kubeflow_tpu.serving.continuous import SlotDecoder

        _, variables = lm
        pm = paged_model(kv_pages=15, kv_page_size=4, max_seq_len=28,
                         attention_window=16)
        dec = SlotDecoder(pm, variables, slots=2, prompt_len=8,
                          max_new_tokens=20)
        try:
            assert dec.alloc.table.shape == (2, 7)
            walked = ticks = rounds = 0
            for real, new, pages, n_rounds in self.SCHEDULE:
                got = dec.submit(list(range(1, real + 1)), max_new=new)
                assert len(got) == new
                walked, ticks = walked + pages, ticks + new
                rounds += n_rounds
                st = dec.stats()
                assert st["kv_pages_walked"] == walked
                # a fused round counts its eight ticks, not one
                assert st["kv_pages_tabled"] == ticks * 14
                assert st["rounds"] == rounds
            assert (walked, ticks, rounds) == (118, 32, 11)
        finally:
            dec.close()

    def test_dense_decoder_has_no_page_counters(self, lm):
        from kubeflow_tpu.serving.continuous import SlotDecoder

        model, variables = lm
        dec = SlotDecoder(model, variables, slots=2, prompt_len=8,
                          max_new_tokens=4)
        try:
            dec.submit([1, 2, 3])
            st = dec.stats()
            assert "kv_pages_walked" not in st
            assert "kv_pages_tabled" not in st
        finally:
            dec.close()


class TestSpeculativeLockstep:
    """speculative_generate's propose/verify round generalized to
    [S, k] inside SlotDecoder._tick: output must be token-for-token
    equal to plain greedy decode, accept or reject."""

    def test_disagreeing_draft_stays_exact(self, lm):
        """A randomly-initialized draft rejects constantly — the
        rejection/resync path must still emit exactly greedy tokens."""
        import jax

        from kubeflow_tpu.serving.continuous import SlotDecoder

        model, variables = lm
        draft_vars = model.init(jax.random.PRNGKey(99),
                                np.zeros((1, 1), np.int32), train=False)
        dec = SlotDecoder(model, variables, slots=3, prompt_len=8,
                          max_new_tokens=6, draft_model=model,
                          draft_variables=draft_vars, draft_k=3)
        try:
            prompts = [[i + 1, i + 2, i + 3] for i in range(7)]
            want = {tuple(p): reference_generate(
                model, variables, p, max_new=6) for p in prompts}
            results: dict = {}
            threads = [threading.Thread(
                target=lambda p=p: results.__setitem__(
                    tuple(p), dec.submit(p))) for p in prompts]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert results == want
        finally:
            dec.close()

    def test_agreeing_draft_emits_multiple_tokens_per_forward(self, lm):
        """Draft == target weights: every proposal is accepted, so each
        verify forward emits k+1 tokens (the counter-based speedup
        claim; the bench banks the same number)."""
        from kubeflow_tpu.serving.continuous import SlotDecoder

        model, variables = lm
        dec = SlotDecoder(model, variables, slots=2, prompt_len=8,
                          max_new_tokens=6, draft_model=model,
                          draft_variables=variables, draft_k=3)
        try:
            prompts = [[1, 2, 3], [4, 5]]
            want = [reference_generate(model, variables, p, max_new=6)
                    for p in prompts]
            assert [dec.submit(p) for p in prompts] == want
            st = dec.stats()
            assert st["spec_tokens_emitted"] / st["spec_rounds"] > 1.0
            assert st["spec_tokens_accepted"] > 0
        finally:
            dec.close()

    @pytest.mark.parametrize("page,hits,clones", [(4, 1, 0), (3, 2, 1)])
    def test_spec_composes_with_paged_and_prefix_reuse(
            self, lm, page, hits, clones):
        from kubeflow_tpu.serving.continuous import SlotDecoder

        model, variables = lm
        pm = paged_model(kv_pages=33, kv_page_size=page)
        dec = SlotDecoder(pm, variables, slots=3, prompt_len=8,
                          max_new_tokens=4, draft_model=model,
                          draft_variables=variables, draft_k=3)
        try:
            p = [2, 7, 1, 8, 2, 8, 1, 8]
            want = reference_generate(model, variables, p)
            assert dec.submit(p) == want
            assert dec.submit(p) == want      # prefix-cache hit path
            st = dec.stats()
            assert (st["prefix_hit_pages"], st["cow_clones"]) == (hits, clones)
            # the hit's suffix is a rung of the ladder too
            assert st["prefill_tokens_computed"] == 8 + page
            assert st["spec_tokens_emitted"] / st["spec_rounds"] > 1.0
        finally:
            dec.close()

    def test_greedy_only(self, lm):
        from kubeflow_tpu.serving.continuous import SlotDecoder

        model, variables = lm
        with pytest.raises(ValueError, match="greedy"):
            SlotDecoder(model, variables, slots=2, prompt_len=8,
                        max_new_tokens=4, temperature=0.7,
                        draft_model=model, draft_variables=variables)


class TestDecodeBenchContract:
    @staticmethod
    def _bench():
        import os
        import sys

        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        sys.path.insert(0, os.path.join(here, "tools"))
        try:
            import serve_bench as sb
        finally:
            sys.path.pop(0)
        return sb

    # a CI-speed miniature of DECODE_CONFIG: same invariants, smaller
    # model geometry (the banked run uses the full config)
    SMALL = {
        "seed": 5, "model": "transformer-test", "vocab_size": 64,
        "prompt_len": 8, "max_new_tokens": 4, "req_new": 2,
        "page_size": 2, "dense_slots": 2, "paged_slots": 4,
        "requests": 4, "shared_prefix": 6, "draft_k": 2,
        "spec_requests": 2,
    }

    def test_banked_results_satisfy_acceptance(self):
        """BENCH_SERVE_r02.json is the PR's acceptance artifact: >= 2x
        admitted sequences at the same cache bytes, >= 40% prefill
        tokens saved by the prefix cache, > 1 token per target forward
        — all token-identical across arms."""
        import json
        import os

        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(here, "BENCH_SERVE_r02.json")) as fh:
            banked = json.load(fh)
        d = banked["decode"]
        assert d["density"]["identical_tokens"] is True
        assert d["density"]["same_cache_bytes"] is True
        assert d["density"]["concurrency_x"] >= 2.0
        assert d["prefix"]["identical_tokens"] is True
        assert d["prefix"]["saving_pct"] >= 40.0
        assert d["speculative"]["identical_tokens"] is True
        assert d["speculative"]["tokens_per_forward"] > 1.0
        assert d["density"]["paged"]["peak_active"] == \
            d["config"]["requests"]

    def test_check_gate_round_trip(self, tmp_path):
        """``--check`` passes against a just-banked run of the same
        config and fails loudly (exit 1) against a poisoned bank —
        the sched_bench ratchet discipline over the new bank."""
        import json

        sb = self._bench()
        result = sb.run_decode_bench(dict(self.SMALL))
        assert result["density"]["identical_tokens"]
        assert result["density"]["concurrency_x"] >= 2.0
        assert result["prefix"]["saving_pct"] >= 40.0
        assert result["speculative"]["tokens_per_forward"] > 1.0
        ok = tmp_path / "bank_ok.json"
        ok.write_text(json.dumps({"decode": result}))
        assert sb.check_decode_bench(str(ok)) == 0
        bad = json.loads(ok.read_text())
        bad["decode"]["fingerprint"] = "poisoned"
        bad_path = tmp_path / "bank_bad.json"
        bad_path.write_text(json.dumps(bad))
        assert sb.check_decode_bench(str(bad_path)) == 1
