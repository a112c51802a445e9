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


class TestPageAllocator:
    def test_admit_shares_prefix_and_cows_the_full_hit(self):
        a = PageAllocator(num_pages=24, page_size=8, slots=4,
                          max_pages_per_slot=6)
        row = list(range(1, 33))                    # 4 full pages
        p0 = a.admit(0, row, 0, 40)
        assert p0.shared_pages == 0 and p0.compute_start == 0
        a.check()
        # identical prompt: every full page hits; the final position is
        # recomputed for logits, so the last shared page COW-clones
        need, cached = a.plan(row, 0, 40)
        assert cached == 32
        p1 = a.admit(1, row, 0, 40)
        assert p1.shared_pages == 4 and p1.compute_start == 31
        assert len(p1.copies) == 1 and a.cow_clones == 1
        a.check()
        # page-aligned divergence: 3 shared pages, no COW
        p2 = a.admit(2, row[:24] + [9] * 8, 0, 40)
        assert p2.shared_pages == 3 and p2.compute_start == 24
        assert not p2.copies
        a.check()
        # mid-page divergence: the divergent page hash misses entirely
        p3 = a.admit(3, row[:28] + [9] * 4, 0, 40)
        assert p3.shared_pages == 3 and p3.compute_start == 24
        a.check()

    def test_plan_accounts_for_the_cow_extra_page(self):
        a = PageAllocator(num_pages=8, page_size=4, slots=2,
                          max_pages_per_slot=3)
        row = list(range(1, 9))                     # 2 full pages
        a.admit(0, row, 0, 8)
        need, cached = a.plan(row, 0, 8)
        assert cached == 8
        assert need == 1                            # 0 fresh + 1 COW clone
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
                row = [rng.randrange(0, 4) for _ in range(plen)]
                total = plen + rng.randrange(0, 16)
                if pages_for(total, a.page_size) > a.max_pages_per_slot:
                    continue
                pad = rng.randrange(0, 2)
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
        # total_len 24 needs 6 pages - 2 hits + 1 COW = 5, obtainable =
        # free(2) + NON-HIT evictables(2) = 4: the naive
        # `need <= free + all evictables(4+2)` gate would admit and
        # starve; the correct gate refuses. 20 (need 4) fits exactly.
        assert a.can_admit(row, 0, 20) is True
        assert a.can_admit(row, 0, 24) is False
        a.admit(0, row, 0, 20)
        a.append(0, 20)                          # never raises
        a.check()

    def test_reset_forgets_everything(self):
        a = PageAllocator(num_pages=16, page_size=4, slots=2,
                          max_pages_per_slot=4)
        a.admit(0, list(range(1, 9)), 0, 12)
        a.reset()
        a.check()
        assert a.free_pages == 15 and a.used_pages == 0


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

    def test_prefix_reuse_cow_does_not_corrupt_the_sharer(self, lm):
        """Three live slots share prompt pages; the full-hit admissions
        COW-clone the page they must rewrite. Every decode must still
        equal the no-sharing reference — a clone that mutated the
        shared original would corrupt its sharers' tokens."""
        from kubeflow_tpu.serving.continuous import SlotDecoder

        model, variables = lm
        pm = paged_model(kv_pages=25, kv_page_size=4)
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
            assert st["prefix_hit_pages"] >= 2   # sharing really happened
            assert st["cow_clones"] >= 1         # and the COW path ran
        finally:
            dec.close()

    def test_admission_gates_on_pages_not_slots(self, lm):
        """A pool sized for ~2 live sequences with 6 slots: requests
        queue on page availability and all complete as pages free."""
        from kubeflow_tpu.serving.continuous import SlotDecoder

        model, variables = lm
        pm = paged_model(kv_pages=8, kv_page_size=4)  # 7 usable pages
        dec = SlotDecoder(pm, variables, slots=6, prompt_len=8,
                          max_new_tokens=4, prefix_cache=False)
        try:
            prompts = [[i + 1, i + 2] for i in range(6)]
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
            # 7 usable pages / 3 pages per sequence -> never 3 at once
            assert dec.stats()["peak_active"] <= 2
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

    def test_spec_composes_with_paged_and_prefix_reuse(self, lm):
        from kubeflow_tpu.serving.continuous import SlotDecoder

        model, variables = lm
        pm = paged_model(kv_pages=33, kv_page_size=4)
        dec = SlotDecoder(pm, variables, slots=3, prompt_len=8,
                          max_new_tokens=4, draft_model=model,
                          draft_variables=variables, draft_k=3)
        try:
            p = [2, 7, 1, 8, 2, 8, 1, 8]
            want = reference_generate(model, variables, p)
            assert dec.submit(p) == want
            assert dec.submit(p) == want      # prefix-cache hit path
            st = dec.stats()
            assert st["prefix_hit_pages"] >= 2 and st["cow_clones"] >= 1
            assert st["spec_tokens_emitted"] / st["spec_rounds"] > 1.0
        finally:
            dec.close()

    def test_spec_round_failure_recovers_instead_of_zombie(self, lm):
        """A failed donated verify poisons in-flight requests ONCE and
        the decoder rebuilds both caches + the allocator."""
        from kubeflow_tpu.serving.continuous import SlotDecoder

        model, variables = lm
        dec = SlotDecoder(model, variables, slots=2, prompt_len=8,
                          max_new_tokens=4, draft_model=model,
                          draft_variables=variables, draft_k=2)
        try:
            real_admit = dec._spec_admit_dense
            blew = []

            def exploding(*a, **kw):
                if not blew:
                    blew.append(1)
                    raise RuntimeError("RESOURCE_EXHAUSTED (simulated)")
                return real_admit(*a, **kw)

            dec._spec_admit_dense = exploding
            with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
                dec.submit([1, 2, 3])
            assert dec.submit([1, 2, 3]) == reference_generate(
                model, variables, [1, 2, 3])
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
