"""`PageAllocator` with two kinds of page (runtime/kvcache.py): the held
kind as ever, and the window kind, whose pages go back to their free
list while the request runs. The property drive of tests/test_kvcache.py
over both kinds, the gate on both, and the bound of a release."""

import random

import numpy as np
import pytest

from kubeflow_tpu.runtime.kvcache import TRASH_PAGE, PageAllocator, pages_for

PS, WINDOW, AHEAD = 4, 16, 3


def two_kinds(num_pages=64, window_pages=40, slots=6, mp=14):
    return PageAllocator(num_pages, PS, slots, mp, prefix_cache=False,
                         window=WINDOW, window_pages=window_pages,
                         window_ahead=AHEAD)


def lowest_seen(query: int, pad: int) -> int:
    """The lowest position a query at `query` sees in a window layer."""
    return max(pad, query - WINDOW + 1)


@pytest.mark.parametrize("fresh", [False, True], ids=["gather", "fresh"])
def test_property_random_transitions_hold_invariants(fresh):
    """Random admit / release / append / free over both kinds: no page in
    two owners, none lost, `check()` after EVERY transition, and a
    released window page never inside any live query's range. `fresh`:
    the prefill reads no page (the flash path), so an admission claims
    the window kind from the first tick's window on."""
    rng = random.Random(20261003)
    # pools that do not hold six whole slots: the gate has work to do
    a = two_kinds(num_pages=48, window_pages=22 if fresh else 36)
    live: dict[int, list] = {}    # slot -> [total_len, cur_len, pad]
    admits = gated = 0
    for _step in range(8000):
        op = rng.random()
        if op < 0.35 and len(live) < a.slots:
            slot = next(s for s in range(a.slots) if s not in live)
            plen = rng.randrange(1, 41)
            total = plen + rng.randrange(0, 16)
            if pages_for(total, PS) > a.max_pages_per_slot:
                continue
            row = [rng.randrange(0, 2) for _ in range(plen)]
            pad = rng.choice([0, 0, 1, rng.randrange(0, plen)])
            reads_from = plen if fresh else None
            if not a.can_admit(row, pad, total, reads_from):
                gated += 1
                continue
            need = a.plan_window(row, pad, total, reads_from)
            used = a.window_used_pages
            plan = a.admit(slot, row, pad, total, reads_from)
            assert a.window_used_pages - used <= need
            live[slot] = [total, plen, pad]
            admits += 1
            # what the next query sees is there, in both kinds
            first = plen if fresh else plan.compute_start
            for pos in range(lowest_seen(first, pad), plen):
                assert a.window_table[slot, pos // PS] != TRASH_PAGE
                assert a.table[slot, pos // PS] != TRASH_PAGE
        elif op < 0.85 and live:
            slot = rng.choice(sorted(live))
            total, cur, pad = live[slot]
            if cur < total:
                step = min(total - cur, rng.randrange(1, AHEAD + 1))
                # a round: release behind its first query, then its pages
                a.release(slot, cur)
                a.append(slot, cur + step)
                live[slot][1] = cur + step
                # every position a query of this round sees holds a page
                # of its own slot, in both kinds
                for query in range(cur, cur + step):
                    for pos in range(lowest_seen(query, pad), query + 1):
                        assert a.window_table[slot, pos // PS] != TRASH_PAGE
                    assert a.table[slot, query // PS] != TRASH_PAGE
                assert a.window_held(slot) <= a._wring
        elif live:
            slot = rng.choice(sorted(live))
            a.free(slot)
            del live[slot]
        a.check()
    assert admits > 300 and a.window_released > 300
    assert gated > 20           # the gate did bite, on one kind or the other
    for slot in sorted(live):
        a.free(slot)
        a.check()
    assert a.used_pages == 0 and a.window_used_pages == 0


def test_a_release_is_tight_and_an_index():
    """A page goes back in the round in which its last position falls
    behind the first query's window, not a round sooner or later."""
    a = two_kinds()
    a.admit(0, list(range(40)), 0, 56)          # the gather: every page
    assert a.window_held(0) == 10 == a.window_covered(0)
    assert a.release(0, 40) == 6                # positions <= 24: pages 0-5
    assert (a.window_table[0, :6] == TRASH_PAGE).all()
    assert a.window_table[0, 6] != TRASH_PAGE    # holds position 25
    assert a.release(0, 42) == 0                # 27 - 1 = 26 is in page 6
    assert a.release(0, 43) == 1                # now page 6 ends at 27 = 43-16
    assert a.release(0, 43) == 0
    a.append(0, 46)
    assert a.window_held(0) == pages_for(46, PS) - 7
    assert a.window_covered(0) == pages_for(46, PS)
    a.check()
    # the held kind keeps everything
    assert (a.table[0, :pages_for(46, PS)] != TRASH_PAGE).all()
    a.free(0)
    a.check()
    assert a.window_used_pages == 0 and a.window_released == 7


def test_fresh_admission_claims_no_page_behind_the_first_tick():
    a = two_kinds()
    row = [1] * 40
    assert a.plan_window(row, 0, 56) == 10          # the gather
    # the first tick at 40 sees 25..40: pages 6-9; later a ring of
    # window + ahead + a page
    assert a.plan_window(row, 0, 56, reads_from=40) == a._wring == 6
    a.admit(0, row, 0, 56, reads_from=40)
    assert a.window_held(0) == 4
    assert (a.window_table[0, :6] == TRASH_PAGE).all()
    assert (a.table[0, :10] != TRASH_PAGE).all()
    a.check()
    # padding that reaches into the window: nothing before the first real
    a.admit(1, [0] * 30 + [1] * 10, 30, 56, reads_from=40)
    assert a.window_held(1) == pages_for(40, PS) - 30 // PS
    a.check()


def test_admission_is_gated_on_the_window_kind_too():
    """Plenty of held pages, few window pages: the gate keeps what live
    slots may still claim (their quota) out of a newcomer's reach."""
    a = two_kinds(num_pages=200, window_pages=14, slots=4)   # 13 usable
    row = [1] * 24
    assert a.can_admit(row, 0, 44, reads_from=24)
    a.admit(0, row, 0, 44, reads_from=24)       # holds 4, may hold 6
    assert a.window_held(0) == 4 and a._wquota[0] == 6
    assert a.can_admit(row, 0, 44, reads_from=24)
    a.admit(1, row, 0, 44, reads_from=24)
    # 13 - 8 held = 5 free, of which 4 are the two quotas' rest: 1 spare
    assert not a.can_admit(row, 0, 44, reads_from=24)
    assert a.can_admit([1] * 2, 0, 4, reads_from=2)          # needs 1
    # both grow through their rings and never find the pool dry
    for cur in range(24, 44):
        for slot in (0, 1):
            a.release(slot, cur)
            a.append(slot, cur + 1)
            a.check()
    a.free(0)
    assert a.can_admit(row, 0, 44, reads_from=24)
    a.check()


def test_the_two_kinds_go_together_and_need_the_prefix_cache_off():
    with pytest.raises(ValueError, match="go together"):
        PageAllocator(8, 4, 2, 4, prefix_cache=False, window=8)
    with pytest.raises(ValueError, match="need prefix_cache=False"):
        PageAllocator(8, 4, 2, 4, window=8, window_pages=8)
    one = PageAllocator(8, 4, 2, 4)
    assert one.window_table is None and one.plan_window([1] * 4, 0, 8) == 0
    assert one.release(0, 100) == 0 and one.window_used_pages == 0


def test_reset_returns_both_kinds():
    a = two_kinds()
    a.admit(0, [1] * 30, 0, 40)
    a.admit(1, [1] * 12, 3, 20, reads_from=12)
    a.reset()
    a.check()
    assert a.window_used_pages == 0 and a.used_pages == 0
    assert not np.asarray(a.window_table).any()
