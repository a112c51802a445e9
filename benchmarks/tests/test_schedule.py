import math

import pytest

from benchmarks.lib import schedule

CHAT = dict(prompt_min=64, prompt_max=2048, answer_min=64, answer_max=512)
DOC = dict(prompt_min=2048, prompt_max=4096, answer_min=16, answer_max=64,
           rate=3.0)


def _totals(reqs):
    return (len(reqs), sum(len(r.prompt) for r in reqs),
            sum(r.max_new for r in reqs))


@pytest.mark.parametrize("traffic,sizes", [(CHAT, [64] * 8), (DOC, [24, 120, 48])])
def test_every_seed_offers_the_same_work(traffic, sizes):
    runs = [schedule.make_requests(traffic, 32000, seed, sizes)
            for seed in (0, 7, 2**31 + 11)]
    assert len({_totals(r) for r in runs}) == 1
    # and block by block, so that the measured window is the same work too
    for b in range(len(sizes)):
        assert len({_totals([r for r in run if r.block == b])
                    for run in runs}) == 1
    # the seed does change the order
    assert [len(r.prompt) for r in runs[0]] != [len(r.prompt) for r in runs[1]]


@pytest.mark.parametrize("traffic,sizes", [(CHAT, [64] * 28), (DOC, [24, 153, 48])])
def test_no_two_prompts_of_one_real_length(traffic, sizes):
    # PageAllocator._chain_hashes salts with the pad length only: equal
    # real lengths would share pad pages and compile a suffix shape
    reqs = schedule.make_requests(traffic, 32000, 5, sizes)
    lens = [len(r.prompt) for r in reqs]
    assert len(set(lens)) == len(lens)
    assert min(lens) >= traffic["prompt_min"]
    assert max(lens) <= traffic["prompt_max"]
    assert all(traffic["answer_min"] <= r.max_new <= traffic["answer_max"]
               for r in reqs)
    assert all(0 < t < 32000 for r in reqs for t in r.prompt)


def test_one_request_due_in_each_slot_first_half():
    reqs = schedule.make_requests(DOC, 32000, 9, [24, 120, 48])
    for k, r in enumerate(reqs):
        assert k / 3.0 <= r.due_s < (k + 0.5) / 3.0
    assert all(r.due_s is None for r in
               schedule.make_requests(CHAT, 32000, 9, [64]))


def test_blocks_are_stratified():
    # each block covers the range: its median prompt is near the
    # log-uniform median whatever the block
    reqs = schedule.make_requests(CHAT, 32000, 3, [64] * 8)
    mid = math.sqrt(64 * 2048)
    for b in range(8):
        lens = sorted(len(r.prompt) for r in reqs if r.block == b)
        assert 0.85 * mid < lens[32] < 1.15 * mid


def test_too_many_distinct_lengths_is_an_error():
    with pytest.raises(ValueError):
        schedule.make_requests(dict(CHAT, prompt_max=100), 32000, 0, [64])
