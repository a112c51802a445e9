"""The one general traffic generator. A mix is a data file of parameters
(benchmarks/traffic/<name>.json); this turns it and a seed into requests.

Every seed offers the same work: the lengths are a fixed stratified
multiset (log-uniform quantiles), cut into blocks that are each a
stratified sample of their own, and the seed only shuffles inside a block
and jitters the arrivals. No two prompts of a run have the same real
length (PERF.md, "the page allocator's trap")."""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    block: int
    prompt: tuple          # real token ids
    max_new: int
    due_s: float | None    # open loop: seconds after the schedule's start


def _log_quantile(lo: int, hi: int, u: float) -> int:
    return int(round(math.exp(
        math.log(lo) + u * (math.log(hi) - math.log(lo)))))


def stratified_lengths(lo: int, hi: int, sizes: list[int],
                       distinct: bool) -> list[np.ndarray]:
    """One array of lengths in lo..hi for each block size. Entry j of
    block b sits at quantile (j + (b + 0.5) / blocks) / size_b, so each
    block covers the whole range evenly. With `distinct`, equal lengths
    are nudged apart across all blocks, keeping the order."""
    n = sum(sizes)
    if distinct and hi - lo + 1 < n:
        raise ValueError(f"{n} distinct lengths do not fit in {lo}..{hi}")
    u = np.concatenate([(np.arange(size) + (b + 0.5) / len(sizes)) / size
                        for b, size in enumerate(sizes)])
    vals = np.array([_log_quantile(lo, hi, x) for x in u])
    if distinct:
        order = np.argsort(u, kind="stable")
        v = vals[order]
        for i in range(1, n):           # forwards: strictly increasing
            v[i] = max(v[i], v[i - 1] + 1)
        v[-1] = min(v[-1], hi)
        for i in range(n - 2, -1, -1):  # backwards: back inside lo..hi
            v[i] = min(v[i], v[i + 1] - 1)
        vals[order] = v
    return np.split(vals, np.cumsum(sizes)[:-1])


def make_requests(traffic: dict, vocab: int, seed: int,
                  sizes: list[int]) -> list[Request]:
    """One block of requests for each size. Open-loop mixes (`rate` set)
    get one due time in each slot of 1/rate seconds, at a seeded offset
    inside the slot's first half."""
    rng = np.random.default_rng(seed)
    p = stratified_lengths(traffic["prompt_min"], traffic["prompt_max"],
                           sizes, distinct=True)
    a = stratified_lengths(traffic["answer_min"], traffic["answer_max"],
                           sizes, distinct=False)
    rate = traffic.get("rate")
    out = []
    for b, size in enumerate(sizes):
        pl, al = rng.permutation(p[b]), rng.permutation(a[b])
        for j in range(size):
            k = len(out)
            due = None if rate is None else (k + 0.5 * rng.random()) / rate
            # token 0 is the server's padding: real tokens are 1..vocab-1
            toks = rng.integers(1, vocab, int(pl[j]), dtype=np.int64)
            out.append(Request(k, b, tuple(int(t) for t in toks),
                               int(al[j]), due))
    return out
