"""One traffic generator for every mix file (``perfbench/mixes/<name>.json``).

A mix fixes the *set* of request sizes and arrival gaps: prompt lengths
come from a few buckets (each prompt length compiles its own prefill
program), output lengths from a clipped lognormal, gaps from an
exponential, all taken at stratified quantiles so that no seed draws
another amount of work.  ``--seed`` only shuffles that set and draws the
prompt tokens.  So two seeds run the same work in another order, and the
same seed runs the same requests.

Mix keys read here:

* ``loop``: ``"closed"`` (a queue kept at ``queue_min`` requests) or
  ``"open"`` (Poisson arrivals at ``rate_per_s``);
* ``prompt``: ``buckets``, ``lognormal_median``, ``lognormal_sigma``;
  each request takes the bucket nearest, in log length, to its lognormal
  draw;
* ``output``: ``lognormal_median``, ``lognormal_sigma``, ``min``, ``max``;
* ``pool_requests`` (closed loop): size of the set, cycled in a new order
  each pass;
* ``serving``: the ``ServingConfig`` fields (``batch_size``,
  ``block_size``, ``chunk_tokens``, ``max_seq``).
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Iterator, List, Set, Tuple

import numpy as np

GOLDEN = (math.sqrt(5.0) - 1.0) / 2.0


@dataclasses.dataclass(frozen=True)
class RequestSpec:
    index: int
    prompt: np.ndarray  # (T,) int32
    max_new_tokens: int
    arrival_s: float = 0.0  # due time from the window's start (open loop)


def rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one purpose of one seed; any whole seed, negative
    or past 64 bits, maps to a valid one."""
    return np.random.default_rng([seed % (1 << 63), stream])


def _stratified(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lognormal(u: np.ndarray, median: float, sigma: float) -> np.ndarray:
    inv = NormalDist().inv_cdf
    return np.array([median * math.exp(sigma * inv(float(x))) for x in u])


def sizes(mix: dict, n: int) -> List[Tuple[int, int]]:
    """The mix's fixed set of ``n`` (prompt length, output length) pairs.

    Outputs take the lognormal at stratified quantiles; prompts at a
    golden-ratio sequence of quantiles, so the two are not correlated."""
    p, o = mix["prompt"], mix["output"]
    buckets = np.array(sorted(p["buckets"]), dtype=np.int64)
    u_prompt = np.mod((np.arange(n) + 0.5) * GOLDEN, 1.0)
    want = _lognormal(u_prompt, p["lognormal_median"], p["lognormal_sigma"])
    idx = np.abs(np.log(want)[:, None] - np.log(buckets)[None, :]).argmin(axis=1)
    prompts = buckets[idx]
    outs = _lognormal(_stratified(n), o["lognormal_median"], o["lognormal_sigma"])
    outs = np.clip(np.round(outs), o["min"], o["max"]).astype(np.int64)
    return [(int(a), int(b)) for a, b in zip(prompts, outs)]


def _request(seed: int, index: int, size: Tuple[int, int], vocab: int, arrival=0.0):
    prompt_len, out_len = size
    toks = rng(seed, 1_000_000 + index).integers(0, vocab, size=prompt_len, dtype=np.int32)
    return RequestSpec(index, toks, out_len, arrival)


def closed_loop(mix: dict, seed: int, vocab: int) -> Iterator[RequestSpec]:
    """Requests without end: the fixed set, in a new seeded order each pass."""
    pool = sizes(mix, mix["pool_requests"])
    index = 0
    for cycle in range(1 << 30):
        for j in rng(seed, cycle).permutation(len(pool)):
            yield _request(seed, index, pool[j], vocab)
            index += 1


def open_loop(
    mix: dict, seed: int, vocab: int, seconds: float, rate_per_s: float
) -> List[RequestSpec]:
    """Poisson arrivals over ``seconds`` at ``rate_per_s``: the gaps are
    exponential quantiles, and the sizes the mix's set, both shuffled by
    ``seed``.  The first request is due at 0."""
    n = max(1, int(round(rate_per_s * seconds)))
    gaps = -np.log1p(-_stratified(n)) / rate_per_s
    gaps = gaps[rng(seed, 1).permutation(n)]
    arrivals = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    pool = sizes(mix, n)
    order = rng(seed, 2).permutation(n)
    return [
        _request(seed, i, pool[j], vocab, float(arrivals[i]))
        for i, j in enumerate(order)
    ]


def prefill_shapes(buckets, chunk: int) -> Set[Tuple[int, int, bool]]:
    """Every (chunk length, prompt length, final) that the scheduler's
    token budget can cut from prompts of these lengths.

    A step spends ``chunk`` tokens on the prefill queue in order; a prompt
    that starts with ``r`` tokens of budget left takes ``min(r, left)``,
    then whole budgets.  Walk the reachable ``r`` from a fresh step (a
    step whose queue runs dry leaves the rest of its budget unused, and
    the next starts fresh, which the walk holds already)."""
    shapes: Set[Tuple[int, int, bool]] = set()
    seen, todo = set(), [chunk]
    while todo:
        r = todo.pop()
        if r in seen:
            continue
        seen.add(r)
        for length in buckets:
            left, budget = length, r
            while True:
                tc = min(budget, left)
                left -= tc
                shapes.add((tc, length, left == 0))
                if left == 0:
                    nxt = budget - tc
                    todo.append(nxt if nxt > 0 else chunk)
                    break
                budget = chunk
    return shapes


def block_counts(mix: dict, pool: List[Tuple[int, int]]) -> Set[int]:
    """Every number of KV blocks a request of ``pool`` reserves."""
    bs = mix["serving"]["block_size"]
    return {-(-(p + o) // bs) for p, o in pool}
