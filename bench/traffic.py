"""The one general generator: training batches and open-loop serving
traffic, both pure functions of the seed and a cell's traffic parameters.

Every seed gets the same set of sizes and gaps (quantiles of the stated
distributions), in an order drawn from the seed. The order is stratified
(`stratified_order`): each run of consecutive requests holds one size from
each stratum of the distribution, so that what a window of a few tens of
requests holds is alike from seed to seed while the order differs.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Sequence

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def zipf_tokens(rng: np.random.Generator, shape, vocab: int) -> np.ndarray:
    """Zipf-like token ids: inverse CDF of a power law over the vocabulary
    (copied from the program's ``data/pipeline.TokenPipeline``)."""
    u = rng.random(shape)
    ranks = np.floor(vocab ** u - 1.0).astype(np.int64)
    return np.clip(ranks, 0, vocab - 1).astype(np.int32)


def train_batch(seed: int, step: int, batch: int, seq: int, vocab: int
                ) -> Dict[str, np.ndarray]:
    """Batch ``step`` of a run: ``batch`` rows of ``seq`` tokens and their
    next-token labels. Rows differ within and across steps."""
    toks = zipf_tokens(rng_for(seed, 1, step), (batch, seq + 1), vocab)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(n: int, median: float, sigma: float, lo: int, hi: int,
                      buckets: Sequence[int] = ()) -> np.ndarray:
    """n quantiles of a lognormal, clipped to [lo, hi] and, where buckets
    are given, rounded up to the next bucket."""
    from statistics import NormalDist
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = np.clip(np.ceil(median * np.exp(sigma * z)), lo, hi).astype(np.int64)
    if len(buckets):
        b = np.asarray(sorted(buckets))
        x = b[np.minimum(np.searchsorted(b, x), len(b) - 1)]
    return x


def stratified_order(n: int, strata: int, rng: np.random.Generator
                     ) -> np.ndarray:
    """A permutation of ``range(n)`` (indices into sorted quantiles) in
    blocks of ``strata`` consecutive places, each block holding one index
    from each of the ``strata`` equal runs of the sorted indices, in an
    order drawn from ``rng``; the last block takes what is left."""
    groups = np.array_split(np.arange(n), max(1, min(strata, n)))
    groups = [rng.permutation(g) for g in groups]
    out = []
    for j in range(max(len(g) for g in groups)):
        block = np.array([g[j] for g in groups if j < len(g)])
        out.extend(block[rng.permutation(len(block))])
    return np.asarray(out, np.int64)


@dataclasses.dataclass(frozen=True)
class Request:
    due_s: float               # when it is due, from the window's start
    prompt: np.ndarray         # int32 token ids
    max_new_tokens: int


def serve_schedule(seed: int, traffic: Dict, seconds: float, vocab: int
                   ) -> List[Request]:
    """Open-loop Poisson arrivals at ``traffic["rate_per_s"]`` over the
    window: round(rate x seconds) requests, gaps at the quantiles of an
    exponential, scaled so that the first request is due at the window's
    start and the last inside it. Prompt and output lengths at the
    quantiles of their stated distributions. Gaps, prompt lengths and
    output lengths each in a stratified order drawn from the seed
    (``traffic["strata"]`` strata); prompt tokens from the seed."""
    n = max(1, int(round(traffic["rate_per_s"] * seconds)))
    k = int(traffic["strata"])
    order = rng_for(seed, 5)
    gaps = -np.log1p(-_quantiles(n))
    gaps = gaps[stratified_order(n, k, order)]
    due = (np.cumsum(gaps) - gaps[0]) * (seconds / gaps.sum())
    p, o = traffic["prompt"], traffic["output"]
    plen = lognormal_lengths(n, p["median"], p["sigma"], p["min"], p["max"],
                             p.get("buckets", ()))[stratified_order(n, k,
                                                                    order)]
    olen = lognormal_lengths(n, o["median"], o["sigma"], o["min"],
                             o["max"])[stratified_order(n, k, order)]
    rng = rng_for(seed, 2)
    return [Request(float(due[i]),
                    zipf_tokens(rng, (int(plen[i]),), vocab),
                    int(olen[i])) for i in range(n)]


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation over all values,
    as ``numpy.percentile`` gives it; NaN for no values."""
    if not len(values):
        return math.nan
    return float(np.percentile(np.asarray(values, np.float64), q))


def latencies(due: Dict[int, float], stamps: Dict[int, List[float]],
              t_end: float):
    """From each request's due time and its tokens' times (one clock, in
    seconds) to what the end-to-end metrics take: TTFT of every request due
    (to ``t_end`` where it has no token yet), every gap between consecutive
    tokens of every request, and the number of tokens. Milliseconds."""
    ttft = [((stamps[r][0] if stamps.get(r) else t_end) - d) * 1e3
            for r, d in due.items()]
    gaps = [(b - a) * 1e3 for got in stamps.values()
            for a, b in zip(got, got[1:])]
    return ttft, gaps, sum(len(g) for g in stamps.values())
