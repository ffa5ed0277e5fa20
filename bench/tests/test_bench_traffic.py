"""The generator and the latency arithmetic: pure functions of the seed."""
import math
from collections import Counter

import numpy as np

from bench import traffic

MIX = {"rate_per_s": 3.0, "strata": 8,
       "prompt": {"median": 512, "sigma": 0.8, "min": 64, "max": 2048,
                  "buckets": [128, 256, 384, 512, 768, 1024, 1536, 2048]},
       "output": {"median": 128, "sigma": 0.7, "min": 16, "max": 512}}


def _key(reqs):
    return [(r.due_s, r.prompt.tobytes(), r.max_new_tokens) for r in reqs]


def test_schedule_is_a_function_of_the_seed():
    a = traffic.serve_schedule(2**33 + 5, MIX, 40.0, 1000)
    b = traffic.serve_schedule(2**33 + 5, MIX, 40.0, 1000)
    c = traffic.serve_schedule(2**33 + 6, MIX, 40.0, 1000)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)


def test_every_seed_gets_the_same_sizes_and_arrivals():
    """The same set of gaps and sizes for every seed, in another order."""
    a = traffic.serve_schedule(1, MIX, 40.0, 1000)
    b = traffic.serve_schedule(99, MIX, 40.0, 1000)
    assert len(a) == len(b) == 120

    def gaps(s):
        due = [r.due_s for r in s]
        return np.diff(due)

    for f in (lambda s: [len(r.prompt) for r in s],
              lambda s: [r.max_new_tokens for r in s]):
        assert sorted(f(a)) == sorted(f(b)) and f(a) != f(b)
    # the gaps between due times: one set, less the one before the first
    ga, gb = (Counter(np.round(gaps(s), 9)) for s in (a, b))
    assert sum(((ga - gb) + (gb - ga)).values()) <= 2
    assert not np.allclose(gaps(a), gaps(b))
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert Counter(len(r.prompt) for r in a)[128] > 0
    for s in (a, b):
        due = [r.due_s for r in s]
        assert due[0] == 0.0 and due == sorted(due) and due[-1] < 40.0


def test_every_block_of_requests_spans_the_distribution():
    """Each run of 8 consecutive requests holds one output length from
    each eighth of the sorted lengths: a window of a few tens of requests
    holds alike work whatever the seed."""
    for seed in (3, 2**40 + 1):
        s = traffic.serve_schedule(seed, MIX, 40.0, 1000)
        olen = np.array([r.max_new_tokens for r in s])
        edges = np.sort(olen)[::15]           # 120 requests, 8 strata
        for j in range(0, 120, 8):
            block = np.sort(olen[j:j + 8])
            assert np.all(block >= edges) and \
                np.all(block[:-1] <= edges[1:]), (seed, j, block)


def test_stratified_order_is_a_permutation_by_blocks():
    rng = np.random.default_rng(4)
    for n, k in ((20, 8), (7, 8), (64, 8), (1, 8)):
        p = traffic.stratified_order(n, k, rng)
        assert sorted(p) == list(range(n))
        groups = np.array_split(np.arange(n), min(k, n))
        stratum = {int(i): g for g, idx in enumerate(groups) for i in idx}
        for j in range(0, n - n % min(k, n), min(k, n)):
            assert sorted(stratum[int(i)] for i in p[j:j + min(k, n)]) == \
                list(range(min(k, n)))


def test_length_distribution():
    s = traffic.serve_schedule(7, MIX, 100.0, 1000)
    plen = np.array([len(r.prompt) for r in s])
    olen = np.array([r.max_new_tokens for r in s])
    assert set(plen) <= set(MIX["prompt"]["buckets"])
    assert 0.45 <= np.mean(plen <= 512) <= 0.55     # median 512, rounded up
    assert plen.min() >= 128 and plen.max() == 2048
    assert 120 <= np.median(olen) <= 136
    assert olen.min() >= 16 and olen.max() <= 512
    assert all(r.prompt.dtype == np.int32 and r.prompt.max() < 1000
               for r in s)


def test_train_batches_differ_and_shift_by_one():
    a = traffic.train_batch(3, 0, 2, 16, 100)
    b = traffic.train_batch(3, 1, 2, 16, 100)
    assert a["tokens"].shape == (2, 16)
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    assert not np.array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"][0], a["tokens"][1])
    np.testing.assert_array_equal(
        a["tokens"], traffic.train_batch(3, 0, 2, 16, 100)["tokens"])


def test_percentiles_count_every_request_and_unserved_ones_at_the_close():
    due = {0: 0.0, 1: 1.0, 2: 2.0, 3: 9.0}
    stamps = {0: [0.1, 0.3, 0.4], 1: [1.5, 2.0], 2: [2.05]}
    ttft, gaps, tokens = traffic.latencies(due, stamps, t_end=10.0)
    assert sorted(np.round(ttft, 6)) == [50.0, 100.0, 500.0, 1000.0]
    assert sorted(np.round(gaps, 6)) == [100.0, 200.0, 500.0]
    assert tokens == 6
    assert traffic.percentile(ttft, 50) == 300.0
    assert math.isclose(traffic.percentile(ttft, 90), 850.0)
    assert math.isnan(traffic.percentile([], 90))


def test_lognormal_quantiles():
    x = traffic.lognormal_lengths(1001, 100, 0.5, 1, 10**6)
    assert x[500] == 100 and x[0] < 30 and x[-1] > 400
    assert list(x) == sorted(x)
    y = traffic.lognormal_lengths(9, 100, 2.0, 50, 150, buckets=[64, 128])
    assert set(y) <= {64, 128}
