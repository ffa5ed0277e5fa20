"""Property tests for the search-based autotuner: candidate legality,
scoring determinism, and persistent-cache behaviour (kernels.search +
kernels.tune_cache + autotune.best_params)."""
import json
import os

import pytest

from _hypothesis_compat import given, settings, st
from repro.kernels import autotune, search, tune_cache
from repro.kernels.autotune import MXU, VMEM_BUDGET, KernelParams


@pytest.fixture()
def fresh_cache(tmp_path, monkeypatch):
    """Re-point the default cache at an empty per-test file."""
    path = str(tmp_path / "tune.json")
    monkeypatch.setenv("REPRO_TUNE_CACHE", path)
    tune_cache.reset()
    yield path
    tune_cache.reset()


# ---------------------------------------------------------------------------
# Candidate enumeration invariants
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(m=st.integers(1, 4096), n=st.integers(1, 4096),
       k=st.integers(1, 4096),
       in_bytes=st.sampled_from([2, 4]),
       ft_level=st.sampled_from(["off", "block", "tile", "inner"]))
def test_candidates_are_legal(m, n, k, in_bytes, ft_level):
    cands = search.enumerate_candidates(m, n, k, in_bytes=in_bytes,
                                        ft_level=ft_level)
    assert cands, (m, n, k)
    mp = autotune._round_up(m, MXU)
    np_ = autotune._round_up(n, MXU)
    kp = autotune._round_up(k, MXU)
    for p in cands:
        # MXU-aligned in every dimension
        assert p.bm % MXU == 0 and p.bn % MXU == 0 and p.bk % MXU == 0, p
        # within the VMEM working-set budget (FT scratch included)
        assert search.vmem_bytes(p, in_bytes, ft_level) <= VMEM_BUDGET, p
        # never exceeds — and exactly divides — the MXU-padded problem
        assert p.bm <= mp and p.bn <= np_ and p.bk <= kp, p
        assert (autotune._round_up(m, p.bm) % p.bm == 0
                and autotune._round_up(n, p.bn) % p.bn == 0
                and autotune._round_up(k, p.bk) % p.bk == 0)


def test_candidate_set_is_deterministic_and_covers_table_sizes():
    c1 = search.enumerate_candidates(2048, 2048, 2048)
    c2 = search.enumerate_candidates(2048, 2048, 2048)
    assert c1 == c2
    tiles = {(p.bm, p.bn, p.bk) for p in c1}
    # The static table's "huge" pick must be in the searched space.
    assert tuple(autotune.TABLE["huge"]) in tiles


@settings(max_examples=15, deadline=None)
@given(m=st.integers(1, 2048), n=st.integers(1, 2048),
       k=st.integers(1, 2048))
def test_model_selection_is_deterministic(m, n, k):
    p1 = search.select_best(m, n, k, measure=False)
    p2 = search.select_best(m, n, k, measure=False)
    assert p1 == p2
    assert search.vmem_bytes(p1) <= VMEM_BUDGET


def test_predicted_time_prefers_fitting_tiles_on_ragged_shapes():
    """The roofline score must charge padding FLOPs: a 512-tile on a 160²
    problem is strictly worse than a 256-tile."""
    small = KernelParams(256, 256, 256, "small")
    huge = KernelParams(512, 512, 256, "huge")
    assert (search.predicted_time_s(160, 160, 256, small)
            < search.predicted_time_s(160, 160, 256, huge))


@settings(max_examples=15, deadline=None)
@given(dim=st.integers(1, 4096), max_tile=st.sampled_from([128, 256, 512]),
       align=st.sampled_from([8, 128]))
def test_fit_tile_minimizes_executed_work(dim, max_tile, align):
    c = search.fit_tile(dim, max_tile, align)
    assert c % align == 0 and align <= c <= max_tile
    waste = -(-dim // c) * c
    for other in range(align, max_tile + 1, align):
        assert waste <= -(-dim // other) * other


def test_fit_tile_examples():
    assert search.fit_tile(100, 128, 8) == 104      # one masked tile
    assert search.fit_tile(77, 128, 128) == 128     # lane floor
    assert search.fit_tile(300, 384, 128) == 384    # single deep k tile
    assert search.fit_tile(4096, 512, 128) == 512   # divisible → largest


# ---------------------------------------------------------------------------
# best_params + persistent cache
# ---------------------------------------------------------------------------

def test_best_params_deterministic_with_warm_cache(fresh_cache):
    p1 = autotune.best_params(300, 300, 600, measure=False)
    assert os.path.exists(fresh_cache)          # search result persisted
    p2 = autotune.best_params(300, 300, 600, measure=False)
    p3 = autotune.best_params(300, 300, 600)    # warm: no search, no measure
    assert p1 == p2 == p3
    # warm-cache hit must serve a *different* shape of the same class by
    # clamping the stored tile, never exceeding the padded problem
    p4 = autotune.best_params(64, 300, 600)
    assert p4.bm <= autotune._round_up(64, MXU)


def test_cache_round_trip(fresh_cache):
    key = tune_cache.cache_key("cpu", "small", 4, "off")
    params = KernelParams(128, 256, 384, "small")
    c = tune_cache.TuneCache(fresh_cache)
    c.put(key, params)
    reloaded = tune_cache.TuneCache(fresh_cache).get(key)
    assert reloaded == params
    # file is valid schema-tagged JSON
    with open(fresh_cache) as f:
        raw = json.load(f)
    assert raw["schema"] == 1 and key in raw["entries"]


def test_cache_corrupt_file_degrades_to_empty(fresh_cache):
    with open(fresh_cache, "w") as f:
        f.write("{not json")
    c = tune_cache.TuneCache(fresh_cache)
    assert c.get(tune_cache.cache_key("cpu", "small", 4, "off")) is None
    assert len(c) == 0
    # and the next put round-trips fine over the corrupt file
    key = tune_cache.cache_key("cpu", "huge", 2, "block")
    c.put(key, KernelParams(512, 512, 256, "huge"))
    assert tune_cache.TuneCache(fresh_cache).get(key) is not None


def test_best_params_ft_levels_keyed_separately(fresh_cache):
    autotune.best_params(256, 256, 512, measure=False, ft_level="off")
    autotune.best_params(256, 256, 512, measure=False, ft_level="tile")
    c = tune_cache.TuneCache(fresh_cache)
    kinds = {k.rsplit("/", 1)[1] for k in c.keys()}
    assert {"ft_off", "ft_tile"} <= kinds


def test_best_params_divides_padded_problem(fresh_cache):
    for (m, n, k) in [(100, 77, 300), (1, 1, 1), (2048, 2048, 2048),
                      (4096, 128, 1024)]:
        p = autotune.best_params(m, n, k, measure=False)
        mp, np_, kp = autotune.padded_shape(m, n, k, p)
        assert mp % p.bm == 0 and np_ % p.bn == 0 and kp % p.bk == 0
        assert p.vmem_bytes() <= VMEM_BUDGET


def test_default_cache_is_the_committed_file_read_only(monkeypatch):
    """Without REPRO_TUNE_CACHE the tiles come from the committed
    benchmarks/tuned/<device_kind>.json (never rewritten) plus the roofline
    model, and the request path never times a candidate."""
    monkeypatch.delenv("REPRO_TUNE_CACHE", raising=False)
    tune_cache.reset()
    try:
        cache = tune_cache.default_cache()
        assert not cache.writable
        assert cache.path == os.path.normpath(os.path.join(
            tune_cache.TUNED_DIR, f"{autotune.device_kind()}.json"))
        before = open(cache.path).read() if os.path.exists(cache.path) \
            else None

        def no_timing(*a, **k):
            raise AssertionError("request path measured a candidate")
        monkeypatch.setattr(search, "measure_candidates", no_timing)
        p = autotune.best_params(333, 777, 1111)
        assert autotune.best_params(333, 777, 1111) == p
        assert len(cache) > 0
        after = open(cache.path).read() if os.path.exists(cache.path) \
            else None
        assert after == before
    finally:
        tune_cache.reset()
