"""Property tests for the paged KV cache (train/kv_cache.py).

Random alloc/grow/free traces drive the host-side `PageAllocator` while a
numpy mirror shadows the device-side pool — the invariants under test:

  * no page is ever aliased across live slots (checked independently of
    `check_invariants`, so the test doesn't trust the code under test);
  * free-list conservation: every non-null page is live xor free;
  * the reserved null page never enters a live row or the free list;
  * gather-via-page-table == the dense mirror for every live slot, for
    arbitrary interleavings of prefill writes, token appends and frees —
    i.e. page recycling never leaks a previous tenant's KV into a reader.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from _hypothesis_compat import given, settings, st

from repro.train import kv_cache as kvc


def _independent_invariants(alloc: kvc.PageAllocator) -> None:
    """Re-derive the allocator invariants without calling the allocator's
    own checker."""
    live = np.flatnonzero(alloc.live)
    owned = []
    for s in live:
        row = alloc.page_table[s, : alloc.n_alloc[s]].tolist()
        assert kvc.NULL_PAGE not in row
        # enough capacity for the recorded length
        assert alloc.n_alloc[s] * alloc.page_size >= alloc.lengths[s]
        owned.extend(row)
    assert len(set(owned)) == len(owned), "page aliased across live slots"
    free = list(alloc._free)
    assert kvc.NULL_PAGE not in free
    assert not set(owned) & set(free), "page both live and free"
    assert len(owned) + len(free) == alloc.n_pages - 1, "page leaked"
    for s in np.flatnonzero(~alloc.live):
        assert (alloc.page_table[s] == kvc.NULL_PAGE).all()


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_alloc_trace_invariants(seed):
    """Random alloc/grow/free trace: invariants hold after every op."""
    rng = np.random.default_rng(seed)
    n_slots = int(rng.integers(1, 5))
    max_pages = int(rng.integers(1, 7))
    page = int(rng.choice([4, 8, 16]))
    n_pages = int(rng.integers(2, 2 + n_slots * max_pages))
    alloc = kvc.PageAllocator(n_pages, n_slots, max_pages, page)
    for _ in range(60):
        op = rng.integers(0, 3)
        live = [int(s) for s in np.flatnonzero(alloc.live)]
        if op == 0:
            length = int(rng.integers(0, max_pages * page + 1))
            if alloc.can_admit(length):
                slot, pages = alloc.alloc_slot(length)
                assert len(pages) == alloc.pages_for(length)
                assert alloc.lengths[slot] == length
        elif op == 1 and live:
            slot = int(rng.choice(live))
            new_len = int(alloc.lengths[slot]) + int(rng.integers(1, page + 1))
            if (alloc.pages_for(new_len) <= max_pages
                    and alloc.pages_for(new_len) - alloc.n_alloc[slot]
                    <= alloc.n_free):
                alloc.ensure(slot, new_len)
                assert alloc.lengths[slot] == new_len
        elif op == 2 and live:
            slot = int(rng.choice(live))
            held = int(alloc.n_alloc[slot])
            before = alloc.n_free
            pages = alloc.free_slot(slot)
            assert len(pages) == held
            assert alloc.n_free == before + held
        alloc.check_invariants()
        _independent_invariants(alloc)


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_free_list_conservation_after_churn(seed):
    """After freeing everything, every non-null page is back on the free
    list exactly once."""
    rng = np.random.default_rng(seed)
    page, mp, n_slots = 8, 4, 3
    alloc = kvc.PageAllocator(1 + n_slots * mp, n_slots, mp, page)
    for _ in range(40):
        if rng.random() < 0.6:
            length = int(rng.integers(1, mp * page + 1))
            if alloc.can_admit(length):
                alloc.alloc_slot(length)
        else:
            live = np.flatnonzero(alloc.live)
            if len(live):
                alloc.free_slot(int(rng.choice(live)))
    for s in np.flatnonzero(alloc.live):
        alloc.free_slot(int(s))
    assert alloc.n_free == alloc.n_pages - 1
    assert sorted(alloc._free) == list(range(1, alloc.n_pages))
    alloc.check_invariants()


def test_allocator_errors():
    with pytest.raises(ValueError):
        kvc.PageAllocator(1, 1, 1, 8)          # no room for the null page
    alloc = kvc.PageAllocator(4, 2, 2, 8)      # 3 usable pages
    with pytest.raises(ValueError):
        alloc.alloc_slot(3 * 8)                # needs 3 pages > max_pages
    s0, _ = alloc.alloc_slot(16)               # 2 pages
    with pytest.raises(RuntimeError):
        alloc.alloc_slot(16)                   # pool exhausted (1 page left)
    s1, _ = alloc.alloc_slot(8)
    with pytest.raises(RuntimeError):
        alloc.ensure(s1, 16)                   # pool exhausted mid-grow
    with pytest.raises(RuntimeError):
        alloc.alloc_slot(1)                    # no free slot
    alloc.free_slot(s0)
    with pytest.raises(RuntimeError):
        alloc.free_slot(s0)                    # double free
    with pytest.raises(RuntimeError):
        alloc.ensure(s0, 8)                    # dead slot
    alloc.check_invariants()


def test_lowest_free_slot_and_page_reuse_order():
    alloc = kvc.PageAllocator(8, 3, 2, 4)
    a, pa = alloc.alloc_slot(4)
    b, pb = alloc.alloc_slot(4)
    assert (a, b) == (0, 1)
    assert pa == [1] and pb == [2]             # low page ids first
    alloc.free_slot(a)
    c, pc = alloc.alloc_slot(4)
    assert c == 0                              # lowest slot recycled
    alloc.check_invariants()


# ---------------------------------------------------------------------------
# device side: gather-via-page-table ≡ dense numpy mirror
# ---------------------------------------------------------------------------

_NL, _KVH, _DH, _PAGE, _MP, _SLOTS = 2, 2, 4, 4, 3, 3


def _mirror_trace(seed: int, n_ops: int = 14):
    """Run a random admit/append/free trace against both the paged device
    cache and a dense numpy mirror; yield (cache, mirror, cur_len, live)."""
    rng = np.random.default_rng(seed)
    smax = _MP * _PAGE
    n_pages = 1 + _SLOTS * _MP
    alloc = kvc.PageAllocator(n_pages, _SLOTS, _MP, _PAGE)
    cache = kvc.init_paged_cache(_NL, n_pages, _SLOTS, _MP, _KVH, _PAGE,
                                 _DH, jnp.float32)
    mirror_k = np.zeros((_NL, _SLOTS, smax, _KVH, _DH), np.float32)
    mirror_v = np.zeros_like(mirror_k)
    cur_len = np.zeros((_SLOTS,), np.int32)

    for _ in range(n_ops):
        op = rng.integers(0, 4)
        live = [int(s) for s in np.flatnonzero(alloc.live)]
        if op <= 1:                                       # admit (weighted)
            length = int(rng.integers(1, smax + 1))
            if not alloc.can_admit(length):
                continue
            slot, _ = alloc.alloc_slot(length)
            ks = rng.standard_normal((_NL, length, _KVH, _DH)) \
                .astype(np.float32)
            vs = rng.standard_normal((_NL, length, _KVH, _DH)) \
                .astype(np.float32)
            cache = kvc.write_prefill(cache, slot,
                                      jnp.asarray(alloc.page_table[slot]),
                                      jnp.asarray(ks), jnp.asarray(vs),
                                      length)
            mirror_k[:, slot, :length] = ks
            mirror_v[:, slot, :length] = vs
            cur_len[slot] = length
        elif op == 2 and live:                            # append one token
            ok = True
            for s in live:
                want = int(cur_len[s]) + 1
                if (alloc.pages_for(want) > _MP
                        or alloc.pages_for(want) - alloc.n_alloc[s]
                        > alloc.n_free):
                    ok = False
            if not ok:
                continue
            for s in live:
                alloc.ensure(s, int(cur_len[s]) + 1)
            cache["page_table"] = jnp.asarray(alloc.page_table)
            cache["length"] = jnp.asarray(cur_len)
            k_new = rng.standard_normal((_NL, _SLOTS, _KVH, _DH)) \
                .astype(np.float32)
            v_new = rng.standard_normal((_NL, _SLOTS, _KVH, _DH)) \
                .astype(np.float32)
            cache = kvc.append_token(cache, jnp.asarray(k_new),
                                     jnp.asarray(v_new))
            for s in live:
                mirror_k[:, s, cur_len[s]] = k_new[:, s]
                mirror_v[:, s, cur_len[s]] = v_new[:, s]
                cur_len[s] += 1
            cache["length"] = jnp.asarray(cur_len)
        elif op == 3 and live:                            # evict
            slot = int(rng.choice(live))
            alloc.free_slot(slot)
            cache["page_table"] = jnp.asarray(alloc.page_table)
            mirror_k[:, slot] = 0.0
            mirror_v[:, slot] = 0.0
            cur_len[slot] = 0
            cache["length"] = jnp.asarray(cur_len)
        alloc.check_invariants()
    return cache, (mirror_k, mirror_v), cur_len, \
        [int(s) for s in np.flatnonzero(alloc.live)]


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_gather_matches_dense_mirror(seed):
    """gather-via-page-table == the dense mirror for every live slot up to
    its length, after a random alloc/append/free trace (page recycling must
    never surface a previous tenant's KV)."""
    cache, (mk, mv), cur_len, live = _mirror_trace(seed)
    kd, vd = kvc.gather_dense(cache)
    kd, vd = np.asarray(kd), np.asarray(vd)
    for s in live:
        n = int(cur_len[s])
        np.testing.assert_array_equal(kd[:, s, :n], mk[:, s, :n])
        np.testing.assert_array_equal(vd[:, s, :n], mv[:, s, :n])


@pytest.mark.parametrize("length", [1, _PAGE, _PAGE * _MP, _PAGE + 1])
def test_write_prefill_roundtrip(length):
    """Prefill scatter + gather is the identity up to ``length``, including
    exact page-boundary lengths and the full-capacity case."""
    rng = np.random.default_rng(length)
    n_pages = 1 + _MP
    alloc = kvc.PageAllocator(n_pages, 1, _MP, _PAGE)
    cache = kvc.init_paged_cache(_NL, n_pages, 1, _MP, _KVH, _PAGE, _DH,
                                 jnp.float32)
    slot, _ = alloc.alloc_slot(length)
    ks = rng.standard_normal((_NL, length, _KVH, _DH)).astype(np.float32)
    vs = rng.standard_normal((_NL, length, _KVH, _DH)).astype(np.float32)
    cache = kvc.write_prefill(cache, slot,
                              jnp.asarray(alloc.page_table[slot]),
                              jnp.asarray(ks), jnp.asarray(vs), length)
    kd, vd = kvc.gather_dense(cache)
    np.testing.assert_array_equal(np.asarray(kd)[:, 0, :length], ks)
    np.testing.assert_array_equal(np.asarray(vd)[:, 0, :length], vs)
    assert int(cache["length"][0]) == length


def test_append_layer_dead_slot_hits_trash_page():
    """Dead (all-NULL) slots scatter into page 0 and never corrupt a live
    slot's pages."""
    n_pages = 1 + 2 * _MP
    alloc = kvc.PageAllocator(n_pages, 2, _MP, _PAGE)
    cache = kvc.init_paged_cache(1, n_pages, 2, _MP, _KVH, _PAGE, _DH,
                                 jnp.float32)
    slot, _ = alloc.alloc_slot(3)
    ks = np.ones((1, 3, _KVH, _DH), np.float32)
    cache = kvc.write_prefill(cache, slot,
                              jnp.asarray(alloc.page_table[slot]),
                              jnp.asarray(ks), jnp.asarray(ks), 3)
    alloc.ensure(slot, 4)
    cache["page_table"] = jnp.asarray(alloc.page_table)
    k_new = np.full((1, 2, _KVH, _DH), 7.0, np.float32)
    cache = kvc.append_token(cache, jnp.asarray(k_new), jnp.asarray(k_new))
    kd, _ = kvc.gather_dense(cache)
    kd = np.asarray(kd)
    np.testing.assert_array_equal(kd[0, 0, :3],
                                  np.ones((3, _KVH, _DH), np.float32))
    np.testing.assert_array_equal(kd[0, 0, 3],
                                  np.full((_KVH, _DH), 7.0, np.float32))
    # the dead slot's write landed in the trash page, not in slot 0's pages
    trash = np.asarray(cache["k_pages"][0, kvc.NULL_PAGE])
    assert float(np.abs(trash).max()) == 7.0


@pytest.mark.parametrize("layer", [0, 2])
def test_append_layer_writes_only_its_layer(layer):
    """On a stacked 3-layer pool, `append_layer` through `layer_table`
    writes the live slot's token into layer ``layer`` alone, the dead
    (all-NULL) slot's into that layer's trash page, and leaves every other
    element of the pool bitwise unchanged."""
    n_l, n_pages, slot_len = 3, 1 + 2 * _MP, _PAGE + 2
    alloc = kvc.PageAllocator(n_pages, 2, _MP, _PAGE)
    slot, _ = alloc.alloc_slot(slot_len + 1)
    rng = np.random.default_rng(layer)
    pool = rng.standard_normal((n_l, n_pages, _KVH, _PAGE, _DH)
                               ).astype(np.float32)
    new = rng.standard_normal((2, _KVH, _DH)).astype(np.float32)
    pos = np.asarray([slot_len, 0], np.int32)
    out = jax.jit(lambda p, l: kvc.append_layer(
        p, jnp.asarray(new),
        kvc.layer_table(jnp.asarray(alloc.page_table), l, n_pages),
        jnp.asarray(pos)))(jnp.asarray(pool), layer)
    want = pool.copy()
    page = alloc.page_table[slot, slot_len // _PAGE]
    want[layer, page, :, slot_len % _PAGE] = new[slot]
    want[layer, kvc.NULL_PAGE, :, 0] = new[1]
    np.testing.assert_array_equal(np.asarray(out), want)


def test_plan_pages_geometry():
    from repro.configs.base import ModelConfig
    from repro.core.policy import ONLINE_BLOCK
    cfg = ModelConfig(arch_id="tiny", family="dense", n_layers=2,
                      d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                      vocab_size=256, head_dim=128)
    plan = kvc.plan_pages(cfg, ONLINE_BLOCK, n_slots=4, max_len=96,
                          dtype=jnp.float32, page_size=16)
    assert plan.page_size == 16
    assert plan.max_pages == -(-96 // 16)
    assert plan.n_pages >= 1 + plan.max_pages
    # paged HBM-per-slot beats the dense slot-based baseline at slack=1
    assert plan.hbm_bytes_per_slot(cfg) <= plan.dense_hbm_bytes_per_slot(cfg)
    # oversubscription shrinks the pool below n_slots * max_pages
    tight = kvc.plan_pages(cfg, ONLINE_BLOCK, n_slots=4, max_len=96,
                           dtype=jnp.float32, page_size=16, slack=0.5)
    assert tight.n_pages < plan.n_pages
    assert tight.hbm_bytes_per_slot(cfg) < plan.hbm_bytes_per_slot(cfg)
    # a page edge below the sublane is rounded up; above max_len clamped
    small = kvc.plan_pages(cfg, ONLINE_BLOCK, n_slots=2, max_len=64,
                           dtype=jnp.float32, page_size=1)
    assert small.page_size >= 1 and small.page_size * small.max_pages >= 64
    big = kvc.plan_pages(cfg, ONLINE_BLOCK, n_slots=2, max_len=64,
                         dtype=jnp.float32, page_size=4096)
    assert big.page_size <= 64
