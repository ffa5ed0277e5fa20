"""Paged/block KV cache for the FT serving engine (PR 9).

Dense per-slot KV caches pay ``n_slots × max_len`` HBM whether or not a
slot is live — the padding the paper's §Perf accounting calls avoidable.
This module replaces that layout with a vLLM/JetStream-style *page pool*:

  * the pool holds ``n_pages`` fixed-size pages per layer, shaped
    ``(n_layers, n_pages, n_kv_heads, page_size, head_dim)`` — the
    trailing two dims are (sublane, lane)-shaped so ONE page is exactly
    one kv block of the paged flash decode kernel
    (`kernels.flashft._flash_decode_kernel`), streamed in through a
    scalar-prefetched page-table index map;
  * a host-side `PageAllocator` (free list) hands pages to slots on
    demand — a slot holds ⌈length/page_size⌉ pages, never max_len;
  * **page 0 is the reserved null/trash page**: unallocated page-table
    entries (and the whole row of a dead slot) point at it, so the
    engine's batched scatters for dead slots land harmlessly and no
    branchy gather/scatter masking is needed device-side. It is never
    allocated and never read by a live slot.

The device-side cache is a plain pytree of arrays (jit/donation
friendly); the allocator is the single mutable owner of the page table
and lengths — the engine pushes `numpy` table/length snapshots to the
device each step (a few KiB). Allocator invariants (no page aliased
across live slots, free-list conservation, null page never allocated)
are queryable via `check_invariants` — the property-test surface
(tests/test_kv_cache.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: The reserved trash page: never allocated, never read by a live slot.
NULL_PAGE = 0


# ---------------------------------------------------------------------------
# sizing: the autotuner picks the page edge
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PagePlan:
    """Resolved paged-cache geometry for one (model, engine) config."""
    page_size: int       # tokens per page (the decode kernel's kv block)
    max_pages: int       # page-table width = pages per slot at max_len
    n_pages: int         # pool size INCLUDING the reserved null page
    n_slots: int
    max_len: int

    def hbm_bytes_per_slot(self, cfg, dtype_bytes: int = 2) -> int:
        """K+V pool bytes per slot at full occupancy (the benchmark's
        HBM-per-slot figure; excludes the shared null page)."""
        per_tok = 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim \
            * dtype_bytes
        usable = (self.n_pages - 1) * self.page_size
        return per_tok * usable // max(self.n_slots, 1)

    def dense_hbm_bytes_per_slot(self, cfg, dtype_bytes: int = 2) -> int:
        """The slot-based dense baseline: max_len tokens per slot, always."""
        per_tok = 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim \
            * dtype_bytes
        return per_tok * self.max_len


def plan_pages(cfg, ft, *, n_slots: int, max_len: int,
               dtype=jnp.bfloat16, page_size: Optional[int] = None,
               slack: float = 1.0) -> PagePlan:
    """Derive the paged-cache geometry. The page edge defaults to the
    autotuned streamed-block (bn) of the ``flashdecode`` variant
    (`templates.FlashKernelSpec(direction="decode")`) — the same tile the
    kernel wants to stream per step, so gather granularity and kernel
    block are one number. ``slack`` scales the pool (1.0 = every slot can
    reach max_len; < 1.0 oversubscribes HBM for bursty traffic)."""
    from repro.kernels import autotune, search
    from repro.kernels.templates.spec import FlashKernelSpec

    in_bytes = jnp.dtype(dtype).itemsize
    sub = search.sublane(in_bytes)
    dh_p = -(-cfg.head_dim // 128) * 128
    n_rep = cfg.n_heads // cfg.n_kv_heads
    bq = -(-n_rep // sub) * sub
    level = ft.level if ft.enabled else "off"
    if page_size is None:
        fspec = FlashKernelSpec(ft_level=level, direction="decode", dh=dh_p)
        p = autotune.best_params(bq, max(max_len, autotune.MXU), dh_p,
                                 in_bytes, ft_level=level, spec=fspec,
                                 batch=n_slots * cfg.n_kv_heads)
        page_size = p.bn
    page_size = max(sub, min(page_size, -(-max_len // sub) * sub))
    assert page_size % sub == 0, (page_size, sub)
    max_pages = -(-max_len // page_size)
    n_pages = 1 + max(max_pages, int(round(n_slots * max_pages * slack)))
    return PagePlan(page_size=page_size, max_pages=max_pages,
                    n_pages=n_pages, n_slots=n_slots, max_len=max_len)


# ---------------------------------------------------------------------------
# host-side allocator
# ---------------------------------------------------------------------------

class PageAllocator:
    """Free-list page allocator over the shared pool (host-side).

    The allocator owns the authoritative page table and per-slot lengths
    as numpy arrays; the engine snapshots them to the device each step.
    All methods are O(pages touched); none touch the device.
    """

    def __init__(self, n_pages: int, n_slots: int, max_pages: int,
                 page_size: int):
        if n_pages < 2:
            raise ValueError(f"need >= 2 pages (one is the reserved null "
                             f"page), got {n_pages}")
        self.n_pages = n_pages
        self.n_slots = n_slots
        self.max_pages = max_pages
        self.page_size = page_size
        # pop() hands out low page ids first
        self._free: List[int] = list(range(n_pages - 1, NULL_PAGE, -1))
        self.page_table = np.full((n_slots, max_pages), NULL_PAGE, np.int32)
        self.lengths = np.zeros((n_slots,), np.int32)
        self.n_alloc = np.zeros((n_slots,), np.int32)   # pages per slot
        self.live = np.zeros((n_slots,), bool)

    # -- queries -----------------------------------------------------------

    @property
    def n_free(self) -> int:
        return len(self._free)

    def pages_for(self, length: int) -> int:
        return -(-int(length) // self.page_size)

    def free_slots(self) -> List[int]:
        return [int(s) for s in np.flatnonzero(~self.live)]

    def can_admit(self, length: int) -> bool:
        return (bool((~self.live).any())
                and self.pages_for(length) + 1 <= self.n_free)

    def live_pages(self) -> Dict[int, List[int]]:
        return {int(s): self.page_table[s, :self.n_alloc[s]].tolist()
                for s in np.flatnonzero(self.live)}

    # -- mutations ---------------------------------------------------------

    def alloc_slot(self, length: int) -> Tuple[int, List[int]]:
        """Claim the lowest free slot and allocate pages for ``length``
        tokens. Returns (slot, pages)."""
        free = self.free_slots()
        if not free:
            raise RuntimeError("no free slot")
        slot = free[0]
        need = self.pages_for(length)
        if need > self.max_pages:
            raise ValueError(f"length {length} needs {need} pages > "
                             f"max_pages {self.max_pages}")
        if need > self.n_free:
            raise RuntimeError(f"pool exhausted: need {need} pages, "
                               f"{self.n_free} free")
        self.live[slot] = True
        self.lengths[slot] = 0
        self.ensure(slot, length)
        return slot, self.page_table[slot, :need].tolist()

    def ensure(self, slot: int, new_length: int) -> List[int]:
        """Grow ``slot`` to hold ``new_length`` tokens, allocating pages as
        needed. Returns the newly allocated pages (possibly empty)."""
        if not self.live[slot]:
            raise RuntimeError(f"slot {slot} is not live")
        need = self.pages_for(new_length)
        if need > self.max_pages:
            raise ValueError(f"length {new_length} needs {need} pages > "
                             f"max_pages {self.max_pages}")
        new: List[int] = []
        while self.n_alloc[slot] < need:
            if not self._free:
                raise RuntimeError("page pool exhausted")
            page = self._free.pop()
            self.page_table[slot, self.n_alloc[slot]] = page
            self.n_alloc[slot] += 1
            new.append(page)
        self.lengths[slot] = new_length
        return new

    def free_slot(self, slot: int) -> List[int]:
        """Return a finished slot's pages to the free list. The table row
        reverts to all-NULL so subsequent dead-slot scatters hit the trash
        page."""
        if not self.live[slot]:
            raise RuntimeError(f"slot {slot} is not live")
        pages = self.page_table[slot, :self.n_alloc[slot]].tolist()
        self._free.extend(pages)
        self.page_table[slot] = NULL_PAGE
        self.lengths[slot] = 0
        self.n_alloc[slot] = 0
        self.live[slot] = False
        return pages

    # -- invariants (the property-test surface) ----------------------------

    def check_invariants(self) -> None:
        """Raise AssertionError on any broken allocator invariant."""
        free = self._free
        assert NULL_PAGE not in free, "null page entered the free list"
        assert len(set(free)) == len(free), "duplicate page in free list"
        live = self.live_pages()
        owned: Dict[int, int] = {}
        for slot, pages in live.items():
            assert len(pages) == self.n_alloc[slot]
            assert self.pages_for(self.lengths[slot]) <= len(pages)
            for pg in pages:
                assert pg != NULL_PAGE, f"slot {slot} owns the null page"
                assert pg not in owned, \
                    f"page {pg} aliased by slots {owned[pg]} and {slot}"
                owned[pg] = slot
        overlap = set(owned) & set(free)
        assert not overlap, f"pages both live and free: {sorted(overlap)}"
        # conservation: every non-null page is either live or free
        assert len(owned) + len(free) == self.n_pages - 1, \
            (len(owned), len(free), self.n_pages)
        for s in np.flatnonzero(~self.live):
            assert (self.page_table[s] == NULL_PAGE).all(), \
                f"dead slot {int(s)} holds table entries"
            assert self.lengths[s] == 0 and self.n_alloc[s] == 0

    def snapshot(self) -> Tuple[jax.Array, jax.Array]:
        """Device copies of (page_table, lengths) for the decode step."""
        return jnp.asarray(self.page_table), jnp.asarray(self.lengths)


# ---------------------------------------------------------------------------
# device-side cache ops (pure functions over the cache pytree)
# ---------------------------------------------------------------------------

def init_paged_cache(n_layers: int, n_pages: int, n_slots: int,
                     max_pages: int, n_kv_heads: int, page_size: int,
                     head_dim: int, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Fresh paged cache pytree. Layout: pages hold (kv_head, position,
    lane) with (page_size, head_dim) as the trailing two dims — one page ≡
    one kv block of the paged decode kernel."""
    kv = (n_layers, n_pages, n_kv_heads, page_size, head_dim)
    return {
        "k_pages": jnp.zeros(kv, dtype),
        "v_pages": jnp.zeros(kv, dtype),
        "page_table": jnp.full((n_slots, max_pages), NULL_PAGE, jnp.int32),
        "length": jnp.zeros((n_slots,), jnp.int32),
    }


def write_prefill(cache: Dict[str, Any], slot, table_row: jax.Array,
                  ks: jax.Array, vs: jax.Array, length: int
                  ) -> Dict[str, Any]:
    """Scatter one slot's prefill KV into its pages.

    table_row int32[max_pages] — the slot's allocator row (NULL-padded:
    unused entries write zero padding into the trash page); ks/vs
    (n_layers, S, n_kv_heads, head_dim) with S ≤ max_pages·page_size.
    Also records ``length`` for the slot."""
    k_pages = cache["k_pages"]
    page = k_pages.shape[3]
    mp = table_row.shape[0]
    n_l, s, kvh, dh = ks.shape
    cap = mp * page
    assert s <= cap, (s, cap)

    def place(pages_arr, x):
        xp = jnp.pad(x.astype(pages_arr.dtype),
                     ((0, 0), (0, cap - s), (0, 0), (0, 0)))
        # (L, MP, page, KVH, dh) → (L, MP, KVH, page, dh): the value for an
        # advanced index on the pool's page axis.
        xp = xp.reshape(n_l, mp, page, kvh, dh).transpose(0, 1, 3, 2, 4)
        return pages_arr.at[:, table_row].set(xp)

    return {
        "k_pages": place(k_pages, ks),
        "v_pages": place(cache["v_pages"], vs),
        "page_table": cache["page_table"].at[slot].set(table_row),
        "length": cache["length"].at[slot].set(length),
    }


def append_layer(pages: jax.Array, kv_new: jax.Array, table: jax.Array,
                 pos: jax.Array) -> jax.Array:
    """Write one token's K (or V) for every slot into the pool, in place.
    pages (..., KVH, page, dh): one layer's pool (P, KVH, page, dh), or the
    stacked pool (L, P, KVH, page, dh) with ``table`` holding ids into its
    flattened (layer, page) axes (`layer_table`); kv_new (B, KVH, dh);
    table (B, MP); pos int32[B] — the target position (the slot's current
    length). One dynamic-update-slice per slot: XLA performs it in place on
    a donated or loop-carried pool, with no change of layout. Dead slots
    (all-NULL rows) write into the trash page; live slots never share a
    page, so the order of the writes does not matter."""
    lead = pages.shape[:-3]
    kvh, page, dh = pages.shape[-3:]
    b, mp = table.shape
    pidx = jnp.minimum(pos // page, mp - 1)
    target = table[jnp.arange(b), pidx]                    # (B,)
    offs = pos % page
    kv_new = kv_new.astype(pages.dtype).reshape(
        (b,) + (1,) * len(lead) + (kvh, 1, dh))
    for i in range(b):
        where = jnp.unravel_index(target[i], lead)
        pages = jax.lax.dynamic_update_slice(
            pages, kv_new[i], (*where, 0, offs[i], 0))
    return pages


def layer_table(table: jax.Array, layer, n_pages: int) -> jax.Array:
    """``table``'s page ids as ids into the stacked pool's flattened
    (layer, page) axes at ``layer`` — page p of layer l is l·n_pages + p —
    the form `append_layer` takes to write one layer of the stacked pool
    where it lies."""
    return table + layer * n_pages


def append_token(cache: Dict[str, Any], k_new: jax.Array, v_new: jax.Array
                 ) -> Dict[str, Any]:
    """Append one token per slot across all layers. k_new/v_new
    (n_layers, B, n_kv_heads, head_dim), written at each slot's current
    ``length``; lengths advance by one (dead all-NULL slots write into the
    trash page and their length stays meaningful to the caller only)."""
    table, pos = cache["page_table"], cache["length"]
    app = jax.vmap(append_layer, in_axes=(0, 0, None, None))
    return {
        "k_pages": app(cache["k_pages"], k_new, table, pos),
        "v_pages": app(cache["v_pages"], v_new, table, pos),
        "page_table": table,
        "length": pos + 1,
    }


def gather_layer(pages: jax.Array, table: jax.Array) -> jax.Array:
    """Dense (B, max_pages·page, KVH, dh) view of ONE layer's pool through
    the page table (NULL entries read the trash page → positions past a
    slot's length are garbage and must stay masked by `length`)."""
    g = pages[table]                         # (B, MP, KVH, page, dh)
    b, mp, kvh, page, dh = g.shape
    return g.transpose(0, 1, 3, 2, 4).reshape(b, mp * page, kvh, dh)


def gather_dense(cache: Dict[str, Any]) -> Tuple[jax.Array, jax.Array]:
    """Dense (n_layers, B, S_max, KVH, dh) K and V views — the oracle
    layout `models.blocks.decode_attention` consumes (and the property
    tests' paged ≡ dense reference)."""
    gat = jax.vmap(gather_layer, in_axes=(0, None))
    return (gat(cache["k_pages"], cache["page_table"]),
            gat(cache["v_pages"], cache["page_table"]))
