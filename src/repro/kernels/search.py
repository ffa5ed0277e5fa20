"""Search-based kernel-parameter autotuning (paper §3.2 / Fig. 10-11).

The paper's code generator enumerates template parameters per input-shape
class, benchmarks the instantiated kernels, and keeps the winner — beating
one fixed kernel by up to 230% on irregular shapes. This module is that
search for the Pallas GEMM template:

  * `enumerate_candidates` — every MXU-aligned `(bm, bn, bk)` whose working
    set (operand double-buffers + f32 accumulator + FT checksum scratch)
    fits the VMEM budget and that does not exceed the padded problem.
  * `predicted_time_s`    — the analytical fallback score: a per-kernel
    roofline (`tools.roofline.kernel_time_s`) over executed (padded) FLOPs
    and modeled HBM traffic with tile-reuse accounting, plus the FT
    checksum-update FLOPs for the requested level.
  * `measure_candidates`  — the empirical score: wall-clock timing of each
    instantiated kernel via `benchmarks.common.time_fn` — only meaningful
    on real hardware, so `select_best` uses it only when asked
    (``measure=True``, from `benchmarks/tune_campaign` on the chip).
  * `fit_tile`            — ragged-dispatch helper: the block edge (aligned
    to hardware granularity) that minimizes executed work on a dimension
    that does not divide the class tile, used by the masked kernels.

Everything here is deterministic given the same inputs: candidate order is
sorted, the model is closed-form, and ties break toward larger tiles
(more VMEM reuse), so a warm cache and a cold cache agree on hardware-free
hosts.
"""
from __future__ import annotations

import math
from typing import Callable, List, Optional, Sequence, Tuple

from repro.tools import roofline
from .autotune import MXU, VMEM_BUDGET, KernelParams, classify, _round_up

#: Largest tile edge the search considers (matches the static TABLE's max).
MAX_TILE = 512

#: Sublane granularity of the (8, 128) VREG by element width — the minimum
#: legal second-to-last block-dim multiple on TPU.
_SUBLANE = {8: 8, 4: 8, 2: 16, 1: 32}


def sublane(in_bytes: int) -> int:
    return _SUBLANE.get(in_bytes, 8)


# ---------------------------------------------------------------------------
# Candidate enumeration
# ---------------------------------------------------------------------------

def vmem_bytes(p: KernelParams, in_bytes: int = 4,
               ft_level: str = "off", spec=None, *,
               m: int = 0, groups: int = 0) -> int:
    """FT-level-and-variant-aware working set — delegates to the single
    model on `KernelSpec.vmem_bytes` (which itself wraps
    `KernelParams.vmem_bytes` plus the fused-epilogue aux / extra-output
    buffers, and which the tgmm variant overrides wholesale — its operand
    tiles and accumulator have a different geometry) so search legality and
    budget clamping can never disagree. A grouped launch (``groups > 0``)
    additionally holds its scalar-prefetched tile→group map and per-group
    row bounds on chip: 4·(num_tiles + groups) bytes, where the tile count
    includes the worst-case per-group alignment padding — the group count
    is part of the working set, not just the key."""
    base = (spec.vmem_bytes(p, in_bytes, ft_level) if spec
            else p.vmem_bytes(in_bytes, ft_level))
    extra = 0
    if groups > 0:
        num_tiles = (m + groups * (p.bm - 1)) // p.bm + 1
        extra += 4 * (num_tiles + groups)
    return base + extra


def _tile_range(dim: int, max_tile: int = MAX_TILE) -> List[int]:
    upper = min(max_tile, _round_up(dim, MXU))
    return list(range(MXU, upper + 1, MXU))


def enumerate_candidates(m: int, n: int, k: int, *, in_bytes: int = 4,
                         ft_level: str = "off", spec=None,
                         max_tile: int = MAX_TILE,
                         groups: int = 0) -> List[KernelParams]:
    """All legal tile configs for the problem: MXU-aligned in every dim,
    no larger than the MXU-padded problem, within the VMEM budget (fused
    epilogue aux buffers — and grouped-dispatch metadata when ``groups`` is
    given — included)."""
    cls = classify(m, n, k)
    out = []
    for bm in _tile_range(m, max_tile):
        for bn in _tile_range(n, max_tile):
            for bk in _tile_range(k, max_tile):
                p = KernelParams(bm=bm, bn=bn, bk=bk, shape_class=cls)
                if vmem_bytes(p, in_bytes, ft_level, spec, m=m,
                              groups=groups) <= VMEM_BUDGET:
                    out.append(p)
    return out


# ---------------------------------------------------------------------------
# Analytical scoring (roofline fallback)
# ---------------------------------------------------------------------------

def executed_dims(m: int, n: int, k: int,
                  p: KernelParams) -> Tuple[int, int, int]:
    """Problem size the kernel actually executes under tiling (grid of
    whole tiles covering the problem)."""
    return (_round_up(m, p.bm), _round_up(n, p.bn), _round_up(k, p.bk))


def ft_overhead_flops(p: KernelParams, ft_level: str, k_steps: int,
                      blocks: int) -> float:
    """Checksum-maintenance FLOPs across the whole launch. Per k-step per
    block: column checksum = reduce A tile (bm·bk) + GEMV (bk·bn MACs → 2×),
    row checksum = reduce B tile (bk·bn) + GEMV (bm·bk MACs → 2×); "inner"
    additionally reduces the materialized Δ both ways every step."""
    if ft_level == "off":
        return 0.0
    per_step = (p.bm * p.bk + 2 * p.bk * p.bn) + (p.bk * p.bn + 2 * p.bm * p.bk)
    if ft_level == "tile":
        per_step += p.bm * p.bn            # per-band verify reductions
    if ft_level == "inner":
        per_step += 2 * p.bm * p.bn        # Δ reduced along both axes
    return float(per_step) * k_steps * blocks


def predicted_time_s(m: int, n: int, k: int, p: KernelParams, *,
                     in_bytes: int = 4, ft_level: str = "off",
                     spec=None, batch: int = 1, groups: int = 0) -> float:
    """Roofline score of one candidate on the (padded) problem.

    HBM traffic model: each A tile is streamed once per output-column of
    tiles and each B tile once per output-row of tiles (no cross-block L2
    reuse on TPU — VMEM is the only cache we control), plus one output
    write. Compute: 2·M·N·K MACs on executed dims + checksum updates. A
    fused-epilogue `spec` adds its aux-operand reads and elementwise FLOPs
    (`KernelSpec.extra_hbm_bytes` / `epilogue_flops`) — the variant shifts
    the roofline intensity, which is why it is part of the tuning key.

    ``batch`` multiplies every term (a uniform batched launch runs the
    whole grid once per batch slice). ``groups`` models the grouped ragged
    dispatch instead: every group starts on a bm row-tile boundary, so up
    to bm-1 padding rows ride along per group — the executed M grows by
    the worst case ``groups·(bm-1)``, which is what steers the search away
    from deep row tiles when the expert count is high.

    The tgmm variant (``spec.tgmm``) is modeled on its own geometry: M is
    the *reduction* dimension (buffer rows, walked in bm tiles, carrying
    the same per-group alignment padding), the output is (G, K, N) written
    once per group in f32, the X buffer streams once per N-block column and
    the G buffer once per K-block row."""
    if groups > 0:
        m = m + groups * (p.bm - 1)     # per-group row-alignment padding
    me, ne, ke = executed_dims(m, n, k, p)
    gm, gn, gk = me // p.bm, ne // p.bn, ke // p.bk
    if spec is not None and spec.flash:
        # Flash-attention geometry: m = stationary seq dim (q for fwd/dq,
        # kv for dkv), n = streamed seq dim, k = head dim (never tiled —
        # spec.dh, not p.bk). Stationary operands are read once; the
        # streamed pair re-streams once per stationary block row; outputs
        # are written once per stationary row. The in-kernel GEMMs beyond
        # the S-GEMM and the softmax chain ride spec.epilogue_flops, the
        # side streams (g, stats, the extra dkv output) ride
        # spec.extra_hbm_bytes — same hooks as the fused-epilogue variants.
        dh = spec.dh
        flops = 2.0 * me * ne * dh + spec.epilogue_flops(me, ne)
        if ft_level != "off":
            # Checksum GEMVs: ~2·(bs + bt)·dh MACs per (stationary,
            # streamed) block pair per protected GEMM.
            n_gemms = spec._GEMMS[spec.direction]
            flops += n_gemms * 4.0 * (p.bm + p.bn) * dh * gm * gn
        stat_bytes = me * dh * in_bytes            # q (or k∥v via extra)
        stream_bytes = gm * 2.0 * ne * dh * in_bytes   # k+v (or q+g) re-read
        out_bytes = me * dh * in_bytes
        extra = spec.extra_hbm_bytes(me, ne, in_bytes)
        return batch * roofline.kernel_time_s(
            flops, stat_bytes + stream_bytes + out_bytes + extra)
    if spec is not None and spec.tgmm:
        tiles = gm
        flops = 2.0 * me * ne * ke
        if ft_level != "off":
            # Per row-tile per (ki, ni) block: two operand reductions
            # (bm·bk + bm·bn) and two checksum GEMVs (2·bm·bn + 2·bm·bk).
            per_step = 3.0 * (p.bm * p.bk + p.bm * p.bn)
            if ft_level == "tile":
                per_step += p.bk * p.bn
            if ft_level == "inner":
                per_step += 2.0 * p.bk * p.bn
            flops += per_step * tiles * gk * gn
        a_bytes = gn * me * ke * in_bytes       # X once per N-block column
        b_bytes = gk * me * ne * in_bytes       # G once per K-block row
        c_bytes = max(groups, 1) * ke * ne * 4  # dw written once, f32
        return roofline.kernel_time_s(flops, a_bytes + b_bytes + c_bytes)
    flops = 2.0 * me * ne * ke + ft_overhead_flops(p, ft_level, gk, gm * gn)
    a_bytes = gn * me * ke * in_bytes
    b_bytes = gm * ke * ne * in_bytes
    c_bytes = me * ne * in_bytes
    extra_bytes = 0.0
    if spec is not None:
        flops += spec.epilogue_flops(me, ne)
        extra_bytes = spec.extra_hbm_bytes(me, ne, in_bytes)
    return batch * roofline.kernel_time_s(
        flops, a_bytes + b_bytes + c_bytes + extra_bytes)


# ---------------------------------------------------------------------------
# Empirical scoring (hardware measurement)
# ---------------------------------------------------------------------------

def _time_fn_fallback(fn: Callable, *args, warmup: int = 2,
                      iters: int = 5) -> float:
    import time
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def _timer() -> Callable:
    try:                                   # shared benchmark harness when
        from benchmarks.common import time_fn  # run from the repo root
        return time_fn
    except ImportError:
        return _time_fn_fallback


def can_measure() -> bool:
    import jax
    return jax.default_backend() == "tpu"


def measure_candidates(m: int, n: int, k: int,
                       candidates: Sequence[KernelParams], *,
                       in_bytes: int = 4, ft_level: str = "off",
                       interpret: bool = False) -> List[float]:
    """Wall-clock each candidate (µs) on the padded problem. Compiles one
    kernel per candidate — intended for offline cache regeneration, not the
    request path."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.policy import FTConfig
    from . import ftgemm, gemm

    dtype = {4: jnp.float32, 2: jnp.bfloat16}.get(in_bytes, jnp.float32)
    time_fn = _timer()
    rng = np.random.default_rng(0)
    times = []
    for p in candidates:
        me, ne, ke = executed_dims(m, n, k, p)
        a = jnp.asarray(rng.normal(size=(me, ke)), dtype)
        b = jnp.asarray(rng.normal(size=(ke, ne)), dtype)
        if ft_level == "off":
            times.append(time_fn(
                lambda a, b, p=p: gemm.gemm(a, b, params=p,
                                            interpret=interpret), a, b))
        else:
            ft = FTConfig(level=ft_level)
            idx, mag = ftgemm.encode_injection(None)
            times.append(time_fn(
                lambda a, b, p=p, ft=ft: ftgemm.ft_gemm(
                    a, b, idx, mag, params=p, ft=ft, interpret=interpret),
                a, b))
    return times


# ---------------------------------------------------------------------------
# Selection
# ---------------------------------------------------------------------------

def select_best(m: int, n: int, k: int, *, in_bytes: int = 4,
                ft_level: str = "off", spec=None,
                measure: bool = False,
                max_tile: int = MAX_TILE,
                candidates: Optional[Sequence[KernelParams]] = None,
                batch: int = 1, groups: int = 0) -> KernelParams:
    """The search: enumerate → score (hardware timing with ``measure``,
    the roofline model otherwise) → deterministic winner (ties → larger
    tiles). The
    measured path times the base 2-D kernel of the requested FT level
    (epilogue chains and the batch axis perturb runtime well under timer
    noise on hardware; the modeled path accounts batch/group counts
    exactly)."""
    cands = list(candidates if candidates is not None else
                 enumerate_candidates(m, n, k, in_bytes=in_bytes,
                                      ft_level=ft_level, spec=spec,
                                      max_tile=max_tile, groups=groups))
    if not cands:
        raise ValueError(f"no legal tile candidates for {(m, n, k)}")
    if measure:
        scores = [t * 1e-6 for t in measure_candidates(
            m, n, k, cands, in_bytes=in_bytes, ft_level=ft_level)]
    else:
        scores = [predicted_time_s(m, n, k, p, in_bytes=in_bytes,
                                   ft_level=ft_level, spec=spec,
                                   batch=batch, groups=groups)
                  for p in cands]
    return min(zip(scores, cands),
               key=lambda sp: (sp[0], -sp[1].bm * sp[1].bn, -sp[1].bk))[1]


# ---------------------------------------------------------------------------
# Planner cost model (PR 10 — additive; the autotune scoring above is pinned
# by the tune-campaign cache diff and is deliberately untouched)
# ---------------------------------------------------------------------------

#: In-kernel GEMM count per population kind: how many k-loop GEMMs one
#: logical site launch runs (flash fwd = QK + PV; the 2-D/fused/batched/
#: grouped/tgmm kinds are one GEMM each).
_PLAN_GEMMS = {"flash": 2}

#: Reference k-tile for the step-verify count — matches MAX_TILE so the
#: model's verify cadence tracks what the autotuner would actually pick
#: for a large-k problem without consulting (or populating) the tune cache.
_PLAN_BK_REF = MAX_TILE


def ft_plan_base(kind: str, m: int, n: int, k: int, batch: int = 1,
                 in_bytes: int = 4) -> Tuple[float, float]:
    """(flops, hbm_bytes) of one *unprotected* launch of a site population.

    Deliberately tile-free: the planner prices sites against each other on
    pure problem geometry (a dims-only roofline), so planning never reads —
    or writes — the autotune cache. For ``kind == "flash"`` the convention
    is m = query rows, n = KV rows, k = head dim, batch = batch·heads; the
    QK and PV GEMMs both count, and K/V stream once in the model (the
    re-stream factor cancels in the overhead *delta* the planner uses)."""
    gemms = _PLAN_GEMMS.get(kind, 1)
    flops = gemms * 2.0 * m * n * k * batch
    if kind == "flash":
        bytes_ = (m * k + 2.0 * n * k + m * k) * in_bytes * batch
    elif kind == "tgmm":
        # Output-stationary dw: m is the reduction (buffer-row) dim; the
        # (k, n) output is written once per group in f32 — batch carries
        # the group count here.
        bytes_ = (m * k + m * n) * in_bytes + max(batch, 1) * k * n * 4.0
    else:
        bytes_ = (m * k + k * n + m * n) * in_bytes * batch
        if kind == "grouped":
            bytes_ = (m * k + m * n) * in_bytes + batch * k * n * in_bytes
    return flops, bytes_


def ft_plan_cost(kind: str, m: int, n: int, k: int, batch: int = 1,
                 in_bytes: int = 4, *, action: str = "correct",
                 verify: str = "step") -> Tuple[float, float]:
    """(base_time_s, ft_overhead_time_s) for one site population under a
    protection rung — the roofline *delta*, so memory-bound sites absorb
    their checksum FLOPs for free (Kosaian & Rashmi, arXiv 2104.09455)
    while compute-bound ones pay the full maintenance + verify price.

    Maintenance (any enabled action): running column + row checksums touch
    each streamed operand element once and fold it with a MAC —
    ≈ 2·(M·K + K·N) FLOPs per GEMM. Verify: ≈ 3·M·N per pass (two checksum
    reductions of the accumulator + compare), `verify="step"` paying it
    every ⌈K/bk_ref⌉ steps vs once at `"final"`; `action="correct"` adds the
    branchless rank-1 correction update ≈ 2·M·N per pass."""
    flops, bytes_ = ft_plan_base(kind, m, n, k, batch, in_bytes)
    base = roofline.kernel_time_s(flops, bytes_)
    if action == "off":
        return base, 0.0
    gemms = _PLAN_GEMMS.get(kind, 1)
    maint = gemms * 2.0 * (m * k + k * n) * batch
    n_verify = max(1, math.ceil(k / _PLAN_BK_REF)) if verify == "step" else 1
    per_pass = 3.0 * m * n + (2.0 * m * n if action == "correct" else 0.0)
    verify_flops = gemms * per_pass * n_verify * batch
    prot = roofline.kernel_time_s(flops + maint + verify_flops, bytes_)
    return base, max(prot - base, 0.0)


# ---------------------------------------------------------------------------
# Ragged-tile fitting (masked dispatch)
# ---------------------------------------------------------------------------

def fit_tile(dim: int, max_tile: int, align: int) -> int:
    """Block edge for a ragged dimension: among multiples of `align` up to
    `max_tile`, minimize executed work `ceil(dim/c)·c`; break ties toward
    the larger tile. `fit_tile(100, 128, 8) == 104` — one masked tile
    instead of a zero-padded 128."""
    assert max_tile >= align > 0
    best = None
    for c in range(align, max_tile + 1, align):
        waste = math.ceil(dim / c) * c
        key = (waste, -c)
        if best is None or key < best[0]:
            best = (key, c)
    return best[1]
