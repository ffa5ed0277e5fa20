"""Shape-class kernel-parameter selection — the TPU analogue of the paper's
template-based code generation (§3.2, Table 1).

The paper's code generator takes 7 tile parameters (threadblock / warp /
thread tile sizes) and emits a CUDA kernel per input-shape class
(small/medium/large/tall-and-skinny/huge). On TPU the corresponding degrees
of freedom are the Pallas BlockSpec tile sizes (bm, bn, bk): they determine
the VMEM working set (the shared-memory analogue), the MXU utilization
(dims must be multiples of 128 to fill the 128×128 systolic array), and the
HBM→VMEM pipeline depth. "Code generation" is JAX tracing of a parameterized
kernel — `build_params(M, N, K)` is the generator's parameter-selection
stage, and `kernels.gemm/ftgemm` are the template.

VMEM budget model (v5e: 16 MiB/core usable — see KernelParams.vmem_bytes):
    2 × (bm·bk + bk·bn) · bytes(in)   — double-buffered operand tiles
  +     bm·bn · 4                      — f32 accumulator
  + n_bands·bn·4 + bm·4               — running checksums (FT modes;
                                        n_bands = bm/128 for "tile", else 1)
The table below keeps every class ≤ 8 MiB so Mosaic has slack for
spills/semaphores, mirroring the paper's "semi-empirical" selection.

Two selection stages live here:
  * `build_params`  — the static Table-1 lookup (search-free baseline).
  * `best_params`   — the autotuned path: persistent-cache lookup backed by
    the candidate search in `kernels.search` (see that module and
    `kernels.tune_cache`). This is what `ops.matmul` / `ops.ft_matmul*`
    and hence `core.ft_gemm`'s Pallas backend route through.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

MXU = 128          # systolic array edge — all tiles aligned to this
VMEM_BUDGET = 8 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class KernelParams:
    bm: int
    bn: int
    bk: int
    shape_class: str = "custom"

    def vmem_bytes(self, in_bytes: int = 4, ft_level: str = "block") -> int:
        """Working-set model — the single source of truth used by the
        static table, the candidate search, and budget clamping. FT scratch
        depends on the level: one running (col, row) checksum pair for
        "block"/"inner", one column-checksum row per 128-row MXU band for
        "tile". Defaults to "block" (the flagship config) so budget checks
        without an explicit level stay conservative for non-tile modes."""
        operands = 2 * (self.bm * self.bk + self.bk * self.bn) * in_bytes
        acc = self.bm * self.bn * 4
        if ft_level == "off":
            return operands + acc
        n_bands = self.bm // MXU if ft_level == "tile" else 1
        colck = max(n_bands, 1) * self.bn * 4
        rowck = self.bm * 4
        return operands + acc + colck + rowck


#: Table-1 analogue. Keys are shape classes; values are (bm, bn, bk).
#: All multiples of the 128-wide MXU edge; chosen so small problems launch
#: enough grid blocks to fill all cores while huge problems maximize reuse.
TABLE = {
    "small":       (128, 128, 256),   # M, N ≤ 256 — many small blocks
    "medium":      (256, 256, 256),   # ≤ 512
    "large":       (256, 512, 256),   # ≤ 2048
    "tall_skinny": (512, 128, 512),   # M ≫ N — deep k-pipeline, narrow n
    "wide_flat":   (128, 512, 512),   # N ≫ M
    "huge":        (512, 512, 256),   # ≥ 2048 square — max VMEM reuse
}


def classify(m: int, n: int, k: int) -> str:
    if m >= 8 * n:
        return "tall_skinny"
    if n >= 8 * m:
        return "wide_flat"
    s = max(m, n)
    if s <= 256:
        return "small"
    if s <= 512:
        return "medium"
    if s <= 2048:
        return "large"
    return "huge"


def clamp_params(p: KernelParams, m: int, n: int, k: int,
                 in_bytes: int = 4, ft_level: str = "block",
                 spec=None) -> KernelParams:
    """Clamp tile params to the (MXU-padded) problem and the VMEM budget —
    shared by the static table and the search/cache paths, so a cached
    class winner is always legal for the concrete shape at hand. Uses the
    same working-set model (`KernelSpec.vmem_bytes`, wrapping
    `KernelParams.vmem_bytes` plus the variant's aux/extra-output buffers —
    or the tgmm override's transposed geometry) the search enumerates
    under."""

    def _ws(q: KernelParams) -> int:
        if spec is not None:
            return spec.vmem_bytes(q, in_bytes, ft_level)
        return q.vmem_bytes(in_bytes, ft_level)

    p = dataclasses.replace(p,
                            bm=min(p.bm, _round_up(m, MXU)),
                            bn=min(p.bn, _round_up(n, MXU)),
                            bk=min(p.bk, _round_up(k, MXU)))
    # Shrink bk first (pipeline depth) if over budget — cheapest dimension.
    while _ws(p) > VMEM_BUDGET and p.bk > MXU:
        p = dataclasses.replace(p, bk=p.bk // 2)
    while (_ws(p) > VMEM_BUDGET
           and max(p.bm, p.bn) > MXU):
        if p.bm >= p.bn:
            p = dataclasses.replace(p, bm=p.bm // 2)
        else:
            p = dataclasses.replace(p, bn=p.bn // 2)
    return p


def build_params(m: int, n: int, k: int, in_bytes: int = 4) -> KernelParams:
    """The static-table selection stage: shape → TABLE params, clamped to
    the problem size and the VMEM budget. Kept as the search-free baseline
    (and the comparison point the codegen benchmark reports against);
    runtime dispatch goes through `best_params` below."""
    cls = classify(m, n, k)
    bm, bn, bk = TABLE[cls]
    return clamp_params(KernelParams(bm=bm, bn=bn, bk=bk, shape_class=cls),
                        m, n, k, in_bytes)


def device_kind() -> str:
    """Normalized accelerator kind for tuning-cache keys ("cpu",
    "tpu_v5_lite", …)."""
    try:
        import jax
        return jax.devices()[0].device_kind.strip().lower().replace(" ", "_")
    except Exception:
        return "unknown"


def _pow2_bucket(x: int) -> int:
    """Round a batch/group count up to a power of two — the tuning-cache
    bucket. Counts inside one bucket share a roofline regime; exact counts
    would fragment the cache across every routing outcome."""
    b = 1
    while b < x:
        b *= 2
    return b


def best_params(m: int, n: int, k: int, in_bytes: int = 4, *,
                ft_level: str = "off", spec=None,
                measure: bool = False, cache=None,
                use_cache: bool = True,
                batch: int = 1, groups: int = 0) -> KernelParams:
    """Autotuned parameter selection: consult the persistent tuning cache
    (keyed by device kind + shape class + element width + FT level + kernel
    variant); on a miss run the candidate search
    (`kernels.search.select_best`, scored by the roofline model), store the
    winner, and return it clamped to this concrete problem. The request
    path never measures: only `benchmarks/tune_campaign` passes
    ``measure=True``, to time candidates on the chip when it regenerates a
    committed tuning file.

    `spec` — optional `templates.KernelSpec`. Fused epilogues shift the
    VMEM budget (aux-operand buffers) and the roofline intensity (aux HBM
    reads + elementwise FLOPs), so the variant is part of the cache key
    (`spec.variant_key()`) and of the candidate space: two variants of one
    shape class can legitimately tune to different tiles. Flash-attention
    variants (`templates.FlashKernelSpec`, PR 5) reinterpret the problem as
    (m, n, k) = (stationary seq dim, streamed seq dim, lane-padded head
    dim): the winner's (bm, bn) become the (bq, bkv)-style sequence blocks
    and bk is advisory (the head dim never tiles — the spec's own VMEM and
    roofline models ignore it).

    ``batch``/``groups`` make the selection batched-aware: a uniform batch
    count multiplies every roofline term, a ragged group count adds
    per-group row-alignment padding and metadata VMEM, and either adds a
    power-of-two-bucketed ``/b_*`` / ``/g_*`` component to the cache key
    (2-D launches keep the bare key, so existing caches stay valid).

    Deterministic given a warm cache: the same key always yields the same
    stored tile, and clamping is pure. The key includes the per-dim search
    cap, so tuning order across shapes of one class cannot pin a winner
    searched under a smaller candidate space onto a larger problem.
    `use_cache=False` forces a fresh search (cache regeneration, tests)."""
    from . import search, tune_cache

    if spec is not None and spec.ft_level != ft_level:
        raise ValueError(f"spec.ft_level={spec.ft_level!r} disagrees with "
                         f"ft_level={ft_level!r}")
    batch_key = ""
    if groups > 0:
        batch_key = f"g_{_pow2_bucket(groups)}"
    elif batch > 1:
        batch_key = f"b_{_pow2_bucket(batch)}"
    if use_cache:
        # NOT `cache or default`: an empty TuneCache is falsy (__len__ == 0)
        # and must still be honored — cache-regeneration campaigns pass one.
        cache = tune_cache.default_cache() if cache is None else cache
        caps = (min(search.MAX_TILE, _round_up(m, MXU)),
                min(search.MAX_TILE, _round_up(n, MXU)),
                min(search.MAX_TILE, _round_up(k, MXU)))
        key = tune_cache.cache_key(device_kind(), classify(m, n, k),
                                   in_bytes, ft_level, caps,
                                   variant=spec.variant_key() if spec else "",
                                   batch=batch_key)
        hit = cache.get(key)
        if hit is not None:
            return clamp_params(hit, m, n, k, in_bytes, ft_level, spec)
    best = search.select_best(m, n, k, in_bytes=in_bytes, ft_level=ft_level,
                              spec=spec, measure=measure,
                              batch=batch, groups=groups)
    if use_cache:
        cache.put(key, best)
    return clamp_params(best, m, n, k, in_bytes, ft_level, spec)


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def padded_shape(m: int, n: int, k: int, p: KernelParams) -> Tuple[int, int, int]:
    """Problem size padded to tile multiples (zero padding is ABFT-neutral:
    checksums of zero rows/cols are zero)."""
    return _round_up(m, p.bm), _round_up(n, p.bn), _round_up(k, p.bk)
