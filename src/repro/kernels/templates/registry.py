"""Variant registry: KernelSpec + KernelParams → a ready pallas_call.

`kernel_call` is the single launch point every GEMM kernel in the repo now
routes through — `kernels.gemm.gemm/gemm_masked`, `kernels.ftgemm.ft_gemm`,
and `kernels.ops.gemm_call` are all thin wrappers over it. Rendering and
compilation are memoized by jit's static-argument cache (the spec and
params are frozen dataclasses), so each (spec, params, grid) variant is
rendered once per process.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.policy import FTConfig
from ..autotune import MXU, KernelParams
from . import emit
from .spec import BatchedKernelSpec, KernelSpec

REPORT_WIDTH = emit.REPORT_WIDTH


def _report(lead, index_map):
    """BlockSpec and shape of an FT report with leading dims ``lead``: one
    REPORT_WIDTH row of fields per stationary output block, held in SMEM
    (the fields are scalars). Mosaic takes a block whose trailing two dims
    equal the array's, hence the unit dim before the row, which
    `_squeeze_report` drops after the call."""
    spec = pl.BlockSpec((1,) * (len(lead) + 1) + (REPORT_WIDTH,),
                        lambda *idx: (*index_map(*idx), 0, 0),
                        memory_space=pltpu.SMEM)
    shape = jax.ShapeDtypeStruct((*lead, 1, REPORT_WIDTH), jnp.float32)
    return spec, shape


def _squeeze_report(rep):
    return rep.reshape(rep.shape[:-2] + (REPORT_WIDTH,))


def validate(spec: KernelSpec, params: KernelParams, m: int, n: int, k: int,
             in_bytes: int = 4) -> None:
    """Static legality of a launch: the operands must divide the tile grid,
    and bm must respect the variant's alignment floor — MXU-aligned for
    unmasked tiles and for "tile" mode (whose per-band checksums slice the
    accumulator in MXU-row bands), sublane-aligned for masked ragged
    tiles."""
    bm, bn, bk = params.bm, params.bn, params.bk
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        ((m, n, k), params, spec)
    from .. import search
    need = MXU if (spec.ft_level == "tile" or not spec.masked) \
        else search.sublane(in_bytes)
    assert bm % need == 0, (params, spec)


@functools.partial(jax.jit,
                   static_argnames=("spec", "params", "ft", "interpret",
                                    "out_dtype"))
def kernel_call(a: jax.Array, b: jax.Array,
                bias: Optional[jax.Array] = None,
                residual: Optional[jax.Array] = None,
                inj_idx: Optional[jax.Array] = None,
                inj_mag: Optional[jax.Array] = None,
                rng: Optional[jax.Array] = None,
                dims: Optional[jax.Array] = None, *,
                spec: KernelSpec, params: KernelParams,
                ft: Optional[FTConfig] = None,
                interpret: bool = False, out_dtype=None):
    """Launch the rendered variant. Returns (C, report) — report is None
    for non-FT specs. Multi-output specs (``spec.extra_outputs``) return
    ((C, extra…), report) — the derived outputs ride between C and the
    report in the pallas_call's output list.

    Operand contract (enforced by `kernels.ops.gemm_call`, the padding
    front door): a (M, K), b (K, N) padded to the tile grid; bias (1, N)
    and residual (M, N) zero-padded likewise; for FT specs inj_idx int32[4]
    / inj_mag f32[1] (see `ftgemm.encode_injection`) and rng int32[3]
    (`flashft.encode_rng` — [enable, seed0, seed1], zeros disable the
    stochastic SEU draw); dims int32[3] true (m, n, k) for masked specs
    (ignored but required for unmasked FT)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    validate(spec, params, m, n, k, a.dtype.itemsize)
    bm, bn, bk = params.bm, params.bn, params.bk
    grid = (m // bm, n // bn, k // bk)
    out_dtype = out_dtype or (jnp.dtype(spec.out_dtype) if spec.out_dtype
                              else a.dtype)
    n_bands = bm // MXU if spec.ft_level == "tile" else 1
    ft = ft or FTConfig(level=spec.ft_level if spec.ft else "block",
                        action="correct" if spec.ft else "off")

    kernel = emit.render(
        spec, k_steps=grid[2], bm=bm, bn=bn, bk=bk, n_bands=n_bands,
        verify_step=(ft.verify == "step"), corrects=ft.corrects,
        rel_tau=ft.rel_tau, inject_rate=ft.inject_rate,
        bit_shift=ft.inject_bit_shift, grid_m=grid[0], grid_n=grid[1])
    lay = emit.layout(spec)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, s, *_: (i, s)),
        pl.BlockSpec((bk, bn), lambda i, j, s, *_: (s, j)),
    ]
    operands = [a, b]
    if spec.needs_bias:
        assert bias is not None and bias.shape == (1, n), \
            (None if bias is None else bias.shape, n)
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, s, *_: (0, j)))
        operands.append(bias)
    if spec.needs_residual:
        assert residual is not None and residual.shape == (m, n), \
            (None if residual is None else residual.shape, (m, n))
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, s, *_: (i, j)))
        operands.append(residual)

    out_specs = [pl.BlockSpec((bm, bn), lambda i, j, s, *_: (i, j))]
    out_shape = [jax.ShapeDtypeStruct((m, n), out_dtype)]
    for _ in spec.extra_outputs:
        out_specs.append(pl.BlockSpec((bm, bn), lambda i, j, s, *_: (i, j)))
        out_shape.append(jax.ShapeDtypeStruct((m, n), out_dtype))
    scratch = [pltpu.VMEM((bm, bn), jnp.dtype(spec.acc_dtype))]
    prefetch = []
    if spec.ft:
        assert inj_idx is not None and inj_mag is not None
        if rng is None:
            rng = jnp.zeros((3,), jnp.int32)
        if dims is None:
            dims = jnp.array([m, n, k], jnp.int32)
        prefetch = [inj_idx, inj_mag, rng, dims]
        rep_spec, rep_shape = _report(grid[:2], lambda i, j, s, *_: (i, j))
        out_specs.append(rep_spec)
        out_shape.append(rep_shape)
        scratch += [pltpu.VMEM((n_bands, bn), jnp.float32),
                    pltpu.VMEM((bm, 1), jnp.float32),
                    pltpu.SMEM((1, 1), jnp.float32),
                    pltpu.SMEM((1, 1), jnp.float32)]
    elif spec.masked:
        assert dims is not None
        prefetch = [dims]
    assert len(prefetch) == lay.n_prefetch and len(operands) == lay.n_inputs

    compiler_params = pltpu.CompilerParams(
        dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL,
                             pltpu.ARBITRARY))

    multi = len(out_shape) > 1           # FT report and/or extra outputs
    if prefetch:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs if multi else out_specs[0],
            scratch_shapes=scratch,
        )
        call = pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=out_shape if multi else out_shape[0],
            compiler_params=compiler_params, interpret=interpret)
        result = call(*prefetch, *operands)
    else:
        call = pl.pallas_call(
            kernel, grid=grid,
            in_specs=in_specs,
            out_specs=out_specs if multi else out_specs[0],
            out_shape=out_shape if multi else out_shape[0],
            scratch_shapes=scratch,
            compiler_params=compiler_params, interpret=interpret)
        result = call(*operands)

    if not multi:
        return result, None
    result = list(result)
    rep = _squeeze_report(result.pop()) if spec.ft else None
    out = tuple(result) if spec.extra_outputs else result[0]
    return out, rep


# ---------------------------------------------------------------------------
# flash-attention variants (PR 5) — the registry's launch builders for the
# `kernels.flashft` kernel family. The kernel bodies live in flashft (online
# softmax is its own body, not an emit.render product); tile selection rides
# `autotune.best_params` under `spec.FlashKernelSpec` variant keys; these
# functions own the grid/BlockSpec plumbing, exactly like `kernel_call` does
# for the 2-D template. Called from the jit'd wrappers in flashft — not
# jit'd themselves.
# ---------------------------------------------------------------------------

def flash_fwd_call(q, k, v, inj_idx, inj_mag, rng, dims, *, bq: int,
                   bkv: int, causal: bool, ft: FTConfig, interpret: bool,
                   protect_qk: bool, scale: float, n_rep: int,
                   save_stats: bool):
    """Forward flash-FT launch. Returns (out, report) or, with
    ``save_stats``, (out, m, l, report) — m/l are (BH, Sq, 1) f32 per-row
    softmax statistics (degenerate rows marked m=−∞, l=0)."""
    from .. import flashft

    bh, sq, dh = q.shape
    skv = k.shape[1]
    grid = (bh, sq // bq, skv // bkv)
    kernel = functools.partial(
        flashft._flash_ft_kernel, kv_steps=grid[2], q_blocks=grid[1],
        bq=bq, bkv=bkv, dh=dh, causal=causal, scale=scale,
        corrects=ft.corrects, rel_tau=ft.rel_tau, protect_qk=protect_qk,
        save_stats=save_stats, inject_rate=ft.inject_rate,
        bit_shift=ft.inject_bit_shift)

    out_specs = [pl.BlockSpec((1, bq, dh), lambda b, i, s, *_: (b, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((bh, sq, dh), q.dtype)]
    if save_stats:
        for _ in ("m", "l"):
            out_specs.append(pl.BlockSpec((1, bq, 1),
                                          lambda b, i, s, *_: (b, i, 0)))
            out_shape.append(jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32))
    rep_spec, rep_shape = _report(grid[:2], lambda b, i, s, *_: (b, i))
    out_specs.append(rep_spec)
    out_shape.append(rep_shape)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, s, *_: (b, i, 0)),
            pl.BlockSpec((1, bkv, dh),
                         lambda b, i, s, *_: (b // n_rep, s, 0)),
            pl.BlockSpec((1, bkv, dh),
                         lambda b, i, s, *_: (b // n_rep, s, 0)),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((bq, dh), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
    )
    result = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL,
                                 pltpu.ARBITRARY),
        ),
        interpret=interpret,
    )(inj_idx, inj_mag, rng, dims, q, k, v)
    return (*result[:-1], _squeeze_report(result[-1]))


def flash_decode_call(q, k_pages, v_pages, inj_idx, inj_mag, rng, lengths,
                      page_table, layer, *, kvh: int, ft: FTConfig,
                      interpret: bool, protect_qk: bool, scale: float):
    """Paged ragged decode launch (PR 9). Grid (B·kvh, max_pages): one row
    per (slot, kv head), reduction walk over the slot's KV pages. The
    scalar-prefetched page table and layer index (int32[1]) drive the K/V
    *index maps* — kv step s of grid row g DMAs physical page
    ``page_table[g // kvh, s]`` of kv head ``g % kvh`` of layer ``layer``
    straight out of the shared (n_layers, n_pages, kvh, page, dh) pool, so
    the kernel streams exactly the slot's pages and the pool is read where
    it lies (no per-layer slice of it is ever materialized; NULL entries
    stream the trash page; the in-body length mask keeps them unattended).
    The length vector replaces the forward's (Sq, Skv) dims pair — per-row
    ragged dispatch. Returns (out (B·kvh, bq, dh), report (B·kvh, 1, W))."""
    from .. import flashft

    g_rows, bq, dh = q.shape
    _, n_pages, _, page, _ = k_pages.shape
    max_pages = page_table.shape[1]
    grid = (g_rows, max_pages)
    rep_spec, rep_shape = _report((g_rows, 1), lambda g, s, *_: (g, 0))
    kernel = functools.partial(
        flashft._flash_decode_kernel, kv_steps=grid[1], kvh=kvh, bq=bq,
        page=page, dh=dh, scale=scale, corrects=ft.corrects,
        rel_tau=ft.rel_tau, protect_qk=protect_qk,
        inject_rate=ft.inject_rate, bit_shift=ft.inject_bit_shift)

    # prefetch order: inj_idx, inj_mag, rng, lengths, page_table, layer —
    # the table is pf[4] and the layer pf[5] inside the index maps. The
    # layer axis is squeezed, so the body sees the (1, 1, page, dh) block.
    kv_spec = pl.BlockSpec(
        (pl.squeezed, 1, 1, page, dh),
        lambda g, s, *pf: (pf[5][0], pf[4][g // kvh, s], g % kvh, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda g, s, *_: (g, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dh), lambda g, s, *_: (g, 0, 0)),
            rep_spec,
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, dh), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
    )
    out, rep = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((g_rows, bq, dh), q.dtype),
            rep_shape,
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.ARBITRARY),
        ),
        interpret=interpret,
    )(inj_idx, inj_mag, rng, lengths, page_table, layer, q, k_pages,
      v_pages)
    return out, _squeeze_report(rep)


def flash_dq_call(q, k, v, g, m, l, di, inj_idx, inj_mag, rng, dims, *,
                  bq: int, bkv: int, causal: bool, ft: FTConfig,
                  interpret: bool, protect_qk: bool, scale: float,
                  n_rep: int):
    """dQ backward launch (q-block stationary, kv-step reduction walk).
    Returns (dq (BH, Sq, dh), report (BH, Sq/bq, W))."""
    from .. import flashft

    bh, sq, dh = q.shape
    skv = k.shape[1]
    grid = (bh, sq // bq, skv // bkv)
    kernel = functools.partial(
        flashft._flash_dq_kernel, kv_steps=grid[2], q_blocks=grid[1],
        bq=bq, bkv=bkv, dh=dh, causal=causal, scale=scale,
        corrects=ft.corrects, rel_tau=ft.rel_tau, protect_qk=protect_qk,
        inject_rate=ft.inject_rate, bit_shift=ft.inject_bit_shift)

    q_spec = pl.BlockSpec((1, bq, dh), lambda b, i, s, *_: (b, i, 0))
    kv_spec = pl.BlockSpec((1, bkv, dh),
                           lambda b, i, s, *_: (b // n_rep, s, 0))
    stat_spec = pl.BlockSpec((1, bq, 1), lambda b, i, s, *_: (b, i, 0))
    rep_spec, rep_shape = _report(grid[:2], lambda b, i, s, *_: (b, i))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec,
                  stat_spec],
        out_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, s, *_: (b, i, 0)),
            rep_spec,
        ],
        scratch_shapes=[pltpu.VMEM((bq, dh), jnp.float32)],
    )
    dq, rep = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, dh), q.dtype),
            rep_shape,
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL,
                                 pltpu.ARBITRARY),
        ),
        interpret=interpret,
    )(inj_idx, inj_mag, rng, dims, q, k, v, g, m, l, di)
    return dq, _squeeze_report(rep)


def flash_dkv_call(q, k, v, g, m, l, di, inj_idx, inj_mag, rng, dims, *,
                   bq: int, bkv: int, causal: bool, ft: FTConfig,
                   interpret: bool, protect_qk: bool, scale: float,
                   n_rep: int):
    """dK/dV backward launch (kv-block stationary; the reduction walk covers
    the n_rep GQA query heads × q blocks of each KV head). Returns
    (dk, dv (BKVH, Skv, dh), report (BKVH, Skv/bkv, W))."""
    from .. import flashft

    bh, sq, dh = q.shape
    bkvh, skv, _ = k.shape
    grid = (bkvh, skv // bkv, n_rep, sq // bq)
    kernel = functools.partial(
        flashft._flash_dkv_kernel, q_steps=grid[3], n_rep=n_rep,
        kv_blocks=grid[1], bq=bq, bkv=bkv, dh=dh, causal=causal,
        scale=scale, corrects=ft.corrects, rel_tau=ft.rel_tau,
        protect_qk=protect_qk, inject_rate=ft.inject_rate,
        bit_shift=ft.inject_bit_shift)

    q_spec = pl.BlockSpec((1, bq, dh),
                          lambda b, kvi, r, qi, *_: (b * n_rep + r, qi, 0))
    stat_spec = pl.BlockSpec((1, bq, 1),
                             lambda b, kvi, r, qi, *_: (b * n_rep + r, qi, 0))
    kv_spec = pl.BlockSpec((1, bkv, dh),
                           lambda b, kvi, r, qi, *_: (b, kvi, 0))
    out_spec = pl.BlockSpec((1, bkv, dh),
                            lambda b, kvi, r, qi, *_: (b, kvi, 0))
    rep_spec, rep_shape = _report(grid[:2],
                                  lambda b, kvi, r, qi, *_: (b, kvi))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[q_spec, q_spec, stat_spec, stat_spec, stat_spec,
                  kv_spec, kv_spec],
        out_specs=[
            out_spec, out_spec, rep_spec,
        ],
        scratch_shapes=[pltpu.VMEM((bkv, dh), jnp.float32),
                        pltpu.VMEM((bkv, dh), jnp.float32)],
    )
    dk, dv, rep = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bkvh, skv, dh), k.dtype),
            jax.ShapeDtypeStruct((bkvh, skv, dh), v.dtype),
            rep_shape,
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL,
                                 pltpu.ARBITRARY, pltpu.ARBITRARY),
        ),
        interpret=interpret,
    )(inj_idx, inj_mag, rng, dims, q, g, m, l, di, k, v)
    return dk, dv, _squeeze_report(rep)


@functools.partial(jax.jit,
                   static_argnames=("n_groups", "spec", "params", "ft",
                                    "interpret", "out_dtype"))
def tgmm_kernel_call(x: jax.Array, g: jax.Array,
                     inj_idx: Optional[jax.Array] = None,
                     inj_mag: Optional[jax.Array] = None,
                     rng: Optional[jax.Array] = None,
                     dims: Optional[jax.Array] = None,
                     gid: Optional[jax.Array] = None,
                     row_end: Optional[jax.Array] = None, *,
                     n_groups: int,
                     spec: BatchedKernelSpec, params: KernelParams,
                     ft: Optional[FTConfig] = None,
                     interpret: bool = False, out_dtype=None):
    """Launch the output-stationary grouped transpose GEMM (``spec.tgmm``):
    ``dw[g] = X_gᵀ G_g`` with x (t_buf, K), g (t_buf, N) group-sorted
    buffers sharing one layout (``gid`` int32[t_buf/bm], ``row_end``
    int32[G]). Returns (dw (G, K, N) f32-by-default, report|None); the
    report is (G, gk, gn, W) — per *group* blocks, since the accumulator
    flushes at group boundaries.

    Output blocks of EMPTY groups are never visited by the grid and hold
    unspecified memory — `kernels.grouped.dispatch.tgmm_buffer_call` (the
    padding/masking front door) zeroes them; call through it."""
    assert spec.tgmm, spec
    bm, bn, bk = params.bm, params.bn, params.bk
    t_buf, k = x.shape
    t2, n = g.shape
    assert t_buf == t2, (x.shape, g.shape)
    assert t_buf % bm == 0 and n % bn == 0 and k % bk == 0, \
        ((t_buf, n, k), params)
    assert gid is not None and row_end is not None
    assert gid.shape == (t_buf // bm,) and row_end.shape == (n_groups,), \
        (gid.shape, row_end.shape, t_buf // bm, n_groups)
    from .. import search
    need = MXU if spec.ft_level == "tile" else 1
    assert bk % need == 0, (params, spec)   # "tile" bands slice dw's K rows
    assert bm % search.sublane(x.dtype.itemsize) == 0, (params, spec)

    grid = (k // bk, n // bn, t_buf // bm)
    out_dtype = out_dtype or jnp.float32    # dw is a gradient — default f32
    n_bands = bk // MXU if spec.ft_level == "tile" else 1
    ft = ft or FTConfig(level=spec.ft_level if spec.ft else "block",
                        action="correct" if spec.ft else "off")
    kernel = emit.render_tgmm(
        spec, t_tiles=grid[2], bm=bm, bn=bn, bk=bk, n_bands=n_bands,
        verify_step=(ft.verify == "step"), corrects=ft.corrects,
        rel_tau=ft.rel_tau, inject_rate=ft.inject_rate,
        bit_shift=ft.inject_bit_shift, grid_k=grid[0], grid_n=grid[1])
    lay = emit.layout(spec)

    if spec.ft:
        assert inj_idx is not None and inj_mag is not None
        if rng is None:
            rng = jnp.zeros((3,), jnp.int32)
        if dims is None:
            dims = jnp.array([t_buf, n, k], jnp.int32)
        prefetch = [inj_idx, inj_mag, rng, dims]
    else:
        assert dims is not None
        prefetch = [dims]
    prefetch += [gid, row_end]
    gpos = len(prefetch) - 2                # index of `gid` among scalar refs
    assert len(prefetch) == lay.n_prefetch, (len(prefetch), lay)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda ki, ni, t, *_: (t, ki)),
        pl.BlockSpec((bm, bn), lambda ki, ni, t, *_: (t, ni)),
    ]
    # Output-stationary: the scalar-prefetched owning group IS the leading
    # output block index — the accumulator stays resident across the
    # group's contiguous row-tile range and flushes at the boundary.
    out_specs = [pl.BlockSpec((1, bk, bn),
                              lambda ki, ni, t, *pf: (pf[gpos][t], ki, ni))]
    out_shape = [jax.ShapeDtypeStruct((n_groups, k, n), out_dtype)]
    scratch = [pltpu.VMEM((bk, bn), jnp.dtype(spec.acc_dtype))]
    if spec.ft:
        rep_spec, rep_shape = _report(
            (n_groups, grid[0], grid[1]),
            lambda ki, ni, t, *pf: (pf[gpos][t], ki, ni))
        out_specs.append(rep_spec)
        out_shape.append(rep_shape)
        scratch += [pltpu.VMEM((n_bands, bn), jnp.float32),
                    pltpu.VMEM((bk, 1), jnp.float32),
                    pltpu.SMEM((1, 1), jnp.float32),
                    pltpu.SMEM((1, 1), jnp.float32),
                    pltpu.SMEM((1, 1), jnp.float32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs if spec.ft else out_specs[0],
        scratch_shapes=scratch,
    )
    call = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=out_shape if spec.ft else out_shape[0],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL,
                                 pltpu.ARBITRARY)),
        interpret=interpret)
    result = call(*prefetch, x, g)
    if spec.ft:
        out, rep = result
        return out, _squeeze_report(rep)
    return result, None


@functools.partial(jax.jit,
                   static_argnames=("spec", "params", "ft", "interpret",
                                    "out_dtype"))
def batched_kernel_call(a: jax.Array, b: jax.Array,
                        inj_idx: Optional[jax.Array] = None,
                        inj_mag: Optional[jax.Array] = None,
                        rng: Optional[jax.Array] = None,
                        dims: Optional[jax.Array] = None,
                        gid: Optional[jax.Array] = None,
                        row_end: Optional[jax.Array] = None, *,
                        spec: BatchedKernelSpec, params: KernelParams,
                        ft: Optional[FTConfig] = None,
                        interpret: bool = False, out_dtype=None):
    """Launch a `BatchedKernelSpec` variant. Returns (C, report|None).

    Uniform batched (``spec.grouped=False``): a (B, M, K); b (B, K, N), or
    (K, N) with ``shared_b``; the grid gains a leading batch axis and the
    report becomes (B, gm, gn, W). ``inj_idx`` is the 5-wide batched layout
    int32[5] = [enable, batch, row, col, k_step].

    Grouped (``spec.grouped=True``): a (T_buf, K) row-sorted token buffer
    whose groups start on bm boundaries; b (G, K, N); ``gid`` int32[T_buf/bm]
    maps each row tile to its owning group (drives B's index map);
    ``row_end`` int32[G] is each group's first dead buffer row (in-kernel
    ragged group-edge mask). ``inj_idx`` keeps the 2-D 4-wide layout with
    rows in global buffer coordinates. The grid/report stay 3-D: the grouped
    launch is a 2-D GEMM over the buffer with per-tile B selection."""
    grouped = spec.grouped
    bm, bn, bk = params.bm, params.bn, params.bk
    if grouped:
        t_buf, k = a.shape
        ng, k2, n = b.shape
        assert k == k2, (a.shape, b.shape)
        assert t_buf % bm == 0 and n % bn == 0 and k % bk == 0, \
            ((t_buf, n, k), params)
        assert gid is not None and row_end is not None
        assert gid.shape == (t_buf // bm,) and row_end.shape == (ng,), \
            (gid.shape, row_end.shape, t_buf // bm, ng)
        grid = (t_buf // bm, n // bn, k // bk)
        batch = None
    else:
        batch, m, k = a.shape
        if spec.shared_b:
            k2, n = b.shape
        else:
            b2, k2, n = b.shape
            assert b2 == batch, (a.shape, b.shape)
        assert k == k2, (a.shape, b.shape)
        assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
            ((m, n, k), params)
        grid = (batch, m // bm, n // bn, k // bk)
    from .. import search
    need = MXU if (spec.ft_level == "tile" or not spec.masked) \
        else search.sublane(a.dtype.itemsize)
    assert bm % need == 0, (params, spec)

    out_dtype = out_dtype or (jnp.dtype(spec.out_dtype) if spec.out_dtype
                              else a.dtype)
    n_bands = bm // MXU if spec.ft_level == "tile" else 1
    ft = ft or FTConfig(level=spec.ft_level if spec.ft else "block",
                        action="correct" if spec.ft else "off")
    kernel = emit.render(
        spec, k_steps=grid[-1], bm=bm, bn=bn, bk=bk, n_bands=n_bands,
        verify_step=(ft.verify == "step"), corrects=ft.corrects,
        rel_tau=ft.rel_tau, inject_rate=ft.inject_rate,
        bit_shift=ft.inject_bit_shift,
        grid_m=grid[0] if grouped else grid[1],
        grid_n=grid[1] if grouped else grid[2],
        grid_b=1 if grouped else grid[0])
    lay = emit.layout(spec)

    prefetch = []
    if spec.ft:
        assert inj_idx is not None and inj_mag is not None
        if rng is None:
            rng = jnp.zeros((3,), jnp.int32)
        if dims is None:
            dims = (jnp.array([a.shape[0], n, k], jnp.int32) if grouped
                    else jnp.array([m, n, k], jnp.int32))
        prefetch = [inj_idx, inj_mag, rng, dims]
    elif spec.masked:
        assert dims is not None
        prefetch = [dims]
    if grouped:
        prefetch += [gid, row_end]
    gpos = len(prefetch) - 2            # index of `gid` among scalar refs

    if grouped:
        in_specs = [
            pl.BlockSpec((bm, bk), lambda i, j, s, *_: (i, s)),
            # The group id *is* the block index of B — the scalar-prefetched
            # tile→group map drives which expert's weights stream in.
            pl.BlockSpec((1, bk, bn),
                         lambda i, j, s, *pf: (pf[gpos][i], s, j)),
        ]
        out_specs = [pl.BlockSpec((bm, bn), lambda i, j, s, *_: (i, j))]
        out_shape = [jax.ShapeDtypeStruct((t_buf, n), out_dtype)]
        rep_spec, rep_shape = _report(grid[:2], lambda i, j, s, *_: (i, j))
        semantics = (pltpu.PARALLEL, pltpu.PARALLEL, pltpu.ARBITRARY)
    else:
        in_specs = [
            pl.BlockSpec((1, bm, bk), lambda g, i, j, s, *_: (g, i, s)),
            (pl.BlockSpec((bk, bn), lambda g, i, j, s, *_: (s, j))
             if spec.shared_b else
             pl.BlockSpec((1, bk, bn), lambda g, i, j, s, *_: (g, s, j))),
        ]
        out_specs = [pl.BlockSpec((1, bm, bn),
                                  lambda g, i, j, s, *_: (g, i, j))]
        out_shape = [jax.ShapeDtypeStruct((batch, m, n), out_dtype)]
        rep_spec, rep_shape = _report(grid[:3],
                                      lambda g, i, j, s, *_: (g, i, j))
        semantics = (pltpu.PARALLEL, pltpu.PARALLEL, pltpu.PARALLEL,
                     pltpu.ARBITRARY)

    scratch = [pltpu.VMEM((bm, bn), jnp.dtype(spec.acc_dtype))]
    if spec.ft:
        out_specs.append(rep_spec)
        out_shape.append(rep_shape)
        scratch += [pltpu.VMEM((n_bands, bn), jnp.float32),
                    pltpu.VMEM((bm, 1), jnp.float32),
                    pltpu.SMEM((1, 1), jnp.float32),
                    pltpu.SMEM((1, 1), jnp.float32)]
    assert len(prefetch) == lay.n_prefetch, (len(prefetch), lay)

    compiler_params = pltpu.CompilerParams(dimension_semantics=semantics)
    if prefetch:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs if spec.ft else out_specs[0],
            scratch_shapes=scratch,
        )
        call = pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=out_shape if spec.ft else out_shape[0],
            compiler_params=compiler_params, interpret=interpret)
        result = call(*prefetch, a, b)
    else:
        call = pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_specs[0],
            out_shape=out_shape[0], scratch_shapes=scratch,
            compiler_params=compiler_params, interpret=interpret)
        result = call(a, b)

    if spec.ft:
        out, rep = result
        return out, _squeeze_report(rep)
    return result, None
