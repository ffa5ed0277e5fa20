"""Kernel-body emitter: `render(spec, …)` → one parameterized Pallas kernel.

This is the template engine the paper's code generator maps onto (§3.2): a
single source body, specialized at trace time by a `KernelSpec`, replaces
the four hand-duplicated plain/masked × FT/non-FT kernels the repo used to
carry. The body is composed of stages:

  prologue  — scratch init on the first k-step (accumulator, running
              checksums, operand-magnitude trackers, report block);
  mac       — operand load (+ ragged masking from scalar-prefetched true
              dims), the MXU MAC, the emulated-SEU hook, and the running
              column/row checksum updates for the requested FT level (the
              paper's "fuse ABFT memory ops with the prefetching stage" —
              checksums ride the operand tiles already in VMEM);
  verify    — per-k-step detection/location/branchless correction
              (verify="step") on intermediate steps;
  epilogue  — on the last k-step: the *linear prefix* of the epilogue chain
              is applied to the accumulator and folded into the checksum
              comparison (so the final verification — and hence detection
              AND correction — runs post-epilogue), then the nonlinear
              suffix, the out-dtype cast, and the single HBM writeback.

Fusing the chain here is what keeps ABFT (and bias/activation/residual)
from costing a second HBM round-trip over C — FT-BLAS's fusion argument
applied to the whole epilogue.

Layout of the generated kernel's positional refs (see `Layout`):

    [inj_idx, inj_mag, dims]?  scalar prefetch   (FT: all 3; masked-only: dims)
    [gid, row_end]?            scalar prefetch   (grouped specs only)
    a, b [, bias][, residual]  VMEM inputs
    out [, extra…][, report]   VMEM outputs
    acc [, colck, rowck]       VMEM scratch
    [amax, bmax]               SMEM scratch      (FT threshold trackers)

Multi-output specs (``spec.extra_outputs``, PR 4) add derived outputs
between C and the report: "act_grad" writes the derivative of the chain's
nonlinear activation evaluated at the (verified, corrected) pre-activation
accumulator — the saved residual `core.ft_dot_fused`'s backward consumes
instead of recomputing the pre-activation GEMM.

Batched specs (`BatchedKernelSpec`) reuse this body: uniform batched adds a
leading batch grid axis (a/b/out/report blocks gain a unit leading dim and
the 5-wide [enable, batch, row, col, k_step] injection layout); grouped
keeps the 3-D grid but reads its owning group from the scalar-prefetched
tile→group map and masks rows past the group's `row_end` — per-group
checksums and correction fall out of per-block state, since row tiles
never span groups. The output-stationary tgmm variant (`render_tgmm`) is
the one structurally different body: its grid walks row tiles as the
reduction axis and flushes per group.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import epilogues
from .spec import KernelSpec

F32EPS = float(jnp.finfo(jnp.float32).eps)
REPORT_WIDTH = 8
MXU = 128


def _iota2(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


_MATMUL = (((1,), (0,)), ((), ()))


def f32dot(a, b, dims=_MATMUL):
    """In-kernel dot with f32 accumulation — every GEMM and checksum of the
    kernel bodies. f32 operands multiply at full f32 precision: ABFT holds
    a product and its checksums to an f32 rounding bound (τ), and a single
    MXU pass would first round f32 operands to bf16. bf16 operands are
    exact either way and keep the single pass."""
    prec = (jax.lax.Precision.HIGHEST
            if jnp.float32 in (a.dtype, b.dtype) else None)
    return jax.lax.dot_general(a, b, dims, precision=prec,
                               preferred_element_type=jnp.float32)


@dataclasses.dataclass(frozen=True)
class Layout:
    """Ref-list layout of a rendered kernel — shared contract between the
    emitter (which unpacks) and the registry (which builds the specs)."""
    n_prefetch: int
    n_inputs: int
    n_outputs: int
    n_vmem_scratch: int
    n_smem_scratch: int


def layout(spec: KernelSpec) -> Layout:
    if spec.tgmm:
        # [inj?, mag?, rng?, dims, gid, row_end] | x, g | dw [, rep] |
        # acc [, colck, rowck] | [amax, bmax, t0]
        if spec.ft:
            return Layout(6, 2, 2, 3, 3)
        return Layout(3, 2, 1, 1, 0)
    aux = int(spec.needs_bias) + int(spec.needs_residual)
    grp = 2 if spec.grouped else 0      # gid[num_tiles], row_end[G]
    nxo = len(spec.extra_outputs)
    if spec.ft:
        # FT scalar prefetch: [inj_idx, inj_mag, rng, dims] — rng is the
        # PR-10 stochastic-SEU seed triple, same slot order as the flash
        # family ([inj, mag, rng, …]).
        return Layout(4 + grp, 2 + aux, 2 + nxo, 3, 2)
    return Layout((1 if spec.masked else 0) + grp, 2 + aux, 1 + nxo, 1, 0)


# ---------------------------------------------------------------------------
# shared FT primitives (moved from kernels/ftgemm.py — single-sourced here)
# ---------------------------------------------------------------------------

def _locate_correct_full(acc, d_col, d_row, tau, corrects, bm, bn):
    """Locate a single error from checksum residuals and (optionally) apply
    the branchless correction. Returns (acc', detected, magnitude, row, col)."""
    dc = d_col[0, :]
    dr = d_row[:, 0]
    col = jnp.argmax(jnp.abs(dc)).astype(jnp.int32)
    row = jnp.argmax(jnp.abs(dr)).astype(jnp.int32)
    mag_c = jnp.max(jnp.abs(dc))
    mag_r = jnp.max(jnp.abs(dr))
    detected = jnp.maximum(mag_c, mag_r) > tau
    # Canonical magnitude from the column residual (signed).
    mag = jnp.where(detected, jnp.sum(jnp.where(
        jax.lax.iota(jnp.int32, bn) == col, dc, 0.0)), 0.0)
    if corrects:
        hit = ((_iota2((bm, bn), 0) == row) & (_iota2((bm, bn), 1) == col)
               & detected)
        acc = acc - jnp.where(hit, mag, 0.0)
    return acc, detected, mag, row, col


def init_report(rep_ref):
    """Zero the report block. The report lives in SMEM, which takes
    scalar stores only, so each field is written on its own."""
    z = (0,) * (len(rep_ref.shape) - 1)
    for f in range(REPORT_WIDTH):
        rep_ref[z + (f,)] = 0.0


def _record(rep_ref, det, mag, row_g, col_g, d_col, d_row, tau, k_elapsed,
            corrects):
    # The report block is an SMEM row of W scalars behind unit leading dims
    # (`registry._report`) — index those away; every store is a scalar.
    z = (0,) * (len(rep_ref.shape) - 1)
    detf = det.astype(jnp.float32)
    resid = jnp.maximum(jnp.max(jnp.abs(d_col)), jnp.max(jnp.abs(d_row)))
    rep_ref[z + (0,)] += detf
    rep_ref[z + (1,)] += detf if corrects else 0.0
    rep_ref[z + (2,)] = jnp.where(det, row_g.astype(jnp.float32),
                                  rep_ref[z + (2,)])
    rep_ref[z + (3,)] = jnp.where(det, col_g.astype(jnp.float32),
                                  rep_ref[z + (3,)])
    rep_ref[z + (4,)] = jnp.where(det, mag, rep_ref[z + (4,)])
    rep_ref[z + (5,)] = jnp.maximum(rep_ref[z + (5,)], resid)
    rep_ref[z + (6,)] = tau
    rep_ref[z + (7,)] = k_elapsed


# ---------------------------------------------------------------------------
# in-kernel stochastic SEU hook (PR 5)
# ---------------------------------------------------------------------------
#
# Stochastic (`ft.inject_rate`-driven) fault campaigns used to live only in
# the jnp paths (`core.fault_injection.Injector`), so forcing a campaign
# onto a Pallas kernel silently dropped the injection — the MPGemmFI
# failure mode where the injector and the kernel disagree and a "campaign"
# measures a clean run. These helpers are the in-kernel counterpart: a
# counter-based splitmix32-style hash (deterministic per grid cell, same
# bits under interpret and compiled modes — unlike the hardware
# `pltpu.prng_*` primitives, which have no interpret-mode lowering) seeded
# from the campaign key via two scalar-prefetched int32 words.


#: Per-template salts for the GEMM-template family (the flash family owns
#: 0x51–0x54 in `kernels.flashft`): each template body draws an independent
#: SEU stream from one campaign key.
SALT_GEMM2D = 0x55
SALT_BATCHED = 0x56
SALT_TGMM = 0x57


def _mix32(x):
    """splitmix32 finalizer on uint32 — full-avalanche integer hash."""
    x = x.astype(jnp.uint32)
    x = (x ^ (x >> jnp.uint32(16))) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> jnp.uint32(15))) * jnp.uint32(0x846CA68B)
    return x ^ (x >> jnp.uint32(16))


def stochastic_seu(rng_ref, salt: int, block_uid, n_steps,
                   bm: int, bn: int, rate: float):
    """Draw one potential SEU for the output block identified by
    ``block_uid`` (an int32 scalar unique per stationary output block).

    rng_ref — scalar-prefetch int32[3] = [enable, seed0, seed1] (the seeds
    derive from the campaign key; enable=0 ⇒ never hits). ``salt`` is a
    static per-kernel/per-GEMM discriminator so the forward and each
    backward kernel draw independent streams from one key.

    With probability ``rate`` the block suffers one SEU at a uniformly
    drawn (step, row, col); returns (hit, step, row, col) where ``hit`` is
    a traced bool and the coordinates are int32 scalars. The caller applies
    it with `apply_seu` on the step whose LIVE index matches ``step``.

    ``n_steps`` is the number of steps the block actually executes and may
    be a traced int32 (flash callers pass the causal/ragged live span, not
    the grid extent — drawing over skipped steps would silently deflate
    the realized injection rate below the nominal Bernoulli(rate), the
    exact mis-measurement the hook exists to prevent). n_steps ≤ 0 ⇒ the
    block never fires."""
    seed = (rng_ref[1].astype(jnp.uint32)
            ^ _mix32(rng_ref[2].astype(jnp.uint32)
                     + jnp.uint32((salt * 0x9E3779B9) & 0xFFFFFFFF)))
    h0 = _mix32(seed ^ (block_uid.astype(jnp.uint32)
                        * jnp.uint32(0x85EBCA6B)))
    # Bernoulli(rate) in integers: the top 24 hash bits against
    # ceil(rate·2^24) (Mosaic has no uint32 -> f32 cast).
    u24 = (h0 >> jnp.uint32(8)).astype(jnp.int32)
    n_steps = jnp.asarray(n_steps, jnp.int32)
    hit = ((rng_ref[0] == 1) & (u24 < math.ceil(rate * (1 << 24)))
           & (n_steps > 0))
    h1, h2, h3 = _mix32(h0 + jnp.uint32(1)), _mix32(h0 + jnp.uint32(2)), \
        _mix32(h0 + jnp.uint32(3))

    def _bounded(h, n):
        return ((h & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)
                % jnp.maximum(jnp.asarray(n, jnp.int32), 1))

    return hit, _bounded(h1, n_steps), _bounded(h2, bm), _bounded(h3, bn)


def stochastic_hook(rng_ref, salt: int, block_uid, n_steps, bm: int,
                    bn: int, rate: float, bit_shift: int):
    """`stochastic_seu` + `apply_seu` for one stationary block: returns
    ``land(delta, step)``, which lands the block's drawn SEU on ``delta``
    when ``step`` is the drawn one. The gate is the STATIC rate: at rate 0
    nothing is traced and ``land`` is the identity."""
    if rate <= 0.0:
        return lambda delta, step: delta
    hit, s_step, row, col = stochastic_seu(rng_ref, salt, block_uid, n_steps,
                                           bm, bn, rate)
    return lambda delta, step: apply_seu(delta, row, col,
                                         hit & (step == s_step), bit_shift)


def apply_seu(delta, row, col, hit_now, bit_shift: int):
    """Land the drawn SEU on one element of a (bm, bn) accumulator delta —
    the same magnitude model as `core.fault_injection.Injector`: the hit
    element scales by 2**bit_shift (a high-order mantissa/exponent flip),
    with an absolute 2**bit_shift offset when the element is ~0 so the flip
    stays observable."""
    bm, bn = delta.shape
    mask = ((_iota2((bm, bn), 0) == row) & (_iota2((bm, bn), 1) == col)
            & hit_now)
    mag = delta * (2.0 ** bit_shift - 1.0)
    mag = jnp.where(jnp.abs(mag) > 1e-6, mag,
                    jnp.full_like(mag, 2.0 ** bit_shift))
    return delta + jnp.where(mask, mag, 0.0)


# ---------------------------------------------------------------------------
# the template
# ---------------------------------------------------------------------------

def render(spec: KernelSpec, *, k_steps: int, bm: int, bn: int, bk: int,
           n_bands: int = 1, verify_step: bool = True, corrects: bool = True,
           rel_tau: float = 64.0, inject_rate: float = 0.0,
           bit_shift: int = 8, grid_m: int = 1, grid_n: int = 1,
           grid_b: int = 1):
    """Instantiate the kernel body for `spec` with the given static
    parameters. Returns a function matching `layout(spec)`'s ref list.

    Batched specs add a leading batch grid axis (uniform batched) or a
    scalar-prefetched tile→group map (grouped); see `BatchedKernelSpec`.

    ``inject_rate`` > 0 arms the in-kernel stochastic SEU hook (PR-5's
    flash-family hook, extended to the 2-D/batched/grouped bodies): each
    output block draws one Bernoulli(rate) SEU from the scalar-prefetched
    rng triple via `stochastic_seu` and lands it with `apply_seu`
    (magnitude model = `ft.inject_bit_shift`). The draw is gated on the
    STATIC rate, so rate-0 renders are bit-identical to pre-hook kernels.
    ``grid_m``/``grid_n``/``grid_b`` are the launch's grid extents — they
    make the per-block uid unique across the whole launch."""
    ft = spec.ft
    mode = spec.ft_level
    masked = spec.masked
    batched = spec.batched and not spec.grouped   # uniform batched (4-D grid)
    grouped = spec.grouped
    shared_b = spec.shared_b
    chain = [epilogues.get(n) for n in spec.epilogue]
    # Linear-prefix fold is a block-mode feature: tile/inner keep their
    # per-band / per-step verification on the raw accumulator and apply the
    # whole chain afterwards (correction has already happened by then).
    split = spec.fold_split() if (ft and mode == "block") else 0
    acc_dt = jnp.dtype(spec.acc_dtype)

    def kernel(*refs):
        refs = list(refs)
        if ft:
            inj_idx_ref, inj_mag_ref, rng_ref, dims_ref = refs[:4]
            del refs[:4]
        else:
            inj_idx_ref = inj_mag_ref = rng_ref = None
            dims_ref = refs.pop(0) if masked else None
        gid_ref = row_end_ref = None
        if grouped:
            gid_ref = refs.pop(0)
            row_end_ref = refs.pop(0)
        a_ref = refs.pop(0)
        b_ref = refs.pop(0)
        bias_ref = refs.pop(0) if spec.needs_bias else None
        res_ref = refs.pop(0) if spec.needs_residual else None
        out_ref = refs.pop(0)
        xo_refs = [refs.pop(0) for _ in spec.extra_outputs]
        rep_ref = refs.pop(0) if ft else None
        acc_ref = refs.pop(0)
        colck_ref = rowck_ref = amax_ref = bmax_ref = None
        if ft:
            colck_ref, rowck_ref, amax_ref, bmax_ref = refs

        if batched:
            g = pl.program_id(0)
            i = pl.program_id(1)
            j = pl.program_id(2)
            s = pl.program_id(3)
        else:
            i = pl.program_id(0)
            j = pl.program_id(1)
            s = pl.program_id(2)
            g = gid_ref[i] if grouped else None
        last = s == k_steps - 1

        def _aux(op):
            if op.aux == "vector":
                return bias_ref[...].astype(jnp.float32)
            if op.aux == "tile":
                return res_ref[...].astype(jnp.float32)
            return None

        def _store(y):
            # Batched output blocks are (1, bm, bn) — reshape the 2-D tile.
            out_ref[...] = y.astype(out_ref.dtype).reshape(out_ref.shape)

        def _apply_chain(y, ops_list):
            """Apply `ops_list` to the accumulator, writing any requested
            derived outputs at their defining point: act_grad is the first
            (only) nonlinear op's derivative at its input — i.e. at the
            *pre-activation*, after verification/correction has run."""
            for op in ops_list:
                if not op.linear and "act_grad" in spec.extra_outputs:
                    ref = xo_refs[spec.extra_outputs.index("act_grad")]
                    ref[...] = op.grad(y).astype(ref.dtype).reshape(ref.shape)
                y = op.apply(y, _aux(op))
            return y

        # ---- prologue: first-step scratch init ---------------------------
        @pl.when(s == 0)
        def _prologue():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            if ft:
                colck_ref[...] = jnp.zeros_like(colck_ref)
                rowck_ref[...] = jnp.zeros_like(rowck_ref)
                amax_ref[0, 0] = 0.0
                bmax_ref[0, 0] = 0.0
                init_report(rep_ref)

        # ---- mac: load (+ragged mask), MAC, checksums --------------------
        a = a_ref[0] if batched else a_ref[...]
        b = b_ref[...] if (not spec.batched or shared_b) else b_ref[0]
        if masked:
            # Ragged dispatch: zero everything past the true (m, n, k)
            # carried in via scalar prefetch. The checksum math then sees
            # exactly zero-padding semantics (checksums of zero rows/cols
            # are zero), so ABFT survives the ragged edges and garbage in
            # the padded region (even NaN/Inf) cannot leak into the
            # accumulator or the running checksums. Grouped dispatch swaps
            # the row bound for the owning group's last live buffer row
            # (`row_end[gid]`) — the per-group ragged edge.
            tm, tn, tk = dims_ref[0], dims_ref[1], dims_ref[2]
            row_hi = row_end_ref[g] if grouped else tm
            a_ok = ((i * bm + _iota2((bm, bk), 0) < row_hi)
                    & (s * bk + _iota2((bm, bk), 1) < tk))
            b_ok = ((s * bk + _iota2((bk, bn), 0) < tk)
                    & (j * bn + _iota2((bk, bn), 1) < tn))
            a = jnp.where(a_ok, a, jnp.zeros_like(a))
            b = jnp.where(b_ok, b, jnp.zeros_like(b))

        if not ft:
            acc_ref[...] += f32dot(a, b).astype(acc_dt)

            @pl.when(last)
            def _flush_plain():
                _store(_apply_chain(acc_ref[...].astype(jnp.float32), chain))
            return

        af = a.astype(jnp.float32)
        bf = b.astype(jnp.float32)

        # Running operand-magnitude bounds for the rounding-aware threshold
        # — free: the tiles are already in VMEM (the fused-with-prefetch
        # point).
        amax_ref[0, 0] = jnp.maximum(amax_ref[0, 0], jnp.max(jnp.abs(af)))
        bmax_ref[0, 0] = jnp.maximum(bmax_ref[0, 0], jnp.max(jnp.abs(bf)))
        k_elapsed = (s + 1).astype(jnp.float32) * bk
        if masked:
            # Rounding-error accumulation stops at the true K.
            k_elapsed = jnp.minimum(k_elapsed,
                                    dims_ref[2].astype(jnp.float32))
        tau = jnp.maximum(rel_tau * F32EPS * k_elapsed
                          * amax_ref[0, 0] * bmax_ref[0, 0], 1e-30)

        delta = f32dot(a, b)

        # ---- emulated SEU (scalar-prefetched spec) -----------------------
        # Uniform batched specs carry a 5-wide index [enable, batch, row,
        # col, k_step]; 2-D and grouped keep the 4-wide layout (grouped rows
        # are global buffer coordinates, so the tile offset locates them).
        if batched:
            enable, inj_b, g_row, g_col, inj_k = (
                inj_idx_ref[0], inj_idx_ref[1], inj_idx_ref[2],
                inj_idx_ref[3], inj_idx_ref[4])
        else:
            enable, g_row, g_col, inj_k = (inj_idx_ref[0], inj_idx_ref[1],
                                           inj_idx_ref[2], inj_idx_ref[3])
            inj_b = None
        r_loc = g_row - i * bm
        c_loc = g_col - j * bn
        hit_now = ((enable == 1) & (s == inj_k)
                   & (r_loc >= 0) & (r_loc < bm)
                   & (c_loc >= 0) & (c_loc < bn))
        if batched:
            # batch < 0 broadcasts the SEU into every slice — matching the
            # jnp path's inject_spec semantics (core._ft_bmm_backend).
            hit_now = hit_now & ((inj_b < 0) | (inj_b == g))
        hit_mask = ((_iota2((bm, bn), 0) == r_loc)
                    & (_iota2((bm, bn), 1) == c_loc)
                    & hit_now)
        delta = delta + jnp.where(hit_mask, inj_mag_ref[0], 0.0)

        # ---- stochastic SEU hook (PR 10: flash-family hook on the GEMM
        # bodies). Static-rate gate: rate-0 renders stay bit-identical.
        if inject_rate > 0.0:
            salt = SALT_BATCHED if batched else SALT_GEMM2D
            if batched:
                uid = (g * grid_m + i) * grid_n + j
            elif grouped:
                # Grouped blocks share (i, j) across the batch-of-one grid —
                # the row-tile index i is already launch-unique.
                uid = i * grid_n + j
            else:
                uid = i * grid_n + j
            # Live k-span: masked kernels zero loads past the true K, so the
            # block only accumulates over ceil(tk/bk) steps — drawing over
            # the padded span would deflate the realized rate.
            n_live = (((dims_ref[2] + bk - 1) // bk) if masked
                      else jnp.int32(k_steps))
            s_hit, s_step, s_row, s_col = stochastic_seu(
                rng_ref, salt, uid, n_live, bm, bn, inject_rate)
            delta = apply_seu(delta, s_row, s_col, s_hit & (s == s_step),
                              bit_shift)

        # ---- checksum maintenance + intermediate verification ------------
        if mode == "inner":
            # Verify this step's contribution in isolation (thread-level
            # analogue: smallest protected unit, no cross-step state).
            ck_col = f32dot(jnp.sum(af, axis=0, keepdims=True), bf)
            ck_row = f32dot(af, jnp.sum(bf, axis=1, keepdims=True))
            d_col = jnp.sum(delta, axis=0, keepdims=True) - ck_col
            d_row = jnp.sum(delta, axis=1, keepdims=True) - ck_row
            delta, det, mag, row_l, col_l = _locate_correct_full(
                delta, d_col, d_row, tau, corrects, bm, bn)
            acc_ref[...] += delta
            _record(rep_ref, det, mag, row_l + i * bm, col_l + j * bn,
                    d_col, d_row, tau, k_elapsed, corrects)
        else:
            acc_ref[...] += delta
            if mode == "block":
                colck_ref[...] += f32dot(jnp.sum(af, axis=0, keepdims=True),
                                         bf)
            else:  # mode == "tile": one running column checksum per MXU band
                for t in range(n_bands):
                    colck_ref[t:t + 1, :] += f32dot(
                        jnp.sum(af[t * MXU:(t + 1) * MXU], axis=0,
                                keepdims=True), bf)
            rowck_ref[...] += f32dot(af, jnp.sum(bf, axis=1, keepdims=True))

            def _verify_raw():
                acc = acc_ref[...]
                d_row = (jnp.sum(acc, axis=1, keepdims=True)
                         - rowck_ref[...])
                if mode == "block":
                    d_col = (jnp.sum(acc, axis=0, keepdims=True)
                             - colck_ref[0:1, :])
                    new_acc, det, mag, row_l, col_l = _locate_correct_full(
                        acc, d_col, d_row, tau, corrects, bm, bn)
                    acc_ref[...] = new_acc
                    _record(rep_ref, det, mag, row_l + i * bm,
                            col_l + j * bn, d_col, d_row, tau, k_elapsed,
                            corrects)
                else:
                    # Per-band verification & correction (one SEU per band).
                    for t in range(n_bands):
                        band = acc[t * MXU:(t + 1) * MXU]
                        d_col = (jnp.sum(band, axis=0, keepdims=True)
                                 - colck_ref[t:t + 1, :])
                        d_row_b = d_row[t * MXU:(t + 1) * MXU]
                        new_band, det, mag, row_l, col_l = \
                            _locate_correct_full(band, d_col, d_row_b, tau,
                                                 corrects, MXU, bn)
                        acc_ref[t * MXU:(t + 1) * MXU, :] = new_band
                        _record(rep_ref, det, mag,
                                row_l + i * bm + t * MXU, col_l + j * bn,
                                d_col, d_row_b, tau, k_elapsed, corrects)

            if verify_step:
                pl.when(jnp.logical_not(last))(_verify_raw)

        # ---- epilogue: fold, final verify, chain, cast, writeback --------
        @pl.when(last)
        def _flush():
            if mode == "block":
                acc = acc_ref[...]
                colck = colck_ref[0:1, :]
                rowck = rowck_ref[...]
                # Fold the linear prefix into the checksum comparison: the
                # final verification (and the branchless correction it
                # drives) runs on the post-epilogue values.
                for op in chain[:split]:
                    aux = _aux(op)
                    acc = op.apply(acc, aux)
                    colck, rowck = op.fold(colck, rowck, aux, bm)
                d_col = jnp.sum(acc, axis=0, keepdims=True) - colck
                d_row = jnp.sum(acc, axis=1, keepdims=True) - rowck
                acc, det, mag, row_l, col_l = _locate_correct_full(
                    acc, d_col, d_row, tau, corrects, bm, bn)
                _record(rep_ref, det, mag, row_l + i * bm, col_l + j * bn,
                        d_col, d_row, tau, k_elapsed, corrects)
                _store(_apply_chain(acc, chain[split:]))
            else:
                if mode == "tile":
                    _verify_raw()          # corrects acc_ref in place
                # "inner" verified every step already.
                _store(_apply_chain(acc_ref[...], chain))

    kernel.__name__ = (f"gemm_{spec.ft_level}"
                       + ("_grouped" if grouped else "")
                       + ("_batched" if batched else "")
                       + ("_masked" if masked else "")
                       + ("".join("_" + n for n in spec.epilogue))
                       + ("".join("_" + n for n in spec.extra_outputs)))
    return kernel


# ---------------------------------------------------------------------------
# the output-stationary grouped transpose GEMM (tgmm) template
# ---------------------------------------------------------------------------

def render_tgmm(spec: KernelSpec, *, t_tiles: int, bm: int, bn: int, bk: int,
                n_bands: int = 1, verify_step: bool = True,
                corrects: bool = True, rel_tau: float = 64.0,
                inject_rate: float = 0.0, bit_shift: int = 8,
                grid_k: int = 1, grid_n: int = 1):
    """The MoE backward-dw kernel: ``dw[g] = X_gᵀ G_g`` over a group-sorted
    buffer (see `BatchedKernelSpec` docs). Output-stationary over (G, K, N):

      grid = (K/bk, N/bn, t_tiles) — the innermost axis walks row tiles of
      the buffer (the *reduction* dimension); the output block index is the
      scalar-prefetched owning group ``gid[t]``, so each (g, ki, ni) block
      stays VMEM-resident over its group's contiguous tile range. The f32
      accumulator and per-group running checksums reset on the first tile of
      a group and flush (final verify → branchless correct → writeback) on
      its last — per-group ABFT falls out of the flush boundary exactly like
      per-block ABFT falls out of the k-loop in the forward template.

    Checksums (Huang–Abraham on the transpose product): the column checksum
    of dw_g is (X_g e_K)ᵀ G_g and the row checksum is X_gᵀ (G_g e_N) — both
    computed from operand tiles already in VMEM, never from dw.

    Ref list (see `layout`): FT — [inj_idx(4), inj_mag(1), rng(3), dims(3),
    gid, row_end | x, g | dw, rep | acc, colck, rowck | amax, bmax, t0];
    non-FT — [dims, gid, row_end | x, g | dw | acc]. ``dims`` is int32
    [t_buf, N, K]
    (true trailing dims — K/N ragged edges are masked in-kernel); injection
    rows/cols are global (K, N) coordinates and ``k_step`` is the row-tile
    index, which selects the owning group."""
    ft = spec.ft
    mode = spec.ft_level
    assert spec.tgmm and not spec.epilogue

    def kernel(*refs):
        refs = list(refs)
        if ft:
            inj_idx_ref, inj_mag_ref, rng_ref, dims_ref = refs[:4]
            del refs[:4]
        else:
            inj_idx_ref = inj_mag_ref = rng_ref = None
            dims_ref = refs.pop(0)
        gid_ref = refs.pop(0)
        row_end_ref = refs.pop(0)
        x_ref = refs.pop(0)
        g_ref = refs.pop(0)
        out_ref = refs.pop(0)
        rep_ref = refs.pop(0) if ft else None
        acc_ref = refs.pop(0)
        colck_ref = rowck_ref = amax_ref = bmax_ref = t0_ref = None
        if ft:
            colck_ref, rowck_ref, amax_ref, bmax_ref, t0_ref = refs

        ki = pl.program_id(0)
        ni = pl.program_id(1)
        t = pl.program_id(2)
        gidx = gid_ref[t]
        # Group boundaries in the (contiguous, group-sorted) tile walk.
        first = (t == 0) | (gid_ref[jnp.maximum(t - 1, 0)] != gidx)
        last = (t == t_tiles - 1) | \
               (gid_ref[jnp.minimum(t + 1, t_tiles - 1)] != gidx)

        @pl.when(first)
        def _prologue():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            if ft:
                colck_ref[...] = jnp.zeros_like(colck_ref)
                rowck_ref[...] = jnp.zeros_like(rowck_ref)
                amax_ref[0, 0] = 0.0
                bmax_ref[0, 0] = 0.0
                t0_ref[0, 0] = t.astype(jnp.float32)
                init_report(rep_ref)

        # ---- load + ragged masking (group edge, true K/N edges) ----------
        tn, tk = dims_ref[1], dims_ref[2]
        row_hi = row_end_ref[gidx]
        rows = t * bm + _iota2((bm, 1), 0)
        x = x_ref[...].astype(jnp.float32)
        g = g_ref[...].astype(jnp.float32)
        x_ok = (rows < row_hi) & (ki * bk + _iota2((bm, bk), 1) < tk)
        g_ok = (rows < row_hi) & (ni * bn + _iota2((bm, bn), 1) < tn)
        x = jnp.where(x_ok, x, 0.0)
        g = jnp.where(g_ok, g, 0.0)

        contract_rows = (((0,), (0,)), ((), ()))     # Xᵀ·G without transpose
        delta = f32dot(x, g, contract_rows)

        if not ft:
            acc_ref[...] += delta

            @pl.when(last)
            def _flush_plain():
                out_ref[...] = (acc_ref[...].astype(out_ref.dtype)
                                .reshape(out_ref.shape))
            return

        amax_ref[0, 0] = jnp.maximum(amax_ref[0, 0], jnp.max(jnp.abs(x)))
        bmax_ref[0, 0] = jnp.maximum(bmax_ref[0, 0], jnp.max(jnp.abs(g)))
        # Rounding-error accumulation follows the live rows reduced so far
        # for THIS group (from its first tile t0 through the group edge).
        rows_elapsed = (jnp.minimum((t + 1) * bm, row_hi).astype(jnp.float32)
                        - t0_ref[0, 0] * bm)
        rows_elapsed = jnp.maximum(rows_elapsed, 1.0)
        tau = jnp.maximum(rel_tau * F32EPS * rows_elapsed
                          * amax_ref[0, 0] * bmax_ref[0, 0], 1e-30)

        # ---- emulated SEU (global (K, N) coordinates, tile-step timed) ---
        enable, g_row, g_col, inj_k = (inj_idx_ref[0], inj_idx_ref[1],
                                       inj_idx_ref[2], inj_idx_ref[3])
        r_loc = g_row - ki * bk
        c_loc = g_col - ni * bn
        hit = ((enable == 1) & (t == inj_k)
               & (r_loc >= 0) & (r_loc < bk) & (c_loc >= 0) & (c_loc < bn))
        hit_mask = ((_iota2((bk, bn), 0) == r_loc)
                    & (_iota2((bk, bn), 1) == c_loc) & hit)
        delta = delta + jnp.where(hit_mask, inj_mag_ref[0], 0.0)

        # ---- stochastic SEU hook: one draw per stationary (group, ki, ni)
        # output block, timed on the group-LOCAL tile step so the realized
        # rate tracks the group's live span (not the whole buffer walk).
        if inject_rate > 0.0:
            t0 = t0_ref[0, 0].astype(jnp.int32)
            n_live = (row_hi - t0 * bm + bm - 1) // bm       # group's tiles
            uid = (gidx * grid_k + ki) * grid_n + ni
            s_hit, s_step, s_row, s_col = stochastic_seu(
                rng_ref, SALT_TGMM, uid, n_live, bk, bn, inject_rate)
            delta = apply_seu(delta, s_row, s_col,
                              s_hit & ((t - t0) == s_step), bit_shift)

        # ---- per-group running checksums ---------------------------------
        xsum = jnp.sum(x, axis=1, keepdims=True)             # (bm, 1): X e_K
        gsum = jnp.sum(g, axis=1, keepdims=True)             # (bm, 1): G e_N
        if mode == "inner":
            ck_col = f32dot(xsum, g, contract_rows)   # (1, bn)
            ck_row = f32dot(x, gsum, contract_rows)   # (bk, 1)
            d_col = jnp.sum(delta, axis=0, keepdims=True) - ck_col
            d_row = jnp.sum(delta, axis=1, keepdims=True) - ck_row
            delta, det, mag, row_l, col_l = _locate_correct_full(
                delta, d_col, d_row, tau, corrects, bk, bn)
            acc_ref[...] += delta
            _record(rep_ref, det, mag, row_l + ki * bk, col_l + ni * bn,
                    d_col, d_row, tau, rows_elapsed, corrects)
        else:
            acc_ref[...] += delta
            if mode == "block":
                colck_ref[...] += f32dot(xsum, g, contract_rows)
            else:  # "tile": one running column checksum per MXU band of dw
                for b in range(n_bands):
                    xb = jnp.sum(x[:, b * MXU:(b + 1) * MXU], axis=1,
                                 keepdims=True)
                    colck_ref[b:b + 1, :] += f32dot(xb, g, contract_rows)
            rowck_ref[...] += f32dot(x, gsum, contract_rows)

            def _verify_raw():
                acc = acc_ref[...]
                d_row = jnp.sum(acc, axis=1, keepdims=True) - rowck_ref[...]
                if mode == "block":
                    d_col = (jnp.sum(acc, axis=0, keepdims=True)
                             - colck_ref[0:1, :])
                    new_acc, det, mag, row_l, col_l = _locate_correct_full(
                        acc, d_col, d_row, tau, corrects, bk, bn)
                    acc_ref[...] = new_acc
                    _record(rep_ref, det, mag, row_l + ki * bk,
                            col_l + ni * bn, d_col, d_row, tau,
                            rows_elapsed, corrects)
                else:
                    for b in range(n_bands):
                        band = acc[b * MXU:(b + 1) * MXU]
                        d_col = (jnp.sum(band, axis=0, keepdims=True)
                                 - colck_ref[b:b + 1, :])
                        d_row_b = d_row[b * MXU:(b + 1) * MXU]
                        new_band, det, mag, row_l, col_l = \
                            _locate_correct_full(band, d_col, d_row_b, tau,
                                                 corrects, MXU, bn)
                        acc_ref[b * MXU:(b + 1) * MXU, :] = new_band
                        _record(rep_ref, det, mag,
                                row_l + ki * bk + b * MXU, col_l + ni * bn,
                                d_col, d_row_b, tau, rows_elapsed, corrects)

            if verify_step:
                pl.when(jnp.logical_not(last))(_verify_raw)

        @pl.when(last)
        def _flush():
            if mode != "inner":
                _verify_raw()            # final per-group verify + correct
            out_ref[...] = (acc_ref[...].astype(out_ref.dtype)
                            .reshape(out_ref.shape))

    kernel.__name__ = f"tgmm_{spec.ft_level}"
    return kernel
