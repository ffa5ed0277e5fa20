"""`ft_dot` / `ft_batched_dot` — the paper's fault-tolerant GEMM as a
composable JAX op.

This is the framework-facing entry point: every projection in the model zoo
routes through these functions, so online ABFT (detect **and** correct
compute SDCs on the fly) is a first-class property of a training/serving
step, not a demo kernel.

Three execution paths, selected by `FTConfig`:

  * fused jnp path (default) — checksum encode/update/verify expressed in jnp
    and fused by XLA into the surrounding computation; GSPMD-compatible
    (checksums inherit operand shardings; verification is shard-local, adds
    zero collectives — see DESIGN.md §2.2).
  * non-fused path (`fused=False`) — the Ding-2011 baseline: explicitly
    materialized augmented matrices and a separate verification pass,
    separated by `optimization_barrier`s so XLA cannot fuse them. This is the
    prior-state-of-the-art baseline the paper (and our benchmarks) compare
    against.
  * Pallas path (`backend="pallas"`) — the fused in-kernel ABFT of
    `repro.kernels.ftgemm`, used on real TPUs inside `shard_map` (per-shard
    local GEMMs). Dry-run/roofline use the jnp path, which lowers the same
    collective structure. Tile parameters come from the autotuner
    (`kernels.autotune.best_params` via `kernels.ops` — candidate search +
    persistent tuning cache, FT-level-aware), and ragged per-shard shapes
    take the masked-tile kernel instead of zero-padding to class tiles.

Differentiation: `custom_vjp` — the two backward GEMMs are protected with the
same policy (a corrupted gradient is as dangerous as a corrupted activation).

Telemetry: the custom_vjp returns a (detections, max_residual) summary as
auxiliary outputs; recording into the ambient `ft_scope` happens *outside*
the custom_vjp boundary (recording inside would leak tracers from the
sub-trace). Backward-pass corrections are applied but not counted — noted in
DESIGN.md.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import abft, telemetry
from .fault_injection import Injector
from .policy import (FTConfig, FTLike, InjectionSpec, FT_OFF, note_site,
                     resolve_ft)

#: PR-4 backward-path switches, read at trace time. Both default to the
#: kernel-protected paths; the legacy behaviours are kept for the
#: `benchmarks/backward_path.py` before/after comparison (and as an escape
#: hatch), not as supported configurations.
#:   TGMM_USE_KERNEL        — pallas-backend grouped backward runs dw as the
#:                            output-stationary tgmm kernel (False: the
#:                            segment-summed jnp einsum with per-group
#:                            checksum verification).
#:   FUSED_BWD_SAVE_RESIDUAL — ft_dot_fused's forward saves act'(preact) as
#:                            a kernel output and its backward consumes it
#:                            (False: the remat-style pre-activation GEMM
#:                            recompute).
TGMM_USE_KERNEL = True
FUSED_BWD_SAVE_RESIDUAL = True


def _bwd_injection(bwd_inject, target: str) -> Optional[InjectionSpec]:
    """Resolve the per-GEMM backward injection hook: ``bwd_inject`` is None
    or a hashable ("dx"|"dw"|"dbuf", InjectionSpec) pair riding the
    custom_vjp's nondiff args — the backward-FT conformance suite uses it to
    land an SEU inside a *specific* backward GEMM."""
    if bwd_inject is not None and bwd_inject[0] == target:
        return bwd_inject[1]
    return None


def _check_bwd_inject(ft: FTConfig, bwd_inject) -> None:
    """The injection paths live inside the FT machinery — with FT off they
    would be silently skipped, turning a conformance test into a vacuous
    clean-vs-clean comparison. Fail loudly instead."""
    if bwd_inject is not None and not ft.enabled:
        raise ValueError(
            "bwd_inject requires an enabled FTConfig: the SEU is emulated "
            "inside the protected backward GEMM, which FT_OFF never runs")


def _inject(ft: FTConfig, spec: Optional[InjectionSpec],
            key: Optional[jax.Array], c: jax.Array) -> jax.Array:
    """Emulate a compute-unit SEU on the accumulator (pre-verification)."""
    if spec is not None:
        from .fault_injection import inject_spec
        return inject_spec(c, spec)
    if key is not None and ft.inject_rate > 0.0:
        return Injector(rate=ft.inject_rate, bit_shift=ft.inject_bit_shift)(key, c)
    return c


def _summary(v: abft.Verdict) -> Tuple[jax.Array, jax.Array]:
    det = jnp.sum(v.detected.astype(jnp.int32))
    maxres = jnp.max(jnp.abs(v.magnitude)).astype(jnp.float32)
    return det, maxres


_ZERO_SUMMARY = lambda: (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32))


# ---------------------------------------------------------------------------
# 2-D core (M,K) @ (K,N)
# ---------------------------------------------------------------------------

def _abft_body(fn):
    """Wrap a jnp ABFT body ``fn(ft, spec, a, b, ...)`` so its checksums
    hold the product to an f32 rounding bound (τ) on the TPU as well:

    * the operands ``a`` and ``b`` are materialized once, behind an
      optimization barrier. Otherwise XLA may fuse their producers into
      both the GEMM and the checksum reductions and recompute them there,
      not always bit for bit, so that the checksums encode operands other
      than the ones multiplied;
    * f32 matmuls run at full precision. XLA on TPU rounds f32 matmul
      operands to bf16 by default (one MXU pass). bf16 operands are exact
      either way.

    Otherwise rounding alone trips detection and "corrects" clean values."""
    @functools.wraps(fn)
    def wrapped(ft, spec, a, b, *rest):
        a, b = jax.lax.optimization_barrier((a, b))
        with jax.default_matmul_precision("highest"):
            return fn(ft, spec, a, b, *rest)
    return wrapped


def _matmul_f32acc(a: jax.Array, b: jax.Array) -> jax.Array:
    return jax.lax.dot_general(
        a, b, (((a.ndim - 1,), (b.ndim - 2,)), ((), ())),
        preferred_element_type=jnp.float32)


def _tau(ft: FTConfig, a, b):
    if ft.static_tau is not None:
        return jnp.asarray(ft.static_tau, jnp.float32)
    return abft.threshold(a, b, ft.rel_tau)


@_abft_body
def _fused_ft_matmul_2d(ft: FTConfig, spec, a, b, key):
    """Fused online ABFT: checksums from operands, verify, branchless correct."""
    acc = _matmul_f32acc(a, b)                       # (M, N) f32 accumulator
    ck = abft.product_checksums(a, b)                # from operands, f32
    acc = _inject(ft, spec, key, acc)
    out, v = abft.detect_and_correct(acc, ck, _tau(ft, a, b),
                                     corrects=ft.corrects)
    return out.astype(a.dtype), v


@_abft_body
def _nonfused_ft_matmul_2d(ft: FTConfig, spec, a, b, key):
    """Ding-2011-style non-fused ABFT: materialized augmented operands,
    separate passes. optimization_barrier pins the pass structure."""
    m, n = a.shape[0], b.shape[1]
    a_aug = jnp.concatenate([a.astype(jnp.float32),
                             abft.encode_col(a)], axis=0)        # (M+1, K)
    b_aug = jnp.concatenate([b.astype(jnp.float32),
                             abft.encode_row(b)], axis=1)        # (K, N+1)
    a_aug, b_aug = jax.lax.optimization_barrier((a_aug, b_aug))
    c_f = _matmul_f32acc(a_aug, b_aug)                           # (M+1, N+1)
    c_f = jax.lax.optimization_barrier(c_f)
    acc = c_f[:m, :n]
    ck = abft.Checksums(col=c_f[m:m + 1, :n], row=c_f[:m, n:n + 1])
    acc = _inject(ft, spec, key, acc)
    acc = jax.lax.optimization_barrier(acc)                       # verify pass
    out, v = abft.detect_and_correct(acc, ck, _tau(ft, a, b),
                                     corrects=ft.corrects)
    return out.astype(a.dtype), v


def _ft_matmul_2d(ft: FTConfig, spec, a, b, key):
    """Returns (out, det_count:int32, max_residual:f32)."""
    if not ft.enabled:
        return _matmul_f32acc(a, b).astype(a.dtype), *_ZERO_SUMMARY()
    if ft.backend == "pallas":
        from repro.kernels import ops as kops
        out, rep = kops.ft_matmul_report(a, b, ft=ft, spec=spec, key=key)
        det = jnp.sum(rep[..., 0]).astype(jnp.int32)
        maxres = jnp.max(rep[..., 5])
        return out, det, maxres
    fn = _fused_ft_matmul_2d if ft.fused else _nonfused_ft_matmul_2d
    out, v = fn(ft, spec, a, b, key)
    det, maxres = _summary(v)
    return out, det, maxres


# ---------------------------------------------------------------------------
# Public API: ft_dot — (…, K) @ (K, N), custom_vjp-protected both directions
# ---------------------------------------------------------------------------

def _float0(x):
    return np.zeros(x.shape, jax.dtypes.float0) if x is not None else None


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _ft_dot_cvjp(ft: FTConfig, spec, bwd_inject, x, w, key):
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    y2, det, maxres = _ft_matmul_2d(ft, spec, x2, w, key)
    return y2.reshape(*lead, w.shape[-1]), det, maxres


def _ft_dot_fwd(ft, spec, bwd_inject, x, w, key):
    return _ft_dot_cvjp(ft, spec, bwd_inject, x, w, key), (x, w, key)


def _ft_dot_bwd(ft, spec, bwd_inject, res, cts):
    g, _, _ = cts                      # ignore summary cotangents
    x, w, key = res
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    g2 = g.reshape(-1, g.shape[-1]).astype(x.dtype)
    kx = jax.random.fold_in(key, 1) if key is not None else None
    kw = jax.random.fold_in(key, 2) if key is not None else None
    # Backward GEMMs are ABFT-protected too (spec applies to fwd only;
    # bwd_inject lands a deterministic SEU in the named backward GEMM).
    dx2, _, _ = _ft_matmul_2d(ft, _bwd_injection(bwd_inject, "dx"),
                              g2, w.T, kx)
    dw, _, _ = _ft_matmul_2d(ft, _bwd_injection(bwd_inject, "dw"),
                             x2.T, g2, kw)
    return dx2.reshape(*lead, x.shape[-1]), dw.astype(w.dtype), _float0(key)


_ft_dot_cvjp.defvjp(_ft_dot_fwd, _ft_dot_bwd)


def _record(det, maxres, corrects: bool,
            site: Optional[str] = None) -> None:
    scope = telemetry.current_scope()
    if scope is not None:
        scope.record_summary(det, maxres, corrects, site=site)


def ft_dot(x: jax.Array, w: jax.Array, ft: FTLike = FT_OFF,
           key: Optional[jax.Array] = None,
           spec: Optional[InjectionSpec] = None,
           bwd_inject=None, site: Optional[str] = None) -> jax.Array:
    """Fault-tolerant dense projection: (…, K) @ (K, N) → (…, N).

    ft    — FTConfig (uniform) or FTPolicy (per-site — resolved against
            ``site`` right here, before any backend/spec derivation, so the
            resolved level flows into the existing template/autotune keys).
    key   — optional PRNG key driving the stochastic SEU injector
            (ft.inject_rate); None ⇒ no stochastic injection.
    spec  — optional deterministic single-SEU injection (tests/benchmarks).
    bwd_inject — optional ("dx"|"dw", InjectionSpec): land a deterministic
            SEU inside the named *backward* GEMM (conformance tests).
    site  — optional structured telemetry label for this call site (e.g.
            "w_gate"); attributes the recorded (det, max_residual) summary
            to a stable per-site slot in the step's FTReport, and keys the
            FTPolicy resolution + planner cost attribution.
    """
    ft = resolve_ft(ft, site)
    _check_bwd_inject(ft, bwd_inject)
    note_site(site, "2d", int(np.prod(x.shape[:-1], dtype=np.int64)),
              w.shape[-1], x.shape[-1], in_bytes=jnp.dtype(x.dtype).itemsize)
    if not ft.enabled and key is None and spec is None:
        # Fast path: a plain dot XLA can pattern-match without custom_vjp.
        return jnp.matmul(x, w, preferred_element_type=jnp.float32).astype(x.dtype)
    y, det, maxres = _ft_dot_cvjp(ft, spec, bwd_inject, x, w, key)
    _record(det, maxres, ft.corrects, site)
    return y


# ---------------------------------------------------------------------------
# Fused-epilogue variant — y = act(x @ w + bias), one pass
# ---------------------------------------------------------------------------
#
# The model blocks' matmul→bias→activation sequences route through here as
# *fused specs*: on the Pallas backend the epilogue runs inside the GEMM
# kernel (templates subsystem — bias/activation applied to the VMEM-resident
# accumulator before the single HBM writeback, linear ops folded into the
# checksum comparison); on the jnp path XLA fuses the same composition. ABFT
# semantics are unchanged: verification/correction happen on the GEMM
# accumulator at the last point where the linear checksum invariant holds.


def _epilogue_fn(act: Optional[str]):
    from repro.kernels.templates import epilogues
    return epilogues.activation(act) if act is not None else (lambda y: y)


def _epilogue_grad_fn(act: str):
    from repro.kernels.templates import epilogues
    return epilogues.activation_grad(act)


def _fused_epilogue_impl(ft: FTConfig, spec, act, x2, w, bias, key,
                         want_grad: bool):
    """One backend dispatch for the fused-epilogue forward. Returns
    (out, det, maxres, act_grad|None): with ``want_grad`` the pallas
    backend runs the multi-output kernel variant (act'(preact) computed
    in-kernel from the verified, corrected accumulator) and the jnp paths
    evaluate the same derivative on the f32 accumulator — the saved
    residual `_ft_fused_bwd` consumes."""
    assert not want_grad or act is not None
    if ft.enabled and ft.backend == "pallas":
        from repro.kernels import ops as kops
        res, rep = kops.fused_matmul(x2, w, bias=bias, act=act, ft=ft,
                                     inject=spec, save_act_grad=want_grad,
                                     key=key)
        out, actp = res if want_grad else (res, None)
        det = jnp.sum(rep[..., 0]).astype(jnp.int32)
        maxres = jnp.max(rep[..., 5])
        return out, det, maxres, actp
    if not ft.enabled:
        # Like _ft_matmul_2d with FT off: no injection either — the two
        # sibling entry points must agree on FT-off semantics.
        acc = _matmul_f32acc(x2, w)
        det, maxres = _ZERO_SUMMARY()
    else:
        fn = _fused_ft_matmul_2d if ft.fused else _nonfused_ft_matmul_2d
        out, v = fn(ft, spec, x2, w, key)
        acc = out.astype(jnp.float32)
        det, maxres = _summary(v)
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)
    actp = (_epilogue_grad_fn(act)(acc).astype(x2.dtype) if want_grad
            else None)
    out = _epilogue_fn(act)(acc)
    return out.astype(x2.dtype), det, maxres, actp


def _fused_epilogue_2d(ft: FTConfig, spec, act, x2, w, bias, key):
    """(out, det, maxres) for y = act(x2 @ w + bias) with policy `ft`."""
    out, det, maxres, _ = _fused_epilogue_impl(ft, spec, act, x2, w, bias,
                                               key, want_grad=False)
    return out, det, maxres


def _fused_epilogue_2d_grad(ft: FTConfig, spec, act, x2, w, bias, key):
    """`_fused_epilogue_2d` + the act'(preact) residual:
    (out, det, maxres, act_grad)."""
    return _fused_epilogue_impl(ft, spec, act, x2, w, bias, key,
                                want_grad=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _ft_fused_cvjp(ft: FTConfig, spec, act, bwd_inject, x, w, bias, key):
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    y2, det, maxres = _fused_epilogue_2d(ft, spec, act, x2, w, bias, key)
    return y2.reshape(*lead, w.shape[-1]), det, maxres


def _ft_fused_fwd(ft, spec, act, bwd_inject, x, w, bias, key):
    if act is None or not FUSED_BWD_SAVE_RESIDUAL:
        # No nonlinearity (nothing to save) or the legacy remat-style path.
        out = _ft_fused_cvjp(ft, spec, act, bwd_inject, x, w, bias, key)
        return out, (x, w, bias, None, key)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    y2, det, maxres, actp = _fused_epilogue_2d_grad(ft, spec, act, x2, w,
                                                    bias, key)
    return ((y2.reshape(*lead, w.shape[-1]), det, maxres),
            (x, w, bias, actp, key))


def _ft_fused_bwd(ft, spec, act, bwd_inject, res, cts):
    g, _, _ = cts                      # ignore summary cotangents
    x, w, bias, actp, key = res
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    g2 = g.reshape(-1, g.shape[-1])
    kx = jax.random.fold_in(key, 1) if key is not None else None
    kw = jax.random.fold_in(key, 2) if key is not None else None
    kp = jax.random.fold_in(key, 5) if key is not None else None
    if act is not None and actp is not None:
        # The forward kernel saved act'(preact) as a second VMEM output
        # (multi-output variant) — the pre-activation GEMM is NOT
        # recomputed here; dpre is one elementwise product.
        dpre = (g2.astype(jnp.float32) * actp.astype(jnp.float32)
                ).astype(x.dtype)
    elif act is not None:
        # Legacy remat-style recompute (FUSED_BWD_SAVE_RESIDUAL=False),
        # ABFT-protected like every other backward GEMM.
        pre, _, _ = _ft_matmul_2d(ft, None, x2, w, kp)
        pre = pre.astype(jnp.float32)
        if bias is not None:
            pre = pre + bias.astype(jnp.float32)
        _, act_vjp = jax.vjp(_epilogue_fn(act), pre)
        dpre = act_vjp(g2.astype(jnp.float32))[0].astype(x.dtype)
    else:
        dpre = g2.astype(x.dtype)
    dbias = (None if bias is None
             else jnp.sum(dpre.astype(jnp.float32), axis=0).astype(bias.dtype)
             .reshape(bias.shape))
    # Backward GEMMs are ABFT-protected too (spec applies to fwd only).
    dx2, _, _ = _ft_matmul_2d(ft, _bwd_injection(bwd_inject, "dx"),
                              dpre, w.T, kx)
    dw, _, _ = _ft_matmul_2d(ft, _bwd_injection(bwd_inject, "dw"),
                             x2.T, dpre, kw)
    return (dx2.reshape(*lead, x.shape[-1]), dw.astype(w.dtype), dbias,
            _float0(key))


_ft_fused_cvjp.defvjp(_ft_fused_fwd, _ft_fused_bwd)


def ft_dot_fused(x: jax.Array, w: jax.Array,
                 bias: Optional[jax.Array] = None,
                 act: Optional[str] = None,
                 ft: FTLike = FT_OFF,
                 key: Optional[jax.Array] = None,
                 spec: Optional[InjectionSpec] = None,
                 bwd_inject=None, site: Optional[str] = None) -> jax.Array:
    """Fault-tolerant fused-epilogue projection:
    (…, K) @ (K, N) → act((…, N) + bias).

    The matmul→bias→activation sequence as ONE spec: no separate bias /
    activation passes over the output (the Pallas backend fuses them into
    the GEMM epilogue before the HBM writeback; XLA fuses the jnp path).
    `act` is a registered elementwise epilogue name ("relu"/"gelu"/"silu");
    both directions are custom_vjp-protected like `ft_dot`.

    When differentiated, the forward runs the *multi-output* kernel variant
    and saves act'(preact) as a residual (computed from the corrected
    accumulator), so the backward is two protected GEMMs + one elementwise
    product — no pre-activation recompute. ``bwd_inject`` =
    ("dx"|"dw", InjectionSpec) lands an SEU in the named backward GEMM."""
    ft = resolve_ft(ft, site)
    _check_bwd_inject(ft, bwd_inject)
    if bias is None and act is None:
        # Delegates to ft_dot, which records the planner cost as "2d".
        return ft_dot(x, w, ft=ft, key=key, spec=spec, bwd_inject=bwd_inject,
                      site=site)
    note_site(site, "fused", int(np.prod(x.shape[:-1], dtype=np.int64)),
              w.shape[-1], x.shape[-1], in_bytes=jnp.dtype(x.dtype).itemsize)
    if not ft.enabled and key is None and spec is None:
        # Fast path: plain fused composition XLA pattern-matches.
        y = jnp.matmul(x, w, preferred_element_type=jnp.float32)
        if bias is not None:
            y = y + bias.astype(jnp.float32)
        return _epilogue_fn(act)(y).astype(x.dtype)
    y, det, maxres = _ft_fused_cvjp(ft, spec, act, bwd_inject, x, w, bias,
                                    key)
    _record(det, maxres, ft.corrects, site)
    return y


# ---------------------------------------------------------------------------
# Batched variant — attention cores (QK^T, PV) and per-expert matmuls
# ---------------------------------------------------------------------------

@_abft_body
def _fused_ft_bmm(ft: FTConfig, spec, a, b, key):
    acc = jnp.matmul(a, b, preferred_element_type=jnp.float32)
    ck = abft.product_checksums(a, b)
    acc = _inject(ft, spec, key, acc)
    tau = (jnp.full(acc.shape[:-2], ft.static_tau, jnp.float32)
           if ft.static_tau is not None else abft.threshold(a, b, ft.rel_tau))
    out, v = abft.detect_and_correct(acc, ck, tau, corrects=ft.corrects)
    det, maxres = _summary(v)
    return out.astype(a.dtype), det, maxres


def _ft_bmm_backend(ft: FTConfig, spec, a, b, key):
    """Backend dispatch for one batched matmul, (out, det, maxres).

    pallas — ONE batched Pallas kernel (leading batch grid axis) via
    `ops.grouped_gemm_call`: the whole (…, M, K) × (…, K, N) problem is a
    single launch, no per-slice Python loop and no jnp fallback; ragged
    (M, N, K) take the masked fitted-tile path inside the kernel.
    Otherwise the XLA-fused jnp checksum path (GSPMD-friendly)."""
    if ft.enabled and ft.backend == "pallas":
        from repro.kernels import ops as kops
        from repro.kernels.templates import BatchedKernelSpec
        lead = a.shape[:-2]
        a3 = a.reshape((-1,) + a.shape[-2:])
        b3 = b.reshape((-1,) + b.shape[-2:])
        # inj_batch=-1: broadcast the SEU into every slice, matching the
        # jnp path's inject_spec (which masks on row/col iotas only).
        out, rep = kops.grouped_gemm_call(
            BatchedKernelSpec(ft_level=ft.level), a3, b3, ft=ft, inject=spec,
            inj_batch=-1, key=key)
        det = jnp.sum(rep[..., 0]).astype(jnp.int32)
        maxres = jnp.max(rep[..., 5])
        return out.reshape(lead + out.shape[-2:]), det, maxres
    return _fused_ft_bmm(ft, spec, a, b, key)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _ft_bmm_cvjp(ft, spec, a, b, key):
    return _ft_bmm_backend(ft, spec, a, b, key)


def _ft_bmm_fwd(ft, spec, a, b, key):
    return _ft_bmm_cvjp(ft, spec, a, b, key), (a, b, key)


def _ft_bmm_bwd(ft, spec, res, cts):
    g, _, _ = cts
    a, b, key = res
    g = g.astype(a.dtype)
    ka = jax.random.fold_in(key, 3) if key is not None else None
    kb = jax.random.fold_in(key, 4) if key is not None else None
    bt = jnp.swapaxes(b, -1, -2)
    at = jnp.swapaxes(a, -1, -2)
    da, _, _ = _ft_bmm_backend(ft, None, g, bt, ka)
    db, _, _ = _ft_bmm_backend(ft, None, at, g, kb)
    return da, db.astype(b.dtype), _float0(key)


_ft_bmm_cvjp.defvjp(_ft_bmm_fwd, _ft_bmm_bwd)


def ft_batched_dot(a: jax.Array, b: jax.Array, ft: FTLike = FT_OFF,
                   key: Optional[jax.Array] = None,
                   spec: Optional[InjectionSpec] = None,
                   site: Optional[str] = None) -> jax.Array:
    """Fault-tolerant batched matmul: (…, M, K) @ (…, K, N) → (…, M, N).
    Leading dims must match (broadcast not supported — callers reshape).
    On `ft.backend == "pallas"` the whole batch runs as one batched Pallas
    kernel with per-slice checksums/report rows (PR 3). `site` labels the
    call for per-site telemetry attribution (see ft_dot) and keys the
    FTPolicy resolution."""
    ft = resolve_ft(ft, site)
    note_site(site, "batched", a.shape[-2], b.shape[-1], a.shape[-1],
              batch=int(np.prod(a.shape[:-2], dtype=np.int64)),
              in_bytes=jnp.dtype(a.dtype).itemsize)
    if not ft.enabled and key is None and spec is None:
        return jnp.matmul(a, b, preferred_element_type=jnp.float32).astype(a.dtype)
    y, det, maxres = _ft_bmm_cvjp(ft, spec, a, b, key)
    _record(det, maxres, ft.corrects, site)
    return y


# ---------------------------------------------------------------------------
# Grouped variant — MoE expert FFNs over ragged routing (zero capacity pad)
# ---------------------------------------------------------------------------
#
# y[t] = x[t] @ w[group_ids[t]] for per-row group assignments with dynamic
# group sizes. The rows are scattered into a group-sorted buffer whose groups
# start on row-tile boundaries (kernels.grouped.layout); the pallas backend
# then runs the CSR-style grouped kernel (per-group B via scalar-prefetched
# index maps, per-group checksums + detection/correction), and the jnp
# backend mirrors the same algebra with segment reductions — checksums,
# thresholds, location, and branchless correction all per group, so an SEU
# in one expert's rows never contaminates a neighboring group.

_HAS_RAGGED_DOT = hasattr(jax.lax, "ragged_dot")


def _row_gids(gid: jax.Array, t_buf: int) -> jax.Array:
    bm = t_buf // gid.shape[0]
    return jnp.repeat(gid, bm, total_repeat_length=t_buf)


def _grouped_dot_jnp(buf, w, gid):
    """f32 grouped product over the aligned buffer (jnp path). Uses
    `jax.lax.ragged_dot` when available (one XLA op, no G× blowup); the
    fallback contracts per row tile against the gathered group weights."""
    t_buf = buf.shape[0]
    num_tiles = gid.shape[0]
    bm = t_buf // num_tiles
    g = w.shape[0]
    if _HAS_RAGGED_DOT:
        tiles_per_group = jnp.zeros((g,), jnp.int32).at[gid].add(1)
        sizes = tiles_per_group * bm          # aligned sizes, sum == t_buf
        return jax.lax.ragged_dot(buf, w, sizes,
                                  preferred_element_type=jnp.float32)
    b3 = buf.reshape(num_tiles, bm, -1)
    return jnp.einsum("tbk,tkn->tbn", b3, w[gid],
                      preferred_element_type=jnp.float32
                      ).reshape(t_buf, w.shape[-1])


@_abft_body
def _fused_ft_grouped(ft: FTConfig, spec, buf, w, gid, key):
    """Fused online ABFT for the grouped product on the jnp path: per-group
    checksums via segment reductions, per-group rounding-aware thresholds,
    one located+corrected SEU per group."""
    t_buf, k = buf.shape
    g, _, n = w.shape
    rg = _row_gids(gid, t_buf)
    bf = buf.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    acc = _grouped_dot_jnp(buf, w, gid)                    # (t_buf, n) f32

    # Checksums from the operands (never from C): per-group column checksum
    # (e^T X_g) W_g and per-row checksum x_t · (W_g e).
    xsum = jnp.zeros((g, k), jnp.float32).at[rg].add(bf)   # (G, K)
    colck = jnp.einsum("gk,gkn->gn", xsum, wf)             # (G, N)
    rowck = jnp.sum(bf * wf.sum(-1)[rg], axis=-1)          # (t_buf,)

    acc = _inject(ft, spec, key, acc)

    d_col = jnp.zeros((g, n), jnp.float32).at[rg].add(acc) - colck
    d_row = jnp.sum(acc, axis=-1) - rowck                  # (t_buf,)
    if ft.static_tau is not None:
        tau = jnp.full((g,), ft.static_tau, jnp.float32)
    else:
        eps = float(jnp.finfo(jnp.float32).eps)
        amax = jax.ops.segment_max(jnp.max(jnp.abs(bf), axis=-1), rg,
                                   num_segments=g)
        amax = jnp.where(jnp.isfinite(amax), amax, 0.0)    # empty groups
        bmax = jnp.max(jnp.abs(wf), axis=(-2, -1))
        tau = jnp.maximum(ft.rel_tau * eps * k * amax * bmax, 1e-30)

    colmax = jnp.max(jnp.abs(d_col), axis=-1)              # (G,)
    rowmax = jax.ops.segment_max(jnp.abs(d_row), rg, num_segments=g)
    rowmax = jnp.where(jnp.isfinite(rowmax), rowmax, 0.0)
    det_g = jnp.maximum(colmax, rowmax) > tau              # (G,) bool

    col_g = jnp.argmax(jnp.abs(d_col), axis=-1)            # (G,)
    mag_g = jnp.take_along_axis(d_col, col_g[:, None], axis=-1)[:, 0]
    # Located row per group: first peak of |d_row| inside the group.
    is_peak = jnp.abs(d_row) >= rowmax[rg]
    row_g = jax.ops.segment_min(
        jnp.where(is_peak, jnp.arange(t_buf, dtype=jnp.int32), t_buf),
        rg, num_segments=g)
    if ft.corrects:
        delta = jnp.where(det_g, mag_g, 0.0)
        acc = acc.at[jnp.clip(row_g, 0, t_buf - 1), col_g].add(-delta)

    det = jnp.sum(det_g.astype(jnp.int32))
    maxres = jnp.maximum(jnp.max(colmax), jnp.max(rowmax))
    return acc.astype(buf.dtype), det, maxres


def _ft_grouped_2d(ft: FTConfig, spec, buf, w, gid, row_end, key):
    """(y_buf, det, maxres) — backend dispatch for one grouped product."""
    if not ft.enabled:
        return (_grouped_dot_jnp(buf, w, gid).astype(buf.dtype),
                *_ZERO_SUMMARY())
    if ft.backend == "pallas":
        import dataclasses as _dc
        from repro.kernels import grouped as kgrouped
        from repro.kernels.templates import BatchedKernelSpec
        t_buf, k = buf.shape
        g, _, n = w.shape
        bm = t_buf // gid.shape[0]
        kspec = BatchedKernelSpec(ft_level=ft.level, grouped=True)
        p = _dc.replace(
            kgrouped.plan_grouped(t_buf, n, k, buf.dtype, n_groups=g,
                                  ft_level=ft.level, spec=kspec),
            bm=bm)
        out, rep = kgrouped.grouped_buffer_call(
            kspec, buf, w, gid=gid, row_end=row_end, params=p, ft=ft,
            inject=spec, key=key)
        det = jnp.sum(rep[..., 0]).astype(jnp.int32)
        maxres = jnp.max(rep[..., 5])
        return out, det, maxres
    return _fused_ft_grouped(ft, spec, buf, w, gid, key)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _ft_grouped_cvjp(ft, spec, bwd_inject, buf, w, gid, row_end, key):
    return _ft_grouped_2d(ft, spec, buf, w, gid, row_end, key)


def _primal_value(x):
    """Unwrap a CustomVJPPrimal (the fwd rule runs under
    ``symbolic_zeros=True`` so the bwd rule can distinguish genuinely-zero
    summary cotangents — see `_ft_grouped_bwd`)."""
    return x.value if hasattr(x, "value") else x


def _ft_grouped_fwd(ft, spec, bwd_inject, buf, w, gid, row_end, key):
    buf, w, gid, row_end, key = map(_primal_value,
                                    (buf, w, gid, row_end, key))
    out = _ft_grouped_cvjp(ft, spec, bwd_inject, buf, w, gid, row_end, key)
    return out, (buf, w, gid, row_end, key)


def _ft_grouped_bwd(ft, spec, bwd_inject, res, cts):
    from jax.custom_derivatives import SymbolicZero

    g_buf, ct_det, ct_maxres = cts
    # The (det, maxres) outputs are *telemetry*, not differentiable
    # quantities: det is a discrete fault counter and maxres a max-residual
    # diagnostic. Their cotangent contribution to (buf, w) is mathematically
    # undefined under the SEU model, so silently dropping a real cotangent
    # here would corrupt training invisibly. With symbolic_zeros we can see
    # the difference and fail loudly instead.
    if not (isinstance(ct_det, SymbolicZero)
            and isinstance(ct_maxres, SymbolicZero)):
        raise ValueError(
            "ft_grouped_matmul: differentiating through the (det, "
            "max_residual) FT telemetry summaries is not defined — they are "
            "fault diagnostics, not smooth functions of the operands. Apply "
            "jax.lax.stop_gradient to the telemetry outputs (or keep them "
            "out of the loss).")
    buf, w, gid, row_end, key = res
    t_buf = buf.shape[0]
    n = w.shape[-1]
    if isinstance(g_buf, SymbolicZero):
        g_buf = jnp.zeros((t_buf, n), buf.dtype)
    else:
        g_buf = g_buf.astype(buf.dtype)
    kx = jax.random.fold_in(key, 6) if key is not None else None
    # d_buf: the same grouped product against the transposed group weights,
    # ABFT-protected like every other backward GEMM.
    dbuf, _, _ = _ft_grouped_2d(ft, _bwd_injection(bwd_inject, "dbuf"),
                                g_buf, jnp.swapaxes(w, -1, -2),
                                gid, row_end, kx)
    kw = jax.random.fold_in(key, 7) if key is not None else None
    dw = _grouped_dw(ft, _bwd_injection(bwd_inject, "dw"), buf, g_buf, gid,
                     row_end, kw)
    return (dbuf, dw.astype(w.dtype), _float0(gid), _float0(row_end),
            _float0(key))


def _grouped_dw(ft: FTConfig, inject, buf, g_buf, gid, row_end, key=None):
    """The grouped backward dw ("tgmm"): dw[g] = X_gᵀ G_g, (G, K, N) f32.

    pallas backend (and `TGMM_USE_KERNEL`) — ONE output-stationary Pallas
    kernel (`kernels.grouped.tgmm_buffer_call`): the grid walks row tiles as
    the reduction axis, per-group checksums flush at group boundaries, and
    detection/correction run in-kernel. Otherwise the segment-summed jnp
    einsum verified with per-group checksums (the pre-PR-4 path — kept as
    the xla-backend implementation and the before/after benchmark
    baseline)."""
    t_buf, k = buf.shape
    ng = row_end.shape[0]
    num_tiles = gid.shape[0]
    bm = t_buf // num_tiles
    if ft.enabled and ft.backend == "pallas" and TGMM_USE_KERNEL:
        from repro.kernels import grouped as kgrouped
        from repro.kernels.templates import BatchedKernelSpec
        n = g_buf.shape[-1]
        kspec = BatchedKernelSpec(ft_level=ft.level, tgmm=True)
        # bm is pinned by the existing forward buffer's layout; plan_tgmm
        # re-clamps bn/bk under the tgmm VMEM model with that bm.
        p = kgrouped.plan_tgmm(t_buf, n, k, buf.dtype, n_groups=ng,
                               ft_level=ft.level, spec=kspec, bm=bm)
        dw, _rep = kgrouped.tgmm_buffer_call(
            kspec, buf, g_buf, gid=gid, row_end=row_end, params=p, ft=ft,
            inject=inject, key=key)
        # Backward-pass corrections are applied but not counted (DESIGN.md).
        return dw
    return _grouped_dw_jnp(ft, inject, buf, g_buf, gid, ng, bm)


@_abft_body
def _grouped_dw_jnp(ft: FTConfig, inject, buf, g_buf, gid, ng: int, bm: int):
    """The jnp tgmm: per-row-tile outer products segment-summed per group —
    exactly the useful FLOPs (T_buf·K·N) — then verified with per-group
    checksums (col: (X_g e_K)^T G_g; row: X_g^T (G_g e_N))."""
    k = buf.shape[1]
    num_tiles = gid.shape[0]
    b3 = buf.reshape(num_tiles, bm, k).astype(jnp.float32)
    g3 = g_buf.reshape(num_tiles, bm, -1).astype(jnp.float32)
    per_tile = jnp.einsum("tbk,tbn->tkn", b3, g3)
    dw = jax.ops.segment_sum(per_tile, gid, num_segments=ng)   # (G, K, N)
    if ft.enabled:
        if inject is not None:
            from .fault_injection import inject_spec
            dw = inject_spec(dw, inject)
        u = jnp.sum(b3, axis=-1)                               # (tiles, bm)
        v = jnp.sum(g3, axis=-1)
        colck = jax.ops.segment_sum(jnp.einsum("tb,tbn->tn", u, g3), gid,
                                    num_segments=ng)           # (G, N)
        rowck = jax.ops.segment_sum(jnp.einsum("tbk,tb->tk", b3, v), gid,
                                    num_segments=ng)           # (G, K)
        ck = abft.Checksums(col=colck[:, None, :], row=rowck[:, :, None])
        if ft.static_tau is not None:
            tau = jnp.full((ng,), ft.static_tau, jnp.float32)
        else:
            eps = float(jnp.finfo(jnp.float32).eps)
            amax = jax.ops.segment_max(jnp.max(jnp.abs(b3), axis=(1, 2)),
                                       gid, num_segments=ng)
            gmax = jax.ops.segment_max(jnp.max(jnp.abs(g3), axis=(1, 2)),
                                       gid, num_segments=ng)
            amax = jnp.where(jnp.isfinite(amax), amax, 0.0)
            gmax = jnp.where(jnp.isfinite(gmax), gmax, 0.0)
            rows = jax.ops.segment_sum(jnp.ones((num_tiles,), jnp.float32),
                                       gid, num_segments=ng) * bm
            tau = jnp.maximum(ft.rel_tau * eps * rows * amax * gmax, 1e-30)
        dw, _ = abft.detect_and_correct(dw, ck, tau, corrects=ft.corrects)
    return dw


_ft_grouped_cvjp.defvjp(_ft_grouped_fwd, _ft_grouped_bwd,
                        symbolic_zeros=True)


def grouped_row_tile(t: int, n: int, k: int, dtype, n_groups: int,
                     ft: FTLike, site: Optional[str] = None) -> int:
    """The row-tile (group-alignment) granularity `ft_grouped_matmul` would
    use for this problem — exposed so multi-GEMM callers (the MoE FFN) can
    build ONE layout/buffer and stay in buffer space across GEMMs. Under an
    `FTPolicy`, pass the ``site`` of the buffer's FIRST grouped GEMM (the
    layout is shared across the chain, so one resolution decides it)."""
    ft = resolve_ft(ft, site)
    if ft.enabled and ft.backend == "pallas":
        from repro.kernels import grouped as kgrouped
        from repro.kernels.templates import BatchedKernelSpec
        kspec = BatchedKernelSpec(ft_level=ft.level, grouped=True)
        return kgrouped.plan_grouped(t, n, k, dtype, n_groups=n_groups,
                                     ft_level=ft.level, spec=kspec).bm
    return {4: 8, 2: 16, 1: 32}.get(jnp.dtype(dtype).itemsize, 8)


def ft_grouped_matmul_buffer(buf: jax.Array, w: jax.Array, gid: jax.Array,
                             row_end: jax.Array, ft: FTLike = FT_OFF,
                             key: Optional[jax.Array] = None,
                             spec: Optional[InjectionSpec] = None,
                             bwd_inject=None,
                             site: Optional[str] = None) -> jax.Array:
    """Buffer-space `ft_grouped_matmul`: operate directly on a group-sorted
    (t_buf, K) buffer (see `kernels.grouped.layout`) and return the
    (t_buf, N) result in buffer space — lets a chain of grouped GEMMs over
    one routing decision (gate/up/down of an expert FFN) scatter once and
    gather once instead of round-tripping per GEMM. ``bwd_inject`` =
    ("dbuf"|"dw", InjectionSpec) lands an SEU in the named backward GEMM
    (the dw one is the tgmm kernel on the pallas backend)."""
    ft = resolve_ft(ft, site)
    _check_bwd_inject(ft, bwd_inject)
    note_site(site, "grouped", buf.shape[0], w.shape[-1], buf.shape[-1],
              batch=w.shape[0], in_bytes=jnp.dtype(buf.dtype).itemsize)
    if not ft.enabled and key is None and spec is None:
        # Fast path mirroring ft_dot: plain grouped product, no custom_vjp.
        return _grouped_dot_jnp(buf, w, gid).astype(buf.dtype)
    y_buf, det, maxres = _ft_grouped_cvjp(ft, spec, bwd_inject, buf, w, gid,
                                          row_end, key)
    _record(det, maxres, ft.corrects, site)
    return y_buf


def ft_grouped_matmul(x: jax.Array, w: jax.Array, group_ids: jax.Array,
                      ft: FTLike = FT_OFF,
                      key: Optional[jax.Array] = None,
                      spec: Optional[InjectionSpec] = None,
                      bwd_inject=None,
                      site: Optional[str] = None) -> jax.Array:
    """Fault-tolerant ragged grouped matmul: y[t] = x[t] @ w[group_ids[t]].

    x: (T, K) rows in caller order; w: (G, K, N); group_ids: int32 (T,).
    Group sizes are whatever routing produced — no capacity, no dropped
    rows; the only padding is ≤ G·(bm-1) row-tile alignment rows. Both
    directions are custom_vjp-protected: d_buf runs the grouped kernel
    against transposed weights, and d_w runs the output-stationary tgmm
    kernel on the pallas backend (PR 4 — the segment-checksum jnp path
    elsewhere). Backend follows `ft.backend` like `ft_dot`."""
    from repro.kernels.grouped import layout as glayout

    ft = resolve_ft(ft, site)
    t, k = x.shape
    ng = w.shape[0]
    bm = grouped_row_tile(t, w.shape[-1], k, x.dtype, ng, ft)
    lay = glayout.make_layout(group_ids, ng, bm)
    buf = glayout.scatter_rows(x, lay)
    y_buf = ft_grouped_matmul_buffer(buf, w, lay.gid, lay.row_end, ft=ft,
                                     key=key, spec=spec,
                                     bwd_inject=bwd_inject, site=site)
    return glayout.gather_rows(y_buf, lay)


def ft_verdict_dot(a: jax.Array, b: jax.Array, ft: FTLike,
                   spec: Optional[InjectionSpec] = None,
                   key: Optional[jax.Array] = None,
                   site: Optional[str] = None
                   ) -> Tuple[jax.Array, abft.Verdict]:
    """2-D ft matmul that also returns the Verdict — used by the offline-ABFT
    recompute loop (§5.5) and by tests asserting detection behaviour."""
    ft = resolve_ft(ft, site)
    a2 = a.reshape(-1, a.shape[-1]) if a.ndim != 2 else a
    fn = _fused_ft_matmul_2d if ft.fused else _nonfused_ft_matmul_2d
    return fn(ft, spec, a2, b, key)
