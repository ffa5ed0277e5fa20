"""jit'd public wrappers around the Pallas kernels.

`gemm_call` is the front door of the template subsystem: it resolves a
`templates.KernelSpec` (FT level × epilogue chain × dtypes) against the
concrete problem — variant-aware autotuned parameters (`autotune.best_params`,
backed by the candidate search + persistent tuning cache), ragged-shape
dispatch (tile-divisible shapes run the plain variant; ragged shapes run the
masked variant padded only to a fitted tile grid instead of full class tiles
— see `dispatch_info`), backend fallback (interpret=True automatically
off-TPU so the same call sites run on CPU in tests), operand padding for the
fused epilogue aux inputs, and report plumbing. `matmul`, `ft_matmul*` and
`fused_matmul` are thin specializations of it.

Element widths are always derived from the *actual operand dtype*
(`a.dtype.itemsize`) — never assumed 4 — so bf16/fp16 problems get the
correct sublane alignment, fitted tiles, and VMEM budgets.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.policy import FTConfig, InjectionSpec, ONLINE_BLOCK, FT_OFF
from repro.tools.trace import traced
from . import autotune, ftgemm, gemm, search
from .templates import BatchedKernelSpec, KernelSpec, registry
from .templates import spec as spec_mod


def _should_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _pad2(x: jax.Array, rows: int, cols: int) -> jax.Array:
    """Zero-pad the trailing two dims to (rows, cols) — any leading batch
    dims pass through (shared by the 2-D and batched/grouped dispatchers;
    zero padding is ABFT-neutral)."""
    pr, pc = rows - x.shape[-2], cols - x.shape[-1]
    if pr == 0 and pc == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, pr), (0, pc)])


def dispatch_info(m: int, n: int, k: int,
                  params: Optional[autotune.KernelParams] = None, *,
                  in_bytes: Optional[int] = None, dtype=None,
                  ft_level: str = "off",
                  spec: Optional[KernelSpec] = None) -> Dict:
    """Pure dispatch decision for a (M, N, K) GEMM.

    Element width comes from `dtype` (preferred) or `in_bytes`; pass the
    actual operand dtype — bf16/fp16 problems have a different sublane floor
    (16/32 rows) and VMEM budget than f32, so a defaulted width would fit
    wrong tiles. (Falls back to 4 bytes with neither given, for
    structural-only queries.)

    path="padded": the shape divides the class tiles — run the plain kernel
    (no padding at all in that case). path="masked": ragged shape — run the
    masked kernel on a *fitted* tile grid (`search.fit_tile` per dim:
    sublane-aligned bm, MXU-aligned bn/bk) carrying true dims via scalar
    prefetch.

    `padded_flop_ratio` is executed FLOPs over the hardware floor (the
    sublane/lane-aligned problem no TPU kernel can go below) — 1.0 means
    zero avoidable padding. The old full-padding path is reported alongside
    as `padded_path_ratio` for comparison (the codegen benchmark's metric).
    """
    if in_bytes is None:
        in_bytes = jnp.dtype(dtype).itemsize if dtype is not None else 4
    p = params or autotune.best_params(m, n, k, in_bytes, ft_level=ft_level,
                                       spec=spec)
    sub = search.sublane(in_bytes)
    align_m = autotune.MXU if ft_level == "tile" else sub
    q = autotune.KernelParams(
        bm=search.fit_tile(m, p.bm, align_m),
        bn=search.fit_tile(n, p.bn, autotune.MXU),
        bk=search.fit_tile(k, p.bk, autotune.MXU),
        shape_class=p.shape_class)
    mp, np_, kp = autotune.padded_shape(m, n, k, p)
    me, ne, ke = search.executed_dims(m, n, k, q)
    hw = (autotune._round_up(m, align_m) * autotune._round_up(n, autotune.MXU)
          * autotune._round_up(k, autotune.MXU))
    divisible = (m % p.bm == 0 and n % p.bn == 0 and k % p.bk == 0)
    path = "padded" if divisible else "masked"
    executed = mp * np_ * kp if divisible else me * ne * ke
    return {
        "path": path,
        "params": p,
        "masked_params": q,
        "executed_shape": (mp, np_, kp) if divisible else (me, ne, ke),
        "executed_flops": 2.0 * executed,
        "hw_aligned_flops": 2.0 * hw,
        "padded_flop_ratio": executed / hw,
        "padded_path_ratio": (mp * np_ * kp) / hw,
    }


@traced("kernel/gemm")
def gemm_call(spec: KernelSpec, a: jax.Array, b: jax.Array, *,
              bias: Optional[jax.Array] = None,
              residual: Optional[jax.Array] = None,
              ft: Optional[FTConfig] = None,
              inject: Optional[InjectionSpec] = None,
              params: Optional[autotune.KernelParams] = None,
              interpret: Optional[bool] = None,
              out_dtype=None,
              key: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """The template subsystem's front door: run any registered kernel
    variant on an arbitrary (M, K) × (K, N) problem.

    spec      — the variant: FT level, epilogue chain, dtypes. `spec.masked`
                is advisory; the dispatcher re-resolves it from the shape
                (tile-divisible → plain, ragged → masked fitted grid).
    bias      — (N,) or (1, N) vector when the chain contains "bias".
    residual  — (M, N) array when the chain contains "residual".
    ft        — FTConfig for FT specs (verify schedule, correction, τ);
                defaults to online-correcting at `spec.ft_level`.
    inject    — optional deterministic SEU (tests/benchmarks).
    key       — PRNG key for the in-kernel stochastic SEU hook; armed only
                when ``ft.inject_rate > 0`` (see `flashft.encode_rng`).

    Returns (C, report) — report is None for non-FT specs, else the
    per-block [detected, corrected, row, col, magnitude, max_residual, τ,
    k_elapsed] array of `ftgemm`. Multi-output specs (``spec.extra_outputs``)
    return ((C, extra…), report) with every output sliced back to (M, N).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    in_bytes = a.dtype.itemsize
    ft_level = spec.ft_level
    if ft is None:
        ft = FTConfig(level=ft_level) if spec.ft else FT_OFF
    if spec.ft != ft.enabled or (spec.ft and ft.level != ft_level):
        raise ValueError(f"FTConfig(level={ft.level!r}, action={ft.action!r})"
                         f" disagrees with spec.ft_level={ft_level!r}")

    p = params or autotune.best_params(m, n, k, in_bytes, ft_level=ft_level,
                                       spec=spec)
    info = dispatch_info(m, n, k, p, in_bytes=in_bytes, ft_level=ft_level,
                         spec=spec)
    masked = info["path"] == "masked"
    rspec = dataclasses.replace(spec, masked=masked)
    rp = info["masked_params"] if masked else p
    me, ne, ke = info["executed_shape"]

    if bias is not None:
        bias = bias.reshape(1, -1)
        assert bias.shape[1] == n, (bias.shape, n)
        bias = _pad2(bias, 1, ne)       # zero pads keep the checksum fold exact
    if residual is not None:
        assert residual.shape == (m, n), (residual.shape, (m, n))
        residual = _pad2(residual, me, ne)

    inj_idx = inj_mag = rng = dims = None
    if rspec.ft:
        from . import flashft
        inj_idx, inj_mag = ftgemm.encode_injection(inject)
        rng = flashft.encode_rng(key, ft)
    if masked:
        dims = jnp.array([m, n, k], jnp.int32)
        a = _pad2(a, me, ke)
        b = _pad2(b, ke, ne)

    out, rep = registry.kernel_call(
        a, b, bias=bias, residual=residual, inj_idx=inj_idx,
        inj_mag=inj_mag, rng=rng, dims=dims, spec=rspec, params=rp, ft=ft,
        interpret=_should_interpret(interpret), out_dtype=out_dtype)
    if masked:
        out = (tuple(o[:m, :n] for o in out) if spec.extra_outputs
               else out[:m, :n])
    return out, rep


def matmul(a: jax.Array, b: jax.Array, *,
           params: Optional[autotune.KernelParams] = None,
           interpret: Optional[bool] = None,
           out_dtype=None) -> jax.Array:
    """High-performance non-FT GEMM (paper §3): C = A @ B, any (M, K, N).
    Tile-divisible shapes run the plain kernel; ragged shapes dispatch to
    the masked kernel on a fitted grid (no full-padding fallback)."""
    out, _ = gemm_call(KernelSpec(), a, b, params=params,
                       interpret=interpret, out_dtype=out_dtype)
    return out


@traced("kernel/fused_matmul")
def fused_matmul(a: jax.Array, b: jax.Array, *,
                 bias: Optional[jax.Array] = None,
                 act: Optional[str] = None,
                 residual: Optional[jax.Array] = None,
                 ft: FTConfig = FT_OFF,
                 inject: Optional[InjectionSpec] = None,
                 params: Optional[autotune.KernelParams] = None,
                 interpret: Optional[bool] = None,
                 out_dtype=None,
                 save_act_grad: bool = False,
                 key: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Canonical fused-epilogue GEMM: C = act(A·B + bias) + residual in one
    kernel — the matmul→bias→activation sequence without the second HBM
    round-trip. With an enabled `ft`, the linear epilogue prefix is folded
    into the checksum comparison so online ABFT verifies (and corrects)
    post-epilogue. Returns (C, report|None).

    ``save_act_grad=True`` (requires ``act``) runs the multi-output variant:
    the kernel additionally writes act'(A·B + bias) — evaluated on the
    verified/corrected accumulator — and the return becomes
    ((C, act_grad), report|None). This is the saved residual
    `core.ft_dot_fused`'s backward consumes instead of recomputing the
    pre-activation GEMM."""
    spec = spec_mod.fused(bias=bias is not None, act=act,
                          residual=residual is not None,
                          ft_level=ft.level if ft.enabled else "off")
    if save_act_grad:
        spec = dataclasses.replace(spec, extra_outputs=("act_grad",))
    return gemm_call(spec, a, b, bias=bias, residual=residual, ft=ft,
                     inject=inject, params=params, interpret=interpret,
                     out_dtype=out_dtype, key=key)


@traced("kernel/grouped_gemm")
def grouped_gemm_call(spec: KernelSpec, a: jax.Array, b: jax.Array, *,
                      group_ids: Optional[jax.Array] = None,
                      n_groups: Optional[int] = None,
                      ft: Optional[FTConfig] = None,
                      inject: Optional[InjectionSpec] = None,
                      inj_batch: int = 0,
                      params: Optional[autotune.KernelParams] = None,
                      interpret: Optional[bool] = None,
                      out_dtype=None,
                      key: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """The batched/grouped front door (PR 3) — `gemm_call`'s sibling for the
    leading-batch-axis variant space, dispatching on operand ranks:

      * a (B, M, K), b (B, K, N) or (K, N): uniform batched GEMM — ONE
        Pallas launch with a leading batch grid axis (this is what
        `core.ft_batched_dot`'s pallas backend emits for attention QK/PV
        and per-expert matmuls). Ragged (m, n, k) shared by the batch takes
        the masked fitted-tile path.
      * a (T, K), b (G, K, N) with ``group_ids`` int32 (T,): ragged grouped
        GEMM — y[t] = a[t] @ b[group_ids[t]] over a group-sorted buffer
        with zero capacity padding; detection/correction run per group
        (`core.ft_grouped_matmul` / `models.moe` route here).
      * a (T, K), b (T, N) with ``group_ids`` int32 (T,) and ``n_groups``:
        the grouped *transpose* GEMM ("tgmm", PR 4) —
        dw[g] = Σ_{t: group_ids[t]=g} a[t] ⊗ b[t], i.e. the (G, K, N)
        per-group outer-product sum of the MoE backward dw, run as ONE
        output-stationary Pallas kernel with per-group checksums
        (`core.ft_grouped_matmul`'s backward routes here).

    `spec` may be a plain `KernelSpec` (promoted to `BatchedKernelSpec`) or
    a `BatchedKernelSpec`; masked/shared_b/grouped/tgmm are re-resolved
    from the operands. Returns (C, report|None)."""
    from . import grouped as grouped_mod

    bspec = BatchedKernelSpec(
        ft_level=spec.ft_level, epilogue=spec.epilogue,
        acc_dtype=spec.acc_dtype, out_dtype=spec.out_dtype)
    if a.ndim == 3:
        assert group_ids is None, "uniform batched GEMM takes no group_ids"
        return grouped_mod.batched_gemm_call(
            bspec, a, b, ft=ft, inject=inject, inj_batch=inj_batch,
            params=params, interpret=interpret, out_dtype=out_dtype,
            key=key)
    assert a.ndim == 2 and group_ids is not None, (a.shape, group_ids)
    if b.ndim == 2:                      # tgmm: two row-aligned buffers
        assert n_groups is not None, "tgmm dispatch needs n_groups"
        return grouped_mod.tgmm_matmul_rows(
            dataclasses.replace(bspec, epilogue=(), tgmm=True), a, b,
            group_ids, n_groups=n_groups, ft=ft, inject=inject,
            params=params, interpret=interpret, out_dtype=out_dtype,
            key=key)
    assert b.ndim == 3, (a.shape, b.shape)
    return grouped_mod.grouped_matmul_rows(
        dataclasses.replace(bspec, grouped=True), a, b, group_ids, ft=ft,
        inject=inject, params=params, interpret=interpret,
        out_dtype=out_dtype, key=key)


def ft_matmul(a: jax.Array, b: jax.Array, *,
              ft: FTConfig = ONLINE_BLOCK,
              spec: Optional[InjectionSpec] = None,
              params: Optional[autotune.KernelParams] = None,
              interpret: Optional[bool] = None,
              out_dtype=None) -> jax.Array:
    """Fused fault-tolerant GEMM (paper §4). Returns the corrected C."""
    out, _ = ft_matmul_report(a, b, ft=ft, spec=spec, params=params,
                              interpret=interpret, out_dtype=out_dtype)
    return out


def ft_matmul_report(a: jax.Array, b: jax.Array, *,
                     ft: FTConfig = ONLINE_BLOCK,
                     spec: Optional[InjectionSpec] = None,
                     params: Optional[autotune.KernelParams] = None,
                     interpret: Optional[bool] = None,
                     out_dtype=None,
                     key: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """FT-GEMM returning (C, report[gm, gn, 8]) — see ftgemm.REPORT_WIDTH.
    Ragged shapes dispatch to the masked kernel; the checksum math is
    masked identically, so ABFT detection/correction works on the ragged
    edge tiles."""
    return gemm_call(KernelSpec(ft_level=ft.level), a, b, ft=ft,
                     inject=spec, params=params, interpret=interpret,
                     out_dtype=out_dtype, key=key)


def _flash_spec(ft: FTConfig, direction: str, dh_p: int,
                save_stats: bool = False):
    from .templates.spec import FlashKernelSpec
    return FlashKernelSpec(ft_level=ft.level if ft.enabled else "off",
                           direction=direction, dh=dh_p,
                           save_stats=save_stats)


def _flash_fit(dim: int, cap: int, align: int) -> int:
    """Fitted flash block edge: ≤ cap (the autotuned/user tile), ≤ the
    128-padded dim (never over-tile), aligned to `align`."""
    cap = max(min(cap, ((dim + 127) // 128) * 128), align)
    return search.fit_tile(dim, cap, align)


def _pad3(x, s_to, d_to, value=0.0):
    return jnp.pad(x, ((0, 0), (0, s_to - x.shape[1]),
                       (0, d_to - x.shape[2])), constant_values=value)


def _check_flash_injection(kernel: str, *, head: int, n_heads: int,
                           blk: int, n_blks: int, step: int, n_steps: int,
                           q_span, kv_span, sq: int, skv: int,
                           causal: bool) -> None:
    """A deterministic flash InjectionSpec addresses a concrete grid cell;
    with autotuned (bq, bkv) the grid shape is no longer fixed, so a stale
    (block, step) target could fall outside the grid — or on a cell the
    causal/ragged dispatch skips — and the SEU would silently never land.
    That is exactly the silently-clean-campaign failure mode this kernel
    family exists to prevent, so fail loudly instead. ``q_span``/``kv_span``
    are the (start, stop) row/col ranges of the targeted cell."""
    ok = (0 <= head < n_heads and 0 <= blk < n_blks
          and 0 <= step < n_steps)
    if ok:
        (q0, q1), (kv0, _) = q_span, kv_span
        ok = q0 < sq and kv0 < skv and (
            not causal or kv0 <= q1 - 1 + (skv - sq))
    if not ok:
        raise ValueError(
            f"{kernel}: deterministic injection targets head {head} of "
            f"{n_heads}, block {blk} of {n_blks}, step {step} of {n_steps} "
            f"— a cell the fitted grid never executes (autotuned/fitted "
            f"tiles, ragged true lengths, or causal skipping). The SEU "
            f"would silently never land; pin bq/bkv or fix the injection "
            f"target.")


@traced("kernel/flash_ft")
def flash_ft(q: jax.Array, k: jax.Array, v: jax.Array, *,
             ft: FTConfig = ONLINE_BLOCK, causal: bool = True,
             spec: Optional[InjectionSpec] = None,
             inj_bh: int = 0, inj_q_block: int = 0,
             bq: Optional[int] = None, bkv: Optional[int] = None,
             interpret: Optional[bool] = None,
             protect_qk: bool = True,
             n_rep: int = 1, save_stats: bool = False,
             key: Optional[jax.Array] = None):
    """Flash attention with fused in-kernel ABFT (see kernels/flashft.py).
    q: (BH, Sq, dh); k, v: (BH/n_rep, Skv, dh) — ``n_rep`` is the GQA
    query-group width (query head h reads KV head h//n_rep via the K/V
    index maps; KV is never repeat-materialized). Pads dh to the 128-lane
    MXU edge; the sequence dims take the masked ragged path: true (Sq, Skv)
    ride in via scalar prefetch, blocks are *fitted* to the ragged lengths
    (sublane-aligned bq, lane-aligned bkv — no padding to full class
    tiles), and padded KV positions are masked to -inf in-kernel. Ragged
    Skv is exact for non-causal AND causal dispatch: the in-kernel
    causal∧kv-edge mask is bottom-right aligned on the true lengths
    (query i attends kv j iff j ≤ i + Skv − Sq), so causal cross-length
    attention (Skv ≥ Sq, the decode convention) no longer needs padded
    shapes.

    ``bq``/``bkv`` default to the autotuned tiles (`autotune.best_params`
    under the ``/v_flashfwd*`` variant key); pass explicit values to pin
    the grid (tests that address report blocks do). ``key`` drives the
    in-kernel stochastic SEU hook when ``ft.inject_rate > 0`` — one
    Bernoulli(rate) SEU per (head, q-block) lands in the PV accumulator at
    a hash-drawn (step, row, col), so fault campaigns exercise the kernel
    itself. ``save_stats`` additionally returns the per-row softmax
    statistics for the dedicated backward.

    Returns (out, report) — or (out, m, l, report) with ``save_stats``
    (m, l are (BH, Sq) f32; degenerate rows hold (−∞, 0))."""
    from . import flashft
    bh, sq, dh = q.shape
    skv = k.shape[1]
    assert bh == k.shape[0] * n_rep, (q.shape, k.shape, n_rep)
    assert not causal or skv >= sq, (
        "causal flash_ft is bottom-right aligned: needs Skv >= Sq "
        f"(got Sq={sq}, Skv={skv})")
    in_bytes = q.dtype.itemsize
    sub = search.sublane(in_bytes)
    dh_p = ((dh + 127) // 128) * 128
    fspec = _flash_spec(ft, "fwd", dh_p, save_stats)
    if bq is None or bkv is None:
        p = autotune.best_params(sq, skv, dh_p, in_bytes,
                                 ft_level=fspec.ft_level, spec=fspec,
                                 batch=bh)
        bq = p.bm if bq is None else bq
        bkv = p.bn if bkv is None else bkv
    bq = _flash_fit(sq, bq, sub)
    bkv = _flash_fit(skv, bkv, autotune.MXU)
    sq_p = ((sq + bq - 1) // bq) * bq
    skv_p = ((skv + bkv - 1) // bkv) * bkv

    if spec is not None:
        _check_flash_injection(
            "flash_ft", head=inj_bh, n_heads=bh, blk=inj_q_block,
            n_blks=sq_p // bq, step=spec.k_step, n_steps=skv_p // bkv,
            q_span=(inj_q_block * bq, (inj_q_block + 1) * bq),
            kv_span=(spec.k_step * bkv, (spec.k_step + 1) * bkv),
            sq=sq, skv=skv, causal=causal)
    qp, kp, vp = (_pad3(q, sq_p, dh_p), _pad3(k, skv_p, dh_p),
                  _pad3(v, skv_p, dh_p))
    inj_idx, inj_mag = flashft.encode_injection(spec, inj_bh, inj_q_block)
    rng = flashft.encode_rng(key, ft)
    dims = jnp.array([sq, skv], jnp.int32)
    res = flashft.flash_ft_attention(
        qp, kp, vp, inj_idx, inj_mag, dims, rng, bq=bq, bkv=bkv,
        causal=causal, ft=ft, interpret=_should_interpret(interpret),
        protect_qk=protect_qk, scale=dh ** -0.5, n_rep=n_rep,
        save_stats=save_stats)
    if save_stats:
        out, m, l, rep = res
        return out[:, :sq, :dh], m[:, :sq, 0], l[:, :sq, 0], rep
    out, rep = res
    return out[:, :sq, :dh], rep


@traced("kernel/flash_decode")
def flash_ft_decode(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    lengths: jax.Array, page_table: jax.Array, layer, *,
                    ft: FTConfig = ONLINE_BLOCK,
                    spec: Optional[InjectionSpec] = None, inj_g: int = 0,
                    interpret: Optional[bool] = None,
                    protect_qk: bool = True,
                    key: Optional[jax.Array] = None):
    """Paged single-position flash decode with per-row ragged lengths
    (PR 9) — the serving engine's attention kernel.

    q: (B, H, dh) — one query position per serving slot; k_pages/v_pages:
    (n_layers, n_pages, KVH, page, dh) — the whole stacked page pool
    (`train.kv_cache`), read where it lies at ``layer`` (an int or an int32
    scalar such as a layer scan's index; a caller holding one layer's pool
    passes ``pool[None]`` and layer 0); lengths: int32[B] per-slot TRUE kv
    lengths (the ragged vector that replaces the forward's one (Sq, Skv)
    pair; 0 marks a dead slot, which returns exact zeros); page_table:
    int32[B, max_pages] physical page ids. Table and layer are
    scalar-prefetched into the kernel's K/V index maps so each (slot, head)
    grid row streams exactly its own pages out of the pool.

    dh must be lane-aligned (128-multiple) — the paged pool is laid out at
    kernel geometry, so there is no pad-and-slice here; callers with
    smaller head dims take the gather+dense oracle path
    (`models.blocks.paged_decode_attention`). The GQA query group of each
    kv head (n_rep = H // KVH rows) is the stationary block, zero-padded
    to the sublane edge (checksum-neutral; garbage rows sliced off).

    ``spec``/``inj_g`` land a deterministic SEU in grid row ``inj_g``
    (= slot·KVH + head) at kv step ``spec.k_step``; ``key`` drives the
    stochastic in-kernel hook (salt ``SALT_DECODE``). Returns
    (out (B, H, dh), report (B·KVH, 1, W))."""
    from . import flashft
    b, h, dh = q.shape
    _, n_pages, kvh, page, dh_k = k_pages.shape
    assert v_pages.shape == k_pages.shape, (k_pages.shape, v_pages.shape)
    assert dh == dh_k, (q.shape, k_pages.shape)
    assert h % kvh == 0, (h, kvh)
    if dh % 128 != 0:
        raise ValueError(f"flash_ft_decode needs a lane-aligned head dim "
                         f"(128-multiple), got {dh} — use the dense "
                         f"decode_attention oracle path")
    max_pages = page_table.shape[1]
    assert page_table.shape[0] == b and lengths.shape == (b,), \
        (page_table.shape, lengths.shape, b)
    n_rep = h // kvh
    in_bytes = q.dtype.itemsize
    sub = search.sublane(in_bytes)
    bq = -(-n_rep // sub) * sub
    # Keep the decode variant in the tuning pipeline: the lookup records /
    # reuses the ``/v_flashdecode`` cache entry whose streamed block chose
    # the page size (`kv_cache.plan_pages` consults the same spec), and
    # validates this geometry against the variant's VMEM model.
    fspec = _flash_spec(ft, "decode", dh)
    autotune.best_params(bq, max(max_pages * page, autotune.MXU), dh,
                         in_bytes, ft_level=fspec.ft_level, spec=fspec,
                         batch=b * kvh)

    if spec is not None:
        if not (0 <= inj_g < b * kvh and 0 <= spec.k_step < max_pages):
            raise ValueError(
                f"flash_ft_decode: deterministic injection targets grid "
                f"row {inj_g} of {b * kvh}, kv step {spec.k_step} of "
                f"{max_pages} — outside the decode grid, the SEU would "
                f"silently never land")
    inj_idx, inj_mag = flashft.encode_injection(spec, inj_g, 0)
    rng = flashft.encode_rng(key, ft)

    qg = q.reshape(b * kvh, n_rep, dh)
    if bq > n_rep:
        qg = jnp.pad(qg, ((0, 0), (0, bq - n_rep), (0, 0)))
    out, rep = flashft.flash_ft_decode_attention(
        qg, k_pages, v_pages, inj_idx, inj_mag,
        lengths.astype(jnp.int32), page_table.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1), rng, kvh=kvh, ft=ft,
        interpret=_should_interpret(interpret), protect_qk=protect_qk,
        scale=dh ** -0.5)
    return out[:, :n_rep].reshape(b, h, dh), rep


@traced("kernel/flash_ft_bwd")
def flash_ft_bwd(q: jax.Array, k: jax.Array, v: jax.Array, o: jax.Array,
                 m: jax.Array, l: jax.Array, g: jax.Array, *,
                 ft: FTConfig = ONLINE_BLOCK, causal: bool = True,
                 n_rep: int = 1, key: Optional[jax.Array] = None,
                 inject: Optional[InjectionSpec] = None,
                 inj_target: str = "dq", inj_bh: int = 0, inj_blk: int = 0,
                 bq: Optional[int] = None, bkv: Optional[int] = None,
                 interpret: Optional[bool] = None,
                 protect_qk: bool = True):
    """The dedicated flash-attention backward (PR 5): dQ/dK/dV as TWO
    Pallas launches over the forward-saved (m, l) statistics — zero
    chunked-oracle recompute, no S×S transient, and all four backward
    GEMMs (dP = g·Vᵀ, dV = Pᵀ·g, dQ = dS·K, dK = dSᵀ·Q) plus the in-kernel
    S recompute carry the same checksum-verify + branchless-correct ABFT
    as the forward.

    q, o, g: (BH, Sq, dh); k, v: (BH/n_rep, Skv, dh); m, l: (BH, Sq) f32
    from ``flash_ft(..., save_stats=True)``. di = rowsum(g ∘ o) is the one
    elementwise preprocess (no GEMM). Each direction autotunes its own
    (bq, bkv) under its ``/v_flashbwd_*`` variant key; GQA reuses the
    forward's K/V index maps, with the dkv kernel folding the n_rep query
    heads of a KV head into its reduction walk — dk/dv come back per KV
    head, never repeat-materialized.

    ``inject``/``inj_target`` land a deterministic SEU inside one named
    backward GEMM ("dp_q"|"dq"|"dp_kv"|"dv"|"dk" — see
    `flashft.encode_bwd_injection`); ``key`` drives the stochastic
    in-kernel hook like the forward. Returns
    (dq, dk, dv, report_dq, report_dkv)."""
    from . import flashft
    bh, sq, dh = q.shape
    bkvh, skv, _ = k.shape
    assert bh == bkvh * n_rep, (q.shape, k.shape, n_rep)
    assert o.shape == q.shape and g.shape == q.shape, (o.shape, g.shape)
    assert m.shape[:2] == (bh, sq) and l.shape[:2] == (bh, sq), \
        (m.shape, l.shape, (bh, sq))
    assert not causal or skv >= sq, (
        "causal flash_ft_bwd is bottom-right aligned: needs Skv >= Sq "
        f"(got Sq={sq}, Skv={skv})")
    in_bytes = q.dtype.itemsize
    sub = search.sublane(in_bytes)
    dh_p = ((dh + 127) // 128) * 128
    itp = _should_interpret(interpret)
    scale = dh ** -0.5
    neg_inf = flashft.NEG_INF

    # The one elementwise preprocess of the flash backward (no GEMM).
    di = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    m3 = m.reshape(bh, sq, 1).astype(jnp.float32)
    l3 = l.reshape(bh, sq, 1).astype(jnp.float32)
    di3 = di.reshape(bh, sq, 1)

    inj_dq, inj_dkv, inj_mag = flashft.encode_bwd_injection(
        inject, inj_target, inj_bh, inj_blk)
    rng = flashft.encode_rng(key, ft)
    dims = jnp.array([sq, skv], jnp.int32)

    def fitted(direction, stat_dim, stream_dim, batch):
        fspec = _flash_spec(ft, direction, dh_p)
        if bq is not None and bkv is not None:
            return bq, bkv
        p = autotune.best_params(stat_dim, stream_dim, dh_p, in_bytes,
                                 ft_level=fspec.ft_level, spec=fspec,
                                 batch=batch)
        if direction == "dq":
            return (p.bm if bq is None else bq,
                    p.bn if bkv is None else bkv)
        return (p.bn if bq is None else bq,
                p.bm if bkv is None else bkv)

    def padded(bq_f, bkv_f):
        sq_p = ((sq + bq_f - 1) // bq_f) * bq_f
        skv_p = ((skv + bkv_f - 1) // bkv_f) * bkv_f
        # Padded query rows carry the degenerate-stat markers (m=−∞, l=0)
        # so both backward kernels see p ≡ 0 there — exact zeros, no
        # reliance on the cotangent being zero-padded.
        return (_pad3(q, sq_p, dh_p), _pad3(k, skv_p, dh_p),
                _pad3(v, skv_p, dh_p), _pad3(g, sq_p, dh_p),
                _pad3(m3, sq_p, 1, value=neg_inf), _pad3(l3, sq_p, 1),
                _pad3(di3, sq_p, 1))

    bq_q, bkv_q = fitted("dq", sq, skv, bh)
    bq_q = _flash_fit(sq, bq_q, sub)
    bkv_q = _flash_fit(skv, bkv_q, autotune.MXU)
    if inject is not None and inj_target in ("dp_q", "dq"):
        _check_flash_injection(
            f"flash_ft_bwd[{inj_target}]", head=inj_bh, n_heads=bh,
            blk=inj_blk, n_blks=-(-sq // bq_q), step=inject.k_step,
            n_steps=-(-skv // bkv_q),
            q_span=(inj_blk * bq_q, (inj_blk + 1) * bq_q),
            kv_span=(inject.k_step * bkv_q, (inject.k_step + 1) * bkv_q),
            sq=sq, skv=skv, causal=causal)
    dq, rep_dq = flashft.flash_ft_dq(
        *padded(bq_q, bkv_q), inj_dq, inj_mag, dims, rng, bq=bq_q,
        bkv=bkv_q, causal=causal, ft=ft, interpret=itp,
        protect_qk=protect_qk, scale=scale, n_rep=n_rep)

    bq_k, bkv_k = fitted("dkv", skv, sq, bkvh)
    bq_k = _flash_fit(sq, bq_k, sub)
    bkv_k = _flash_fit(skv, bkv_k, autotune.MXU)
    if inject is not None and inj_target in ("dp_kv", "dv", "dk"):
        _check_flash_injection(
            f"flash_ft_bwd[{inj_target}]", head=inj_bh, n_heads=bh,
            blk=inj_blk, n_blks=-(-skv // bkv_k), step=inject.k_step,
            n_steps=-(-sq // bq_k),
            q_span=(inject.k_step * bq_k, (inject.k_step + 1) * bq_k),
            kv_span=(inj_blk * bkv_k, (inj_blk + 1) * bkv_k),
            sq=sq, skv=skv, causal=causal)
    dk, dv, rep_dkv = flashft.flash_ft_dkv(
        *padded(bq_k, bkv_k), inj_dkv, inj_mag, dims, rng, bq=bq_k,
        bkv=bkv_k, causal=causal, ft=ft, interpret=itp,
        protect_qk=protect_qk, scale=scale, n_rep=n_rep)
    return (dq[:, :sq, :dh], dk[:, :skv, :dh], dv[:, :skv, :dh],
            rep_dq, rep_dkv)
