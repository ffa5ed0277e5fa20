"""Flash attention with fused online ABFT — the beyond-paper kernel family.

The paper's core insight is that ABFT only becomes ~free when its memory
operations are fused into a kernel that already holds the data in fast
memory. We apply that insight to the other GEMM-dominated hot spot of every
assigned architecture: attention — in BOTH directions.

Forward flash attention (online softmax over kv blocks; scores never touch
HBM) where BOTH in-kernel GEMMs are ABFT-protected per kv-step:

  * scores S = Q_blk·K_blkᵀ — verified against (eᵀQ)·Kᵀ and Q·(Kᵀe)
    *before* masking/softmax (the check is linear; the nonlinearity comes
    after);
  * delta  Δ = P·V_blk     — verified against (eᵀP)·V and P·(Ve); a located
    SEU is corrected branchlessly before Δ is rescaled into the
    accumulator.

With ``save_stats`` the forward additionally writes the per-row softmax
statistics (m = running row max of the scaled scores, l = running row sum
of exp) as extra VMEM outputs — the saved residual of the dedicated
backward (PR 5), which replaces the chunked-jnp oracle recompute:

  * `_flash_dq_kernel`  — q-block-stationary: recomputes S from (m, l),
    then dP = g·Vᵀ and dQ = Σ_kv dS·K, each GEMM checksum-verified and
    branchlessly corrected per kv-step;
  * `_flash_dkv_kernel` — kv-block-stationary (GQA folds the n_rep query
    heads of a KV head into the reduction walk): S recompute + dP = g·Vᵀ,
    dV = Σ_q Pᵀ·g and dK = Σ_q dSᵀ·Q, all verified per q-step.

So the four backward GEMMs of the attention train step (dP, dV, dQ, dK)
carry in-kernel ABFT exactly like the two forward ones; one SEU per
(stationary block × reduction step × GEMM) is detected AND corrected, and
the backward's HBM traffic is flash-shaped (Q, K, V, g, dQ, dK, dV + three
O(S) statistic columns — no S×S materialization, no O(chunk·S) oracle
transient).

Fully-masked query rows (a ragged Sq edge, or a causal row whose kv span is
empty) are *m-degenerate*: their running max never leaves −∞, so the
pre-fix kernel flushed `exp(0)=1` garbage weights (and `acc/1e-30` when
nothing accumulated). Degenerate rows are now zeroed at every step AND at
flush, their saved stats are written as (m=−∞, l=0), and the backward maps
l=0 to p≡0 — so both directions return exact zeros for such rows.

Stochastic SEU campaigns (`ft.inject_rate` > 0 with an injection key) run
IN-KERNEL through `templates.emit.stochastic_seu`: two words derived from
the campaign key ride in via scalar prefetch and a counter-based hash draws
one Bernoulli(rate) SEU per stationary output block per direction — so a
forced-flash fault campaign exercises the kernels it measures instead of
silently running clean (the MPGemmFI injector/kernel-disagreement pitfall).

Ragged sequence lengths take the masked dispatch of the GEMM kernels: the
true (Sq, Skv) ride in via scalar prefetch, kv blocks wholly past the true
Skv are skipped, and padded positions are masked after the (linear) score
verification and before softmax.

Launch construction lives in `templates.registry` (flash_fwd_call /
flash_dq_call / flash_dkv_call) and tile selection in `autotune.best_params`
under `templates.spec.FlashKernelSpec` variant keys (``/v_flashfwd*``,
``/v_flashbwd_dq``, ``/v_flashbwd_dkv``). Validated in interpret mode
against jnp oracles (tests/test_flashft.py, tests/test_flash_backward.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.policy import FTConfig, InjectionSpec
from repro.tools.trace import traced
from .templates import emit as temit
from .templates import registry as tregistry
from .templates.emit import f32dot

F32EPS = float(jnp.finfo(jnp.float32).eps)
NEG_INF = -1e30
REPORT_WIDTH = temit.REPORT_WIDTH

#: Contract flag `models.blocks` checks before launching a stochastic
#: (`ft.inject_rate`-driven) campaign down the flash path: True means the
#: kernels honor the campaign key in-kernel (both directions). A build that
#: cannot (e.g. a future backend without the hook) must flip this so forced
#: campaigns raise instead of silently measuring a clean run.
SUPPORTS_STOCHASTIC_INJECTION = True

#: Deterministic backward-injection targets (`encode_bwd_injection`):
#: which of the four backward GEMMs the SEU lands in. "dp_q"/"dp_kv" hit the
#: dP = g·Vᵀ product inside the dq / dkv kernel respectively.
BWD_TARGETS = {"dp_q": 0, "dq": 1, "dp_kv": 0, "dv": 2, "dk": 3}
_DQ_KERNEL_TARGETS = ("dp_q", "dq")
_DKV_KERNEL_TARGETS = ("dp_kv", "dv", "dk")

#: Per-kernel salts for the stochastic hook — one independent stream per
#: direction from a single campaign key.
SALT_FWD, SALT_DQ, SALT_DKV, SALT_DECODE = 0x51, 0x52, 0x53, 0x54

_CONTRACT_ROWS = (((0,), (0,)), ((), ()))     # Aᵀ·B without a transpose


def _iota2(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _row_mask(q_start, bq, width, true_sq):
    """(bq, width) mask of live query rows (rows past true Sq are dead)."""
    return q_start + _iota2((bq, width), 0) < true_sq


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _flash_ft_kernel(inj_ref, mag_ref, rng_ref, dims_ref,
                     q_ref, k_ref, v_ref,
                     *out_and_scratch,
                     kv_steps: int, q_blocks: int, bq: int, bkv: int,
                     dh: int, causal: bool, scale: float, corrects: bool,
                     rel_tau: float, protect_qk: bool, save_stats: bool,
                     inject_rate: float, bit_shift: int):
    refs = list(out_and_scratch)
    o_ref = refs.pop(0)
    m_out_ref = refs.pop(0) if save_stats else None
    l_out_ref = refs.pop(0) if save_stats else None
    rep_ref, acc_ref, m_ref, l_ref = refs

    h = pl.program_id(0)
    qi = pl.program_id(1)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        temit.init_report(rep_ref)

    q_start = qi * bq
    kv_start = s * bkv
    true_sq = dims_ref[0]
    true_skv = dims_ref[1]
    # Causal positions are bottom-right aligned on the TRUE lengths: query
    # row i attends kv j iff j ≤ i + (Skv − Sq) — the decode/cross-length
    # convention (Sq == Skv ⇒ the familiar triangular mask). The offset is
    # dynamic (scalar-prefetched), which is what lets ragged Sq ≠ Skv run
    # causally on fitted blocks instead of falling back to padded shapes.
    c_off = true_skv - true_sq
    # Ragged dispatch: kv blocks wholly past the true Skv are skipped
    # (scalar-prefetched seq lens, not padded shapes, drive the loop).
    run = kv_start < true_skv
    if causal:
        run = run & (kv_start <= q_start + bq - 1 + c_off)

    # One stochastic SEU per (head, q-block) with probability inject_rate,
    # landing in the PV accumulator at a uniformly drawn (kv step, row,
    # col) — the in-kernel campaign hook (see templates.emit). The step is
    # drawn over the block's LIVE kv span, not the grid extent, so the
    # realized rate matches the nominal one under causal/ragged skipping.
    n_live = _live_kv_steps(true_skv, q_start, bq, bkv, c_off, causal)
    land_seu = temit.stochastic_hook(
        rng_ref, SALT_FWD, h * q_blocks + qi, n_live, bq, dh, inject_rate,
        bit_shift)

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)                 # (bq, dh)
        k = k_ref[0].astype(jnp.float32)                 # (bkv, dh)
        v = v_ref[0].astype(jnp.float32)

        scores = f32dot(q, k.T)
        if protect_qk:
            ck_col = f32dot(jnp.sum(q, 0, keepdims=True), k.T)    # (1,bkv)
            ck_row = f32dot(q, jnp.sum(k.T, 1, keepdims=True))    # (bq,1)
            d_col = jnp.sum(scores, 0, keepdims=True) - ck_col
            d_row = jnp.sum(scores, 1, keepdims=True) - ck_row
            tau_qk = jnp.maximum(
                rel_tau * F32EPS * dh
                * jnp.max(jnp.abs(q)) * jnp.max(jnp.abs(k)), 1e-30)
            scores, det_qk, mag_qk, row_qk, col_qk = \
                temit._locate_correct_full(scores, d_col, d_row, tau_qk,
                                           corrects, bq, bkv)
            temit._record(rep_ref, det_qk, mag_qk, row_qk + q_start,
                          col_qk + kv_start, d_col, d_row, tau_qk,
                          (s + 1.0) * 1.0, corrects)
        scores = scores * scale

        # ---- emulated SEU on the scores accumulator ----------------------
        enable, g_h, g_qi, g_s, g_row, g_col = (
            inj_ref[0], inj_ref[1], inj_ref[2], inj_ref[3], inj_ref[4],
            inj_ref[5])
        hit = ((enable == 1) & (g_h == h) & (g_qi == qi) & (g_s == s))
        # injection lands in the Δ=PV accumulator below (paper §5.3 semantics)

        # Ragged edge masking: padded KV positions (past the true Skv) and
        # padded/dead QUERY rows (past the true Sq) must not receive
        # attention — masked to -inf *after* the linear-GEMM checksum
        # verification above (zero-padded operand rows are checksum-neutral)
        # and *before* softmax, exactly like the causal mask. Dead query
        # rows therefore stay m-degenerate and flush as exact zeros below
        # instead of accumulating exp(0)=1 garbage weights.
        kpos = kv_start + _iota2((bq, bkv), 1)
        scores = jnp.where(kpos < true_skv, scores, NEG_INF)
        scores = jnp.where(_row_mask(q_start, bq, bkv, true_sq), scores,
                           NEG_INF)
        if causal:
            qpos = q_start + _iota2((bq, bkv), 0)
            scores = jnp.where(qpos + c_off >= kpos, scores, NEG_INF)

        m_prev = m_ref[...]                               # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, 1, keepdims=True))
        # m-degenerate rows (every position masked so far — dead ragged
        # rows, empty causal spans) would see exp(−∞ − (−∞)) = 1 here;
        # clamp the exponent and zero their weights so they accumulate
        # nothing.
        good = m_new > 0.5 * NEG_INF                      # (bq, 1)
        p = jnp.exp(jnp.minimum(scores - m_new, 0.0))     # (bq, bkv)
        p = jnp.where(good, p, 0.0)
        alpha = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))  # (bq, 1)

        delta = f32dot(p, v)  # (bq, dh)
        inj_mask = ((_iota2((bq, dh), 0) == g_row)
                    & (_iota2((bq, dh), 1) == g_col) & hit)
        delta = delta + jnp.where(inj_mask, mag_ref[0], 0.0)
        delta = land_seu(delta, s)

        # ---- fused ABFT on the PV GEMM ------------------------------------
        ck_col = f32dot(jnp.sum(p, 0, keepdims=True), v)           # (1, dh)
        ck_row = f32dot(p, jnp.sum(v, 1, keepdims=True))           # (bq, 1)
        d_col = jnp.sum(delta, 0, keepdims=True) - ck_col
        d_row = jnp.sum(delta, 1, keepdims=True) - ck_row
        # Rounding-error accumulation stops at the true Skv: on a ragged
        # edge block only the live kv positions contribute to the p·V
        # reduction, so the threshold must not inflate to the full bkv
        # (same clamp as the masked GEMM template's k_elapsed).
        eff_kv = jnp.minimum(true_skv - kv_start, bkv).astype(jnp.float32)
        tau = jnp.maximum(rel_tau * F32EPS * eff_kv * jnp.max(jnp.abs(v)),
                          1e-30)
        delta, det_pv, mag_pv, row_pv, col_pv = temit._locate_correct_full(
            delta, d_col, d_row, tau, corrects, bq, dh)
        temit._record(rep_ref, det_pv, mag_pv, row_pv + q_start, col_pv,
                      d_col, d_row, tau, eff_kv, corrects)

        acc_ref[...] = acc_ref[...] * alpha + delta
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, 1, keepdims=True)
        m_ref[...] = m_new

    @pl.when(s == kv_steps - 1)
    def _flush():
        # m-degenerate rows (m still −∞: dead ragged rows, empty causal kv
        # spans, or q-blocks whose every kv block was skipped) flush exact
        # zeros — never `garbage_acc / 1e-30` — and their saved statistics
        # are the degenerate markers (m=−∞, l=0) the backward kernels map
        # to p ≡ 0.
        m_fin = m_ref[...]
        l_fin = l_ref[...]
        good = (m_fin > 0.5 * NEG_INF) & (l_fin > 0.0)
        linv = jnp.where(good, 1.0 / jnp.maximum(l_fin, 1e-30), 0.0)
        o_ref[0] = (acc_ref[...] * linv).astype(o_ref.dtype)
        if save_stats:
            m_out_ref[0] = jnp.where(good, m_fin, NEG_INF
                                     ).astype(m_out_ref.dtype)
            l_out_ref[0] = jnp.where(good, l_fin, 0.0
                                     ).astype(l_out_ref.dtype)


# ---------------------------------------------------------------------------
# paged ragged decode kernel (PR 9)
# ---------------------------------------------------------------------------

def _flash_decode_kernel(inj_ref, mag_ref, rng_ref, len_ref, tbl_ref,
                         q_ref, k_ref, v_ref,
                         o_ref, rep_ref, acc_ref, m_ref, l_ref, *,
                         kv_steps: int, kvh: int, bq: int, page: int,
                         dh: int, scale: float, corrects: bool,
                         rel_tau: float, protect_qk: bool,
                         inject_rate: float, bit_shift: int):
    """Single-position paged decode with per-row ragged lengths.

    Grid (n_slots · n_kv_heads, max_pages): one grid row per (serving slot,
    kv head); its stationary q block holds that head's n_rep GQA query rows
    (zero-padded to the sublane-aligned bq — checksum-neutral, sliced off by
    the ops wrapper) at ONE decode position, and the reduction walk streams
    the slot's KV-cache pages. The page table (``tbl_ref``) is consumed by
    the K/V *index maps* — each kv step DMAs exactly the physical page the
    slot's table names, so thousands of slots share one pool with no dense
    padding; the body itself reads only the per-slot true length
    (``len_ref``, the ragged `int32[B]` replacing the forward's one
    (Sq, Skv) pair). Both GEMMs carry the same fused ABFT as the forward:
    S = QKᵀ verified before masking, Δ = PV verified with the τ clamped to
    the row's LIVE kv span (min(true_len − page·s, page)) so detection
    stays exact on ragged rows. Slots with true length 0 (dead slots
    streaming the null page) never execute a step and flush exact zeros via
    the m-degenerate clamp."""
    del tbl_ref                      # routing only — consumed by index maps
    g = pl.program_id(0)
    s = pl.program_id(1)
    slot = g // kvh

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        temit.init_report(rep_ref)

    true_len = len_ref[slot]
    kv_start = s * page
    run = kv_start < true_len

    # One stochastic SEU per (slot, kv head) grid row, step drawn over the
    # slot's LIVE page walk (ceil(len/page)) so the realized rate matches
    # the nominal one across ragged rows.
    n_live = jnp.maximum((true_len + page - 1) // page, 0)
    land_seu = temit.stochastic_hook(rng_ref, SALT_DECODE, g, n_live, bq,
                                     dh, inject_rate, bit_shift)

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)                  # (bq, dh)
        k = k_ref[0, 0].astype(jnp.float32)               # (page, dh)
        v = v_ref[0, 0].astype(jnp.float32)

        scores = f32dot(q, k.T)
        if protect_qk:
            ck_col = f32dot(jnp.sum(q, 0, keepdims=True), k.T)   # (1,page)
            ck_row = f32dot(q, jnp.sum(k.T, 1, keepdims=True))   # (bq, 1)
            d_col = jnp.sum(scores, 0, keepdims=True) - ck_col
            d_row = jnp.sum(scores, 1, keepdims=True) - ck_row
            tau_qk = jnp.maximum(
                rel_tau * F32EPS * dh
                * jnp.max(jnp.abs(q)) * jnp.max(jnp.abs(k)), 1e-30)
            scores, det_qk, mag_qk, row_qk, col_qk = \
                temit._locate_correct_full(scores, d_col, d_row, tau_qk,
                                           corrects, bq, page)
            temit._record(rep_ref, det_qk, mag_qk, row_qk,
                          col_qk + kv_start, d_col, d_row, tau_qk,
                          (s + 1.0) * 1.0, corrects)
        scores = scores * scale

        # ---- emulated SEU (deterministic campaign vector) ----------------
        enable, g_g, g_qi, g_s, g_row, g_col = (
            inj_ref[0], inj_ref[1], inj_ref[2], inj_ref[3], inj_ref[4],
            inj_ref[5])
        hit = ((enable == 1) & (g_g == g) & (g_qi == 0) & (g_s == s))

        # Per-row ragged masking: positions at or past the slot's true
        # length (including every position of a trailing NULL/garbage page)
        # are dead — masked AFTER the linear score verification, like the
        # forward's kv edge. Decode needs no causal term: the query IS
        # position true_len − 1, so the span mask is the causal mask.
        kpos = kv_start + _iota2((bq, page), 1)
        scores = jnp.where(kpos < true_len, scores, NEG_INF)

        m_prev = m_ref[...]                               # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, 1, keepdims=True))
        good = m_new > 0.5 * NEG_INF
        p = jnp.exp(jnp.minimum(scores - m_new, 0.0))     # (bq, page)
        p = jnp.where(good, p, 0.0)
        alpha = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))

        delta = f32dot(p, v)  # (bq,dh)
        inj_mask = ((_iota2((bq, dh), 0) == g_row)
                    & (_iota2((bq, dh), 1) == g_col) & hit)
        delta = delta + jnp.where(inj_mask, mag_ref[0], 0.0)
        delta = land_seu(delta, s)

        # ---- fused ABFT on the PV GEMM -----------------------------------
        ck_col = f32dot(jnp.sum(p, 0, keepdims=True), v)           # (1, dh)
        ck_row = f32dot(p, jnp.sum(v, 1, keepdims=True))           # (bq, 1)
        d_col = jnp.sum(delta, 0, keepdims=True) - ck_col
        d_row = jnp.sum(delta, 1, keepdims=True) - ck_row
        # τ follows the row's live span on the final (partial) page, not
        # the full page width — the ragged-rows-stay-exact clamp.
        eff_kv = jnp.minimum(true_len - kv_start, page).astype(jnp.float32)
        tau = jnp.maximum(rel_tau * F32EPS * eff_kv * jnp.max(jnp.abs(v)),
                          1e-30)
        delta, det_pv, mag_pv, row_pv, col_pv = temit._locate_correct_full(
            delta, d_col, d_row, tau, corrects, bq, dh)
        temit._record(rep_ref, det_pv, mag_pv, row_pv, col_pv,
                      d_col, d_row, tau, eff_kv, corrects)

        acc_ref[...] = acc_ref[...] * alpha + delta
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, 1, keepdims=True)
        m_ref[...] = m_new

    @pl.when(s == kv_steps - 1)
    def _flush():
        m_fin = m_ref[...]
        l_fin = l_ref[...]
        good = (m_fin > 0.5 * NEG_INF) & (l_fin > 0.0)
        linv = jnp.where(good, 1.0 / jnp.maximum(l_fin, 1e-30), 0.0)
        o_ref[0] = (acc_ref[...] * linv).astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# backward kernels — shared per-step softmax/score recompute
# ---------------------------------------------------------------------------

def _recompute_p(q, k, m, linv, *, q_start, kv_start, bq, bkv, true_sq,
                 true_skv, c_off, causal, scale, rel_tau, corrects,
                 protect_qk, rep_ref):
    """Rebuild the (bq, bkv) probability block from the saved statistics:
    p = exp(scale·QKᵀ − m) / l with the kv-edge/causal/dead-row masks of the
    forward. The S = QKᵀ recompute is checksum-verified like the forward's
    (the backward's fifth GEMM). Degenerate rows (l=0 ⇒ linv=0) come out
    exactly zero. Returns (p, scores_scaled, det)."""
    scores = f32dot(q, k.T)
    det = jnp.zeros((), bool)
    if protect_qk:
        ck_col = f32dot(jnp.sum(q, 0, keepdims=True), k.T)
        ck_row = f32dot(q, jnp.sum(k.T, 1, keepdims=True))
        d_col = jnp.sum(scores, 0, keepdims=True) - ck_col
        d_row = jnp.sum(scores, 1, keepdims=True) - ck_row
        tau_qk = jnp.maximum(
            rel_tau * F32EPS * q.shape[1]
            * jnp.max(jnp.abs(q)) * jnp.max(jnp.abs(k)), 1e-30)
        scores, det, mag, row_l, col_l = temit._locate_correct_full(
            scores, d_col, d_row, tau_qk, corrects, bq, bkv)
        temit._record(rep_ref, det, mag, row_l + q_start, col_l + kv_start,
                      d_col, d_row, tau_qk, 1.0, corrects)
    scores = scores * scale
    live = ((kv_start + _iota2((bq, bkv), 1) < true_skv)
            & _row_mask(q_start, bq, bkv, true_sq))
    if causal:
        qpos = q_start + _iota2((bq, bkv), 0)
        live = live & (qpos + c_off >= kv_start + _iota2((bq, bkv), 1))
    # exp is clamped so masked/degenerate entries cannot overflow before
    # they are zeroed (m is the row max over *live* positions only).
    p = jnp.exp(jnp.minimum(scores - m, 0.0)) * linv
    p = jnp.where(live, p, 0.0)
    return p, scores, det


def _verify_dp(dp, g, v, rep_ref, *, bq, bkv, dh, rel_tau, corrects,
               q_start, kv_start):
    """Checksum-verify (and correct) the dP = g·Vᵀ product."""
    ck_col = f32dot(jnp.sum(g, 0, keepdims=True), v.T)           # (1, bkv)
    ck_row = f32dot(g, jnp.sum(v, 0, keepdims=True).T)           # (bq, 1)
    d_col = jnp.sum(dp, 0, keepdims=True) - ck_col
    d_row = jnp.sum(dp, 1, keepdims=True) - ck_row
    tau = jnp.maximum(rel_tau * F32EPS * dh * jnp.max(jnp.abs(g))
                      * jnp.max(jnp.abs(v)), 1e-30)
    dp, det, mag, row_l, col_l = temit._locate_correct_full(
        dp, d_col, d_row, tau, corrects, bq, bkv)
    temit._record(rep_ref, det, mag, row_l + q_start, col_l + kv_start,
                  d_col, d_row, tau, float(dh), corrects)
    return dp


def _verify_delta(delta, a, b, eff, rep_ref, *, row_off, rel_tau, corrects,
                  transpose_a):
    """Checksum-verify (and correct) one accumulator delta of the backward
    GEMMs — the shared Huang–Abraham step for dQ = dS·K
    (``transpose_a=False``: delta = a·b) and dV = Pᵀ·g / dK = dSᵀ·Q
    (``transpose_a=True``: delta = aᵀ·b, contraction over rows, no
    materialized transpose). ``eff`` is the live contraction length driving
    the rounding-aware threshold."""
    if transpose_a:
        ck_col = f32dot(jnp.sum(a, 1, keepdims=True), b,
                        _CONTRACT_ROWS)                          # (1, n)
        ck_row = f32dot(a, jnp.sum(b, 1, keepdims=True),
                        _CONTRACT_ROWS)                          # (m, 1)
    else:
        ck_col = f32dot(jnp.sum(a, 0, keepdims=True), b)
        ck_row = f32dot(a, jnp.sum(b, 1, keepdims=True))
    d_col = jnp.sum(delta, 0, keepdims=True) - ck_col
    d_row = jnp.sum(delta, 1, keepdims=True) - ck_row
    tau = jnp.maximum(rel_tau * F32EPS * eff * jnp.max(jnp.abs(a))
                      * jnp.max(jnp.abs(b)), 1e-30)
    delta, det, mag, row_l, col_l = temit._locate_correct_full(
        delta, d_col, d_row, tau, corrects, *delta.shape)
    temit._record(rep_ref, det, mag, row_l + row_off, col_l, d_col, d_row,
                  tau, eff, corrects)
    return delta


def _live_kv_steps(true_skv, q_start, bq, bkv, c_off, causal: bool):
    """Number of kv steps a q-block actually executes (the ragged kv edge
    and, for causal dispatch, the bottom-right-aligned bound) — the live
    span the stochastic hook draws its step over."""
    kv_hi = true_skv
    if causal:
        kv_hi = jnp.minimum(kv_hi, q_start + bq + c_off)
    return jnp.maximum((kv_hi + bkv - 1) // bkv, 0)


def _flash_dq_kernel(inj_ref, mag_ref, rng_ref, dims_ref,
                     q_ref, k_ref, v_ref, g_ref, m_ref, l_ref, di_ref,
                     dq_ref, rep_ref, acc_ref, *,
                     kv_steps: int, q_blocks: int, bq: int, bkv: int,
                     dh: int, causal: bool, scale: float, corrects: bool,
                     rel_tau: float, protect_qk: bool, inject_rate: float,
                     bit_shift: int):
    """dQ = Σ_kv (P ∘ (g·Vᵀ − di))·scale·K — q-block stationary, kv blocks
    as the reduction walk (the forward's grid transposed onto gradients).
    Both in-step GEMMs (dP and the dQ delta) are verified per kv-step."""
    h = pl.program_id(0)
    qi = pl.program_id(1)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        temit.init_report(rep_ref)

    true_sq = dims_ref[0]
    true_skv = dims_ref[1]
    q_start = qi * bq
    kv_start = s * bkv
    c_off = true_skv - true_sq
    run = (kv_start < true_skv) & (q_start < true_sq)
    if causal:
        run = run & (kv_start <= q_start + bq - 1 + c_off)

    enable, target, g_h, g_blk, g_s, g_row, g_col = (inj_ref[i]
                                                     for i in range(7))
    det_hit = (enable == 1) & (g_h == h) & (g_blk == qi) & (g_s == s)
    n_live = _live_kv_steps(true_skv, q_start, bq, bkv, c_off, causal)
    land_seu = temit.stochastic_hook(
        rng_ref, SALT_DQ, h * q_blocks + qi, n_live, bq, dh, inject_rate,
        bit_shift)

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)                  # (bq, dh)
        k = k_ref[0].astype(jnp.float32)                  # (bkv, dh)
        v = v_ref[0].astype(jnp.float32)
        g = g_ref[0].astype(jnp.float32)                  # (bq, dh)
        m = m_ref[0]                                      # (bq, 1) f32
        l = l_ref[0]
        di = di_ref[0]
        linv = jnp.where(l > 0.0, 1.0 / jnp.maximum(l, 1e-30), 0.0)

        p, _, _ = _recompute_p(
            q, k, m, linv, q_start=q_start, kv_start=kv_start, bq=bq,
            bkv=bkv, true_sq=true_sq, true_skv=true_skv, c_off=c_off,
            causal=causal, scale=scale, rel_tau=rel_tau, corrects=corrects,
            protect_qk=protect_qk, rep_ref=rep_ref)

        dp = f32dot(g, v.T)  # (bq,bkv)
        inj_dp = ((_iota2((bq, bkv), 0) == g_row)
                  & (_iota2((bq, bkv), 1) == g_col)
                  & det_hit & (target == BWD_TARGETS["dp_q"]))
        dp = dp + jnp.where(inj_dp, mag_ref[0], 0.0)
        dp = _verify_dp(dp, g, v, rep_ref, bq=bq, bkv=bkv, dh=dh,
                        rel_tau=rel_tau, corrects=corrects,
                        q_start=q_start, kv_start=kv_start)

        ds = p * (dp - di) * scale                        # (bq, bkv)
        delta = f32dot(ds, k)
        inj_dq = ((_iota2((bq, dh), 0) == g_row)
                  & (_iota2((bq, dh), 1) == g_col)
                  & det_hit & (target == BWD_TARGETS["dq"]))
        delta = delta + jnp.where(inj_dq, mag_ref[0], 0.0)
        delta = land_seu(delta, s)

        eff_kv = jnp.minimum(true_skv - kv_start, bkv).astype(jnp.float32)
        delta = _verify_delta(delta, ds, k, eff_kv, rep_ref,
                              row_off=q_start, rel_tau=rel_tau,
                              corrects=corrects, transpose_a=False)
        acc_ref[...] += delta

    @pl.when(s == kv_steps - 1)
    def _flush():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _flash_dkv_kernel(inj_ref, mag_ref, rng_ref, dims_ref,
                      q_ref, g_ref, m_ref, l_ref, di_ref, k_ref, v_ref,
                      dk_ref, dv_ref, rep_ref, dk_acc, dv_acc, *,
                      q_steps: int, n_rep: int, kv_blocks: int, bq: int,
                      bkv: int, dh: int, causal: bool, scale: float,
                      corrects: bool, rel_tau: float, protect_qk: bool,
                      inject_rate: float, bit_shift: int):
    """dV = Σ_q Pᵀ·g and dK = Σ_q dSᵀ·Q·scale — kv-block stationary. The
    reduction walk covers (n_rep × q-blocks): GQA is served by the same
    query-head index maps as the forward (query head b·n_rep + r reads KV
    head b), so the per-KV-head gradient sums its n_rep query heads without
    repeat-materializing anything. All three in-step GEMMs verified."""
    b = pl.program_id(0)
    kvi = pl.program_id(1)
    r = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when((r == 0) & (qi == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        temit.init_report(rep_ref)

    true_sq = dims_ref[0]
    true_skv = dims_ref[1]
    q_start = qi * bq
    kv_start = kvi * bkv
    c_off = true_skv - true_sq
    run = (kv_start < true_skv) & (q_start < true_sq)
    if causal:
        run = run & (kv_start <= q_start + bq - 1 + c_off)

    h_q = b * n_rep + r                      # the query head of this step
    enable, target, g_h, g_blk, g_s, g_row, g_col = (inj_ref[i]
                                                     for i in range(7))
    det_hit = (enable == 1) & (g_h == h_q) & (g_blk == kvi) & (g_s == qi)
    # Live (r, qi) span of this kv block: q blocks past the true Sq and,
    # for causal dispatch, q blocks wholly above the bottom-right bound
    # never execute — the stochastic step is drawn over the live walk only
    # (uniform realized rate), and compared against the step's live index.
    qi_hi = jnp.minimum((true_sq + bq - 1) // bq, q_steps)
    qi_lo = (jnp.maximum((kv_start - c_off) // bq, 0) if causal
             else jnp.zeros((), jnp.int32))
    span = jnp.maximum(qi_hi - qi_lo, 0)
    n_live = jnp.where(kv_start < true_skv, n_rep * span, 0)
    land_seu = temit.stochastic_hook(
        rng_ref, SALT_DKV, b * kv_blocks + kvi, n_live, bkv, dh,
        inject_rate, bit_shift)
    step_idx = r * span + (qi - qi_lo)

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)                  # (bq, dh)
        g = g_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)                  # (bkv, dh)
        v = v_ref[0].astype(jnp.float32)
        m = m_ref[0]
        l = l_ref[0]
        di = di_ref[0]
        linv = jnp.where(l > 0.0, 1.0 / jnp.maximum(l, 1e-30), 0.0)

        p, _, _ = _recompute_p(
            q, k, m, linv, q_start=q_start, kv_start=kv_start, bq=bq,
            bkv=bkv, true_sq=true_sq, true_skv=true_skv, c_off=c_off,
            causal=causal, scale=scale, rel_tau=rel_tau, corrects=corrects,
            protect_qk=protect_qk, rep_ref=rep_ref)

        dp = f32dot(g, v.T)  # (bq,bkv)
        inj_dp = ((_iota2((bq, bkv), 0) == g_row)
                  & (_iota2((bq, bkv), 1) == g_col)
                  & det_hit & (target == BWD_TARGETS["dp_kv"]))
        dp = dp + jnp.where(inj_dp, mag_ref[0], 0.0)
        dp = _verify_dp(dp, g, v, rep_ref, bq=bq, bkv=bkv, dh=dh,
                        rel_tau=rel_tau, corrects=corrects,
                        q_start=q_start, kv_start=kv_start)

        eff_q = jnp.maximum(
            jnp.minimum(true_sq - q_start, bq), 1).astype(jnp.float32)

        # ---- dV delta: Pᵀ·g ---------------------------------------------
        dv_delta = f32dot(p, g, _CONTRACT_ROWS)
        inj_dv = ((_iota2((bkv, dh), 0) == g_row)
                  & (_iota2((bkv, dh), 1) == g_col)
                  & det_hit & (target == BWD_TARGETS["dv"]))
        dv_delta = dv_delta + jnp.where(inj_dv, mag_ref[0], 0.0)
        dv_delta = land_seu(dv_delta, step_idx)
        dv_delta = _verify_delta(dv_delta, p, g, eff_q, rep_ref,
                                 row_off=kv_start, rel_tau=rel_tau,
                                 corrects=corrects, transpose_a=True)
        dv_acc[...] += dv_delta

        # ---- dK delta: dSᵀ·Q --------------------------------------------
        ds = p * (dp - di) * scale                        # (bq, bkv)
        dk_delta = f32dot(ds, q, _CONTRACT_ROWS)
        inj_dk = ((_iota2((bkv, dh), 0) == g_row)
                  & (_iota2((bkv, dh), 1) == g_col)
                  & det_hit & (target == BWD_TARGETS["dk"]))
        dk_delta = dk_delta + jnp.where(inj_dk, mag_ref[0], 0.0)
        dk_delta = _verify_delta(dk_delta, ds, q, eff_q, rep_ref,
                                 row_off=kv_start, rel_tau=rel_tau,
                                 corrects=corrects, transpose_a=True)
        dk_acc[...] += dk_delta

    @pl.when((r == n_rep - 1) & (qi == q_steps - 1))
    def _flush():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# jit'd entry points (launch construction lives in templates.registry)
# ---------------------------------------------------------------------------

@traced("kernel/flashft/fwd")
@functools.partial(jax.jit, static_argnames=("bq", "bkv", "causal", "ft",
                                             "interpret", "protect_qk",
                                             "scale", "n_rep", "save_stats"))
def flash_ft_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                       inj_idx: jax.Array, inj_mag: jax.Array,
                       dims: Optional[jax.Array] = None,
                       rng: Optional[jax.Array] = None, *,
                       bq: int = 128, bkv: int = 128, causal: bool = True,
                       ft: FTConfig, interpret: bool = False,
                       protect_qk: bool = True, scale: float = None,
                       n_rep: int = 1, save_stats: bool = False):
    """q: (BH, Sq, dh); k, v: (BH/n_rep, Skv, dh); dh lane-aligned (pad to
    128 in the ops wrapper). ``n_rep`` is the GQA query-group width: query
    head h reads KV head h // n_rep straight through the K/V *index maps*,
    so grouped-query attention runs without repeat-materializing the KV
    operands. inj_idx int32[6] = [enable, bh, q_block, kv_step, row, col];
    inj_mag f32[1]; dims int32[2] true (Sq, Skv) for the masked ragged path
    (None → the padded shapes are the true lengths); rng int32[3] =
    [enable, seed0, seed1] drives the in-kernel stochastic SEU hook
    (`encode_rng`; None → disabled). Returns (out (BH, Sq, dh), report) —
    or (out, m, l, report) with ``save_stats`` (the per-row softmax
    statistics (BH, Sq, 1) f32 the dedicated backward consumes)."""
    bh, sq, dh = q.shape
    bkvh, skv, _ = k.shape
    assert bh == bkvh * n_rep, (q.shape, k.shape, n_rep)
    assert sq % bq == 0 and skv % bkv == 0, (q.shape, k.shape, bq, bkv)
    if dims is None:
        dims = jnp.array([sq, skv], jnp.int32)
    if rng is None:
        rng = jnp.zeros((3,), jnp.int32)
    # dh here may be the 128-padded width; callers pass the true-dh scale
    scale = scale if scale is not None else dh ** -0.5
    return tregistry.flash_fwd_call(
        q, k, v, inj_idx, inj_mag, rng, dims, bq=bq, bkv=bkv, causal=causal,
        ft=ft, interpret=interpret, protect_qk=protect_qk, scale=scale,
        n_rep=n_rep, save_stats=save_stats)


@traced("kernel/flashft/decode")
@functools.partial(jax.jit, static_argnames=("kvh", "ft", "interpret",
                                             "protect_qk", "scale"))
def flash_ft_decode_attention(q: jax.Array, k_pages: jax.Array,
                              v_pages: jax.Array, inj_idx: jax.Array,
                              inj_mag: jax.Array, lengths: jax.Array,
                              page_table: jax.Array,
                              rng: Optional[jax.Array] = None, *,
                              kvh: int, ft: FTConfig,
                              interpret: bool = False,
                              protect_qk: bool = True,
                              scale: float = None):
    """Paged ragged decode: q (B·kvh, bq, dh) — one stationary block per
    (slot, kv head) holding the head's n_rep GQA query rows at the slot's
    current position; k_pages/v_pages (n_pages, kvh, page, dh) — ONE
    layer's shared page pool; lengths int32[B] per-slot true kv lengths
    (the ragged vector; 0 = dead slot → exact-zero output); page_table
    int32[B, max_pages] physical page ids (NULL-padded), scalar-prefetched
    into the K/V index maps. inj_idx int32[6] = [enable, g, 0, kv_step,
    row, col] with g = slot·kvh + head (`encode_injection(spec, bh=g)`);
    rng int32[3] the stochastic hook (`encode_rng`). Returns
    (out (B·kvh, bq, dh), report (B·kvh, 1, W))."""
    g_rows, bq, dh = q.shape
    n_pages, kvh_p, page, dh_k = k_pages.shape
    assert kvh_p == kvh and dh_k == dh, (k_pages.shape, kvh, dh)
    assert g_rows == page_table.shape[0] * kvh, (q.shape, page_table.shape,
                                                 kvh)
    assert lengths.shape == (page_table.shape[0],), (lengths.shape,
                                                     page_table.shape)
    if rng is None:
        rng = jnp.zeros((3,), jnp.int32)
    scale = scale if scale is not None else dh ** -0.5
    return tregistry.flash_decode_call(
        q, k_pages, v_pages, inj_idx, inj_mag, rng, lengths, page_table,
        kvh=kvh, ft=ft, interpret=interpret, protect_qk=protect_qk,
        scale=scale)


@traced("kernel/flashft/dq")
@functools.partial(jax.jit, static_argnames=("bq", "bkv", "causal", "ft",
                                             "interpret", "protect_qk",
                                             "scale", "n_rep"))
def flash_ft_dq(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                m: jax.Array, l: jax.Array, di: jax.Array,
                inj_idx: jax.Array, inj_mag: jax.Array, dims: jax.Array,
                rng: Optional[jax.Array] = None, *,
                bq: int = 128, bkv: int = 128, causal: bool = True,
                ft: FTConfig, interpret: bool = False,
                protect_qk: bool = True, scale: float = None,
                n_rep: int = 1):
    """The dQ half of the dedicated flash backward: ONE Pallas launch over
    the saved (m, l) statistics and the precomputed di = rowsum(g ∘ o) —
    zero chunked-oracle recompute, no S×S transient. Operands padded to the
    (bq, bkv)-fitted grid by the ops wrapper; m/l/di are (BH, Sq, 1) f32
    with degenerate rows marked (m=−∞, l=0). inj_idx is the int32[7]
    deterministic-SEU vector (`encode_bwd_injection`). Returns (dq, rep)."""
    bh, sq, dh = q.shape
    assert bh == k.shape[0] * n_rep, (q.shape, k.shape, n_rep)
    assert sq % bq == 0 and k.shape[1] % bkv == 0, (q.shape, k.shape, bq,
                                                    bkv)
    if rng is None:
        rng = jnp.zeros((3,), jnp.int32)
    scale = scale if scale is not None else dh ** -0.5
    return tregistry.flash_dq_call(
        q, k, v, g, m, l, di, inj_idx, inj_mag, rng, dims, bq=bq, bkv=bkv,
        causal=causal, ft=ft, interpret=interpret, protect_qk=protect_qk,
        scale=scale, n_rep=n_rep)


@traced("kernel/flashft/dkv")
@functools.partial(jax.jit, static_argnames=("bq", "bkv", "causal", "ft",
                                             "interpret", "protect_qk",
                                             "scale", "n_rep"))
def flash_ft_dkv(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                 m: jax.Array, l: jax.Array, di: jax.Array,
                 inj_idx: jax.Array, inj_mag: jax.Array, dims: jax.Array,
                 rng: Optional[jax.Array] = None, *,
                 bq: int = 128, bkv: int = 128, causal: bool = True,
                 ft: FTConfig, interpret: bool = False,
                 protect_qk: bool = True, scale: float = None,
                 n_rep: int = 1):
    """The dK/dV half of the dedicated flash backward: ONE kv-stationary
    Pallas launch whose reduction walk covers the n_rep GQA query heads ×
    q blocks of each KV head (same K/V index maps as the forward — nothing
    repeat-materialized). Returns (dk, dv, rep) per KV head."""
    bh, sq, dh = q.shape
    assert bh == k.shape[0] * n_rep, (q.shape, k.shape, n_rep)
    assert sq % bq == 0 and k.shape[1] % bkv == 0, (q.shape, k.shape, bq,
                                                    bkv)
    if rng is None:
        rng = jnp.zeros((3,), jnp.int32)
    scale = scale if scale is not None else dh ** -0.5
    return tregistry.flash_dkv_call(
        q, k, v, g, m, l, di, inj_idx, inj_mag, rng, dims, bq=bq, bkv=bkv,
        causal=causal, ft=ft, interpret=interpret, protect_qk=protect_qk,
        scale=scale, n_rep=n_rep)


# ---------------------------------------------------------------------------
# injection encoders
# ---------------------------------------------------------------------------

def encode_injection(spec: Optional[InjectionSpec], bh: int = 0,
                     q_block: int = 0):
    if spec is None:
        return (jnp.zeros((6,), jnp.int32), jnp.zeros((1,), jnp.float32))
    idx = jnp.array([1, bh, q_block, spec.k_step, spec.row, spec.col],
                    jnp.int32)
    return idx, jnp.array([spec.magnitude], jnp.float32)


def encode_bwd_injection(spec: Optional[InjectionSpec], target: str = "dq",
                         bh: int = 0, blk: int = 0):
    """Deterministic SEU vectors for the backward kernels. ``target`` names
    the backward GEMM the SEU lands in — "dp_q"/"dq" (dq kernel; ``blk`` is
    the q-block, ``spec.k_step`` the kv step) or "dp_kv"/"dv"/"dk" (dkv
    kernel; ``blk`` is the kv block, ``spec.k_step`` the q step; ``bh`` is
    always the QUERY head). Returns (inj_dq int32[7], inj_dkv int32[7],
    mag f32[1]) with only the targeted kernel's vector enabled."""
    zero = jnp.zeros((7,), jnp.int32)
    if spec is None:
        return zero, zero, jnp.zeros((1,), jnp.float32)
    if target not in BWD_TARGETS:
        raise ValueError(f"unknown backward injection target {target!r}; "
                         f"one of {tuple(BWD_TARGETS)}")
    vec = jnp.array([1, BWD_TARGETS[target], bh, blk, spec.k_step,
                     spec.row, spec.col], jnp.int32)
    mag = jnp.array([spec.magnitude], jnp.float32)
    if target in _DQ_KERNEL_TARGETS:
        return vec, zero, mag
    return zero, vec, mag


def encode_rng(key: Optional[jax.Array], ft: FTConfig) -> jax.Array:
    """int32[3] = [enable, seed0, seed1] for the in-kernel stochastic SEU
    hook — seeds derived from the campaign key; disabled (zeros) when no
    key is supplied or the policy's inject_rate is 0."""
    if key is None or ft.inject_rate <= 0.0:
        return jnp.zeros((3,), jnp.int32)
    seeds = jax.random.randint(key, (2,), 0, jnp.iinfo(jnp.int32).max,
                               dtype=jnp.int32)
    return jnp.concatenate([jnp.ones((1,), jnp.int32), seeds])
