"""Shared model building blocks. Every GEMM routes through repro.core.ft_dot
/ ft_batched_dot so the paper's online ABFT protects the full model.

Conventions:
  * params are nested dicts of jnp arrays (pure-functional modules);
  * `Ctx` carries the FT policy + per-step injection key + compute dtype;
    call sites derive deterministic sub-keys from their name (crc32) so an
    injection campaign exercises every GEMM in the model;
  * training/prefill attention: on the pallas FT backend the core runs the
    `kernels.flashft` ragged-causal kernel (PR 4) — ONE Pallas launch with
    both in-kernel GEMMs ABFT-protected, no O(chunk × S) score transient in
    the forward, GQA served through the K/V index maps (KV never
    repeat-materialized). Since PR 5 the backward is first-class too: the
    forward saves the per-row (m, l) softmax statistics and the backward
    runs the dedicated dQ and dK/dV flash kernels (four ABFT-protected
    backward GEMMs, zero chunked-oracle recompute); stochastic
    `ft.inject_rate` campaigns ride the in-kernel SEU hook in both
    directions. Elsewhere (and under ``Ctx.attn_impl="chunked"``) the
    flash-style query-chunked scan runs end to end — O(chunk × S) transient
    memory, never materializing S×S, in both directions. Required for the
    32k prefill shapes.
"""
from __future__ import annotations

import dataclasses
import functools
import zlib
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import ft_dot, ft_dot_fused, ft_batched_dot, telemetry
from repro.core import loops
from repro.core.ft_gemm import _float0
from repro.core.policy import (FTConfig, FTLike, FT_OFF, note_site,
                               resolve_ft)


def named_subkey(key: Optional[jax.Array], name: str) -> Optional[jax.Array]:
    """THE per-call-site key derivation (crc32 of the site name) — shared
    by `Ctx.subkey` and the ctx-free attention cores so every GEMM of an
    injection campaign sees the same deterministic sub-key either way."""
    if key is None:
        return None
    return jax.random.fold_in(key, zlib.crc32(name.encode()))


@dataclasses.dataclass(frozen=True)
class Ctx:
    """Per-call context: FT policy, injection key, activation dtype,
    attention sharding scheme ("heads" = Megatron-SP head-TP inside the
    attention core with seq gathered per layer; "none" = leave placement to
    GSPMD propagation — a §Perf comparison axis).

    ``attn_impl`` selects the training/prefill attention core: "auto"
    (default — the flashft kernel when the FT backend is pallas and the
    geometry is eligible, the chunked scan otherwise), "flash" (force the
    kernel), or "chunked" (force the query-chunked jnp path — the oracle
    the flash path is validated against).

    ``inject_sites`` restricts the stochastic SEU campaign to the named
    telemetry sites: `subkey` returns None (⇒ no injection) for every other
    site, so a campaign can target e.g. one MoE expert GEMM and the per-site
    report must attribute every detection to exactly that site. The site
    *names* are the same labels `dot`/`dot_fused`/`bdot` record telemetry
    under ("wq", "w_gate", "attn_qk", …; the flash kernel is one fused site,
    "attn_flash"). None (default) = campaign covers every GEMM. Call
    `check_inject_sites` once per traced forward to fail loudly on labels
    the registry never saw (a filter that silently matches nothing would
    report a clean run AS the campaign result).

    ``ft`` is either a plain `FTConfig` (uniform — legacy behavior,
    bit-identical) or an `FTPolicy` (PR 10): every GEMM resolves its own
    site label through `ft_for`, so one model trace can mix e.g.
    correct/step on `moe_*` with detect/final on `attn_*` and off on the
    rest."""
    ft: FTLike = FT_OFF
    key: Optional[jax.Array] = None
    dtype: Any = jnp.bfloat16
    attn_shard: str = "heads"
    attn_impl: str = "auto"
    inject_sites: Optional[Tuple[str, ...]] = None

    def ft_for(self, name: Optional[str]) -> FTConfig:
        """THE per-site resolution point on the model side: the site's
        `FTConfig` under this context's policy (identity for a bare
        FTConfig)."""
        return resolve_ft(self.ft, name)

    def site_allowed(self, name: str) -> bool:
        return self.inject_sites is None or name in self.inject_sites

    def check_inject_sites(self) -> None:
        """Validate ``inject_sites`` against the telemetry site registry —
        call at the END of a traced forward (every site has registered by
        then) and raise on labels no GEMM records under, instead of a
        campaign that silently injects nothing (the PR-5 out-of-grid
        failure mode, at the filter layer)."""
        if self.inject_sites is None:
            return
        known = set(telemetry.site_labels())
        unknown = sorted(set(self.inject_sites) - known)
        if unknown:
            raise ValueError(
                f"Ctx.inject_sites names unknown telemetry sites "
                f"{unknown}: no GEMM in this model records under them, so "
                f"the campaign would inject nothing. Known sites: "
                f"{sorted(known)}")

    def subkey(self, name: str) -> Optional[jax.Array]:
        if not self.site_allowed(name):
            return None
        return named_subkey(self.key, name)

    def dot(self, name: str, x: jax.Array, w: jax.Array) -> jax.Array:
        return ft_dot(x, w, ft=self.ft_for(name), key=self.subkey(name),
                      site=name)

    def dot_fused(self, name: str, x: jax.Array, w: jax.Array,
                  bias: Optional[jax.Array] = None,
                  act: Optional[str] = None) -> jax.Array:
        """Projection with a fused epilogue spec: y = act(x @ w + bias) as
        one kernel-level op (no separate bias/activation passes — see
        repro.core.ft_dot_fused / the kernels.templates subsystem)."""
        return ft_dot_fused(x, w, bias=bias, act=act, ft=self.ft_for(name),
                            key=self.subkey(name), site=name)

    def bdot(self, name: str, a: jax.Array, b: jax.Array) -> jax.Array:
        ft = self.ft_for(name)
        ft = ft if ft.protect_attention else FT_OFF
        return ft_batched_dot(a, b, ft=ft, key=self.subkey(name), site=name)

    def fold(self, tag: int) -> "Ctx":
        if self.key is None:
            return self
        return dataclasses.replace(self, key=jax.random.fold_in(self.key, tag))


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def make_remat(fn, remat):
    """Remat-policy dispatch (a §Perf lever):
      False/"none" — no remat (saves everything, max memory, min recompute)
      True/"full"  — jax.checkpoint default (saves inputs only)
      "dots"       — save GEMM outputs, recompute elementwise only
                     (jax.checkpoint_policies.checkpoint_dots…): trades
                     activation memory for ~⅓ less recompute FLOPs."""
    if not remat or remat == "none":
        return fn
    if remat == "dots":
        return jax.checkpoint(
            fn,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return jax.checkpoint(fn)


def dense_init(key, d_in: int, d_out: int, dtype, scale: float = 0.02):
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale
            ).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype, scale: float = 0.02):
    return (jax.random.normal(key, (vocab, d), jnp.float32) * scale
            ).astype(dtype)


# ---------------------------------------------------------------------------
# normalization / rope
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(var + eps)) * w.astype(jnp.float32)
            ).astype(x.dtype)


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, dh); positions: (S,) or (B, S)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)                       # (dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (…, S, dh/2)
    if angles.ndim == 2:                                # (S, dh/2) → (1,S,1,·)
        angles = angles[None, :, None, :]
    else:                                               # (B,S,dh/2) → (B,S,1,·)
        angles = angles[:, :, None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg, dtype) -> Dict[str, Any]:
    d = cfg.d_model
    qd, kvd = cfg.qkv_dims
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, qd, dtype),
        "wk": dense_init(ks[1], d, kvd, dtype),
        "wv": dense_init(ks[2], d, kvd, dtype),
        "wo": dense_init(ks[3], qd, d, dtype, scale=0.02 / (2 * cfg.n_layers) ** 0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((qd,), dtype)
        p["bk"] = jnp.zeros((kvd,), dtype)
        p["bv"] = jnp.zeros((kvd,), dtype)
    return p


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return k
    b, s, h, dh = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, dh)
                            ).reshape(b, s, h * n_rep, dh)


def _chunked_core(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool, chunk: int, ft: FTConfig,
                  key: Optional[jax.Array],
                  q_offset: int = 0,
                  inject_sites: Optional[Tuple[str, ...]] = None
                  ) -> Tuple[jax.Array, telemetry.FTReport]:
    """The query-chunked jnp attention core. q: (B,Sq,H,dh); k,v:
    (B,Sk,KVH,dh) → ((B,Sq,H,dh), FTReport). Never materializes (Sq, Sk)
    scores — per chunk only — and GQA is computed as a *grouped* batched
    matmul over (B, KVH) with the rep·chunk rows folded together: KV is
    never repeat-materialized (the v0 baseline paid n_rep× KV bytes;
    §Perf). This is BOTH the oracle the flashft path is validated against
    and the recompute body of the flash custom_vjp's backward — its GEMMs
    ride `ft_batched_dot`, so the attention backward stays ABFT-protected
    on every backend."""
    b, sq, h, dh = q.shape
    _, sk, kvh, _ = k.shape
    n_rep = h // kvh
    scale = dh ** -0.5
    kT = jnp.swapaxes(k, 1, 2).swapaxes(2, 3)           # (B, KVH, dh, Sk)
    vT = jnp.swapaxes(v, 1, 2)                          # (B, KVH, Sk, dh)
    kpos = jnp.arange(sk)

    def subkey(name: str) -> Optional[jax.Array]:
        if inject_sites is not None and name not in inject_sites:
            return None
        return named_subkey(key, name)

    def chunk_fn(qc: jax.Array, qpos: jax.Array):
        # qc: (B, C, H, dh) → grouped scores (B, KVH, rep·C, Sk). FT records
        # are scoped inside the checkpointed body and re-emitted at the
        # caller's trace level (telemetry can't cross remat/scan as a side
        # channel).
        def inner():
            c = qc.shape[1]
            # (B, C, KVH, rep, dh) → (B, KVH, rep·C, dh)
            qg = qc.reshape(b, c, kvh, n_rep, dh).transpose(0, 2, 3, 1, 4)
            qg = qg.reshape(b, kvh, n_rep * c, dh)
            scores = ft_batched_dot(qg, kT, ft=ft, key=subkey("attn_qk"),
                                    site="attn_qk"
                                    ).astype(jnp.float32) * scale
            if causal:
                mask = qpos[:, None] >= kpos[None, :]   # (C, Sk)
                maskg = jnp.tile(mask, (n_rep, 1))      # (rep·C, Sk)
                scores = jnp.where(maskg[None, None], scores, -1e30)
            p = jax.nn.softmax(scores, axis=-1).astype(qc.dtype)
            out = ft_batched_dot(p, vT, ft=ft, key=subkey("attn_pv"),
                                 site="attn_pv")
            out = out.reshape(b, kvh, n_rep, c, dh).transpose(0, 3, 1, 2, 4)
            return out.reshape(b, c, h, dh)             # (B, C, H, dh)
        return telemetry.scoped(inner)

    chunk_fn = jax.checkpoint(chunk_fn)
    chunk = min(chunk, sq)
    if sq % chunk != 0:
        chunk = sq  # ragged smoke shapes — single chunk
    n_chunks = sq // chunk
    if n_chunks == 1:
        return chunk_fn(q, q_offset + jnp.arange(sq))

    qs = q.reshape(b, n_chunks, chunk, h, dh).swapaxes(0, 1)
    pos = (q_offset + jnp.arange(sq)).reshape(n_chunks, chunk)

    def body(rep, qp):
        qc, qpos = qp
        out, rep_c = chunk_fn(qc, qpos)
        return rep.merge(rep_c), out

    rep, outs = loops.scan(body, telemetry.FTReport.empty(), (qs, pos))
    return outs.swapaxes(0, 1).reshape(b, sq, h, dh), rep


# ---------------------------------------------------------------------------
# flashft-routed training attention (PR 4; dedicated kernel backward PR 5)
# ---------------------------------------------------------------------------

#: Trace-time switch (PR 5): True — the flash custom_vjp's backward runs the
#: dedicated dQ/dK/dV Pallas kernels over the forward-saved (m, l) softmax
#: statistics (zero chunked-oracle recompute, all four backward GEMMs under
#: in-kernel ABFT). False — the legacy PR-4 path: the backward recomputes
#: through the chunked-jnp oracle (protected batched kernels, but an
#: O(chunk·S) transient and one extra softmax pass). Kept for the
#: before/after benchmark and as an escape hatch.
FLASH_BWD_USE_KERNEL = True


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _flash_attn_cvjp(ft: FTConfig, causal, chunk, q_offset, q3, k3, v3, key):
    """Flash-kernel attention over head-major 3-D operands: q3 (B·H, Sq,
    dh); k3, v3 (B·KVH, Sk, dh). Forward = ONE `kernels.flashft` launch
    (both in-kernel GEMMs ABFT-protected per kv-step, GQA via the K/V index
    maps, no score transient); backward = the dedicated dQ and dK/dV flash
    kernels over the saved (m, l) statistics — no oracle recompute (see
    `FLASH_BWD_USE_KERNEL`). ``key`` drives the in-kernel stochastic SEU
    hook when ``ft.inject_rate > 0`` — campaigns stay on the kernel path in
    BOTH directions. Returns (out3, det, maxres)."""
    from repro.kernels import ops as kops
    n_rep = q3.shape[0] // k3.shape[0]
    out, rep = kops.flash_ft(q3, k3, v3, ft=ft, causal=causal, n_rep=n_rep,
                             key=key)
    det = jnp.sum(rep[..., 0]).astype(jnp.int32)
    maxres = jnp.max(rep[..., 5])
    return out, det, maxres


def _flash_attn_fwd(ft, causal, chunk, q_offset, q3, k3, v3, key):
    from repro.kernels import ops as kops
    n_rep = q3.shape[0] // k3.shape[0]
    if not FLASH_BWD_USE_KERNEL:
        out = _flash_attn_cvjp(ft, causal, chunk, q_offset, q3, k3, v3, key)
        return out, (q3, k3, v3, None, None, None, key)
    # Multi-output forward: the kernel additionally writes the per-row
    # softmax statistics (m, l) — the saved residual that lets the backward
    # run as dedicated kernels instead of recomputing the whole forward.
    out, m, l, rep = kops.flash_ft(q3, k3, v3, ft=ft, causal=causal,
                                   n_rep=n_rep, save_stats=True, key=key)
    det = jnp.sum(rep[..., 0]).astype(jnp.int32)
    maxres = jnp.max(rep[..., 5])
    return (out, det, maxres), (q3, k3, v3, out, m, l, key)


def _flash_attn_bwd(ft, causal, chunk, q_offset, res, cts):
    g3, _, _ = cts                     # ignore summary cotangents
    q3, k3, v3, o3, m, l, key = res
    bh, sq, dh = q3.shape
    bkvh, sk, _ = k3.shape
    n_rep = bh // bkvh
    if m is not None:
        # Dedicated flash backward (PR 5): TWO Pallas launches (dQ; dK/dV)
        # over the saved statistics + the elementwise di = rowsum(g ∘ o).
        # All four backward GEMMs (dP, dV, dQ, dK) and the in-kernel S
        # recompute carry the forward's checksum-verify + branchless
        # correction; the stochastic campaign key is folded so the backward
        # draws its own SEU stream.
        from repro.kernels import ops as kops
        kb = jax.random.fold_in(key, 0x5B) if key is not None else None
        dq, dk, dv, _, _ = kops.flash_ft_bwd(
            q3, k3, v3, o3, m, l, g3.astype(q3.dtype), ft=ft, causal=causal,
            n_rep=n_rep, key=kb)
        return dq, dk.astype(k3.dtype), dv.astype(v3.dtype), _float0(key)
    # Legacy (FLASH_BWD_USE_KERNEL=False): recompute through the chunked
    # oracle. Fold the GQA repetition into the head axis of a (B'=B·KVH,
    # H'=n_rep, KVH'=1) problem — row (b·KVH + kv)·n_rep + r of q3 is
    # exactly head r of batch b·KVH + kv, so the chunked oracle reproduces
    # the kernel's head→kv-head mapping and its vjp transposes it.
    q4 = q3.reshape(bkvh, n_rep, sq, dh).transpose(0, 2, 1, 3)
    k4 = k3[:, :, None, :]
    v4 = v3[:, :, None, :]

    def ref(q4, k4, v4):
        return _chunked_core(q4, k4, v4, causal=causal, chunk=chunk, ft=ft,
                             key=key, q_offset=q_offset)[0]

    _, vjp = jax.vjp(ref, q4, k4, v4)
    g4 = g3.reshape(bkvh, n_rep, sq, dh).transpose(0, 2, 1, 3)
    dq4, dk4, dv4 = vjp(g4.astype(q3.dtype))
    dq3 = dq4.transpose(0, 2, 1, 3).reshape(bh, sq, dh)
    return dq3, dk4[:, :, 0, :], dv4[:, :, 0, :], _float0(key)


_flash_attn_cvjp.defvjp(_flash_attn_fwd, _flash_attn_bwd)


def _flash_attention(q, k, v, *, causal, chunk, ft, key, q_offset):
    """4-D front: (B,Sq,H,dh) × (B,Sk,KVH,dh) → (B,Sq,H,dh) through the
    flashft kernel, recording the FT summary at the caller's trace level
    (outside the custom_vjp boundary, like ft_dot — exactly once per call,
    even when the call is differentiated; backward-pass corrections are
    applied but not counted, per DESIGN.md)."""
    if ft.inject_rate > 0.0 and key is not None:
        from repro.kernels import flashft as _flashft
        if not _flashft.SUPPORTS_STOCHASTIC_INJECTION:
            # A fault campaign whose injections silently do not happen is
            # worse than a crash: it reports a clean run AS the campaign
            # result (the MPGemmFI injector/kernel-disagreement pitfall).
            raise ValueError(
                "flash attention cannot honor the stochastic injection key "
                f"(ft.inject_rate={ft.inject_rate}): this build's flashft "
                "kernels lack the in-kernel SEU hook. Use "
                "attn_impl='chunked' for the campaign instead of letting a "
                "forced flash path report a clean run.")
    b, sq, h, dh = q.shape
    _, sk, kvh, _ = k.shape
    note_site("attn_flash", "flash", sq, sk, dh, batch=b * h,
              in_bytes=jnp.dtype(q.dtype).itemsize)
    q3 = q.transpose(0, 2, 1, 3).reshape(b * h, sq, dh)
    k3 = k.transpose(0, 2, 1, 3).reshape(b * kvh, sk, dh)
    v3 = v.transpose(0, 2, 1, 3).reshape(b * kvh, sk, dh)
    out3, det, maxres = _flash_attn_cvjp(ft, causal, chunk, q_offset,
                                         q3, k3, v3, key)
    scope = telemetry.current_scope()
    if scope is not None:
        # One fused site: the kernel verifies both in-kernel GEMMs under a
        # single report, so qk/pv are not separable here.
        scope.record_summary(det, maxres, ft.corrects, site="attn_flash")
    return out3.reshape(b, h, sq, dh).transpose(0, 2, 1, 3)


def _use_flash(ctx: Ctx, ft: FTConfig, causal: bool, sq: int, sk: int,
               q_offset: int) -> bool:
    """Resolve the attention core for this call site (see `Ctx.attn_impl`).
    The flash kernel's causal mask is bottom-right aligned on the true
    lengths, so causal dispatch needs q_offset ≡ Sk − Sq (the self-attention
    q_offset=0, Sq=Sk case and the decode convention both satisfy it)."""
    if ctx.attn_impl == "chunked":
        return False
    geometry_ok = not causal or (sk >= sq and sk - sq == q_offset)
    if ctx.attn_impl == "flash":
        if not geometry_ok:
            raise ValueError(
                f"attn_impl='flash' needs bottom-right-aligned causal "
                f"geometry (q_offset == Sk - Sq), got Sq={sq}, Sk={sk}, "
                f"q_offset={q_offset}")
        return True
    # auto: the kernel carries the FT policy in-kernel — including the
    # stochastic SEU hook (PR 5), so key-driven `inject_rate` campaigns
    # stay on the kernel path in both directions instead of falling back
    # to the jnp oracle.
    return ft.enabled and ft.backend == "pallas" and geometry_ok


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool, chunk: int, ctx: Ctx,
                      q_offset: int = 0) -> jax.Array:
    """Training/prefill attention core. q: (B,Sq,H,dh); k,v: (B,Sk,KVH,dh).

    On the pallas FT backend (or ``ctx.attn_impl="flash"``) this routes to
    the `kernels.flashft` ragged-causal kernel: one Pallas launch, both
    in-kernel GEMMs ABFT-protected, GQA via K/V index maps, and no
    O(chunk·Sk) score transient in the forward; the backward runs the
    dedicated dQ/dK/dV flash kernels over the forward-saved (m, l)
    statistics — four ABFT-protected backward GEMMs, zero oracle
    recompute. Otherwise (and under ``ctx.attn_impl="chunked"``) the
    query-chunked jnp scan runs both directions — kept as the oracle."""
    if ctx.attn_shard == "heads":
        # Megatron-SP: seq gathered, heads TP-sharded through the core
        # (GSPMD pads when head count ∤ mesh — measured in §Roofline's
        # useful ratio); o-proj reduce-scatters back to seq sharding.
        from repro.distributed.sharding import shard as _shard
        q = _shard(q, "batch", None, "heads", None)
        k = _shard(k, "batch", None, "kv_heads", None)
        v = _shard(v, "batch", None, "kv_heads", None)
    # Per-site resolution: the flash kernel is one fused site
    # ("attn_flash"); the chunked oracle's qk/pv pair shares one resolution
    # keyed on "attn_qk" (one kernel family, one level — the two GEMMs are
    # not separable on the flash path either).
    fft = ctx.ft_for("attn_flash")
    fft = fft if fft.protect_attention else FT_OFF
    if _use_flash(ctx, fft, causal, q.shape[1], k.shape[1], q_offset):
        # Targeted campaigns: the flash kernel is one fused injection site.
        fkey = ctx.key if ctx.site_allowed("attn_flash") else None
        return _flash_attention(q, k, v, causal=causal, chunk=chunk, ft=fft,
                                key=fkey, q_offset=q_offset)
    cft = ctx.ft_for("attn_qk")
    cft = cft if cft.protect_attention else FT_OFF
    out, rep = _chunked_core(q, k, v, causal=causal, chunk=chunk, ft=cft,
                             key=ctx.key, q_offset=q_offset,
                             inject_sites=ctx.inject_sites)
    telemetry.record_report(rep)
    return out


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     length: jax.Array, ctx: Ctx, *,
                     site_prefix: str = "dec") -> jax.Array:
    """Single-position attention against a (B, Smax, KVH, dh) cache.
    Positions ≥ length are masked. q: (B, 1, H, dh). GQA is grouped — the
    cache is never repeat-materialized.

    ``site_prefix`` labels the two grouped cache GEMMs in the telemetry
    registry (``{prefix}_qk`` / ``{prefix}_pv``): "dec" for decoder
    self-attention, "xdec" for whisper's cross-attention over the cached
    encoder KV, "dec_page" for the paged-cache fallback — so the planner
    prices each decode population separately instead of one aggregate."""
    b, _, h, dh = q.shape
    s, kvh = k_cache.shape[1], k_cache.shape[2]
    n_rep = h // kvh
    qg = q.reshape(b, kvh, n_rep, dh)                    # (B, KVH, rep, dh)
    kT = jnp.swapaxes(k_cache, 1, 2).swapaxes(2, 3)      # (B, KVH, dh, S)
    scores = ctx.bdot(f"{site_prefix}_qk", qg, kT
                      ).astype(jnp.float32) * dh ** -0.5
    mask = jnp.arange(s)[None, :] < length[:, None]      # (B, S)
    scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = ctx.bdot(f"{site_prefix}_pv", p, jnp.swapaxes(v_cache, 1, 2))
    return out.reshape(b, 1, h, dh)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, lengths: jax.Array,
                           page_table: jax.Array, layer, ctx: Ctx
                           ) -> jax.Array:
    """Single-position attention against layer ``layer`` of a *paged* KV
    cache (train/kv_cache.py). q: (B, 1, H, dh); k_pages, v_pages: (L, P,
    KVH, page, dh) stacked page pools; lengths: int32 (B,) true kv lengths;
    page_table: int32 (B, max_pages) pool-page ids per slot (NULL-padded);
    layer: int or int32 scalar.

    On the pallas FT backend this is ONE `kernels.flashft` decode launch:
    the page table and the layer are scalar-prefetched and consumed by the
    K/V index maps (each grid step streams exactly one pool page — no dense
    gather, no padding traffic, no copy of the layer), the per-slot ragged
    lengths ride a prefetched int32 vector, and both in-kernel GEMMs carry
    the checksum verify with the kv-span clamp folded into the PV
    tolerance. Recorded as one fused telemetry site, "dec_flash".
    Elsewhere (and under ``ctx.attn_impl="chunked"``) the layer's pages
    are gathered back to the dense (B, S, KVH, dh) layout and
    `decode_attention` runs as the oracle,
    recording under its own "dec_page_qk"/"dec_page_pv" labels (the paged
    cache GEMMs are a different population than the dense decode path —
    the planner prices them separately)."""
    b, _, h, dh = q.shape
    ft = ctx.ft_for("dec_flash")
    ft = ft if ft.protect_attention else FT_OFF
    use_kernel = (ctx.attn_impl != "chunked" and dh % 128 == 0
                  and (ctx.attn_impl == "flash"
                       or (ft.enabled and ft.backend == "pallas")))
    if use_kernel:
        from repro.kernels import ops as kops
        kvh = k_pages.shape[2]
        note_site("dec_flash", "flash", h // kvh,
                  page_table.shape[1] * k_pages.shape[3], dh,
                  batch=b * kvh, in_bytes=jnp.dtype(q.dtype).itemsize)
        fkey = ctx.key if ctx.site_allowed("dec_flash") else None
        out, rep = kops.flash_ft_decode(q[:, 0], k_pages, v_pages, lengths,
                                        page_table, layer, ft=ft, key=fkey)
        scope = telemetry.current_scope()
        if scope is not None:
            det = jnp.sum(rep[..., 0]).astype(jnp.int32)
            maxres = jnp.max(rep[..., 5])
            scope.record_summary(det, maxres, ft.corrects, site="dec_flash")
        return out[:, None]
    from repro.train import kv_cache as _kvc
    kd = _kvc.gather_layer(k_pages[layer], page_table)
    vd = _kvc.gather_layer(v_pages[layer], page_table)
    return decode_attention(q, kd, vd, lengths, ctx, site_prefix="dec_page")


def attention(p: Dict[str, Any], x: jax.Array, cfg, ctx: Ctx, *,
              causal: bool = True, positions: Optional[jax.Array] = None,
              kv: Optional[jax.Array] = None,
              chunk: int = 512) -> jax.Array:
    """Full attention block (self- or cross-). x: (B, S, d)."""
    b, s, d = x.shape
    src = x if kv is None else kv
    # qkv biases ride the projection GEMMs as fused epilogue specs.
    q = ctx.dot_fused("wq", x, p["wq"], bias=p.get("bq"))
    k = ctx.dot_fused("wk", src, p["wk"], bias=p.get("bk"))
    v = ctx.dot_fused("wv", src, p["wv"], bias=p.get("bv"))
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, src.shape[1], cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, src.shape[1], cfg.n_kv_heads, cfg.head_dim)
    if positions is None:
        positions = jnp.arange(s)
    if kv is None:  # RoPE on self-attention only
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    out = chunked_attention(q, k, v, causal=causal, chunk=chunk, ctx=ctx)
    return ctx.dot("wo", out.reshape(b, s, -1), p["wo"])


# ---------------------------------------------------------------------------
# MLP (SwiGLU)
# ---------------------------------------------------------------------------

def init_mlp(key, d: int, d_ff: int, n_layers: int, dtype) -> Dict[str, Any]:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], d, d_ff, dtype),
        "w_up": dense_init(ks[1], d, d_ff, dtype),
        "w_down": dense_init(ks[2], d_ff, d, dtype,
                             scale=0.02 / (2 * n_layers) ** 0.5),
    }


def mlp(p: Dict[str, Any], x: jax.Array, ctx: Ctx) -> jax.Array:
    g = ctx.dot_fused("w_gate", x, p["w_gate"], act="silu")  # fused epilogue
    u = ctx.dot("w_up", x, p["w_up"])
    return ctx.dot("w_down", g * u, p["w_down"])


# ---------------------------------------------------------------------------
# embedding / head / loss
# ---------------------------------------------------------------------------

def embed(tokens: jax.Array, table: jax.Array) -> jax.Array:
    return jnp.take(table, tokens, axis=0)


def lm_head(x: jax.Array, table: jax.Array, ctx: Ctx) -> jax.Array:
    return ctx.dot("lm_head", x, table)


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  ignore: int = -1) -> jax.Array:
    """Mean CE over positions with label != ignore. logits (…, V).

    GSPMD-friendly: the gold-logit gather is expressed as a masked reduction
    over the vocab dim (fuses to an iota-compare + reduce under a
    vocab-sharded mesh — no all-gather of the logits, no gather op)."""
    logits = logits.astype(jnp.float32)
    mask = (labels != ignore)
    safe = jnp.where(mask, labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                          logits.ndim - 1)
    gold = jnp.sum(jnp.where(vocab_iota == safe[..., None], logits, 0.0),
                   axis=-1)
    nll = (logz - gold) * mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1)
