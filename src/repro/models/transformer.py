"""Decoder-only transformer LM (dense + MoE families).

Covers: qwen2-7b, codeqwen1.5-7b, phi4-mini, minitron-4b (dense);
arctic-480b, qwen3-moe-235b (MoE — arctic additionally has a parallel dense
residual FFN per layer). Also the backbone for phi-3-vision.

Layers are scanned (stacked params) with optional per-layer remat — keeps
the HLO size O(1) in depth, which the 512-device dry-run depends on.

Training/prefill attention routes through `blocks.chunked_attention`, which
since PR 4 dispatches to the `kernels.flashft` ragged-causal kernel on the
pallas FT backend (one protected Pallas launch, chunked-oracle recompute in
the backward) — so a train-step jaxpr on that backend carries no large
dot_general outside registry-emitted kernels (tests/test_backward_ft.py's
protection audit).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import telemetry
from repro.core import loops
from repro.distributed.sharding import shard
from . import blocks, moe as moe_lib
from .blocks import Ctx


class AuxOut(NamedTuple):
    balance: jax.Array          # MoE load-balance loss
    ft: telemetry.FTReport      # per-step SDC telemetry (DESIGN.md §2.3)


def init_layer(key, cfg: ModelConfig, dtype) -> Dict[str, Any]:
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {
        "attn_norm": jnp.ones((cfg.d_model,), jnp.float32),
        "attn": blocks.init_attention(ks[0], cfg, dtype),
        "ffn_norm": jnp.ones((cfg.d_model,), jnp.float32),
    }
    if cfg.moe is not None:
        p["moe"] = moe_lib.init_moe(ks[1], cfg.d_model, cfg.moe,
                                    cfg.n_layers, dtype)
        if cfg.moe.dense_d_ff:
            p["mlp"] = blocks.init_mlp(ks[2], cfg.d_model, cfg.moe.dense_d_ff,
                                       cfg.n_layers, dtype)
    else:
        p["mlp"] = blocks.init_mlp(ks[2], cfg.d_model, cfg.d_ff,
                                   cfg.n_layers, dtype)
    return p


def apply_layer(p: Dict[str, Any], x: jax.Array, cfg: ModelConfig, ctx: Ctx,
                *, positions: Optional[jax.Array] = None,
                chunk: int = 512) -> Tuple[jax.Array, jax.Array]:
    """Pre-norm block. Returns (x, aux_loss)."""
    x = shard(x, "batch", "seq", "embed")
    h = blocks.rmsnorm(x, p["attn_norm"], cfg.norm_eps)
    x = x + blocks.attention(p["attn"], h, cfg, ctx, causal=True,
                             positions=positions, chunk=chunk)
    h = blocks.rmsnorm(x, p["ffn_norm"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if cfg.moe is not None:
        y, aux = moe_lib.apply_moe(p["moe"], h, cfg.moe, ctx)
        if cfg.moe.dense_d_ff:
            y = y + blocks.mlp(p["mlp"], h, ctx)   # arctic parallel residual
        x = x + y
    else:
        x = x + blocks.mlp(p["mlp"], h, ctx)
    return shard(x, "batch", "seq", "embed"), aux


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------

def init(cfg: ModelConfig, key, dtype=jnp.bfloat16) -> Dict[str, Any]:
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    stacked = jax.vmap(lambda k: init_layer(k, cfg, dtype))(layer_keys)
    v = cfg.padded_vocab()
    params = {
        "embed": {"table": blocks.embed_init(k_emb, v, cfg.d_model, dtype)},
        "layers": stacked,
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["head"] = {"table": blocks.dense_init(k_head, cfg.d_model, v,
                                                     dtype)}
    return params


def _scan_layers(params, x, fn, remat: bool):
    """Scan stacked layers carrying (activations, aux-loss, FTReport) — SDC
    telemetry crosses the scan via the carry (telemetry.scoped). Each
    layer's single-row report lands at row 1 + idx of the carried report
    (row 0 stays for un-layered sites), so the step report resolves
    (layer, site) pairs."""

    def wrapped(lp, h, idx):
        return telemetry.scoped(lambda: fn(lp, h, idx))

    body_fn = blocks.make_remat(wrapped, remat)

    def body(carry, scanned):
        h, aux, rep = carry
        lp, idx = scanned
        (h, aux_l), rep_l = body_fn(lp, h, idx)
        return (h, aux + aux_l, rep.merge_at(rep_l, idx + 1)), None

    n = jax.tree.leaves(params)[0].shape[0]
    (x, aux, rep), _ = loops.scan(
        body, (x, jnp.zeros((), jnp.float32),
               telemetry.FTReport.empty(rows=n + 1)),
        (params, jnp.arange(n)))
    return x, aux, rep


def forward(params, tokens: jax.Array, cfg: ModelConfig, ctx: Ctx, *,
            remat: bool = True, chunk: int = 512,
            extra_embeds: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """tokens: (B, S) int32 → (logits (B, S', V), aux). If `extra_embeds`
    (B, P, d) is given (VLM patch stubs), it is prepended to the sequence."""
    x = blocks.embed(tokens, params["embed"]["table"]).astype(ctx.dtype)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(ctx.dtype), x], axis=1)
    x = shard(x, "batch", "seq", "embed")
    positions = jnp.arange(x.shape[1])

    def layer_fn(lp, h, idx):
        return apply_layer(lp, h, cfg, ctx.fold(idx), positions=positions,
                           chunk=chunk)

    x, aux, rep = _scan_layers(params["layers"], x, layer_fn, remat)
    x = blocks.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    table = (params["embed"]["table"].T if cfg.tie_embeddings
             else params["head"]["table"])
    logits, rep_h = telemetry.scoped(lambda: blocks.lm_head(x, table, ctx))
    ctx.check_inject_sites()
    # "seq" claims the model axis first ⇒ logits stay sequence-sharded and
    # the CE loss is fully local (only the head table is gathered, once).
    return shard(logits, "batch", "seq", "vocab"), AuxOut(aux,
                                                          rep.merge(rep_h))


def loss_fn(params, batch: Dict[str, jax.Array], cfg: ModelConfig, ctx: Ctx,
            *, remat: bool = True, chunk: int = 512) -> Tuple[jax.Array, Dict]:
    logits, aux = forward(params, batch["tokens"], cfg, ctx, remat=remat,
                          chunk=chunk, extra_embeds=batch.get("patches"))
    labels = batch["labels"]
    if logits.shape[1] != labels.shape[1]:      # VLM: logits cover patches too
        logits = logits[:, -labels.shape[1]:]
    ce = blocks.cross_entropy(logits, labels)
    total = ce + 0.01 * aux.balance
    return total, {"ce": ce, "aux": aux.balance, "ft": aux.ft}


# ---------------------------------------------------------------------------
# serving: KV cache, prefill, decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
               kv_batch_axis: str = "batch") -> Dict[str, Any]:
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "length": jnp.zeros((batch,), jnp.int32),
    }


def _shard_cache(cache):
    cache["k"] = shard(cache["k"], None, "batch", "kv_seq", "kv_heads", None)
    cache["v"] = shard(cache["v"], None, "batch", "kv_seq", "kv_heads", None)
    return cache


def _project_qkv(p, h, cfg: ModelConfig, ctx: Ctx, positions):
    b, s, _ = h.shape
    # qkv biases ride the projection GEMMs as fused epilogue specs.
    q = ctx.dot_fused("wq", h, p["wq"], bias=p.get("bq"))
    k = ctx.dot_fused("wk", h, p["wk"], bias=p.get("bk"))
    v = ctx.dot_fused("wv", h, p["wv"], bias=p.get("bv"))
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    q = blocks.apply_rope(q, positions, cfg.rope_theta)
    k = blocks.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def decode_step(params, token: jax.Array, cache: Dict[str, Any],
                cfg: ModelConfig, ctx: Ctx) -> Tuple[jax.Array, Dict]:
    """One decode step. token: (B, 1) int32; cache holds `length` tokens.
    Returns (logits (B, 1, V), new cache)."""
    cache = _shard_cache(dict(cache))
    x = blocks.embed(token, params["embed"]["table"]).astype(ctx.dtype)
    pos = cache["length"]                                  # (B,)

    def layer_fn(lp, h, scanned_cache):
        k_c, v_c, idx = scanned_cache
        lctx = ctx.fold(idx)
        hn = blocks.rmsnorm(h, lp["attn_norm"], cfg.norm_eps)
        q, k_new, v_new = _project_qkv(lp["attn"], hn, cfg, lctx,
                                       pos[:, None])
        # write the new kv at `pos` for every batch row
        b = h.shape[0]
        oh = jax.nn.one_hot(pos, k_c.shape[1], dtype=k_c.dtype)  # (B, S)
        k_c = k_c + oh[:, :, None, None] * k_new
        v_c = v_c + oh[:, :, None, None] * v_new
        att = blocks.decode_attention(q, k_c, v_c, pos + 1, lctx)
        h = h + lctx.dot("wo", att.reshape(b, 1, -1), lp["attn"]["wo"])
        hn = blocks.rmsnorm(h, lp["ffn_norm"], cfg.norm_eps)
        if cfg.moe is not None:
            y, _ = moe_lib.apply_moe(lp["moe"], hn, cfg.moe, lctx)
            if cfg.moe.dense_d_ff:
                y = y + blocks.mlp(lp["mlp"], hn, lctx)
            h = h + y
        else:
            h = h + blocks.mlp(lp["mlp"], hn, lctx)
        return h, (k_c, v_c)

    # Serve-path telemetry is opt-in: records appended from inside the scan
    # body to an outer-trace scope would leak tracers, so per-layer scoping
    # (and the report carry) only runs when the caller opened an ft_scope
    # (train/serve.py's with_report path) — gate resolved at trace time.
    want_ft = telemetry.current_scope() is not None
    n = cfg.n_layers

    def body(carry, scanned):
        h, rep = carry
        lp, k_c, v_c, idx = scanned
        if want_ft:
            (h, (k_c, v_c)), rep_l = telemetry.scoped(
                lambda: layer_fn(lp, h, (k_c, v_c, idx)))
            rep = rep.merge_at(rep_l, idx + 1)
        else:
            h, (k_c, v_c) = layer_fn(lp, h, (k_c, v_c, idx))
        return (h, rep), (k_c, v_c)

    (x, rep), (new_k, new_v) = loops.scan(
        body, (x, telemetry.FTReport.empty(rows=n + 1)),
        (params["layers"], cache["k"], cache["v"], jnp.arange(n)))
    if want_ft:
        telemetry.record_report(rep)
    x = blocks.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    table = (params["embed"]["table"].T if cfg.tie_embeddings
             else params["head"]["table"])
    logits = blocks.lm_head(x, table, ctx)
    new_cache = {"k": new_k, "v": new_v, "length": cache["length"] + 1}
    return logits, _shard_cache(new_cache)


def paged_decode_step(params, token: jax.Array, cache: Dict[str, Any],
                      cfg: ModelConfig, ctx: Ctx) -> Tuple[jax.Array, Dict]:
    """One decode step against the *paged* KV cache (train/kv_cache.py —
    the serving engine's layout). token: (B, 1) int32 over the engine's
    slot axis; cache: {"k_pages", "v_pages": (L, P, KVH, page, dh) pools,
    "page_table": int32 (B, max_pages), "length": int32 (B,)}. Returns
    (logits (B, 1, V), new cache).

    The whole pool rides the layer scan's carry, so it is updated where it
    lies: the scan's xs are only the layer params and index, and no layer
    of the pool is ever sliced out or stacked back (with the cache donated,
    the step writes one token per slot and layer, and copies nothing). The
    new kv lands via `kv_cache.append_layer` (one in-place
    dynamic-update-slice per slot) and attention runs through
    `blocks.paged_decode_attention` — on the pallas FT backend one flashft
    decode launch per layer that reads the layer in place through
    prefetched page-table and layer indices, with ragged lengths, so
    thousands of slots share the pool with zero dense padding. Dead slots
    (all-NULL table rows, length 0) write into the reserved null page and
    produce ignored garbage logits; the engine rebuilds
    `page_table`/`length` from the host allocator each step."""
    from repro.train import kv_cache as kv_cache_lib
    x = blocks.embed(token, params["embed"]["table"]).astype(ctx.dtype)
    pos = cache["length"]                                  # (B,)
    table = cache["page_table"]
    n_pages = cache["k_pages"].shape[1]

    def layer_fn(lp, h, k_all, v_all, idx):
        lctx = ctx.fold(idx)
        hn = blocks.rmsnorm(h, lp["attn_norm"], cfg.norm_eps)
        q, k_new, v_new = _project_qkv(lp["attn"], hn, cfg, lctx,
                                       pos[:, None])
        b = h.shape[0]
        rows = kv_cache_lib.layer_table(table, idx, n_pages)
        k_all = kv_cache_lib.append_layer(k_all, k_new[:, 0], rows, pos)
        v_all = kv_cache_lib.append_layer(v_all, v_new[:, 0], rows, pos)
        att = blocks.paged_decode_attention(q, k_all, v_all, pos + 1, table,
                                            idx, lctx)
        h = h + lctx.dot("wo", att.reshape(b, 1, -1), lp["attn"]["wo"])
        hn = blocks.rmsnorm(h, lp["ffn_norm"], cfg.norm_eps)
        if cfg.moe is not None:
            y, _ = moe_lib.apply_moe(lp["moe"], hn, cfg.moe, lctx)
            if cfg.moe.dense_d_ff:
                y = y + blocks.mlp(lp["mlp"], hn, lctx)
            h = h + y
        else:
            h = h + blocks.mlp(lp["mlp"], hn, lctx)
        return h, k_all, v_all

    # Same serve-path telemetry gate as decode_step: per-layer scoping only
    # when the caller opened an ft_scope (resolved at trace time).
    want_ft = telemetry.current_scope() is not None
    n = cfg.n_layers

    def body(carry, scanned):
        h, rep, k_all, v_all = carry
        lp, idx = scanned
        if want_ft:
            (h, k_all, v_all), rep_l = telemetry.scoped(
                lambda: layer_fn(lp, h, k_all, v_all, idx))
            rep = rep.merge_at(rep_l, idx + 1)
        else:
            h, k_all, v_all = layer_fn(lp, h, k_all, v_all, idx)
        return (h, rep, k_all, v_all), None

    (x, rep, new_k, new_v), _ = loops.scan(
        body, (x, telemetry.FTReport.empty(rows=n + 1), cache["k_pages"],
               cache["v_pages"]),
        (params["layers"], jnp.arange(n)))
    if want_ft:
        telemetry.record_report(rep)
    x = blocks.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = (params["embed"]["table"].T if cfg.tie_embeddings
            else params["head"]["table"])
    logits = blocks.lm_head(x, head, ctx)
    new_cache = {"k_pages": new_k, "v_pages": new_v,
                 "page_table": table, "length": pos + 1}
    return logits, new_cache


def prefill(params, tokens: jax.Array, cache: Dict[str, Any],
            cfg: ModelConfig, ctx: Ctx, *, chunk: int = 512,
            remat: bool = True,
            extra_embeds: Optional[jax.Array] = None) -> Tuple[jax.Array, Dict]:
    """Run the prompt through the model, filling the KV cache.
    `extra_embeds` (B, P, d) — VLM patch stubs prepended to the prompt.
    Returns (last-position logits (B, V), cache)."""
    cache = _shard_cache(dict(cache))
    b = tokens.shape[0]
    x = blocks.embed(tokens, params["embed"]["table"]).astype(ctx.dtype)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(ctx.dtype), x], axis=1)
    s = x.shape[1]
    positions = jnp.arange(s)

    def layer_fn(lp, h, idx):
        lctx = ctx.fold(idx)
        hn = blocks.rmsnorm(h, lp["attn_norm"], cfg.norm_eps)
        q, k, v = _project_qkv(lp["attn"], hn, cfg, lctx, positions)
        att = blocks.chunked_attention(q, k, v, causal=True, chunk=chunk,
                                       ctx=lctx)
        h = h + lctx.dot("wo", att.reshape(b, s, -1), lp["attn"]["wo"])
        hn = blocks.rmsnorm(h, lp["ffn_norm"], cfg.norm_eps)
        if cfg.moe is not None:
            y, _ = moe_lib.apply_moe(lp["moe"], hn, cfg.moe, lctx)
            if cfg.moe.dense_d_ff:
                y = y + blocks.mlp(lp["mlp"], hn, lctx)
            h = h + y
        else:
            h = h + blocks.mlp(lp["mlp"], hn, lctx)
        return h, (k, v)

    # Like decode_step: per-layer telemetry only when the caller opened an
    # ft_scope — scoping must sit INSIDE the remat wrapper (records cannot
    # cross a checkpoint region as a side channel).
    want_ft = telemetry.current_scope() is not None

    def wrapped(lp, h, idx):
        return telemetry.scoped(lambda: layer_fn(lp, h, idx))

    fn = blocks.make_remat(wrapped if want_ft else layer_fn, remat)

    def body(carry, scanned):
        lp, idx = scanned
        h, rep = carry
        if want_ft:
            (h, (k, v)), rep_l = fn(lp, h, idx)
            rep = rep.merge_at(rep_l, idx + 1)
        else:
            h, (k, v) = fn(lp, h, idx)
        return (h, rep), (k, v)

    (x, rep), (ks, vs) = loops.scan(
        body, (x, telemetry.FTReport.empty(rows=cfg.n_layers + 1)),
        (params["layers"], jnp.arange(cfg.n_layers)))
    if want_ft:
        telemetry.record_report(rep)
    # place prompt KV into the cache buffers
    max_len = cache["k"].shape[2]
    pad = max_len - s
    k_full = jnp.pad(ks.astype(cache["k"].dtype),
                     ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    v_full = jnp.pad(vs.astype(cache["v"].dtype),
                     ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    x = blocks.rmsnorm(x[:, -1:, :], params["final_norm"], cfg.norm_eps)
    table = (params["embed"]["table"].T if cfg.tie_embeddings
             else params["head"]["table"])
    logits = blocks.lm_head(x, table, ctx)[:, 0]
    new_cache = {"k": k_full, "v": v_full,
                 "length": jnp.full((b,), s, jnp.int32)}
    return logits, _shard_cache(new_cache)
