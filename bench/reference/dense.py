"""Plain float32 dense decoder (Phi-4-mini's block), written from the
published description, for the benchmark's comparison.

Block: RMSNorm -> q, k, v projections (no bias) -> rotary embedding ->
causal softmax attention, query head j reading key/value head j // n_rep ->
output projection, residual; RMSNorm -> SwiGLU MLP (SiLU(x Wg) * x Wu) Wd,
residual. Final RMSNorm, then logits against the embedding table (tied) or
the head.

Departures from the published Phi-4-mini, as the configuration states
them: the rotary embedding turns the whole head (rotate-half pairs, theta
10000), where the published model turns 3/4 of it with LongRoPE factors.
Nothing here imports the program.

Weights are held in the dtype they were made in (bf16 matrices) and
widened to float32 inside each layer, one layer at a time, so that the
whole model never sits on the chip in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.precision import matmul_for

PAD = 512


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """x (B, T, H, dh): pairs (i, i + dh/2) turned by positions x
    theta^(-2i/dh)."""
    dh = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = positions[:, None].astype(jnp.float32) * freqs     # (T, dh/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(lp, h, cfg, mm):
    """One residual block on h (B, T, d), float32."""
    f32 = lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t)  # noqa
    lp = f32(lp)
    b, t, _ = h.shape
    nh, kvh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    a = lp["attn"]
    x = rmsnorm(h, lp["attn_norm"], cfg.norm_eps)
    pos = jnp.arange(t)
    q = rope(mm(x, a["wq"]).reshape(b, t, nh, dh), pos, cfg.rope_theta)
    k = rope(mm(x, a["wk"]).reshape(b, t, kvh, dh), pos, cfg.rope_theta)
    v = mm(x, a["wv"]).reshape(b, t, kvh, dh)
    rep = nh // kvh
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    hp = jax.lax.Precision.HIGHEST
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=hp) / jnp.sqrt(dh)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v, precision=hp)
    h = h + mm(o.reshape(b, t, nh * dh), a["wo"])
    m = lp["mlp"]
    x = rmsnorm(h, lp["ffn_norm"], cfg.norm_eps)
    return h + mm(jax.nn.silu(mm(x, m["w_gate"])) * mm(x, m["w_up"]),
                  m["w_down"])


def _table(top):
    if "head" in top:
        return top["head"]["table"].astype(jnp.float32)
    return top["embed"]["table"].astype(jnp.float32).T


def embed(top, tokens):
    return jnp.take(top["embed"]["table"], tokens, axis=0).astype(jnp.float32)


def logits(top, h, cfg, mm):
    return mm(rmsnorm(h, top["final_norm"].astype(jnp.float32),
                      cfg.norm_eps), _table(top))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer_at(stacked, i, h, cfg, precision):
    with jax.default_matmul_precision("highest"):
        return layer(jax.tree.map(lambda a: a[i], stacked), h, cfg,
                     matmul_for(precision))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _rows(top, h, rows, cfg, precision):
    with jax.default_matmul_precision("highest"):
        return logits(top, h[0, rows][None], cfg, matmul_for(precision))[0]


def logit_rows(params, cfg, tokens: np.ndarray, rows: np.ndarray,
               precision: str = "float32"):
    """Logits (len(rows), V) at positions ``rows`` of one sequence. The
    sequence is padded at its end to a multiple of `PAD` (causal: padding
    changes no earlier position) so that few programs compile."""
    t = len(tokens)
    tp = -(-t // PAD) * PAD
    toks = np.zeros((1, tp), np.int32)
    toks[0, :t] = tokens
    top = {k: v for k, v in params.items() if k != "layers"}
    h = embed(top, jnp.asarray(toks))
    for i in range(cfg.n_layers):
        h = _layer_at(params["layers"], i, h, cfg, precision)
    return _rows(top, h, jnp.asarray(rows), cfg, precision)


def _gaps(ref, chosen):
    best = jnp.max(ref, -1)
    got = jnp.take_along_axis(ref, chosen[:, None], -1)[:, 0]
    return best - got


def serve_numbers(params, cfg, seqs, precision: str = "float32"):
    """Over every (prompt, served tokens) of ``seqs``, the gap by which a
    served token's reference logit lies below the reference's best: its
    widest (``widest_gap``) and its mean over all served tokens
    (``mean_gap``); and the share of served tokens that are not the float32
    reference's first choice (``argmax_miss``). With ``precision`` another
    than float32, the chosen token at each position is the one that
    precision puts first (the control)."""
    widest, total_gap, miss, total = -np.inf, 0.0, 0, 0
    for prompt, served in seqs:
        toks = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        rows = np.arange(len(prompt) - 1, len(toks), dtype=np.int32)
        ref = logit_rows(params, cfg, toks, rows)
        if precision == "float32":
            chosen = jnp.asarray(served)
        else:
            chosen = jnp.argmax(logit_rows(params, cfg, toks, rows,
                                           precision), -1)
        gaps = _gaps(ref, chosen)
        widest = max(widest, float(jnp.max(gaps)))
        total_gap += float(jnp.sum(gaps))
        miss += int(jnp.sum(chosen != jnp.argmax(ref, -1)))
        total += len(served)
    return {"argmax_miss": miss / max(total, 1), "widest_gap": widest,
            "mean_gap": total_gap / max(total, 1)}
