"""Plain float32 Mamba-2 language model (arXiv:2405.21060), written from
the paper and the published block, for the benchmark's comparison.

Block: RMSNorm -> in_proj -> (z, xBC, dt) -> depthwise causal conv1d over
xBC + bias -> SiLU -> SSD with A = -exp(A_log), dt = softplus(dt + dt_bias)
and skip D -> gated RMSNorm(y * SiLU(z)) -> out_proj, residual. The SSD is
the paper's minimal chunked form (its Listing 1, "ssd_minimal_discrete");
`ssd_sequential` is the recurrence itself, which the tests hold it to.

Departures from the published model, as the configuration states them:
separate (untied) embedding and head, vocabulary padded to a multiple of
256 with zero rows, residual stream in the compute dtype. Nothing here
imports the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp



def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def segsum(x):
    """x (..., T) -> (..., T, T): sum of x over (j, i] below the diagonal,
    -inf above it."""
    t = x.shape[-1]
    cs = jnp.cumsum(x, -1)
    seg = cs[..., :, None] - cs[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), seg, -jnp.inf)


def ssd_chunked(x, a, b, c, chunk):
    """x (B, L, H, P) already scaled by dt; a (B, L, H) = dt * A;
    b, c (B, L, H, N). Returns y (B, L, H, P)."""
    bs, l, h, p = x.shape
    nc = l // chunk
    x, a, b, c = (t.reshape(bs, nc, chunk, *t.shape[2:]) for t in (x, a, b, c))
    a = jnp.moveaxis(a, -1, 1)                       # (B, H, C, Q)
    a_cum = jnp.cumsum(a, -1)
    hp = jax.lax.Precision.HIGHEST
    ell = jnp.exp(segsum(a))                         # (B, H, C, Q, Q)
    y_diag = jnp.einsum("bclhn,bcshn,bhcls,bcshp->bclhp", c, b, ell, x,
                        precision=hp)
    decay_states = jnp.exp(a_cum[..., -1:] - a_cum)  # (B, H, C, Q)
    states = jnp.einsum("bclhn,bhcl,bclhp->bchpn", b, decay_states, x,
                        precision=hp)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    decay_chunk = jnp.exp(segsum(jnp.pad(a_cum[..., -1], ((0, 0), (0, 0),
                                                          (1, 0)))))
    new_states = jnp.einsum("bhzc,bchpn->bzhpn", decay_chunk, states,
                            precision=hp)[:, :-1]
    y_off = jnp.einsum("bclhn,bchpn,bhcl->bclhp", c, new_states,
                       jnp.exp(a_cum), precision=hp)
    return (y_diag + y_off).reshape(bs, l, h, p)


def ssd_sequential(x, a, b, c):
    """The recurrence h_t = exp(a_t) h_{t-1} + b_t x_t^T, y_t = c_t . h_t."""
    def step(hs, inp):
        xt, at, bt, ct = inp
        hs = jnp.exp(at)[..., None, None] * hs + bt[..., :, None] * xt[..., None, :]
        return hs, jnp.einsum("bhn,bhnp->bhp", ct, hs)
    bs, _, h, p = x.shape
    h0 = jnp.zeros((bs, h, b.shape[-1], p), x.dtype)
    _, y = jax.lax.scan(step, h0, tuple(jnp.moveaxis(t, 1, 0)
                                        for t in (x, a, b, c)))
    return jnp.moveaxis(y, 0, 1)


def layer(lp, h, cfg, mm):
    """One residual Mamba-2 block on h (B, L, d), float32."""
    sc = cfg.ssm
    d_inner = sc.expand * cfg.d_model
    nh = d_inner // sc.head_dim
    g, n = sc.n_groups, sc.state
    p = lp["ssm"]
    bs, l, _ = h.shape
    zxbcdt = mm(rmsnorm(h, lp["pre_norm"], cfg.norm_eps), p["in_proj"])
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:2 * d_inner + 2 * g * n]
    dt = zxbcdt[..., 2 * d_inner + 2 * g * n:]
    w = p["conv_w"]
    wl = w.shape[0]
    xp = jnp.pad(xbc, ((0, 0), (wl - 1, 0), (0, 0)))
    conv = sum(xp[:, i:i + l] * w[i] for i in range(wl)) + p["conv_b"]
    xbc = jax.nn.silu(conv)
    x = xbc[..., :d_inner].reshape(bs, l, nh, sc.head_dim)
    rep = nh // g
    b = jnp.repeat(xbc[..., d_inner:d_inner + g * n].reshape(bs, l, g, n),
                   rep, axis=2)
    c = jnp.repeat(xbc[..., d_inner + g * n:].reshape(bs, l, g, n),
                   rep, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])          # (B, L, H)
    a = -jnp.exp(p["A_log"])
    chunk = min(sc.chunk, l)
    y = ssd_chunked(x * dt[..., None], dt * a, b, c, chunk)
    y = y + p["D"][:, None] * x
    y = y.reshape(bs, l, d_inner)
    y = rmsnorm(y * jax.nn.silu(z), p["norm_w"], cfg.norm_eps)
    return h + mm(y, p["out_proj"])


def embed(top, tokens):
    return jnp.take(top["embed"]["table"], tokens, axis=0)


def logits(top, h, cfg, mm):
    return mm(rmsnorm(h, top["final_norm"], cfg.norm_eps),
              top["head"]["table"])
