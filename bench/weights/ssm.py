"""Seeded Mamba-2 weights in the program's layout (stacked layers), made on
the device in one jitted call. Matrices in the serving/training dtype,
vectors in float32, as the program keeps them.

Values follow the published Mamba-2 initialisation where it is random
(A = -U(1, 16), dt log-uniform in [1e-3, 1e-1] through an inverse softplus,
conv1d kaiming-uniform), and put a small random spread on the norm and
skip weights that the published init sets to one, so that a path which
drops them cannot agree with the reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make(cfg, key, dtype=jnp.bfloat16):
    sc = cfg.ssm
    d, L = cfg.d_model, cfg.n_layers
    d_inner = sc.expand * d
    h = d_inner // sc.head_dim
    g, n = sc.n_groups, sc.state
    conv_ch = d_inner + 2 * g * n
    proj_out = 2 * d_inner + 2 * g * n + h
    vp = cfg.padded_vocab()
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std, dt=jnp.float32):
        return jax.random.normal(next(ks), shape, dt) * jnp.asarray(std, dt)

    def near_one(shape):
        return 1.0 + normal(shape, 0.05)

    bound = sc.conv_width ** -0.5
    dt = jnp.exp(jax.random.uniform(next(ks), (L, h), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    vocab_rows = (jnp.arange(vp) < cfg.vocab_size)[:, None]
    return {
        "embed": {"table": jnp.where(vocab_rows, normal((vp, d), 0.02, dtype),
                                     jnp.zeros((), dtype))},
        "layers": {
            "ssm": {
                "in_proj": normal((L, d, proj_out), 0.02, dtype),
                "conv_w": jax.random.uniform(
                    next(ks), (L, sc.conv_width, conv_ch), jnp.float32,
                    -bound, bound).astype(dtype),
                "conv_b": jax.random.uniform(
                    next(ks), (L, conv_ch), jnp.float32, -bound, bound
                ).astype(dtype),
                "A_log": jnp.log(jax.random.uniform(
                    next(ks), (L, h), jnp.float32, 1.0, 16.0)),
                "D": near_one((L, h)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "norm_w": near_one((L, d_inner)),
                "out_proj": normal((L, d_inner, d), 0.02 / (2 * L) ** 0.5,
                                   dtype),
            },
            "pre_norm": near_one((L, d)),
        },
        "final_norm": near_one((d,)),
        "head": {"table": jnp.where(vocab_rows.T, normal((d, vp), 0.02, dtype),
                                    jnp.zeros((), dtype))},
    }
