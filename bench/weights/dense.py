"""Seeded dense-transformer weights in the program's layout (stacked
layers), made on the device in one jitted call: every matrix N(0, 0.02) in
the serving/training dtype, norm weights 1 + N(0, 0.05) in float32, and
zero rows for the vocabulary's padding, as a checkpoint's padded rows are.

The output projections are not scaled down by depth: with tied embeddings
and the usual 1/sqrt(2L) scaling, the token's own embedding dominates the
residual stream, and a random model then repeats its input token whatever
its layers compute. Unscaled, the layers' outputs dominate, and the greedy
tokens depend on the context through every layer."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make(cfg, key, dtype=jnp.bfloat16):
    d, L, dff = cfg.d_model, cfg.n_layers, cfg.d_ff
    qd, kvd = cfg.qkv_dims
    vp = cfg.padded_vocab()
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std, dt=jnp.float32):
        return jax.random.normal(next(ks), shape, dt) * jnp.asarray(std, dt)

    def mat(shape, std=0.02):
        return normal(shape, std, dtype)

    def near_one(shape):
        return 1.0 + normal(shape, 0.05)

    vocab_rows = (jnp.arange(vp) < cfg.vocab_size)[:, None]
    params = {
        "embed": {"table": jnp.where(vocab_rows, mat((vp, d)),
                                     jnp.zeros((), dtype))},
        "layers": {
            "attn_norm": near_one((L, d)),
            "attn": {"wq": mat((L, d, qd)), "wk": mat((L, d, kvd)),
                     "wv": mat((L, d, kvd)), "wo": mat((L, qd, d))},
            "ffn_norm": near_one((L, d)),
            "mlp": {"w_gate": mat((L, d, dff)), "w_up": mat((L, d, dff)),
                    "w_down": mat((L, dff, d))},
        },
        "final_norm": near_one((d,)),
    }
    if not cfg.tie_embeddings:
        params["head"] = {"table": jnp.where(vocab_rows.T, mat((d, vp)),
                                             jnp.zeros((), dtype))}
    return params
