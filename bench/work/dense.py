"""Operations and bytes of the dense-transformer family, from the model's
shapes.

Matrix products at 2 operations per multiply-add; attention at 4 x context
x (query heads x head size) per token and layer (scores and values), over
the positions a causal query sees; the vocabulary at its published size;
no rematerialisation, no ABFT checksum work.
"""
from __future__ import annotations

from typing import Sequence


def matmul_params_per_layer(cfg) -> int:
    d = cfg.d_model
    qd, kvd = cfg.qkv_dims
    return d * (qd + 2 * kvd) + qd * d + 3 * d * cfg.d_ff


def forward_flops_per_token(cfg, context: float) -> float:
    """One token that attends over ``context`` positions."""
    qd, _ = cfg.qkv_dims
    per_layer = 2 * matmul_params_per_layer(cfg) + 4 * context * qd
    return cfg.n_layers * per_layer + 2 * cfg.d_model * cfg.vocab_size


def train_flops_per_token(cfg, seq: int) -> float:
    """Forward and backward over causal rows of ``seq`` tokens (mean
    context (seq + 1) / 2)."""
    return 3.0 * forward_flops_per_token(cfg, (seq + 1) / 2)


def decode_step_flops(cfg, lengths: Sequence[int]) -> float:
    """One decode step over live slots holding ``lengths`` tokens each (the
    new token attends over length + 1 positions)."""
    return float(sum(forward_flops_per_token(cfg, n + 1) for n in lengths))


def decode_attn_bytes(cfg, lengths: Sequence[int],
                      bytes_per_el: int = 2) -> float:
    """Bytes the decode attention of one step has to move at least: every
    layer reads the keys and values of each live slot's length + 1
    positions, and reads a query row and writes an output row per head."""
    kvh, nh, dh = cfg.n_kv_heads, cfg.n_heads, cfg.head_dim
    per_layer = sum(2 * (n + 1) * kvh * dh + 2 * nh * dh for n in lengths)
    return float(cfg.n_layers * per_layer * bytes_per_el)
