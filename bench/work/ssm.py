"""Operations and bytes of the Mamba-2 family, from the model's shapes.

Counts are of the model's work: matrix products at 2 operations per
multiply-add, the vocabulary at its published size (no padding), no
rematerialisation, no ABFT checksum work. The SSD term counts the chunked
dual form's products (arXiv:2405.21060 section 6) with the causal half of
each chunk's quadratic part.
"""
from __future__ import annotations

from typing import List, Tuple

Gemm = Tuple[int, int, int]            # (M, K, N) of an (M, K) @ (K, N)


def dims(cfg):
    sc = cfg.ssm
    d_inner = sc.expand * cfg.d_model
    return d_inner, d_inner // sc.head_dim, sc.state, sc.n_groups


def forward_flops_per_token(cfg) -> float:
    d = cfg.d_model
    d_inner, h, n, g = dims(cfg)
    sc = cfg.ssm
    proj = 2 * d * (2 * d_inner + 2 * g * n + h) + 2 * d_inner * d
    q, p = sc.chunk, sc.head_dim
    ssd = g * q * n + h * (q * p + 4 * n * p)
    return cfg.n_layers * (proj + ssd) + 2 * d * cfg.vocab_size


def train_flops_per_token(cfg) -> float:
    """Forward and backward: three times the forward."""
    return 3.0 * forward_flops_per_token(cfg)


def projection_gemms(cfg, tokens: int) -> List[Gemm]:
    """The forward 2-D products of one step over ``tokens`` tokens: each
    layer's in_proj and out_proj, and the head."""
    d = cfg.d_model
    d_inner, h, n, g = dims(cfg)
    per_layer = [(tokens, d, 2 * d_inner + 2 * g * n + h),
                 (tokens, d_inner, d)]
    return per_layer * cfg.n_layers + [(tokens, d, cfg.vocab_size)]


def gemm_least_time_s(gemms: List[Gemm], peaks, bytes_per_el: int = 2,
                      backward: bool = True) -> float:
    """Least time of the products on one chip: for each, the larger of its
    operations over peak FLOP/s and its operand and result bytes over HBM
    bandwidth; with ``backward`` also the two products of its gradient
    (dX = dY W^T, dW = X^T dY)."""
    def one(m, k, n):
        return max(2.0 * m * k * n / peaks["bf16_flops"],
                   (m * k + k * n + m * n) * bytes_per_el / peaks["hbm_bw"])
    tot = 0.0
    for m, k, n in gemms:
        tot += one(m, k, n)
        if backward:
            tot += one(m, n, k) + one(k, m, n)
    return tot
