"""Operation and byte counts of ``work/`` against numbers worked by hand."""
import dataclasses
import math
import os

from bench import harness

WORK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "work")
ssm = harness.load_module(os.path.join(WORK, "ssm.py"))
dense = harness.load_module(os.path.join(WORK, "dense.py"))


@dataclasses.dataclass(frozen=True)
class SSM:
    state: int = 4
    head_dim: int = 2
    expand: int = 2
    chunk: int = 8
    n_groups: int = 1


@dataclasses.dataclass(frozen=True)
class Cfg:
    d_model: int = 4
    n_layers: int = 2
    vocab_size: int = 10
    ssm: SSM = SSM()
    n_heads: int = 2
    n_kv_heads: int = 1
    head_dim: int = 3
    d_ff: int = 5

    @property
    def qkv_dims(self):
        return self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim


def test_ssm_forward_flops_by_hand():
    # d_inner 8, heads 4, state 4, groups 1, chunk 8, head_dim 2:
    # in_proj 2*4*(16+8+4) = 224, out_proj 2*8*4 = 64,
    # ssd 1*8*4 + 4*(8*2 + 4*4*2) = 32 + 192 = 224; per layer 512,
    # two layers 1024, head 2*4*10 = 80.
    assert ssm.forward_flops_per_token(Cfg()) == 1104
    assert ssm.train_flops_per_token(Cfg()) == 3312


def test_ssm_gemms_and_least_time():
    g = ssm.projection_gemms(Cfg(), 16)
    assert g == [(16, 4, 28), (16, 8, 4)] * 2 + [(16, 4, 10)]
    peaks = {"bf16_flops": 1.0, "hbm_bw": 1e30}    # FLOP-bound: time = ops
    assert ssm.gemm_least_time_s([(2, 3, 5)], peaks) == 3 * 60
    assert ssm.gemm_least_time_s([(2, 3, 5)], peaks, backward=False) == 60
    peaks = {"bf16_flops": 1e30, "hbm_bw": 2.0}    # bytes-bound
    # (2*3 + 3*5 + 2*5) * 2 bytes / 2 = 31; dX (2,5,3): 31; dW (3,2,5): 31
    assert ssm.gemm_least_time_s([(2, 3, 5)], peaks) == 93


def test_dense_counts_by_hand():
    c = Cfg()
    # matmul params per layer: 4*(6+3+3) + 6*4 + 3*4*5 = 48 + 24 + 60 = 132
    assert dense.matmul_params_per_layer(c) == 132
    # one token over 7 positions: 2*132 + 4*7*6 = 432 per layer; + head 80
    assert dense.forward_flops_per_token(c, 7) == 2 * 432 + 80
    assert dense.decode_step_flops(c, [6, 6]) == 2 * (2 * 432 + 80)
    assert math.isclose(dense.train_flops_per_token(c, 13),
                        3 * dense.forward_flops_per_token(c, 7))
    # keys + values of 4 and 1 positions, 1 kv head of 3, plus q and out
    # rows of 2 heads of 3, two layers, 2 bytes:
    # per layer (2*4*3 + 12) + (2*1*3 + 12) = 36 + 18 = 54
    assert dense.decode_attn_bytes(c, [3, 0]) == 2 * 54 * 2
