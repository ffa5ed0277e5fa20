"""Compile the main-path FT kernels at real widths for a described TPU v5e.

No chip is needed: the TPU compiler runs for a topology that is described,
not attached, and refuses what the chip would refuse (block shapes, scalar
stores, casts Mosaic cannot lower). Interpret mode, which every other test
uses, accepts all of those. Each case asserts that the compiled program
holds a TPU custom call and that its lowering names the expected kernel.

The topology is described inside a fixture, never at import: only the test
worker that runs this file loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.policy import ONLINE_BLOCK
from repro.kernels import ops
from repro.kernels.templates import KernelSpec

FT = ONLINE_BLOCK.replace(backend="pallas")
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32

# phi4-mini-3.8b widths; attention at 24 query heads over 8 KV heads
# (n_rep 3), 2048 positions, head dim 128.
D, FF, VOCAB = 3072, 8192, 200064
H, KVH, S, DH = 24, 8, 2048, 128
# qwen3-moe expert widths: d_model 4096, expert d_ff 1536; 8 experts held.
MOE_D, MOE_FF, EXPERTS, TOKENS = 4096, 1536, 8, 2048


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _gemm(a, b):
    return ops.ft_matmul_report(a, b, ft=FT, interpret=False)


def _fused(a, b):
    return ops.fused_matmul(a, b, act="silu", ft=FT, interpret=False)


def _flash_fwd(q, k, v):
    return ops.flash_ft(q, k, v, ft=FT, n_rep=H // KVH, save_stats=True,
                        interpret=False)


def _flash_bwd(q, k, v, o, m, l, g):
    return ops.flash_ft_bwd(q, k, v, o, m, l, g, ft=FT, n_rep=H // KVH,
                            interpret=False)


def _flash_dq(*args):
    dq, _, _, rep_dq, _ = _flash_bwd(*args)
    return dq, rep_dq


def _flash_dkv(*args):
    _, dk, dv, _, rep_dkv = _flash_bwd(*args)
    return dk, dv, rep_dkv


def _decode(q, k_pages, v_pages, lengths, table):
    return ops.flash_ft_decode(q, k_pages, v_pages, lengths, table, ft=FT,
                               interpret=False)


def _batched(a, b):
    return ops.grouped_gemm_call(KernelSpec(ft_level="block"), a, b, ft=FT,
                                 interpret=False)


def _grouped(a, b, gid):
    return ops.grouped_gemm_call(KernelSpec(ft_level="block"), a, b,
                                 group_ids=gid, ft=FT, interpret=False)


def _tgmm(a, b, gid):
    return ops.grouped_gemm_call(KernelSpec(ft_level="block"), a, b,
                                 group_ids=gid, n_groups=EXPERTS, ft=FT,
                                 interpret=False)


_FLASH_BWD_ARGS = ((H, S, DH, BF16), (KVH, S, DH, BF16), (KVH, S, DH, BF16),
                   (H, S, DH, BF16), (H, S, F32), (H, S, F32),
                   (H, S, DH, BF16))

#: id → (function, operand (shape…, dtype) list, kernel name it must hold)
CASES = {
    "gemm_2d": (_gemm, [(2048, D, BF16), (D, FF, BF16)], "gemm_block"),
    "gemm_fused_silu": (_fused, [(2048, D, BF16), (D, FF, BF16)],
                        "gemm_block_silu"),
    "gemm_masked_lm_head": (_gemm, [(8, D, BF16), (D, VOCAB, BF16)],
                            "gemm_block_masked"),
    "flash_fwd_stats": (_flash_fwd, [(H, S, DH, BF16), (KVH, S, DH, BF16),
                                     (KVH, S, DH, BF16)], "_flash_ft_kernel"),
    "flash_dq": (_flash_dq, list(_FLASH_BWD_ARGS), "_flash_dq_kernel"),
    "flash_dkv": (_flash_dkv, list(_FLASH_BWD_ARGS),
                  "_flash_dkv_kernel"),
    "paged_decode": (_decode, [(8, H, DH, BF16), (64, KVH, 128, DH, BF16),
                               (64, KVH, 128, DH, BF16), (8, I32),
                               (8, 16, I32)], "_flash_decode_kernel"),
    # the chunked-attention QK GEMM: (B·heads, Sq, dh) x (B·heads, dh, Skv)
    "batched": (_batched, [(64, 512, DH, BF16), (64, DH, S, BF16)],
                "gemm_block_batched"),
    "grouped": (_grouped, [(TOKENS, MOE_D, BF16),
                           (EXPERTS, MOE_D, MOE_FF, BF16), (TOKENS, I32)],
                "gemm_block_grouped"),
    "tgmm": (_tgmm, [(TOKENS, MOE_D, BF16), (TOKENS, MOE_FF, BF16),
                     (TOKENS, I32)], "tgmm_block"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_ft_kernel_compiles_for_v5e(one_chip, case):
    fn, operands, kernel = CASES[case]
    args = [jax.ShapeDtypeStruct(o[:-1], o[-1], sharding=one_chip)
            for o in operands]
    lowered = jax.jit(fn).lower(*args)
    names = set(re.findall(r'kernel_name = "([^"]+)"', lowered.as_text()))
    assert any(n.startswith(kernel) for n in names), (kernel, names)
    assert "tpu_custom_call" in lowered.compile().as_text()
