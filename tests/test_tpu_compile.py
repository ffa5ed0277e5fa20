"""Compile the main-path FT kernels at real widths for a described TPU v5e.

No chip is needed: the TPU compiler runs for a topology that is described,
not attached, and refuses what the chip would refuse (block shapes, scalar
stores, casts Mosaic cannot lower). Interpret mode, which every other test
uses, accepts all of those. Each case asserts that the compiled program
holds a TPU custom call and that its lowering names the expected kernel.

The topology is described inside a fixture, never at import: only the test
worker that runs this file loads the TPU library.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
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


def _decode(q, k_pages, v_pages, lengths, table, layer):
    return ops.flash_ft_decode(q, k_pages, v_pages, lengths, table, layer,
                               ft=FT, interpret=False)


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
    # a stacked 2-layer pool, read at a traced layer index
    "paged_decode": (_decode, [(8, H, DH, BF16), (2, 64, KVH, 128, DH, BF16),
                               (2, 64, KVH, 128, DH, BF16), (8, I32),
                               (8, 16, I32), (I32,)], "_flash_decode_kernel"),
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


# The serving cell's decode geometry: 10 slots, max_len 2560, 512-token
# pages, so 5 pages a slot and 51 in the pool with the null page.
SLOTS, MAX_PAGES, PAGE = 10, 5, 512
POOL_PAGES = 1 + SLOTS * MAX_PAGES
#: ops that only name or pass a buffer, and may hold the whole pool
_NAMING_OPS = {"parameter", "get-tuple-element", "tuple", "bitcast", "while"}


def _instructions(hlo: str):
    """{name: (opcode, shape, operand names)} of every array-valued
    instruction of a compiled module's text."""
    pat = r"%([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(([^)]*)\)"
    return {name: (opcode, [int(d) for d in shape.split(",") if d],
                   re.findall(r"%([\w.\-]+)", operands))
            for name, shape, opcode, operands in re.findall(pat, hlo)}


def test_paged_decode_step_keeps_pool_in_place(one_chip, monkeypatch):
    """The serving engine's decode step, compiled for the chip with Mosaic
    kernels at phi4-mini widths (2 layers) and the serving cell's pool,
    moves no layer of the K/V page pool: every instruction whose result
    holds a layer of the pool (all of its sizes among its dimensions, so
    in any layout) only names or passes the buffer, or is a
    dynamic-update-slice that writes a small update into it in place — no
    copy, slice, scatter, fusion or allocation of one — and the donated
    pools are aliased to the step's outputs."""
    from repro.configs import registry
    from repro.models import transformer as tfm
    from repro.models.blocks import Ctx

    monkeypatch.setattr(ops, "_should_interpret",
                        lambda interpret=None: False)
    cfg = dataclasses.replace(registry.get_config("phi4-mini-3.8b"),
                              n_layers=2, tie_embeddings=True)
    ctx = Ctx(ft=FT, key=None, dtype=BF16)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda k: tfm.init(cfg, k, BF16),
                       jax.random.PRNGKey(0)))
    pool = (cfg.n_layers, POOL_PAGES, cfg.n_kv_heads, PAGE, cfg.head_dim)
    cache = {"k_pages": sds(pool, BF16), "v_pages": sds(pool, BF16),
             "page_table": sds((SLOTS, MAX_PAGES), I32),
             "length": sds((SLOTS,), I32)}
    step = jax.jit(lambda p, t, c: tfm.paged_decode_step(p, t, c, cfg, ctx),
                   donate_argnums=(2,))
    hlo = step.lower(params, sds((SLOTS, 1), I32), cache).compile().as_text()

    layer = pool[1:]
    ins = _instructions(hlo)
    moved = []
    for name, (opcode, shape, operands) in ins.items():
        if (np.prod(shape) < np.prod(layer)
                or any(shape.count(d) < layer.count(d) for d in layer)
                or opcode in _NAMING_OPS):
            continue
        if opcode == "dynamic-update-slice":
            update = ins[operands[1]][1]
            if np.prod(update) < np.prod(layer):
                continue
        moved.append((name, opcode, shape))
    assert not moved, moved

    # Donated cache leaves alias the new cache's. Flat argument order is
    # params, token, then the cache's keys sorted; outputs are the logits,
    # then the new cache's keys sorted.
    n_params = len(jax.tree.leaves(params))
    keys = sorted(cache)
    alias = dict(re.findall(r"\{(\d+)\}: \((\d+), \{\}", hlo))
    for key in ("k_pages", "v_pages"):
        out_i, arg_i = 1 + keys.index(key), n_params + 1 + keys.index(key)
        assert alias.get(str(out_i)) == str(arg_i), (key, alias)
