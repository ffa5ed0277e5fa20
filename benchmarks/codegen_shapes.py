"""Fig. 10/11 (+14/15/19/20) analogue — template code generation across
irregular input shapes.

Paper result: shape-class parameter selection beats one fixed hard-coded
kernel by up to 230% on irregular shapes and cuBLAS by up to 41%. The TPU
analogue of the win is *padding efficiency*: a fixed 'huge' 512×512 tile on
a 160×160 problem wastes (512/160)² ≈ 10× FLOPs in padding; the generator
picks class-fit tiles. Derived column = padded/useful FLOPs per variant and
the resulting predicted speedup of autotuned over fixed (plus interpret-mode
correctness of the generated kernels).

Tuning
------
Runtime dispatch is the spec → template → autotune pipeline (see the
`repro.kernels` package docstring): a `templates.KernelSpec` — or, for
batched/grouped launches, a `templates.BatchedKernelSpec` — names the
kernel variant (FT level × epilogue chain × dtypes × batch structure),
`templates.emit` renders it into one Pallas body, and
`autotune.best_params` picks the tile parameters — memoizing the candidate
search (`kernels.search`) in a JSON cache: the writable
``$REPRO_TUNE_CACHE`` when set, else the committed
``benchmarks/tuned/<device_kind>.json``, read-only.

Cache keys are ``device/class/caps/bytes/ft_level[/v_variant][/b_N|/g_N]``:
element width comes from the *actual operand dtype* (bf16 gets its own
entries and sublane floor); the variant component
(`KernelSpec.variant_key()`, e.g. ``v_bias+gelu``, ``v_batched``,
``v_grouped``) separates fused-epilogue chains and batched/grouped bodies,
whose aux-operand VMEM and roofline intensity legitimately move the
winner; and the batch component (``best_params(..., batch=B)`` →
``/b_<pow2>``, ``groups=G`` → ``/g_<pow2>``) captures the batch/group
count — a uniform batch multiplies every roofline term, while a group
count charges the per-group row-alignment padding (``G·(bm-1)`` worst
case), which steers grouped launches toward shallower row tiles. Plain
f32 2-D GEMM keeps the bare key, so PR-1/2 caches stay valid.

Worked grouped-MoE tuning example — an E-expert FFN over T routed rows::

    spec = templates.BatchedKernelSpec(ft_level="block", grouped=True)
    autotune.best_params(T, d_ff, d_model, 4, ft_level="block",
                         spec=spec, groups=E)   # key: …/v_grouped/g_<E↑2>

To regenerate a device's cache wholesale, run
``python -m benchmarks.run --only tune_campaign``: it re-searches a fixed
campaign (2-D, fused, batched, grouped — measured on TPU hardware,
roofline-modeled elsewhere) into ``$REPRO_TUNE_CAMPAIGN_OUT`` and diffs
the result against the checked-in ``benchmarks/tuned/<device>.json``.
This benchmark keeps the per-class view: each row reports the
static-table params next to the autotuned ones (``table=… tuned=…``) so
table/search divergence is visible per class. Fused-variant rows live in
`benchmarks.fused_epilogue`; to tune a *new* epilogue (after
`templates.epilogues.register`) just call ``best_params(m, n, k,
dtype.itemsize, ft_level=…, spec=your_spec)`` once: the miss searches
under the variant's working-set model and stores under its own key
(written to disk only for a ``$REPRO_TUNE_CACHE`` file).
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.kernels import autotune, ops, tune_cache
from repro.core.policy import ONLINE_BLOCK
from .common import emit


def padded_flops_ratio(m, n, k, p: autotune.KernelParams) -> float:
    mp, np_, kp = autotune.padded_shape(m, n, k, p)
    return (mp * np_ * kp) / (m * n * k)


def run() -> None:
    fixed = autotune.KernelParams(*autotune.TABLE["huge"], "huge")
    shapes = [
        ("small_96", 96, 96, 256),
        ("medium_160", 160, 160, 256),
        ("large_448", 448, 448, 256),
        ("tall_4096x128", 4096, 128, 1024),
        ("wide_128x4096", 128, 4096, 1024),
        ("huge_2048", 2048, 2048, 512),
        ("ragged_100x77x300", 100, 77, 300),
    ]
    rng = np.random.default_rng(0)
    cache = tune_cache.default_cache()
    for name, m, n, k in shapes:
        dtype = jnp.float32
        in_bytes = jnp.dtype(dtype).itemsize      # width from the real dtype
        table = autotune.build_params(m, n, k, in_bytes)
        # ft_level="block" throughout: the kernel run below is ONLINE_BLOCK,
        # so the reported params/path must come from the same tuning key.
        tuned = autotune.best_params(m, n, k, in_bytes, cache=cache,
                                     ft_level="block")
        r_fixed = padded_flops_ratio(m, n, k, fixed)
        r_table = padded_flops_ratio(m, n, k, table)
        info = ops.dispatch_info(m, n, k, tuned, dtype=dtype,
                                 ft_level="block")
        r_disp = (info["executed_flops"] / 2.0) / (m * n * k)
        speedup = 100.0 * (r_fixed / r_disp - 1.0)
        # correctness of the dispatched kernel (FT on) on this shape
        a = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
        out = ops.ft_matmul(a, b, ft=ONLINE_BLOCK, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(a @ b),
                                   rtol=1e-4, atol=1e-3)
        emit(f"codegen/{name}", float("nan"),
             f"class={tuned.shape_class} path={info['path']} "
             f"table=({table.bm},{table.bn},{table.bk}) "
             f"tuned=({tuned.bm},{tuned.bn},{tuned.bk}) "
             f"padded_x_fixed={r_fixed:.2f} padded_x_table={r_table:.2f} "
             f"padded_x_dispatch={r_disp:.2f} "
             f"predicted_speedup={speedup:.0f}% correct=1")
    emit("codegen/tune_cache", float("nan"),
         f"path={cache.path} entries={len(cache)} "
         f"writable={int(cache.writable)}")
