"""Tune-cache regeneration campaign — the ROADMAP "measured cache" follow-on.

Regenerates the persistent autotuning cache for the CURRENT device kind over
a fixed campaign of shape classes (2-D, fused-epilogue, batched, grouped —
every key family `autotune.best_params` can produce), then diffs it against
the checked-in baseline under ``benchmarks/tuned/<device_kind>.json``:

  * on TPU hardware the campaign *measures* candidates
    (`search.measure_candidates` wall-clocks each tile config), so running
    this benchmark on a new device kind and checking in the emitted file is
    how a measured cache ships;
  * on CPU (CI) scoring falls back to the deterministic roofline model, so
    the diff doubles as a regression gate: an unintended cost-model change
    shows up as ``changed=…`` rows against the checked-in baseline.

Rows report added/removed/changed keys; ``REPRO_TUNE_CAMPAIGN_OUT`` (or a
temp file) receives the regenerated cache for checking in. Wired into
``python -m benchmarks.run`` as the ``tune_campaign`` suite.
"""
from __future__ import annotations

import os
import tempfile

import jax.numpy as jnp

from repro.kernels import autotune, search, tune_cache
from repro.kernels.templates import BatchedKernelSpec, KernelSpec
from .common import emit

#: (name, m, n, k, dtype, ft_level, spec, batch, groups) — one entry per
#: cache-key family the runtime dispatch can produce. Keep this list in sync
#: with the hot paths: codegen_shapes' classes, the fused model-block
#: chains, attention QK/PV batched shapes, grouped MoE FFN shapes.
CAMPAIGN = [
    ("small_f32", 96, 96, 256, jnp.float32, "off", None, 1, 0),
    ("small_ft", 96, 96, 256, jnp.float32, "block", None, 1, 0),
    ("medium_ft", 300, 300, 600, jnp.float32, "block", None, 1, 0),
    ("large_ft", 1024, 2048, 1024, jnp.float32, "block", None, 1, 0),
    ("tall_ft", 4096, 128, 1024, jnp.float32, "block", None, 1, 0),
    ("huge_bf16", 2048, 2048, 2048, jnp.bfloat16, "block", None, 1, 0),
    ("fused_mlp", 512, 2048, 512, jnp.float32, "block",
     KernelSpec(ft_level="block", epilogue=("bias", "silu")), 1, 0),
    # attention QK/PV cores: uniform batched, ragged seq dims
    ("attn_qk_b16", 512, 512, 128, jnp.float32, "block",
     BatchedKernelSpec(ft_level="block"), 16, 0),
    ("attn_pv_b16", 512, 128, 512, jnp.float32, "block",
     BatchedKernelSpec(ft_level="block"), 16, 0),
    # grouped MoE expert FFN: G experts over a routed token buffer
    ("moe_ffn_g64", 8192, 1536, 1024, jnp.float32, "block",
     BatchedKernelSpec(ft_level="block", grouped=True), 1, 64),
    ("moe_ffn_g64_off", 8192, 1536, 1024, jnp.float32, "off",
     BatchedKernelSpec(ft_level="off", grouped=True), 1, 64),
]


def baseline_path(dev: str) -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tuned", f"{dev}.json")


def regenerate(path: str) -> tune_cache.TuneCache:
    """Run the campaign into a fresh cache at `path` (search per entry —
    measured on TPU, roofline-modeled elsewhere)."""
    if os.path.exists(path):
        os.unlink(path)
    cache = tune_cache.TuneCache(path)
    for (_, m, n, k, dtype, ft_level, spec, batch, groups) in CAMPAIGN:
        autotune.best_params(m, n, k, jnp.dtype(dtype).itemsize,
                             ft_level=ft_level, spec=spec, batch=batch,
                             groups=groups, cache=cache,
                             measure=search.can_measure())
    return cache


def diff(baseline: dict, fresh: dict):
    added = sorted(set(fresh) - set(baseline))
    removed = sorted(set(baseline) - set(fresh))
    changed = sorted(k for k in set(fresh) & set(baseline)
                     if fresh[k] != baseline[k])
    return added, removed, changed


def run() -> None:
    dev = autotune.device_kind()
    out_path = os.environ.get(
        "REPRO_TUNE_CAMPAIGN_OUT",
        os.path.join(tempfile.gettempdir(), f"repro_tuned_{dev}.json"))
    fresh = regenerate(out_path)
    base_file = baseline_path(dev)
    base = tune_cache.TuneCache(base_file)
    if len(base) == 0:
        emit(f"tune_campaign/{dev}", float("nan"),
             f"entries={len(fresh)} baseline=absent "
             f"regenerated={out_path} (check in as {base_file})")
        return
    added, removed, changed = diff(base.as_dict(), fresh.as_dict())
    emit(f"tune_campaign/{dev}", float("nan"),
         f"entries={len(fresh)} baseline={len(base)} added={len(added)} "
         f"removed={len(removed)} changed={len(changed)} "
         f"regenerated={out_path}")
    for key in changed:
        emit(f"tune_campaign/changed/{key}", float("nan"),
             f"baseline={base.as_dict()[key]} fresh={fresh.as_dict()[key]}")
    # On CPU the scorer is the deterministic roofline model: any drift from
    # the checked-in baseline is an unintended cost-model change — fail the
    # suite so it surfaces at PR time. (On TPU, measured results may move
    # with hardware/runtime; the diff is informational there.)
    import jax
    if jax.default_backend() != "tpu":
        assert not changed and not removed, (
            "tune cache drift vs checked-in baseline", changed, removed)
