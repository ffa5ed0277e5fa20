#!/usr/bin/env python3
"""Bring-up check: the fault-tolerant serve and train paths on a TPU, with
the ABFT kernels compiled (never interpreted), at published model widths.

    python chip_smoke.py              # one chip: device, kernel, serve, train
    python chip_smoke.py --chips 4    # four chips: the sharded train step only

Phases run in order in this one process (it starts no other); a failed
check raises, so the exit code is non-zero and no result line is printed.
The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Weights and data are random, made from fixed seeds; nothing is downloaded.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import registry  # noqa: E402
from repro.configs.base import RunConfig, ShapeConfig  # noqa: E402
from repro.core import telemetry  # noqa: E402
from repro.core.policy import FT_OFF, ONLINE_BLOCK, InjectionSpec  # noqa: E402
from repro.data import pipeline as data_lib  # noqa: E402
from repro.distributed import sharding as shd  # noqa: E402
from repro.kernels import ops, tune_cache  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import model_zoo  # noqa: E402
from repro.models import transformer as tfm  # noqa: E402
from repro.models.blocks import Ctx  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.tools import roofline  # noqa: E402
from repro.train import train_loop  # noqa: E402
from repro.train.engine import EngineConfig, ServeEngine  # noqa: E402

#: The FT policy under test: the paper's fused block-level online ABFT,
#: run inside the repo's Pallas kernels.
FT = ONLINE_BLOCK.replace(backend="pallas")

#: Serve logits vs the float32 reference, max over positions of
#: ||engine - ref|| / ||ref||. The engine keeps activations, weights and the
#: KV cache in bf16 (unit roundoff 2**-9), rounding the inputs of ~7 GEMMs
#: in each of 32 layers; those independent roundings add up to a few
#: percent of the logit vector. A wrong kernel (a lost block, a bad page,
#: an uncorrected SEU) moves it by O(1).
SERVE_LOGIT_RTOL = 0.05

#: Injected vs clean train loss, relative. A corrected SEU leaves only the
#: rounding of the checksum residual behind (~2**-24 relative on one element
#: of a block), which can still move that element to the neighbouring bf16
#: value when the output is cast; over one SEU per block of every protected
#: GEMM these add up to ~1e-5. An uncorrected SEU (2**bit_shift) shifts the
#: loss by far more.
INJECT_LOSS_RTOL = 1e-3

#: Sharded-layout agreement (2x2 vs 4x1 mesh), relative. The layouts sum
#: bf16 partial products over different shards, so the step's values differ
#: by bf16 rounding (2**-9), averaged over the batch for the loss and
#: accumulated over every gradient entry for the norm.
SHARDED_LOSS_RTOL = 5e-3
SHARDED_GNORM_RTOL = 2e-2


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def log(msg: str) -> None:
    print(msg, flush=True)


def kernels_in(jitted, *args) -> set:
    """Compile ``jitted`` for ``args`` and return the Pallas kernel names its
    program holds (from the lowering, whose custom calls carry them). The
    compiled text must hold TPU custom calls, i.e. compiled kernels. A later
    call with the same shapes reuses this compilation."""
    lowered = jitted.lower(*args)
    names = set(re.findall(r'kernel_name = "([^"]+)"', lowered.as_text()))
    check("tpu_custom_call" in lowered.compile().as_text(),
          "compiled program holds no tpu_custom_call")
    return names


def peak_bytes() -> int:
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


def bytes_in_use(device) -> int:
    return int(device.memory_stats()["bytes_in_use"])


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def device_phase(chips: int) -> dict:
    devs = jax.devices()
    dev = devs[0]
    check(dev.platform == "tpu", f"JAX found no TPU (platform "
                                 f"{dev.platform!r})")
    check(len(devs) == chips, f"{len(devs)} devices, expected {chips}")
    row = roofline.peaks(dev.device_kind)
    log(f"device: {dev.device_kind} x{len(devs)}; peaks {row} "
        f"(roofline.PEAKS)")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def kernel_phase(m: int = 4096, k: int = 3072, n: int = 8192,
                 seed: int = 0) -> None:
    """One FT GEMM, clean and with one SEU: detected once, corrected once,
    output equal to the clean run."""
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.random.normal(ka, (m, k), jnp.bfloat16)
    b = jax.random.normal(kb, (k, n), jnp.bfloat16)
    seu = InjectionSpec(row=m // 3, col=n // 5, magnitude=1024.0, k_step=0)
    clean, rep_c = ops.ft_matmul_report(a, b, ft=FT)
    fixed, rep_f = ops.ft_matmul_report(a, b, ft=FT, spec=seu)
    rep_c, rep_f = np.asarray(rep_c), np.asarray(rep_f)
    det_c, fix_c = rep_c[..., 0].sum(), rep_c[..., 1].sum()
    det_f, fix_f = rep_f[..., 0].sum(), rep_f[..., 1].sum()
    clean, fixed = np.asarray(clean, np.float32), np.asarray(fixed, np.float32)
    ref = np.asarray(jnp.dot(a, b, preferred_element_type=jnp.float32))
    rel = np.linalg.norm(clean - ref) / np.linalg.norm(ref)
    names = kernels_in(jax.jit(
        lambda a, b: ops.ft_matmul_report(a, b, ft=FT, spec=seu)), a, b)
    info = ops.dispatch_info(m, n, k, dtype=jnp.bfloat16, ft_level="block")
    log(f"kernel: ft_matmul_report {m}x{k}x{n} bf16 params {info['params']} "
        f"kernels {sorted(names)}; clean det/fix {det_c:g}/{fix_c:g}, "
        f"injected det/fix {det_f:g}/{fix_f:g}, "
        f"max |fixed - clean| {np.abs(fixed - clean).max():g}, "
        f"rel err vs XLA dot {rel:.3g}")
    check(det_c == 0 and fix_c == 0, "clean run reported a detection")
    check(det_f == 1 and fix_f == 1, "injected SEU not detected and "
                                     "corrected exactly once")
    check(np.array_equal(fixed, clean), "corrected output != clean output")
    check(rel < 1e-2, f"FT GEMM disagrees with the XLA dot ({rel:.3g})")
    check(any(x.startswith("gemm_block") for x in names),
          f"no GEMM template kernel in {sorted(names)}")


def serve_phase(cfg, *, n_requests: int = 16,
                lengths=(128, 512, 1024), new_tokens: int = 32,
                ec: EngineConfig = EngineConfig(n_slots=8, max_len=2048),
                seed: int = 0) -> None:
    """Serve ``cfg`` (random bf16 weights) through `ServeEngine` with the
    pallas FT backend, then hold request 0's logits against a float32
    forward pass (xla backend, FT off, highest matmul precision)."""
    run = RunConfig(model=cfg, ft=FT, dtype="bfloat16")
    mod = model_zoo.module_for(cfg)
    params = jax.jit(lambda key: mod.init(cfg, key, jnp.bfloat16))(
        jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    lens = [int(x) for x in rng.choice(lengths, n_requests)]
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    eng = ServeEngine(params, cfg, run, dataclasses.replace(
        ec, max_new_tokens=new_tokens))
    plan = eng.plan
    log(f"serve: {cfg.arch_id} {cfg.n_layers}L d={cfg.d_model} "
        f"vocab={cfg.vocab_size}; page {plan.page_size} tokens, "
        f"{plan.n_pages} pages, {plan.max_pages} per slot; prompt lengths "
        f"{lens}")

    # The programs the engine runs, compiled up front: their kernels by name.
    prefill_k = kernels_in(eng._prefill, params,
                           jnp.zeros((1, lens[0]), jnp.int32),
                           tfm.init_cache(cfg, 1, lens[0], jnp.bfloat16))
    decode_k = kernels_in(eng._decode, params,
                          jnp.zeros((ec.n_slots, 1), jnp.int32), eng.cache)
    log(f"serve: prefill kernels {sorted(prefill_k)}; decode kernels "
        f"{sorted(decode_k)}")
    check("_flash_ft_kernel" in prefill_k, "prefill runs no flash forward")
    check("_flash_decode_kernel" in decode_k,
          "decode runs no flash decode kernel (gather fallback)")
    for what, names in (("prefill", prefill_k), ("decode", decode_k)):
        check(any(x.startswith("gemm_block") for x in names),
              f"{what} runs no GEMM template kernel")

    # Request 0's logits, as the engine computes them: its prefill is the
    # first one (FIFO), then one row per decode step while it is live.
    captured = []
    prefill, decode = eng._prefill, eng._decode

    def prefill_rec(p, toks, dcache):
        out = prefill(p, toks, dcache)
        if not captured:
            captured.append(out[0][0])
        return out

    def decode_rec(p, tok, cache):
        out = decode(p, tok, cache)
        slots = [s for s, r in enumerate(eng.slot_req)
                 if r is not None and r.rid == 0]
        if slots:
            captured.append(out[0][slots[0], 0])
        return out

    eng._prefill, eng._decode = prefill_rec, decode_rec
    t0 = time.perf_counter()
    for p in prompts:
        eng.submit(p)
    results = eng.run()
    wall = time.perf_counter() - t0
    check(len(results) == n_requests, f"{len(results)} of {n_requests} "
                                      f"requests finished")
    check(all(len(r.tokens) == new_tokens for r in results),
          "a request stopped short of its token budget")
    check(eng.alloc.n_free == plan.n_pages - 1,
          f"{plan.n_pages - 1 - eng.alloc.n_free} pages not returned")
    check(len(captured) == new_tokens, f"captured {len(captured)} logit rows")

    toks = np.concatenate([prompts[0], results[0].tokens[:-1]])
    rctx = Ctx(ft=FT_OFF, key=None, dtype=jnp.float32)

    def reference(p, t):
        with jax.default_matmul_precision("highest"):
            logits, _ = tfm.forward(p, t, cfg, rctx, remat=False)
        return logits[0, lens[0] - 1:]

    ref = np.asarray(jax.jit(reference)(params, jnp.asarray(toks[None])),
                     np.float32)
    got = np.asarray(jnp.stack(captured), np.float32)
    rel = (np.linalg.norm(got - ref, axis=-1)
           / np.linalg.norm(ref, axis=-1))
    agree = float(np.mean(got.argmax(-1) == ref.argmax(-1)))
    log(f"serve: {n_requests} requests x {new_tokens} tokens in {wall:.1f} s "
        f"wall (set-up, compiles included); pages all returned; request 0 "
        f"logits vs f32 reference: max rel err {rel.max():.4g}, mean "
        f"{rel.mean():.4g}, argmax agreement {agree:.3f}; peak device "
        f"memory {peak_bytes() / 2**30:.2f} GiB")
    check(bool(np.isfinite(got).all()), "non-finite engine logits")
    check(rel.max() <= SERVE_LOGIT_RTOL,
          f"logits off the reference by {rel.max():.4g} > "
          f"{SERVE_LOGIT_RTOL}")


def train_phase(cfg, *, batch: int = 4, seq: int = 1024, steps: int = 3,
                seed: int = 0) -> None:
    """A few train steps with the pallas FT backend, then one step with an
    SEU in every protected block: all corrected, loss unchanged."""
    mod = model_zoo.module_for(cfg)
    opt_cfg = adamw.AdamWConfig()
    tc = train_loop.TrainConfig(total_steps=steps, warmup_steps=1)
    init = jax.jit(lambda key: mod.init(cfg, key, jnp.bfloat16))
    init_opt = jax.jit(lambda p: train_loop.init_opt_state(p, opt_cfg, tc))
    pipe = data_lib.for_model(cfg, ShapeConfig("chip_smoke", seq, batch,
                                               "train"), seed=seed)
    batches = [{k: jnp.asarray(v) for k, v in pipe.batch_at(s).items()}
               for s in range(steps)]

    def step_fn(rate: float):
        run = RunConfig(model=cfg, ft=FT.replace(inject_rate=rate))
        return jax.jit(train_loop.make_train_step(cfg, run, opt_cfg, tc),
                       donate_argnums=(0, 1))

    params = init(jax.random.PRNGKey(seed))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    opt = init_opt(params)
    step = step_fn(0.0)
    losses = []
    for s in range(steps):
        params, opt, m = step(params, opt, batches[s], jnp.asarray(s), None)
        losses.append(float(m["loss"]))
        check(np.isfinite(losses[-1]), f"step {s} loss {losses[-1]}")
        check(float(m["ft"].detected) == 0,
              f"clean step {s} reported {float(m['ft'].detected):g} "
              f"detections")
    del params, opt, m

    # The same first step again from the same seed, an SEU in every block.
    params = init(jax.random.PRNGKey(seed))
    opt = init_opt(params)
    _, _, m = step_fn(1.0)(params, opt, batches[0], jnp.asarray(0),
                           jax.random.PRNGKey(seed + 1))
    loss_i = float(m["loss"])
    det, fix = float(m["ft"].detected), float(m["ft"].corrected)
    log(f"train: {cfg.arch_id} {cfg.n_layers}L d={cfg.d_model} "
        f"({n_params / 1e6:.1f} M params) batch {batch}x{seq}; clean losses "
        f"{[round(x, 5) for x in losses]}; injected step sdc_det {det:g} "
        f"sdc_fix {fix:g} loss {loss_i:.6f} vs clean {losses[0]:.6f}; "
        f"process peak device memory {peak_bytes() / 2**30:.2f} GiB")
    check(det == fix and det > 0, f"sdc_det {det:g} / sdc_fix {fix:g}")
    check(abs(loss_i - losses[0]) <= INJECT_LOSS_RTOL * abs(losses[0]),
          f"injected loss {loss_i} vs clean {losses[0]}")


def sharded_phase(cfg, *, batch: int = 8, seq: int = 1024,
                  seed: int = 0) -> None:
    """The GSPMD train step (xla FT backend) on a 2x2 (data, model) mesh
    against the same step on a 4x1 data-only mesh: same first-step loss and
    grad norm, and each device holding about a quarter of the state."""
    mod = model_zoo.module_for(cfg)
    run = RunConfig(model=cfg, ft=ONLINE_BLOCK)
    opt_cfg = adamw.AdamWConfig()
    tc = train_loop.TrainConfig(total_steps=1, warmup_steps=1)
    init = lambda key: mod.init(cfg, key, jnp.bfloat16)  # noqa: E731
    init_opt = lambda p: train_loop.init_opt_state(p, opt_cfg, tc)  # noqa
    pipe = data_lib.for_model(cfg, ShapeConfig("chip_smoke", seq, batch,
                                               "train"), seed=seed)
    host_batch = pipe.batch_at(0)
    out = {}
    for name, model_axis in (("2x2", 2), ("4x1", 1)):
        mesh = make_host_mesh(model=model_axis)
        with shd.use_mesh(mesh):
            p_sh = shd.named_shardings(jax.eval_shape(
                init, jax.random.PRNGKey(seed)), mesh)
            params = jax.jit(init, out_shardings=p_sh)(
                jax.random.PRNGKey(seed))
            o_sh = shd.named_shardings(jax.eval_shape(init_opt, params),
                                       mesh)
            opt = jax.jit(init_opt, out_shardings=o_sh)(params)
            state = sum(x.nbytes for x in jax.tree.leaves((params, opt)))
            jax.block_until_ready((params, opt))
            held = [bytes_in_use(d) for d in mesh.devices.flat]
            data = jax.device_put(
                host_batch, jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec("data")))
            step = jax.jit(train_loop.make_train_step(cfg, run, opt_cfg, tc),
                           donate_argnums=(0, 1))
            params, opt, m = step(params, opt, data, jnp.asarray(0), None)
            loss, gnorm = float(m["loss"]), float(m["grad_norm"])
            det = float(m["ft"].detected)
            for row in telemetry.site_rows(m["ft"]):
                log(f"sharded: {name} detections {row}")
        shares = [h / state for h in held]
        log(f"sharded: {name} mesh {dict(mesh.shape)}; params+adam "
            f"{state / 2**30:.2f} GiB; per-device share "
            f"{[round(s, 3) for s in shares]}; loss {loss:.6f} grad norm "
            f"{gnorm:.6f} sdc_det {det:g}")
        out[name] = (loss, gnorm, det, shares)
        del params, opt, m, data
    # Checked once both layouts have run, so a failure shows both.
    for name, (loss, gnorm, det, shares) in out.items():
        check(det == 0, f"{name}: clean step reported {det:g} detections")
        check(all(0.2 <= s <= 0.35 for s in shares),
              f"{name}: per-device state shares {shares}, expected ~0.25")
        check(np.isfinite(loss) and np.isfinite(gnorm), f"{name}: non-finite")
    (l2, g2, _, _), (l4, g4, _, _) = out["2x2"], out["4x1"]
    check(abs(l2 - l4) <= SHARDED_LOSS_RTOL * abs(l4),
          f"loss 2x2 {l2} vs 4x1 {l4}")
    check(abs(g2 - g4) <= SHARDED_GNORM_RTOL * abs(g4),
          f"grad norm 2x2 {g2} vs 4x1 {g4}")


def tile_choices() -> None:
    """The tiles this run compiled: committed tuning file entries plus the
    roofline model's picks (deterministic for a given tree)."""
    cache = tune_cache.default_cache()
    entries = sorted(cache.as_dict().items())
    log(f"tiles: {len(entries)} choices (file {cache.path}, "
        f"writable {cache.writable})")
    for key, val in entries:
        log(f"tile {key} {list(val)}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded train step on a 2x2 host")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    device = device_phase(args.chips)
    log(f"compile cache: {compile_cache.enable_compile_cache()}")
    if args.chips == 4:
        phases = [lambda: sharded_phase(dataclasses.replace(
            registry.get_config("phi4-mini-3.8b"), n_layers=8))]
    else:
        phases = [kernel_phase,
                  lambda: serve_phase(registry.get_config("phi4-mini-3.8b")),
                  lambda: train_phase(registry.get_config("mamba2-780m"))]
    for phase in phases:
        phase()
        gc.collect()       # free the phase's device buffers before the next
    tile_choices()
    log(f"total wall {time.perf_counter() - t0:.1f} s (set-up and compiles "
        f"included)")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
