"""Serving launcher: batched generation with the FT-protected decode path.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b-smoke \
        --batch 4 --prompt-len 32 --new-tokens 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.configs.base import RunConfig
from repro.core.policy import ONLINE_BLOCK, FT_OFF
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model_zoo
from repro.train import serve as serve_lib


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--no-ft", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    if args.arch.endswith("-smoke"):
        cfg = registry.get_smoke(args.arch[:-len("-smoke")])
    else:
        cfg = registry.get_config(args.arch)
    run = RunConfig(model=cfg, ft=FT_OFF if args.no_ft else ONLINE_BLOCK,
                    dtype="float32", attn_chunk=64)
    mod = model_zoo.module_for(cfg)
    params = mod.init(cfg, jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, args.prompt_len)).astype(np.int32)
    extra = None
    if cfg.family == "vlm":
        extra = jnp.asarray(rng.standard_normal(
            (args.batch, cfg.n_patches, cfg.d_model)), jnp.float32)
    if cfg.family == "encdec":
        extra = jnp.asarray(rng.standard_normal(
            (args.batch, cfg.n_audio_frames, cfg.d_model)), jnp.float32)
    sc = serve_lib.ServeConfig(max_len=args.max_len,
                               temperature=args.temperature)
    t0 = time.time()
    out = serve_lib.generate(params, prompts, cfg, run, sc,
                             max_new_tokens=args.new_tokens, extra=extra)
    dt = time.time() - t0
    print(f"generated {out.shape} tokens in {dt:.2f}s "
          f"({out.size / dt:.1f} tok/s)")
    print(out[:, :12])


if __name__ == "__main__":
    main()
