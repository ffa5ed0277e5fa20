"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b-smoke \
        --steps 200 --batch 8 --seq 128 [--ckpt-dir /tmp/ckpt --resume]

One process, one device, no mesh: it trains through `train_loop.train`
on whatever device JAX finds first. The sharded step is exercised by
`chip_smoke.py --chips 4` and tests/test_distributed.py.
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import registry
from repro.configs.base import RunConfig, ShapeConfig
from repro.core.policy import ONLINE_BLOCK, FT_OFF
from repro.launch.compile_cache import enable_compile_cache
from repro.train import train_loop


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    help="arch id (append '-smoke' for the reduced config)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--no-ft", action="store_true")
    ap.add_argument("--inject-every", type=int, default=0)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=200)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--compress-grads", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    if args.arch.endswith("-smoke"):
        cfg = registry.get_smoke(args.arch[:-len("-smoke")])
    else:
        cfg = registry.get_config(args.arch)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    run = RunConfig(model=cfg, ft=FT_OFF if args.no_ft else ONLINE_BLOCK,
                    learning_rate=args.lr, microbatch=args.microbatch,
                    attn_chunk=min(128, args.seq))
    tc = train_loop.TrainConfig(
        total_steps=args.steps, warmup_steps=max(args.steps // 10, 1),
        ckpt_every=args.ckpt_every, inject_every=args.inject_every,
        compress_grads=args.compress_grads)
    out = train_loop.train(cfg, run, shape, tc, ckpt_dir=args.ckpt_dir,
                           resume=args.resume)
    print(f"finished at step {out['final_step']}; "
          f"final loss {out['history'][-1]['loss']:.4f}; "
          f"stragglers {len(out['stragglers'])}")


if __name__ == "__main__":
    main()
