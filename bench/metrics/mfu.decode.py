"""Model FLOP utilisation of the decode steps, in %: the model's
operations of every decode step of the window (live tokens x 2 x matrix
parameters, plus attention over each live length; ``work/dense.py``) over
the host time of those steps, each from the decode call to the return of
the engine's ``step()``, times peak bf16 FLOP/s."""
import os

from bench import harness


def read(layer):
    steps = [s for s in layer.get("decode_steps", ()) if "t1" in s]
    if not steps:
        return None
    cfg = layer["cfg"]
    work = harness.load_module(os.path.join(layer["bench_dir"], "work",
                                            cfg.family + ".py"))
    flops = sum(work.decode_step_flops(cfg, s["lengths"]) for s in steps)
    wall = sum(s["t1"] - s["t0"] for s in steps)
    return 100.0 * flops / (wall * layer["chips"]
                            * layer["peaks"]["bf16_flops"])
