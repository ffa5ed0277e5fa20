"""Roofline share of the paged decode attention, in %: the HBM-bound least
time of the decode steps that started in the traced window (keys and
values of each live slot's length + 1 positions in every layer, plus query
and output rows; ``work/dense.py``) over the device time of the ops under
the ``kernel/flash_decode`` scope (which holds ``kernel/flashft/decode``)."""
import os

from bench import harness

SCOPES = ("kernel/flash_decode", "kernel/flashft/decode")


def read(layer):
    red = layer.get("trace")
    t0, t1 = layer.get("trace_t", (None, None))
    if red is None or t0 is None or t1 is None:
        return None
    dev = red.scope_time_s(SCOPES)
    steps = [s for s in layer.get("decode_steps", ()) if t0 <= s["t0"] < t1]
    if dev <= 0 or not steps:
        return None
    cfg = layer["cfg"]
    work = harness.load_module(os.path.join(layer["bench_dir"], "work",
                                            cfg.family + ".py"))
    nbytes = sum(work.decode_attn_bytes(cfg, s["lengths"]) for s in steps)
    return 100.0 * nbytes / layer["peaks"]["hbm_bw"] / dev
