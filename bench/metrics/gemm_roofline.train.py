"""Roofline share of the training step's projection GEMMs, in %: the
least time of the model's 2-D products of every step in the traced window
(forward and both gradient products of each layer's in/out projection and
the head, each bound by FLOPs or by HBM bytes; ``work/``), over the device
time of the ops under the ``kernel/gemm`` and ``kernel/fused_matmul``
scopes. Kernel time includes the recomputed forward of full remat; the
least time does not."""
import os

from bench import harness

SCOPES = ("kernel/gemm", "kernel/fused_matmul")


def read(layer):
    red = layer.get("trace")
    if red is None or not layer.get("steps"):
        return None
    dev = red.scope_time_s(SCOPES)
    if dev <= 0:
        return None
    cfg = layer["cfg"]
    work = harness.load_module(os.path.join(layer["bench_dir"], "work",
                                            cfg.family + ".py"))
    least = work.gemm_least_time_s(
        work.projection_gemms(cfg, layer["tokens_per_step"]),
        layer["peaks"]) * layer["steps"]
    return 100.0 * least / dev
