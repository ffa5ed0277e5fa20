"""Model FLOP utilisation of training, in %: the model's operations per
token (forward and backward, no recomputation, no ABFT work; ``work/``)
times tokens per second, over chips x peak bf16 FLOP/s. Tokens per second
come from the FT step timed by the host clock before the profiler starts
(``ft_timing``), so the trace's own cost is not in it."""
import os

from bench import harness


def read(layer):
    timing = layer.get("ft_timing")
    if not timing:
        return None
    cfg = layer["cfg"]
    work = harness.load_module(os.path.join(layer["bench_dir"], "work",
                                            cfg.family + ".py"))
    if cfg.family == "ssm":
        per_token = work.train_flops_per_token(cfg)
    else:
        per_token = work.train_flops_per_token(cfg, layer["seq"])
    tokens_per_s = layer["tokens_per_step"] / timing["ft_on_step_s"]
    peak = layer["chips"] * layer["peaks"]["bf16_flops"]
    return 100.0 * per_token * tokens_per_s / peak
