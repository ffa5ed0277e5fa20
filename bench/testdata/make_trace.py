#!/usr/bin/env python3
"""Record the small trace the trace-reduction tests read, on a TPU.

    python bench/testdata/make_trace.py

Runs a jitted program with two FT GEMMs (the program's ``kernel/gemm``
scope) and an elementwise op, three times inside a ``bench/window`` span
with a host ``bench/wait`` span and a host-only pause between runs, under
the profiler. Writes ``small.xplane.pb`` and ``small.hlo.json`` (the
program's compiled HLO text, for the scopes) beside this file.
"""
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main():
    import jax
    import jax.numpy as jnp
    from bench import harness, trace_reduce
    from repro.core import ft_dot
    from repro.core.policy import ONLINE_BLOCK
    harness.require_chips(1)
    ft = ONLINE_BLOCK.replace(backend="pallas")

    def prog(a, b):
        c = ft_dot(a, b, ft=ft, site="t1")
        return jnp.tanh(ft_dot(c, b.T, ft=ft, site="t2")).sum()

    f = jax.jit(prog)
    a = jnp.ones((1024, 512), jnp.bfloat16)
    b = jnp.ones((512, 1024), jnp.bfloat16) * 0.01
    f(a, b).block_until_ready()
    hlo = harness.hlo_text(f, a, b)
    out = os.path.join(ROOT, "bench_out", "trace-small")
    trace_reduce.start(out)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        for _ in range(3):
            r = f(a, b)
            with jax.profiler.TraceAnnotation("bench/wait"):
                r.block_until_ready()
            with jax.profiler.TraceAnnotation("bench/pause"):
                time.sleep(0.002)
    trace_reduce.stop()
    shutil.copy(trace_reduce.find_xplane(out),
                os.path.join(HERE, "small.xplane.pb"))
    with open(os.path.join(HERE, "small.hlo.json"), "w") as fh:
        json.dump(hlo, fh)
    red = trace_reduce.reduce_dir(out, hlo)
    print(json.dumps({"window_s": red.window_s, "busy_s": red.busy_s,
                      "gemm_s": red.scope_time_s(["kernel/gemm"]),
                      "breakdown": red.breakdown()}))


if __name__ == "__main__":
    main()
