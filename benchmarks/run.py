"""Benchmark harness — one module per paper table/figure (DESIGN.md §7).

    PYTHONPATH=src python -m benchmarks.run [--only ft_schemes]

Prints ``name,us_per_call,derived`` CSV rows (us = NaN for structural-only
rows; see benchmarks/common.py for what transfers to TPU and what is a
CPU-trend measurement).
"""
import argparse
import sys
import traceback

SUITES = ("stepwise_gemm", "ft_schemes", "codegen_shapes",
          "fused_epilogue", "error_injection", "online_vs_offline",
          "moe_dispatch", "flash_attention", "backward_path",
          "tune_campaign", "telemetry_overhead", "serve_engine", "ft_plan")


def main() -> None:
    import contextlib

    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=SUITES)
    ap.add_argument("--trace-dir", default=None,
                    help="capture a Perfetto-compatible profiler trace of "
                         "the selected suites into this directory (open "
                         "with ui.perfetto.dev)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.trace_dir:
        from repro.tools.trace import trace_dump
        tracer = trace_dump(args.trace_dir)
    else:
        tracer = contextlib.nullcontext()
    print("name,us_per_call,derived")
    failed = []
    with tracer:
        for name in SUITES:
            if args.only and name != args.only:
                continue
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            try:
                mod.run()
            except Exception:                     # noqa: BLE001
                traceback.print_exc()
                failed.append(name)
    if failed:
        print(f"FAILED suites: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
