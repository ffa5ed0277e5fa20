#!/usr/bin/env python3
"""On-chip benchmark entry point.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs from the checkout root on the machine that holds the chips. See
``bench/harness.py`` for how a cell's files are found, and ``PERF.md`` for
the cells and metrics.
"""
import time

T_PROCESS = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "src"))

if __name__ == "__main__":
    from bench import harness
    sys.exit(harness.main(t_process=T_PROCESS))
