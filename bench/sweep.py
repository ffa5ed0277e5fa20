#!/usr/bin/env python3
"""Find a serving cell's knee: the same engine under the cell's traffic mix
at several fixed open-loop rates, one window each, in one process.

    python bench/sweep.py --workload <cell> --rates 1,2,3,4 --seconds 30

Prints one JSON line per rate: requests due and finished, TTFT p50/p90,
token-gap p50/p95, output tokens per second, and how many requests were
still queued or unsubmitted at the close (a backlog that grows with the
window means the rate is above what the engine sustains). Not part of the
benchmark's runs; exits non-zero without a TPU.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "src"))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args()
    from bench import harness, traffic
    from bench.model import key_for
    cell = harness.find_cell(a.workload)
    harness.require_chips(int(cell.workload["chips"]))
    harness.enable_compile_cache()
    drv = cell.module("loops", "serve")
    sess = drv.Session(cell)
    params = sess.make(key_for(a.seed))
    eng = sess.engine(params)
    sess.warm(eng, a.seed)
    args = harness.Args(cell.name, a.seed, a.seconds, False)
    for rate in (float(r) for r in a.rates.split(",")):
        tr = dict(sess.traffic, rate_per_s=rate)
        sched = traffic.serve_schedule(a.seed, tr, a.seconds,
                                       sess.cfg.vocab_size)
        w = drv.serve_window(sess, eng, sched, a.seconds, args, "",
                             harness.spans(False))
        backlog = len(eng.queue) + w["unsubmitted"]
        live = sum(r is not None for r in eng.slot_req)
        pct = traffic.percentile
        print(json.dumps({
            "rate_per_s": rate, "due": w["n_due"],
            "finished": len(eng.results), "queued_at_close": backlog,
            "live_at_close": live,
            "ttft_p50_ms": pct(w["ttft_ms"], 50),
            "ttft_p90_ms": pct(w["ttft_ms"], 90),
            "tpot_p50_ms": pct(w["gaps_ms"], 50),
            "tpot_p95_ms": pct(w["gaps_ms"], 95),
            "tokens_per_s": w["tokens"] / w["elapsed"],
            "prefill_share": w["rec"].admit_s / w["elapsed"],
            "late_ms_max": max(w["late_ms"]) if w["late_ms"] else None}),
            flush=True)
        w["rec"].restore()            # then drain what is left
        eng.queue.clear()
        eng.run()
        eng.results.clear()


if __name__ == "__main__":
    main()
