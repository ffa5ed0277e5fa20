#!/usr/bin/env python3
"""Readings that set a cell's limits, many seeds in one process.

    python bench/study.py --workload <cell> --seeds 1,2,3 \
        --what program,control[,half_batch,token] [--seconds 20]
    python bench/study.py --workload <train cell> --seeds 1,2 \
        --what ft_report --backends xla [--steps 6]

For each seed it prints one JSON line with the numbers `compare` computes:

* ``program``: the program's own run against the float32 reference (train:
  the first steps; serve: a window of ``--seconds`` at the cell's load,
  then the sampled finished requests), the lower readings;
* ``control``: the reference computed in int8 put in the program's place
  (train: int8 products in every forward and backward GEMM; serve: the
  token int8 logits put first at each position of the same requests);
* ``half_batch``, ``token`` (train): the reference with that fault planted
  (the mean over half of each batch; one input token altered);
* ``ft_report`` (train): the FT report's detections and the loss of each of
  ``--steps`` clean steps, per site, with the cell's FT backend and again
  with each backend of ``--backends`` (another path of the program).

Not part of the benchmark's runs; it exits non-zero without a TPU.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "src"))


def train_study(cell, seeds, what):
    from bench import compare
    drv = cell.module("loops", "train")
    sess = drv.Session(cell)
    for seed in seeds:
        t = time.perf_counter()
        out = {"seed": seed}
        ref = sess.reference(seed)
        out["ref_s"] = time.perf_counter() - t
        if "program" in what:
            params, opt, prog = sess.first_steps(seed)
            del params, opt
            gc.collect()
            out["program"] = compare.train_numbers(prog, ref)
            out["worst_leaves"] = compare.worst_leaves(prog, ref)
            out["detections"] = prog["detections"]
        for fault in [w for w in what if w != "program"]:
            alt = (sess.reference(seed, precision="int8") if fault == "control"
                   else sess.reference(seed, fault=fault))
            out[fault] = compare.train_numbers(alt, ref)
        out["s"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)


def ft_report_study(cell, seeds, backends, steps):
    """Per step of a clean run: the FT report's detections (in total and
    by site) and the loss, for each FT backend in turn."""
    import jax
    from repro.core import telemetry
    from bench.model import ft_config, key_for
    drv = cell.module("loops", "train")
    for backend in backends:
        conf = dict(cell.config, ft=dict(cell.config["ft"], backend=backend))
        sess = drv.Session(cell, ft=ft_config(conf))
        for seed in seeds:
            params = sess.make(key_for(seed))
            opt = sess.init_opt(params)
            rows = []
            for i in range(steps):
                params, opt, m = sess.step(params, opt, sess.feed(seed, i),
                                           sess.schedule_step(i), None)
                by_site = np.asarray(m["ft"].site_detected).sum(0)
                labels = telemetry.site_labels()
                rows.append({
                    "loss": float(m["loss"]),
                    "detected": float(m["ft"].detected),
                    "max_residual": float(m["ft"].max_residual),
                    "sites": {labels[j] if j < len(labels) else str(j):
                              float(v) for j, v in enumerate(by_site) if v}})
            print(json.dumps({"seed": seed, "backend": backend,
                              "steps": rows}), flush=True)
            del params, opt, m
            gc.collect()
        del sess
        jax.clear_caches()
        gc.collect()


def serve_study(cell, seeds, what, seconds):
    """One engine for every seed: each seed's weights are put in its place
    (same shapes, so nothing compiles again), the engine is emptied, and a
    window of the cell's traffic runs; the reference then reads the same
    weights, with the engine still on the chip."""
    from bench import harness, traffic
    from bench.model import key_for
    drv = cell.module("loops", "serve")
    sess = drv.Session(cell)
    c = cell.workload["check"]
    args = harness.Args(cell.name, 0, seconds, False)
    eng = None
    for seed in seeds:
        t = time.perf_counter()
        if eng is not None:
            eng.params = None          # free the last seed's weights first
            gc.collect()
        params = sess.make(key_for(seed))
        if eng is None:
            eng = sess.engine(params)
            sess.warm(eng, seed)
        eng.params = params
        sched = traffic.serve_schedule(seed, sess.traffic, seconds,
                                       sess.cfg.vocab_size)
        w = drv.serve_window(sess, eng, sched, seconds, args, "",
                             harness.spans(False))
        w["rec"].restore()
        results = list(eng.results)
        eng.queue.clear()
        for s in range(len(eng.slot_req)):
            if eng.slot_req[s] is not None:
                eng._finish(s)
        eng.results.clear()
        pick = drv._sample_requests(results, seed, c["min_tokens"],
                                    c["max_requests"])
        seqs = [(w["prompts"][r.rid], np.asarray(r.tokens, np.int32))
                for r in pick]
        fam = cell.module("reference", cell.config["reference"])
        out = {"seed": seed, "finished": len(results),
               "checked_tokens": sum(len(s) for _, s in seqs)}
        if "program" in what:
            out["program"] = fam.serve_numbers(params, sess.cfg, seqs)
        if "control" in what:
            out["control"] = fam.serve_numbers(params, sess.cfg, seqs,
                                               "int8")
        del params
        out["s"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program,control")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--backends", default="")
    a = ap.parse_args()
    from bench import harness
    cell = harness.find_cell(a.workload)
    harness.require_chips(int(cell.workload["chips"]))
    harness.enable_compile_cache()
    seeds = [int(s) for s in a.seeds.split(",")]
    what = a.what.split(",")
    if what == ["ft_report"]:
        backends = [cell.config["ft"]["backend"]] + [
            b for b in a.backends.split(",") if b]
        ft_report_study(cell, seeds, backends, a.steps)
    elif cell.workload["kind"] == "train":
        train_study(cell, seeds, what)
    else:
        serve_study(cell, seeds, what, a.seconds)


if __name__ == "__main__":
    main()
