"""Serving cells: the program's ``ServeEngine`` under open-loop traffic.

Set-up makes the weights on the device, builds the engine, and warms every
shape the cell's traffic uses: one request per prompt-length bucket, which
compiles each prefill program, the decode program and the host-side ops
around them. The window then offers the seed's schedule
(`traffic.serve_schedule`): each request is submitted once it is due, and
the engine steps while it has work. Each token is stamped when the host
sees it: a first token when ``_admit`` samples it, every later one when the
decode step that made it returns. The window closes at ``--seconds``; what
is unfinished then stays unfinished.

After the window the program's state is freed, and the reference
(``reference/<family>.py``) runs once over a sample of the finished
requests, drawn from the seed with the longest among them: each prompt
followed by its served tokens. The numbers of ``serve_numbers`` (the
reference module's: gaps by which served tokens' logits lie below the
reference's best) that the cell's ``limits`` name decide ``correct``; the
others go to the notes.

End to end: output tokens per second over the window, and the median of
every gap between consecutive tokens of every request. The per-layer
readers also get TTFT from when each request was due to its first token (a
request unserved at the close counts the close), and the gaps.
"""
from __future__ import annotations

import collections
import gc
import math
import os
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, trace_reduce, traffic
from bench.model import ft_config, key_for, model_config


class Recorder:
    """Host spans around the engine's layers (prefill admission, decode
    dispatch, sampling) and the token stamps, from the benchmark's side."""

    def __init__(self, eng, clock, span):
        self.eng, self.clock, self.span = eng, clock, span
        self.admit_s = 0.0
        self.decode_steps: List[Dict[str, Any]] = []
        self._decode_t0 = None
        admit, decode, sample = eng._admit, eng._decode, eng._sample

        def timed_admit():
            t = clock()
            with span("bench/admit"):
                admit()
            self.admit_s += clock() - t

        def timed_decode(p, tok, cache):
            live = [s for s, r in enumerate(eng.slot_req) if r is not None]
            self._decode_t0 = clock()
            self.decode_steps.append({
                "t0": self._decode_t0, "live": len(live),
                "lengths": [int(eng.cur_len[s]) for s in live]})
            with span("bench/decode"):
                return decode(p, tok, cache)

        def timed_sample(logits):
            with span("bench/sample"):
                return sample(logits)

        self.originals = (admit, decode, sample)
        eng._admit, eng._decode, eng._sample = (timed_admit, timed_decode,
                                                timed_sample)

    def step_returned(self, t: float) -> None:
        """The engine's ``step()`` has returned at ``t``: that ends the
        decode step it ran, if it ran one."""
        if self._decode_t0 is not None:
            self.decode_steps[-1]["t1"] = t
            self._decode_t0 = None

    def restore(self) -> None:
        self.eng._admit, self.eng._decode, self.eng._sample = self.originals


class Session:
    def __init__(self, cell: harness.Cell):
        from repro.configs.base import RunConfig
        conf = cell.config
        self.cell = cell
        self.cfg = cfg = model_config(conf)
        self.dtype = jnp.dtype(conf["dtype"])
        self.traffic = cell.traffic
        self.run_cfg = RunConfig(model=cfg, ft=ft_config(conf),
                                 dtype=conf["dtype"])
        self.weights = cell.module("weights", cfg.family)
        self.make = jax.jit(lambda k: self.weights.make(cfg, k, self.dtype))

    def engine(self, params):
        from repro.train.engine import EngineConfig, ServeEngine
        e = self.cell.workload["engine"]
        ec = EngineConfig(max_len=e["max_len"], n_slots=e["n_slots"],
                          max_new_tokens=1, temperature=0.0, eos_id=-1)
        return ServeEngine(params, self.cfg, self.run_cfg, ec)

    def warm(self, eng, seed: int) -> None:
        """One request per prompt bucket, two tokens each, drained: every
        prefill length, the decode step and the ops around them compile."""
        rng = traffic.rng_for(seed, 3)
        for n in self.traffic["prompt"]["buckets"]:
            eng.submit(traffic.zipf_tokens(rng, (int(n),), self.cfg.vocab_size),
                       max_new_tokens=2)
        eng.run()
        eng.results.clear()

    def reference_numbers(self, seed: int, seqs, precision: str = "float32"):
        """The reference module's ``serve_numbers`` of the served requests
        ``seqs`` (or, with ``precision`` naming a control, of the tokens
        that the control puts first), with the seed's weights made again."""
        fam = self.cell.module("reference", self.cell.config["reference"])
        params = self.make(key_for(seed))
        return fam.serve_numbers(params, self.cfg, seqs, precision)


def _sample_requests(results, seed: int, min_tokens: int, max_requests: int):
    """The finished requests the comparison takes: the one with the most
    served tokens, then others in an order drawn from the seed, until
    ``min_tokens`` served tokens are in."""
    if not results:
        return []
    order = sorted(results, key=lambda r: (-len(r.tokens), r.rid))
    pick = [order[0]]
    rest = order[1:]
    rng = traffic.rng_for(seed, 4)
    for i in rng.permutation(len(rest)):
        if sum(len(r.tokens) for r in pick) >= min_tokens or \
                len(pick) >= max_requests:
            break
        pick.append(rest[i])
    return pick


def serve_window(sess: Session, eng, schedule, seconds: float, args,
                 trace_dir: str, span) -> Dict[str, Any]:
    clock = time.perf_counter
    rec = Recorder(eng, clock, span)
    pending = collections.deque(schedule)
    prompts = {}
    due_at: Dict[int, float] = {}
    submitted_at: Dict[int, float] = {}
    stamps: Dict[int, List[float]] = collections.defaultdict(list)
    seen_results = 0
    wl = sess.cell.workload
    trace_at = wl.get("trace_start_s", 0.0) if args.trace else None
    trace_len = wl.get("trace_seconds", 8.0)
    tracing = False
    trace_t = [None, None]

    def stamp(t_now):
        nonlocal seen_results
        for s, r in enumerate(eng.slot_req):
            if r is not None:
                _observe(r.rid, len(eng.gen[s]), r, t_now)
        while seen_results < len(eng.results):
            res = eng.results[seen_results]
            _observe(res.rid, len(res.tokens), None, t_now, res)
            seen_results += 1

    def _observe(rid, n, req, t_now, res=None):
        got = stamps[rid]
        if n <= len(got):
            return
        if not got:
            t_first = (req.t_submit + eng.ttft[eng.slot_req.index(req)]
                       if req is not None else submitted_at[rid] + res.ttft_s)
            got.append(t_first)
        while len(got) < n:
            got.append(t_now)

    t0 = clock()
    with span(trace_reduce.WINDOW if not args.trace else "bench/serve"):
        while True:
            now = clock() - t0
            if now >= seconds:
                break
            if trace_at is not None and not tracing and now >= trace_at \
                    and trace_t[0] is None:
                trace_reduce.start(trace_dir)
                tracing = True
                trace_t[0] = clock()
                win_span = span(trace_reduce.WINDOW)
                win_span.__enter__()
            if tracing and clock() - trace_t[0] >= trace_len:
                win_span.__exit__(None, None, None)
                trace_t[1] = clock()
                trace_reduce.stop()
                tracing = False
            while pending and pending[0].due_s <= now:
                r = pending.popleft()
                rid = eng.submit(r.prompt, max_new_tokens=r.max_new_tokens)
                due_at[rid] = t0 + r.due_s
                submitted_at[rid] = eng.queue[-1].t_submit
                prompts[rid] = r.prompt
            if eng.queue or any(x is not None for x in eng.slot_req):
                eng.step()
                t_step = clock()
                rec.step_returned(t_step)
                stamp(t_step)
            else:
                nxt = pending[0].due_s if pending else seconds
                time.sleep(max(0.0, min(nxt, seconds) - (clock() - t0)))
        t_end = clock()
    if tracing:
        win_span.__exit__(None, None, None)
        trace_t[1] = clock()
        trace_reduce.stop()
    unsubmitted = len(pending)
    n_due = len(schedule)
    due = dict(due_at)
    for k, r in enumerate(pending):           # due, never submitted
        due[-1 - k] = t0 + r.due_s
    ttft, gaps, tokens = traffic.latencies(due, stamps, t_end)
    late = [(submitted_at[r] - due_at[r]) * 1e3 for r in due_at]
    return {"t0": t0, "t_end": t_end, "elapsed": t_end - t0, "ttft_ms": ttft,
            "gaps_ms": gaps, "tokens": tokens, "n_due": n_due,
            "unsubmitted": unsubmitted, "late_ms": late, "rec": rec,
            "prompts": prompts, "trace_t": trace_t}


def run(cell: harness.Cell, args: harness.Args, *, t_start: float,
        device: Dict[str, Any]) -> harness.Run:
    sess = Session(cell)
    seed = args.seed
    params = sess.make(key_for(seed))
    eng = sess.engine(params)
    sess.warm(eng, seed)
    schedule = traffic.serve_schedule(seed, sess.traffic, args.seconds,
                                      sess.cfg.vocab_size)
    hlo = {}
    if args.trace:
        n = sess.cell.workload["engine"]["n_slots"]
        hlo = harness.hlo_text(eng._decode, params,
                               jnp.zeros((n, 1), jnp.int32), eng.cache)
    setup_s = time.perf_counter() - t_start
    span = harness.spans(args.trace)
    trace_dir = os.path.join(args.out_dir, "trace-" + cell.name)
    w = serve_window(sess, eng, schedule, args.seconds, args, trace_dir, span)
    peak = harness.memory_peak_bytes()
    results = list(eng.results)
    rec = w.pop("rec")
    rec.restore()
    layer: Dict[str, Any] = {
        "cfg": sess.cfg, "chips": int(cell.workload["chips"]),
        "peaks": harness.peaks(device["kind"], cell.bench_dir),
        "bench_dir": cell.bench_dir, "window_s": w["elapsed"],
        "admit_s": rec.admit_s, "decode_steps": rec.decode_steps,
        "trace_t": w["trace_t"]}
    busy = window_traced = breakdown = None
    if args.trace:
        red = trace_reduce.reduce_dir(trace_dir, hlo)
        layer["trace"] = red
        busy, window_traced = red.busy_s, red.window_s
        breakdown = red.breakdown()
    del eng, params, rec
    gc.collect()

    c = cell.workload["check"]
    pick = _sample_requests(results, seed, c["min_tokens"], c["max_requests"])
    seqs = [(w["prompts"][r.rid], np.asarray(r.tokens, np.int32))
            for r in pick]
    t_ref = time.perf_counter()
    nums = (sess.reference_numbers(seed, seqs) if seqs else
            {"argmax_miss": math.nan, "widest_gap": math.nan,
             "mean_gap": math.nan})
    t_ref = time.perf_counter() - t_ref
    checks = [harness.Check(k, nums[k], v)
              for k, v in cell.workload["limits"].items()]
    late = w["late_ms"]
    notes = {"requests_due": w["n_due"], "finished": len(results),
             "unsubmitted_at_close": w["unsubmitted"],
             "generator_late_ms_p50_max": [traffic.percentile(late, 50),
                                           max(late) if late else None],
             "checked_requests": len(pick),
             "checked_tokens": int(sum(len(s[1]) for s in seqs)),
             "setup_s": setup_s, "reference_s": t_ref,
             "served_token_gaps": nums,
             "ttft_p50_p90_ms": [traffic.percentile(w["ttft_ms"], q)
                                 for q in (50, 90)],
             "tpot_p95_ms": traffic.percentile(w["gaps_ms"], 95)}
    layer.update(ttft_ms=w["ttft_ms"], gaps_ms=w["gaps_ms"])
    e2e = {"tpot_p50_ms": traffic.percentile(w["gaps_ms"], 50),
           "serve_tokens_per_s": w["tokens"] / w["elapsed"],
           "setup_s": setup_s}
    return harness.Run(end_to_end=e2e, checks=checks, attempted=w["n_due"],
                       failed=0, memory_peak_bytes=peak, layer=layer,
                       breakdown=breakdown, busy_s=busy,
                       window_s=window_traced, notes=notes)
