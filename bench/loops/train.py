"""Training cells: the program's jitted, donated train step
(``train_loop.make_train_step``) driven from the seed.

Set-up builds the step once, makes the weights on the device, and drives
the step through its first three steps on the window's own feed; those
compile it. The window then runs the same step object on fresh rows until
``--seconds`` have passed, one step after another, each ending in the
host's read of its loss. After the window the program's state is freed and
the reference (``reference/<family>.py`` through ``reference/train.py``)
follows the same three steps in float32; `compare.train_numbers` and the
FT report's count of detections over every step of the run (the data is
clean, so the reference's count is 0) decide ``correct``.

With ``--trace 1`` the run also times the step with FT off against FT on
(same weights, same batch, before the profiler starts), then records a
profiler trace of a few seconds of steps.
"""
from __future__ import annotations

import gc
import math
import os
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, harness, trace_reduce, traffic
from bench.model import ft_config, key_for, model_config

#: Steps the set-up drives and the reference follows.
FIRST_STEPS = 3



class Session:
    """The program's compiled train step for one cell, and what its
    comparison needs. One object serves every seed of a process."""

    def __init__(self, cell: harness.Cell, ft=None):
        from repro.optim import adamw
        from repro.configs.base import RunConfig
        from repro.train import train_loop
        self.cell = cell
        conf, tr = cell.config, cell.traffic
        self.cfg = cfg = model_config(conf)
        self.batch, self.seq = int(tr["batch"]), int(tr["seq"])
        self.tokens_per_step = self.batch * self.seq
        self.dtype = jnp.dtype(conf["dtype"])
        o = conf["optimizer"]
        self.opt = o
        self.opt_cfg = adamw.AdamWConfig(
            lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
            weight_decay=o["weight_decay"], grad_clip=o["grad_clip"])
        self.tc = train_loop.TrainConfig(total_steps=o["total_steps"],
                                         warmup_steps=o["warmup_steps"])
        self.ft = ft_config(conf) if ft is None else ft
        self.weights = cell.module("weights", cfg.family)
        self.make = jax.jit(lambda k: self.weights.make(cfg, k, self.dtype))

        def init_opt(p):
            return train_loop.init_opt_state(p, self.opt_cfg, self.tc)

        self.init_opt = jax.jit(init_opt)
        self.run_cfg = RunConfig(model=cfg, ft=self.ft, dtype=conf["dtype"],
                                 remat=conf["remat"])
        self.step = self.make_step(self.ft)
        b1 = o["b1"]
        self.grad_norms = jax.jit(lambda m: jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x))) / (1 - b1), m))

        def change_norms(p, key):
            p0 = self.weights.make(cfg, key, self.dtype)
            return jax.tree.map(
                lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
                    a.astype(jnp.float32) - b.astype(jnp.float32)))), p, p0)

        self.change_norms = jax.jit(change_norms)

    def make_step(self, ft):
        import dataclasses
        from repro.train import train_loop
        run = dataclasses.replace(self.run_cfg, ft=ft)
        return jax.jit(train_loop.make_train_step(self.cfg, run, self.opt_cfg,
                                                  self.tc),
                       donate_argnums=(0, 1))

    def feed(self, seed: int, i: int) -> Dict[str, jax.Array]:
        b = traffic.train_batch(seed, i, self.batch, self.seq,
                                self.cfg.vocab_size)
        return {k: jnp.asarray(v) for k, v in b.items()}

    @staticmethod
    def schedule_step(i: int) -> jax.Array:
        """The schedule's step number of the i-th step of a run (the
        warm-up's first step has rate 0, so counting starts at 1)."""
        return jnp.asarray(i + 1, jnp.int32)

    def first_steps(self, seed: int):
        """Weights from the seed, then the first steps through the step
        object. Returns (params, opt, readings)."""
        from bench.reference.train import named_leaves
        key = key_for(seed)
        params = self.make(key)
        opt = self.init_opt(params)
        losses, dets, grad = [], [], None
        for i in range(FIRST_STEPS):
            params, opt, m = self.step(params, opt, self.feed(seed, i),
                                       self.schedule_step(i), None)
            losses.append(float(m["loss"]))
            dets.append(float(m["ft"].detected))
            if i == 0:
                grad = {k: float(v) for k, v in named_leaves(
                    self.grad_norms(opt["adam"]["m"])).items()}
        change = {k: float(v) for k, v in named_leaves(
            self.change_norms(params, key)).items()}
        return params, opt, {"losses": losses, "grad": grad,
                             "change": change, "detections": sum(dets)}

    def reference(self, seed: int, precision: str = "float32",
                  fault: str = "") -> Dict[str, Any]:
        """The reference over the same first steps, from the same seed.
        ``fault`` plants one of the faults the tests name: ``half_batch``
        (the mean over the first half of each batch only) or ``token``
        (one input token of each batch altered)."""
        from bench.reference.train import TrainReference
        fam = self.cell.module("reference", self.cell.config["reference"])
        ref = TrainReference(fam, self.cfg, self.opt, precision)
        key = key_for(seed)
        to_f32 = jax.jit(lambda k: jax.tree.map(
            lambda x: x.astype(jnp.float32),
            self.weights.make(self.cfg, k, self.dtype)))
        batches = []
        for i in range(FIRST_STEPS):
            b = traffic.train_batch(seed, i, self.batch, self.seq,
                                    self.cfg.vocab_size)
            if fault == "half_batch":
                b = {k: v[: max(1, self.batch // 2)] for k, v in b.items()}
            elif fault == "token":
                b = {k: v.copy() for k, v in b.items()}
                b["tokens"][0, self.seq // 2] = (
                    b["tokens"][0, self.seq // 2] + 1) % self.cfg.vocab_size
            elif fault:
                raise ValueError(f"unknown fault {fault!r}")
            batches.append(b)
        stored = jax.tree.map(lambda s: s.dtype, jax.eval_shape(
            lambda k: self.weights.make(self.cfg, k, self.dtype), key))
        with jax.default_matmul_precision("highest"):
            return ref.run(lambda: to_f32(key), batches,
                           [int(self.schedule_step(i)) for i in
                            range(FIRST_STEPS)], stored)


def _timed_steps(step, params, opt, batch, i0: int, n: int):
    t0 = time.perf_counter()
    for i in range(n):
        params, opt, m = step(params, opt, batch, Session.schedule_step(i0 + i),
                              None)
    jax.block_until_ready(m["loss"])
    return params, opt, time.perf_counter() - t0


def ft_overhead(sess: Session, params, opt, seed: int, i0: int):
    """Step time with the cell's FT policy over the same step with FT off,
    minus one: rounds of a few steps each way on one batch, each round
    timed by the host clock from dispatch to the last loss."""
    from repro.core.policy import FT_OFF
    off = sess.make_step(FT_OFF)
    batch = sess.feed(seed, i0)
    params, opt, _ = _timed_steps(off, params, opt, batch, i0, 1)  # compile
    params, opt, t1 = _timed_steps(sess.step, params, opt, batch, i0, 1)
    n = max(2, int(math.ceil(0.5 / max(t1, 1e-3))))
    on_t, off_t = [], []
    for r in range(3):
        params, opt, t = _timed_steps(sess.step, params, opt, batch, i0, n)
        on_t.append(t / n)
        params, opt, t = _timed_steps(off, params, opt, batch, i0, n)
        off_t.append(t / n)
    on, offm = float(np.median(on_t)), float(np.median(off_t))
    return params, opt, {"ft_on_step_s": on, "ft_off_step_s": offm,
                         "rounds": n}


def run(cell: harness.Cell, args: harness.Args, *, t_start: float,
        device: Dict[str, Any]) -> harness.Run:
    sess = Session(cell)
    seed = args.seed
    params, opt, prog = sess.first_steps(seed)
    i = FIRST_STEPS
    layer: Dict[str, Any] = {"cfg": sess.cfg, "seq": sess.seq,
                             "tokens_per_step": sess.tokens_per_step,
                             "chips": int(cell.workload["chips"]),
                             "peaks": harness.peaks(device["kind"],
                                                    cell.bench_dir),
                             "bench_dir": cell.bench_dir}
    busy = window_traced = breakdown = None
    hlo = {}
    if args.trace:
        params, opt, layer["ft_timing"] = ft_overhead(sess, params, opt,
                                                      seed, i)
        i += 1
        hlo = harness.hlo_text(sess.step, params, opt, sess.feed(seed, i),
                               sess.schedule_step(i), None)
    setup_s = time.perf_counter() - t_start

    # The window: one step after another; the next batch is made on the
    # host while the device runs the step.
    trace_dir = os.path.join(args.out_dir, "trace-" + cell.name)
    window = min(args.seconds, cell.workload.get("trace_seconds", 5.0)) \
        if args.trace else args.seconds
    if args.trace:
        trace_reduce.start(trace_dir)
    span = harness.spans(args.trace)
    batch = sess.feed(seed, i)
    det = []
    n = 0
    with span(trace_reduce.WINDOW):
        t0 = time.perf_counter()
        while True:
            with span("bench/dispatch"):
                params, opt, m = sess.step(params, opt, batch,
                                           sess.schedule_step(i), None)
            i += 1
            n += 1
            with span("bench/feed"):
                batch = sess.feed(seed, i)
            det.append(m["ft"].detected)
            with span("bench/wait"):
                jax.block_until_ready(m["loss"])
            t1 = time.perf_counter()
            if t1 - t0 >= window:
                break
    elapsed = t1 - t0
    if args.trace:
        trace_reduce.stop()
        red = trace_reduce.reduce_dir(trace_dir, hlo)
        layer["trace"] = red
        busy, window_traced = red.busy_s, red.window_s
        breakdown = red.breakdown()
    window_det = float(sum(float(x) for x in det))
    layer["ft_detections"] = window_det
    layer["steps"] = n
    peak = harness.memory_peak_bytes()
    del params, opt, m, batch
    gc.collect()

    t_ref = time.perf_counter()
    ref = sess.reference(seed)
    t_ref = time.perf_counter() - t_ref
    nums = compare.train_numbers(prog, ref)
    # The data is clean: every detection the FT report makes is false.
    nums["ft_detections"] = prog["detections"] + window_det
    lim = cell.workload["limits"]
    checks = [harness.Check(k, v, lim[k]) for k, v in nums.items()]
    notes = {"worst_leaves": compare.worst_leaves(prog, ref),
             "ft_detections_first_steps": prog["detections"],
             "ft_detections_window": window_det,
             "losses_program": prog["losses"], "losses_reference":
             ref["losses"], "steps_in_window": n, "setup_s": setup_s,
             "reference_s": t_ref}
    return harness.Run(
        end_to_end={"train_tokens_per_s": n * sess.tokens_per_step / elapsed,
                    "setup_s": setup_s},
        checks=checks, attempted=n, failed=0, memory_peak_bytes=peak,
        layer=layer, breakdown=breakdown, busy_s=busy,
        window_s=window_traced, notes=notes)
