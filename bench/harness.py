"""The benchmark's harness: find a cell's files by name, hold the run to the
contract, and print the result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by name under ``bench/``:

    workloads/<cell>.json     config and traffic names, kind, limits
    configs/<config>.json     the model configuration as it is run
    mixes/<traffic>.json      the traffic mix's parameters (``traffic.py``)
    loops/<kind>.py         the loop of that kind (``train`` | ``serve``)
    metrics/<metric>.py       one reader per per-layer metric
    work/<family>.py          FLOP and byte counts of a model family
    weights/<family>.py       seeded weights in the program's layout
    reference/<family>.py     the plain float32 reference of that family

``BENCHMARK.json`` at the checkout root says which metrics a cell reports.
A loop returns a `Run`: end-to-end values, the checks that decide
``correct`` (each with its limit), and what the per-layer readers need.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys
import time
from typing import Any, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Check:
    """One number that decides ``correct``: it passes when ``value`` is at
    most ``limit`` (every compared number here is a gap)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Run:
    """What a loop hands back to the harness."""
    end_to_end: Dict[str, float]
    checks: List[Check]
    attempted: int
    failed: int
    memory_peak_bytes: int
    #: Inputs of the per-layer readers (trace reduction, host spans, counts).
    layer: Dict[str, Any] = dataclasses.field(default_factory=dict)
    breakdown: Optional[Dict[str, list]] = None
    busy_s: Optional[float] = None
    window_s: Optional[float] = None
    #: Extra facts for standard error only (not read by any tool).
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


# ---------------------------------------------------------------------------
# finding files by name
# ---------------------------------------------------------------------------

def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import the Python file at ``path`` under a name made from its path."""
    rel = os.path.relpath(path, ROOT).replace(os.sep, "_")
    name = "bench_file_" + "".join(c if c.isalnum() else "_" for c in rel)
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    workload: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    bench_dir: str

    def module(self, kind: str, name: str):
        return load_module(os.path.join(self.bench_dir, kind, name + ".py"))


def find_cell(name: str, bench_dir: str = BENCH) -> Cell:
    wl = load_json(os.path.join(bench_dir, "workloads", name + ".json"))
    cfg = load_json(os.path.join(bench_dir, "configs", wl["config"] + ".json"))
    mix = load_json(os.path.join(bench_dir, "mixes", wl["traffic"] + ".json"))
    return Cell(name, wl, cfg, mix, bench_dir)


def cell_metrics(bench: Dict[str, Any], cell: str, trace: bool
                 ) -> List[Dict[str, Any]]:
    """The metrics ``BENCHMARK.json`` has this cell report: end-to-end ones
    untraced, per-layer ones traced."""
    rows = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in rows if cell in m.get("workloads", [cell])]


# ---------------------------------------------------------------------------
# device and set-up
# ---------------------------------------------------------------------------

def require_chips(chips: int) -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU (platform "
                            f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise NoAccelerator(f"{len(devs)} chips, the cell asks for {chips}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def host_device() -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program cached."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def peaks(device_kind: str, bench_dir: str = BENCH) -> Dict[str, float]:
    table = load_json(os.path.join(bench_dir, "peaks.json"))
    try:
        return table["kinds"][device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} in bench/peaks.json") from None


def hlo_text(jitted, *args) -> Dict[str, str]:
    """{program name: compiled HLO text} of ``jitted`` at ``args`` (from
    the compilation cache where it is there), for the trace's scopes."""
    text = jitted.lower(*args).compile().as_text()
    name = text.split("\n", 1)[0].split()[1].rstrip(",")
    return {name: text}


def memory_peak_bytes() -> int:
    import jax
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Args:
    workload: str
    seed: int
    seconds: float
    trace: bool
    out_dir: str = os.path.join(ROOT, "bench_out")


def spans(on: bool):
    """Host spans for the profiler trace when ``on``, else no-ops."""
    import contextlib
    import jax
    if on:
        return jax.profiler.TraceAnnotation
    return lambda name: contextlib.nullcontext()


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def execute(args: Args, *, bench_dir: str = BENCH,
            bench_json: Optional[Dict[str, Any]] = None,
            require_tpu: bool = True,
            t_start: Optional[float] = None) -> Dict[str, Any]:
    """Run one cell and return the result object (not yet printed).
    ``require_tpu=False`` skips the look for a chip (tests only).
    ``t_start`` is the process's start on the ``perf_counter`` clock, from
    which set-up is counted."""
    if t_start is None:
        t_start = time.perf_counter()
    cell = find_cell(args.workload, bench_dir)
    chips = int(cell.workload["chips"])
    if require_tpu:
        device = require_chips(chips)
    else:
        device = host_device()
    enable_compile_cache()
    if bench_json is None:
        bench_json = load_json(os.path.join(os.path.dirname(bench_dir),
                                            "BENCHMARK.json"))
    loop = cell.module("loops", cell.workload["kind"])
    run: Run = loop.run(cell, args, t_start=t_start, device=device)

    metrics: Dict[str, Dict[str, Any]] = {}
    for m in cell_metrics(bench_json, cell.name, args.trace):
        if args.trace:
            reader = cell.module("metrics", m["name"])
            value = reader.read(run.layer)
        else:
            value = run.end_to_end.get(m["name"])
        if value is None:
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=int(run.memory_peak_bytes))
    if args.trace:
        dev["busy_s"] = run.busy_s
        dev["window_s"] = run.window_s
    result: Dict[str, Any] = {
        "correct": run.correct, "attempted": int(run.attempted),
        "failed": int(run.failed), "metrics": metrics, "device": dev}
    if args.trace and run.breakdown is not None:
        result["breakdown"] = run.breakdown
    for k, v in run.notes.items():
        log(f"note {k}: {v}")
    for c in run.checks:
        log(f"check {c.name} {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'FAILED'}")
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in run.checks}
    return result


def main(argv=None, t_process: Optional[float] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    args = Args(a.workload, a.seed, a.seconds, bool(a.trace))
    try:
        result = execute(args, t_start=t_process)
    except NoAccelerator as e:
        log(f"error: {e}")
        return 3
    print(json.dumps(result), flush=True)
    return 0
