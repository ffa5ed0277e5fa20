"""From a profiler trace to the numbers the per-layer readers use.

``start``/``stop`` wrap ``jax.profiler``; the traced window is the host span
``bench/window`` that the loop opens inside it. `reduce_dir` reads the
``.xplane.pb`` the profiler wrote (with nothing but ``jax.profiler``) into a
`Reduction`:

* each device's op events (the ``XLA Ops`` line of each ``/device:TPU:n``
  plane), clipped to the window; an event's name is its HLO instruction's
  text, and control flow (``while``, ``conditional``, ``call``) is left out
  because its events enclose the ops of its body;
* busy time: the union of those intervals, per device;
* the name scope of each op: the ``op_name`` metadata of its instruction in
  the compiled program that ran it (`scope_map`; the program is the
  ``XLA Modules`` event around the op), so that device time can be
  attributed to the program's ``kernel/...`` scopes;
* the host's spans (``bench/...``) on the same clock, so that each idle gap
  on a device can be put down to what the host was doing.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW = "bench/window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CONTAINERS = ("while", "conditional", "call")

Interval = Tuple[int, int]


def start(trace_dir: str) -> None:
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    jax.profiler.start_trace(trace_dir)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def union_length(intervals: Iterable[Interval]) -> int:
    """Total length covered by half-open [start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


@dataclasses.dataclass
class Op:
    name: str          # the HLO instruction's name, e.g. "kernel_call.87"
    start: int
    end: int
    module: str        # the program, e.g. "jit_train_step"
    scope: str         # its op_name metadata, where known

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclasses.dataclass
class Span:
    name: str
    start: int
    end: int


_INST = re.compile(r"^%?([\w.\-]+)")


@dataclasses.dataclass
class Reduction:
    window: Interval
    ops: Dict[str, List[Op]]           # device plane -> ops in the window
    spans: List[Span]                  # host spans in the window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_ns(self, device: str) -> int:
        return union_length((o.start, o.end) for o in self.ops[device])

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices."""
        if not self.ops:
            return 0.0
        return sum(self.busy_ns(d) for d in self.ops) / len(self.ops) * 1e-9

    def scope_time_s(self, prefixes: Iterable[str]) -> float:
        """Device seconds of ops whose scope holds one of ``prefixes``,
        summed over devices and averaged over them."""
        prefixes = tuple(prefixes)
        tot = 0
        for ops in self.ops.values():
            tot += sum(o.dur for o in ops
                       if any(p in o.scope for p in prefixes))
        return tot / max(len(self.ops), 1) * 1e-9

    def top_ops(self, n: int = 10) -> List[list]:
        """Device seconds by kind of op (the instruction's name without its
        number, and the last two parts of its scope where known), averaged
        over the devices."""
        acc: Dict[str, int] = {}
        for ops in self.ops.values():
            for o in ops:
                key = kind(o.name)
                if o.scope:
                    key += " " + short_scope(o.scope)
                acc[key] = acc.get(key, 0) + o.dur
        k = max(len(self.ops), 1)
        rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / k * 1e-9] for name, ns in rows]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """The longest idle gaps of the first device, each named by the
        innermost host span that covers its midpoint."""
        if not self.ops:
            return []
        dev = sorted(self.ops)[0]
        busy = merge((o.start, o.end) for o in self.ops[dev])
        edges = [self.window[0]] + [x for iv in busy for x in iv] + \
            [self.window[1]]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:n]:
            mid = (s + e) // 2
            cover = [sp for sp in self.spans if sp.start <= mid < sp.end]
            name = min(cover, key=lambda sp: sp.end - sp.start).name \
                if cover else "host: outside any span"
            out.append([name, (e - s) * 1e-9])
        return out

    def breakdown(self) -> Dict[str, list]:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def inst_name(event_name: str) -> str:
    """"%kernel_call.87 = (bf16[...]) custom-call(...)" -> "kernel_call.87"."""
    m = _INST.match(event_name)
    return m.group(1) if m else event_name


def kind(inst: str) -> str:
    """"kernel_call.87" -> "kernel_call"; "pad.362.clone" -> "pad"."""
    return re.sub(r"(\.(\d+|clone))+$", "", inst)


def short_scope(scope: str) -> str:
    """The program's ``kernel/...`` scope where the op has one (up to the
    kernel's own nested jit), else the last two parts of its scope."""
    i = scope.find("kernel/")
    if i >= 0:
        return scope[i:].split("/jit(")[0]
    return "/".join(scope.split("/")[-2:])


def module_name(event_name: str) -> str:
    """"jit_train_step(16069231217728693045)" -> "jit_train_step"."""
    return event_name.split("(")[0]


def scope_map(hlo_texts: Dict[str, str]) -> Dict[Tuple[str, str], str]:
    """(module, instruction) -> op_name metadata, from the compiled HLO text
    of each program (``compiled.as_text()``)."""
    out: Dict[Tuple[str, str], str] = {}
    pat = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?"
                     r"op_name=\"([^\"]*)\"", re.M)
    for module, text in hlo_texts.items():
        for inst, op_name in pat.findall(text):
            out[(module, inst)] = op_name
    return out


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce_file(path: str, hlo_texts: Optional[Dict[str, str]] = None
                ) -> Reduction:
    """``hlo_texts``: compiled HLO text by program name, for the scopes of
    the ops of those programs."""
    import bisect
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    scopes = scope_map(hlo_texts or {})
    spans: List[Span] = []
    raw_ops: Dict[str, List[Op]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench/"):
                        s = int(ev.start_ns)
                        spans.append(Span(ev.name, s, s + int(ev.duration_ns)))
        elif plane.name.startswith("/device:TPU"):
            lines = {line.name: line for line in plane.lines}
            mods = sorted((int(ev.start_ns), int(ev.start_ns)
                           + int(ev.duration_ns), module_name(ev.name))
                          for ev in (lines[MODULES_LINE].events
                                     if MODULES_LINE in lines else ()))
            starts = [m[0] for m in mods]
            ops = []
            for ev in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
                inst = inst_name(ev.name)
                if kind(inst) in CONTAINERS:
                    continue
                s = int(ev.start_ns)
                j = bisect.bisect_right(starts, s) - 1
                module = mods[j][2] if j >= 0 and s < mods[j][1] else ""
                ops.append(Op(inst, s, s + int(ev.duration_ns), module,
                              scopes.get((module, inst), "")))
            raw_ops[plane.name] = ops
    win = [s for s in spans if s.name == WINDOW]
    if win:
        w = (win[0].start, win[0].end)
    else:
        allops = [o for ops in raw_ops.values() for o in ops]
        w = (min(o.start for o in allops), max(o.end for o in allops)) \
            if allops else (0, 0)
    clipped = {}
    for dev, ops in raw_ops.items():
        clipped[dev] = [dataclasses.replace(o, start=max(o.start, w[0]),
                                            end=min(o.end, w[1]))
                        for o in ops if o.end > w[0] and o.start < w[1]]
    spans = [dataclasses.replace(s, start=max(s.start, w[0]),
                                 end=min(s.end, w[1]))
             for s in spans if s.end > w[0] and s.start < w[1]]
    return Reduction(w, clipped, spans)


def reduce_dir(trace_dir: str, hlo_texts: Optional[Dict[str, str]] = None
               ) -> Reduction:
    return reduce_file(find_xplane(trace_dir), hlo_texts)
