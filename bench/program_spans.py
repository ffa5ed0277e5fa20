"""The program's own spans and counters, as the serving readers take them.

``repro.tools.trace`` keeps the program's host spans in a process-wide ring
(`records`), on the ``time.perf_counter`` clock that the benchmark's loop
stamps too. A program older than that ring has no `records`: every reader
built on this module then reads nothing and returns None.

Host-side readers take the records of the serving window (`window`): those
that end between the start of the window's first decode step and the end
of its last (``layer["decode_steps"]``), so warm-up and the reference are
left out.

`idle_by_record` puts the device's idle time in the traced window down to
the innermost program record covering the host at each instant. The loop
stamps ``layer["trace_t"][0]`` as it opens the ``bench/window``
annotation, where the trace's window starts (``Reduction.window[0]``):
that pair maps the ring's clock onto the trace's.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

from bench import trace_reduce


def ring() -> List[Any]:
    """Every record the program's ring holds (none from an older program)."""
    from repro.tools import trace
    read = getattr(trace, "records", None)
    return read() if read is not None else []


def window(layer: Dict[str, Any]) -> Optional[List[Any]]:
    """The ring's records that end inside the serving window, or None."""
    steps = [s for s in layer.get("decode_steps", ()) if "t1" in s]
    if not steps:
        return None
    t0 = steps[0]["t0"] * 1e9
    t1 = max(s["t1"] for s in steps) * 1e9
    recs = [r for r in ring() if t0 <= r.end_ns <= t1]
    return recs or None


def innermost(recs, lo: int, hi: int) -> List[tuple]:
    """[(a, b, name)] over [lo, hi): the innermost of ``recs`` ((start, end,
    name), nested as spans nest) covering each piece, the latest to start;
    name None where none covers."""
    recs = sorted(recs)
    cuts = sorted({min(max(x, lo), hi) for s, e, _ in recs for x in (s, e)}
                  | {lo, hi})
    out, active, j = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(recs) and recs[j][0] <= a:
            active.append(recs[j])
            j += 1
        active = [r for r in active if r[1] > a]
        inner = max(active, key=lambda r: (r[0], -r[1]))[2] if active \
            else None
        out.append((a, b, inner))
    return out


def idle_by_record(layer: Dict[str, Any]) -> Optional[Dict[Any, int]]:
    """Device 0's idle ns in the traced window by the name of the innermost
    program record covering the host then (None: no record). A request's
    ``serve/queued`` wait covers no host work and is left out. None without
    a traced window or without program records in it."""
    red = layer.get("trace")
    t0 = (layer.get("trace_t") or (None, None))[0]
    if red is None or t0 is None or not red.ops:
        return None
    shift = red.window[0] - int(round(t0 * 1e9))
    w0, w1 = red.window
    recs = [(r.start_ns + shift, r.end_ns + shift, r.name) for r in ring()
            if r.name != "serve/queued"]
    recs = [r for r in recs if r[1] > w0 and r[0] < w1]
    if not recs:
        return None
    busy = trace_reduce.merge((o.start, o.end)
                              for o in red.ops[sorted(red.ops)[0]])
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    out: Dict[Any, int] = {}
    i = 0
    for a, b, name in innermost(recs, w0, w1):
        while i < len(idle) and idle[i][1] <= a:
            i += 1
        k = i
        while k < len(idle) and idle[k][0] < b:
            ov = min(b, idle[k][1]) - max(a, idle[k][0])
            if ov > 0:
                out[name] = out.get(name, 0) + ov
            k += 1
    return out
