"""Host spans, trace-time scopes and the in-memory span ring.

  * `span(name, rid=None, **attrs)` — a host span. It opens a
    `jax.profiler.TraceAnnotation` (a named slice on the host track of a
    profiler trace, with ``attrs`` as its stats, when a trace is active)
    and, whether or not one is, appends a `Record` to a process-wide
    ring of the last `RING_SIZE` spans on the `time.perf_counter_ns`
    clock. Spans nest per thread: each record names the span that
    enclosed it (``parent``) and inherits its request id. Attributes known
    only at the end go into the handle's ``attrs`` before it closes.
  * `record(name, start_ns, end_ns, rid=None, **attrs)` — an interval known
    only after the fact (a request's queue wait), under the open span.
  * `records()` — the ring, oldest first.
  * `watch_compiles()` — from then on every jaxpr trace and backend compile
    JAX reports through `jax.monitoring` becomes a ``jax/trace`` or
    ``jax/compile`` record under the span open at the time.
  * `traced_span(name)` / `@traced(name)` — a trace-time `jax.named_scope`
    on the kernel dispatch fronts. The name reaches the compiled program's
    ``op_name`` metadata (joined back to the trace's op events by
    instruction), not the op events' own names.
  * `trace_dump(dir)` — capture a Perfetto-compatible profiler trace of the
    enclosed block (`jax.profiler.start_trace`/`stop_trace`);
    `benchmarks/run.py --trace-dir` wraps suites with it.

The ring's records and the profiler's host events share one clock up to a
constant offset: a reader that stamps `time.perf_counter` where it opens an
annotation of its own maps records onto the trace by that anchor.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import itertools
import threading
import time
from typing import Any, Callable, Deque, Dict, Iterator, List, NamedTuple, \
    Optional

import jax

#: Records the ring holds: a decode step of the serving engine makes about
#: ten, so this keeps ~3,000 steps (minutes of serving) before the oldest
#: drop.
RING_SIZE = 1 << 15

#: `jax.monitoring` duration events -> the record each becomes.
JAX_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "jax/trace",
              "/jax/core/compile/backend_compile_duration": "jax/compile"}


class Record(NamedTuple):
    name: str
    start_ns: int                 # time.perf_counter_ns()
    end_ns: int
    id: int
    parent: Optional[int]         # id of the enclosing span, if any
    rid: Optional[int]            # request id, if any
    attrs: Dict[str, Any]


#: Plain tuples in `Record`'s field order (cheaper to make than a `Record`).
_RING: Deque[tuple] = collections.deque(maxlen=RING_SIZE)
_Annotation = jax.profiler.TraceAnnotation
_IDS = itertools.count(1)
_LOCAL = threading.local()
_WATCH_LOCK = threading.Lock()
_WATCHING = False


def _stack() -> List["span"]:
    st = getattr(_LOCAL, "stack", None)
    if st is None:
        st = _LOCAL.stack = []
    return st


class span:
    """Host span around a layer boundary or a step phase (see the module
    docstring). ``with span(...) as sp:`` yields the handle; ``sp.attrs``
    may be added to until the span closes."""

    __slots__ = ("name", "rid", "attrs", "id", "parent", "start_ns", "_ann")

    def __init__(self, name: str, rid: Optional[int] = None, **attrs: Any):
        self.name = name
        self.rid = rid
        self.attrs = attrs

    def __enter__(self) -> "span":
        st = _stack()
        if st:
            top = st[-1]
            self.parent = top.id
            if self.rid is None:
                self.rid = top.rid
        else:
            self.parent = None
        self.id = next(_IDS)
        if self.rid is None:
            self._ann = _Annotation(self.name, **self.attrs)
        else:
            self._ann = _Annotation(self.name, rid=self.rid, **self.attrs)
        self._ann.__enter__()
        st.append(self)
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        _stack().pop()
        _RING.append((self.name, self.start_ns, end, self.id, self.parent,
                      self.rid, self.attrs))


def record(name: str, start_ns: int, end_ns: int,
           rid: Optional[int] = None, **attrs: Any) -> Record:
    """Append an interval measured elsewhere, under the open span."""
    st = _stack()
    parent = st[-1].id if st else None
    if rid is None and st:
        rid = st[-1].rid
    rec = (name, int(start_ns), int(end_ns), next(_IDS), parent, rid, attrs)
    _RING.append(rec)
    return Record._make(rec)


def records() -> List[Record]:
    """The ring's records, oldest first."""
    return [Record._make(r) for r in list(_RING)]


def _on_duration(event: str, duration_secs: float, **kwargs: Any) -> None:
    name = JAX_EVENTS.get(event)
    if name is not None:
        end = time.perf_counter_ns()
        record(name, end - int(duration_secs * 1e9), end,
               fun=kwargs.get("fun_name"))


def watch_compiles() -> None:
    """Register the `jax.monitoring` listener behind the ``jax/trace`` and
    ``jax/compile`` records (once per process; later calls do nothing)."""
    global _WATCHING
    with _WATCH_LOCK:
        if not _WATCHING:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _WATCHING = True


@contextlib.contextmanager
def _noop() -> Iterator[None]:
    yield


def traced_span(name: str):
    """Trace-time span: names the enclosed jaxpr region (the compiled
    program's ``op_name`` metadata)."""
    ns = getattr(jax, "named_scope", None)
    return ns(name) if ns is not None else _noop()


def traced(name: str) -> Callable:
    """Decorator form of `traced_span` — the kernel dispatch entry points
    wear this so every pallas launch is attributable under a stable name."""
    def deco(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with traced_span(name):
                return fn(*args, **kwargs)
        return wrapper
    return deco


@contextlib.contextmanager
def trace_dump(log_dir: str) -> Iterator[None]:
    """Capture a Perfetto-compatible profiler trace of the enclosed block
    into `log_dir` (open with ui.perfetto.dev or TensorBoard's profile
    plugin)."""
    start = getattr(jax.profiler, "start_trace", None)
    stop = getattr(jax.profiler, "stop_trace", None)
    if start is None or stop is None:
        yield
        return
    start(log_dir)
    try:
        yield
    finally:
        stop()
