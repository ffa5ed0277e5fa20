"""`tools/trace`: the span ring (parent links, request ids, the bound,
explicit intervals), the `jax.monitoring` compile records, and the ring's
clock against the profiler's host events."""
import glob
import time

import jax
import jax.numpy as jnp
import pytest

from repro.tools import trace


def _since(t_ns):
    return [r for r in trace.records() if r.end_ns >= t_ns]


def test_parent_links_and_request_ids():
    t0 = time.perf_counter_ns()
    with trace.span("t/outer") as outer:
        with trace.span("t/req", rid=7, k=1) as req:
            with trace.span("t/inner") as inner:
                inner.attrs["late"] = 2
        with trace.span("t/sibling"):
            pass
    recs = {r.name: r for r in _since(t0) if r.name.startswith("t/")}
    assert set(recs) == {"t/outer", "t/req", "t/inner", "t/sibling"}
    assert recs["t/outer"].parent is None and recs["t/outer"].rid is None
    assert recs["t/req"].parent == outer.id and recs["t/req"].rid == 7
    assert recs["t/req"].attrs == {"k": 1}
    assert recs["t/inner"].parent == req.id and recs["t/inner"].rid == 7
    assert recs["t/inner"].attrs == {"late": 2}
    assert recs["t/sibling"].parent == outer.id
    assert recs["t/sibling"].rid is None
    o, i = recs["t/outer"], recs["t/inner"]
    assert o.start_ns <= recs["t/req"].start_ns <= i.start_ns <= i.end_ns \
        <= recs["t/req"].end_ns <= o.end_ns


def test_spans_close_on_error():
    with pytest.raises(ValueError):
        with trace.span("t/raises"):
            raise ValueError("x")
    with trace.span("t/after") as after:
        pass
    assert after.parent is None
    assert trace.records()[-1].name == "t/after"


def test_ring_drops_the_oldest_first():
    n = trace.RING_SIZE + 3
    for i in range(n):
        trace.record("t/bound", i, i + 1, i=i)
    recs = trace.records()
    assert len(recs) == trace.RING_SIZE
    assert [r.attrs["i"] for r in recs[:2]] == [3, 4]
    assert recs[-1].attrs["i"] == n - 1


def test_record_takes_an_explicit_interval_under_the_open_span():
    with trace.span("t/open", rid=3) as sp:
        rec = trace.record("t/wait", 100, 250, what="queue")
    got = next(r for r in reversed(trace.records()) if r.id == rec.id)
    assert got == rec
    assert (got.start_ns, got.end_ns) == (100, 250)
    assert got.parent == sp.id and got.rid == 3
    assert got.attrs == {"what": "queue"}
    alone = trace.record("t/wait", 1, 2, rid=5)
    assert alone.parent is None and alone.rid == 5


@pytest.mark.parametrize("name", ["jax/trace", "jax/compile"])
def test_a_new_shape_records_its_compile_under_the_open_span(name):
    trace.watch_compiles()
    trace.watch_compiles()                # once per process
    f = jax.jit(lambda x: x * 3 + 1)
    x = jnp.ones((7, 13 + len(name)), jnp.float32)
    t0 = time.perf_counter_ns()
    with trace.span("t/jit", rid=11) as sp:
        f(x).block_until_ready()
    got = [r for r in _since(t0) if r.name == name
           and "<lambda>" in r.attrs["fun"]]
    assert len(got) == 1, got
    assert got[0].parent == sp.id and got[0].rid == 11
    assert sp.start_ns <= got[0].start_ns <= got[0].end_ns <= \
        time.perf_counter_ns()
    t1 = time.perf_counter_ns()
    with trace.span("t/jit"):
        f(x).block_until_ready()          # cached: nothing new
    assert not [r for r in _since(t1) if r.name == name]


def test_records_land_on_their_profiler_events(tmp_path):
    """The anchor a reader uses: a ``perf_counter`` stamp taken as it opens
    an annotation of its own. Shifted by it, every record starts and ends
    within 50 us of its own annotation's event in the ``.xplane.pb``."""
    from jax.profiler import ProfileData
    names = ["t/clock/a", "t/clock/b", "t/clock/c"]
    jax.profiler.start_trace(str(tmp_path))
    try:
        anchor = time.perf_counter()
        with jax.profiler.TraceAnnotation("t/anchor"):
            for name in names:
                with trace.span(name, rid=1, n=2):
                    time.sleep(0.003)
                time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    events[ev.name] = (int(ev.start_ns),
                                       int(ev.start_ns + ev.duration_ns))
    shift = events["t/anchor"][0] - int(anchor * 1e9)
    recs = {r.name: r for r in trace.records() if r.name in names}
    for name in names:
        s, e = events[name]
        r = recs[name]
        assert abs(r.start_ns + shift - s) < 50_000, (name, r, s)
        assert abs(r.end_ns + shift - e) < 50_000, (name, r, e)
