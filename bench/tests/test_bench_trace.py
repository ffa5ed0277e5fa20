"""The reduction from a profiler trace to busy time, scope time, idle gaps
and the breakdown: on intervals worked by hand, and on a small trace
recorded on a TPU v5 lite (``bench/testdata/make_trace.py``)."""
import json
import os

import pytest

from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")


def test_interval_arithmetic():
    iv = [(0, 10), (5, 15), (20, 30), (30, 31)]
    assert tr.union_length(iv) == 26
    assert tr.merge(iv) == [(0, 15), (20, 31)]
    assert tr.union_length([]) == 0


def test_names():
    assert tr.inst_name("%kernel_call.87 = (bf16[4]) custom-call(...)") == \
        "kernel_call.87"
    assert tr.kind("kernel_call.87") == "kernel_call"
    assert tr.kind("pad.362.clone") == "pad"
    assert tr.module_name("jit_train_step(1606923)") == "jit_train_step"
    hlo = ('HloModule jit_f, entry\n  %kernel_call.3 = f32[2] custom-call('
           'f32[2] %a), metadata={op_name="jit(f)/kernel/gemm/pallas_call" '
           'source_file="x.py"}\n  ROOT %fusion.1 = f32[] fusion(), '
           'metadata={op_name="jit(f)/reduce_sum"}\n')
    assert tr.scope_map({"jit_f": hlo}) == {
        ("jit_f", "kernel_call.3"): "jit(f)/kernel/gemm/pallas_call",
        ("jit_f", "fusion.1"): "jit(f)/reduce_sum"}


def _hand():
    ops = {"/device:TPU:0": [
        tr.Op("kernel_call.1", 0, 40, "m", "jit(f)/kernel/gemm/x"),
        tr.Op("fusion.2", 30, 50, "m", "jit(f)/tanh"),
        tr.Op("all-reduce.3", 45, 70, "m", ""),
        tr.Op("kernel_call.4", 90, 100, "m", "jit(f)/kernel/gemm/x")],
        "/device:TPU:1": [tr.Op("fusion.2", 0, 100, "m", "")]}
    spans = [tr.Span("bench/window", 0, 100), tr.Span("bench/wait", 60, 95)]
    return tr.Reduction((0, 100), ops, spans)


def test_reduction_by_hand():
    r = _hand()
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_ns("/device:TPU:0") == 80
    assert r.busy_s == pytest.approx((80 + 100) / 2 * 1e-9)
    assert r.scope_time_s(["kernel/gemm"]) == pytest.approx(50 / 2 * 1e-9)
    assert r.idle_gaps() == [["bench/wait", pytest.approx(20e-9)]]
    top = dict(r.top_ops())
    assert top["fusion jit(f)/tanh"] == pytest.approx(10e-9)
    assert top["kernel_call kernel/gemm/x"] == pytest.approx(25e-9)


@pytest.fixture(scope="module")
def small():
    path = os.path.join(DATA, "small.xplane.pb")
    if not os.path.exists(path):
        pytest.fail("bench/testdata/small.xplane.pb is missing")
    with open(os.path.join(DATA, "small.hlo.json")) as f:
        hlo = json.load(f)
    return tr.reduce_file(path, hlo)


def test_recorded_trace(small):
    assert list(small.ops) == ["/device:TPU:0"]
    assert 0 < small.busy_s < small.window_s
    gemm = small.scope_time_s(["kernel/gemm"])
    assert 0 < gemm < small.busy_s
    assert all(o.module == "jit_prog" for o in small.ops["/device:TPU:0"])
    assert any(o.scope for o in small.ops["/device:TPU:0"])
    b = small.breakdown()
    assert 1 <= len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert sum(s for _, s in b["device_ops"]) <= small.busy_s * 1.0001
    names = {n for n, _ in b["idle_gaps"]}
    assert names <= {"bench/window", "bench/wait", "bench/pause"}
    # the three host-only pauses are the three longest idle gaps
    assert [n for n, _ in b["idle_gaps"][:3]] == ["bench/pause"] * 3


def test_recorded_trace_numbers(small):
    """Against the raw events, read without the reducer: the six FT GEMM
    launches (two per run, three runs), clipped to the host's window span,
    are the ``kernel/gemm`` time. (The device's clock runs ~0.3-0.5 ms
    ahead of the host's in this trace, so the first launch starts before
    the window does.)"""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(os.path.join(DATA, "small.xplane.pb"))
    host = next(p for p in pd.planes if p.name == "/host:CPU")
    w0, w1 = next((e.start_ns, e.start_ns + e.duration_ns)
                  for ln in host.lines for e in ln.events
                  if e.name == "bench/window")
    dev = next(p for p in pd.planes if p.name == "/device:TPU:0")
    ops = next(ln for ln in dev.lines if ln.name == "XLA Ops")
    calls = [(e.start_ns, e.start_ns + e.duration_ns) for e in ops.events
             if e.name.startswith("%kernel_call")]
    assert len(calls) == 6
    inside = sum(max(0, min(e, w1) - max(s, w0)) for s, e in calls)
    assert small.scope_time_s(["kernel/gemm"]) == pytest.approx(
        inside * 1e-9)
    assert small.window_s == pytest.approx(0.01009997)
    assert small.busy_s == pytest.approx(0.000139692)
