"""The readers of the program's own spans and counters
(``bench/program_spans.py`` and the four metrics built on it), on records
and a trace reduction built by hand, with every value worked out by hand;
on the recorded v5e trace; and against a program whose ring holds nothing
of the window or that has no ring."""
import os

import pytest

from bench import harness
from bench import trace_reduce as tr
from repro.tools import trace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READERS = ("queue_wait_p50_ms.serve", "slot_occupancy.serve",
           "step_host_ms.decode", "engine_idle_share.serve")
MS = 1_000_000          # ns
T0 = 7_000_000          # where the hand-built trace's window starts


def reader(name):
    return harness.load_module(os.path.join(BENCH, "metrics", name + ".py"))


def _rec(i, name, s, e, parent=None, rid=None, **attrs):
    return trace.Record(name, int(s * MS), int(e * MS), i, parent, rid, attrs)


def _step(i, s, e, prepare, dispatch, sample, retire, live, finished):
    """A decode step ``i`` with its four phases (times in ms)."""
    return [_rec(i, "serve/step", s, e),
            _rec(i + 1, "serve/prepare", *prepare, parent=i),
            _rec(i + 2, "serve/dispatch", *dispatch, parent=i, live=live,
                 n_slots=10, free_pages=3),
            _rec(i + 3, "serve/sample", *sample, parent=i),
            _rec(i + 9, "serve/retire", *retire, parent=i,
                 finished=finished)]


def _admission(i, step, rid, s, prefill, kv, sample, queued_from, plen):
    return [_rec(i, "serve/queued", queued_from, s, parent=step, rid=rid),
            _rec(i + 1, "serve/admit", s, sample[1], parent=step, rid=rid),
            _rec(i + 2, "serve/prefill", *prefill, parent=i + 1, rid=rid,
                 prompt_len=plen),
            _rec(i + 3, "serve/kv_write", *kv, parent=i + 1, rid=rid),
            _rec(i + 4, "serve/sample", *sample, parent=i + 1, rid=rid)]


def hand_records():
    """Times in ms on the ``perf_counter`` clock. Warm-up before the window,
    steps A-D in it (B and C each admit one request), E after it."""
    return (
        [_rec(1, "serve/queued", 400, 450, rid=0),
         _rec(2, "serve/dispatch", 500, 510, live=1, n_slots=10,
              free_pages=9)]
        + _step(10, 1000, 1099, (1000, 1002), (1002, 1006), (1006, 1097),
                (1097, 1098), live=8, finished=1)                  # A
        + _step(20, 1100, 1249, (1140, 1143), (1143, 1148), (1148, 1245),
                (1245, 1248), live=10, finished=0)                 # B
        + _admission(30, 20, 5, 1100, (1101, 1130), (1130, 1135),
                     (1135, 1140), queued_from=900, plen=512)
        + [_rec(38, "jax/trace", 1241, 1243, parent=23, fun="argmax")]
        + _step(40, 1250, 1349, (1290, 1292), (1292, 1300), (1300, 1345),
                (1345, 1348), live=9, finished=1)                  # C
        + _admission(50, 40, 6, 1250, (1251, 1280), (1280, 1285),
                     (1285, 1290), queued_from=1000, plen=256)
        + _step(60, 1350, 1450, (1350, 1352), (1352, 1355), (1355, 1445),
                (1445, 1446), live=10, finished=0)                 # D
        + _step(70, 1460, 1560, (1460, 1470), (1470, 1480), (1480, 1549),
                (1550, 1551), live=10, finished=0))                # E


#: The benchmark's own stamps: each decode call's start (inside the
#: program's ``serve/dispatch``) and the return of ``step()``, in s.
DECODE_STEPS = [{"t0": 1.003, "t1": 1.0995, "live": 8},
                {"t0": 1.144, "t1": 1.2495, "live": 10},
                {"t0": 1.293, "t1": 1.3495, "live": 9},
                {"t0": 1.353, "t1": 1.4505, "live": 10}]

#: Device 0's ops in the traced window [1100, 1300) ms of host time.
BUSY_MS = [(1102, 1129), (1131, 1134), (1136, 1139), (1144, 1240),
           (1252, 1279), (1281, 1284), (1293, 1300)]


def hand_reduction():
    shift = T0 - 1100 * MS
    ops = [tr.Op("fusion.%d" % k, s * MS + shift, e * MS + shift, "m", "")
           for k, (s, e) in enumerate(BUSY_MS)]
    return tr.Reduction((T0, T0 + 200 * MS), {"/device:TPU:0": ops},
                        [tr.Span(tr.WINDOW, T0, T0 + 200 * MS)])


def hand_layer():
    return {"decode_steps": DECODE_STEPS, "trace": hand_reduction(),
            "trace_t": [1.100, 1.300], "window_s": 0.45}


@pytest.fixture
def ring(monkeypatch):
    def use(recs):
        monkeypatch.setattr(trace, "records", lambda: list(recs))
    return use


@pytest.mark.parametrize("name,want", [
    # rid 5 waited 900-1100, rid 6 1000-1250; rid 0 (warm-up) is outside
    ("queue_wait_p50_ms.serve", (200 + 250) / 2),
    # A, B, C, D dispatch 8, 10, 9, 10 of 10 slots; warm-up and E outside
    ("slot_occupancy.serve", 100 * 37 / 40),
    # A: 2 + 4 + 1, D: 2 + 3 + 1; B and C admitted, E is outside
    ("step_host_ms.decode", (7 + 6) / 2),
    # idle 34 of 200 ms; engine work in it: 1100-1102 admit/prefill (2),
    # 1129-1131 prefill/kv_write (2), 1134-1135 kv_write (1), 1140-1144
    # prepare/dispatch (4), 1241-1243 jax/trace inside B's sample (2),
    # 1245-1249 retire and step B (4), 1250-1252 admit/prefill (2; nothing
    # covers 1249-1250), 1279-1281 prefill/kv_write (2), 1284-1285 kv_write
    # (1), 1290-1293 prepare/dispatch (3): 23 ms
    ("engine_idle_share.serve", 100 * 23 / 200),
])
def test_reader_by_hand(ring, name, want):
    ring(hand_records())
    assert reader(name).read(hand_layer()) == pytest.approx(want)


def test_engine_idle_is_part_of_idle_by_hand(ring):
    ring(hand_records())
    layer = hand_layer()
    assert reader("idle_share.serve").read(layer) == pytest.approx(17.0)


def test_engine_idle_within_idle_on_the_recorded_trace(ring):
    """On the v5e trace, a step over the whole window with its host sync
    over the middle third: some of the idle time is the engine's, and no
    more than all of it."""
    import json
    data = os.path.join(BENCH, "testdata")
    with open(os.path.join(data, "small.hlo.json")) as f:
        red = tr.reduce_file(os.path.join(data, "small.xplane.pb"),
                             json.load(f))
    w = red.window[1] - red.window[0]
    base = 10**9                          # the anchor, on the host's clock
    ring([trace.Record("serve/step", base - MS, base + w + MS, 1, None,
                       None, {}),
          trace.Record("serve/sample", base + w // 3, base + 2 * w // 3, 2,
                       1, None, {})])
    layer = {"trace": red, "trace_t": [base / 1e9, (base + w) / 1e9]}
    idle = reader("idle_share.serve").read(layer)
    engine = reader("engine_idle_share.serve").read(layer)
    assert 0 < engine <= idle


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("program", ["nothing in the window", "no ring"])
def test_reader_without_window_records_returns_none(ring, monkeypatch,
                                                    name, program):
    if program == "no ring":
        monkeypatch.delattr(trace, "records")
    else:
        ring([r for r in hand_records() if r.end_ns < 900 * MS])
    assert reader(name).read(hand_layer()) is None


def test_readers_find_the_engine_records_of_a_real_run(tmp_path_factory,
                                                       monkeypatch):
    """The tiny serving cell, traced on the CPU: the host-side readers read
    the records the engine left in the window. (The CPU trace has no
    device plane, so the device-side readers have nothing to read.)"""
    import _tiny
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "")
    bench_dir = _tiny.make_bench(tmp_path_factory.mktemp("tb"))
    bench_json = dict(_tiny.BENCH_JSON, per_layer=[
        {"name": n, "unit": "x", "workloads": ["tiny-serve"]}
        for n in READERS])
    args = harness.Args("tiny-serve", 2**32 + 5, 3.0, True,
                        out_dir=str(tmp_path_factory.mktemp("out")))
    r = harness.execute(args, bench_dir=bench_dir, bench_json=bench_json,
                        require_tpu=False)
    got = {k: v["value"] for k, v in r["metrics"].items()}
    assert set(got) == set(READERS) - {"engine_idle_share.serve"}
    assert 0 < got["slot_occupancy.serve"] <= 100
    assert got["step_host_ms.decode"] > 0
    assert got["queue_wait_p50_ms.serve"] >= 0


def test_idle_split_by_hand(ring):
    """Each idle ms of the hand-built window under the innermost record
    (None: 1249-1250, between steps B and C)."""
    from bench import program_spans
    ring(hand_records())
    split = program_spans.idle_by_record(hand_layer())
    assert {k: v / MS for k, v in split.items()} == {
        "serve/admit": 2, "serve/prefill": 4, "serve/kv_write": 4,
        "serve/sample": 10, "serve/prepare": 5, "serve/dispatch": 2,
        "jax/trace": 2, "serve/retire": 3, "serve/step": 1, None: 1}
