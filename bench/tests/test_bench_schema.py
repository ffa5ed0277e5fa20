"""``BENCHMARK.json`` and the files it names: the names, units and keys
the benchmark's contract allows, and every reference resolving to a file
of its own under ``bench/``."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    B = json.load(f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./\-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
ALL_NAMES = ([c["name"] for c in B["configs"]]
             + [w["name"] for w in B["workloads"]]
             + [m["name"] for m in B["end_to_end"] + B["per_layer"]])


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_paths():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(B["paths"]) <= 16
    for p in B["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    assert 1 <= len(B["command"]) <= 32 and all(_line(w) for w in B["command"])
    for w in B["command"]:
        if "/" in w:
            assert any(w.startswith(p + "/") for p in B["paths"])
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


@pytest.mark.parametrize("name", ALL_NAMES)
def test_names(name):
    assert NAME.match(name), name
    assert ALL_NAMES.count(name) == 1


def test_configs():
    used = {w["config"] for w in B["workloads"]}
    files = set()
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used and _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith(tuple(p + "/" for p in B["paths"]))
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["name"] == c["name"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not re.search(r"_dim$|_rank$|hidden|intermediate|head|"
                                 r"state|expand|experts_per", key), key


def test_workloads_name_files_of_their_own():
    pairs = set()
    four = 0
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and _line(w["why"])
        four += w["chips"] == 4
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        for rel in (f"bench/workloads/{w['name']}.json",
                    f"bench/mixes/{w['traffic']}.json"):
            assert os.path.exists(os.path.join(ROOT, rel)), rel
        with open(os.path.join(ROOT, "bench/workloads",
                               w["name"] + ".json")) as f:
            wl = json.load(f)
        assert (wl["config"], wl["traffic"], wl["chips"]) == (
            w["config"], w["traffic"], w["chips"])
        assert os.path.exists(os.path.join(
            ROOT, "bench/loops", wl["kind"] + ".py"))
    assert four <= max(1, len(B["workloads"]) // 2)


def _cells_reporting(metric):
    m = next(x for x in B["end_to_end"] if x["name"] == metric)
    return set(m.get("workloads", [w["name"] for w in B["workloads"]]))


def test_metrics():
    cells = {w["name"] for w in B["workloads"]}
    e2e = {m["name"] for m in B["end_to_end"]}
    assert "setup_s" in e2e and 2 <= len(e2e) <= 16
    for m in B["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    for c in cells:      # setup_s and at least one more, one per-layer
        assert sum(c in _cells_reporting(m) for m in e2e) >= 2
        assert any(c in m["workloads"] for m in B["per_layer"])
    layers = {}
    for m in B["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES and _line(m["layer"])
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= _cells_reporting(m["moves"])
        assert os.path.exists(os.path.join(ROOT, "bench/metrics",
                                           m["name"] + ".py")), m["name"]
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    assert all(len(v) == 1 for v in layers.values())


def test_every_roofline_has_a_whole_step_mfu_beside_it():
    by_moves = {}
    for m in B["per_layer"]:
        by_moves.setdefault(m["moves"], []).append(m["name"])
    for moves, names in by_moves.items():
        if any("_roofline" in n for n in names):
            assert any("mfu" in n for n in names), moves


def test_run_seconds_fit_the_full_check():
    rs = B["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
