"""A serving cell defined only by new files is found by name and runs end
to end on the CPU (the look for a chip skipped); with the timed path broken
underneath, ``correct`` comes out false."""
import numpy as np
import pytest

from bench import harness

import _tiny

SEED = 2**32 + 11


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    """Leave JAX's global cache settings alone for the other tests of the
    worker (the harness turns the persistent cache on in a real run)."""
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "")


@pytest.fixture(scope="module")
def bench_dir(tmp_path_factory):
    return _tiny.make_bench(tmp_path_factory.mktemp("tb"))


def _run(bench_dir, tmp_path, seconds=10.0):
    args = harness.Args("tiny-serve", SEED, seconds, False,
                        out_dir=str(tmp_path))
    return harness.execute(args, bench_dir=bench_dir,
                           bench_json=_tiny.BENCH_JSON, require_tpu=False)


def test_new_cell_runs_and_is_correct(bench_dir, tmp_path):
    r = _run(bench_dir, tmp_path)
    assert r["correct"], r["checks"]
    assert r["attempted"] == 20 and r["failed"] == 0
    assert set(r["metrics"]) == {"tpot_p50_ms", "serve_tokens_per_s",
                                 "setup_s"}
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == {"mean_gap", "widest_gap"}


def test_altered_token_is_caught(bench_dir, tmp_path, monkeypatch):
    from repro.train.engine import ServeEngine
    real = ServeEngine._sample
    calls = []

    def sample(self, logits):
        out = np.array(real(self, logits))
        calls.append(1)
        if len(calls) % 3 == 0:           # every slot of that call
            out[:] = (out + 1) % self.cfg.vocab_size
        return out
    monkeypatch.setattr(ServeEngine, "_sample", sample)
    r = _run(bench_dir, tmp_path)
    assert not r["correct"]


def test_state_left_unchanged_is_caught(bench_dir, tmp_path, monkeypatch):
    """Decode never writes the new token's keys and values to the cache."""
    from repro.train import kv_cache
    monkeypatch.setattr(kv_cache, "append_layer",
                        lambda pages, new, table, pos: pages)
    r = _run(bench_dir, tmp_path)
    assert not r["correct"]
