"""A training cell defined only by new files is found by name and runs end
to end on the CPU (the look for a chip skipped); with the timed path broken
underneath, ``correct`` comes out false."""
import jax
import jax.numpy as jnp
import pytest

from bench import harness

import _tiny

SEED = 2**31 + 7


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    """Leave JAX's global cache settings alone for the other tests of the
    worker (the harness turns the persistent cache on in a real run)."""
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "")


@pytest.fixture(scope="module")
def bench_dir(tmp_path_factory):
    return _tiny.make_bench(tmp_path_factory.mktemp("tb"))


def _run(bench_dir, tmp_path, trace=False):
    args = harness.Args("tiny-train", SEED, 0.5, trace,
                        out_dir=str(tmp_path))
    return harness.execute(args, bench_dir=bench_dir,
                           bench_json=_tiny.BENCH_JSON, require_tpu=False)


def _train_loop_module(bench_dir):
    return harness.find_cell("tiny-train", bench_dir).module("loops",
                                                             "train")


def test_new_cell_runs_and_is_correct(bench_dir, tmp_path):
    r = _run(bench_dir, tmp_path)
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert list(r)[-1] == "checks"
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert r["metrics"]["train_tokens_per_s"]["unit"] == "tokens/s"
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    assert set(r["checks"]) == {"loss_gap", "grad_gap", "change_gap",
                                "ft_detections"}
    assert r["checks"]["ft_detections"] == {"value": 0.0, "limit": 0}


def _copying(step):
    def call(p, o, *a):
        return step(jax.tree.map(jnp.copy, p), jax.tree.map(jnp.copy, o), *a)
    return call


def test_state_left_unchanged_is_caught(bench_dir, tmp_path, monkeypatch):
    drv = _train_loop_module(bench_dir)
    real = drv.Session.make_step

    def make_step(self, ft):
        step = _copying(real(self, ft))

        def unchanged(p, o, *a):
            _, _, m = step(p, o, *a)
            return p, o, m
        return unchanged
    monkeypatch.setattr(drv.Session, "make_step", make_step)
    r = _run(bench_dir, tmp_path)
    assert not r["correct"]
    assert r["checks"]["change_gap"]["value"] > 0.5


def test_half_batch_is_caught(bench_dir, tmp_path, monkeypatch):
    drv = _train_loop_module(bench_dir)
    real = drv.Session.feed

    def feed(self, seed, i):
        return {k: v[: self.batch // 2] for k, v in real(self, seed,
                                                          i).items()}
    monkeypatch.setattr(drv.Session, "feed", feed)
    assert not _run(bench_dir, tmp_path)["correct"]


def test_altered_token_is_caught(bench_dir, tmp_path, monkeypatch):
    drv = _train_loop_module(bench_dir)
    real = drv.Session.feed

    def feed(self, seed, i):
        b = real(self, seed, i)
        b["tokens"] = b["tokens"].at[0, 5].set((b["tokens"][0, 5] + 1)
                                               % self.cfg.vocab_size)
        return b
    monkeypatch.setattr(drv.Session, "feed", feed)
    assert not _run(bench_dir, tmp_path)["correct"]


def test_false_detection_on_clean_data_is_caught(bench_dir, tmp_path,
                                                 monkeypatch):
    """The step's FT report claims one detection on the clean data of a
    step in the window: the comparison with the reference alone would pass."""
    drv = _train_loop_module(bench_dir)
    real = drv.Session.make_step

    def make_step(self, ft):
        step = real(self, ft)
        calls = []

        def claiming(p, o, *a):
            p, o, m = step(p, o, *a)
            calls.append(1)
            if len(calls) == 4:
                m = dict(m, ft=m["ft"]._replace(detected=m["ft"].detected + 1))
            return p, o, m
        return claiming
    monkeypatch.setattr(drv.Session, "make_step", make_step)
    r = _run(bench_dir, tmp_path)
    assert not r["correct"]
    assert r["checks"]["ft_detections"]["value"] == 1.0
    assert all(r["checks"][k]["value"] <= r["checks"][k]["limit"]
               for k in ("loss_gap", "grad_gap", "change_gap"))
