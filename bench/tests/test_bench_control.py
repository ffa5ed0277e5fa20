"""The control of each cell kind comes out not correct: the float32
reference computed in int8 (every GEMM's operands quantised) put in the
program's place, read against the float32 reference by the numbers and
limits that decide ``correct`` (here at the tiny cells' size; on the chip
at the cells' own, ``bench/study.py``)."""
import numpy as np
import pytest

from bench import compare, harness

import _tiny

SEED = 5


@pytest.fixture(scope="module")
def bench_dir(tmp_path_factory):
    return _tiny.make_bench(tmp_path_factory.mktemp("tb"))


def test_train_control_fails_a_limit(bench_dir):
    cell = harness.find_cell("tiny-train", bench_dir)
    sess = cell.module("loops", "train").Session(cell)
    ref = sess.reference(SEED)
    ctl = compare.train_numbers(sess.reference(SEED, precision="int8"), ref)
    lim = cell.workload["limits"]
    assert any(v > lim[k] for k, v in ctl.items()), ctl


def test_serve_control_fails_the_limit(bench_dir):
    cell = harness.find_cell("tiny-serve", bench_dir)
    sess = cell.module("loops", "serve").Session(cell)
    rng = np.random.default_rng(SEED)
    v = sess.cfg.vocab_size
    seqs = [(rng.integers(0, v, 16).astype(np.int32),
             rng.integers(0, v, 24).astype(np.int32)) for _ in range(2)]
    nums = sess.reference_numbers(SEED, seqs, precision="int8")
    lim = cell.workload["limits"]
    assert any(nums[k] > v for k, v in lim.items()), nums
