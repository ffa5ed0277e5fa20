"""The plain float32 references against the program with FT off, at small
sizes on the CPU, and the SSD's chunked form against its recurrence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.model import key_for
from bench.reference import mamba2 as ref_ssm
from bench.reference import dense as ref_dense
from bench.reference.precision import matmul_for

F32 = matmul_for("float32")


def _cfg(arch, **over):
    from repro.configs import registry
    base = registry.get_config(arch)
    if "ssm" in over:
        over["ssm"] = dataclasses.replace(base.ssm, **over["ssm"])
    return dataclasses.replace(base, **over)


SSM_CFG = dict(n_layers=2, d_model=64, vocab_size=300, norm_eps=1e-5,
               ssm={"state": 16, "head_dim": 16, "chunk": 16})
DENSE_CFG = dict(n_layers=2, d_model=256, n_heads=4, n_kv_heads=2,
                 head_dim=64, d_ff=384, vocab_size=300, norm_eps=1e-5,
                 tie_embeddings=True)


def _weights(family, cfg, seed=3):
    w = harness.load_module(f"{harness.BENCH}/weights/{family}.py")
    return jax.tree.map(lambda x: x.astype(jnp.float32),
                        w.make(cfg, key_for(seed), jnp.float32))


def _program_logits(cfg, params, tokens):
    from repro.core.policy import FT_OFF
    from repro.models import model_zoo
    from repro.models.blocks import Ctx
    mod = model_zoo.module_for(cfg)
    with jax.default_matmul_precision("highest"):
        logits, _ = mod.forward(params, tokens, cfg,
                                Ctx(ft=FT_OFF, dtype=jnp.float32),
                                remat=False)
    return logits


def _reference_logits(fam, cfg, params, tokens):
    top = {k: v for k, v in params.items() if k != "layers"}
    with jax.default_matmul_precision("highest"):
        h = fam.embed(top, tokens)
        for i in range(cfg.n_layers):
            h = fam.layer(jax.tree.map(lambda a: a[i], params["layers"]),
                          h, cfg, F32)
        return fam.logits(top, h, cfg, F32)


def test_ssd_chunked_is_the_recurrence():
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    b, l, h, p, n = 2, 48, 3, 4, 5
    x = jax.random.normal(ks[0], (b, l, h, p))
    a = -jax.random.uniform(ks[1], (b, l, h), minval=0.01, maxval=0.5)
    bm = jax.random.normal(ks[2], (b, l, h, n))
    cm = jax.random.normal(ks[3], (b, l, h, n))
    with jax.default_matmul_precision("highest"):
        y1 = ref_ssm.ssd_chunked(x, a, bm, cm, 16)
        y2 = ref_ssm.ssd_sequential(x, a, bm, cm)
    np.testing.assert_allclose(y1, y2, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("family,arch,over,fam", [
    ("ssm", "mamba2-780m", SSM_CFG, ref_ssm),
    ("dense", "phi4-mini-3.8b", DENSE_CFG, ref_dense)])
def test_reference_matches_program_with_ft_off(family, arch, over, fam):
    cfg = _cfg(arch, **over)
    params = _weights(family, cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                cfg.vocab_size)
    got = _program_logits(cfg, params, tokens)
    want = _reference_logits(fam, cfg, params, tokens)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_dense_serve_rows_match_the_full_forward():
    cfg = _cfg("phi4-mini-3.8b", **DENSE_CFG)
    params = _weights("dense", cfg)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (40,), 0,
                                         cfg.vocab_size), np.int32)
    rows = np.arange(30, 40, dtype=np.int32)
    got = ref_dense.logit_rows(params, cfg, toks, rows)
    want = _reference_logits(ref_dense, cfg, params, jnp.asarray(toks[None]))
    np.testing.assert_allclose(got, want[0, 30:40], rtol=1e-4, atol=1e-4)


def test_dense_serve_numbers_by_hand(monkeypatch):
    """The gaps of served tokens below the reference's best, on hand-made
    logits: the widest, the mean over every served token, and the share off
    the first choice."""
    logits = np.array([[0.0, 2.0, 1.0], [3.0, 0.0, 2.5], [1.0, 1.0, 4.0]],
                      np.float32)
    monkeypatch.setattr(ref_dense, "logit_rows",
                        lambda params, cfg, toks, rows, precision="float32":
                        jnp.asarray(logits[: len(rows)]))
    prompt = np.array([7], np.int32)
    served = np.array([2, 0, 2], np.int32)    # gaps 1.0, 0.0, 0.0
    nums = ref_dense.serve_numbers(None, None, [(prompt, served)])
    assert nums["widest_gap"] == 1.0
    assert abs(nums["mean_gap"] - 1.0 / 3) < 1e-7
    assert abs(nums["argmax_miss"] - 1.0 / 3) < 1e-7
