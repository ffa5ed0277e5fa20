"""Unit + property tests for the framework-level ABFT core (repro.core)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from _hypothesis_compat import given, settings, st

from repro.core import (ft_dot, ft_batched_dot, ft_verdict_dot, abft,
                        ONLINE_BLOCK, OFFLINE_DETECT, NONFUSED_BASELINE,
                        FT_OFF, InjectionSpec, ft_scope)


def _ab(m=64, k=32, n=48, dtype=jnp.float32, seed=0):
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(ka, (m, k), dtype),
            jax.random.normal(kb, (k, n), dtype))


def test_clean_fused_exact():
    a, w = _ab()
    np.testing.assert_array_equal(np.asarray(ft_dot(a, w, ft=ONLINE_BLOCK)),
                                  np.asarray(a @ w))


def test_ft_off_is_plain_dot():
    a, w = _ab()
    np.testing.assert_array_equal(np.asarray(ft_dot(a, w, ft=FT_OFF)),
                                  np.asarray(a @ w))


@pytest.mark.parametrize("ft", [ONLINE_BLOCK, NONFUSED_BASELINE])
def test_injected_error_corrected(ft):
    a, w = _ab()
    spec = InjectionSpec(row=10, col=20, magnitude=100.0)
    out, v = ft_verdict_dot(a, w, ft, spec=spec)
    assert bool(v.detected) and int(v.row) == 10 and int(v.col) == 20
    np.testing.assert_allclose(np.asarray(out), np.asarray(a @ w),
                               rtol=1e-5, atol=1e-4)


def test_detect_only_leaves_error():
    a, w = _ab()
    spec = InjectionSpec(row=10, col=20, magnitude=100.0)
    out, v = ft_verdict_dot(a, w, OFFLINE_DETECT, spec=spec)
    assert bool(v.detected)
    assert abs(float(out[10, 20] - (a @ w)[10, 20]) - 100.0) < 1e-3


def test_gradients_flow_and_match_plain():
    a, w = _ab()
    g1 = jax.grad(lambda a, w: jnp.sum(ft_dot(a, w, ft=ONLINE_BLOCK) ** 2))(a, w)
    g2 = jax.grad(lambda a, w: jnp.sum((a @ w) ** 2))(a, w)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-6)


def test_gradients_with_injection_are_clean():
    """SEUs injected into fwd AND bwd GEMMs must be corrected so gradients
    equal the fault-free ones — end-to-end training-step hardening."""
    a, w = _ab()
    key = jax.random.PRNGKey(3)
    ft = ONLINE_BLOCK.replace(inject_rate=1.0)
    g1 = jax.grad(lambda a, w: jnp.sum(ft_dot(a, w, ft=ft, key=key) ** 2))(a, w)
    g2 = jax.grad(lambda a, w: jnp.sum((a @ w) ** 2))(a, w)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-4, atol=1e-2)


def test_batched_dot_clean_and_injected():
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    a = jax.random.normal(k1, (4, 8, 16, 32))
    b = jax.random.normal(k2, (4, 8, 32, 16))
    out = ft_batched_dot(a, b, ft=ONLINE_BLOCK)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a @ b),
                               rtol=1e-5, atol=1e-4)
    # stochastic injection with rate 1 → every batch element hit; corrected
    out2 = ft_batched_dot(a, b, ft=ONLINE_BLOCK.replace(inject_rate=1.0),
                          key=jax.random.PRNGKey(9))
    np.testing.assert_allclose(np.asarray(out2), np.asarray(a @ b),
                               rtol=1e-4, atol=1e-2)


@pytest.mark.parametrize("call", ["dot", "batched", "nonfused"])
def test_jnp_abft_materializes_its_operands(call):
    """The GEMM and its checksums must read one materialized copy of each
    operand. Fused into both and recomputed, a producer (here tanh) need
    not come out bit for bit the same, and the checksums then encode
    another matrix: false detections on the TPU, invisible on the CPU."""
    from repro.tools import audit
    a, w = _ab()
    fns = {
        "dot": lambda a, w: ft_dot(jnp.tanh(a), w, ft=ONLINE_BLOCK),
        "batched": lambda a, w: ft_batched_dot(jnp.tanh(a)[None], w[None],
                                               ft=ONLINE_BLOCK),
        "nonfused": lambda a, w: ft_dot(jnp.tanh(a), w,
                                        ft=NONFUSED_BASELINE),
    }
    n = audit.count_primitives(fns[call], a, w,
                               primitive="optimization_barrier")
    # nonfused pins its three passes with barriers of its own
    assert n == (4 if call == "nonfused" else 1)


def test_telemetry_scope_counts():
    a, w = _ab()
    with ft_scope() as s:
        ft_dot(a, w, ft=ONLINE_BLOCK.replace(inject_rate=1.0),
               key=jax.random.PRNGKey(7))
        ft_dot(a, w, ft=ONLINE_BLOCK)  # clean
        rep = s.report()
    assert int(rep.detected) == 1 and int(rep.corrected) == 1
    assert float(rep.max_residual) > 0


def test_under_jit_with_telemetry():
    a, w = _ab()

    @jax.jit
    def step(a, w, key):
        with ft_scope() as s:
            y = ft_dot(a, w, ft=ONLINE_BLOCK.replace(inject_rate=1.0), key=key)
            return y, s.report()

    y, rep = step(a, w, jax.random.PRNGKey(11))
    assert int(rep.detected) == 1
    np.testing.assert_allclose(np.asarray(y), np.asarray(a @ w),
                               rtol=1e-4, atol=1e-2)


# ---------------------------------------------------------------------------
# hypothesis: checksum-algebra invariants
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(m=st.integers(2, 40), k=st.integers(2, 40), n=st.integers(2, 40),
       seed=st.integers(0, 10_000))
def test_property_checksum_identity(m, k, n, seed):
    """(e^T A)·B == e^T(A·B) and A·(B e) == (A·B)e — Huang–Abraham Eq. 3."""
    a, b = _ab(m, k, n, seed=seed)
    c = a @ b
    ck = abft.product_checksums(a, b)
    np.testing.assert_allclose(np.asarray(ck.col),
                               np.asarray(jnp.sum(c, 0, keepdims=True)),
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(ck.row),
                               np.asarray(jnp.sum(c, 1, keepdims=True)),
                               rtol=1e-4, atol=1e-3)


@settings(max_examples=30, deadline=None)
@given(m=st.integers(2, 32), k=st.integers(2, 32), n=st.integers(2, 32),
       row=st.integers(0, 31), col=st.integers(0, 31),
       mag=st.floats(1.0, 1e5), sign=st.sampled_from([-1.0, 1.0]),
       seed=st.integers(0, 10_000))
def test_property_single_error_always_located(m, k, n, row, col, mag, sign,
                                              seed):
    """∀ single SEU above threshold: detected, located exactly, corrected to
    within relative eps of the magnitude."""
    row, col = row % m, col % n
    a, b = _ab(m, k, n, seed=seed)
    spec = InjectionSpec(row=row, col=col, magnitude=sign * mag)
    out, v = ft_verdict_dot(a, b, ONLINE_BLOCK, spec=spec)
    assert bool(v.detected)
    assert int(v.row) == row and int(v.col) == col
    atol = max(1e-3, 4e-7 * mag)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a @ b),
                               rtol=1e-4, atol=atol)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000),
       dtype=st.sampled_from([jnp.float32, jnp.bfloat16]))
def test_property_no_false_positive(seed, dtype):
    a, b = _ab(48, 64, 32, dtype=dtype, seed=seed)
    _, v = ft_verdict_dot(a, b, ONLINE_BLOCK)
    assert not bool(v.detected)
