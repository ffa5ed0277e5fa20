"""Matrix products of the references: float32 at the highest precision, or
the control's int8 (both operands quantised symmetrically, per row of the
left and per column of the right operand, in every product of the forward
and the backward pass)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _q8(x: jax.Array, axis: int) -> jax.Array:
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s) * s


def _f32(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _i8(a, b):
    return jnp.matmul(_q8(a, -1), _q8(b, -2), precision=HIGHEST)


@jax.custom_vjp
def int8_matmul(a, b):
    return _i8(a, b)


def _i8_fwd(a, b):
    return _i8(a, b), (a, b)


def _i8_bwd(res, g):
    a, b = res
    da = _i8(g, jnp.swapaxes(b, -1, -2))
    a2 = a.reshape(-1, a.shape[-1])
    db = _i8(a2.T, g.reshape(-1, g.shape[-1]))
    return da, db.reshape(b.shape)


int8_matmul.defvjp(_i8_fwd, _i8_bwd)

MATMULS = {"float32": _f32, "int8": int8_matmul}


def matmul_for(mode: str):
    """(..., K) @ (K, N) in the named precision."""
    return MATMULS[mode]
