"""Mesh construction.

FUNCTIONS, not module constants — importing this module never touches JAX
device state (device count is locked at first backend init, and only
dryrun.py is allowed to force 512 host devices).

Every mesh here has Auto axes: the model code places activations with
`with_sharding_constraint` and leaves the rest to GSPMD, which Explicit
axes (the `jax.make_mesh` default since JAX 0.7) refuse.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """`jax.make_mesh` with Auto axes over ``devices`` (default: all)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod over ("data","model"); multi-pod adds a leading
    pod axis: (2,16,16) = 512 chips over ("pod","data","model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Mesh over whatever devices exist (tests / single-host training)."""
    n = len(jax.devices())
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))
