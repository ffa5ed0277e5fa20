"""Build the program's model and FT configuration from a
``configs/<name>.json``, and the seeded PRNG key every run starts from."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict


def model_config(conf: Dict[str, Any]):
    """The registry entry named by ``conf["program"]["arch_id"]``, with the
    keys of ``conf["program"]["overrides"]`` replaced (nested dataclass
    fields, such as ``ssm``, replaced key by key)."""
    from repro.configs import registry
    prog = conf["program"]
    base = registry.get_config(prog["arch_id"])
    over = dict(prog.get("overrides", {}))
    for key, val in list(over.items()):
        sub = getattr(base, key)
        if isinstance(val, dict) and dataclasses.is_dataclass(sub):
            over[key] = dataclasses.replace(sub, **val)
    return dataclasses.replace(base, **over)


def key_for(seed: int, stream: int = 0):
    """A PRNG key from a seed of any size (a 64-bit seed is common),
    split by ``stream`` so weights and traffic draw independently."""
    import jax
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


def ft_config(conf: Dict[str, Any]):
    """The program's FT configuration that ``conf["ft"]`` names: a policy
    (``ONLINE_BLOCK`` | ``FT_OFF``) on a backend (``pallas`` | ``xla``)."""
    from repro.core.policy import FT_OFF, ONLINE_BLOCK
    ft = conf["ft"]
    base = {"ONLINE_BLOCK": ONLINE_BLOCK, "FT_OFF": FT_OFF}[ft["policy"]]
    return base.replace(backend=ft["backend"])
