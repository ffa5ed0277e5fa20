"""Protection audit: walk a jaxpr and account every GEMM's FLOPs.

The end-to-end claim of the backward-FT work (PR 4) is structural: a train
step on the pallas FT backend contains **no large `dot_general` outside
registry-emitted kernels** — every GEMM above a size threshold runs inside a
`pallas_call` (where online ABFT is fused with the MACs) or not at all.
FT-BLAS's argument is that fault tolerance must cover every BLAS call on the
critical path to claim end-to-end protection; this module is the mechanized
version of that audit for our jaxprs, used by

  * `tests/test_backward_ft.py::test_protection_audit_*` — the regression
    gate (zero unprotected large dot_generals for a dense and a MoE
    optimizer step);
  * `benchmarks/backward_path.py` — the before/after fraction of train-step
    GEMM FLOPs running under in-kernel ABFT.

Accounting model: the walk recurses into every sub-jaxpr (custom_vjp calls,
remat/checkpoint, scan/while/cond bodies, jit calls) EXCEPT the kernel body
of a `pallas_call` — dot_generals there are the registry-emitted MACs and
checksum GEMVs, classified as "kernel". Loop trip counts are not multiplied
in (the audit is structural, not a cost model): a dot_general inside a
scanned layer counts once, which is exactly what the zero-unprotected gate
needs, and close enough for the benchmark's fraction when layers are
homogeneous.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Tuple

import jax
import jax.extend
import numpy as np


@dataclasses.dataclass
class DotRecord:
    """One dot_general occurrence: FLOPs, operand shapes, and whether it
    sits inside a pallas_call kernel body ("kernel") or in open XLA code
    ("open")."""
    flops: float
    lhs_shape: Tuple[int, ...]
    rhs_shape: Tuple[int, ...]
    where: str                 # "kernel" | "open"
    primitive: str = "dot_general"


def _dot_flops(eqn) -> Tuple[float, Tuple[int, ...], Tuple[int, ...]]:
    """2 · batch · M · N · K FLOPs of one dot_general eqn from its operand
    avals and dimension_numbers (any rank, any batching)."""
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    batch = np.prod([lhs.shape[d] for d in lb], dtype=np.float64) if lb else 1.0
    contract = (np.prod([lhs.shape[d] for d in lc], dtype=np.float64)
                if lc else 1.0)
    lhs_free = np.prod([s for d, s in enumerate(lhs.shape)
                        if d not in lc and d not in lb], dtype=np.float64)
    rhs_free = np.prod([s for d, s in enumerate(rhs.shape)
                        if d not in rc and d not in rb], dtype=np.float64)
    return (2.0 * batch * contract * lhs_free * rhs_free,
            tuple(lhs.shape), tuple(rhs.shape))


def _sub_jaxprs(params: dict):
    """Yield every jaxpr stored in an eqn's params (call_jaxpr, branches,
    scan/while bodies, custom_vjp fwd/bwd thunks, …)."""
    for v in params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for item in vals:
            if isinstance(item, jax.extend.core.ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, jax.extend.core.Jaxpr):
                yield item


def collect_dots(jaxpr, _in_kernel: bool = False) -> List[DotRecord]:
    """Every dot_general in `jaxpr` (recursively), tagged by whether it is
    inside a pallas_call kernel body. Accepts a ClosedJaxpr or Jaxpr."""
    if isinstance(jaxpr, jax.extend.core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    out: List[DotRecord] = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            flops, ls, rs = _dot_flops(eqn)
            out.append(DotRecord(flops, ls, rs,
                                 "kernel" if _in_kernel else "open"))
            continue
        kernelish = _in_kernel or name == "pallas_call"
        for sub in _sub_jaxprs(eqn.params):
            out.extend(collect_dots(sub, _in_kernel=kernelish))
    return out


def count_primitives(fn, *args, primitive: str = "pallas_call",
                     **make_jaxpr_kwargs) -> int:
    """Count call-site occurrences of `primitive` in `fn(*args)`'s jaxpr,
    recursing through every sub-jaxpr. Unlike `str(jaxpr).count(...)`, this
    counts each *call site*: the printer let-binds repeated identical
    sub-jaxprs once, so string counts undercount launches."""
    jaxpr = jax.make_jaxpr(fn, **make_jaxpr_kwargs)(*args)

    def walk(j) -> int:
        if isinstance(j, jax.extend.core.ClosedJaxpr):
            j = j.jaxpr
        c = 0
        for eqn in j.eqns:
            if eqn.primitive.name == primitive:
                c += 1
            for sub in _sub_jaxprs(eqn.params):
                c += walk(sub)
        return c

    return walk(jaxpr)


def pallas_call_names(fn, *args, **make_jaxpr_kwargs) -> List[str]:
    """Kernel names of every pallas_call site in `fn(*args)`'s jaxpr, in
    traversal order (recursing through every sub-jaxpr — custom_vjp
    branches, scan bodies, …). The name is the kernel body's function name
    (e.g. ``_flash_ft_kernel``, ``gemm_block_batched``), which is how tests
    assert that a campaign's jaxpr contains the kernels it claims to
    exercise — e.g. that a stochastic-injection attention step runs the
    flash kernels rather than silently falling back to the oracle."""
    jaxpr = jax.make_jaxpr(fn, **make_jaxpr_kwargs)(*args)
    names: List[str] = []

    def walk(j):
        if isinstance(j, jax.extend.core.ClosedJaxpr):
            j = j.jaxpr
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["jaxpr"].debug_info.func_name)
            for sub in _sub_jaxprs(eqn.params):
                walk(sub)

    walk(jaxpr)
    return names


def unprotected_dots(fn, *args, min_flops: float = 0.0,
                     **make_jaxpr_kwargs) -> List[DotRecord]:
    """Trace `fn(*args)` and return the open (outside-kernel) dot_generals
    with FLOPs ≥ `min_flops` — the audit's violation list (empty = the step
    is fully covered by registry-emitted kernels above the threshold)."""
    jaxpr = jax.make_jaxpr(fn, **make_jaxpr_kwargs)(*args)
    return [d for d in collect_dots(jaxpr)
            if d.where == "open" and d.flops >= min_flops]


def flop_accounting(fn, *args, **make_jaxpr_kwargs) -> dict:
    """GEMM-FLOP accounting of `fn(*args)`'s jaxpr: total dot FLOPs inside
    pallas kernels vs in open XLA code, and the in-kernel fraction."""
    jaxpr = jax.make_jaxpr(fn, **make_jaxpr_kwargs)(*args)
    dots = collect_dots(jaxpr)
    kernel = sum(d.flops for d in dots if d.where == "kernel")
    open_ = sum(d.flops for d in dots if d.where == "open")
    total = kernel + open_
    return {
        "kernel_flops": kernel,
        "open_flops": open_,
        "total_flops": total,
        "kernel_fraction": kernel / total if total else 1.0,
        "n_kernel_dots": sum(1 for d in dots if d.where == "kernel"),
        "n_open_dots": sum(1 for d in dots if d.where == "open"),
        "records": dots,
    }
