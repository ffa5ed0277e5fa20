"""Three-term roofline analysis from compiled dry-run artifacts.

    compute    = HLO_FLOPs   / (chips × peak_FLOP/s)
    memory     = HLO_bytes   / (chips × HBM_bw)
    collective = coll_bytes  / (chips × link_bw)

HLO_FLOPs / HLO_bytes come from compiled.cost_analysis(). Collective bytes
are NOT in cost_analysis — we parse the post-SPMD HLO (compiled.as_text())
and sum operand sizes of all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute ops.

Note on units: with XLA SPMD, cost_analysis and the partitioned module are
**per-device**, so dividing by `chips` again would double-count; we therefore
use per-device quantities directly against per-chip peak rates (numerically
identical to the assignment's global-total formulation).

Hardware constants come from `PEAKS`, one row per `device_kind`; the
analytical models (tuner, planner, dry-run) price the chip `MODEL_KIND`.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional, Tuple

#: Published per-chip peaks, keyed by `jax.Device.device_kind`. Source:
#: Google Cloud documentation, "TPU v5e" (system architecture table):
#: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
#: of chip-to-chip interconnect over 4 ICI ports.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bw": 819e9, "ici_bw": 200e9,
                    "ici_links": 4},
}

#: The chip the analytical models assume.
MODEL_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Dict:
    """The `PEAKS` row of ``device_kind``; an unknown kind is an error, never
    a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add a row to roofline.PEAKS "
                         f"with its source") from None


PEAK_FLOPS = PEAKS[MODEL_KIND]["bf16_flops"]            # per chip
HBM_BW = PEAKS[MODEL_KIND]["hbm_bw"]                    # bytes/s per chip
LINK_BW = (PEAKS[MODEL_KIND]["ici_bw"]
           / PEAKS[MODEL_KIND]["ici_links"])            # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  %x = bf16[16,512,128]{2,1,0} all-gather(...)   (tuple results are
# handled exclusively by _TUPLE_RE — no leading "(" allowed here)
_OP_RE = re.compile(
    r"=\s([a-z0-9]+)\[([0-9,]*)\][^=]*?\s("
    + "|".join(_COLLECTIVES) + r")[\(\.]")
# tuple-result collectives:  %x = (f32[4,8]{1,0}, f32[4,8]{1,0}) all-reduce(
_TUPLE_RE = re.compile(
    r"=\s*\(([^()]+)\)\s*(" + "|".join(_COLLECTIVES) + r")\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _bytes_of(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> Tuple[int, Dict[str, int]]:
    """Sum per-device result bytes of every collective op in partitioned HLO.
    Returns (total, per-op-kind breakdown)."""
    per: Dict[str, int] = {}
    for m in _OP_RE.finditer(hlo_text):
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        per[kind] = per.get(kind, 0) + _bytes_of(dtype, dims)
    for m in _TUPLE_RE.finditer(hlo_text):
        shapes, kind = m.group(1), m.group(2)
        for sm in _SHAPE_RE.finditer(shapes):
            per[kind] = per.get(kind, 0) + _bytes_of(sm.group(1), sm.group(2))
    return sum(per.values()), per


@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    hlo_flops: float            # per device
    hlo_bytes: float            # per device
    coll_bytes: float           # per device
    model_flops: float          # useful (6·N_active·D), per device
    coll_breakdown: Dict[str, int]

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Perfect-overlap model: step time = max of the three terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_ratio(self) -> float:
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the compute roofline achieved at the modelled step
        time: (useful FLOPs / step_time) / peak."""
        if self.step_time_s == 0:
            return 0.0
        return (self.model_flops / self.step_time_s) / PEAK_FLOPS

    def to_dict(self) -> Dict:
        return {
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "hlo_flops": self.hlo_flops,
            "hlo_bytes": self.hlo_bytes, "coll_bytes": self.coll_bytes,
            "model_flops": self.model_flops,
            "bottleneck": self.bottleneck,
            "useful_ratio": self.useful_ratio,
            "step_time_s": self.step_time_s,
            "roofline_fraction": self.roofline_fraction,
            "coll_breakdown": self.coll_breakdown,
        }


def cost_dict(compiled) -> Dict:
    """`compiled.cost_analysis()` as a plain dict (empty when the backend
    reports none)."""
    return dict(compiled.cost_analysis() or {})


def kernel_time_s(flops: float, hbm_bytes: float,
                  peak_flops: float = PEAK_FLOPS,
                  hbm_bw: float = HBM_BW) -> float:
    """Single-kernel roofline: perfect-overlap time for a kernel that
    executes `flops` and moves `hbm_bytes` through HBM. This is the
    analytical scoring model the autotuner (`kernels.search`) falls back to
    when candidates cannot be timed on hardware — same constants as the
    whole-model roofline above, so benchmark and tuner numbers agree."""
    return max(flops / peak_flops, hbm_bytes / hbm_bw)


def analyze(cost: Dict, hlo_text: str, model_flops_per_device: float
            ) -> Roofline:
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    cbytes, breakdown = collective_bytes(hlo_text)
    return Roofline(
        compute_s=flops / PEAK_FLOPS,
        memory_s=byts / HBM_BW,
        collective_s=cbytes / LINK_BW,
        hlo_flops=flops, hlo_bytes=byts, coll_bytes=float(cbytes),
        model_flops=model_flops_per_device,
        coll_breakdown=breakdown,
    )
