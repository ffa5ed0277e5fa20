"""The numbers that decide ``correct``, computed the same way for the
program and for a control put in its place."""
from __future__ import annotations

import math
from statistics import median
from typing import Dict, Iterable, List, Optional


def loss_gap(prog: List[float], ref: List[float]) -> float:
    """Largest relative gap of a step's loss."""
    return max(abs(p - r) / abs(r) for p, r in zip(prog, ref))


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   leaves: Optional[Iterable[str]] = None) -> float:
    """Largest gap between the program's norm of a leaf and the
    reference's, against the larger of that leaf's reference norm and the
    median leaf's."""
    names = sorted(ref if leaves is None else leaves)
    med = median(ref[k] for k in ref)
    gaps = [abs(prog[k] - ref[k]) / max(ref[k], med) for k in names]
    return max(gaps) if gaps else math.nan


def moved_leaves(ref_grad: Dict[str, float], share: float = 1e-3
                 ) -> List[str]:
    """Leaves whose reference gradient is at least ``share`` of the median
    leaf's: the others move under Adam by round-off alone."""
    med = median(ref_grad.values())
    return sorted(k for k, g in ref_grad.items() if g >= share * med)


def train_numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    """``prog`` and ``ref`` each hold ``losses`` (per step), ``grad`` (the
    first clipped gradient's norm per leaf) and ``change`` (each leaf's
    change over the steps). Returns the largest relative loss gap over
    the steps and the worst leaf's gaps of the first gradient's norm and
    of the change's norm (over the leaves that `moved_leaves` keeps)."""
    n = min(len(prog["losses"]), len(ref["losses"]))
    return {
        "loss_gap": loss_gap(prog["losses"][:n], ref["losses"][:n]),
        "grad_gap": worst_leaf_gap(prog["grad"], ref["grad"]),
        "change_gap": worst_leaf_gap(prog["change"], ref["change"],
                                     moved_leaves(ref["grad"])),
    }


def worst_leaves(prog: Dict, ref: Dict) -> Dict[str, str]:
    """The leaf that sets each leaf-wise number (for the run's notes)."""
    def arg(p, r, leaves=None):
        names = sorted(r if leaves is None else leaves)
        med = median(r.values())
        return max(names, key=lambda k: abs(p[k] - r[k]) / max(r[k], med))
    return {"grad_gap": arg(prog["grad"], ref["grad"]),
            "change_gap": arg(prog["change"], ref["change"],
                              moved_leaves(ref["grad"]))}


