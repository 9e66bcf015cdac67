"""The numbers that decide ``correct``, and their comparison with the
cell's limits (``bench/limits/<workload>.json``).

Training compares the program's first steps with the reference's:
  loss_gap    worst relative gap of the loss over the checked steps;
  grad_gap    worst leaf of the first gradient's norm (as the optimizer
              holds it after one step), the gap of the two norms over
              the larger of the reference's norm of that leaf and of the
              median leaf;
  change_gap  the same for the norm of each leaf's change over the
              checked steps.  A family may name leaves that are read per
              index of one axis instead (``SUBLEAF_AXES`` in
              ``bench/models/<family>.py``: an SFNO's weights per degree);
              each such part counts as a leaf of its own.  Leaves and
              parts whose reference gradient is under a thousandth of the
              median leaf's are left out: they move by round-off alone
              under Adam.
Serving compares answered fields with the reference's, each answer by
its L2 distance over the larger of its reference field's norm and the
median field's:
  field_gap         the worst sampled answer's;
  field_gap_median  the median sampled answer's: the one compared, as
                    the low-precision control reads it far from the
                    program's while the worst answer's tail lies
                    between them (PERF.md).

Norms are given as {leaf path: norm}; a part of a leaf is keyed
``<leaf path>#<index>``, beside the whole leaf's norm.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, List, Optional, Tuple

#: leaves whose reference gradient norm is below this share of the
#: median leaf's are left out of change_gap
STILL_LEAF = 1e-3


def loss_gap(prog: List[float], ref: List[float]) -> float:
    return max(abs(a - b) / abs(b) for a, b in zip(prog, ref, strict=True))


def whole(norms: Dict[str, float]) -> Dict[str, float]:
    """The whole leaves' norms."""
    return {k: v for k, v in norms.items() if "#" not in k}


def units(norms: Dict[str, float]) -> List[str]:
    """What change_gap reads: each part of a split leaf, each other leaf
    whole."""
    split = {k.split("#")[0] for k in norms if "#" in k}
    return [k for k in norms if k not in split]


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
             leaves: Optional[List[str]] = None,
             med: Optional[float] = None) -> Tuple[float, str]:
    """(worst gap, its leaf) over ``leaves`` (default: every whole leaf);
    ``med`` defaults to the median whole leaf's reference norm."""
    leaves = list(whole(ref)) if leaves is None else leaves
    med = statistics.median(whole(ref).values()) if med is None else med

    def gap(k):
        return abs(prog[k] - ref[k]) / max(ref[k], med)

    worst = max(leaves, key=gap)
    return gap(worst), worst


def moving(ref_grad: Dict[str, float]) -> List[str]:
    """The leaves and parts whose reference gradient is not nought to
    rounding."""
    med = statistics.median(whole(ref_grad).values())
    return [k for k in units(ref_grad) if ref_grad[k] >= STILL_LEAF * med]


def training_readings(prog: dict, ref: dict) -> dict:
    """``prog``/``ref``: {"losses": [...], "grad": {leaf: norm},
    "change": {leaf: norm}}."""
    g, g_leaf = leaf_gap(prog["grad"], ref["grad"])
    read = moving(ref["grad"])
    c, c_leaf = leaf_gap(prog["change"], ref["change"], read)
    return {"loss_gap": loss_gap(prog["losses"], ref["losses"]),
            "grad_gap": g, "change_gap": c,
            "_leaves": {"grad_gap": g_leaf, "change_gap": c_leaf,
                        "left_out": len(units(ref["grad"])) - len(read)}}


def judge(readings: dict, limits: dict) -> Tuple[bool, Dict[str, dict]]:
    """Every limited number must read at or under its limit; a number
    that is missing or not finite fails."""
    out, ok = {}, True
    for name, spec in limits.items():
        v = readings.get(name)
        good = v is not None and math.isfinite(v) and v <= spec["limit"]
        ok = ok and good
        finite = v is not None and math.isfinite(v)
        out[name] = {"value": v if finite else None, "limit": spec["limit"]}
    return ok, out
