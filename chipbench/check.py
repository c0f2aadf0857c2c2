"""The comparison that decides ``correct``.

Both sides give, for the first ``n`` steps of one seed:

- ``losses``  (n, pods): each step's loss per pod;
- ``grad1``   (pods, leaves): norm of each leaf of the first step's
  gradient as the optimizer receives it;
- ``change``  (pods, leaves): norm of each leaf's change from the initial
  weights to the weights the step after the last one starts from;
- ``resid``   (pods, leaves), under error feedback only: norm of each
  leaf's part of the residual the last round left.

The reference also gives ``grad1_f32``, its own float32 gradient norms.

The numbers compared:

- ``loss``:   the largest ``|L_prog - L_ref| / |L_ref|`` over steps and pods;
- ``grad1``:  over (pod, leaf), the largest gap between the two norms,
  ``|n_prog - n_ref|``, over the larger of the reference's norm of that
  leaf and the reference's median leaf norm;
- ``change``: the same for the weight change, leaving out leaves whose
  reference gradient is under a thousandth of the median leaf's (such a
  leaf moves by round-off alone);
- ``change_median``: the median of those per-leaf change gaps, where the
  worst leaf is a small one whose bf16 update is mostly rounding;
- ``resid``:  as ``grad1``, for the error-feedback residual.

A cell compares the numbers its limits file lists.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np

QUIET_LEAF = 1e-3
NUMBERS = ("loss", "grad1", "change", "change_median", "resid")


def _gaps(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray
          ) -> np.ndarray:
    """Per (pod, leaf) norm gaps; -1 where the leaf is left out."""
    med = float(np.median(ref[keep]))
    gap = np.abs(prog - ref) / np.maximum(ref, med)
    return np.where(keep, gap, -1.0)


def _worst(gap: np.ndarray, leaves: List[str]) -> Tuple[float, str]:
    pod, leaf = np.unravel_index(int(np.argmax(gap)), gap.shape)
    return float(gap[pod, leaf]), f"pod{pod}{leaves[leaf]}"


def compare(prog: Dict, ref: Dict) -> Dict:
    if list(prog["leaves"]) != list(ref["leaves"]):
        raise ValueError("program and reference trees differ: "
                         f"{prog['leaves']} vs {ref['leaves']}")
    leaves = list(ref["leaves"])
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    loss = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    every = np.ones_like(ref["grad1"], bool)
    grad1, grad1_at = _worst(_gaps(prog["grad1"], ref["grad1"], every),
                             leaves)
    g32 = np.asarray(ref["grad1_f32"])
    moving = g32 >= QUIET_LEAF * np.median(g32)
    gap = _gaps(prog["change"], ref["change"], moving)
    change, change_at = _worst(gap, leaves)
    out = {"loss": loss, "grad1": grad1, "change": change,
           "change_median": float(np.median(gap[moving])),
           "where": {"grad1": grad1_at, "change": change_at,
                     "quiet_leaves": int(np.sum(~moving))}}
    if "resid" in ref:
        out["resid"], out["where"]["resid"] = _worst(
            _gaps(prog["resid"], ref["resid"], every), leaves)
    return out


def judge(numbers: Dict, limits: Dict) -> Tuple[bool, Dict]:
    """``correct`` and, for each number that has a limit, its value and
    limit.  A cell with no limits is never correct."""
    checks = {}
    for name, entry in limits.get("limits", {}).items():
        value = numbers[name]
        checks[name] = {"value": value, "limit": entry["limit"]}
    ok = bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    return ok, checks
