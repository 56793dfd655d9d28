"""The comparison that decides ``correct`` for a training cell.

The program's readings and the reference's are those of the first steps of
a run (``bench/reference/train.py``): the loss of each step, the per-slice
norms of the first gradient as the optimizer received it, and the per-slice
norms of the parameters' change after the last of those steps.  A slice is
one layer's part of a stacked weight, or a whole unstacked weight.

- ``loss_gap``: the largest |loss - loss_ref| / |loss_ref| over the steps.
- ``grad_norm_gap``, ``change_norm_gap``: by the worst slice, the gap
  between the program's norm and the reference's (not the norm of their
  difference), over the larger of the reference's norm of that slice and
  the median slice's norm, since some gradients are all but zero.
- The change leaves out slices whose reference gradient is under a
  thousandth of the median slice's: such a slice (a key bias, under the
  softmax) moves under Adam by round-off alone.
- ``picks_differ`` (data-parallel cells): the number of steps at which the
  program's controller picked another program than the reference's; an
  exact comparison, limit 0.

Each number passes when it is at most its limit; the workload file gives
the limits, one for every number computed and none for a number that is
not, and PERF.md the readings each was set from.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

CHANGE_MIN_GRAD = 1e-3    # of the median slice's reference gradient norm


def loss_gap(prog: Sequence[float], ref: Sequence[float]) -> float:
    return float(max(abs(a - b) / abs(b) for a, b in zip(prog, ref)))


def norm_gap(prog, ref, keep: Optional[np.ndarray] = None):
    """-> (gap, index of the worst slice)."""
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    scale = np.maximum(ref, np.median(ref))
    gap = np.abs(prog - ref) / np.maximum(scale, 1e-30)
    if keep is not None:
        gap = np.where(keep, gap, 0.0)
    i = int(np.argmax(gap))
    return float(gap[i]), i


def numbers(prog: dict, ref: dict, names: List[str]) -> Dict[str, dict]:
    """The compared numbers, each with the slice or step it came from."""
    grad_ref = np.asarray(ref["grad0"], np.float64)
    moved = grad_ref >= CHANGE_MIN_GRAD * np.median(grad_ref)
    g, gi = norm_gap(prog["grad0"], ref["grad0"])
    c, ci = norm_gap(prog["change"], ref["change"], keep=moved)
    out = {
        "loss_gap": {"value": loss_gap(prog["losses"], ref["losses"])},
        "grad_norm_gap": {"value": g, "at": names[gi]},
        "change_norm_gap": {"value": c, "at": names[ci],
                            "left_out": int((~moved).sum())},
    }
    if "picks" in prog:
        out["picks_differ"] = {"value": float(sum(
            a != b for a, b in zip(prog["picks"], ref["picks"])))}
    return out


def judge(nums: Dict[str, dict], limits: Dict[str, float]):
    """-> (correct, checks): every number beside its limit."""
    missing = sorted(set(limits) - set(nums))
    if missing:
        raise KeyError(f"limits for numbers never computed: {missing}")
    checks = []
    for name, rec in nums.items():
        limit = limits[name]
        v = rec["value"]
        ok = bool(np.isfinite(v) and v <= limit)
        checks.append(dict(rec, name=name, limit=limit, ok=ok))
    return all(c["ok"] for c in checks), checks
