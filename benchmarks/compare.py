"""The comparison that decides ``correct`` for a training cell.

Both sides give: the loss of each of the first steps, the per-leaf norm of
the first gradient (the momentum trace after one step) and the per-leaf norm
of the parameters' change after those steps.  A leaf's gap is the distance
between the two norms over the reference's norm of that leaf or of the median
leaf, whichever is larger; the numbers are the worst, the 90th-centile and the
median leaf's.  Which of them a cell compares, and against what limit, is in
its ``limits/<cell>.json``."""

from __future__ import annotations

import math

import numpy as np


def leaf_gaps(got, want) -> np.ndarray:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape:
        return np.full(want.shape, np.inf)
    gap = np.abs(got - want) / np.maximum(want, np.median(want))
    return np.where(np.isfinite(gap), gap, np.inf)


def whole_gap(got, want) -> float:
    """Gap between the two norms over all leaves together."""
    a = math.sqrt(float(np.sum(np.square(np.asarray(got, np.float64)))))
    b = math.sqrt(float(np.sum(np.square(np.asarray(want, np.float64)))))
    gap = abs(a - b) / b if b > 0 else math.inf
    return gap if math.isfinite(gap) else math.inf


def numbers(got: dict, want: dict) -> dict:
    """``{name: value}`` for every number compared."""
    out = {}
    for i, (a, b) in enumerate(zip(got["loss"], want["loss"])):
        gap = abs(float(a) - float(b)) / abs(float(b))
        out[f"loss{i + 1}_gap"] = gap if math.isfinite(gap) else math.inf
    # a leaf whose gradient is nought to rounding in the reference (a key's
    # bias under softmax) moves by round-off alone: its change is not compared
    moved = np.asarray(want["gnorm"], np.float64)
    moved = moved >= 1e-3 * np.median(moved)
    out["leaves_not_moved"] = int((~moved).sum())
    for what, key in (("grad", "gnorm"), ("change", "dnorm")):
        gaps = leaf_gaps(got[key], want[key])
        if what == "change" and gaps.shape == moved.shape:
            gaps = np.where(moved, gaps, 0.0)
        out[f"{what}_norm_gap"] = float(gaps.max())
        out[f"{what}_worst_leaf"] = int(gaps.argmax())
        out[f"{what}_norm_gap_median_leaf"] = float(np.median(gaps))
        out[f"{what}_norm_gap_p90_leaf"] = float(np.quantile(gaps, 0.9))
        out[f"{what}_norm_gap_whole"] = whole_gap(got[key], want[key])
    return out


def judge(nums: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, {name: [value, limit]})`` over the names ``limits`` holds."""
    table = {k: [nums[k], lim] for k, lim in limits.items()}
    ok = all(math.isfinite(v) and v <= lim for v, lim in table.values())
    return ok, table
