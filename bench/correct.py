"""The comparison that decides a run's ``correct``.

The program's first three training steps (driven through the window's own
``train_epoch`` and feed, on three batches of distinct targets) against the
plain reference's three steps on the same blocks from the same weights:

* ``loss_gap``: the largest over the three steps of
  ``|loss_program - loss_reference| / |loss_reference|``.
* ``grad_norm_gap``: over the parameter leaves, the largest
  ``| ||g_program|| - ||g_reference|| |`` of the first step's gradient, over
  the larger of the reference leaf's norm and the median leaf's norm. The
  program's gradient is read from its Adam state after one step
  (``m_1 = (1 - b1) g_1``).
* ``update_norm_gap``: the same measure of the parameters' change over the
  three steps (the parameters step 4 starts from, less the initial ones).

Leaves whose reference gradient norm is under a thousandth of the median
leaf's are left out of both leaf measures: Adam moves such a leaf by its
rounding alone. Each number has its own limit, in the cell's workload file
(``correct_limits``); a run is correct when every number is finite and at
or under its limit.
"""
from __future__ import annotations

import numpy as np

NUMBERS = ("loss_gap", "grad_norm_gap", "update_norm_gap")
NEGLIGIBLE = 1e-3  # of the median leaf's reference gradient norm


def _leaves(tree) -> list[np.ndarray]:
    import jax

    return [np.asarray(x, np.float64) for x in jax.tree_util.tree_leaves(tree)]


def _norm_gap(got: list, want: list, keep: np.ndarray) -> float:
    g = np.array([np.linalg.norm(x) for x in got])[keep]
    w = np.array([np.linalg.norm(x) for x in want])[keep]
    scale = np.maximum(w, np.median(w))
    return float(np.max(np.abs(g - w) / scale))


def numbers(prog: dict, ref: dict, params0) -> dict:
    """The compared numbers. ``prog`` and ``ref`` each hold ``losses`` (one
    per step), ``grad1`` (first step's gradient) and ``params`` (after the
    last step), as pytrees of one layout."""
    lp, lr = np.asarray(prog["losses"], np.float64), np.asarray(ref["losses"], np.float64)
    if lp.shape != lr.shape:
        return {k: float("inf") for k in NUMBERS}
    g_ref = _leaves(ref["grad1"])
    ref_norms = np.array([np.linalg.norm(x) for x in g_ref])
    keep = ref_norms >= NEGLIGIBLE * np.median(ref_norms)
    p0 = _leaves(params0)
    d_prog = [a - b for a, b in zip(_leaves(prog["params"]), p0)]
    d_ref = [a - b for a, b in zip(_leaves(ref["params"]), p0)]
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_norm_gap": _norm_gap(_leaves(prog["grad1"]), g_ref, keep),
        "update_norm_gap": _norm_gap(d_prog, d_ref, keep),
    }


def judge(nums: dict, limits: dict) -> bool:
    """True when every number is finite and within its limit."""
    return all(
        np.isfinite(nums[k]) and nums[k] <= float(limits[k]) for k in NUMBERS
    )
