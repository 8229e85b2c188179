"""The comparison that decides ``correct``: program against reference.

Numbers, each held to a limit of its own from ``bench/limits/<cell>.json``:

- ``loss_gap``: largest |L_prog − L_ref| / |L_ref| over the compared rounds'
  mean local-phase losses (forward pass, data feed);
- ``u_gap``: largest |u_prog − u_ref| over rounds × workers, u = log of the
  worker-master distance (the comm phase's input);
- ``h_gap``: largest |h_prog − h_ref| / α over h1 and h2, rounds × workers
  (the dynamic-weighting maps and the failure mask);
- ``m_gap``, ``v_gap``: AdaHessian's first and second moments after the
  first chunk, the gradient and the Hutchinson diagonal as the optimizer
  got them, by the worst leaf: | ‖x_prog‖ − ‖x_ref‖ | over the larger of
  ‖x_ref‖ and the median leaf's ‖x_ref‖;
- ``change_gap``: the same for the change of the master and of every worker
  from the initial weights after all compared rounds. Leaves whose
  reference gradient (first moment) is under a thousandth of the median
  leaf's are left out: they move by round-off alone.

A number that is not finite fails its limit.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import numpy as np

NOUGHT = 1e-3  # leaves with a reference gradient below this share of the
#                median leaf's move by round-off alone


def worst_leaf(prog: np.ndarray, ref: np.ndarray,
               keep: np.ndarray | None = None) -> float:
    """Rows are workers (or the master), columns leaves."""
    prog, ref = np.atleast_2d(prog), np.atleast_2d(ref)
    floor = np.median(ref, axis=1, keepdims=True)
    gap = np.abs(prog - ref) / np.maximum(ref, floor)
    if keep is not None:
        gap = gap[:, keep]
    return float(np.max(gap)) if gap.size else 0.0


def numbers(prog, ref, alpha: float) -> Dict[str, float]:
    """``prog`` and ``ref`` are ``bench.reference.Readings``."""
    n = len(ref.loss)
    g = ref.m_norm.mean(axis=0)
    keep = g >= NOUGHT * np.median(g)
    return {
        "loss_gap": float(np.max(np.abs(prog.loss[:n] - ref.loss)
                                 / np.abs(ref.loss))),
        "u_gap": float(np.max(np.abs(prog.u[:n] - ref.u))),
        "h_gap": float(max(np.max(np.abs(prog.h1[:n] - ref.h1)),
                           np.max(np.abs(prog.h2[:n] - ref.h2)))) / alpha,
        "m_gap": worst_leaf(prog.m_norm, ref.m_norm),
        "v_gap": worst_leaf(prog.v_norm, ref.v_norm),
        "change_gap": max(
            worst_leaf(prog.master_change, ref.master_change, keep),
            worst_leaf(prog.worker_change, ref.worker_change, keep)),
    }


def judge(nums: Dict[str, float], limits: Dict[str, dict]
          ) -> Tuple[bool, Dict[str, dict]]:
    """Every number that has a limit must be finite and within it."""
    checks, ok = {}, True
    for name, lim in limits.items():
        if name.startswith("_"):
            continue
        if name not in nums:
            raise KeyError(f"limit for unknown number {name!r}")
        v, limit = nums[name], float(lim["limit"])
        checks[name] = {"value": v, "limit": limit}
        ok = ok and math.isfinite(v) and v <= limit
    return ok, checks
