"""The comparisons that decide ``correct``.

Training: the program's readings of its first steps against the plain
reference's, three numbers, each against its limit in
``bench/limits/<cell>.json``:

- ``loss_gap``: the largest relative gap of a step's mean loss;
- ``grad_norm_gap``: over every (agent, leaf), the gap between the
  program's and the reference's norm of the first gradient, over the
  larger of the reference's norm of that leaf and of the median leaf;
- ``update_norm_gap``: the same for the norm of x(3) − x(0), leaving out
  the leaves whose reference gradient is under a thousandth of the median
  leaf's (they move by round-off alone).
"""
from __future__ import annotations

from typing import Dict

import numpy as np

TINY_GRAD = 1e-3
NOT_A_NUMBER = 1e300


def norm_gap(prog: np.ndarray, ref: np.ndarray, keep=None) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    keep = np.ones(ref.shape, bool) if keep is None else keep
    floor = np.median(ref[keep])
    gaps = np.abs(prog - ref) / np.maximum(ref, floor)
    return float(np.max(gaps[keep]))


def train_readings(prog: Dict, ref: Dict) -> Dict[str, float]:
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    keep = ref["grad_norms"] >= TINY_GRAD * np.median(ref["grad_norms"])
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_norm_gap": norm_gap(prog["grad_norms"], ref["grad_norms"]),
        "update_norm_gap": norm_gap(prog["change_norms"],
                                    ref["change_norms"], keep),
    }


def with_limits(readings: Dict[str, float], limits: Dict) -> Dict:
    """{name: {"value", "limit"}}; a reading that is missing or not a
    finite number reads as 1e300, so it fails."""
    out = {}
    for name, limit in limits["limits"].items():
        v = float(readings.get(name, NOT_A_NUMBER))
        v = v if np.isfinite(v) else NOT_A_NUMBER
        out[name] = {"value": v, "limit": float(limit)}
    return out
