"""The comparison that decides ``correct``: host float64 arithmetic.

For a reduced QR (Q m x k, R k x n) of A, these numbers, each in units
of ``eps(float32) * max(m, n)`` so that one limit serves every size:

  residual       ||Q R - A||_F / ||A||_F
  orthogonality  ||Q^T Q - I||_F
  r_vs_ref       ||D R - R_ref||_F / ||R_ref||_F, with R_ref the float64
                 LAPACK R (the reference) and D = diag(+-1) matching the
                 signs of the diagonals (R is unique up to row signs).
  r_vs_ref_cond  r_vs_ref / cond_2(A): R's error over its sensitivity,
                 for inputs whose condition swings from one to the next
                 (cond_2(A) = cond_2(R_ref), an SVD of R_ref in float64).

Copied from the program's ``chip_smoke.py`` host comparison (the first
three).  A cell compares the numbers its workload file gives limits for;
how each limit was set is in PERF.md.
"""

from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

EPS32 = float(np.finfo(np.float32).eps)
NUMBERS = ("residual", "orthogonality", "r_vs_ref", "r_vs_ref_cond")


def unit(m: int, n: int) -> float:
    return EPS32 * max(m, n)


def errors(a, q, r, r_ref,
           names: Sequence[str] = NUMBERS[:3]) -> Dict[str, float]:
    """The numbers ``names`` for one factorization, in units of
    :func:`unit`.  Non-finite output reads as infinity."""
    a = np.asarray(a, np.float64)
    q = np.asarray(q, np.float64)
    r = np.asarray(r, np.float64)
    m, n = a.shape
    k = r_ref.shape[0]
    if q.shape != (m, k) or r.shape != (k, n):
        return {name: float("inf") for name in names}
    sign = np.sign(np.diag(r)[:k]) * np.sign(np.diag(r_ref))
    sign[sign == 0] = 1.0
    out = {
        "residual": np.linalg.norm(q @ r - a) / np.linalg.norm(a),
        "orthogonality": np.linalg.norm(q.T @ q - np.eye(k)),
        "r_vs_ref": (np.linalg.norm(sign[:, None] * r - r_ref)
                     / np.linalg.norm(r_ref)),
    }
    if "r_vs_ref_cond" in names:
        out["r_vs_ref_cond"] = out["r_vs_ref"] / np.linalg.cond(r_ref)
    u = unit(m, n)
    return {name: (float(out[name]) / u if np.isfinite(out[name])
                   else float("inf")) for name in names}


class Worst:
    """Largest reading of each number over the factorizations compared,
    and how many of them missed a limit."""

    def __init__(self, limits: Dict[str, float]):
        self.names = tuple(name for name in NUMBERS if name in limits)
        self.limits = {name: float(limits[name]) for name in self.names}
        self.worst = {name: 0.0 for name in self.names}
        self.compared = 0
        self.failed = 0

    def add(self, errs: Dict[str, float]) -> bool:
        """Fold in one factorization's readings."""
        self.compared += 1
        ok = True
        for name in self.names:
            self.worst[name] = max(self.worst[name], errs[name])
            ok &= errs[name] <= self.limits[name]
        self.failed += not ok
        return ok

    @property
    def correct(self) -> bool:
        return self.compared > 0 and self.failed == 0

    def checks(self) -> Dict[str, Dict[str, float]]:
        return {name: {"value": self.worst[name],
                       "limit": self.limits[name]} for name in self.names}
