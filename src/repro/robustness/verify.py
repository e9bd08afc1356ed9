"""Post-dispatch health checks: is the factorization a factorization?

Householder QR has cheap, well-conditioned post-conditions — for an
accepted (Q, R) of an m x n input A,

    relative residual   ||A - Q R||_F / ||A||_F        <= tol
    orthogonality       ||Q^T Q - I||_F                <= tol

both hold to O(eps * max(m, n)) for HT and MHT orderings (paper §IV)
and for the tiled flat-tree DAG, so an O(mn k) check certifies an
O(mn^2) factorization.  The tolerance is **derived from the repo's
conformance rule** (tests/test_conformance.py pins every registered
method to ``100 * eps(dtype) * max(m, n)``): a dispatch whose output a
conformance test would fail is exactly a dispatch the escalation
ladder should retry.

For R-only results (serving mode="r") there is no Q to test; the Gram
identity ``A^T A = R^T R`` stands in — its backward error carries the
same eps * max(m, n) scaling relative to ||A||_F^2.

Batched dispatches are checked **per slice** with one vmapped jitted
program (:func:`check_batch` / :func:`check_ortho_batch`) so a single
bad slice is identified and re-solved alone — the rest of the bucket's
results ship as-is.

The knob: ``QRConfig.verify`` (tri-state) with the ``REPRO_VERIFY``
environment default.  Resolution is host-side only
(:func:`verify_enabled`), and verification never runs under a trace —
the verify-off (and traced) paths are jaxpr-identical to an unchecked
solve, pinned in tests/test_robustness.py.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "HealthReport",
    "VERIFY_TOL_FACTOR",
    "check_batch",
    "check_ortho",
    "check_ortho_batch",
    "check_qr",
    "check_r",
    "tolerance",
    "verify_enabled",
]

# The conformance suite's single tolerance rule (tests/test_conformance.py
# ``_tol``): every registered method is held to 100 * eps * max(m, n).
# Health checks reuse it verbatim so "fails verification" and "would
# fail conformance" are the same predicate.
VERIFY_TOL_FACTOR = 100.0


def tolerance(dtype, m: int, n: int) -> float:
    """The conformance rule: ``100 * eps(dtype) * max(m, n)``."""
    eps = float(jnp.finfo(jnp.dtype(dtype)).eps)
    return VERIFY_TOL_FACTOR * eps * max(m, n, 1)


def verify_enabled(flag: Optional[bool] = None) -> bool:
    """Resolve the tri-state verify knob: an explicit True/False wins;
    None falls back to the ``REPRO_VERIFY`` environment default (read
    at call time, so tests and deployments can flip it live)."""
    if flag is not None:
        return bool(flag)
    return os.environ.get("REPRO_VERIFY", "").strip().lower() in (
        "1", "true", "on", "yes")


@dataclasses.dataclass(frozen=True)
class HealthReport:
    """One slice's verdict.  ``reason`` is None when healthy, else a
    stable slug ("nonfinite_output" | "residual_exceeds_tol" |
    "ortho_defect_exceeds_tol" | "gram_residual_exceeds_tol")."""

    ok: bool
    residual: float
    ortho_defect: float
    tol: float
    reason: Optional[str] = None


def _report(residual: float, defect: float, tol: float,
            gram: bool = False) -> HealthReport:
    residual, defect = float(residual), float(defect)
    if not (np.isfinite(residual) and np.isfinite(defect)):
        reason = "nonfinite_output"
    elif residual > tol:
        reason = "gram_residual_exceeds_tol" if gram \
            else "residual_exceeds_tol"
    elif defect > tol:
        reason = "ortho_defect_exceeds_tol"
    else:
        reason = None
    return HealthReport(ok=reason is None, residual=residual,
                        ortho_defect=defect, tol=tol, reason=reason)


# --------------------------------------------------------- jitted stats
# Products at full precision: a check must not be coarser than the
# factorization it judges (XLA on TPU would otherwise run a float32
# product as one bfloat16 pass).

def _mm(x, y):
    return jnp.matmul(x, y, precision=jax.lax.Precision.HIGHEST)


# One compiled program per (batch, m, n, k, dtype) signature; jit's own
# cache keys on shapes so repeated buckets reuse their executable.

@jax.jit
def _qr_stats(a, q, r):
    """Per-slice (relative residual, orthogonality defect) over a
    leading batch axis.  Empty (all-zero) padding slices report 0/0."""
    b = a.shape[0]
    resid = jnp.linalg.norm((a - _mm(q, r)).reshape(b, -1), axis=-1)
    scale = jnp.linalg.norm(a.reshape(b, -1), axis=-1)
    rel = jnp.where(scale > 0, resid / jnp.maximum(scale, 1e-300), resid)
    k = q.shape[-1]
    gram = _mm(jnp.swapaxes(q, -1, -2), q) - jnp.eye(k, dtype=q.dtype)
    defect = jnp.linalg.norm(gram.reshape(b, -1), axis=-1)
    return rel, defect


@jax.jit
def _r_stats(a, r):
    """Per-slice Gram residual ||A^T A - R^T R||_F / ||A||_F^2 plus an
    upper-triangularity defect (relative mass below the diagonal)."""
    b = a.shape[0]
    ata = _mm(jnp.swapaxes(a, -1, -2), a)
    rtr = _mm(jnp.swapaxes(r, -1, -2), r)
    resid = jnp.linalg.norm((ata - rtr).reshape(b, -1), axis=-1)
    scale = jnp.linalg.norm(a.reshape(b, -1), axis=-1) ** 2
    rel = jnp.where(scale > 0, resid / jnp.maximum(scale, 1e-300), resid)
    low = r - jnp.triu(r)
    rscale = jnp.linalg.norm(r.reshape(b, -1), axis=-1)
    tri = jnp.linalg.norm(low.reshape(b, -1), axis=-1) \
        / jnp.maximum(rscale, 1e-300)
    return rel, tri


@jax.jit
def _ortho_stats(q):
    b = q.shape[0]
    k = q.shape[-1]
    gram = _mm(jnp.swapaxes(q, -1, -2), q) - jnp.eye(k, dtype=q.dtype)
    return jnp.linalg.norm(gram.reshape(b, -1), axis=-1)


# ------------------------------------------------------- public checks

def check_qr(a, q, r, *, tol: Optional[float] = None) -> HealthReport:
    """Health of one (Q, R) against its input."""
    a, q, r = jnp.asarray(a), jnp.asarray(q), jnp.asarray(r)
    m, n = int(a.shape[-2]), int(a.shape[-1])
    tol = tolerance(a.dtype, m, n) if tol is None else tol
    rel, defect = _qr_stats(a[None], q[None], r[None])
    return _report(rel[0], defect[0], tol)


def check_r(a, r, *, tol: Optional[float] = None) -> HealthReport:
    """Health of an R-only result via the Gram identity."""
    a, r = jnp.asarray(a), jnp.asarray(r)
    m, n = int(a.shape[-2]), int(a.shape[-1])
    tol = tolerance(a.dtype, m, n) if tol is None else tol
    rel, tri = _r_stats(a[None], r[None])
    return _report(rel[0], tri[0], tol, gram=True)


def check_ortho(q, *, tol: Optional[float] = None) -> HealthReport:
    """Orthogonality-only health (the optimizer path holds Q, not R)."""
    q = jnp.asarray(q)
    m, n = int(q.shape[-2]), int(q.shape[-1])
    tol = tolerance(q.dtype, m, n) if tol is None else tol
    defect = _ortho_stats(q[None])
    return _report(0.0, defect[0], tol)


def check_batch(a_stack, q_stack, r_stack, *,
                tol: Optional[float] = None) -> List[HealthReport]:
    """Per-slice health of one batched (Q, R) dispatch — ONE vmapped
    jitted stats program, then host-side verdicts, so a single bad
    slice is identified without re-running the good ones.  Pass
    ``q_stack=None`` for R-only buckets (Gram-identity check)."""
    a_stack = jnp.asarray(a_stack)
    m, n = int(a_stack.shape[-2]), int(a_stack.shape[-1])
    tol = tolerance(a_stack.dtype, m, n) if tol is None else tol
    if q_stack is None:
        rel, defect = _r_stats(a_stack, jnp.asarray(r_stack))
        gram = True
    else:
        rel, defect = _qr_stats(a_stack, jnp.asarray(q_stack),
                                jnp.asarray(r_stack))
        gram = False
    rel = np.asarray(rel)
    defect = np.asarray(defect)
    return [_report(rel[i], defect[i], tol, gram=gram)
            for i in range(rel.shape[0])]


def check_ortho_batch(q_stack, *, tol: Optional[float] = None
                      ) -> List[HealthReport]:
    """Per-slice orthogonality defects of a batched thin-Q stack."""
    q_stack = jnp.asarray(q_stack)
    m, n = int(q_stack.shape[-2]), int(q_stack.shape[-1])
    tol = tolerance(q_stack.dtype, m, n) if tol is None else tol
    defect = np.asarray(_ortho_stats(q_stack))
    return [_report(0.0, defect[i], tol) for i in range(defect.shape[0])]
