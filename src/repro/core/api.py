"""Public QR API — thin wrappers over the :mod:`repro.core.plan` planner.

    qr(a, config=QRConfig(...))  -> (Q, R) or R       (batched: a.ndim >= 2)
    orthogonalize(m)             -> sign-fixed thin Q (optimizer primitive)
    lstsq(a, b)                  -> QR-based least-squares solve
    qr_algorithm_eig(a, iters)   -> eigenvalues via the QR algorithm (§1 App. 2)

Every realization lives in the method registry (see
:func:`repro.core.plan.available_methods`); the built-ins:

    "geqr2"      classical HT, two-pass updates          (LAPACK_DGEQR2)
    "geqr2_ht"   MHT, fused macro-op updates             (LAPACK_DGEQR2HT)
    "geqrf"      blocked WY, classical HT panels         (LAPACK_DGEQRF)
    "geqrf_ht"   blocked WY, MHT panels                  (LAPACK_DGEQRFHT)
    "geqrf_fori" blocked MHT, fori_loop panels           (optimizer path)
    "tsqr"       tall-skinny tree QR (single device)
    "tiled"      tiled task-graph QR via the wavefront macro-op engine
                 (GEQRT/TSQRT/LARFB/SSRFB; block = tile size;
                 use_kernel=True -> Pallas dispatch per
                 QRConfig.dispatch_mode: "wavefront" = one in-place
                 call per DAG level, "megakernel" = the whole schedule
                 as ONE persistent call over a scalar-prefetched task
                 table with double-buffered tile DMA, None = auto by
                 table/VMEM budgets; False -> the bitwise-identical
                 jnp oracle)
    "sharded_tiled"  multi-device tiled QR: per-device row-block
                 wavefront domains via shard_map + TSQR-style R merge
                 tree (ndomains = device domains; testable on CPU with
                 XLA_FLAGS=--xla_force_host_platform_device_count=8)
    "auto"       planner heuristics: tall-skinny => tsqr, large
                 near-square => tiled, past the tiled ceiling with >1
                 device => sharded_tiled, panel-fits-VMEM on TPU =>
                 kernel-backed geqrf_ht, single panel => geqr2_ht

Selection, batching (vmap over leading dims), and the Pallas kernel
policy (``use_kernel=None`` => compiled on TPU when the panel fits VMEM,
interpret-mode available on CPU) are all decided by
``plan(shape, dtype, config) -> QRSolver``; prefer holding a solver when
factorizing many same-shaped matrices.  Configuration is by
``config=QRConfig(...)`` only — the pre-planner string kwargs
(``method=``/``block=``/...) were removed after their deprecation cycle.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.plan import QRConfig, plan
from repro.observability import trace as _trace

Array = jax.Array

__all__ = ["qr", "orthogonalize", "lstsq", "qr_algorithm_eig",
           "QRConfig", "plan"]

_DEFAULT = QRConfig()


def qr(a: Array, *, config: Optional[QRConfig] = None
       ) -> Tuple[Array, Array] | Array:
    """QR factorization with a registry-selected HT/MHT realization.

    ``config.mode``: "reduced" -> (Q thin m x k, R k x n); "r" -> R only;
    "full" -> (Q m x m, R m x n).  Inputs with leading batch dims
    (``a.ndim > 2``) are factorized batch-wise via the solver's vmap rule.
    ``config=None`` plans with ``QRConfig()`` (method "auto").

    Spans (host time, never blocking): ``qr.call`` around the whole call,
    ``qr.plan`` around the planner.
    """
    if a.ndim < 2:
        raise ValueError(f"qr expects a matrix, got shape {a.shape}")
    cfg = _DEFAULT if config is None else config
    with _trace.span("qr.call", shape=a.shape):
        with _trace.span("qr.plan"):
            solver = plan(a.shape, a.dtype, cfg)
        if cfg.verify is not False and not isinstance(a, jax.core.Tracer):
            # Health-checked path (QRConfig.verify / $REPRO_VERIFY):
            # verify the planned result and walk the degradation ladder
            # on failure (repro.robustness.escalate).  Resolution is
            # host-side and never fires under a trace, so verify-off
            # stays jaxpr-identical to solver.solve — the lazy import
            # keeps the robustness layer out of the import graph until
            # the knob is actually on.
            from repro.robustness.verify import verify_enabled

            if verify_enabled(cfg.verify):
                from repro.robustness.escalate import checked_solve

                return checked_solve(solver, a)
        return solver.solve(a)


def orthogonalize(m_in: Array, *, config: Optional[QRConfig] = None) -> Array:
    """Nearest-column-space orthonormal factor via QR with sign fixing.

    Returns Q * diag(sign(diag(R))) so the result is a deterministic,
    continuous function of the input (the optimizer primitive; wide
    matrices are handled by factorizing the transpose).  With
    ``config=QRConfig()`` (method "auto") tall-skinny momentum routes
    through TSQR."""
    if m_in.ndim < 2:
        raise ValueError(f"orthogonalize expects a matrix, got shape {m_in.shape}")
    cfg = (_DEFAULT if config is None else config).replace(
        mode="reduced", sign_fix=True)
    transpose = m_in.shape[-2] < m_in.shape[-1]
    a = jnp.swapaxes(m_in, -1, -2) if transpose else m_in
    q = plan(a.shape, a.dtype, cfg).orthogonalize(a)
    return jnp.swapaxes(q, -1, -2) if transpose else q


def lstsq(a: Array, b: Array, *, config: Optional[QRConfig] = None) -> Array:
    """Least-squares solve ``min ||a x - b||`` via QR (m >= n).

    x = R^{-1} Q^T b — the numerically stable path the paper motivates for
    Kalman filtering (§1, Application 1).  With ``config=QRConfig()``
    tall-skinny systems route through TSQR."""
    cfg = (_DEFAULT if config is None else config).replace(
        mode="reduced", sign_fix=False)
    return plan(a.shape, a.dtype, cfg).lstsq(a, b)


def qr_algorithm_eig(a: Array, *, iters: int = 200,
                     config: Optional[QRConfig] = None) -> Array:
    """Eigenvalues of symmetric ``a`` via the (unshifted) QR algorithm —
    paper §1 Application 2, Algorithm 1:  A_{k} = R_k Q_k."""
    cfg = (_DEFAULT if config is None else config).replace(
        mode="reduced", sign_fix=False)
    solver = plan(a.shape, a.dtype, cfg)

    def body(_, ak):
        q, r = solver.solve(ak)
        return r @ q

    ak = jax.lax.fori_loop(0, iters, body, a)
    return jnp.sort(jnp.diagonal(ak))[::-1]
