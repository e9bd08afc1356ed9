"""Blocked (WY-representation) Householder QR — ``DGEQRF`` / ``DGEQRFHT``.

Paper §2.3/§4: the blocked algorithm factors a b-column *panel* with the
unblocked transform (classical HT or MHT), accumulates the reflectors into
the compact WY form

    H_{j0} H_{j0+1} ... H_{j0+b-1} = I - V T V^T        (T upper triangular)

and applies the aggregate to the trailing matrix with three GEMMs

    C <- C - V (T^T (V^T C))

so the trailing update runs at Level-3 (MXU) intensity.  ``DGEQRFHT`` is
this routine with MHT panels — the combination the paper shows reaching
99.3% of DGEMM throughput on the co-designed PE.

Kernel dispatch: with ``use_kernel=True`` the panel factorization runs in
the Pallas ``mht_panel`` kernel (whole panel VMEM-resident) and the
trailing update in the fused ``wy_trailing`` kernel (one HBM pass over C).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.householder import _write_packed_column, _zeros_carry, house_vector
from repro.core.mht import mht_update

Array = jax.Array

__all__ = ["larft", "geqrf", "panel_factor", "unpack_v_panel", "wy_apply"]


def larft(v: Array, taus: Array) -> Array:
    """Form the upper-triangular block reflector T (LAPACK ``DLARFT``,
    direction=Forward, storage=Columnwise).

    ``v`` is (m, b) unit-lower-trapezoidal, ``taus`` length b.
    """
    b = v.shape[1]
    gram = v.T @ v  # (b, b); only the strictly-lower part is consumed

    def body(i, t):
        cols = jnp.arange(b)
        mask = cols < i
        w = jnp.where(mask, jnp.take(gram, i, axis=1), 0.0)  # V[:, :i]^T v_i
        tcol = -jnp.take(taus, i) * (t @ w)
        tcol = jnp.where(mask, tcol, 0.0)
        tcol = jnp.where(cols == i, jnp.take(taus, i), tcol)
        return t.at[:, i].set(tcol)

    t0 = _zeros_carry((b, b), v)
    return lax.fori_loop(0, b, body, t0)


def unpack_v_panel(panel: Array, row0: int) -> Array:
    """Extract the unit-lower-trapezoidal V from a packed panel whose
    pivot rows start at ``row0`` (column lj pivots at row ``row0 + lj``)."""
    m, b = panel.shape
    rows = jnp.arange(m)[:, None]
    pivs = row0 + jnp.arange(b)[None, :]
    v = jnp.where(rows > pivs, panel, 0.0)
    return v + (rows == pivs).astype(panel.dtype)


def panel_factor(
    panel: Array, row0: int, *, method: str = "mht"
) -> Tuple[Array, Array]:
    """Factor an (m, b) panel whose pivot rows start at ``row0``.

    Rows above each column's pivot are preserved (they hold R entries from
    earlier trailing updates).  ``method``: "mht" (fused update) or "ht"
    (classical two-pass).
    """
    if method not in ("mht", "ht"):
        raise ValueError(f"unknown panel method: {method!r}")
    b = panel.shape[1]
    taus0 = _zeros_carry((b,), panel)

    def body(lj, carry):
        p, taus = carry
        x = jnp.take(p, lj, axis=1)
        pivot = row0 + lj
        v, tau, beta = house_vector(x, pivot)
        v = jnp.asarray(v, p.dtype)
        tau_c = jnp.asarray(tau, p.dtype)
        if method == "mht":
            p = mht_update(p, v, tau_c, lj)
        else:
            n = p.shape[1]
            trailing = jnp.arange(n) > lj
            w = tau_c * (v @ p)  # pass 1: DGEMV
            upd = jnp.outer(v, w)  # pass 2: DGER
            p = p - jnp.where(trailing[None, :], upd, 0.0)
        p = _write_packed_column(p, v, jnp.asarray(beta, p.dtype), lj, pivot)
        taus = taus.at[lj].set(tau_c)
        return p, taus

    return lax.fori_loop(0, b, body, (panel, taus0))


def wy_apply(v: Array, t: Array, c: Array, *, use_kernel: bool = False) -> Array:
    """Trailing update ``C <- C - V (T^T (V^T C))`` (applies Q^T).

    The kernel path fuses all three products into a single pass over C
    (:mod:`repro.kernels.wy_trailing`)."""
    if use_kernel:
        from repro.kernels import ops  # lazy: kernels.ref imports core

        return ops.wy_trailing(v, t, c)
    w = v.T @ c
    w = t.T @ w
    return c - v @ w


@functools.partial(jax.jit, static_argnames=("block",))
def geqrf_fori(a: Array, *, block: int = 128) -> Tuple[Array, Array]:
    """Blocked MHT QR with a ``fori_loop`` over panels — O(1) HLO size.

    The trailing update runs full-width with a column mask (~2x the FLOPs
    of the exact-width unrolled :func:`geqrf`), which is the right trade
    when n is large and the QR is a small fraction of the step (the
    QR-Muon optimizer path: one fused program regardless of matrix size).
    Requires ``min(m, n) % block == 0`` — callers pad.
    """
    m, n = a.shape
    k = min(m, n)
    if k % block != 0:
        raise ValueError(f"min(m,n)={k} not divisible by block={block}")
    npanels = k // block
    taus0 = _zeros_carry((k,), a)

    def body(pidx, carry):
        a, taus = carry
        j0 = pidx * block
        panel = lax.dynamic_slice(a, (0, j0), (m, block))
        panel_f, taus_p = panel_factor(panel, j0)
        a = lax.dynamic_update_slice(a, panel_f, (0, j0))
        taus = lax.dynamic_update_slice(taus, taus_p, (j0,))
        v = unpack_v_panel(panel_f, j0)
        t = larft(v, taus_p)
        w = t.T @ (v.T @ a)
        colmask = jnp.arange(n)[None, :] >= (j0 + block)
        a = a - jnp.where(colmask, v @ w, 0.0)
        return a, taus

    return lax.fori_loop(0, npanels, body, (a, taus0))


@functools.partial(jax.jit, static_argnames=("block", "panel_method", "use_kernel"))
def geqrf(
    a: Array,
    *,
    block: int = 32,
    panel_method: str = "mht",
    use_kernel: bool = False,
) -> Tuple[Array, Array]:
    """Blocked WY QR factorization.

    ``panel_method="ht"`` gives DGEQRF; ``"mht"`` gives DGEQRFHT.  Output
    is bit-compatible in layout with :func:`repro.core.householder.geqr2`:
    (packed, taus).
    """
    m, n = a.shape
    k = min(m, n)
    taus = _zeros_carry((k,), a)

    j0 = 0
    while j0 < k:
        bw = min(block, k - j0)
        panel = lax.dynamic_slice(a, (0, j0), (m, bw))
        if use_kernel:
            from repro.kernels import ops  # lazy

            panel_f, taus_p = ops.mht_panel(panel, row0=j0)
        else:
            panel_f, taus_p = panel_factor(panel, j0, method=panel_method)
        a = lax.dynamic_update_slice(a, panel_f, (0, j0))
        taus = lax.dynamic_update_slice(taus, taus_p, (j0,))

        if j0 + bw < n:
            v = unpack_v_panel(panel_f, j0)
            t = larft(v, taus_p)
            c = lax.dynamic_slice(a, (0, j0 + bw), (m, n - j0 - bw))
            c = wy_apply(v, t, c, use_kernel=use_kernel)
            a = lax.dynamic_update_slice(a, c, (0, j0 + bw))
        j0 += bw

    return a, taus


# -- registry -----------------------------------------------------------------
from repro.core.plan import MethodSpec, QRConfig, register_method  # noqa: E402


def _vmem_geqrf_panel(m: int, n: int, cfg: QRConfig) -> int:
    """Working set of the kernel path: the widest VMEM-resident panel or
    one trailing-update step, whichever is larger."""
    from repro.kernels import ops

    b = min(cfg.block, n)
    return max(ops.vmem_bytes_mht_panel(m, b),
               ops.vmem_bytes_wy_trailing(m, b))


register_method(MethodSpec(
    name="geqrf",
    factor=lambda a, cfg: geqrf(a, block=cfg.block, panel_method="ht",
                                use_kernel=False),
    description="blocked WY, classical HT panels (LAPACK DGEQRF)",
))

register_method(MethodSpec(
    name="geqrf_ht",
    factor=lambda a, cfg: geqrf(a, block=cfg.block, panel_method="mht",
                                use_kernel=bool(cfg.use_kernel)),
    kernel_backed=True,
    vmem_bytes=_vmem_geqrf_panel,
    description="blocked WY, MHT panels (LAPACK DGEQRFHT) [default]",
))


def _resolve_geqrf_fori(m: int, n: int, cfg: QRConfig, *, dtype=None,
                        explain=None, backend=None) -> QRConfig:
    del dtype, explain, backend  # divisibility is all that is checked
    k = min(m, n)
    if k % cfg.block != 0:
        raise ValueError(
            f"geqrf_fori needs min(m,n) divisible by block "
            f"(got {m}x{n}, block={cfg.block}); callers pad")
    return cfg


register_method(MethodSpec(
    name="geqrf_fori",
    factor=lambda a, cfg: geqrf_fori(a, block=cfg.block),
    resolve=_resolve_geqrf_fori,
    description="blocked MHT with fori_loop panels — O(1)-HLO optimizer path",
))
