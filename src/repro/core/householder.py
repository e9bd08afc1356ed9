"""Classical Householder Transform (HT) QR factorization — paper §2.2 / Algorithm 2.

LAPACK ``DGEQR2`` semantics throughout the library:

    H_j = I - tau_j * v_j v_j^T,   v_j[0] = 1,   A = Q R,
    Q = H_0 H_1 ... H_{k-1},       k = min(m, n).

The factored form is packed LAPACK-style: R in the upper triangle, the
Householder vectors (sans their implicit leading 1) below the diagonal.

This module is the *classical* realization: per column, the Householder
matrix / reflection is applied to the trailing matrix in two separate
passes (GEMV then rank-1 update), mirroring the paper's Algorithm 2 where
``P = I - 2 v v^T`` is formed conceptually before the trailing update.
The Modified HT (paper §4) lives in :mod:`repro.core.mht`.

Everything is shape-static and ``jit``-compatible: the column loop is a
``lax.fori_loop`` over masked full-width operations.  :func:`form_q` is
the exception: it forms Q from 128-wide block reflectors (LAPACK
``DORGQR``, GEMMs on the MXU), while :func:`apply_q` keeps the
reflector-by-reflector loop (``DORM2R``).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.observability import metrics as _metrics

Array = jax.Array

__all__ = [
    "house_vector",
    "geqr2",
    "geqr2_explicit_p",
    "form_q",
    "apply_q",
    "unpack_r",
    "unpack_v",
]


def _safe_sign(x: Array) -> Array:
    """sign(x) with sign(0) := 1 (LAPACK convention for dlarfg)."""
    return jnp.where(x >= 0, jnp.ones_like(x), -jnp.ones_like(x))


def _zeros_carry(shape, like: Array) -> Array:
    """Zeros for a loop carry that inherit the varying-manual-axes type of
    ``like`` — required when the factorizations run inside ``shard_map``
    (a plain ``jnp.zeros`` carry is device-invariant and the scan carry
    types would mismatch)."""
    z = jnp.zeros(shape, like.dtype)
    return z + jnp.zeros((), like.dtype) * like.reshape(-1)[0]


def house_vector(x: Array, offset: Array | int) -> Tuple[Array, Array, Array]:
    """Compute the Householder reflector annihilating ``x[offset+1:]``.

    Rows ``< offset`` are ignored (masked to zero); the pivot is
    ``x[offset]``.  Returns ``(v, tau, beta)`` with ``v[offset] = 1``,
    ``v[i] = 0`` for ``i < offset``, and

        (I - tau v v^T) x = [*, ..., beta, 0, ..., 0]^T.

    Numerically this follows LAPACK ``dlarfg``:
        beta = -sign(x0) * ||x[offset:]||_2
        tau  = (beta - x0) / beta
        v[offset+1:] = x[offset+1:] / (x0 - beta)

    Degenerate case ``||x[offset+1:]|| == 0`` gives ``tau = 0`` (H = I).
    """
    m = x.shape[0]
    idx = jnp.arange(m)
    below = idx > offset
    at = idx == offset

    x0 = jnp.sum(jnp.where(at, x, 0.0))
    tail = jnp.where(below, x, 0.0)
    # Scale for overflow safety: ||tail||^2 computed on normalized data.
    scale = jnp.maximum(jnp.max(jnp.abs(tail)), jnp.abs(x0))
    scale = jnp.where(scale == 0.0, 1.0, scale)
    t = tail / scale
    x0s = x0 / scale
    tail_norm2 = jnp.sum(t * t)
    norm = scale * jnp.sqrt(x0s * x0s + tail_norm2)

    beta = -_safe_sign(x0) * norm
    degenerate = tail_norm2 == 0.0

    denom = jnp.where(degenerate, 1.0, x0 - beta)
    v = jnp.where(below, x / denom, 0.0)
    v = v + at.astype(x.dtype)  # v[offset] = 1
    tau = jnp.where(degenerate, 0.0, (beta - x0) / jnp.where(beta == 0.0, 1.0, beta))
    beta = jnp.where(degenerate, x0, beta)
    return v, tau, beta


def _ht_update_two_pass(a: Array, v: Array, tau: Array, col: Array) -> Array:
    """Classical trailing update, two passes (paper Algorithm 2 / fig 6).

    Pass 1 (DGEMV):  w = tau * (v^T A)
    Pass 2 (DGER):   A <- A - v w
    Columns ``<= col`` are left untouched (they hold R / packed V).
    """
    n = a.shape[1]
    trailing = jnp.arange(n) > col
    w = tau * (v @ a)  # (n,)
    update = jnp.outer(v, w)
    return a - jnp.where(trailing[None, :], update, 0.0)


def _write_packed_column(
    a: Array, v: Array, beta: Array, col: Array, pivot_row: Array | int | None = None
) -> Array:
    """Store beta at the pivot row and v (below the pivot) into column ``col``.

    ``pivot_row`` defaults to ``col`` (the square/aligned case); blocked
    panel factorizations pass ``pivot_row = row0 + local_col``.
    """
    m = a.shape[0]
    pivot = col if pivot_row is None else pivot_row
    idx = jnp.arange(m)
    newcol = jnp.where(idx == pivot, beta, jnp.where(idx > pivot, v, 0.0))
    oldcol = jnp.take(a, col, axis=1)
    newcol = jnp.where(idx < pivot, oldcol, newcol)
    return a.at[:, col].set(jnp.asarray(newcol, a.dtype))


@functools.partial(jax.jit, static_argnames=("num_cols",))
def geqr2(a: Array, *, num_cols: int | None = None) -> Tuple[Array, Array]:
    """Classical HT QR (LAPACK ``DGEQR2``): two-pass trailing updates.

    Returns ``(packed, taus)`` where ``packed`` holds R in its upper
    triangle and the Householder vectors below the diagonal, and
    ``taus`` has length ``min(m, n)``.
    """
    m, n = a.shape
    k = min(m, n) if num_cols is None else num_cols
    if m > 1 and k == min(m, n) and n >= m:
        # For square/wide, the last pivot still needs annihilation of 0 rows
        # below it only when m > k; keep full k columns.
        pass
    taus0 = _zeros_carry((k,), a)

    def body(j, carry):
        a, taus = carry
        x = jnp.take(a, j, axis=1)
        v, tau, beta = house_vector(x, j)
        # Store the Householder vector below the diagonal of column j, with
        # v[j] implicit (=1); store beta (the new R diagonal) at (j, j).
        a = _ht_update_two_pass(a, jnp.asarray(v, a.dtype), jnp.asarray(tau, a.dtype), j)
        a = _write_packed_column(a, jnp.asarray(v, a.dtype), jnp.asarray(beta, a.dtype), j)
        taus = taus.at[j].set(jnp.asarray(tau, a.dtype))
        return a, taus

    a_out, taus = lax.fori_loop(0, k, body, (a, taus0))
    return a_out, taus


@functools.partial(jax.jit, static_argnames=())
def geqr2_explicit_p(a: Array) -> Tuple[Array, Array]:
    """Textbook classical HT: materialize ``P = I - tau v v^T`` and GEMM.

    This is the paper's fig-6 DAG made literal — used for DAG/FLOP analysis
    and as the slowest baseline in the QR-variant benchmark. O(m^2 n) per
    column instead of O(mn).
    """
    m, n = a.shape
    k = min(m, n)
    taus0 = _zeros_carry((k,), a)
    eye = jnp.eye(m, dtype=a.dtype)

    def body(j, carry):
        a, taus = carry
        x = jnp.take(a, j, axis=1)
        v, tau, beta = house_vector(x, j)
        v = jnp.asarray(v, a.dtype)
        p = eye - jnp.asarray(tau, a.dtype) * jnp.outer(v, v)  # P materialized
        a_new = p @ a
        trailing = jnp.arange(n)[None, :] > j
        a = jnp.where(trailing, a_new, a)
        a = _write_packed_column(a, v, jnp.asarray(beta, a.dtype), j)
        taus = taus.at[j].set(jnp.asarray(tau, a.dtype))
        return a, taus

    a_out, taus = lax.fori_loop(0, k, body, (a, taus0))
    return a_out, taus


def unpack_r(packed: Array, n: int | None = None) -> Array:
    """Extract R (upper triangular, k x n) from the packed factorization."""
    m, ncols = packed.shape
    n = ncols if n is None else n
    k = min(m, ncols)
    r = jnp.triu(packed)[:k, :n]
    return r


def unpack_v(packed: Array) -> Array:
    """Extract V (m x k, unit lower trapezoidal) from the packed form."""
    m, n = packed.shape
    k = min(m, n)
    v = jnp.tril(packed[:, :k], -1)
    v = v + jnp.eye(m, k, dtype=packed.dtype)
    return v


def apply_q(packed: Array, taus: Array, c: Array, *, transpose: bool = False) -> Array:
    """Apply Q (or Q^T) from the packed factorization to ``c`` (m x p).

    Q   = H_0 H_1 ... H_{k-1}          (applied back-to-front)
    Q^T = H_{k-1} ... H_1 H_0          (applied front-to-back)
    """
    m = packed.shape[0]
    k = taus.shape[0]
    v_all = unpack_v(packed)  # (m, k)

    def apply_one(j, c):
        v = jnp.take(v_all, j, axis=1)
        tau = jnp.take(taus, j)
        w = tau * (v @ c)
        return c - jnp.outer(v, w)

    if transpose:
        c = lax.fori_loop(0, k, apply_one, c)
    else:
        c = lax.fori_loop(0, k, lambda i, c: apply_one(k - 1 - i, c), c)
    return c


#: Panel width of Q formation: the MXU's width.
Q_BLOCK = 128


def form_q(packed: Array, taus: Array, *, full: bool = False) -> Array:
    """Materialize Q — thin (m x k) by default, or full (m x m).

    Blocked WY Q formation (LAPACK ``DORGQR``): starting from the
    identity, the reflectors are applied back to front in panels of
    :data:`Q_BLOCK`, each as one block reflector ``I - V T V^T`` through
    three GEMMs.  Panel ``j0`` touches only ``Q[j0:, j0:]``: the rows and
    columns above ``j0`` still hold the identity's.  One jitted program
    per shape and ``full``; its products take the caller's matmul
    precision.

    Counters: ``householder.form_q`` (one per Q formed) and
    ``householder.form_q_panels`` (panels applied), labelled
    ``phase="execute"`` for a call on concrete arrays and
    ``phase="trace"`` for one under an outer trace (counted once per
    compiled program, as the ``engine.*`` series are).
    """
    phase = "trace" if isinstance(packed, jax.core.Tracer) else "execute"
    _metrics.counter("householder.form_q", phase=phase).inc()
    _metrics.counter("householder.form_q_panels", phase=phase).inc(
        -(-taus.shape[0] // Q_BLOCK))
    return _form_q_blocked(packed, taus, full=full)


@functools.partial(jax.jit, static_argnames=("full",))
def _form_q_blocked(packed: Array, taus: Array, *, full: bool) -> Array:
    from repro.core.blocked import larft, unpack_v_panel, wy_apply  # lazy: blocked imports us

    m = packed.shape[0]
    k = taus.shape[0]
    b = Q_BLOCK
    nfull = k // b
    q = jnp.eye(m, m if full else k, dtype=packed.dtype)
    if nfull:
        # T does not depend on Q: form it for every full panel in one
        # vmapped DLARFT over full-height V (zeros above each pivot).
        panels = packed[:, :nfull * b].reshape(m, nfull, b).transpose(1, 0, 2)
        vs = jax.vmap(unpack_v_panel)(panels, jnp.arange(nfull) * b)
        ts = jax.vmap(larft)(vs, taus[:nfull * b].reshape(nfull, b))
    for j0 in reversed(range(0, k, b)):
        bw = min(b, k - j0)
        v = unpack_v_panel(packed[j0:, j0:j0 + bw], 0)
        t = ts[j0 // b] if bw == b else larft(v, taus[j0:])
        # wy_apply applies the block's transpose: hand it T^T.
        q = q.at[j0:, j0:].set(wy_apply(v, t.T, q[j0:, j0:]))
    return q


# -- registry -----------------------------------------------------------------
from repro.core.plan import MethodSpec, register_method  # noqa: E402

register_method(MethodSpec(
    name="geqr2",
    factor=lambda a, cfg: geqr2(a),
    description="classical HT, two-pass updates (LAPACK DGEQR2)",
))
