"""The control: the reference algorithm on the chip, one precision lower.

The configurations state float32 products at ``highest`` precision (exact
to float32).  The step below it, the one that would tempt a later change,
is ``high``: three bfloat16 passes.  This module computes a plain blocked
Householder QR (LAPACK GEQRF: unblocked panels of ``nb`` columns, the
compact WY form V T V^T, the trailing update by matrix products; then
ORGQR for the thin Q) in ``jax.numpy``, with every product made of three
bfloat16 passes: each operand is split into a bfloat16 head and a
bfloat16 tail with ``lax.reduce_precision`` and hi*hi + hi*lo + lo*hi are
summed in float32.  The split is explicit so that no compiler may fold it
away, and it reads the same on the chip and on the CPU.  ``passes=6``
makes the same algorithm with exact float32 products: the witness that
the algorithm itself meets the limits.

It imports nothing of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST


def _bf16(x):
    return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def dot(x, y, passes: int):
    """``x @ y`` with float32 products (6) or three bfloat16 passes (3)."""
    if passes == 6:
        return jnp.dot(x, y, precision=HIGHEST, preferred_element_type=F32)
    if passes != 3:
        raise ValueError(f"passes must be 3 or 6, got {passes}")
    xh, yh = _bf16(x), _bf16(y)
    xl, yl = _bf16(x - xh), _bf16(y - yh)

    def d(u, v):  # bfloat16 values: the products are exact in float32
        return jnp.dot(u, v, precision=HIGHEST, preferred_element_type=F32)

    return d(xh, yh) + d(xh, yl) + d(xl, yh)


def _panel(p, m, j0, k, passes):
    """Unblocked Householder QR of the m x nb panel ``p`` whose first
    column is column ``j0`` of the matrix: (factored panel, V, taus)."""
    nb = p.shape[1]
    rows = jnp.arange(m)
    cols = jnp.arange(nb)

    def column(i, carry):
        p, vs, taus = carry
        j = j0 + i
        x = jnp.where(rows >= j, lax.dynamic_index_in_dim(p, i, 1, False),
                      0.0)
        alpha = jnp.sum(jnp.where(rows == j, x, 0.0))
        norm = jnp.sqrt(jnp.sum(x * x))
        live = (norm > 0) & (j < k)
        beta = jnp.where(alpha >= 0, -norm, norm)
        v = jnp.where(rows == j, 1.0,
                      jnp.where(live, x / jnp.where(live, alpha - beta, 1.0),
                                0.0))
        v = jnp.where(live | (rows != j), v, 0.0)
        tau = jnp.where(live, (beta - alpha) / jnp.where(live, beta, 1.0),
                        0.0)
        w = dot(v[None, :], p, passes)[0]
        w = jnp.where(cols >= i, w, 0.0)
        p = p - tau * v[:, None] * w[None, :]
        return p, vs.at[:, i].set(v), taus.at[i].set(tau)

    return lax.fori_loop(0, nb, column, (p, jnp.zeros((m, nb), F32),
                                         jnp.zeros((nb,), F32)))


def _larft(vs, taus, passes):
    """T of the compact WY form: H_1 ... H_nb = I - V T V^T."""
    nb = taus.shape[0]
    g = dot(vs.T, vs, passes)
    idx = jnp.arange(nb)

    def column(i, t):
        gi = jnp.where(idx < i, lax.dynamic_index_in_dim(g, i, 1, False),
                       0.0)
        ti = -taus[i] * dot(t, gi[:, None], passes)[:, 0]
        ti = jnp.where(idx < i, ti, 0.0) + jnp.where(idx == i, taus[i], 0.0)
        return lax.dynamic_update_index_in_dim(t, ti, i, 1)

    return lax.fori_loop(0, nb, column, jnp.zeros((nb, nb), F32))


def _qr2d(a, passes: int, nb: int):
    m, n = a.shape
    k = min(m, n)
    nb = min(nb, k)
    npan = -(-k // nb)
    width = max(n, npan * nb)
    a = jnp.pad(a, ((0, 0), (0, width - n)))
    cols = jnp.arange(width)

    def panel(b, carry):
        a, vall, tall = carry
        j0 = b * nb
        p = lax.dynamic_slice(a, (0, j0), (m, nb))
        p, vs, taus = _panel(p, m, j0, k, passes)
        t = _larft(vs, taus, passes)
        a = lax.dynamic_update_slice(a, p, (0, j0))
        w = dot(t.T, dot(vs.T, a, passes), passes)
        a = a - jnp.where(cols[None, :] >= j0 + nb, dot(vs, w, passes), 0.0)
        return (a, lax.dynamic_update_slice(vall, vs, (0, j0)),
                tall.at[b].set(t))

    a, vall, tall = lax.fori_loop(
        0, npan, panel, (a, jnp.zeros((m, npan * nb), F32),
                         jnp.zeros((npan, nb, nb), F32)))
    r = jnp.triu(a[:k, :n])

    def form(i, q):
        b = npan - 1 - i
        vs = lax.dynamic_slice(vall, (0, b * nb), (m, nb))
        return q - dot(vs, dot(tall[b], dot(vs.T, q, passes), passes),
                       passes)

    q = lax.fori_loop(0, npan, form, jnp.eye(m, k, dtype=F32))
    return q, r


@functools.partial(jax.jit, static_argnames=("passes", "nb"))
def householder_qr(a, passes: int = 3, nb: int = 128):
    """Reduced (Q, R) of ``a`` (m x n, or a stack of them)."""
    f = functools.partial(_qr2d, passes=passes, nb=nb)
    for _ in range(a.ndim - 2):
        f = jax.vmap(f)
    return f(a.astype(F32))
