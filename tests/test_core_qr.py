"""Correctness of the HT/MHT/blocked QR core against LAPACK semantics.

Paper claims under test:
  C1: MHT is numerically identical to classical HT (same reflectors, same
      R) — only the update dataflow changes (§4).
  C4: blocked (WY) variants produce the same factorization as unblocked.
"""

import math

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from hypothesis_compat import given, settings, st

from repro.core import (
    QRConfig,
    apply_q,
    form_q,
    geqr2,
    geqr2_ht,
    geqrf,
    geqrf_fori,
    house_vector,
    lstsq,
    orthogonalize,
    qr,
    qr_algorithm_eig,
    unpack_r,
)
from repro.core import householder
from repro.core.householder import geqr2_explicit_p
from repro.observability import metrics

SHAPES = [(8, 8), (16, 8), (12, 5), (33, 17), (32, 32), (64, 48), (48, 64)]

# Shared deterministic matrix factory (tests/conftest.py).
from conftest import gaussian as _rand  # noqa: E402


def _check_qr(a, packed, taus, rtol=3e-5):
    m, n = a.shape
    k = min(m, n)
    q = form_q(packed, taus)
    r = unpack_r(packed, n)
    np.testing.assert_allclose(np.asarray(q @ r), np.asarray(a), atol=rtol * np.linalg.norm(a))
    np.testing.assert_allclose(np.asarray(q.T @ q), np.eye(k), atol=1e-4)
    assert float(jnp.linalg.norm(jnp.tril(r[:, :k], -1))) == 0.0


@pytest.mark.parametrize("m,n", SHAPES)
@pytest.mark.parametrize("factor", ["geqr2", "geqr2_ht", "explicit_p"])
def test_unblocked_reconstruction(m, n, factor):
    a = _rand(m, n, seed=m * 100 + n)
    fn = {"geqr2": geqr2, "geqr2_ht": geqr2_ht, "explicit_p": geqr2_explicit_p}[factor]
    packed, taus = fn(a)
    _check_qr(a, packed, taus)


@pytest.mark.parametrize("m,n", SHAPES)
def test_mht_identical_to_ht(m, n):
    """C1: the MHT re-arrangement changes the DAG, not the numbers."""
    a = _rand(m, n, seed=m + n)
    p1, t1 = geqr2(a)
    p2, t2 = geqr2_ht(a)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))


@pytest.mark.parametrize("m,n", SHAPES)
@pytest.mark.parametrize("block", [4, 8, 32])
@pytest.mark.parametrize("panel_method", ["ht", "mht"])
def test_blocked_matches_unblocked(m, n, block, panel_method):
    a = _rand(m, n, seed=block)
    pb, tb = geqrf(a, block=block, panel_method=panel_method)
    pu, tu = geqr2(a)
    np.testing.assert_allclose(np.asarray(pb), np.asarray(pu), atol=2e-4)
    np.testing.assert_allclose(np.asarray(tb), np.asarray(tu), atol=2e-5)
    _check_qr(a, pb, tb)


@pytest.mark.parametrize("m,n", [(16, 8), (32, 32)])
def test_matches_jnp_linalg_qr(m, n):
    a = _rand(m, n, seed=7)
    q, r = qr(a, config=QRConfig(method="geqrf_ht", block=8))
    qn, rn = jnp.linalg.qr(a)
    s = jnp.sign(jnp.diagonal(r)) * jnp.sign(jnp.diagonal(rn))
    np.testing.assert_allclose(np.asarray(r * s[:, None]), np.asarray(rn), atol=3e-5)
    np.testing.assert_allclose(np.asarray(q * s[None, :]), np.asarray(qn), atol=3e-5)


def test_house_vector_annihilates():
    x = jnp.asarray([3.0, 4.0, 0.0, 12.0], jnp.float32)
    v, tau, beta = house_vector(x, 0)
    h = jnp.eye(4) - tau * jnp.outer(v, v)
    hx = h @ x
    assert abs(float(hx[0]) - float(beta)) < 1e-5
    np.testing.assert_allclose(np.asarray(hx[1:]), 0.0, atol=1e-5)
    assert abs(float(beta)) == pytest.approx(13.0, rel=1e-5)
    assert float(beta) == pytest.approx(-13.0, rel=1e-5)  # -sign(x0)*||x||


def test_house_vector_offset_and_degenerate():
    x = jnp.asarray([5.0, 2.0, 0.0, 0.0], jnp.float32)
    v, tau, beta = house_vector(x, 1)
    assert float(v[0]) == 0.0 and float(v[1]) == 1.0
    # degenerate: nothing to annihilate below offset 1
    assert float(tau) == 0.0
    assert float(beta) == pytest.approx(2.0)


def test_apply_q_transpose_roundtrip():
    a = _rand(24, 10, seed=3)
    packed, taus = geqr2_ht(a)
    c = _rand(24, 6, seed=4)
    back = apply_q(packed, taus, apply_q(packed, taus, c, transpose=True))
    np.testing.assert_allclose(np.asarray(back), np.asarray(c), atol=1e-4)


# Every packed method's output feeds form_q.  geqrf_fori needs its block
# to divide k, so it takes the largest power of two that does.
_PACKED = {
    "geqrf_ht": lambda a: geqrf(a, block=32, panel_method="mht"),
    "geqr2": geqr2,
    "geqrf_fori": lambda a: geqrf_fori(a, block=math.gcd(min(a.shape), 128)),
}
# f32 tolerance fixed beforehand: ~84 eps32, an order above the readings.
_FORM_Q_ATOL = 1e-5


def _check_form_q(q, packed, taus, cols):
    """Q against the rank-1 reference (``apply_q`` on the identity), and
    its orthogonality."""
    m = packed.shape[0]
    ref = apply_q(packed, taus, jnp.eye(m, cols, dtype=packed.dtype))
    assert q.shape == (m, cols)
    np.testing.assert_allclose(np.asarray(q), np.asarray(ref), atol=_FORM_Q_ATOL)
    np.testing.assert_allclose(np.asarray(q.T @ q), np.eye(cols), atol=_FORM_Q_ATOL)


@pytest.mark.parametrize("factor", sorted(_PACKED))
@pytest.mark.parametrize("full", [False, True])
@pytest.mark.parametrize("m,n", [(512, 512), (600, 300), (300, 600), (1000, 130),
                                 (100, 60)])
def test_form_q_blocked_matches_rank1(m, n, full, factor):
    """Blocked WY Q formation (square, tall, wide, k not a multiple of the
    128-wide panel, k below it) equals the reflector-by-reflector loop."""
    a = _rand(m, n, seed=m + 3 * n)
    packed, taus = _PACKED[factor](a)
    cols = m if full else min(m, n)
    _check_form_q(form_q(packed, taus, full=full), packed, taus, cols)


def test_form_q_under_outer_jit():
    """As QR-Muon calls it: inside the optimizer step's jit."""
    a = _rand(600, 300, seed=21)
    packed, taus = geqrf(a, block=32, panel_method="mht")
    q = jax.jit(lambda p, t: form_q(p, t))(packed, taus)
    _check_form_q(q, packed, taus, 300)


def test_form_q_traces_once_per_shape():
    """One program per shape: a second input of the same shape reuses it."""
    impl = householder._form_q_blocked
    sizes = [impl._cache_size()]
    for seed in (31, 32):
        packed, taus = geqr2(_rand(72, 40, seed=seed))
        jax.block_until_ready(form_q(packed, taus))
        sizes.append(impl._cache_size())
    assert sizes[2] == sizes[1] == sizes[0] + 1


@pytest.mark.parametrize("under_jit", [False, True])
def test_form_q_counters(under_jit):
    """One Q and ceil(k/128) panels per formation; an eager call counts at
    execute, one inside jit once at trace."""
    phase = "trace" if under_jit else "execute"

    def count():
        return (metrics.counter_value("householder.form_q", phase=phase),
                metrics.counter_value("householder.form_q_panels", phase=phase))

    f = jax.jit(lambda p, t: form_q(p, t)) if under_jit else form_q
    q0, p0 = count()
    for seed in (41, 42):
        packed, taus = geqr2(_rand(600, 300, seed=seed))
        jax.block_until_ready(f(packed, taus))
    calls = 1 if under_jit else 2
    assert count() == (q0 + calls, p0 + 3 * calls)


@pytest.mark.parametrize("m,n", [(8, 8), (16, 8), (12, 5), (32, 32)])
def test_qr_full_mode(m, n):
    """Regression: mode="full" used to return (q, (q, r)) when m == k
    (the ternary bound to the tuple's second element)."""
    from repro.core import QRConfig, plan

    a = _rand(m, n, seed=m * 7 + n)
    out = qr(a, config=QRConfig(method="geqrf_ht", mode="full"))
    assert isinstance(out, tuple) and len(out) == 2
    q, r = out
    assert q.shape == (m, m), "full Q must be m x m"
    assert r.shape == (m, n), "full R must be m x n"
    assert isinstance(r, jnp.ndarray), "R must be an array, not a nested tuple"
    np.testing.assert_allclose(np.asarray(q @ r), np.asarray(a), atol=1e-4)
    np.testing.assert_allclose(np.asarray(q.T @ q), np.eye(m), atol=1e-4)
    # config path produces the identical full factorization
    q2, r2 = plan(a.shape, a.dtype, QRConfig(method="geqrf_ht", mode="full")
                  ).solve(a)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(r), np.asarray(r2))


def test_orthogonalize_tall_and_wide():
    a = _rand(40, 16, seed=9)
    o = orthogonalize(a)
    np.testing.assert_allclose(np.asarray(o.T @ o), np.eye(16), atol=1e-4)
    ow = orthogonalize(a.T)
    assert ow.shape == (16, 40)
    np.testing.assert_allclose(np.asarray(ow @ ow.T), np.eye(16), atol=1e-4)


def test_orthogonalize_is_deterministic_sign():
    """diag(R)-sign fixing makes the factor continuous in the input."""
    a = _rand(20, 8, seed=11)
    o1 = orthogonalize(a)
    o2 = orthogonalize(a * 1.0001)
    assert float(jnp.max(jnp.abs(o1 - o2))) < 1e-2  # no sign flips


def test_lstsq():
    a = _rand(30, 6, seed=5)
    x_true = _rand(6, 1, seed=6)[:, 0]
    b = a @ x_true
    x = lstsq(a, b)
    np.testing.assert_allclose(np.asarray(x), np.asarray(x_true), atol=1e-3)


def test_qr_algorithm_eigenvalues():
    """Paper §1 Application 2: eigenvalues via the QR algorithm."""
    rng = np.random.default_rng(12)
    q, _ = np.linalg.qr(rng.standard_normal((8, 8)))
    lam = np.array([9.0, 7.5, 5.0, 3.2, 2.0, 1.0, 0.5, 0.1])
    a = jnp.asarray(q @ np.diag(lam) @ q.T, jnp.float32)
    ev = qr_algorithm_eig(a, iters=300)
    np.testing.assert_allclose(np.asarray(ev), lam, rtol=2e-3)


@settings(max_examples=25, deadline=None)
@given(
    m=st.integers(2, 48),
    n=st.integers(2, 32),
    seed=st.integers(0, 2**31 - 1),
    scale=st.floats(1e-3, 1e3),
)
def test_property_qr_invariants(m, n, seed, scale):
    """Property: for any well-scaled matrix, all methods yield Q R = A with
    orthonormal Q and upper-triangular R, and HT == MHT exactly."""
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.standard_normal((m, n)) * scale, jnp.float32)
    p1, t1 = geqr2(a)
    p2, t2 = geqr2_ht(a)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
    q = form_q(p2, t2)
    r = unpack_r(p2, n)
    norm = max(float(jnp.linalg.norm(a)), 1e-6)
    assert float(jnp.linalg.norm(q @ r - a)) / norm < 5e-5
    assert float(jnp.linalg.norm(q.T @ q - jnp.eye(min(m, n)))) < 5e-4


@settings(max_examples=15, deadline=None)
@given(m=st.integers(8, 64), n=st.integers(4, 24), block=st.integers(2, 16),
       seed=st.integers(0, 1000))
def test_property_blocked_equals_unblocked(m, n, block, seed):
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    pb, tb = geqrf(a, block=block, panel_method="mht")
    pu, tu = geqr2_ht(a)
    np.testing.assert_allclose(np.asarray(pb), np.asarray(pu), atol=5e-4)
