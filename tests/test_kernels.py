"""Pallas kernel tests: shape/dtype sweeps + property tests vs. ref.py.

Kernels run in interpret mode on CPU (the body executes exactly as it
would on TPU, minus the Mosaic lowering).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from hypothesis_compat import given, settings, st

from repro.core import geqr2_ht, geqrf
from repro.core.blocked import larft, panel_factor, unpack_v_panel
from repro.kernels import ops, ref, tile_ops


# Shared deterministic matrix factory (tests/conftest.py).
from conftest import randn as _rand  # noqa: E402


# ---------------------------------------------------------------- mht_panel

PANEL_SHAPES = [(8, 4), (32, 8), (64, 16), (128, 32), (256, 64), (128, 128),
                (512, 16), (96, 24)]


@pytest.mark.parametrize("m,b", PANEL_SHAPES)
def test_mht_panel_matches_ref_f32(m, b):
    p = _rand((m, b), seed=m + b)
    pk, tk = ops.mht_panel(p)
    pr, tr = ref.mht_panel_ref(p)
    # fp32 accumulation-order differences grow with factorization depth b.
    np.testing.assert_allclose(np.asarray(pk), np.asarray(pr), atol=2e-5)
    np.testing.assert_allclose(np.asarray(tk), np.asarray(tr), atol=1e-7 * b + 2e-6)


@pytest.mark.parametrize("m,b", [(64, 16), (128, 32)])
@pytest.mark.parametrize("row0", [0, 8, 32])
def test_mht_panel_row_offsets(m, b, row0):
    p = _rand((m, b), seed=row0)
    pk, tk = ops.mht_panel(p, row0=row0)
    pr, tr = ref.mht_panel_ref(p, row0=row0)
    np.testing.assert_allclose(np.asarray(pk), np.asarray(pr), atol=2e-5)
    np.testing.assert_allclose(np.asarray(tk), np.asarray(tr), atol=2e-6)
    # rows above the pivot band must be bit-identical to the input
    np.testing.assert_array_equal(np.asarray(pk[:row0]), np.asarray(p[:row0]))


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5), (jnp.bfloat16, 5e-2)])
def test_mht_panel_dtypes(dtype, atol):
    p = _rand((64, 16), dtype=dtype, seed=5)
    pk, tk = ops.mht_panel(p)
    pr, tr = ref.mht_panel_ref(p)
    np.testing.assert_allclose(
        np.asarray(pk, np.float32), np.asarray(pr, np.float32), atol=atol)
    np.testing.assert_allclose(
        np.asarray(tk, np.float32), np.asarray(tr, np.float32), atol=atol)


def test_mht_panel_vmem_guard():
    with pytest.raises(ValueError, match="VMEM"):
        ops.mht_panel(jnp.zeros((8192, 256), jnp.float32))


def test_mht_panel_degenerate_column():
    """A column that is already zero below the pivot must give tau=0."""
    p = _rand((32, 4), seed=1)
    p = p.at[1:, 0].set(0.0)
    pk, tk = ops.mht_panel(p)
    pr, tr = ref.mht_panel_ref(p)
    assert float(tk[0]) == 0.0
    np.testing.assert_allclose(np.asarray(pk), np.asarray(pr), atol=2e-5)


@settings(max_examples=20, deadline=None)
@given(m=st.integers(4, 128), b=st.integers(2, 32), seed=st.integers(0, 10_000),
       scale=st.floats(1e-2, 1e2))
def test_property_mht_panel(m, b, seed, scale):
    b = min(b, m)
    rng = np.random.default_rng(seed)
    p = jnp.asarray(rng.standard_normal((m, b)) * scale, jnp.float32)
    pk, tk = ops.mht_panel(p)
    pr, tr = ref.mht_panel_ref(p)
    np.testing.assert_allclose(np.asarray(pk), np.asarray(pr),
                               atol=3e-5 * max(scale, 1.0))
    np.testing.assert_allclose(np.asarray(tk), np.asarray(tr), atol=3e-5)


# -------------------------------------------------------------- wy_trailing

WY_SHAPES = [(32, 8, 16), (64, 16, 40), (128, 32, 128), (256, 32, 300),
             (512, 64, 96), (128, 128, 256)]


def _make_vt(m, k, seed):
    a = _rand((m, k), seed=seed)
    pf, taus = panel_factor(a, 0)
    v = unpack_v_panel(pf, 0)
    return v, larft(v, taus)


@pytest.mark.parametrize("m,k,n", WY_SHAPES)
def test_wy_trailing_matches_ref_f32(m, k, n):
    v, t = _make_vt(m, k, seed=m + k + n)
    c = _rand((m, n), seed=n)
    np.testing.assert_allclose(
        np.asarray(ops.wy_trailing(v, t, c)),
        np.asarray(ref.wy_trailing_ref(v, t, c)), atol=3e-5)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 3e-5), (jnp.bfloat16, 1e-1)])
def test_wy_trailing_dtypes(dtype, atol):
    v, t = _make_vt(128, 32, seed=2)
    c = _rand((128, 100), dtype=dtype, seed=3)
    out_k = ops.wy_trailing(v.astype(dtype), t.astype(dtype), c)
    out_r = ref.wy_trailing_ref(v.astype(dtype), t.astype(dtype), c)
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32), atol=atol)


def test_wy_trailing_applies_qt():
    """Kernel output must equal applying Q^T from the packed factors."""
    from repro.core import apply_q

    m, k, n = 96, 16, 24
    a = _rand((m, k), seed=9)
    pf, taus = panel_factor(a, 0)
    v = unpack_v_panel(pf, 0)
    t = larft(v, taus)
    c = _rand((m, n), seed=10)
    out = ops.wy_trailing(v, t, c)
    expected = apply_q(pf, taus, c, transpose=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(m=st.integers(8, 128), k=st.integers(2, 32), n=st.integers(1, 200),
       seed=st.integers(0, 10_000))
def test_property_wy_trailing(m, k, n, seed):
    k = min(k, m)
    v, t = _make_vt(m, k, seed=seed)
    c = _rand((m, n), seed=seed + 1)
    np.testing.assert_allclose(
        np.asarray(ops.wy_trailing(v, t, c)),
        np.asarray(ref.wy_trailing_ref(v, t, c)), atol=5e-5)


# ------------------------------------------------ tile ops (TSQRT / SSRFB)

def _tsqrt_inputs(nb, seed):
    r = jnp.triu(_rand((nb, nb), seed=seed))
    a = _rand((nb, nb), seed=seed + 1)
    return r, a


@pytest.mark.parametrize("nb", [4, 8, 16, 32])
def test_tsqrt_matches_ref(nb):
    r, a = _tsqrt_inputs(nb, seed=nb)
    rk, vk, tk = tile_ops.tsqrt(r, a)
    rr, vr, tr = ref.tsqrt_ref(r, a)
    np.testing.assert_allclose(np.asarray(rk), np.asarray(rr), atol=3e-5)
    np.testing.assert_allclose(np.asarray(vk), np.asarray(vr), atol=3e-5)
    np.testing.assert_allclose(np.asarray(tk), np.asarray(tr), atol=3e-5)
    # structured output: the updated R stays exactly upper triangular
    assert float(jnp.linalg.norm(jnp.tril(rk, -1))) == 0.0


def test_tsqrt_reduces_stacked_pair():
    """[R; A] = Q [R'; 0]: R' must match the QR of the stacked pair."""
    nb = 16
    r, a = _tsqrt_inputs(nb, seed=3)
    rk, _, _ = tile_ops.tsqrt(r, a)
    rn = jnp.linalg.qr(jnp.concatenate([r, a], axis=0))[1]
    s = jnp.sign(jnp.diagonal(rk)) * jnp.sign(jnp.diagonal(rn))
    np.testing.assert_allclose(np.asarray(rk * s[:, None]), np.asarray(rn),
                               atol=3e-5)


def test_tsqrt_degenerate_zero_tail():
    """A zero A-tile must pass R through untouched (all tau = 0)."""
    nb = 8
    r = jnp.triu(_rand((nb, nb), seed=4))
    rk, vk, tk = tile_ops.tsqrt(r, jnp.zeros((nb, nb), jnp.float32))
    np.testing.assert_allclose(np.asarray(tk), np.zeros(nb), atol=0)
    np.testing.assert_allclose(np.asarray(vk), np.zeros((nb, nb)), atol=0)
    np.testing.assert_allclose(np.asarray(rk), np.asarray(r), atol=1e-6)


@pytest.mark.parametrize("nb", [4, 8, 16, 32])
def test_ssrfb_matches_ref(nb):
    from repro.kernels.macro_ops import stacked_larft

    r, a = _tsqrt_inputs(nb, seed=nb + 7)
    _, v2, taus = ref.tsqrt_ref(r, a)
    t = stacked_larft(v2, taus)
    ck, ci = _rand((nb, nb), seed=1), _rand((nb, nb), seed=2)
    ck_k, ci_k = tile_ops.ssrfb(v2, t, ck, ci)
    ck_r, ci_r = ref.ssrfb_ref(v2, t, ck, ci)
    np.testing.assert_allclose(np.asarray(ck_k), np.asarray(ck_r), atol=3e-5)
    np.testing.assert_allclose(np.asarray(ci_k), np.asarray(ci_r), atol=3e-5)


@pytest.mark.parametrize("dtype,rtol", [("float32", 2e-5), ("float64", 1e-12)])
@pytest.mark.parametrize("m,b", [(8, 8), (64, 16), (256, 32), (128, 128)])
def test_larft_body_matches_blocked_larft(m, b, dtype, rtol):
    """The kernel-safe T-formation (iota masks and masked reductions over
    the reflector Gram, as the engine's kernels run it) equals the
    host realization ``blocked.larft``, in float32 and float64."""
    from repro.kernels.macro_ops import larft_body

    with jax.enable_x64(dtype == "float64"):
        a = jnp.asarray(np.random.default_rng(m + b).standard_normal((m, b)),
                        dtype)
        pf, taus = panel_factor(a, 0)
        v = unpack_v_panel(pf, 0)
        gram = jnp.matmul(v.T, v, precision=jax.lax.Precision.HIGHEST)
        t_kernel = larft_body(gram, taus)
        t_host = larft(v, taus)
        assert t_kernel.dtype == t_host.dtype == jnp.dtype(dtype)
        np.testing.assert_allclose(np.asarray(t_kernel), np.asarray(t_host),
                                   rtol=rtol, atol=rtol)


def test_tile_ops_vmem_guards():
    big = 2048  # 6 * 2048^2 * 4 bytes > the shared 8 MiB budget
    z = jnp.zeros((big, big), jnp.float32)
    with pytest.raises(ValueError, match="VMEM"):
        tile_ops.tsqrt(z, z)
    with pytest.raises(ValueError, match="VMEM"):
        tile_ops.ssrfb(z, z, z, z)


@settings(max_examples=10, deadline=None)
@given(nb=st.integers(2, 24), seed=st.integers(0, 10_000))
def test_property_tsqrt_ssrfb(nb, seed):
    from repro.kernels.macro_ops import stacked_larft

    rng = np.random.default_rng(seed)
    r = jnp.triu(jnp.asarray(rng.standard_normal((nb, nb)), jnp.float32))
    a = jnp.asarray(rng.standard_normal((nb, nb)), jnp.float32)
    rk, vk, tk = tile_ops.tsqrt(r, a)
    rr, vr, tr = ref.tsqrt_ref(r, a)
    np.testing.assert_allclose(np.asarray(rk), np.asarray(rr), atol=5e-5)
    np.testing.assert_allclose(np.asarray(vk), np.asarray(vr), atol=5e-5)
    t = stacked_larft(vr, tr)
    c = jnp.asarray(rng.standard_normal((2, nb, nb)), jnp.float32)
    out_k = tile_ops.ssrfb(vr, t, c[0], c[1])
    out_r = ref.ssrfb_ref(vr, t, c[0], c[1])
    for ok, orf in zip(out_k, out_r):
        np.testing.assert_allclose(np.asarray(ok), np.asarray(orf), atol=5e-5)


# ------------------------------------------------- end-to-end kernel geqrf

@pytest.mark.parametrize("m,n,block", [(64, 32, 8), (96, 64, 16), (128, 128, 32)])
def test_geqrf_kernel_path_matches_unblocked(m, n, block):
    a = _rand((m, n), seed=m)
    pk, tk = geqrf(a, block=block, use_kernel=True)
    pu, tu = geqr2_ht(a)
    np.testing.assert_allclose(np.asarray(pk), np.asarray(pu), atol=5e-4)
    np.testing.assert_allclose(np.asarray(tk), np.asarray(tu), atol=5e-5)
