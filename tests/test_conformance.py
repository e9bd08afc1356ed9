"""Registry-wide numerical conformance suite.

Every method in the planner registry — current and future — is held to
the SAME numerical bar, with no per-method tolerance carve-outs:

    * ||Q^T Q - I||_max        <= tol(dtype, shape)
    * ||A - Q R||_F / ||A||_F  <= tol(dtype, shape)
    * R strictly upper triangular (exact zeros below the diagonal)
    * sign-fix convention: cfg.sign_fix=True  =>  diag(R) >= 0

across square / tall / wide / non-multiple-of-block shapes and
float32/float64, plus the kernel paths (use_kernel=True, interpret mode
on CPU) of every kernel-backed method.  The method list is read from the
registry at collection time, so a newly registered backend inherits the
bar for free.

Shape skips are *capability* skips only (the planner's own checks:
TSQR's 4:1 aspect, geqrf_fori's divisibility, thin-Q-only methods in
full mode) — never looser tolerances.

Under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI
multi-device job) the identical assertions exercise ``sharded_tiled``'s
real shard_map path; on one device it degenerates to the tiled backend.
"""

import pytest
import jax
import jax.numpy as jnp

from repro.core.plan import QRConfig, available_methods, plan

METHODS = available_methods()
BLOCK = 8

# (label, (m, n)) — square / tall (TSQR-eligible) / wide / off-block.
SHAPES = [
    ("square", (32, 32)),
    ("tall", (96, 16)),
    ("wide", (16, 40)),
    ("offblock", (37, 23)),
]
DTYPES = ["float32", "float64"]


def _tol(dtype, m, n) -> float:
    """One tolerance rule for every method: 100 eps max(m, n)."""
    return 100.0 * float(jnp.finfo(dtype).eps) * max(m, n)


def _plan_or_skip(shape, dtype, cfg):
    """Planner capability checks double as the conformance skip rule."""
    try:
        return plan(shape, dtype, cfg)
    except ValueError as e:
        pytest.skip(f"capability: {e}")


def _x64():
    return jax.enable_x64(True)


class _nullctx:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def _ctx(dtype):
    return _x64() if dtype == "float64" else _nullctx()


def _assert_conformance(a, q, r, tol):
    m, n = a.shape
    k = min(m, n)
    assert q.shape[-1] == r.shape[-2]
    orth = float(jnp.abs(q.T @ q - jnp.eye(q.shape[1], dtype=a.dtype)).max())
    rec = float(jnp.linalg.norm(q @ r - a) / max(float(jnp.linalg.norm(a)), 1e-30))
    assert orth <= tol, f"||Q^T Q - I|| = {orth} > {tol}"
    assert rec <= tol, f"||A - QR||/||A|| = {rec} > {tol}"
    assert float(jnp.abs(jnp.tril(r[:, :k], -1)).max()) == 0.0, \
        "R not strictly upper triangular"


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("label,shape", SHAPES, ids=[s[0] for s in SHAPES])
@pytest.mark.parametrize("method", METHODS)
def test_reduced_conformance(method, label, shape, dtype, matrices):
    """(Q, R) in reduced mode meets the shared bar for every method."""
    m, n = shape
    with _ctx(dtype):
        a = matrices.well_conditioned(m, n, cond=100.0, dtype=dtype)
        solver = _plan_or_skip(a.shape, a.dtype,
                               QRConfig(method=method, block=BLOCK))
        q, r = solver.solve(a)
        assert q.shape == (m, min(m, n)) and r.shape == (min(m, n), n)
        _assert_conformance(a, q, r, _tol(dtype, m, n))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("label,shape", SHAPES, ids=[s[0] for s in SHAPES])
@pytest.mark.parametrize("method", METHODS)
def test_r_mode_conformance(method, label, shape, dtype, matrices):
    """R-only mode: triangular, and R^T R recovers the Gram matrix."""
    m, n = shape
    with _ctx(dtype):
        a = matrices.well_conditioned(m, n, cond=100.0, dtype=dtype)
        solver = _plan_or_skip(a.shape, a.dtype,
                               QRConfig(method=method, block=BLOCK, mode="r"))
        r = solver.solve(a)
        k = min(m, n)
        assert r.shape == (k, n)
        assert float(jnp.abs(jnp.tril(r[:, :k], -1)).max()) == 0.0
        gram = float(jnp.linalg.norm(r.T @ r - a.T @ a)
                     / max(float(jnp.linalg.norm(a.T @ a)), 1e-30))
        assert gram <= _tol(dtype, m, n), gram


@pytest.mark.parametrize("label,shape", SHAPES, ids=[s[0] for s in SHAPES])
@pytest.mark.parametrize("method", METHODS)
def test_full_mode_conformance(method, label, shape, matrices):
    """Full (m x m) Q where the method supports it — same bar."""
    m, n = shape
    a = matrices.well_conditioned(m, n, cond=100.0)
    solver = _plan_or_skip(
        a.shape, a.dtype, QRConfig(method=method, block=BLOCK, mode="full"))
    q, r = solver.solve(a)
    assert q.shape == (m, m) and r.shape == (m, n)
    _assert_conformance(a, q, r, _tol("float32", m, n))


@pytest.mark.parametrize("method", METHODS)
def test_sign_fix_convention(method, matrices):
    """sign_fix=True => diag(R) >= 0, with Q R unchanged as a product."""
    a = matrices.well_conditioned(48, 24, cond=50.0)
    solver = _plan_or_skip(a.shape, a.dtype,
                           QRConfig(method=method, block=BLOCK, sign_fix=True))
    q, r = solver.solve(a)
    assert bool((jnp.diagonal(r) >= 0).all()), "sign-fix convention violated"
    _assert_conformance(a, q, r, _tol("float32", 48, 24))


@pytest.mark.parametrize("method", METHODS)
def test_graded_spectrum_conformance(method, matrices):
    """cond = 1e3 graded singular values: same tolerances still hold
    (refinement/formq must absorb moderate ill-conditioning)."""
    a = matrices.graded(64, 32, cond=1e3)
    solver = _plan_or_skip(a.shape, a.dtype,
                           QRConfig(method=method, block=BLOCK))
    q, r = solver.solve(a)
    _assert_conformance(a, q, r, _tol("float32", 64, 32))


@pytest.mark.parametrize("method", METHODS)
def test_rank_deficient_finite_and_triangular(method, matrices):
    """Exactly rank-deficient input: every method must stay finite and
    keep R triangular (Q orthogonality is method-defined here — solve-
    based thin-Q paths clamp the singular pivots)."""
    a = matrices.rank_deficient(48, 16, rank=8)
    solver = _plan_or_skip(a.shape, a.dtype,
                           QRConfig(method=method, block=BLOCK))
    q, r = solver.solve(a)
    assert bool(jnp.isfinite(q).all()) and bool(jnp.isfinite(r).all())
    assert float(jnp.abs(jnp.tril(r[:, :16], -1)).max()) == 0.0


from repro.core.plan import get_method  # noqa: E402

_KERNEL_METHODS = [m for m in METHODS if get_method(m).kernel_backed]


@pytest.mark.parametrize("method", _KERNEL_METHODS)
def test_kernel_path_conformance(method, matrices):
    """use_kernel=True (Pallas, interpret mode on CPU) meets the same
    bar as the jnp path for every kernel-backed method."""
    a = matrices.well_conditioned(64, 32, cond=100.0)
    solver = _plan_or_skip(
        a.shape, a.dtype,
        QRConfig(method=method, block=BLOCK, use_kernel=True))
    q, r = solver.solve(a)
    _assert_conformance(a, q, r, _tol("float32", 64, 32))


@pytest.mark.parametrize("dispatch_mode", ["wavefront", "megakernel"])
@pytest.mark.parametrize("method", METHODS)
def test_engine_path_bitwise_vs_oracle(method, dispatch_mode, matrices):
    """Every registry method executing through the wavefront macro-op
    engine (kernel_policy == "macro_ops" — today `tiled` and
    `sharded_tiled`, plus any future engine-backed backend for free)
    must produce BITWISE-identical (Q, R) on BOTH kernel dispatch modes
    (per-level wavefront dispatches AND the single-call megakernel over
    the scalar-prefetched task table; interpret mode on CPU) and its
    ``use_kernel=False`` jnp-oracle lowering.  Not a tolerance —
    equality."""
    if get_method(method).kernel_policy != "macro_ops":
        pytest.skip("capability: method does not execute through "
                    "repro.core.engine")
    a = matrices.well_conditioned(48, 32, cond=100.0)
    sk = _plan_or_skip(a.shape, a.dtype,
                       QRConfig(method=method, block=BLOCK, use_kernel=True,
                                dispatch_mode=dispatch_mode))
    sj = _plan_or_skip(a.shape, a.dtype,
                       QRConfig(method=method, block=BLOCK, use_kernel=False))
    qk, rk = sk.solve(a)
    qj, rj = sj.solve(a)
    assert bool((qk == qj).all()), \
        f"{dispatch_mode} engine Q != oracle Q (bitwise)"
    assert bool((rk == rj).all()), \
        f"{dispatch_mode} engine R != oracle R (bitwise)"


# --------------------------------------------- batched engine (serving hook)

from repro.core import engine  # noqa: E402
from repro.core.tilegraph import _split_tiles  # noqa: E402


@pytest.mark.parametrize("dispatch_mode", [None, "wavefront", "megakernel"],
                         ids=["jnp", "wavefront", "megakernel"])
@pytest.mark.parametrize("batch", [1, 3])
@pytest.mark.parametrize("grid", [(3, 3), (3, 2), (2, 3)],
                         ids=["square", "tall", "wide"])
def test_factor_tiles_batched_bitwise_per_slice(dispatch_mode, batch, grid,
                                                matrices):
    """The serving contract: ``factor_tiles_batched`` over a stacked
    workspace is BITWISE-identical per slice to B independent
    ``factor_tiles`` runs — on the jnp oracle, the wavefront kernels,
    and the batched megakernel (interpret on CPU).  Slices include
    ragged bucket padding (odd slices carry a smaller matrix zero-padded
    to the bucket shape, exactly what QRService stages) and the B=1
    degeneracy.  Not a tolerance — equality."""
    p, q = grid
    nb = BLOCK
    use_kernel = dispatch_mode is not None
    mats = []
    for b in range(batch):
        mr = p * nb - (b % 2) * (nb // 2)  # ragged rows/cols on odd slices
        nr = q * nb - (b % 2) * (nb // 2)
        a = matrices.well_conditioned(mr, nr, cond=100.0)
        mats.append(jnp.zeros((p * nb, q * nb), a.dtype).at[:mr, :nr].set(a))
    tiles = jnp.stack([_split_tiles(a, p, q, nb) for a in mats])
    singles = [engine.factor_tiles(tiles[b], p=p, q=q, nb=nb,
                                   use_kernel=use_kernel,
                                   dispatch_mode=dispatch_mode)
               for b in range(batch)]
    batched = engine.factor_tiles_batched(tiles, p=p, q=q, nb=nb,
                                          use_kernel=use_kernel,
                                          dispatch_mode=dispatch_mode)
    for b, single in enumerate(singles):
        for field, bat, ref in zip(engine.FactorState._fields, batched,
                                   single):
            assert bool((bat[b] == ref).all()), \
                f"slice {b} field {field} differs from independent run " \
                f"(dispatch_mode={dispatch_mode})"


def test_registry_has_all_expected_methods():
    """The suite is only meaningful if it sweeps the full registry."""
    for name in ("geqr2", "geqr2_ht", "geqrf", "geqrf_ht", "tsqr", "tiled",
                 "sharded_tiled", "degenerate"):
        assert name in METHODS, f"{name} missing from registry"


# --------------------------------------------- degenerate (zero-dim) parity

_DEGENERATE_SHAPES = [(0, 5), (5, 0), (0, 0)]


@pytest.mark.parametrize("mode", ["reduced", "r", "full"])
@pytest.mark.parametrize("shape", _DEGENERATE_SHAPES,
                         ids=[f"{m}x{n}" for m, n in _DEGENERATE_SHAPES])
def test_degenerate_shapes_match_linalg_qr(shape, mode):
    """PR-8 bugfix: zero-dim inputs used to crash the planner where
    ``jnp.linalg.qr`` succeeds.  The trivial route must match the oracle
    exactly (shapes AND values — identity Q, zero R)."""
    a = jnp.zeros(shape, jnp.float32)
    solver = plan(a.shape, a.dtype, QRConfig(mode=mode))
    assert solver.config.method == "degenerate"
    oracle_mode = {"reduced": "reduced", "r": "r", "full": "complete"}[mode]
    ref = jnp.linalg.qr(a, mode=oracle_mode)
    if mode == "r":
        r = solver.solve(a)
        assert r.shape == ref.shape and bool((r == ref).all())
    else:
        q, r = solver.solve(a)
        assert q.shape == ref[0].shape and r.shape == ref[1].shape
        assert bool((q == ref[0]).all()) and bool((r == ref[1]).all())


def test_degenerate_capability_guard_skips_nonempty():
    """Explicit method='degenerate' on a nonempty shape is a capability
    error (so the registry-wide suites above skip it, same as tsqr's
    aspect guard)."""
    with pytest.raises(ValueError):
        plan((32, 32), jnp.float32, QRConfig(method="degenerate"))
