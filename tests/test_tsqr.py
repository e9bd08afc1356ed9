"""TSQR / distributed QR tests (paper §5.2 parallel realization).

The shard_map paths need >1 device; those run in a subprocess with
``--xla_force_host_platform_device_count`` so the rest of the suite keeps
the single real CPU device (per the dry-run isolation rule).
"""

import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis_compat import given, settings, st

from repro.core import tsqr_qr, tsqr_r
from repro.core.tsqr import triangular_inverse_apply


def _rand(m, n, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((m, n)), jnp.float32)


@pytest.mark.parametrize("nblocks", [2, 3, 4, 8])
def test_tsqr_r_matches_linalg(nblocks):
    a = _rand(240, 12, seed=nblocks)
    r = tsqr_r(a, nblocks=nblocks)
    rn = jnp.linalg.qr(a)[1]
    s = jnp.sign(jnp.diagonal(r)) * jnp.sign(jnp.diagonal(rn))
    np.testing.assert_allclose(np.asarray(r * s[:, None]), np.asarray(rn), atol=1e-4)


def test_tsqr_qr_reconstruction_and_orthogonality():
    a = _rand(512, 24, seed=1)
    q, r = tsqr_qr(a, nblocks=8)
    np.testing.assert_allclose(np.asarray(q @ r), np.asarray(a), atol=1e-4)
    np.testing.assert_allclose(np.asarray(q.T @ q), np.eye(24), atol=1e-5)


def test_tsqr_qr_ill_conditioned_refinement():
    """CQR2-style refinement keeps Q orthonormal for cond ~ 1e4 inputs."""
    rng = np.random.default_rng(2)
    u, _ = np.linalg.qr(rng.standard_normal((256, 16)))
    v, _ = np.linalg.qr(rng.standard_normal((16, 16)))
    s = np.logspace(0, -4, 16)
    a = jnp.asarray(u @ np.diag(s) @ v.T, jnp.float32)
    q, r = tsqr_qr(a, nblocks=4, refine=True)
    assert float(jnp.linalg.norm(q.T @ q - jnp.eye(16))) < 1e-3
    np.testing.assert_allclose(np.asarray(q @ r), np.asarray(a), atol=1e-4)


def test_triangular_inverse_apply_clamps_rank_deficiency():
    a = _rand(64, 8, seed=3)
    r = jnp.linalg.qr(a)[1]
    r = r.at[4, 4].set(0.0)  # kill a pivot
    out = triangular_inverse_apply(a, r)
    assert bool(jnp.all(jnp.isfinite(out)))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(2, 16))
def test_property_tsqr_gram_identity(seed, n):
    """R from TSQR satisfies R^T R == A^T A regardless of tree shape."""
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.standard_normal((128, n)), jnp.float32)
    r = tsqr_r(a, nblocks=4)
    np.testing.assert_allclose(
        np.asarray(r.T @ r), np.asarray(a.T @ a), atol=5e-3 * n
    )


_SHARDED_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.core.tsqr import distributed_qr, tsqr_tree_sharded

    mesh = jax.make_mesh((8,), ("data",))
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((256, 16)), jnp.float32)

    f = jax.jit(
        jax.shard_map(
            lambda x: distributed_qr(x, "data"),
            mesh=mesh,
            in_specs=P("data", None),
            out_specs=(P("data", None), P()),
        )
    )
    q, r = f(a)
    assert np.linalg.norm(np.asarray(q) @ np.asarray(r) - np.asarray(a)) < 1e-3
    assert np.linalg.norm(np.asarray(q).T @ np.asarray(q) - np.eye(16)) < 1e-3

    g = jax.jit(
        jax.shard_map(
            lambda x: tsqr_tree_sharded(x, "data"),
            mesh=mesh,
            in_specs=P("data", None),
            out_specs=P(),
        )
    )
    r2 = np.asarray(g(a))
    rn = np.linalg.qr(np.asarray(a))[1]
    s = np.sign(np.diagonal(r2)) * np.sign(np.diagonal(rn))
    assert np.abs(r2 * s[:, None] - rn).max() < 1e-3
    print("SHARDED_TSQR_OK")
    """
)


def test_sharded_tsqr_subprocess():
    """Butterfly-tree TSQR + distributed thin-QR on an 8-way mesh."""
    res = subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin",
             "JAX_PLATFORMS": "cpu"},
        cwd=__file__.rsplit("/", 2)[0],
    )
    assert "SHARDED_TSQR_OK" in res.stdout, res.stderr[-3000:]
