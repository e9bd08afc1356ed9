"""Distributed QR-Muon: orthogonalize FSDP-sharded momentum with the
butterfly-tree TSQR (paper §5.2 as a production optimizer path)."""

import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.tsqr import distributed_qr
    from repro.optim import muon_init, muon_update, qr_orthogonalize_2d

    mesh = jax.make_mesh((8,), ("data",))

    # the distributed orthogonalizer: rows sharded over "data", thin Q out
    def tsqr_orth(m2d):
        rows = m2d.shape[0]
        transpose = m2d.shape[0] < m2d.shape[1]
        a = m2d.T if transpose else m2d
        f = jax.shard_map(lambda x: distributed_qr(x, "data"),
                      mesh=mesh, in_specs=P("data", None),
                      out_specs=(P("data", None), P()))
        q, r = f(a)
        signs = jnp.where(jnp.diagonal(r) >= 0, 1.0, -1.0)
        q = q * signs[None, :]
        return q.T if transpose else q

    params = {"w": jax.device_put(
        jax.random.normal(jax.random.PRNGKey(0), (512, 64), jnp.float32),
        NamedSharding(mesh, P("data", None)))}
    grads = {"w": jax.device_put(
        jax.random.normal(jax.random.PRNGKey(1), (512, 64), jnp.float32),
        NamedSharding(mesh, P("data", None)))}
    state = muon_init(params)

    with mesh:
        step = jax.jit(lambda g, s, p: muon_update(
            g, s, p, lr=1.0, momentum=0.0, nesterov=False,
            orthogonalize_fn=tsqr_orth))
        new_params, _ = step(grads, state, params)

    delta = np.asarray(params["w"] - new_params["w"]) / np.sqrt(512 / 64)
    err = np.abs(delta.T @ delta - np.eye(64)).max()
    assert err < 1e-3, err
    # matches the single-device QR orthogonalizer
    ref = np.asarray(qr_orthogonalize_2d(grads["w"]))
    assert np.abs(delta - ref).max() < 1e-3
    print("DIST_MUON_OK", err)
""")


@pytest.mark.slow
def test_distributed_qr_muon_subprocess():
    res = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        timeout=900,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin",
             "JAX_PLATFORMS": "cpu"},  # never probe a TPU from the test
        cwd=__file__.rsplit("/", 2)[0])
    assert "DIST_MUON_OK" in res.stdout, res.stderr[-3000:]
