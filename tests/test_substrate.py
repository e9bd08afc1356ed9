"""Substrate tests: data pipeline, checkpointing, compression,
fault tolerance, sharding rules."""

import os
import tempfile
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from hypothesis_compat import given, settings, st

from repro.checkpoint import CheckpointManager
from repro.data import DataConfig, SyntheticLM
from repro.distributed import (
    StepWatchdog, dequantize, ef_compress_tree, init_error_state, quantize,
)
from repro.distributed.fault_tolerance import plan_elastic_mesh


# ----------------------------------------------------------------- data

def test_pipeline_deterministic_and_resumable():
    cfg = DataConfig(vocab_size=1000, seq_len=32, global_batch=4, seed=7)
    p1 = SyntheticLM(cfg)
    it = iter(p1)
    batches = [next(it) for _ in range(5)]
    # resume from step 3
    p2 = SyntheticLM(cfg)
    p2.load_state_dict({"step": 3, "seed": 7})
    b3 = next(iter(p2))
    np.testing.assert_array_equal(b3["tokens"], batches[3]["tokens"])
    np.testing.assert_array_equal(b3["labels"], batches[3]["labels"])


def test_pipeline_labels_are_shifted_stream():
    cfg = DataConfig(vocab_size=50, seq_len=16, global_batch=2, seed=1)
    b = SyntheticLM(cfg).peek(0)
    # labels[t] is the next token of tokens[t] by construction
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


def test_pipeline_embedding_input_stub():
    cfg = DataConfig(vocab_size=64, seq_len=8, global_batch=2, seed=0,
                     embedding_input=True, d_model=32)
    b = SyntheticLM(cfg).peek(0)
    assert "tokens" not in b and b["embeds"].shape == (2, 8, 32)
    assert np.isfinite(b["embeds"]).all()


def test_pipeline_seed_mismatch_raises():
    cfg = DataConfig(vocab_size=10, seq_len=4, global_batch=1, seed=1)
    p = SyntheticLM(cfg)
    with pytest.raises(AssertionError):
        p.load_state_dict({"step": 0, "seed": 2})


# ----------------------------------------------------------- checkpoint

def _tree(key):
    return {"a": jax.random.normal(key, (8, 4)),
            "b": {"c": jnp.arange(5), "d": jnp.float32(3.5)}}


def test_checkpoint_roundtrip_and_gc():
    with tempfile.TemporaryDirectory() as td:
        mgr = CheckpointManager(td, keep_n=2)
        for step in (1, 2, 3, 4):
            mgr.save(step, _tree(jax.random.PRNGKey(step)))
        assert mgr.all_steps() == [3, 4]  # gc keeps 2
        restored = mgr.restore(4, _tree(jax.random.PRNGKey(0)))
        expect = _tree(jax.random.PRNGKey(4))
        np.testing.assert_allclose(np.asarray(restored["a"]),
                                   np.asarray(expect["a"]))


def test_checkpoint_async_and_metadata():
    with tempfile.TemporaryDirectory() as td:
        mgr = CheckpointManager(td)
        mgr.save(10, _tree(jax.random.PRNGKey(1)),
                 metadata={"data": {"step": 10, "seed": 0}}, blocking=False)
        mgr.wait_until_finished()
        assert mgr.latest_step() == 10
        assert mgr.metadata(10)["data"]["step"] == 10


def test_checkpoint_ignores_uncommitted():
    with tempfile.TemporaryDirectory() as td:
        mgr = CheckpointManager(td)
        mgr.save(1, _tree(jax.random.PRNGKey(1)))
        # simulate a crash mid-save: directory without COMMITTED
        os.makedirs(os.path.join(td, "step_00000002"))
        assert mgr.latest_step() == 1


def test_checkpoint_structure_mismatch_raises():
    with tempfile.TemporaryDirectory() as td:
        mgr = CheckpointManager(td)
        mgr.save(1, _tree(jax.random.PRNGKey(1)))
        with pytest.raises(ValueError):
            mgr.restore(1, {"a": jnp.zeros((8, 4))})  # missing leaves


# ---------------------------------------------------------- compression

@pytest.mark.parametrize("shape", [(100,), (64, 64), (3, 5, 7)])
def test_quantize_roundtrip_bound(shape):
    x = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32) * 10
    codes, scales = quantize(x)
    back = dequantize(codes, scales, shape)
    # int8 symmetric quantization: error <= scale/2 per element
    err = np.abs(np.asarray(back - x))
    bound = np.asarray(scales).max() / 2 + 1e-6
    assert err.max() <= bound
    assert codes.dtype == jnp.int8


def test_error_feedback_accumulates_to_unbiased():
    """Sum of decoded updates converges to sum of true grads (EF property)."""
    key = jax.random.PRNGKey(3)
    g = {"w": jax.random.normal(key, (256,), jnp.float32)}
    err = init_error_state(g)
    total_dec = jnp.zeros((256,))
    steps = 50
    for i in range(steps):
        dec, err = ef_compress_tree(g, err)
        total_dec = total_dec + dec["w"]
    # mean decoded ~= true grad: residual bounded by one quantization step
    diff = np.abs(np.asarray(total_dec / steps - g["w"]))
    assert diff.max() < np.abs(np.asarray(g["w"])).max() / 100


def test_compressed_psum_subprocess():
    import subprocess, sys, textwrap

    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import compressed_psum, init_error_state

        mesh = jax.make_mesh((4,), ("data",))
        g = jax.random.normal(jax.random.PRNGKey(0), (4, 256), jnp.float32)
        err = jnp.zeros((4, 256), jnp.float32)
        f = jax.jit(jax.shard_map(
            lambda gg, ee: compressed_psum({"g": gg}, "data", {"g": ee}),
            mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=({"g": P()}, {"g": P("data")})))
        red, new_err = f(g, err)
        true_mean = np.asarray(g).mean(0)
        got = np.asarray(red["g"])[0]
        assert np.abs(got - true_mean).max() < 0.05, np.abs(got - true_mean).max()
        print("COMPRESSED_PSUM_OK")
    """)
    res = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=600,
                         env={"PYTHONPATH": "src",
                              "PATH": "/usr/bin:/bin:/usr/local/bin",
                              "JAX_PLATFORMS": "cpu"},
                         cwd=__file__.rsplit("/", 2)[0])
    assert "COMPRESSED_PSUM_OK" in res.stdout, res.stderr[-2000:]


# ------------------------------------------------------ fault tolerance

def test_watchdog_flags_stragglers():
    flagged = []
    wd = StepWatchdog(threshold=3.0,
                      on_straggler=lambda s, dt, med: flagged.append(s))
    for step in range(10):
        wd.start()
        time.sleep(0.01 if step != 7 else 0.2)
        wd.stop(step)
    assert flagged == [7]


def test_elastic_mesh_shrinks_after_failure():
    devices = jax.devices()
    plan = plan_elastic_mesh(devices, failed=[], prefer_model=1)
    assert plan.mesh.size >= 1
    # simulate loss of all but one device
    if len(devices) > 1:
        plan2 = plan_elastic_mesh(devices, failed=[d.id for d in devices[1:]],
                                  prefer_model=1)
        assert plan2.mesh.size == 1


# --------------------------------------------------------- sharding rules

def test_sharding_rules_divisibility_fallbacks():
    import subprocess, sys, textwrap

    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.distributed.sharding import MeshRules, param_specs, batch_specs
        from repro.configs import get_config
        from repro.models import init_params

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        rules = MeshRules(mesh=mesh, data_axes=("data",))
        # smollm: 9 heads (not div by 4) must fall back, never crash
        cfg = get_config("smollm-135m")
        sds = jax.eval_shape(lambda k: init_params(k, cfg),
                             jax.ShapeDtypeStruct((2,), jnp.uint32))
        specs = param_specs(sds, rules)
        flat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        assert len(flat) > 0
        # every spec must be consistent with its leaf's divisibility
        for (path, leaf), spec in zip(
                jax.tree_util.tree_leaves_with_path(sds),
                flat):
            for dim, ax in zip(leaf.shape, spec):
                if ax is None: continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                size = 1
                for a in axes: size *= mesh.shape[a]
                assert dim % size == 0, (path, leaf.shape, spec)
        # batch=1 falls back to sequence sharding
        b = {"tokens": jax.ShapeDtypeStruct((1, 16), jnp.int32)}
        bs = batch_specs(b, rules)
        assert bs["tokens"] == P(None, "data")
        print("SHARDING_RULES_OK")
    """)
    res = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=600,
                         env={"PYTHONPATH": "src",
                              "PATH": "/usr/bin:/bin:/usr/local/bin",
                              "JAX_PLATFORMS": "cpu"},
                         cwd=__file__.rsplit("/", 2)[0])
    assert "SHARDING_RULES_OK" in res.stdout, res.stderr[-2000:]


@settings(max_examples=20, deadline=None)
@given(scale=st.floats(1e-4, 1e4), seed=st.integers(0, 10_000),
       n=st.integers(1, 2000))
def test_property_quantization_error_bound(scale, seed, n):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n,), jnp.float32) * scale
    codes, scales = quantize(x)
    back = dequantize(codes, scales, (n,))
    err = np.abs(np.asarray(back - x))
    assert err.max() <= np.asarray(scales).max() / 2 + 1e-6 * scale
