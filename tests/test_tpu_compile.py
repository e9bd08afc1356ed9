"""Ahead-of-time compiles of the main path's Pallas kernels for TPU v5e.

No chip is needed: the TPU compiler is installed and compiles for a
described ``v5e:2x2`` topology, refusing what it would refuse on the chip
(an op Mosaic cannot lower, a block shape off the lane tiling, more SMEM
or VMEM than a kernel may use).  The interpret-mode kernel tests check
none of that.  Each test compiles one kernel at a real width and asserts
the kernel is in the compiled program.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and every
test worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import engine
from repro.core.plan import DEFAULT_TABLE_BUDGET
from repro.kernels import ops

NB = 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _assert_compiles(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text


def _largest_admitted_grid() -> int:
    """Side of the largest square grid whose megakernel task table fits
    ``DEFAULT_TABLE_BUDGET``."""
    p = 4
    while engine.table_fits(p + 1, p + 1, DEFAULT_TABLE_BUDGET)[0]:
        p += 1
    return p


@pytest.mark.parametrize("batched", [False, True], ids=["single", "batched"])
@pytest.mark.parametrize("grid", ["4x4", "largest"])
def test_megakernel_compiles(one_chip, grid, batched):
    p = 4 if grid == "4x4" else _largest_admitted_grid()
    lead = (2,) if batched else ()
    x = jax.ShapeDtypeStruct(lead + (p, p, NB, NB), jnp.float32,
                             sharding=one_chip)
    impl = engine._factor_batched_impl if batched else engine._factor_impl
    _assert_compiles(lambda t: impl(t, p, p, NB, True, False, "megakernel"),
                     x)


@pytest.mark.parametrize("kind", engine._KIND_ORDER)
def test_wavefront_kernel_compiles(one_chip, kind):
    p = q = 4
    idx = next(level[kind] for level in engine.wavefront_task_arrays(p, q)
               if kind in level)
    x = jax.ShapeDtypeStruct((p, q, NB, NB), jnp.float32, sharding=one_chip)
    _assert_compiles(
        lambda t: engine._DISPATCH[kind](engine.initial_state(t, p, q, NB),
                                         idx, NB, False),
        x)


@pytest.mark.parametrize("m,b", [(4096, 32), (512, 128)])
def test_mht_panel_compiles(one_chip, m, b):
    x = jax.ShapeDtypeStruct((m, b), jnp.float32, sharding=one_chip)
    _assert_compiles(lambda a: ops.mht_panel(a, interpret=False), x)


@pytest.mark.parametrize("m,k", [(4096, 32), (512, 128)])
def test_wy_trailing_compiles(one_chip, m, k):
    v = jax.ShapeDtypeStruct((m, k), jnp.float32, sharding=one_chip)
    t = jax.ShapeDtypeStruct((k, k), jnp.float32, sharding=one_chip)
    c = jax.ShapeDtypeStruct((m, 256), jnp.float32, sharding=one_chip)
    _assert_compiles(lambda v, t, c: ops.wy_trailing(v, t, c, interpret=False),
                     v, t, c)
