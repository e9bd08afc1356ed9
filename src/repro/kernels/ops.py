"""jit'd public wrappers for the Pallas kernels.

Dispatch policy: on TPU the kernels run compiled; everywhere else they
run in ``interpret=True`` mode (the kernel body executes in Python/XLA on
CPU) so correctness is validated in CI without hardware.  Callers can
force either with ``interpret=``.

Padding: ``wy_trailing`` pads the C column count to the tile size and
strips it after; ``mht_panel`` takes the panel exactly as given (the
panel IS the block).

VMEM budget: this backend registers a :class:`repro.core.plan.KernelPolicy`
carrying its working-set estimator and the shared
:data:`repro.core.plan.DEFAULT_VMEM_BUDGET`; the wrappers' runtime guards
below and the planner's fits-in-VMEM decisions both read that one policy,
so they cannot disagree.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.plan import (DEFAULT_VMEM_BUDGET, KernelPolicy,
                             register_kernel_policy)
from repro.kernels.macro_ops import default_interpret
from repro.kernels.mht_panel import mht_panel_pallas
from repro.kernels.wy_trailing import wy_trailing_pallas

Array = jax.Array

__all__ = ["mht_panel", "wy_trailing", "vmem_bytes_mht_panel",
           "vmem_bytes_wy_trailing", "default_interpret"]


def vmem_bytes_mht_panel(m: int, b: int) -> int:
    """fp32 working set of the panel kernel (panel + packed copy)."""
    return 2 * m * b * 4


def vmem_bytes_wy_trailing(m: int, k: int, bn: int = 128) -> int:
    """fp32 working set of one trailing-update step: V, T, the C tile in
    and out, and W.  V's k columns occupy whole 128-lane rows in VMEM."""
    lanes = -(-k // 128) * 128
    return (m * lanes + k * lanes + 2 * m * bn + k * bn) * 4


# The kernel backend registers its dispatch policy (VMEM estimator +
# budget + interpret default) with the planner, so ``method="auto"`` /
# the ``use_kernel=None`` auto policy can decide panel-fits-VMEM
# centrally against the very same budget enforced here.
_POLICY = register_kernel_policy(KernelPolicy(
    name="mht_panel",
    vmem_bytes=vmem_bytes_mht_panel,
    vmem_budget=DEFAULT_VMEM_BUDGET,
    default_interpret=default_interpret,
))


@functools.partial(jax.jit, static_argnames=("row0", "interpret"))
def _mht_panel_jit(panel: Array, row0: int, interpret: bool):
    return mht_panel_pallas(panel, row0=row0, interpret=interpret)


def mht_panel(panel: Array, *, row0: int = 0,
              interpret: bool | None = None) -> Tuple[Array, Array]:
    """Fused VMEM-resident MHT panel factorization.

    Returns (packed, taus) exactly like
    :func:`repro.core.blocked.panel_factor`; oracle:
    :func:`repro.kernels.ref.mht_panel_ref`.
    """
    m, b = panel.shape
    if vmem_bytes_mht_panel(m, b) > _POLICY.vmem_budget:
        raise ValueError(
            f"panel ({m},{b}) exceeds VMEM budget "
            f"({vmem_bytes_mht_panel(m, b)} > {_POLICY.vmem_budget}); "
            "factor via TSQR leaves instead")
    interp = default_interpret() if interpret is None else interpret
    return _mht_panel_jit(panel, row0, interp)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def _wy_trailing_jit(v: Array, t: Array, c: Array, bn: int, interpret: bool):
    n = c.shape[1]
    n_pad = (n + bn - 1) // bn * bn
    c_p = jnp.pad(c, ((0, 0), (0, n_pad - n))) if n_pad != n else c
    out = wy_trailing_pallas(v, t, c_p, bn=bn, interpret=interpret)
    return out[:, :n]


def wy_trailing(v: Array, t: Array, c: Array, *, bn: int = 128,
                interpret: bool | None = None) -> Array:
    """Fused WY trailing update ``C - V (T^T (V^T C))``.

    Oracle: :func:`repro.kernels.ref.wy_trailing_ref`."""
    m, k = v.shape
    if vmem_bytes_wy_trailing(m, k, bn) > _POLICY.vmem_budget:
        raise ValueError(f"wy_trailing working set too large for VMEM: m={m} k={k} bn={bn}")
    interp = default_interpret() if interpret is None else interpret
    bn_eff = min(bn, max(8, c.shape[1]))
    return _wy_trailing_jit(v, t, c, bn_eff, interp)
