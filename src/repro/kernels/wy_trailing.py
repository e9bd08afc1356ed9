"""Pallas TPU kernel: fused WY trailing update  ``C <- C - V (T^T (V^T C))``.

The blocked QR trailing update is three chained GEMMs.  Run naively that
is three HBM round-trips over C-sized data; fused per column-tile it is
one read + one write of C, with W = V^T C_tile and X = T^T W living
entirely in VMEM.  This is the Level-3 counterpart of the paper's fused
macro-op: the same "never let the intermediate leave the fast memory"
co-design argument, re-blocked for the 128x128 MXU instead of the DOT4.

The fused product chain is :func:`repro.kernels.macro_ops.wy_body` — the
ONE WY apply this package owns, shared with the tile-DAG LARFB/SSRFB
macro ops and the wavefront engine.  This module only streams C through
it, one column-tile per grid cell.

Grid: one program per C column-tile (bn columns).  V (m, k), T (k, k) are
broadcast to every program; C tiles stream.  VMEM per program:
m·bn + m·k + k·k + k·bn floats — the ops wrapper checks the budget and
requires m ≤ 8192 for k, bn = 128.

All matmuls accumulate in ``promote_types(dtype, float32)``
(``preferred_element_type``).
"""

from __future__ import annotations

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.plan import KERNEL_VMEM_LIMIT
from repro.kernels import macro_ops

Array = jax.Array

__all__ = ["wy_trailing_kernel", "wy_trailing_pallas"]


def wy_trailing_kernel(v_ref, t_ref, c_ref, out_ref):
    """One C column-tile: W = V^T C (MXU), X = T^T W (MXU), C -= V X (MXU)."""
    out_ref[...] = macro_ops.wy_body(v_ref[...], t_ref[...], c_ref[...])


def wy_trailing_pallas(
    v: Array, t: Array, c: Array, *, bn: int = 128, interpret: bool = False
) -> Array:
    """Fused trailing update over all of C, tiled bn columns at a time.

    Requires c.shape[1] % bn == 0 (ops wrapper pads)."""
    m, k = v.shape
    n = c.shape[1]
    if n % bn != 0:
        raise ValueError(f"n={n} not a multiple of bn={bn}")
    grid = (n // bn,)
    return pl.pallas_call(
        wy_trailing_kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), c.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, k), lambda j: (0, 0)),   # V broadcast
            pl.BlockSpec((k, k), lambda j: (0, 0)),   # T broadcast
            pl.BlockSpec((m, bn), lambda j: (0, j)),  # C tile streams
        ],
        out_specs=pl.BlockSpec((m, bn), lambda j: (0, j)),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=KERNEL_VMEM_LIMIT),
        interpret=interpret,
    )(v, t, c)
