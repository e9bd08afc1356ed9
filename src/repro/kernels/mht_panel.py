"""Pallas TPU kernel: fused MHT panel factorization (``DGEQR2HT`` panel).

This is the TPU realization of the paper's algorithm-architecture
co-design (§5.1).  The REDEFINE PE streams the panel from Global Memory
into its Local Memory once, then the reconfigured DOT4 data-path executes
the fused macro-operation

    a_ij <- a_ij - tau * v_i * (v . a_:j)

for every column without further GM traffic.  Here the *whole panel* is a
single VMEM block (BlockSpec = full (m, b) tile); the column loop runs
inside the kernel, so per column the dot-reduce (VPU cross-lane) and the
rank-1 fused-multiply-subtract happen register/VMEM-resident — one HBM
read and one HBM write for the entire panel factorization, versus
2·b HBM passes for a column-by-column classical HT.

The column loop itself is :func:`repro.kernels.macro_ops.panel_body` —
the ONE Householder inner loop this package owns, shared with the
tile-DAG GEQRT/TSQRT macro ops and the wavefront engine.  This module
only binds it to a single-grid-cell ``pallas_call``.

VMEM budget: (m, b) fp32 once ≈ m·b·4 bytes; the ops wrapper enforces
m·b·4 ≤ 8 MiB (half of v5e VMEM, leaving room for double buffering).
Taller panels are handled above this kernel by TSQR leaves.

Layout notes for the MXU/VPU era (vs. the paper's 4-wide RDP):
  * all tensors kept 2-D; reductions are cross-lane VPU ops;
  * row/column masks from ``broadcasted_iota`` (TPU requires 2-D iota);
  * accumulation in ``promote_types(dtype, float32)`` irrespective of
    the I/O dtype.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.plan import KERNEL_VMEM_LIMIT
from repro.kernels import macro_ops

Array = jax.Array

__all__ = ["mht_panel_kernel", "mht_panel_pallas"]


def mht_panel_kernel(panel_ref, out_ref, taus_ref, *, row0: int):
    """Kernel body: factor the VMEM-resident panel in place.

    panel_ref: (m, b) input block
    out_ref:   (m, b) packed factor (R upper / V below pivots)
    taus_ref:  (1, b) tau row
    """
    packed, taus = macro_ops.panel_body(panel_ref[...], row0)
    out_ref[...] = packed
    taus_ref[...] = taus[None]


def mht_panel_pallas(
    panel: Array, *, row0: int = 0, interpret: bool = False
) -> Tuple[Array, Array]:
    """Invoke the panel kernel on a full (m, b) panel (single grid cell —
    the panel IS the block, as in the paper's LM-resident dataflow)."""
    m, b = panel.shape
    kernel = functools.partial(mht_panel_kernel, row0=row0)
    out, taus = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((m, b), panel.dtype),
            jax.ShapeDtypeStruct((1, b), panel.dtype),
        ],
        in_specs=[pl.BlockSpec((m, b), lambda: (0, 0))],
        out_specs=[
            pl.BlockSpec((m, b), lambda: (0, 0)),
            pl.BlockSpec((1, b), lambda: (0, 0)),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=KERNEL_VMEM_LIMIT),
        interpret=interpret,
    )(panel)
    return out, taus[0]
