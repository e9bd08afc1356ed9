"""The unified macro-op library: one Householder/WY core, four DAG kinds.

The paper's co-design realizes every QR DAG node as a *fused macro
operation* on the Reconfigurable Data-path instead of a sequence of BLAS
calls (§4-§5).  Before this module the software mirrored the opposite:
four kernel modules (``ops``, ``mht_panel``, ``wy_trailing``,
``tile_ops``) each re-implemented the Householder reflector / WY apply
inner loops.  ``macro_ops`` is the single RDP-analogue:

  * **value-level bodies** — :func:`panel_body`, :func:`tsqrt_factor`,
    :func:`wy_body`, and the four tile-DAG macro ops
    :func:`geqrt_body` / :func:`larfb_body` / :func:`tsqrt_body` /
    :func:`ssrfb_body`.  Each is a pure jnp function on tile *values*:
    the same callable is the Pallas kernel body (traced inside
    ``pallas_call``) **and** the ``use_kernel=False`` oracle (vmapped by
    the engine's jnp lowering).  Because both paths trace the identical
    op sequence, the engine path is *bitwise* equal to the oracle —
    asserted in tests/test_engine.py and tests/test_conformance.py.
  * **wavefront kernels** — ``*_wavefront_kernel``: the uniform-signature
    Pallas bodies the engine (:mod:`repro.core.engine`) dispatches, one
    ``pallas_call`` per (wavefront, kind) task batch.  Tiles move
    HBM -> VMEM scratch -> HBM by explicit DMA against a ``(p, q, nb,
    nb)`` workspace held in ``pl.ANY`` memory space and aliased
    in-place; task coordinates arrive as scalar-prefetch index arrays.
  * **VMEM estimators** — :func:`vmem_bytes` per op and
    :func:`engine_vmem_bytes` for the engine's worst case, registered as
    the ``"macro_ops"`` :class:`repro.core.plan.KernelPolicy` so the
    planner's fits-in-VMEM decisions and the engine's runtime guard read
    the same number.

jnp oracles for the bodies live in :mod:`repro.kernels.ref`
(independent realizations via ``panel_factor`` — the numerical anchors);
the legacy single-tile wrappers in ``ops`` / ``tile_ops`` and the panel /
trailing kernels in ``mht_panel`` / ``wy_trailing`` are now thin shells
over these bodies.

All bodies accumulate in ``promote_types(dtype, float32)`` — fp32 for
fp32/bf16 I/O (the VPU/MXU reality), fp64 when x64 is enabled (the
conformance suite's float64 bar).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.plan import (DEFAULT_TABLE_BUDGET, DEFAULT_VMEM_BUDGET,
                             KernelPolicy, register_kernel_policy)

Array = jax.Array

__all__ = [
    "MacroOp",
    "MACRO_OPS",
    "default_interpret",
    "acc_dtype",
    "reflector_coeffs",
    "panel_body",
    "wy_body",
    "larft_body",
    "stacked_larft",
    "geqrt_body",
    "larfb_body",
    "tsqrt_factor",
    "tsqrt_body",
    "ssrfb_body",
    "geqrt_wavefront_kernel",
    "larfb_wavefront_kernel",
    "tsqrt_wavefront_kernel",
    "ssrfb_wavefront_kernel",
    "vmem_bytes",
    "engine_vmem_bytes",
    "megakernel_vmem_bytes",
    "batched_megakernel_vmem_bytes",
    "MEGAKERNEL_VMEM_TILES",
]


def default_interpret() -> bool:
    """Kernel dispatch default: compiled on TPU, interpret elsewhere."""
    return jax.default_backend() != "tpu"


# Products run at the accumulation dtype's own precision: left at the
# default, a float32 product compiled for TPU may take a single bfloat16
# pass, which puts the factorization's error near bfloat16's epsilon.
_EXACT = lax.Precision.HIGHEST


def _tdot(x: Array, y: Array) -> Array:
    """``x^T y`` as one contraction over rows.  A separate transpose op
    is folded into the product in some programs and not in others, which
    changes the summation order between the kernel and its oracle."""
    return lax.dot_general(x, y, (((0,), (0,)), ((), ())),
                           precision=_EXACT,
                           preferred_element_type=acc_dtype(x.dtype))


def acc_dtype(dtype) -> jnp.dtype:
    """Accumulation dtype: never below fp32, fp64 when the I/O is fp64."""
    return jnp.promote_types(dtype, jnp.float32)


# ---------------------------------------------------------------------------
# the shared Householder reflector core
# ---------------------------------------------------------------------------

def reflector_coeffs(x0, tail2):
    """LAPACK-convention reflector coefficients from the pivot value and
    the squared tail norm: ``(beta, tau, denom)`` with ``v = x / denom``
    below the pivot and ``tau = 0`` for already-eliminated columns.

    This is THE inner loop the paper fuses onto the RDP; every macro op
    below calls it (shapes broadcast, so it serves the (1, 1)-masked
    panel loop and the scalar TSQRT pivot alike).
    """
    norm = jnp.sqrt(x0 * x0 + tail2)
    beta = jnp.where(x0 >= 0.0, -norm, norm)
    degen = tail2 == 0.0
    denom = jnp.where(degen, 1.0, x0 - beta)
    tau = jnp.where(degen, 0.0, (beta - x0) / jnp.where(beta == 0.0, 1.0, beta))
    beta_val = jnp.where(degen, x0, beta)
    return beta_val, tau, denom


def panel_body(panel: Array, row0: int) -> Tuple[Array, Array]:
    """Fused MHT panel factorization of an (m, b) block, pivot rows
    starting at ``row0`` — the ``DGEQR2HT`` macro op (paper §5.1).

    One pass per column: dot-reduce + rank-1 fused-multiply-subtract,
    panel resident the whole time.  Returns ``(packed, taus)`` in the
    LAPACK layout of :func:`repro.core.blocked.panel_factor` (its oracle,
    :func:`repro.kernels.ref.mht_panel_ref`).
    """
    m, b = panel.shape
    acc = acc_dtype(panel.dtype)
    a0 = panel.astype(acc)
    rows = lax.broadcasted_iota(jnp.int32, (m, 1), 0)
    cols = lax.broadcasted_iota(jnp.int32, (1, b), 1)
    taus0 = jnp.zeros((1, b), acc)

    def body(lj, carry):
        a, taus = carry
        pivot = row0 + lj
        colmask = cols == lj                                   # (1, b)
        at = rows == pivot                                     # (m, 1)
        below = rows > pivot

        x = jnp.sum(jnp.where(colmask, a, 0.0), axis=1, keepdims=True)  # (m,1)
        x0 = jnp.sum(jnp.where(at, x, 0.0), axis=0, keepdims=True)      # (1,1)
        tail2 = jnp.sum(jnp.where(below, x * x, 0.0), axis=0, keepdims=True)
        beta_val, tau, denom = reflector_coeffs(x0, tail2)
        v = jnp.where(below, x / denom, 0.0) + jnp.where(at, 1.0, 0.0)  # (m,1)

        # --- the fused macro-op: one pass over the panel ---------------
        w = tau * jnp.sum(v * a, axis=0, keepdims=True)         # (1, b)
        trailing = cols > lj
        a = a - jnp.where(trailing, v * w, 0.0)

        # pack column lj: R diag at pivot, reflector below, R above kept
        a = jnp.where(colmask & at, beta_val, a)
        a = jnp.where(colmask & below, v, a)
        taus = jnp.where(colmask, tau, taus)
        return a, taus

    a_out, taus = lax.fori_loop(0, b, body, (a0, taus0))
    return a_out.astype(panel.dtype), taus[0].astype(panel.dtype)


def wy_body(v: Array, t: Array, c: Array) -> Array:
    """Fused WY trailing update ``C - V (T^T (V^T C))`` — three chained
    MXU products with the intermediates never leaving fast memory."""
    acc = acc_dtype(c.dtype)
    v_a = v.astype(acc)
    c_a = c.astype(acc)
    w = _tdot(t.astype(acc), _tdot(v_a, c_a))
    return (c_a - jnp.dot(v_a, w, preferred_element_type=acc,
                          precision=_EXACT)).astype(c.dtype)


def larft_body(gram: Array, taus: Array) -> Array:
    """Block reflector T (``DLARFT``, forward, columnwise) from the
    reflector Gram matrix ``V^T V`` — the kernel-safe realization.

    Column i of T is ``-tau_i T[:, :i] (V[:, :i]^T v_i)`` with ``tau_i``
    on the diagonal.  Only the strictly-lower triangle of ``gram`` is
    read (its diagonal never enters T), so callers may pass the Gram of
    V without its unit diagonal.  Every step is an iota mask and a
    masked reduction, as in :func:`panel_body`: no dynamic gather,
    scatter or mat-vec, which Mosaic cannot lower.  The host/jnp
    realization is :func:`repro.core.blocked.larft`.
    """
    b = gram.shape[0]
    acc = acc_dtype(gram.dtype)
    g = gram.astype(acc)
    rows = lax.broadcasted_iota(jnp.int32, (b, b), 0)
    cols = lax.broadcasted_iota(jnp.int32, (b, b), 1)
    rvec = lax.broadcasted_iota(jnp.int32, (b, 1), 0)
    lane = lax.broadcasted_iota(jnp.int32, (1, b), 1)
    tau_row = taus.reshape(1, b).astype(acc)

    def body(i, t):
        tau_i = jnp.sum(jnp.where(lane == i, tau_row, 0.0), axis=1,
                        keepdims=True)                          # (1, 1)
        w = jnp.sum(jnp.where((rows == i) & (cols < i), g, 0.0), axis=0,
                    keepdims=True)                              # (1, b)
        tcol = jnp.sum(t * w, axis=1, keepdims=True)            # (b, 1)
        tcol = jnp.where(rvec < i, -tau_i * tcol,
                         jnp.where(rvec == i, tau_i, 0.0))
        return jnp.where(cols == i, tcol, t)

    t = lax.fori_loop(0, b, body, jnp.zeros((b, b), acc))
    return t.astype(gram.dtype)


def _gram(v: Array) -> Array:
    v_a = v.astype(acc_dtype(v.dtype))
    return _tdot(v_a, v_a)


def _unpack_v1(packed: Array) -> Array:
    """Unit-lower V1 of a packed square tile (2-D iota masks)."""
    nb = packed.shape[0]
    rows = lax.broadcasted_iota(jnp.int32, (nb, nb), 0)
    cols = lax.broadcasted_iota(jnp.int32, (nb, nb), 1)
    return (jnp.where(rows > cols, packed, 0.0)
            + jnp.where(rows == cols, 1.0, 0.0)).astype(packed.dtype)


def stacked_larft(v2: Array, taus: Array) -> Array:
    """Block reflector T for the stacked TSQRT reflectors V = [I; V2].
    Off the diagonal the Gram of ``[I; V2]`` is ``V2^T V2``."""
    return larft_body(_gram(v2), taus).astype(v2.dtype)


# ---------------------------------------------------------------------------
# the four tile-DAG macro ops (value level — kernel body AND jnp oracle)
# ---------------------------------------------------------------------------

def geqrt_body(tile: Array) -> Tuple[Array, Array, Array]:
    """GEQRT: QR of one diagonal tile, T formed in the same pass.

    Returns ``(packed, T, taus)`` — V1 strictly below / R on and above
    the diagonal, plus the WY block reflector for the step's LARFBs.
    """
    packed, taus = panel_body(tile, 0)
    return packed, larft_body(_gram(_unpack_v1(packed)), taus), taus


def larfb_body(diag_packed: Array, t: Array, c: Array) -> Array:
    """LARFB: apply Q_k^T to one trailing tile from the packed diagonal
    tile (V1 unpacked in place — the tile ref is the input)."""
    return wy_body(_unpack_v1(diag_packed), t, c)


def tsqrt_factor(diag: Array, sub: Array) -> Tuple[Array, Array, Array]:
    """TSQRT inner loop: QR of the stacked pair [R; A] exploiting the
    ``[e_j; v2_j]`` reflector structure (R upper triangular on top).

    ``diag`` may carry V1 strictly below its diagonal (the packed layout)
    — only the upper triangle is factored and the sub-diagonal part is
    passed through untouched in the merged output.  Returns
    ``(merged, V2, taus)``.
    """
    nb = diag.shape[0]
    acc = acc_dtype(diag.dtype)
    rows = lax.broadcasted_iota(jnp.int32, (nb, 1), 0)
    cols = lax.broadcasted_iota(jnp.int32, (1, nb), 1)
    upper = rows <= cols
    r0 = jnp.where(upper, diag, 0.0).astype(acc)
    a0 = sub.astype(acc)

    def body(j, carry):
        r, a, vacc, taus = carry
        colmask = cols == j                                     # (1, nb)
        pivmask = (rows == j) & colmask                         # (nb, nb)
        x0 = jnp.sum(jnp.where(pivmask, r, 0.0))                # pivot R[j,j]
        x2 = jnp.sum(jnp.where(colmask, a, 0.0), axis=1,
                     keepdims=True)                             # (nb, 1)
        tail2 = jnp.sum(x2 * x2)
        beta_val, tau, denom = reflector_coeffs(x0, tail2)
        v2 = x2 / denom                                         # (nb, 1)

        # Structured macro-op: the reflector is [e_j; v2], so the dot
        # touches only R's row j plus the A block — one fused pass.
        rrow = jnp.sum(jnp.where(rows == j, r, 0.0), axis=0,
                       keepdims=True)                           # (1, nb)
        w = tau * (rrow + jnp.sum(v2 * a, axis=0, keepdims=True))
        trailing = cols > j
        r = r - jnp.where((rows == j) & trailing, w, 0.0)
        a = a - jnp.where(trailing, v2 * w, 0.0)

        r = jnp.where(pivmask, beta_val, r)
        vacc = jnp.where(colmask, v2, vacc)
        taus = jnp.where(colmask, tau, taus)
        return r, a, vacc, taus

    r_fin, _, vacc, taus = lax.fori_loop(
        0, nb, body,
        (r0, a0, jnp.zeros((nb, nb), acc), jnp.zeros((1, nb), acc)))
    merged = jnp.where(upper, r_fin, diag.astype(acc))
    return (merged.astype(diag.dtype), vacc.astype(diag.dtype),
            taus[0].astype(diag.dtype))


def tsqrt_body(diag: Array, sub: Array) -> Tuple[Array, Array, Array, Array]:
    """TSQRT as the engine's fused macro op: factor + stacked-T formation.

    Returns ``(merged, V2, T, taus)``.
    """
    merged, v2, taus = tsqrt_factor(diag, sub)
    return merged, v2, stacked_larft(v2, taus), taus


def ssrfb_body(v2: Array, t: Array, ck: Array, ci: Array
               ) -> Tuple[Array, Array]:
    """SSRFB: apply the TSQRT block reflector to a tile pair.

    With V = [I; V2]:  W = T^T (C_k + V2^T C_i);  C_k -= W;  C_i -= V2 W.
    Four chained MXU products fused into one VMEM pass per tile pair.
    """
    acc = acc_dtype(ck.dtype)
    v_a = v2.astype(acc)
    ck_a = ck.astype(acc)
    ci_a = ci.astype(acc)
    w = _tdot(t.astype(acc), ck_a + _tdot(v_a, ci_a))
    return ((ck_a - w).astype(ck.dtype),
            (ci_a - jnp.dot(v_a, w, preferred_element_type=acc,
                            precision=_EXACT)
             ).astype(ci.dtype))


# ---------------------------------------------------------------------------
# wavefront kernels — the engine's per-(wavefront, kind) Pallas bodies
# ---------------------------------------------------------------------------
#
# Uniform signature: scalar-prefetch index refs first (task coordinates,
# one row per grid cell), then the ANY-space workspace + blocked state
# inputs, the aliased outputs, VMEM tile scratch, and one DMA semaphore.
# Tiles are DMA'd workspace -> scratch, transformed by the value-level
# body above, and DMA'd back — the whole DAG node is one VMEM-resident
# fused pass, and the workspace is updated in place (the gather ->
# compute -> ``.at[].set`` round trip of the old scheduler is gone).

def _copy(src, dst, sem) -> None:
    cp = pltpu.make_async_copy(src, dst, sem)
    cp.start()
    cp.wait()


def geqrt_wavefront_kernel(kk_ref, ws_in, dt_in, dtaus_in,
                           ws_out, dt_out, dtaus_out, tile_scr, taus_scr,
                           sem):
    """One GEQRT task per grid cell: tile (k, k) -> packed, T, taus."""
    del ws_in, dt_in, dtaus_in  # aliased: reads go through the out refs
    g = pl.program_id(0)
    k = kk_ref[g]
    _copy(ws_out.at[k, k], tile_scr, sem)
    packed, t, taus = geqrt_body(tile_scr[...])
    dt_out[0] = t
    tile_scr[...] = packed
    taus_scr[...] = taus
    _copy(tile_scr, ws_out.at[k, k], sem)
    _copy(taus_scr, dtaus_out.at[k], sem)


def larfb_wavefront_kernel(kk_ref, jj_ref, ws_in, dt_ref,
                           ws_out, diag_scr, c_scr, sem):
    """One LARFB task per grid cell: tile (k, j) -= V1 (T^T (V1^T .))."""
    del ws_in
    g = pl.program_id(0)
    k = kk_ref[g]
    j = jj_ref[g]
    _copy(ws_out.at[k, k], diag_scr, sem)
    _copy(ws_out.at[k, j], c_scr, sem)
    c_scr[...] = larfb_body(diag_scr[...], dt_ref[0], c_scr[...])
    _copy(c_scr, ws_out.at[k, j], sem)


def tsqrt_wavefront_kernel(kk_ref, ii_ref, ws_in, tt_in, ttaus_in,
                           ws_out, tt_out, ttaus_out, diag_scr, sub_scr,
                           taus_scr, sem):
    """One TSQRT task per grid cell: stacked QR of tiles (k,k) / (i,k)."""
    del ws_in, tt_in, ttaus_in
    g = pl.program_id(0)
    k = kk_ref[g]
    i = ii_ref[g]
    _copy(ws_out.at[k, k], diag_scr, sem)
    _copy(ws_out.at[i, k], sub_scr, sem)
    merged, v2, t, taus = tsqrt_body(diag_scr[...], sub_scr[...])
    tt_out[0, 0] = t
    diag_scr[...] = merged
    sub_scr[...] = v2
    taus_scr[...] = taus
    _copy(diag_scr, ws_out.at[k, k], sem)
    _copy(sub_scr, ws_out.at[i, k], sem)
    _copy(taus_scr, ttaus_out.at[i, k], sem)


def ssrfb_wavefront_kernel(kk_ref, ii_ref, jj_ref, ws_in, tt_ref,
                           ws_out, v_scr, ck_scr, ci_scr, sem):
    """One SSRFB task per grid cell: tile pair (k,j) / (i,j) update."""
    del ws_in
    g = pl.program_id(0)
    k = kk_ref[g]
    i = ii_ref[g]
    j = jj_ref[g]
    _copy(ws_out.at[i, k], v_scr, sem)
    _copy(ws_out.at[k, j], ck_scr, sem)
    _copy(ws_out.at[i, j], ci_scr, sem)
    ck, ci = ssrfb_body(v_scr[...], tt_ref[0, 0], ck_scr[...], ci_scr[...])
    ck_scr[...] = ck
    ci_scr[...] = ci
    _copy(ck_scr, ws_out.at[k, j], sem)
    _copy(ci_scr, ws_out.at[i, j], sem)


# ---------------------------------------------------------------------------
# registry + VMEM accounting
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MacroOp:
    """Capability card for one DAG macro op.

    body:        value-level fused realization (kernel body == jnp oracle)
    kernel:      the engine's wavefront Pallas body (uniform signature)
    tile_reads:  workspace tiles read per task  (HBM traffic model)
    tile_writes: workspace tiles written per task
    vmem_tiles:  nb x nb VMEM-resident tiles per task (working-set bound)
    """

    name: str
    body: Callable
    kernel: Callable
    tile_reads: int
    tile_writes: int
    vmem_tiles: int


MACRO_OPS: Dict[str, MacroOp] = {
    "GEQRT": MacroOp("GEQRT", geqrt_body, geqrt_wavefront_kernel,
                     tile_reads=1, tile_writes=1, vmem_tiles=4),
    "LARFB": MacroOp("LARFB", larfb_body, larfb_wavefront_kernel,
                     tile_reads=2, tile_writes=1, vmem_tiles=5),
    "TSQRT": MacroOp("TSQRT", tsqrt_body, tsqrt_wavefront_kernel,
                     tile_reads=2, tile_writes=2, vmem_tiles=6),
    "SSRFB": MacroOp("SSRFB", ssrfb_body, ssrfb_wavefront_kernel,
                     tile_reads=3, tile_writes=2, vmem_tiles=7),
}


def vmem_bytes(kind: str, nb: int, itemsize: int = 4) -> int:
    """Per-task VMEM working set of one macro op at tile size nb."""
    return MACRO_OPS[kind].vmem_tiles * nb * nb * itemsize


def engine_vmem_bytes(nb: int, itemsize: int = 4) -> int:
    """Worst-case per-task working set across all engine macro ops."""
    return max(vmem_bytes(k, nb, itemsize) for k in MACRO_OPS)


# The megakernel dispatch mode holds, per grid step: two phases of the
# worst-case operand set (3 tiles + 1 block reflector, double-buffered
# so task t+1's fetch overlaps task t's compute), the write-back staging
# tiles, and the worst-case body temporaries (SSRFB's 4-product chain).
MEGAKERNEL_VMEM_TILES = 2 * (3 + 1) + 3 + 4


def megakernel_vmem_bytes(nb: int, itemsize: int = 4) -> int:
    """Resident working set of the engine's single-dispatch megakernel
    lowering at tile size nb (double-buffered operands + staging)."""
    return MEGAKERNEL_VMEM_TILES * nb * nb * itemsize


def batched_megakernel_vmem_bytes(nb: int, itemsize: int = 4,
                                  batch: int = 1) -> int:
    """Resident working set of the *batched* megakernel
    (``engine.factor_tiles_batched``): the batch is an outer sequential
    grid axis replaying one shared task table, so the per-step set —
    double-buffered operands + staging — does not grow with ``batch``.
    The explicit ``batch`` parameter keeps the serving layer's VMEM
    gating honest about that invariance instead of assuming it."""
    del batch  # batch-invariant by construction (outer grid axis)
    return megakernel_vmem_bytes(nb, itemsize)


_POLICY = register_kernel_policy(KernelPolicy(
    name="macro_ops",
    vmem_bytes=lambda nb, _b=0: engine_vmem_bytes(nb),
    vmem_budget=DEFAULT_VMEM_BUDGET,
    default_interpret=default_interpret,
    table_budget=DEFAULT_TABLE_BUDGET,
))
