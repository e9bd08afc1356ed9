"""Multi-device sharded tiled QR — wavefront domains over a device mesh.

The paper's thesis is that QR speed comes from exposing more parallel
macro operations per DAG level (§4-§5).  :mod:`repro.core.tilegraph`
realizes that on one device: the tile DAG is levelized statically and
executed by the wavefront macro-op engine (:mod:`repro.core.engine` —
one in-place Pallas dispatch per level on the kernel path, the vmapped
jnp oracle otherwise).  This module is the next rung — the hierarchical / distributed tiled QR of Dongarra et
al. (arXiv:1110.1553) on top of the PLASMA tiled algorithm (Buttari et
al., arXiv:0707.3548) — mapped onto a JAX device mesh:

  1. **Domain partition**: the p x q tile grid splits into ``d``
     contiguous row-block *domains*, one per device
     (:func:`repro.core.tilegraph.domain_rows`; rows are zero-padded so
     every device owns ``ceil(p/d)`` tile rows — padded rows yield
     exact-zero reflectors, so the unpadded slices are untouched).
  2. **Domain-local wavefronts**: inside ``shard_map`` each device runs
     the ordinary GEQRT/TSQRT/LARFB/SSRFB wavefront schedule on its own
     (p/d x q) sub-grid through the same :func:`repro.core.engine.
     factor_tiles` loop as the single-device backend — zero cross-device
     traffic during the sweep, one execution path for both backends
     (``dispatch_mode`` selects the kernel lowering per domain sweep:
     per-level wavefront dispatches or the single-call megakernel).
  3. **Hierarchical R merge**: the per-domain R factors reduce through
     the TSQR butterfly tree (:func:`repro.core.tsqr.butterfly_merge_r`),
     exchanging one n x n triangle per link per round; after
     ``log2(d)`` rounds every device holds the identical global R.
  4. **Thin Q** (mode="reduced"): ``Q = A R^{-1}`` domain-locally
     (:func:`repro.core.tsqr.triangular_inverse_apply`), with a CQR2
     refinement pass (a second local-R + merge round) restoring
     orthogonality to ~machine eps; Q never materializes unsharded.

Cross-device critical path: ``wavefront_count(p/d, q) + ceil(log2 d)``
wavefronts — O(p/d + 2q + log d) instead of the single-device
O(p + 2q) (:func:`repro.core.tilegraph.sharded_wavefront_count`), which
is what lets the repo's largest-matrix path scale with device count.

Degeneracies (tested in tests/test_distgraph.py):
  * ``d == 1`` (one device, or ``ndomains=1``) skips shard_map entirely
    and returns the single-device tiled backend's result bit-for-bit.
  * tile grids with fewer row-tiles than devices cap ``d`` at the
    row-tile count; non-power-of-two requests round down (the butterfly
    needs 2^k participants).
  * ``p`` not divisible by ``d`` zero-pads rows up to ``d * ceil(p/d)``.
  * wide matrices (m < n) fall back to the single-device tiled path —
    row-sharding only helps when there are rows to spare.

CPU testing recipe (no accelerator needed — see the CI multi-device job):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        JAX_PLATFORMS=cpu python -m pytest tests/test_distgraph.py
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.tilegraph import tile_grid, tiled_qr
from repro.core.tsqr import butterfly_merge_r, triangular_inverse_apply
from repro.distributed.sharding import (
    QR_DOMAIN_AXIS, largest_pow2, row_domain_mesh, row_domain_specs)

Array = jax.Array

__all__ = [
    "effective_domains",
    "sharded_tiled_qr",
]


def effective_domains(m: int, n: int, tile: int,
                      requested: Optional[int] = None,
                      device_count: Optional[int] = None) -> int:
    """The domain count the executor will actually use.

    Caps the request (default: every local device) at the available
    device count and the tile-row count, rounds down to a power of two
    (butterfly merge), and degenerates to 1 for wide matrices.
    """
    if m < n:
        return 1
    p, _ = tile_grid(m, n, tile)
    avail = jax.local_device_count() if device_count is None else device_count
    d = avail if requested is None else min(requested, avail)
    return largest_pow2(max(1, min(d, p)))


def _pad_rows(x: Array, rows: int) -> Array:
    return x if x.shape[0] == rows else jnp.pad(
        x, ((0, rows - x.shape[0]), (0, 0)))


def _domain_r(a_dom: Array, tile: int, use_kernel: bool,
              dispatch_mode) -> Array:
    """Domain-local R via the tiled wavefront schedule, padded to n x n
    (domains shorter than n contribute zero rows to the merge stack)."""
    n = a_dom.shape[1]
    return _pad_rows(tiled_qr(a_dom, tile=tile, mode="r",
                              use_kernel=use_kernel,
                              dispatch_mode=dispatch_mode), n)


def _merged_r(a_dom: Array, tile: int, use_kernel: bool,
              dispatch_mode) -> Array:
    """Global R from inside shard_map: local tiled wavefronts, then the
    TSQR butterfly over n x n triangles (combine = stacked blocked QR,
    the same tree :func:`repro.core.tsqr.tsqr_tree_sharded` runs)."""
    from repro.core.tsqr import _local_r  # combine logic, shared with TSQR

    n = a_dom.shape[1]
    r = _domain_r(a_dom, tile, use_kernel, dispatch_mode)
    return butterfly_merge_r(
        r, QR_DOMAIN_AXIS,
        lambda stack: _local_r(stack, qr_block=min(32, n)))


def _sharded_body(a_dom: Array, *, tile: int, mode: str, use_kernel: bool,
                  refine: bool, dispatch_mode):
    """Per-device program: local wavefronts -> R merge (-> thin Q)."""
    r1 = _merged_r(a_dom, tile, use_kernel, dispatch_mode)
    if mode == "r":
        return r1
    q_dom = triangular_inverse_apply(a_dom, r1)
    if refine:
        r2 = _merged_r(q_dom, tile, use_kernel, dispatch_mode)
        q_dom = triangular_inverse_apply(q_dom, r2)
        return q_dom, r2 @ r1
    return q_dom, r1


@functools.lru_cache(maxsize=None)
def _sharded_fn(d: int, tile: int, mode: str, use_kernel: bool, refine: bool,
                dispatch_mode):
    """Compiled shard_map program for one (domain count, tile, mode)."""
    mesh = row_domain_mesh(d)
    in_spec, r_spec, qr_specs = row_domain_specs()
    body = functools.partial(_sharded_body, tile=tile, mode=mode,
                             use_kernel=use_kernel, refine=refine,
                             dispatch_mode=dispatch_mode)
    out_specs = r_spec if mode == "r" else qr_specs
    # pallas_call has no replication rule: the kernel path must skip the
    # check (outputs are still replicated — the merge ends in a pmax).
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_spec,
                                 out_specs=out_specs,
                                 check_vma=not use_kernel))


def sharded_tiled_qr(a: Array, *, tile: int = 32, mode: str = "reduced",
                     use_kernel: bool = False, ndomains: Optional[int] = None,
                     refine: bool = True,
                     dispatch_mode: Optional[str] = None):
    """QR of ``a`` via per-device tiled wavefront domains + R merge tree.

    mode: "reduced" -> (Q m x k, R k x n) with k = min(m, n); "r" -> R.
    Full Q is not supported, and with more than one domain the thin Q is
    always solve-based (CQR2-refined ``A R^{-1}``, like TSQR) — the
    merge tree never materializes the domain-crossing reflectors, so
    there is no formq realization; use ``method="tiled"`` when exact
    reflector-accumulated Q of singular input matters.

    ``ndomains=None`` uses every local device; the effective count is
    :func:`effective_domains` (capped, power-of-two, 1 for wide input).
    With one effective domain this IS ``tiled_qr`` — same program, same
    bits.  ``refine`` runs the CQR2 second pass on the thin Q (two merge
    trees total) — keep it on; it is what holds Q orthogonality at
    ~machine eps independent of the domain count.  ``dispatch_mode``
    picks the engine lowering of each domain-local sweep on the kernel
    path ("wavefront" / "megakernel" / None = the engine's auto rule on
    the per-domain grid).
    """
    if mode not in ("reduced", "r"):
        raise ValueError(
            f"sharded_tiled supports modes 'reduced'/'r', got {mode!r}")
    m, n = a.shape
    d = effective_domains(m, n, tile, ndomains)
    if d == 1:
        return tiled_qr(a, tile=tile, mode=mode, use_kernel=use_kernel,
                        dispatch_mode=dispatch_mode)

    # Equalize domains: pad tile rows up to d * ceil(p / d).
    p, _ = tile_grid(m, n, tile)
    p_dom = -(-p // d)
    m_pad = d * p_dom * tile

    from repro.core.tilegraph import merge_levels
    from repro.observability import metrics as _obs_metrics
    from repro.observability import trace as _obs_trace

    _obs_metrics.counter("distributed.solves", domains=d, mode=mode).inc()
    _obs_metrics.counter("distributed.merge_rounds",
                         domains=d).inc(merge_levels(d) * (2 if (
                             mode != "r" and refine) else 1))
    _obs_metrics.gauge("distributed.domain_tile_rows",
                       domains=d).set(p_dom)

    a_pad = _pad_rows(a, m_pad)
    fn = _sharded_fn(d, tile, mode, bool(use_kernel), bool(refine),
                     dispatch_mode)
    k = min(m, n)
    with _obs_trace.span("distgraph.sharded_tiled_qr", domains=d,
                         shape=f"{m}x{n}", tile=tile,
                         merge_levels=merge_levels(d)):
        if mode == "r":
            return fn(a_pad)[:k, :n]
        q, r = fn(a_pad)
        return q[:m, :k], r[:k, :n]


# -- registry -----------------------------------------------------------------
from repro.core.plan import (  # noqa: E402
    MethodSpec, QRConfig, register_method, sign_fix_qr, sign_fix_r)
from repro.core.tilegraph import (  # noqa: E402
    _kernel_tile, _solve_tiled, _vmem_tiled)

# Keep each domain's symbolic task DAG within the single-device budget:
# grow the tile size until the per-domain grid is at most this many tiles
# on its long side (task count is O(p q min(p,q)) per domain).
_MAX_DOMAIN_GRID = 64


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _resolve_sharded(m: int, n: int, cfg: QRConfig, *, dtype=None,
                     explain=None, backend=None) -> QRConfig:
    from repro.core.plan import RouteDecision
    from repro.observability import metrics as _metrics

    d = effective_domains(m, n, cfg.block, cfg.ndomains)
    tile = min(cfg.block, m, n)

    # Silent-degradation sites: the executor runs fewer domains than the
    # request (or the device count) implies — surface the concrete cause.
    avail = jax.local_device_count()
    wanted = avail if cfg.ndomains is None else min(cfg.ndomains, avail)
    if d == 1 and wanted > 1:
        _metrics.counter("planner.fallbacks",
                         reason="sharded_degraded_to_tiled").inc()
        if explain is not None:
            explain.append(RouteDecision(
                "sharded_degraded_to_tiled", "fallback",
                f"wide matrix m={m} < n={n} shards to 1 domain"
                if m < n else
                f"{wanted} domains requested but the {m}x{n} grid at "
                f"tile {cfg.block} supports 1 — running the "
                f"single-device tiled path bit-for-bit"))
    elif d < wanted:
        _metrics.counter("planner.fallbacks",
                         reason="sharded_domains_capped").inc()
        if explain is not None:
            explain.append(RouteDecision(
                "sharded_domains_capped", "fallback",
                f"{wanted} domains requested, running {d} (capped at "
                f"the tile-row count and rounded down to a power of "
                f"two for the butterfly merge)"))

    def domain_rows_of(t: int) -> int:
        return _ceil_div(_ceil_div(m, t), d)  # ceil(p / d) tile rows/device

    def domain_grid_side(t: int) -> int:
        return max(domain_rows_of(t), _ceil_div(n, t))

    tile = _kernel_tile(tile, cfg, backend, explain)
    start = tile
    while domain_grid_side(tile) > _MAX_DOMAIN_GRID and tile < min(m, n):
        tile = _kernel_tile(min(2 * tile, m, n), cfg, backend, None)
    if explain is not None and tile != start:
        explain.append(RouteDecision(
            "sharded_tile_grown", "resolved",
            f"tile grown {start} -> {tile} to keep each domain's "
            f"grid side <= {_MAX_DOMAIN_GRID} (task count is "
            f"O(p q min(p, q)) per domain)"))
    if cfg.dispatch_mode is None and cfg.use_kernel:
        # The engine lowering each domain-local sweep will run: resolve
        # the auto rule on the per-domain tile grid, not the global one,
        # at the planned element width.
        from repro.core.tilegraph import (_planned_itemsize,
                                          _resolve_dispatch_explained)

        cfg = cfg.replace(dispatch_mode=_resolve_dispatch_explained(
            domain_rows_of(tile), _ceil_div(n, tile), tile,
            _planned_itemsize(cfg, dtype), explain))
    if d > 1:
        # Across domains the thin Q is always solve-based (CQR2-refined
        # A R^{-1}, like TSQR) — the merge tree never materializes the
        # domain-crossing reflectors, so there is no formq realization.
        # Recording it keeps the resolved config truthful; with d == 1
        # the tiled path runs and honors q_method as planned.
        return cfg.replace(block=tile, ndomains=d, q_method="solve")
    return cfg.replace(block=tile, ndomains=d)


def _solve_sharded(a: Array, cfg: QRConfig):
    m, n = a.shape
    d = effective_domains(m, n, cfg.block, cfg.ndomains)
    if d == 1:
        # Bit-for-bit the single-device tiled backend (same solve hook).
        return _solve_tiled(a, cfg)
    if cfg.mode == "r":
        r = sharded_tiled_qr(a, tile=cfg.block, mode="r",
                             use_kernel=bool(cfg.use_kernel), ndomains=d,
                             dispatch_mode=cfg.dispatch_mode)
        return sign_fix_r(r) if cfg.sign_fix else r
    q, r = sharded_tiled_qr(a, tile=cfg.block, mode="reduced",
                            use_kernel=bool(cfg.use_kernel), ndomains=d,
                            refine=cfg.refine,
                            dispatch_mode=cfg.dispatch_mode)
    return sign_fix_qr(q, r) if cfg.sign_fix else (q, r)


register_method(MethodSpec(
    name="sharded_tiled",
    solve=_solve_sharded,
    resolve=_resolve_sharded,
    supports_full_q=False,
    batched=False,  # shard_map under vmap is not part of the contract
    kernel_backed=True,
    # Per-device working set is one domain's engine dispatch — sharding
    # divides the grid, not the tiles, so the tiled (macro-op engine)
    # estimator is the sharded estimator.
    vmem_bytes=_vmem_tiled,
    kernel_policy="macro_ops",
    description="multi-device tiled QR: per-device row-block wavefront "
                "domains (shard_map) + TSQR-style hierarchical R merge",
))
