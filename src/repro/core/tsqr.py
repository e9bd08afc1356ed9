"""TSQR / CAQR — communication-avoiding distributed QR over a mesh axis.

Paper §5.2 realizes parallel QR by tiling PEs on the REDEFINE NoC with
PLASMA-style block partitioning.  The TPU-native analogue is TSQR
(tall-skinny QR): row-block-local MHT factorizations reduced through a
binary tree of small stacked-R factorizations, exchanging only n x n
triangles over ICI instead of matrix panels.

Three layers:
  * :func:`tsqr_r` / :func:`tsqr_qr` — single-device reference (the oracle
    for the sharded paths; also used for local block counts > 1).
  * :func:`tsqr_tree_sharded` — inside ``shard_map``: log2(P) rounds of
    ``lax.ppermute`` butterfly exchange; every shard finishes with the
    same global R.
  * :func:`distributed_qr` — thin-Q/R of a row-sharded matrix: TSQR for R,
    ``Q = A R^{-1}`` locally (optionally CQR2-refined).

All in fp32: these feed the QR-Muon optimizer, which orthogonalizes
fp32 momentum.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.scipy.linalg import solve_triangular

from repro.core.blocked import geqrf
from repro.core.householder import unpack_r

Array = jax.Array

__all__ = [
    "tsqr_r",
    "tsqr_qr",
    "tsqr_tree_sharded",
    "butterfly_merge_r",
    "distributed_qr",
    "triangular_inverse_apply",
    "default_nblocks",
]


def _local_r(block: Array, *, qr_block: int = 32, use_kernel: bool = False) -> Array:
    """R factor (n x n) of one (mb x n) block via blocked MHT QR."""
    n = block.shape[1]
    packed, _ = geqrf(block, block=min(qr_block, n), panel_method="mht",
                      use_kernel=use_kernel)
    return unpack_r(packed)[:n, :n]


def tsqr_r(a: Array, *, nblocks: int = 4, qr_block: int = 32,
           use_kernel: bool = False) -> Array:
    """R factor of tall-skinny ``a`` (m x n, m >= n*nblocks) via a local
    TSQR reduction tree.  Single-device reference implementation."""
    m, n = a.shape
    if m % nblocks != 0:
        raise ValueError(f"m={m} not divisible by nblocks={nblocks}")
    blocks = a.reshape(nblocks, m // nblocks, n)
    rs = jax.vmap(lambda b: _local_r(b, qr_block=qr_block, use_kernel=use_kernel))(blocks)

    p = nblocks
    while p > 1:
        if p % 2 == 1:
            # Carry the odd block up one level untouched.
            carry, rs = rs[-1:], rs[:-1]
            p -= 1
        else:
            carry = None
        stacked = jnp.concatenate([rs[0::2], rs[1::2]], axis=1)  # (p/2, 2n, n)
        rs = jax.vmap(lambda b: _local_r(b, qr_block=qr_block,
                                         use_kernel=use_kernel))(stacked)
        if carry is not None:
            rs = jnp.concatenate([rs, carry], axis=0)
        p = rs.shape[0]
    return rs[0]


def triangular_inverse_apply(a: Array, r: Array, *, rcond: float = 1e-7) -> Array:
    """Compute ``a @ r^{-1}`` by triangular solve, with a sign-preserving
    diagonal clamp for near-singular R (rank-deficient momentum)."""
    d = jnp.diagonal(r)
    dmax = jnp.maximum(jnp.max(jnp.abs(d)), 1e-30)
    clamp = jnp.where(jnp.abs(d) < rcond * dmax,
                      jnp.where(d >= 0, rcond * dmax, -rcond * dmax), d)
    r_safe = r + jnp.diag(clamp - d)
    # a r^{-1}  <=>  solve r^T x^T = a^T with lower-triangular r^T
    return solve_triangular(r_safe.T, a.T, lower=True).T


def tsqr_qr(a: Array, *, nblocks: int = 4, refine: bool = True,
            qr_block: int = 32, use_kernel: bool = False
            ) -> Tuple[Array, Array]:
    """Thin QR of tall-skinny ``a`` via TSQR-R + ``Q = A R^{-1}``.

    ``refine=True`` runs a second pass (CQR2-style) restoring orthogonality
    to ~machine eps even for moderately ill-conditioned inputs."""
    r1 = tsqr_r(a, nblocks=nblocks, qr_block=qr_block, use_kernel=use_kernel)
    q = triangular_inverse_apply(a, r1)
    if refine:
        r2 = tsqr_r(q, nblocks=nblocks, qr_block=qr_block,
                    use_kernel=use_kernel)
        q = triangular_inverse_apply(q, r2)
        return q, r2 @ r1
    return q, r1


# ---------------------------------------------------------------------------
# shard_map collective versions
# ---------------------------------------------------------------------------

def butterfly_merge_r(r: Array, axis_name: str, combine) -> Array:
    """Merge per-shard (n x n) R factors into the global R, from inside
    ``shard_map`` — the TSQR combine tree, factored out so other sharded
    backends (the ``sharded_tiled`` task-graph runtime) reuse it.

    Butterfly tree: at round r every shard exchanges its current (n x n) R
    with the partner ``rank XOR 2^r`` (``lax.ppermute``), stacks the pair
    and re-factors via ``combine((2n x n) stack) -> (n x n) R``.  After
    log2(P) rounds all shards hold the identical global R — no broadcast
    needed.  Per-round traffic is one n x n triangle per link, vs. P
    triangles for an all-gather TSQR.

    Requires the mesh axis size to be a power of two (all production
    meshes here are 16/32-way; the sharded-tiled planner rounds its
    domain count down to a power of two).
    """
    p = lax.axis_size(axis_name)
    if p & (p - 1):
        raise ValueError(f"butterfly_merge_r needs power-of-two axis, got {p}")
    rounds = p.bit_length() - 1
    for level in range(rounds):
        stride = 1 << level
        perm = [(i, i ^ stride) for i in range(p)]
        r_partner = lax.ppermute(r, axis_name, perm)
        # Deterministic stacking order (lower rank's R on top) so every
        # shard computes bitwise-identical results.
        idx = lax.axis_index(axis_name)
        first = jnp.where((idx & stride) == 0, 1, 0)
        top = jnp.where(first, r, r_partner)
        bot = jnp.where(first, r_partner, r)
        r = combine(jnp.concatenate([top, bot], axis=0))
    # Every shard now holds the identical global R, but the type system
    # cannot infer that; a pmax over bitwise-identical values is an exact
    # no-op that makes the replication provable (n^2 bytes, negligible).
    return lax.pmax(r, axis_name)


def tsqr_tree_sharded(a_local: Array, axis_name: str, *, qr_block: int = 32,
                      use_kernel: bool = False) -> Array:
    """Global R of a row-sharded tall matrix, from inside ``shard_map``.

    Local blocked-MHT R per shard, then the :func:`butterfly_merge_r`
    combine tree; every shard finishes with the identical global R.
    """
    r = _local_r(a_local, qr_block=qr_block, use_kernel=use_kernel)
    return butterfly_merge_r(
        r, axis_name,
        lambda stack: _local_r(stack, qr_block=qr_block,
                               use_kernel=use_kernel))


def distributed_qr(a_local: Array, axis_name: str, *, refine: bool = True,
                   qr_block: int = 32, use_kernel: bool = False
                   ) -> Tuple[Array, Array]:
    """Thin QR of a row-sharded matrix from inside ``shard_map``.

    Returns ``(q_local, r)``: the caller's row-shard of the thin Q, and the
    (replicated) global R.  This is the distributed orthogonalization
    primitive behind the QR-Muon optimizer: momentum is FSDP-sharded on
    the ``data`` axis, so Q never materializes unsharded anywhere.
    """
    r1 = tsqr_tree_sharded(a_local, axis_name, qr_block=qr_block,
                           use_kernel=use_kernel)
    q_local = triangular_inverse_apply(a_local, r1)
    if refine:
        r2 = tsqr_tree_sharded(q_local, axis_name, qr_block=qr_block,
                               use_kernel=use_kernel)
        q_local = triangular_inverse_apply(q_local, r2)
        return q_local, r2 @ r1
    return q_local, r1


# -- registry -----------------------------------------------------------------
from repro.core.plan import (  # noqa: E402
    MethodSpec, QRConfig, register_method, sign_fix_qr, sign_fix_r)


def default_nblocks(m: int, n: int) -> int:
    """Largest divisor of m in [2, 8] scaled by aspect (legacy heuristic:
    deep enough trees for tall inputs, always an exact row partition)."""
    nb = max(2, min(8, m // max(n, 1)))
    while m % nb != 0:
        nb -= 1
    return max(nb, 1)


def _resolve_tsqr(m: int, n: int, cfg: QRConfig, *, dtype=None,
                  explain=None, backend=None) -> QRConfig:
    del dtype, backend  # tree shape is element-width independent
    nb = cfg.nblocks if cfg.nblocks is not None else default_nblocks(m, n)
    if m % nb != 0:
        raise ValueError(f"m={m} not divisible by nblocks={nb}")
    if explain is not None and cfg.nblocks is None:
        from repro.core.plan import RouteDecision

        explain.append(RouteDecision(
            "tsqr_nblocks", "resolved",
            f"nblocks={nb} (largest divisor of m={m} in [2, 8] scaled "
            f"by aspect) — merge tree depth {(nb - 1).bit_length()}"))
    return cfg.replace(nblocks=nb)


def _solve_tsqr(a: Array, cfg: QRConfig):
    from repro.observability import metrics as _obs_metrics

    _obs_metrics.counter("tsqr.solves", nblocks=cfg.nblocks,
                         mode=cfg.mode).inc()
    _obs_metrics.gauge("tsqr.tree_depth", nblocks=cfg.nblocks).set(
        (cfg.nblocks - 1).bit_length())
    qr_block = min(cfg.block, a.shape[1])
    if cfg.mode == "r":
        r = tsqr_r(a, nblocks=cfg.nblocks, qr_block=qr_block,
                   use_kernel=bool(cfg.use_kernel))
        return sign_fix_r(r) if cfg.sign_fix else r
    q, r = tsqr_qr(a, nblocks=cfg.nblocks, refine=cfg.refine, qr_block=qr_block,
                   use_kernel=bool(cfg.use_kernel))
    return sign_fix_qr(q, r) if cfg.sign_fix else (q, r)


def _vmem_tsqr(m: int, n: int, cfg: QRConfig) -> int:
    """Leaf working set: one (m/nblocks, min(block, n)) panel in VMEM."""
    from repro.kernels import ops

    nb = cfg.nblocks if cfg.nblocks is not None else default_nblocks(m, n)
    return ops.vmem_bytes_mht_panel(m // nb, min(cfg.block, n))


register_method(MethodSpec(
    name="tsqr",
    solve=_solve_tsqr,
    resolve=_resolve_tsqr,
    supports_full_q=False,
    min_aspect=4.0,
    kernel_backed=True,
    vmem_bytes=_vmem_tsqr,
    description="tall-skinny tree QR (single device; sharded via shard_map)",
))
