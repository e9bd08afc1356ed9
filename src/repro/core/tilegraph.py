"""Tiled QR task-graph runtime — tile kernels + static wavefront scheduler.

The paper's thesis is that QR speed comes from (1) exposing more parallel
operations per DAG level and (2) realizing each DAG node as a fused macro
operation on specialized hardware (§4-§5).  The unblocked and blocked
realizations in this package still serialize across panels: panel k+1
cannot start until the full trailing update of panel k finished.  Tiled
QR (Buttari et al., PLASMA) removes that barrier by decomposing the
factorization into a DAG of *tile tasks* over an (p x q) grid of nb x nb
tiles:

    GEQRT(k)      QR of diagonal tile (k,k)          -> V1, R, T
    LARFB(k,j)    apply Q_k^T to tile (k,j), j > k   (WY trailing update)
    TSQRT(i,k)    QR of the stacked pair [R_kk; A_ik] (triangle on top)
    SSRFB(k,i,j)  apply the TSQRT reflectors to the tile pair
                  [A_kj; A_ij], j > k

Tasks from *different* panels run concurrently whenever their tile
dependencies allow — exactly the "more macro operations per DAG level"
structure that :mod:`repro.core.dag` quantifies for HT vs MHT
(:func:`repro.core.dag.analyze_tiled` extends the beta/theta metric to
this DAG).

Execution model: the DAG is levelized *statically* (every task's
wavefront = 1 + max over its dependencies) and handed to the wavefront
macro-op engine (:mod:`repro.core.engine`), which lowers each level's
same-kind task batch to a **single in-place Pallas dispatch** over a
``(p, q, nb, nb)`` tile workspace (``use_kernel=True``) or to the
bitwise-identical vmapped jnp oracle (``use_kernel=False``).  Shapes are
static per wavefront, so the whole factorization traces into one
jittable program — no runtime scheduler, the schedule IS the program.

Tile kernels: all four macro ops (GEQRT / LARFB / TSQRT / SSRFB) live in
the unified :mod:`repro.kernels.macro_ops` library — one Householder /
WY core shared with the panel and trailing kernels — with
``interpret=True`` CPU fallback.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core.blocked import unpack_v_panel

Array = jax.Array

__all__ = [
    "TileTask",
    "TiledFactors",
    "build_tasks",
    "task_deps",
    "levelize",
    "wavefronts",
    "wavefront_count",
    "tile_grid",
    "tiled_qr",
    "tiled_qr_batched",
    "domain_rows",
    "domain_wavefronts",
    "merge_levels",
    "sharded_wavefront_count",
]


# ---------------------------------------------------------------------------
# symbolic tile-task DAG (no jax — pure graph arithmetic)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, order=True)
class TileTask:
    """One macro operation on the tile grid.

    kind: "GEQRT" | "LARFB" | "TSQRT" | "SSRFB"
    k:    panel step (0 <= k < min(p, q))
    i:    row-tile index (GEQRT/LARFB: i == k)
    j:    column-tile index (GEQRT/TSQRT: j == k)
    """

    kind: str
    k: int
    i: int
    j: int


def tile_grid(m: int, n: int, tile: int) -> Tuple[int, int]:
    """Tile-grid shape (p, q) covering an m x n matrix (ceil division)."""
    if tile < 1:
        raise ValueError(f"tile must be >= 1, got {tile}")
    return -(-m // tile), -(-n // tile)


def build_tasks(p: int, q: int) -> List[TileTask]:
    """All tile tasks of a p x q grid, in a valid topological order."""
    tasks: List[TileTask] = []
    for k in range(min(p, q)):
        tasks.append(TileTask("GEQRT", k, k, k))
        tasks.extend(TileTask("LARFB", k, k, j) for j in range(k + 1, q))
        for i in range(k + 1, p):
            tasks.append(TileTask("TSQRT", k, i, k))
            tasks.extend(TileTask("SSRFB", k, i, j) for j in range(k + 1, q))
    return tasks


def task_deps(t: TileTask) -> Tuple[TileTask, ...]:
    """Immediate dependencies of one task (the PLASMA flat-tree DAG).

    The chain structure: TSQRT(i,k) serializes in i (each updates R_kk),
    SSRFB(k,i,j) serializes in i (each updates the top tile A_kj), and
    every step-k task waits for the step-(k-1) update of its tiles.
    """
    k, i, j = t.k, t.i, t.j
    deps: List[TileTask] = []
    if t.kind == "GEQRT":
        if k > 0:
            deps.append(TileTask("SSRFB", k - 1, k, k))
    elif t.kind == "LARFB":
        deps.append(TileTask("GEQRT", k, k, k))
        if k > 0:
            deps.append(TileTask("SSRFB", k - 1, k, j))
    elif t.kind == "TSQRT":
        deps.append(TileTask("TSQRT", k, i - 1, k) if i > k + 1
                    else TileTask("GEQRT", k, k, k))
        if k > 0:
            deps.append(TileTask("SSRFB", k - 1, i, k))
    elif t.kind == "SSRFB":
        deps.append(TileTask("TSQRT", k, i, k))
        deps.append(TileTask("SSRFB", k, i - 1, j) if i > k + 1
                    else TileTask("LARFB", k, k, j))
        if k > 0:
            deps.append(TileTask("SSRFB", k - 1, i, j))
    else:
        raise ValueError(f"unknown task kind {t.kind!r}")
    return tuple(deps)


def levelize(p: int, q: int) -> Dict[TileTask, int]:
    """Wavefront index of every task: 1 + max over its dependencies."""
    levels: Dict[TileTask, int] = {}
    for t in build_tasks(p, q):
        deps = task_deps(t)
        levels[t] = 1 + max((levels[d] for d in deps), default=0)
    return levels


def wavefronts(p: int, q: int) -> List[List[TileTask]]:
    """Tasks grouped by wavefront (ascending), deterministic order within."""
    levels = levelize(p, q)
    out: List[List[TileTask]] = [[] for _ in range(max(levels.values(), default=0))]
    for t, lv in levels.items():
        out[lv - 1].append(t)
    for wf in out:
        wf.sort()
    return out


def wavefront_count(p: int, q: int) -> int:
    """Closed-form critical-path length of the p x q flat-tree tile DAG.

    Derivation from the recurrences in :func:`task_deps`:
      * q == 1: the TSQRT chain alone — p levels.
      * p >= q: GEQRT(k) fires at 3k+1, the last TSQRT of step k at
        (3k+1) + (p-1-k), giving p + 2q - 2 overall.
      * p <  q: the trailing LARFB of the last step adds one level on
        top of the square case 3p - 2, giving 3p - 1.
    Verified against :func:`levelize` in tests/test_tilegraph.py.
    """
    if p < 1 or q < 1:
        raise ValueError(f"grid must be at least 1x1, got {p}x{q}")
    return p + 2 * q - 2 if p >= q else 3 * p - 1


# ---------------------------------------------------------------------------
# domain-aware DAG metadata (multi-device sharded schedule, core.distgraph)
# ---------------------------------------------------------------------------
#
# The sharded runtime partitions the p x q tile grid into d contiguous
# row-block *domains*, one per device.  Each domain runs the ordinary
# flat-tree wavefront schedule on its own (p_i x q) sub-grid — fully
# independent of the other domains — and the per-domain R factors merge
# through a TSQR-style binary reduction tree (ceil(log2 d) rounds).  The
# cross-device critical path is therefore
#
#     wavefront_count(ceil(p / d), q) + ceil(log2 d)
#
# i.e. O(p/d + 2q + log d) wavefronts instead of the single-device
# O(p + 2q) — the DAG exposes d-way *domain* parallelism on top of the
# per-wavefront tile parallelism.

def domain_rows(p: int, d: int) -> Tuple[Tuple[int, int], ...]:
    """Contiguous per-domain tile-row ranges ``((start, stop), ...)``.

    Balanced split of p tile rows over d domains; when p is not divisible
    by d the first ``p % d`` domains carry one extra tile row (the
    executor instead zero-pads rows so every device gets ``ceil(p / d)``
    — padding rows factor to exact-zero reflectors, see
    :func:`tiled_qr`).  Requires ``1 <= d <= p``.
    """
    if d < 1 or d > p:
        raise ValueError(f"need 1 <= d <= p, got d={d}, p={p}")
    base, extra = divmod(p, d)
    out, start = [], 0
    for i in range(d):
        stop = start + base + (1 if i < extra else 0)
        out.append((start, stop))
        start = stop
    return tuple(out)


def domain_wavefronts(p: int, q: int, d: int) -> List[List[List[TileTask]]]:
    """Per-domain wavefront schedules: ``out[i]`` is the wavefront list of
    domain i's local (p_i x q) tile DAG (task indices are domain-local).
    Domains are mutually independent — level L of every domain runs
    concurrently across devices."""
    return [wavefronts(stop - start, q) if stop > start else []
            for start, stop in domain_rows(p, d)]


def merge_levels(d: int) -> int:
    """Depth of the binary R-merge reduction tree over d domains."""
    if d < 1:
        raise ValueError(f"need d >= 1, got {d}")
    return (d - 1).bit_length()


def sharded_wavefront_count(p: int, q: int, d: int) -> int:
    """Closed-form cross-device critical path of the d-domain schedule.

    The executor pads p up to ``d * ceil(p / d)`` tile rows so every
    domain has the same local grid; the critical path is the (tallest)
    local schedule plus the merge-tree rounds.  ``d=1`` degenerates to
    :func:`wavefront_count` exactly (no merge levels).
    """
    if d < 1:
        raise ValueError(f"need d >= 1, got {d}")
    if d == 1:
        return wavefront_count(p, q)
    p_dom = -(-p // d)
    return wavefront_count(p_dom, q) + merge_levels(d)


# ---------------------------------------------------------------------------
# wavefront execution (repro.core.engine + repro.kernels.macro_ops)
# ---------------------------------------------------------------------------

# The factored tile state is the engine's — re-exported under the
# historical name (same fields, same layout).
TiledFactors = engine.FactorState


def _split_tiles(a: Array, p: int, q: int, nb: int) -> Array:
    return a.reshape(p, nb, q, nb).transpose(0, 2, 1, 3)


def _join_tiles(tiles: Array) -> Array:
    p, q, nb, _ = tiles.shape
    return tiles.transpose(0, 2, 1, 3).reshape(p * nb, q * nb)


def _form_q_tiled(f: TiledFactors, ncols: int) -> Array:
    """Materialize Q columns by applying the task transforms in reverse.

    A = G_0 T_{0,1}..T_{0,p-1} G_1 T_{1,2}.. ... R, so Q E applies the
    per-step transforms right-to-left: TSQRT pairs top-down in reverse,
    then the GEQRT diagonal block.  All applications are (nb x ncols)
    row-block updates — plain jnp, the cost matches the factorization.
    """
    p, q, nb, _ = f.tiles.shape
    m_pad = p * nb
    e = jnp.eye(m_pad, ncols, dtype=f.tiles.dtype)

    for k in reversed(range(min(p, q))):
        for i in reversed(range(k + 1, p)):
            v2, t = f.tiles[i, k], f.t_t[i, k]
            ek, ei = e[k * nb:(k + 1) * nb], e[i * nb:(i + 1) * nb]
            w = t @ (ek + v2.T @ ei)          # non-transposed Q
            e = e.at[k * nb:(k + 1) * nb].set(ek - w)
            e = e.at[i * nb:(i + 1) * nb].set(ei - v2 @ w)
        v1 = unpack_v_panel(f.tiles[k, k], 0)
        ek = e[k * nb:(k + 1) * nb]
        e = e.at[k * nb:(k + 1) * nb].set(ek - v1 @ (f.d_t[k] @ (v1.T @ ek)))
    return e


@functools.partial(jax.jit, static_argnames=("tile", "mode", "use_kernel",
                                             "dispatch_mode"))
def tiled_qr(a: Array, *, tile: int = 32, mode: str = "reduced",
             use_kernel: bool = False, dispatch_mode: str = None):
    """QR of ``a`` via the tiled task-graph runtime.

    ``use_kernel=True`` executes the schedule through the macro-op
    engine's Pallas lowering (:func:`repro.core.engine.factor_tiles`;
    interpret mode off-TPU) selected by ``dispatch_mode`` — per-level
    ``"wavefront"`` dispatches, the single-call ``"megakernel"``, or
    ``None`` for the engine's budget-driven auto rule; ``use_kernel=
    False`` runs the bitwise-identical pure-jnp oracle lowering of the
    same schedule.

    Non-multiple-of-tile shapes are zero-padded: padded rows/columns
    yield exactly-zero reflector entries (degenerate ``tau = 0`` columns),
    so the unpadded Q/R slices are the factorization of ``a`` itself.

    mode: "reduced" -> (Q m x k, R k x n); "r" -> R; "full" -> (Q m x m,
    R m x n), with k = min(m, n).

    Cost note: the symbolic DAG holds O(p q min(p, q)) tasks for a p x q
    tile grid — scale ``tile`` with the matrix so the grid stays modest
    (the "auto" planner caps dims at 2048 for the default tile).
    """
    m, n = a.shape
    if m == 0 or n == 0:
        raise ValueError(
            f"tiled_qr needs a nonempty matrix, got {a.shape}; zero-dim "
            "inputs route to the planner's 'degenerate' method "
            "(jnp.linalg.qr semantics)")
    p, q = tile_grid(m, n, tile)
    nb = tile
    pad = ((0, p * nb - m), (0, q * nb - n))
    a_pad = jnp.pad(a, pad) if (pad[0][1] or pad[1][1]) else a

    f = engine.factor_tiles(_split_tiles(a_pad, p, q, nb),
                            p=p, q=q, nb=nb, use_kernel=use_kernel,
                            dispatch_mode=dispatch_mode)
    k = min(m, n)
    r_full = jnp.triu(_join_tiles(f.tiles))
    if mode == "r":
        return r_full[:k, :n]
    if mode == "reduced":
        q_mat = _form_q_tiled(f, ncols=min(p * nb, q * nb))[:m, :k]
        return q_mat, r_full[:k, :n]
    if mode == "full":
        q_mat = _form_q_tiled(f, ncols=p * nb)[:m, :m]
        return q_mat, r_full[:m, :n]
    raise ValueError(f"unknown mode {mode!r}")


def _factor_stack_padded(a_pad: Array, *, p: int, q: int, nb: int,
                         mode: str, use_kernel: bool = False,
                         dispatch_mode: str = None, interpret: bool = None):
    """Factor a tile-aligned ``(B, p*nb, q*nb)`` stack through ONE
    batched engine dispatch, returning FULL padded factors —
    ``(r_full,)`` for mode="r", ``(q_full, r_full)`` otherwise (both
    batch-leading, grid-extent shapes).  Keeping outputs full-extent lets
    callers that donate the input stack (the serving bucket executables)
    alias it into an output buffer; the unpadding slice lives in the
    wrappers instead.

    The stack shares one task table: on the megakernel path the whole
    batch is a single ``pallas_call`` with a batch axis on the grid;
    other modes vmap the per-slice program.  Bitwise-equal per slice to
    independent :func:`tiled_qr` runs (the ``B == 1`` Q formation skips
    vmap — batch-1 vmapped ``dot_general`` is not bitwise-stable)."""
    b = a_pad.shape[0]
    tiles = jax.vmap(lambda x: _split_tiles(x, p, q, nb))(a_pad)
    f = engine.factor_tiles_batched(tiles, p=p, q=q, nb=nb,
                                    use_kernel=use_kernel,
                                    interpret=interpret,
                                    dispatch_mode=dispatch_mode)
    r_full = jax.vmap(lambda t: jnp.triu(_join_tiles(t)))(f.tiles)
    if mode == "r":
        return (r_full,)
    if mode not in ("reduced", "full"):
        raise ValueError(f"unknown mode {mode!r}")
    ncols = min(p * nb, q * nb) if mode == "reduced" else p * nb
    form = lambda *fs: _form_q_tiled(  # noqa: E731
        engine.FactorState(*fs), ncols=ncols)
    q_mat = (form(*(x[0] for x in f))[None] if b == 1
             else jax.vmap(form)(*f))
    return (q_mat, r_full)


def _tiled_qr_batched_impl(a: Array, *, tile: int = 32,
                           mode: str = "reduced", use_kernel: bool = False,
                           dispatch_mode: str = None,
                           interpret: bool = None):
    """QR of a ``(B, m, n)`` stack through ONE batched engine dispatch.

    Zero-pads every slice to the shared tile grid, factors the whole
    stack via :func:`_factor_stack_padded` (one
    :func:`repro.core.engine.factor_tiles_batched` call — a single
    ``pallas_call`` on the megakernel path), and returns unpadded
    slices: same modes/shapes as :func:`tiled_qr` with a leading batch
    axis.  This is the shared lowering behind the serving layer's bucket
    programs and the optimizer's shape-class dispatch
    (:mod:`repro.optim.batched_ortho`).
    """
    b, m, n = a.shape
    if m == 0 or n == 0:
        raise ValueError(
            f"tiled_qr_batched needs nonempty matrices, got {a.shape}")
    p, q = tile_grid(m, n, tile)
    nb = tile
    pad = ((0, 0), (0, p * nb - m), (0, q * nb - n))
    a_pad = jnp.pad(a, pad) if (pad[1][1] or pad[2][1]) else a
    out = _factor_stack_padded(a_pad, p=p, q=q, nb=nb, mode=mode,
                               use_kernel=use_kernel,
                               dispatch_mode=dispatch_mode,
                               interpret=interpret)
    k = min(m, n)
    if mode == "r":
        return out[0][:, :k, :n]
    q_mat, r_full = out
    if mode == "reduced":
        return q_mat[:, :m, :k], r_full[:, :k, :n]
    return q_mat[:, :m, :m], r_full[:, :m, :n]


# The public wrapper jits once per (shape, knobs); callers composing the
# lowering into a larger traced program (the serving bucket executables,
# the batched-ortho optimizer path) trace the impl or
# ``_factor_stack_padded`` directly — donation does not cross a nested
# jit boundary.
tiled_qr_batched = jax.jit(
    _tiled_qr_batched_impl,
    static_argnames=("tile", "mode", "use_kernel", "dispatch_mode",
                     "interpret"))


# -- registry -----------------------------------------------------------------
from repro.core.plan import (  # noqa: E402
    MethodSpec, QRConfig, register_method, sign_fix_qr, sign_fix_r)


def _planned_itemsize(cfg, dtype) -> int:
    """Element width of the compute dtype the solve will actually run
    (the ``precision`` override wins over the input dtype)."""
    import numpy as np

    if cfg.precision is not None:
        return np.dtype(cfg.precision).itemsize
    return np.dtype(dtype).itemsize if dtype is not None else 4


def _resolve_dispatch_explained(p: int, q: int, nb: int, itemsize: int,
                                explain) -> str:
    """Resolve the engine dispatch mode, surfacing megakernel-over-budget
    rejections as a planner fallback (counter + explain decision) —
    shared by the tiled and sharded resolve hooks."""
    from repro.core.plan import RouteDecision
    from repro.observability import metrics as _metrics

    mode, why = engine.explain_dispatch_mode(p, q, nb, itemsize)
    if mode == "wavefront":
        _metrics.counter("planner.fallbacks",
                         reason="megakernel_over_budget").inc()
        if explain is not None:
            explain.append(RouteDecision("megakernel_over_budget",
                                         "fallback", why))
    elif explain is not None:
        explain.append(RouteDecision("dispatch_mode_auto", "resolved", why))
    return mode


def _kernel_tile(tile: int, cfg: QRConfig, backend, explain) -> int:
    """The tile the planned path can run at: a TPU-compiled kernel needs
    a lane-aligned tile (:func:`repro.core.engine.lane_aligned_tile`),
    so a narrower one is raised and the matrix zero-pads to the grid."""
    if not (cfg.use_kernel and backend == "tpu"):
        return tile
    aligned = engine.lane_aligned_tile(tile)
    if aligned != tile and explain is not None:
        from repro.core.plan import RouteDecision

        explain.append(RouteDecision(
            "tpu_tile_lane_aligned", "resolved",
            f"tile {tile} -> {aligned}: the TPU compiler refuses a DMA of "
            f"a tile narrower than the {engine.TPU_LANES}-lane HBM tiling "
            f"of the engine workspace"))
    return aligned


def _resolve_tiled(m: int, n: int, cfg: QRConfig, *, dtype=None,
                   explain=None, backend=None) -> QRConfig:
    # cfg.block doubles as the tile size; never exceed the matrix itself
    # (except to align a TPU kernel's tile).
    cfg = cfg.replace(block=_kernel_tile(min(cfg.block, m, n), cfg, backend,
                                         explain))
    if cfg.dispatch_mode is None and cfg.use_kernel:
        # Record the engine lowering the kernel path will actually run
        # (megakernel iff the task table + working set fit the budgets
        # at the planned element width — fp64 doubles the working set);
        # the jnp-oracle path has no kernel dispatch — mode stays None.
        p, q = tile_grid(m, n, cfg.block)
        cfg = cfg.replace(dispatch_mode=_resolve_dispatch_explained(
            p, q, cfg.block, _planned_itemsize(cfg, dtype), explain))
    return cfg


def _solve_tiled(a: Array, cfg: QRConfig):
    m, n = a.shape
    tile = cfg.block  # capped at min(m, n) by the _resolve_tiled hook
    if cfg.mode == "r":
        r = tiled_qr(a, tile=tile, mode="r", use_kernel=bool(cfg.use_kernel),
                     dispatch_mode=cfg.dispatch_mode)
        return sign_fix_r(r) if cfg.sign_fix else r
    if cfg.mode == "reduced" and cfg.q_method == "solve" and m >= n:
        from repro.core.tsqr import triangular_inverse_apply

        r = tiled_qr(a, tile=tile, mode="r", use_kernel=bool(cfg.use_kernel),
                     dispatch_mode=cfg.dispatch_mode)
        q = triangular_inverse_apply(a, r[:n, :n])
    else:
        q, r = tiled_qr(a, tile=tile, mode=cfg.mode,
                        use_kernel=bool(cfg.use_kernel),
                        dispatch_mode=cfg.dispatch_mode)
    return sign_fix_qr(q, r) if cfg.sign_fix else (q, r)


def _solve_tiled_batched(a: Array, cfg: QRConfig):
    """Native (B, m, n) solve: same semantics as :func:`_solve_tiled` per
    slice, but the whole stack factors through one batched engine
    dispatch (sign fixing and Q-by-solve vmap over the batch — they are
    elementwise / per-slice dense ops, not engine work)."""
    _, m, n = a.shape
    tile = cfg.block  # capped at min(m, n) by the _resolve_tiled hook
    if cfg.mode == "r":
        r = tiled_qr_batched(a, tile=tile, mode="r",
                             use_kernel=bool(cfg.use_kernel),
                             dispatch_mode=cfg.dispatch_mode)
        return jax.vmap(sign_fix_r)(r) if cfg.sign_fix else r
    if cfg.mode == "reduced" and cfg.q_method == "solve" and m >= n:
        from repro.core.tsqr import triangular_inverse_apply

        r = tiled_qr_batched(a, tile=tile, mode="r",
                             use_kernel=bool(cfg.use_kernel),
                             dispatch_mode=cfg.dispatch_mode)
        q = jax.vmap(triangular_inverse_apply)(a, r[:, :n, :n])
    else:
        q, r = tiled_qr_batched(a, tile=tile, mode=cfg.mode,
                                use_kernel=bool(cfg.use_kernel),
                                dispatch_mode=cfg.dispatch_mode)
    return jax.vmap(sign_fix_qr)(q, r) if cfg.sign_fix else (q, r)


def _vmem_tiled(m: int, n: int, cfg: QRConfig) -> int:
    """Smallest working set the kernel path can run in (fp32 units — the
    caller scales by element width).  With ``dispatch_mode`` unset or
    "wavefront" that is the per-level wavefront set: the megakernel's
    larger double-buffered set is only ever auto-picked when it *also*
    fits (at the planned width, see ``_resolve_tiled``), so pricing it
    here would wrongly reject shapes the wavefront mode handles.  Only a
    forced megakernel must be gated on its own footprint."""
    from repro.kernels import macro_ops

    nb = min(cfg.block, m, n)
    if cfg.dispatch_mode == "megakernel":
        return macro_ops.megakernel_vmem_bytes(nb)
    return macro_ops.engine_vmem_bytes(nb)


register_method(MethodSpec(
    name="tiled",
    solve=_solve_tiled,
    solve_batched=_solve_tiled_batched,
    resolve=_resolve_tiled,
    kernel_backed=True,
    vmem_bytes=_vmem_tiled,
    kernel_policy="macro_ops",
    description="tiled task-graph QR via the wavefront macro-op engine "
                "(GEQRT/TSQRT/LARFB/SSRFB, one Pallas dispatch per level)",
))
