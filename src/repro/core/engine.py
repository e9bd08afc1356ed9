"""Wavefront macro-op execution engine — the levelized tile DAG as one
in-place Pallas dispatch per level, or ONE per factorization.

:mod:`repro.core.tilegraph` levelizes the tiled-QR task DAG statically;
this module *executes* that schedule.  It is the software analogue of the
paper's Reconfigurable Data-path orchestration (§5): every DAG node runs
as a fused macro operation (:mod:`repro.kernels.macro_ops`).  Two kernel
lowerings of the same schedule exist, selected by ``dispatch_mode``:

  * ``"wavefront"`` — every wavefront's same-kind task batch lowers to a
    **single** ``pallas_call`` whose grid enumerates the level's
    independent tiles (~``levels x kinds`` dispatches per factorization);
  * ``"megakernel"`` — the whole schedule flattens into one
    scalar-prefetched **task table** (one ``(kind, k, i, j)`` record per
    DAG node, wavefront-ordered, NOOP-padded to a rectangular
    ``(levels, slots)`` grid) and executes as **one** persistent
    ``pallas_call``: the grid walks the table, each step switches on
    ``kind`` into the same macro-op bodies, and operand DMA is
    **double-buffered** — while task t computes, task t+1's tiles are
    already streaming into the other buffer half (back-to-back macro-op
    streaming, the paper's RDP §5 in software).  Prefetch never crosses
    a level boundary (the level barrier that preserves inter-wavefront
    dependencies), and one-ahead prefetch within a level is value-exact
    because a task's reads never overlap its predecessor's writes —
    asserted per adjacent pair at table-build time (the canonical kind
    order is load-bearing there: it keeps the one same-level same-tile
    overlap, LARFB's strictly-lower V1 read vs TSQRT's upper-triangle
    merge of the diagonal tile, read-before-write and region-disjoint).
    Consecutive tasks reading the same tile reuse the resident copy
    instead of re-touching HBM.  ``dispatch_mode=None`` resolves automatically: megakernel when
    the task table fits the scalar-prefetch budget and the
    double-buffered working set fits VMEM (both read off the
    ``"macro_ops"`` kernel policy), wavefront otherwise —
    :func:`resolve_dispatch_mode` / :func:`schedule_stats`.

Execution model (``use_kernel=True``):

  * the factorization state lives in a ``(p, q, nb, nb)`` tile
    **workspace** plus four small reflector-state arrays (``d_t`` /
    ``d_taus`` for GEQRT, ``t_t`` / ``t_taus`` for TSQRT);
  * task coordinates are **scalar-prefetch** index arrays; block
    index-maps and in-kernel DMA read/write tiles *directly* from the
    workspace (held in ``ANY`` memory space), so the gather ->
    vmap-compute -> ``.at[].set`` scatter round trips of the old
    scheduler never happen;
  * every ``pallas_call`` aliases the workspace (and the state arrays it
    writes) input -> output, so the whole factor loop is in place — no
    fresh tile array materializes per wavefront;
  * :func:`factor_tiles` additionally **donates** the workspace
    (``jax.jit(..., donate_argnums=(0,))``), so callers outside a jit
    don't retain a second copy of the input buffer either.

``use_kernel=False`` is the pure-jnp oracle lowering: the *same*
value-level macro-op bodies, vmapped over each batch with functional
updates.  Both lowerings trace identical op sequences per task, so the
engine path is **bitwise** equal to the oracle (asserted in
tests/test_engine.py and tests/test_conformance.py).  Interpret-mode
Pallas (the CPU default) is preserved via the ``interpret`` knob /
``macro_ops.default_interpret``.

Both the single-device ``tiled`` backend and the per-domain local sweeps
of the multi-device ``sharded_tiled`` backend execute through this
engine; the planner's ``"macro_ops"`` kernel policy carries its VMEM
accounting.
"""

from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import macro_ops
from repro.observability import metrics as _metrics
from repro.observability import profiler as _profiler

Array = jax.Array

__all__ = [
    "DISPATCH_MODES",
    "FactorState",
    "explain_dispatch_mode",
    "factor_tiles",
    "factor_tiles_batched",
    "megakernel_task_table",
    "modeled_dma_bytes",
    "resolve_dispatch_mode",
    "schedule_stats",
    "wavefront_task_arrays",
]

_KIND_ORDER = ("GEQRT", "LARFB", "TSQRT", "SSRFB")

#: The engine's kernel lowerings of the static schedule (see module doc).
DISPATCH_MODES = ("wavefront", "megakernel")

#: Compiled for TPU, the kernels DMA whole ``nb x nb`` tiles out of the
#: HBM workspace, whose minor dimension is laid out in 128-lane tiles:
#: Mosaic refuses a tile slice narrower than that ("slice shape along
#: dimension 3 must be aligned to tiling (128)").  Interpret mode has no
#: such limit.
TPU_LANES = 128


def lane_aligned_tile(nb: int) -> int:
    """The smallest tile >= ``nb`` the TPU-compiled lowerings accept."""
    return -(-nb // TPU_LANES) * TPU_LANES


class FactorState(NamedTuple):
    """Factored tile state: packed reflectors + per-task block reflectors.

    tiles:  (p, q, nb, nb) — diagonal tiles hold V1 strictly below / R on
            and above the diagonal; tiles (i, k), i > k hold the TSQRT V2;
            tiles (k, j), j > k hold R blocks.
    d_t:    (r, nb, nb) GEQRT block reflectors T;  d_taus: (r, nb)
    t_t:    (p, r, nb, nb) TSQRT block reflectors; t_taus: (p, r, nb)
    """

    tiles: Array
    d_t: Array
    d_taus: Array
    t_t: Array
    t_taus: Array


# lru-cache purity contract: the @lru_cache'd helpers below
# (wavefront_task_arrays, megakernel_task_table, modeled_dma_bytes) are
# PURE functions of their integer arguments — schedule structure and
# traffic counts only.  None of them may read the "macro_ops" kernel
# policy budgets: budgets are runtime knobs (re-registrable, and swept
# by repro.tuning), so every budget comparison happens un-cached at call
# time (explain_dispatch_mode / schedule_stats / _check_dispatch).
# Asserted in tests/test_engine.py (budget-staleness regression).
@functools.lru_cache(maxsize=None)
def wavefront_task_arrays(p: int, q: int
                          ) -> Tuple[Dict[str, np.ndarray], ...]:
    """The static schedule as dispatchable batches: one dict per
    wavefront mapping kind -> int32 ``(ntasks, 3)`` array of (k, i, j)."""
    from repro.core.tilegraph import wavefronts  # lazy: tilegraph imports us

    out: List[Dict[str, np.ndarray]] = []
    for wf in wavefronts(p, q):
        by_kind: Dict[str, List] = {}
        for t in wf:
            by_kind.setdefault(t.kind, []).append(t)
        out.append({kind: np.array([[t.k, t.i, t.j] for t in tasks],
                                   dtype=np.int32)
                    for kind, tasks in by_kind.items()})
    return tuple(out)


# ---------------------------------------------------------------------------
# megakernel task table — the whole schedule as one scalar-prefetch array
# ---------------------------------------------------------------------------
#
# One int32 row per (level, slot) grid cell.  Valid tasks fill each
# level's leading slots in canonical kind order (then (k, i, j)); the
# rectangular remainder is NOOP padding.  Besides the task identity the
# row carries everything the kernel's double-buffered DMA needs decided
# statically: the ordered operand-tile coordinates, whether the
# predecessor slot already prefetched this task's operands, whether this
# slot should prefetch its successor's (never across a level boundary —
# the inter-wavefront barrier), and per-operand reuse flags (successor
# reads the same tile the current task holds resident -> VMEM-local copy
# instead of an HBM fetch).

_KIND_ID = {kind: n for n, kind in enumerate(_KIND_ORDER)}
_NOOP = len(_KIND_ORDER)

_COL_KIND, _COL_K, _COL_I, _COL_J = 0, 1, 2, 3
_COL_R0 = 4            # 3 (row, col) operand-tile coords: columns 4..9
_COL_FETCHED = 10      # operands already streaming (predecessor prefetch)
_COL_PREFETCH = 11     # this slot prefetches the successor's operands
_COL_REUSE0 = 12       # per-operand buffer-reuse flags: columns 12..14
_COL_REUSET = 15       # block-reflector (T) operand reuse flag
_NCOLS = 16


def _task_reads(kind: str, k: int, i: int, j: int) -> List[Tuple[int, int]]:
    """Ordered workspace tiles a task DMAs in (matches the body args)."""
    if kind == "GEQRT":
        return [(k, k)]
    if kind == "LARFB":
        return [(k, k), (k, j)]
    if kind == "TSQRT":
        return [(k, k), (i, k)]
    return [(i, k), (k, j), (i, j)]  # SSRFB


def _task_writes(kind: str, k: int, i: int, j: int) -> set:
    """Workspace tiles a task DMAs back out."""
    if kind == "GEQRT":
        return {(k, k)}
    if kind == "LARFB":
        return {(k, j)}
    if kind == "TSQRT":
        return {(k, k), (i, k)}
    return {(k, j), (i, j)}  # SSRFB


def _task_t_source(kind: str, k: int, i: int, j: int):
    """Identity of the block-reflector (T) operand, or None."""
    if kind == "LARFB":
        return ("d_t", k)
    if kind == "SSRFB":
        return ("t_t", i, k)
    return None


def task_count(p: int, q: int) -> int:
    """Closed-form DAG size: step k contributes (p - k)(q - k) tasks."""
    return sum((p - k) * (q - k) for k in range(min(p, q)))


@functools.lru_cache(maxsize=None)
def megakernel_task_table(p: int, q: int
                          ) -> Tuple[np.ndarray, int, int]:
    """The flattened schedule: ``(table, nlevels, nslots)`` with ``table``
    an int32 ``(nlevels * nslots, 16)`` array, one row per grid cell.

    Builds the prefetch/reuse chains and *verifies* the invariants the
    one-ahead double buffering relies on: level-wide, no two tasks write
    the same tile; and per adjacent slot pair, the successor's reads
    never overlap the current task's writes (so fetching task t+1's
    operands before task t's write-back is value-exact, not just
    race-tolerant).  NOTE the second invariant is a property of the
    canonical ``_KIND_ORDER`` slot ordering, not of levels at large —
    e.g. LARFB reads the diagonal tile a same-level TSQRT later merges
    into (disjoint regions, but the same tile); ordering LARFB first
    keeps every adjacent window clean.  Deepening the prefetch window
    beyond one task would need a correspondingly wider assert.
    """
    levels: List[List[Tuple[str, int, int, int]]] = []
    for by_kind in wavefront_task_arrays(p, q):
        rows = [(kind, int(k), int(i), int(j))
                for kind in _KIND_ORDER
                for k, i, j in by_kind.get(kind, ())]
        levels.append(rows)
    nlevels = len(levels)
    nslots = max(len(rows) for rows in levels)
    tab = np.zeros((nlevels * nslots, _NCOLS), np.int32)
    tab[:, _COL_KIND] = _NOOP
    for lv, rows in enumerate(levels):
        writes = [w for task in rows for w in _task_writes(*task)]
        assert len(writes) == len(set(writes)), "same-level write overlap"
        for s, task in enumerate(rows):
            kind, k, i, j = task
            t = lv * nslots + s
            tab[t, _COL_KIND] = _KIND_ID[kind]
            tab[t, _COL_K], tab[t, _COL_I], tab[t, _COL_J] = k, i, j
            for b, (r, c) in enumerate(_task_reads(*task)):
                tab[t, _COL_R0 + 2 * b] = r
                tab[t, _COL_R0 + 2 * b + 1] = c
        for s in range(len(rows) - 1):
            cur, nxt = rows[s], rows[s + 1]
            t = lv * nslots + s
            cw = _task_writes(*cur)
            nr = _task_reads(*nxt)
            # The level-local safety invariant behind one-ahead prefetch.
            assert not (set(nr) & cw), (cur, nxt)
            tab[t, _COL_PREFETCH] = 1
            tab[t + 1, _COL_FETCHED] = 1
            cr = _task_reads(*cur)
            for b in range(min(len(cr), len(nr))):
                if nr[b] == cr[b]:
                    tab[t + 1, _COL_REUSE0 + b] = 1
            cts = _task_t_source(*cur)
            if cts is not None and cts == _task_t_source(*nxt):
                tab[t + 1, _COL_REUSET] = 1
    return tab, nlevels, nslots


def table_fits(p: int, q: int, budget: int) -> Tuple[bool, int]:
    """Does the ``(p, q)`` megakernel task table fit ``budget`` bytes?
    Returns ``(fits, bytes)``.  Checks the closed-form lower bound first
    so grids whose table cannot fit anyway (the symbolic DAG is
    O(p q min(p, q)) tasks) are rejected without ever being levelized."""
    bound = task_count(p, q) * _NCOLS * 4
    if bound > budget:
        return False, bound
    nbytes = int(megakernel_task_table(p, q)[0].nbytes)
    return nbytes <= budget, nbytes


def explain_dispatch_mode(p: int, q: int, nb: int, itemsize: int = 4, *,
                          vmem_budget: Optional[int] = None,
                          table_budget: Optional[int] = None
                          ) -> Tuple[str, str]:
    """The ``dispatch_mode=None`` auto rule with its concrete reason:
    ``(mode, reason)``.  ``"megakernel"`` when the task table fits the
    scalar-prefetch budget AND the double-buffered tile working set fits
    VMEM, ``"wavefront"`` otherwise — and the reason string names exactly
    which budget rejected it.

    Budgets default to the CURRENT ``"macro_ops"`` kernel policy, read at
    call time — deliberately un-cached, so re-registering the policy (or
    a tuner sweeping budgets) changes the verdict immediately (the
    staleness-vs-lru contract documented at
    :func:`wavefront_task_arrays`).  Explicit ``vmem_budget`` /
    ``table_budget`` overrides let a sweep ask "what would auto pick
    under budget X" without touching the registry."""
    from repro.core.plan import kernel_table_budget, kernel_vmem_budget

    need = macro_ops.megakernel_vmem_bytes(nb, itemsize)
    vbudget = (kernel_vmem_budget("macro_ops") if vmem_budget is None
               else int(vmem_budget))
    if need > vbudget:
        return "wavefront", (
            f"megakernel working set {need} B > VMEM budget {vbudget} B "
            f"at nb={nb}, itemsize={itemsize}")
    tbudget = (kernel_table_budget("macro_ops") if table_budget is None
               else int(table_budget))
    fits, tbytes = table_fits(p, q, tbudget)
    if not fits:
        return "wavefront", (
            f"({p}, {q}) grid's task table >= {tbytes} B > "
            f"scalar-prefetch budget {tbudget} B")
    return "megakernel", (
        f"task table {tbytes} B <= budget {tbudget} B and working set "
        f"{need} B <= VMEM budget {vbudget} B")


def resolve_dispatch_mode(p: int, q: int, nb: int, itemsize: int = 4, *,
                          vmem_budget: Optional[int] = None,
                          table_budget: Optional[int] = None) -> str:
    """The ``dispatch_mode=None`` auto rule: ``"megakernel"`` when the
    task table fits the scalar-prefetch budget AND the double-buffered
    tile working set fits VMEM (both limits read off the current
    ``"macro_ops"`` kernel policy at call time, or passed explicitly),
    ``"wavefront"`` otherwise.  See :func:`explain_dispatch_mode` for
    the rule with its reasoning."""
    return explain_dispatch_mode(p, q, nb, itemsize,
                                 vmem_budget=vmem_budget,
                                 table_budget=table_budget)[0]


@functools.lru_cache(maxsize=None)
def modeled_dma_bytes(p: int, q: int, nb: int,
                      itemsize: int = 4) -> Dict[str, int]:
    """Analytic HBM tile traffic of one ``(p, q)`` factorization, per
    dispatch mode, from the per-op tile_reads/tile_writes cards
    (:mod:`repro.kernels.macro_ops`) — the traffic model behind
    ``benchmarks/bench_kernel_traffic.wavefront_traffic``, totalled.

    ``wavefront``: every task re-fetches its operand tiles from HBM each
    level.  ``megakernel``: the same minus the fetches the persistent
    kernel's double buffer serves from the resident copy
    (:func:`megakernel_reused_reads`).  ``roofline``: compulsory traffic
    — one read + one write of the whole workspace.  Reflector-state
    arrays (~nb/tile smaller) are ignored, as in the benchmark.
    """
    tile = nb * nb * itemsize
    eng = 0
    for by_kind in wavefront_task_arrays(p, q):
        for kind, idx in by_kind.items():
            op = macro_ops.MACRO_OPS[kind]
            eng += idx.shape[0] * (op.tile_reads + op.tile_writes) * tile
    reused = int(megakernel_reused_reads(p, q).sum())
    return dict(
        wavefront=eng,
        megakernel=eng - reused * tile,
        roofline=2 * p * q * tile,
    )


def schedule_stats(p: int, q: int, nb: int = 32, itemsize: int = 4, *,
                   vmem_budget: Optional[int] = None,
                   table_budget: Optional[int] = None) -> Dict[str, object]:
    """Dispatch counts, table/working-set bytes, and modeled HBM traffic
    for both dispatch modes of the ``(p, q)`` schedule — the numbers
    behind the auto rule, the ``bench_kernel_traffic``
    dispatch-reduction row, and the engine's ``engine.*`` metrics.

    Un-cached on purpose: the ``auto`` verdict (and the budget fields)
    reflect the "macro_ops" policy AT CALL TIME unless explicit budget
    overrides are passed — see the lru-cache purity contract at
    :func:`wavefront_task_arrays`."""
    from repro.core.plan import kernel_table_budget, kernel_vmem_budget

    batches = wavefront_task_arrays(p, q)
    table, nlevels, nslots = megakernel_task_table(p, q)
    ntasks = int((table[:, _COL_KIND] != _NOOP).sum())
    dma = modeled_dma_bytes(p, q, nb, itemsize)
    vbudget = (kernel_vmem_budget("macro_ops") if vmem_budget is None
               else int(vmem_budget))
    tbudget = (kernel_table_budget("macro_ops") if table_budget is None
               else int(table_budget))
    return dict(
        p=p, q=q, nb=nb, levels=nlevels, tasks=ntasks,
        vmem_budget=vbudget, table_budget=tbudget,
        roofline_dma_bytes=dma["roofline"],
        wavefront=dict(
            dispatches=sum(len(b) for b in batches),
            vmem_bytes=macro_ops.engine_vmem_bytes(nb, itemsize),
            modeled_dma_bytes=dma["wavefront"],
        ),
        megakernel=dict(
            dispatches=1,
            grid=(nlevels, nslots),
            table_shape=tuple(table.shape),
            table_bytes=int(table.nbytes),
            padded_slots=nlevels * nslots - ntasks,
            reused_tile_fetches=int(
                table[:, _COL_REUSE0:_COL_REUSE0 + 3].sum()),
            reused_t_fetches=int(table[:, _COL_REUSET].sum()),
            vmem_bytes=macro_ops.megakernel_vmem_bytes(nb, itemsize),
            modeled_dma_bytes=dma["megakernel"],
        ),
        auto=resolve_dispatch_mode(p, q, nb, itemsize,
                                   vmem_budget=vbudget,
                                   table_budget=tbudget),
    )


def megakernel_reused_reads(p: int, q: int) -> np.ndarray:
    """Per-level count of operand-tile fetches the megakernel serves from
    the resident double buffer instead of HBM (traffic-model input)."""
    table, nlevels, nslots = megakernel_task_table(p, q)
    per_slot = table[:, _COL_REUSE0:_COL_REUSE0 + 3].sum(axis=1)
    return per_slot.reshape(nlevels, nslots).sum(axis=1)


# ---------------------------------------------------------------------------
# jnp lowering — the bitwise oracle (vmap of the same macro-op bodies)
# ---------------------------------------------------------------------------

def _batched(body, *args):
    """vmap the macro-op body over a task batch — except singleton
    batches, which run unbatched: XLA lowers a batch-1 ``dot_general``
    through a different (reshaped) contraction than the plain dot the
    Pallas body traces, breaking bitwise parity between the lowerings.
    For every batch size > 1 the per-slice results ARE bitwise equal to
    the unbatched body (stress-checked in tests/test_engine.py)."""
    if args[0].shape[0] == 1:
        out = body(*(x[0] for x in args))
        if isinstance(out, tuple):
            return tuple(o[None] for o in out)
        return out[None]
    return jax.vmap(body)(*args)


def _jnp_wavefront(state: FactorState, by_kind: Dict[str, np.ndarray]
                   ) -> FactorState:
    tiles, d_t, d_taus, t_t, t_taus = state
    # Gathers read the pre-wavefront tiles; same-level tasks touch
    # disjoint tile regions (TSQRT merges into the upper triangle only,
    # preserving the GEQRT V1 below the diagonal), so deferring all
    # scatters to the end of the level is value-identical to the
    # engine's in-place execution.
    updates = []
    if "GEQRT" in by_kind:
        kk = by_kind["GEQRT"][:, 0]
        packed, t, taus = _batched(macro_ops.geqrt_body, tiles[kk, kk])
        d_t = d_t.at[kk].set(t)
        d_taus = d_taus.at[kk].set(taus)
        updates.append((kk, kk, packed))
    if "LARFB" in by_kind:
        kk = by_kind["LARFB"][:, 0]
        jj = by_kind["LARFB"][:, 2]
        out = _batched(macro_ops.larfb_body, tiles[kk, kk], d_t[kk],
                       tiles[kk, jj])
        updates.append((kk, jj, out))
    if "TSQRT" in by_kind:
        kk = by_kind["TSQRT"][:, 0]
        ii = by_kind["TSQRT"][:, 1]
        merged, v2, t, taus = _batched(
            macro_ops.tsqrt_body, tiles[kk, kk], tiles[ii, kk])
        t_t = t_t.at[ii, kk].set(t)
        t_taus = t_taus.at[ii, kk].set(taus)
        updates.append((kk, kk, merged))
        updates.append((ii, kk, v2))
    if "SSRFB" in by_kind:
        kk = by_kind["SSRFB"][:, 0]
        ii = by_kind["SSRFB"][:, 1]
        jj = by_kind["SSRFB"][:, 2]
        ck, ci = _batched(
            macro_ops.ssrfb_body,
            tiles[ii, kk], t_t[ii, kk], tiles[kk, jj], tiles[ii, jj])
        updates.append((kk, jj, ck))
        updates.append((ii, jj, ci))
    for ri, ci_, vals in updates:
        tiles = tiles.at[ri, ci_].set(vals)
    return FactorState(tiles, d_t, d_taus, t_t, t_taus)


# ---------------------------------------------------------------------------
# Pallas lowering — one in-place pallas_call per (wavefront, kind) batch
# ---------------------------------------------------------------------------

def _any_spec():
    return pl.BlockSpec(memory_space=pl.ANY)


def _dispatch_geqrt(state: FactorState, idx: np.ndarray, nb: int,
                    interpret: bool) -> FactorState:
    tiles, d_t, d_taus, t_t, t_taus = state
    kk = jnp.asarray(idx[:, 0])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(idx.shape[0],),
        in_specs=[
            _any_spec(),
            pl.BlockSpec((1, nb, nb), lambda g, kk: (kk[g], 0, 0)),
            _any_spec(),
        ],
        out_specs=[
            _any_spec(),
            pl.BlockSpec((1, nb, nb), lambda g, kk: (kk[g], 0, 0)),
            _any_spec(),
        ],
        scratch_shapes=[pltpu.VMEM((nb, nb), tiles.dtype),
                        pltpu.VMEM((nb,), tiles.dtype),
                        pltpu.SemaphoreType.DMA],
    )
    tiles, d_t, d_taus = pl.pallas_call(
        macro_ops.geqrt_wavefront_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(tiles.shape, tiles.dtype),
                   jax.ShapeDtypeStruct(d_t.shape, d_t.dtype),
                   jax.ShapeDtypeStruct(d_taus.shape, d_taus.dtype)],
        input_output_aliases={1: 0, 2: 1, 3: 2},
        interpret=interpret,
    )(kk, tiles, d_t, d_taus)
    return FactorState(tiles, d_t, d_taus, t_t, t_taus)


def _dispatch_larfb(state: FactorState, idx: np.ndarray, nb: int,
                    interpret: bool) -> FactorState:
    tiles, d_t, d_taus, t_t, t_taus = state
    kk = jnp.asarray(idx[:, 0])
    jj = jnp.asarray(idx[:, 2])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(idx.shape[0],),
        in_specs=[
            _any_spec(),
            pl.BlockSpec((1, nb, nb), lambda g, kk, jj: (kk[g], 0, 0)),
        ],
        out_specs=[_any_spec()],
        scratch_shapes=[pltpu.VMEM((nb, nb), tiles.dtype),
                        pltpu.VMEM((nb, nb), tiles.dtype),
                        pltpu.SemaphoreType.DMA],
    )
    (tiles,) = pl.pallas_call(
        macro_ops.larfb_wavefront_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(tiles.shape, tiles.dtype)],
        input_output_aliases={2: 0},
        interpret=interpret,
    )(kk, jj, tiles, d_t)
    return FactorState(tiles, d_t, d_taus, t_t, t_taus)


def _dispatch_tsqrt(state: FactorState, idx: np.ndarray, nb: int,
                    interpret: bool) -> FactorState:
    tiles, d_t, d_taus, t_t, t_taus = state
    kk = jnp.asarray(idx[:, 0])
    ii = jnp.asarray(idx[:, 1])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(idx.shape[0],),
        in_specs=[
            _any_spec(),
            pl.BlockSpec((1, 1, nb, nb),
                         lambda g, kk, ii: (ii[g], kk[g], 0, 0)),
            _any_spec(),
        ],
        out_specs=[
            _any_spec(),
            pl.BlockSpec((1, 1, nb, nb),
                         lambda g, kk, ii: (ii[g], kk[g], 0, 0)),
            _any_spec(),
        ],
        scratch_shapes=[pltpu.VMEM((nb, nb), tiles.dtype),
                        pltpu.VMEM((nb, nb), tiles.dtype),
                        pltpu.VMEM((nb,), tiles.dtype),
                        pltpu.SemaphoreType.DMA],
    )
    tiles, t_t, t_taus = pl.pallas_call(
        macro_ops.tsqrt_wavefront_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(tiles.shape, tiles.dtype),
                   jax.ShapeDtypeStruct(t_t.shape, t_t.dtype),
                   jax.ShapeDtypeStruct(t_taus.shape, t_taus.dtype)],
        input_output_aliases={2: 0, 3: 1, 4: 2},
        interpret=interpret,
    )(kk, ii, tiles, t_t, t_taus)
    return FactorState(tiles, d_t, d_taus, t_t, t_taus)


def _dispatch_ssrfb(state: FactorState, idx: np.ndarray, nb: int,
                    interpret: bool) -> FactorState:
    tiles, d_t, d_taus, t_t, t_taus = state
    kk = jnp.asarray(idx[:, 0])
    ii = jnp.asarray(idx[:, 1])
    jj = jnp.asarray(idx[:, 2])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(idx.shape[0],),
        in_specs=[
            _any_spec(),
            pl.BlockSpec((1, 1, nb, nb),
                         lambda g, kk, ii, jj: (ii[g], kk[g], 0, 0)),
        ],
        out_specs=[_any_spec()],
        scratch_shapes=[pltpu.VMEM((nb, nb), tiles.dtype),
                        pltpu.VMEM((nb, nb), tiles.dtype),
                        pltpu.VMEM((nb, nb), tiles.dtype),
                        pltpu.SemaphoreType.DMA],
    )
    (tiles,) = pl.pallas_call(
        macro_ops.ssrfb_wavefront_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(tiles.shape, tiles.dtype)],
        input_output_aliases={3: 0},
        interpret=interpret,
    )(kk, ii, jj, tiles, t_t)
    return FactorState(tiles, d_t, d_taus, t_t, t_taus)


_DISPATCH = {
    "GEQRT": _dispatch_geqrt,
    "LARFB": _dispatch_larfb,
    "TSQRT": _dispatch_tsqrt,
    "SSRFB": _dispatch_ssrfb,
}


def _pallas_wavefront(state: FactorState, by_kind: Dict[str, np.ndarray],
                      nb: int, interpret: bool,
                      level: Optional[int] = None) -> FactorState:
    # Kind order is part of the in-place contract: within a level the
    # only tile shared between kinds is the diagonal, and its two users
    # touch disjoint regions (TSQRT writes the upper triangle, LARFB
    # reads the strictly-lower V1), so any order is value-identical —
    # the canonical order just keeps dispatch deterministic.
    for kind in _KIND_ORDER:
        if kind in by_kind:
            with _profiler.annotate(_profiler.kernel_label(kind, level)):
                state = _DISPATCH[kind](state, by_kind[kind], nb, interpret)
    return state


# ---------------------------------------------------------------------------
# Pallas lowering — megakernel: ONE pallas_call for the whole schedule
# ---------------------------------------------------------------------------
#
# The grid is (levels, slots): the sequential walk over the task table.
# Each step reads its row, switches on kind into the same value-level
# macro-op bodies the wavefront lowering uses, and moves tiles by
# explicit DMA against the ANY-space workspace.  Operand fetch is
# double-buffered on the flat task parity: while task t computes out of
# buffer half t%2, it has already started task t+1's fetches into the
# other half (or a VMEM-local copy when t+1 re-reads a tile t holds
# resident).  Start and wait reconstruct their copy descriptors from the
# same table row, so semaphore pairing is static.  Prefetch stops at
# level boundaries: the first slot of each level fetches synchronously,
# after every prior write-back has completed — the wavefront barrier.

def _cell(tab_ref, t, col):
    """Column ``col`` of task-table row ``t``.  The table reaches SMEM
    flattened to 1-D: SMEM pads a 2-D array's minor dimension to 128
    words, which would cost 8x the ``_NCOLS``-wide table's bytes."""
    return tab_ref[t * _NCOLS + col]


def _op_copies(tab_ref, t, phase, ws_at, dt_at, tt_at, opbuf, tbuf, sems,
               start: bool):
    """Start (or wait for) the operand DMAs of task-table row ``t`` into
    buffer half ``phase``.  ``start`` is trace-time: the wait side
    rebuilds the identical descriptors, so each semaphore is started
    exactly once per wait.  ``ws_at`` / ``dt_at`` / ``tt_at`` are
    accessor closures over the workspace refs — the batched lowering
    binds the batch index there, the single-matrix one binds nothing."""
    kind = _cell(tab_ref, t, _COL_KIND)

    def go(cp):
        cp.start() if start else cp.wait()

    def tile_fetch(b):
        r = _cell(tab_ref, t, _COL_R0 + 2 * b)
        c = _cell(tab_ref, t, _COL_R0 + 2 * b + 1)
        reuse = _cell(tab_ref, t, _COL_REUSE0 + b)

        @pl.when(reuse == 1)
        def _():
            go(pltpu.make_async_copy(opbuf.at[1 - phase, b],
                                     opbuf.at[phase, b], sems.at[phase, b]))

        @pl.when(reuse == 0)
        def _():
            go(pltpu.make_async_copy(ws_at(r, c), opbuf.at[phase, b],
                                     sems.at[phase, b]))

    tile_fetch(0)  # every kind reads at least one tile

    @pl.when(kind != _KIND_ID["GEQRT"])
    def _():
        tile_fetch(1)

    @pl.when(kind == _KIND_ID["SSRFB"])
    def _():
        tile_fetch(2)

    def t_fetch(src):
        reuse = _cell(tab_ref, t, _COL_REUSET)

        @pl.when(reuse == 1)
        def _():
            go(pltpu.make_async_copy(tbuf.at[1 - phase], tbuf.at[phase],
                                     sems.at[phase, 3]))

        @pl.when(reuse == 0)
        def _():
            go(pltpu.make_async_copy(src, tbuf.at[phase], sems.at[phase, 3]))

    @pl.when(kind == _KIND_ID["LARFB"])
    def _():
        t_fetch(dt_at(_cell(tab_ref, t, _COL_K)))

    @pl.when(kind == _KIND_ID["SSRFB"])
    def _():
        t_fetch(tt_at(_cell(tab_ref, t, _COL_I), _cell(tab_ref, t, _COL_K)))


def _sync_put(src, dst, sem):
    cp = pltpu.make_async_copy(src, dst, sem)
    cp.start()
    cp.wait()


def _megakernel_step(tab_ref, ws, d_t, d_taus, t_t, t_taus,
                     opbuf, tbuf, outbuf, taubuf, sems, wbsem,
                     lvl, slot, nslots_axis: int, b=None):
    """One task-table slot: fetch/prefetch bookkeeping + kind-switched
    compute.  ``b`` is the (optional) batch index of the stacked-workspace
    lowering — every batch element replays the SAME table, so the only
    difference is the leading workspace index the accessors bind."""
    if b is None:
        ws_at = lambda r, c: ws.at[r, c]                    # noqa: E731
        dt_at = lambda k: d_t.at[k]                         # noqa: E731
        dtaus_at = lambda k: d_taus.at[k]                   # noqa: E731
        tt_at = lambda i, k: t_t.at[i, k]                   # noqa: E731
        ttaus_at = lambda i, k: t_taus.at[i, k]             # noqa: E731
    else:
        ws_at = lambda r, c: ws.at[b, r, c]                 # noqa: E731
        dt_at = lambda k: d_t.at[b, k]                      # noqa: E731
        dtaus_at = lambda k: d_taus.at[b, k]                # noqa: E731
        tt_at = lambda i, k: t_t.at[b, i, k]                # noqa: E731
        ttaus_at = lambda i, k: t_taus.at[b, i, k]          # noqa: E731

    t = lvl * pl.num_programs(nslots_axis) + slot
    phase = jax.lax.rem(t, 2)
    kind = _cell(tab_ref, t, _COL_KIND)
    k = _cell(tab_ref, t, _COL_K)
    i = _cell(tab_ref, t, _COL_I)
    j = _cell(tab_ref, t, _COL_J)
    valid = kind != _NOOP

    # -- operands: self-fetch at level heads, else already in flight ----
    @pl.when(valid & (_cell(tab_ref, t, _COL_FETCHED) == 0))
    def _():
        _op_copies(tab_ref, t, phase, ws_at, dt_at, tt_at, opbuf, tbuf,
                   sems, start=True)

    @pl.when(valid)
    def _():
        _op_copies(tab_ref, t, phase, ws_at, dt_at, tt_at, opbuf, tbuf,
                   sems, start=False)

    # -- double buffering: start the successor's fetches before compute -
    @pl.when(_cell(tab_ref, t, _COL_PREFETCH) == 1)
    def _():
        _op_copies(tab_ref, t + 1, 1 - phase, ws_at, dt_at, tt_at, opbuf,
                   tbuf, sems, start=True)

    # -- compute: switch on kind into the shared macro-op bodies --------
    @pl.when(kind == _KIND_ID["GEQRT"])
    def _():
        packed, tmat, taus = macro_ops.geqrt_body(opbuf[phase, 0])
        outbuf[0] = packed
        outbuf[1] = tmat
        taubuf[...] = taus
        _sync_put(outbuf.at[0], ws_at(k, k), wbsem)
        _sync_put(outbuf.at[1], dt_at(k), wbsem)
        _sync_put(taubuf, dtaus_at(k), wbsem)

    @pl.when(kind == _KIND_ID["LARFB"])
    def _():
        outbuf[0] = macro_ops.larfb_body(opbuf[phase, 0], tbuf[phase],
                                         opbuf[phase, 1])
        _sync_put(outbuf.at[0], ws_at(k, j), wbsem)

    @pl.when(kind == _KIND_ID["TSQRT"])
    def _():
        merged, v2, tmat, taus = macro_ops.tsqrt_body(opbuf[phase, 0],
                                                      opbuf[phase, 1])
        outbuf[0] = merged
        outbuf[1] = v2
        outbuf[2] = tmat
        taubuf[...] = taus
        _sync_put(outbuf.at[0], ws_at(k, k), wbsem)
        _sync_put(outbuf.at[1], ws_at(i, k), wbsem)
        _sync_put(outbuf.at[2], tt_at(i, k), wbsem)
        _sync_put(taubuf, ttaus_at(i, k), wbsem)

    @pl.when(kind == _KIND_ID["SSRFB"])
    def _():
        ck, ci = macro_ops.ssrfb_body(opbuf[phase, 0], tbuf[phase],
                                      opbuf[phase, 1], opbuf[phase, 2])
        outbuf[0] = ck
        outbuf[1] = ci
        _sync_put(outbuf.at[0], ws_at(k, j), wbsem)
        _sync_put(outbuf.at[1], ws_at(i, j), wbsem)


def megakernel_kernel(tab_ref, ws_in, dt_in, dtaus_in, tt_in, ttaus_in,
                      ws, d_t, d_taus, t_t, t_taus,
                      opbuf, tbuf, outbuf, taubuf, sems, wbsem):
    """One task-table slot per grid cell; the whole schedule is one call."""
    del ws_in, dt_in, dtaus_in, tt_in, ttaus_in  # aliased in place
    _megakernel_step(tab_ref, ws, d_t, d_taus, t_t, t_taus,
                     opbuf, tbuf, outbuf, taubuf, sems, wbsem,
                     lvl=pl.program_id(0), slot=pl.program_id(1),
                     nslots_axis=1)


def megakernel_batched_kernel(tab_ref, ws_in, dt_in, dtaus_in, tt_in,
                              ttaus_in, ws, d_t, d_taus, t_t, t_taus,
                              opbuf, tbuf, outbuf, taubuf, sems, wbsem):
    """The batched megakernel: grid ``(B, levels, slots)`` — ONE
    pallas_call factors the whole stacked ``(B, p, q, nb, nb)`` workspace
    by replaying the SAME task table per batch element.  The flat task
    index (and with it the double-buffer parity and the prefetch chain)
    restarts at every batch boundary: the last slot of a schedule never
    prefetches (``_COL_PREFETCH`` is 0 there) and the first slot of the
    next element self-fetches (``_COL_FETCHED`` is 0), so batch elements
    are as isolated as levels are."""
    del ws_in, dt_in, dtaus_in, tt_in, ttaus_in  # aliased in place
    _megakernel_step(tab_ref, ws, d_t, d_taus, t_t, t_taus,
                     opbuf, tbuf, outbuf, taubuf, sems, wbsem,
                     lvl=pl.program_id(1), slot=pl.program_id(2),
                     nslots_axis=2, b=pl.program_id(0))


def _dispatch_megakernel(state: FactorState, p: int, q: int, nb: int,
                         interpret: bool) -> FactorState:
    table_np, nlevels, nslots = megakernel_task_table(p, q)
    dt = state.tiles.dtype
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nlevels, nslots),
        in_specs=[_any_spec()] * 5,
        out_specs=[_any_spec()] * 5,
        scratch_shapes=[
            pltpu.VMEM((2, 3, nb, nb), dt),   # double-buffered operand tiles
            pltpu.VMEM((2, nb, nb), dt),      # double-buffered T operand
            pltpu.VMEM((3, nb, nb), dt),      # write-back staging
            pltpu.VMEM((nb,), dt),            # taus staging
            pltpu.SemaphoreType.DMA((2, 4)),  # per (phase, operand) fetch
            pltpu.SemaphoreType.DMA,          # synchronous write-back
        ],
    )
    outs = pl.pallas_call(
        megakernel_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in state],
        input_output_aliases={1: 0, 2: 1, 3: 2, 4: 3, 5: 4},
        interpret=interpret,
    )(jnp.asarray(table_np.reshape(-1)), *state)
    return FactorState(*outs)


def _dispatch_megakernel_batched(state: FactorState, p: int, q: int,
                                 nb: int, interpret: bool) -> FactorState:
    """ONE pallas_call for a whole bucket: the single-matrix megakernel
    grid extended by a leading batch axis.  One task table (scalar
    prefetch) is shared across the batch; the per-step VMEM working set
    is batch-invariant (``macro_ops.batched_megakernel_vmem_bytes``)."""
    table_np, nlevels, nslots = megakernel_task_table(p, q)
    batch = state.tiles.shape[0]
    dt = state.tiles.dtype
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(batch, nlevels, nslots),
        in_specs=[_any_spec()] * 5,
        out_specs=[_any_spec()] * 5,
        scratch_shapes=[
            pltpu.VMEM((2, 3, nb, nb), dt),   # double-buffered operand tiles
            pltpu.VMEM((2, nb, nb), dt),      # double-buffered T operand
            pltpu.VMEM((3, nb, nb), dt),      # write-back staging
            pltpu.VMEM((nb,), dt),            # taus staging
            pltpu.SemaphoreType.DMA((2, 4)),  # per (phase, operand) fetch
            pltpu.SemaphoreType.DMA,          # synchronous write-back
        ],
    )
    outs = pl.pallas_call(
        megakernel_batched_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in state],
        input_output_aliases={1: 0, 2: 1, 3: 2, 4: 3, 5: 4},
        interpret=interpret,
    )(jnp.asarray(table_np.reshape(-1)), *state)
    return FactorState(*outs)


# ---------------------------------------------------------------------------
# the factor loop
# ---------------------------------------------------------------------------

def initial_state(tiles: Array, p: int, q: int, nb: int) -> FactorState:
    """The workspace ``tiles`` (``(..., p, q, nb, nb)``, any leading batch
    dims) with zeroed block-reflector state beside it."""
    r = min(p, q)
    lead = tiles.shape[:-4]
    dt = tiles.dtype
    return FactorState(
        tiles,
        jnp.zeros(lead + (r, nb, nb), dt),
        jnp.zeros(lead + (r, nb), dt),
        jnp.zeros(lead + (p, r, nb, nb), dt),
        jnp.zeros(lead + (p, r, nb), dt),
    )


def _factor_impl(tiles: Array, p: int, q: int, nb: int, use_kernel: bool,
                 interpret: bool, dispatch_mode: str = "wavefront"
                 ) -> FactorState:
    state = initial_state(tiles, p, q, nb)
    if use_kernel and dispatch_mode == "megakernel":
        with _profiler.annotate(_profiler.megakernel_label(p, q)):
            return _dispatch_megakernel(state, p, q, nb, interpret)
    for lv, by_kind in enumerate(wavefront_task_arrays(p, q)):
        if use_kernel:
            state = _pallas_wavefront(state, by_kind, nb, interpret, level=lv)
        else:
            with _profiler.annotate(f"wavefront@L{lv}"):
                state = _jnp_wavefront(state, by_kind)
    return state


_factor_jit = jax.jit(_factor_impl, static_argnums=(1, 2, 3, 4, 5, 6),
                      donate_argnums=(0,))


def _factor_batched_impl(tiles: Array, p: int, q: int, nb: int,
                         use_kernel: bool, interpret: bool,
                         dispatch_mode: str = "wavefront") -> FactorState:
    """Factor a stacked ``(B, p, q, nb, nb)`` workspace — per-slice
    BITWISE equal to B independent :func:`_factor_impl` runs.

    Megakernel mode extends the persistent kernel's grid by a leading
    batch axis (still exactly ONE ``pallas_call`` per bucket, one shared
    task table).  The wavefront and jnp lowerings vmap the single-matrix
    path — bitwise-clean because every per-task op keeps its task-batch
    shape under the outer vmap.  ``B == 1`` runs the single-matrix path
    directly: a batch-1 outer vmap lowers ``dot_general`` through a
    different contraction (the same quirk :func:`_batched` documents),
    which would break per-slice parity exactly in the degenerate case
    buckets hit most often.
    """
    batch = tiles.shape[0]
    if batch == 1:
        state = _factor_impl(tiles[0], p, q, nb, use_kernel, interpret,
                             dispatch_mode)
        return FactorState(*(x[None] for x in state))
    if use_kernel and dispatch_mode == "megakernel":
        state = initial_state(tiles, p, q, nb)
        with _profiler.annotate(_profiler.megakernel_label(p, q, batch)):
            return _dispatch_megakernel_batched(state, p, q, nb, interpret)
    return jax.vmap(
        lambda w: _factor_impl(w, p, q, nb, use_kernel, interpret,
                               dispatch_mode))(tiles)


_factor_batched_jit = jax.jit(_factor_batched_impl,
                              static_argnums=(1, 2, 3, 4, 5, 6),
                              donate_argnums=(0,))


def _emit_factor_metrics(tiles: Array, p: int, q: int, nb: int, mode: str,
                         use_kernel: bool, batch: int = 1) -> None:
    """Record one factor call in the ``engine.*`` metric series.

    Runs at Python-call time — which, when the entry point is reached
    from inside an outer ``jax.jit`` trace (``tiled_qr``, the serving
    bucket solvers), is *trace* time: the call happens once per compiled
    program, not once per execution.  The ``phase`` label makes that
    explicit ("trace" = counted at compile, replays are invisible;
    "execute" = counted per eager call)."""
    phase = "trace" if isinstance(tiles, jax.core.Tracer) else "execute"
    itemsize = jnp.dtype(tiles.dtype).itemsize
    kernel = "pallas" if use_kernel else "jnp"
    ndisp = 1 if (use_kernel and mode == "megakernel") else (
        sum(len(b) for b in wavefront_task_arrays(p, q)) * batch
        if use_kernel else 0)
    ntasks = task_count(p, q) * batch
    dma = modeled_dma_bytes(p, q, nb, itemsize)
    dma_mode = dma[mode] if use_kernel and mode in dma else dma["wavefront"]
    _metrics.counter("engine.factor_calls", mode=mode, kernel=kernel,
                     phase=phase).inc()
    _metrics.counter("engine.matrices", mode=mode, phase=phase).inc(batch)
    _metrics.counter("engine.dispatches", mode=mode, phase=phase).inc(ndisp)
    _metrics.counter("engine.tasks", mode=mode, phase=phase).inc(ntasks)
    _metrics.counter("engine.modeled_dma_bytes", mode=mode,
                     phase=phase).inc(dma_mode * batch)
    _metrics.counter("engine.roofline_dma_bytes", mode=mode,
                     phase=phase).inc(dma["roofline"] * batch)
    if use_kernel and mode == "megakernel":
        _metrics.gauge("engine.table_bytes", grid=f"{p}x{q}").set(
            megakernel_task_table(p, q)[0].nbytes)


def factor_tiles(tiles: Array, *, p: int, q: int, nb: int,
                 use_kernel: bool = False,
                 interpret: Optional[bool] = None,
                 dispatch_mode: Optional[str] = None) -> FactorState:
    """Run the full wavefront schedule over a ``(p, q, nb, nb)`` workspace.

    The workspace argument is **donated** — the engine factors in place
    and the caller's buffer is consumed (pass ``tiles.copy()`` to keep
    it).  ``use_kernel=True`` runs the Pallas lowering selected by
    ``dispatch_mode`` — ``"wavefront"`` (one in-place macro-op call per
    (wavefront, kind) batch), ``"megakernel"`` (the whole schedule as ONE
    persistent call over the scalar-prefetched task table with
    double-buffered tile DMA), or ``None`` for the budget-driven auto
    rule (:func:`resolve_dispatch_mode`).  ``interpret=None`` resolves
    via the ``macro_ops`` kernel policy: compiled on TPU, interpret
    elsewhere.  ``use_kernel=False`` runs the bitwise-identical jnp
    oracle lowering of the same schedule (``dispatch_mode`` is then
    irrelevant — there is no kernel to dispatch).
    """
    if tiles.ndim != 4 or tiles.shape[:2] != (p, q) \
            or tiles.shape[2:] != (nb, nb):
        raise ValueError(
            f"expected a ({p}, {q}, {nb}, {nb}) tile workspace, "
            f"got {tiles.shape}")
    mode = _check_dispatch(tiles.dtype, p, q, nb, use_kernel, dispatch_mode)
    if interpret is None:
        interpret = macro_ops.default_interpret()
    _emit_factor_metrics(tiles, p, q, nb, mode, bool(use_kernel))
    return _factor_jit(tiles, p, q, nb, bool(use_kernel), bool(interpret),
                       mode)


def _check_dispatch(dtype, p: int, q: int, nb: int, use_kernel: bool,
                    dispatch_mode: Optional[str], batched: bool = False
                    ) -> str:
    """Shared mode resolution + budget guards of the factor entry points.

    Returns the concrete dispatch mode; raises when a *forced* mode does
    not fit its VMEM / task-table budget (auto never picks past them).
    The batched lowering changes neither limit: the batch axis is an
    outer sequential grid dimension over one shared table, so the
    per-step working set and the scalar-prefetch bytes are
    batch-invariant (``macro_ops.batched_megakernel_vmem_bytes``)."""
    if dispatch_mode not in (None,) + DISPATCH_MODES:
        raise ValueError(
            f"unknown dispatch_mode {dispatch_mode!r}; expected one of "
            f"{DISPATCH_MODES} or None (auto)")
    from repro.robustness import inject as _inject

    if _inject.enabled():
        # Chaos hook: a forced VMEM-budget rejection fires from the
        # exact site a real over-budget workspace raises (trace time,
        # Python level — no jaxpr impact), so the escalation ladder
        # sees an indistinguishable failure.
        _inject.check("vmem", f"p{p}q{q}nb{nb}:{dispatch_mode}")
    mode = "wavefront"
    if use_kernel:
        from repro.core.plan import kernel_table_budget, kernel_vmem_budget

        itemsize = jnp.dtype(dtype).itemsize
        mode = (resolve_dispatch_mode(p, q, nb, itemsize)
                if dispatch_mode is None else dispatch_mode)
        if mode == "megakernel":
            need = (macro_ops.batched_megakernel_vmem_bytes(nb, itemsize)
                    if batched else
                    macro_ops.megakernel_vmem_bytes(nb, itemsize))
        else:
            need = macro_ops.engine_vmem_bytes(nb, itemsize)
        budget = kernel_vmem_budget("macro_ops")
        if need > budget:
            raise ValueError(
                f"tile ({nb},{nb}) exceeds the {mode} VMEM budget "
                f"({need} > {budget}); shrink the tile")
        if mode == "megakernel":
            # The scalar-prefetch side of the same contract: a forced
            # megakernel must also fit its task table (auto never picks
            # it past the budget, and an oversized table would only fail
            # opaquely at Mosaic compile time).
            tbudget = kernel_table_budget("macro_ops")
            fits, tbytes = table_fits(p, q, tbudget)
            if not fits:
                raise ValueError(
                    f"({p}, {q}) grid's megakernel task table "
                    f"(>= {tbytes} bytes) exceeds the scalar-prefetch "
                    f"budget ({tbudget}); grow the tile or use "
                    f"dispatch_mode='wavefront'")
    return mode


def factor_tiles_batched(tiles: Array, *, p: int, q: int, nb: int,
                         use_kernel: bool = False,
                         interpret: Optional[bool] = None,
                         dispatch_mode: Optional[str] = None) -> FactorState:
    """Run the full wavefront schedule over a stacked ``(B, p, q, nb, nb)``
    workspace — B independent factorizations in one dispatch, the
    serving layer's batched entry point (:mod:`repro.serving.qr_service`).

    Per batch slice the result is **bitwise** equal to
    :func:`factor_tiles` on that slice (asserted across the conformance
    matrix in tests/test_qr_service.py and tests/test_conformance.py).
    On the kernel path, ``dispatch_mode="megakernel"`` extends the
    persistent kernel's grid by a leading batch axis — still exactly ONE
    ``pallas_call`` for the whole bucket, sharing one scalar-prefetched
    task table across the batch; ``"wavefront"`` and the jnp-oracle
    lowering (``use_kernel=False``) vmap the single-matrix path.  As in
    :func:`factor_tiles`, the workspace argument is **donated**.
    """
    if tiles.ndim != 5 or tiles.shape[1:3] != (p, q) \
            or tiles.shape[3:] != (nb, nb):
        raise ValueError(
            f"expected a (B, {p}, {q}, {nb}, {nb}) stacked tile "
            f"workspace, got {tiles.shape}")
    if tiles.shape[0] < 1:
        raise ValueError("batched workspace needs at least one slice")
    mode = _check_dispatch(tiles.dtype, p, q, nb, use_kernel, dispatch_mode,
                           batched=True)
    if interpret is None:
        interpret = macro_ops.default_interpret()
    _emit_factor_metrics(tiles, p, q, nb, mode, bool(use_kernel),
                         batch=int(tiles.shape[0]))
    return _factor_batched_jit(tiles, p, q, nb, bool(use_kernel),
                               bool(interpret), mode)
