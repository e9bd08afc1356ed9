"""repro.tuning.sweep — measure candidate planner configs per shape class.

    PYTHONPATH=src python -m repro.tuning.sweep --out tuning_cache.json
    PYTHONPATH=src python -m repro.tuning.sweep --smoke --check

For each swept shape class the sweep builds the candidate set
(method x block x dispatch_mode), prunes it structurally (capability
guards, the engine's task-table/VMEM budgets via
:func:`repro.core.engine.explain_dispatch_mode`) and against the
roofline model (:func:`repro.launch.roofline.modeled_seconds` over
:func:`qr_flops` + :func:`repro.core.engine.modeled_dma_bytes` — a
candidate whose modeled lower bound already loses by ``PRUNE_FACTOR``x
is never timed), measures wall time on the **actual** backend
(warm-then-min-of-reps), and records a
:class:`repro.tuning.cache.TuningEntry` whose best pick the planner's
``"tuned"`` routing rule consults.

The heuristic pick (``select_method`` with the cache disabled) is always
measured, so "tuned is never slower than heuristic on swept shapes" is a
same-run comparison CI can gate on (``--check``); ``--baseline`` adds a
tolerance-banded drift gate against a committed cache's recorded
timings (catches a kernel change regressing the previously-measured
best config).

Sweeps time ``mode="r"`` (the factorization core — Q formation is mode-
specific and excluded, so ``q_method`` stays at its default in the
candidate grid); the measured mode is recorded in the entry provenance.
Kernel-path candidates are swept only where the kernel compiles
(TPU) — interpret-mode Pallas timings on CPU are not a serving
configuration and would dominate the sweep budget for nothing.

The sweep emits ``tuning.*`` metrics (candidates measured/pruned/
skipped, per-candidate wall histograms) and ``tuning.sweep`` /
``tuning.shape`` trace spans when observability is enabled.
"""

from __future__ import annotations

import argparse
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.observability import metrics as _metrics
from repro.observability import trace as _trace
from repro.tuning.cache import (DEFAULT_CACHE_PATH, TunedConfig, TuningCache,
                                TuningEntry, shape_class)

__all__ = [
    "DEFAULT_SHAPES",
    "SMOKE_SHAPES",
    "PRUNE_FACTOR",
    "candidates",
    "modeled_bound_us",
    "prune_candidates",
    "measure_candidate",
    "sweep_shapes",
    "check_cache",
    "main",
]

#: Square shape classes the committed default cache covers — around the
#: CPU tiled-vs-blocked crossover the heuristics hard-code at 512
#: (_TILED_MIN_DIM_CPU), which is exactly the guess the cache replaces.
DEFAULT_SHAPES: Tuple[Tuple[int, int], ...] = (
    (256, 256), (384, 384), (512, 512))

#: Reduced grid for the CI smoke gate.
SMOKE_SHAPES: Tuple[Tuple[int, int], ...] = ((256, 256), (512, 512))

#: Candidates whose roofline lower bound already exceeds the best
#: candidate's bound by this factor are pruned unmeasured.  Deliberately
#: loose: the model ranks asymptotics (it cannot see constant factors),
#: so only order-of-magnitude losers are dropped.
PRUNE_FACTOR = 32.0

_TILED_BLOCKS = (32, 64)


def _heuristic_config(m: int, n: int, dtype, backend: str):
    """The planner's pick with the tuning cache pinned off — the
    baseline every tuned pick is measured against."""
    from repro.core.plan import QRConfig, plan

    solver = plan((m, n), dtype, QRConfig(mode="r", use_tuning_cache=False),
                  backend=backend)
    return solver.config


def candidates(m: int, n: int, dtype, backend: str
               ) -> List[Tuple[str, "object"]]:
    """The ``(label, QRConfig)`` candidate grid for one shape class —
    structurally pruned (capability guards, engine budgets) but not yet
    roofline-pruned.  Always includes the heuristic pick."""
    from repro.core import engine
    from repro.core.plan import QRConfig, available_methods

    reg = available_methods()
    base = dict(mode="r", use_tuning_cache=False)
    out: List[Tuple[str, QRConfig]] = []

    for meth in ("geqrf", "geqrf_ht"):
        if meth in reg:
            out.append((meth, QRConfig(method=meth, **base)))
    # Unblocked MHT is O(m n^2) with no blocking — only plausible when
    # the matrix is at most a few panels tall.
    if "geqr2_ht" in reg and min(m, n) <= 128:
        out.append(("geqr2_ht", QRConfig(method="geqr2_ht", **base)))
    if "tsqr" in reg and n >= 1 and m >= 4 * n:
        out.append(("tsqr", QRConfig(method="tsqr", **base)))
    if "tiled" in reg:
        itemsize = np.dtype(dtype).itemsize
        for b in _TILED_BLOCKS:
            if min(m, n) < 2 * b:
                continue  # fewer than 2 tiles per side: no wavefront
            out.append((f"tiled[b{b}]",
                        QRConfig(method="tiled", block=b, use_kernel=False,
                                 **base)))
            if backend != "tpu":
                continue  # interpret-mode Pallas is not a serving config
            from repro.core.tilegraph import tile_grid

            p, q = tile_grid(m, n, b)
            out.append((f"tiled[b{b},wavefront]",
                        QRConfig(method="tiled", block=b, use_kernel=True,
                                 dispatch_mode="wavefront", **base)))
            mode, _ = engine.explain_dispatch_mode(p, q, b, itemsize)
            if mode == "megakernel":  # budget-pruned otherwise
                out.append((f"tiled[b{b},megakernel]",
                            QRConfig(method="tiled", block=b,
                                     use_kernel=True,
                                     dispatch_mode="megakernel", **base)))

    heur = _heuristic_config(m, n, dtype, backend)
    if not any(_cand_key(cfg) == _cand_key(heur) for _, cfg in out):
        out.append((f"heuristic:{heur.method}", heur))
    return out


def _cand_key(cfg) -> Tuple:
    """Dedup key: the knobs that change what actually runs.  Normalizes
    ``use_kernel=None`` (planner resolves it to False off-TPU) so the
    heuristic pick dedups against the equivalent grid candidate."""
    return (cfg.method, cfg.block, bool(cfg.use_kernel), cfg.dispatch_mode,
            cfg.q_method)


def modeled_bound_us(cfg, m: int, n: int, dtype) -> float:
    """Roofline lower bound (us) on one solve: max(compute, HBM) time
    from the analytic QR flop count and the candidate's modeled traffic
    (the engine's per-dispatch-mode DMA model for tiled; compulsory
    read+write for the dense methods)."""
    from repro.core import engine
    from repro.launch.roofline import MODEL_DEVICE, modeled_seconds, qr_flops

    itemsize = np.dtype(dtype).itemsize
    flops = qr_flops(m, n)
    if cfg.method == "tiled":
        from repro.core.tilegraph import tile_grid

        nb = min(cfg.block, m, n)
        p, q = tile_grid(m, n, nb)
        dma = engine.modeled_dma_bytes(p, q, nb, itemsize)
        key = cfg.dispatch_mode if (cfg.use_kernel and cfg.dispatch_mode
                                    in dma) else "wavefront"
        hbm = dma[key]
    elif cfg.method in ("geqr2", "geqr2_ht"):
        # Unblocked: every reflector re-streams the trailing matrix.
        hbm = 2.0 * min(m, n) * m * n * itemsize / 2.0
    else:
        hbm = 2.0 * (m * n + m * min(m, n) + min(m, n) * n) * itemsize
    # Relative pruning: the bound's shape, modeled on one chip's peaks
    # whatever backend the sweep measures on.
    return 1e6 * modeled_seconds(flops, hbm, device_kind=MODEL_DEVICE)


def prune_candidates(cands: Sequence[Tuple[str, "object"]], m: int, n: int,
                     dtype) -> List[Tuple[str, "object"]]:
    """Drop candidates whose modeled lower bound already loses by
    :data:`PRUNE_FACTOR`x — logged, counted, never silently."""
    bounds = {label: modeled_bound_us(cfg, m, n, dtype)
              for label, cfg in cands}
    floor = min(bounds.values())
    kept = []
    for label, cfg in cands:
        if bounds[label] > PRUNE_FACTOR * floor:
            _metrics.counter("tuning.candidates", status="pruned").inc()
            print(f"  pruned {label}: modeled {bounds[label]:.0f} us > "
                  f"{PRUNE_FACTOR:g}x floor {floor:.0f} us", file=sys.stderr)
        else:
            kept.append((label, cfg))
    return kept


def measure_candidate(cfg, a, reps: int = 3) -> Optional[float]:
    """Min wall time (us) over ``reps`` warm solves (min, not mean: the
    fastest rep is the least scheduler-noise-contaminated estimate of
    the config's cost, which is what the ranking needs); None when the
    plan is infeasible for this shape (capability ValueError)."""
    from repro.core.plan import plan

    try:
        solver = plan(a.shape, a.dtype, cfg)
        jax.block_until_ready(solver.solve(a))  # compile
        jax.block_until_ready(solver.solve(a))  # warm caches
        walls = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(solver.solve(a))
            walls.append(time.perf_counter() - t0)
        return float(min(walls) * 1e6)
    except ValueError as e:
        _metrics.counter("tuning.candidates", status="skipped").inc()
        print(f"  skipped {cfg.method}: {e}", file=sys.stderr)
        return None


def sweep_shapes(shapes: Sequence[Tuple[int, int]], *,
                 dtype=jnp.float32, reps: int = 3,
                 backend: Optional[str] = None,
                 smoke: bool = False) -> TuningCache:
    """Measure every candidate on every shape class; return the cache."""
    backend = jax.default_backend() if backend is None else backend
    device_kind = (jax.devices()[0].device_kind
                   if backend == jax.default_backend() else backend)
    rng = np.random.default_rng(0)
    out = TuningCache(source="sweep")
    dt = str(np.dtype(dtype))

    with _trace.span("tuning.sweep", backend=backend, shapes=len(shapes)):
        for m, n in shapes:
            cls = shape_class(m, n)
            label_cls = f"{cls[0]}x{cls[1]}"
            print(f"sweep {m}x{n} (class {label_cls}, {backend}/{dt})",
                  file=sys.stderr)
            _metrics.counter("tuning.sweeps", backend=backend).inc()
            heur = _heuristic_config(cls[0], cls[1], dtype, backend)
            with _trace.span("tuning.shape", cls=label_cls):
                cands = prune_candidates(
                    candidates(cls[0], cls[1], dtype, backend),
                    cls[0], cls[1], dtype)
                a = jnp.asarray(rng.standard_normal(cls, dtype=np.float32)
                                ).astype(dtype)
                timings: Dict[str, float] = {}
                for label, cfg in cands:
                    us = measure_candidate(cfg, a, reps)
                    if us is None:
                        continue
                    timings[label] = us
                    _metrics.counter("tuning.candidates",
                                     status="measured").inc()
                    _metrics.histogram("tuning.candidate_wall_us",
                                       cls=label_cls).observe(us)
                    print(f"  {label:<24s} {us:10.0f} us", file=sys.stderr)
            if not timings:
                print(f"  no measurable candidate for {label_cls} — "
                      "class skipped", file=sys.stderr)
                continue
            best_label = min(timings, key=timings.get)
            best_cfg = dict(cands)[best_label]
            heur_label = next((lb for lb, c in cands
                               if _cand_key(c) == _cand_key(heur)), None)
            heur_us = timings.get(heur_label, float("nan"))
            entry = TuningEntry(
                backend=backend, device_kind=device_kind,
                shape_class=cls, dtype=dt,
                best=TunedConfig(
                    method=best_cfg.method, block=best_cfg.block,
                    dispatch_mode=best_cfg.dispatch_mode,
                    q_method=best_cfg.q_method,
                    use_kernel=bool(best_cfg.use_kernel)),
                best_us=timings[best_label],
                heuristic_method=heur.method, heuristic_us=heur_us,
                timings=tuple(sorted(timings.items())),
                provenance=tuple(sorted({
                    "generated_by": "repro.tuning.sweep",
                    "mode": "r", "reps": str(reps),
                    "smoke": str(bool(smoke)).lower(),
                }.items())),
            )
            out.add(entry)
            _metrics.counter("tuning.entries", backend=backend).inc()
            print(f"  best: {best_label} ({entry.best_us:.0f} us) vs "
                  f"heuristic {heur.method} ({heur_us:.0f} us)",
                  file=sys.stderr)
    return out


def check_cache(fresh: TuningCache, baseline: Optional[TuningCache] = None,
                *, heuristic_tol: float = 0.05,
                drift_tol: float = 5.0) -> List[str]:
    """The CI gate.  Returns problem strings (empty = pass).

    Per fresh entry: the tuned pick must not be slower than the measured
    heuristic pick (same-run comparison; ``heuristic_tol`` absorbs timer
    noise — the argmin construction makes big violations impossible, so
    this mostly guards hand-edited caches).  With a ``baseline`` (the
    committed cache), the fresh measurement of the baseline's best config
    must stay within ``drift_tol``x of its recorded time — a kernel
    change that slowed a previously-measured winner fails here.  The
    band is generous because CI runners and dev machines differ.
    """
    problems = []
    for e in fresh.entries():
        if np.isfinite(e.heuristic_us) and \
                e.best_us > e.heuristic_us * (1.0 + heuristic_tol):
            problems.append(
                f"{e.backend}:{e.shape_class}: tuned {e.best.method} "
                f"{e.best_us:.0f} us slower than heuristic "
                f"{e.heuristic_method} {e.heuristic_us:.0f} us")
        if baseline is None:
            continue
        b = baseline.lookup(backend=e.backend, m=e.shape_class[0],
                            n=e.shape_class[1], dtype=e.dtype,
                            device_kind=e.device_kind)
        if b is None:
            continue
        base_best_label = next((lb for lb, _ in b.timings
                                if lb == _best_label(b)), _best_label(b))
        fresh_us = e.timings_dict.get(base_best_label)
        if fresh_us is not None and fresh_us > b.best_us * drift_tol:
            problems.append(
                f"{e.backend}:{e.shape_class}: committed best "
                f"{base_best_label} regressed {b.best_us:.0f} -> "
                f"{fresh_us:.0f} us (> {drift_tol:g}x band)")
    return problems


def _best_label(entry: TuningEntry) -> str:
    td = entry.timings_dict
    return min(td, key=td.get) if td else entry.best.method


def _parse_shapes(text: str) -> Tuple[Tuple[int, int], ...]:
    out = []
    for part in text.split(","):
        m, n = part.lower().split("x")
        out.append((int(m), int(n)))
    return tuple(out)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="measure candidate QR configs per shape class and "
                    "write the planner tuning cache")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="where to write the cache JSON")
    ap.add_argument("--shapes", default=None,
                    help="comma-separated MxN list (default: full grid)")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced CI grid (%s)" % (SMOKE_SHAPES,))
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--check", action="store_true",
                    help="fail (exit 1) when a tuned pick is slower than "
                         "the heuristic pick or the baseline regressed")
    ap.add_argument("--baseline", default=DEFAULT_CACHE_PATH, metavar="PATH",
                    help="committed cache the drift gate compares against")
    ap.add_argument("--heuristic-tol", type=float, default=0.05)
    ap.add_argument("--drift", type=float, default=5.0,
                    help="allowed factor vs the baseline's recorded times")
    args = ap.parse_args(argv)

    shapes = (_parse_shapes(args.shapes) if args.shapes
              else SMOKE_SHAPES if args.smoke else DEFAULT_SHAPES)
    cache = sweep_shapes(shapes, dtype=jnp.dtype(args.dtype),
                         reps=args.reps, smoke=args.smoke)
    if args.out:
        cache.save(args.out)
        print(f"wrote {len(cache)} entries to {args.out}", file=sys.stderr)
    if args.check:
        baseline = None
        try:
            baseline = TuningCache.load(args.baseline)
        except (FileNotFoundError, ValueError):
            print(f"no usable baseline at {args.baseline}; "
                  "heuristic gate only", file=sys.stderr)
        problems = check_cache(cache, baseline,
                               heuristic_tol=args.heuristic_tol,
                               drift_tol=args.drift)
        for p in problems:
            print(f"GATE: {p}", file=sys.stderr)
        if problems:
            return 1
        print("tuning gate passed: tuned picks beat (or tie) heuristics "
              f"on all {len(cache)} swept classes", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
