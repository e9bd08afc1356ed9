"""repro.core.plan — typed QR planning: QRConfig, method registry, QRSolver.

The paper's contribution is a *family* of QR realizations (HT, MHT,
blocked WY, TSQR, Pallas kernel-backed variants) whose relative merit
depends on shape, aspect ratio, and hardware.  This module centralizes
that selection problem once, instead of string dispatch scattered across
call sites:

  * :class:`QRConfig` — a frozen, hashable description of *how* to
    factorize (method, block size, kernel policy, precision, sign fixing,
    Q mode).  Safe to use as a ``jax.jit`` static argument.
  * a **method registry** — every realization registers capability
    metadata (:class:`MethodSpec`) via :func:`register_method`;
    :mod:`repro.core.householder`, :mod:`repro.core.mht`,
    :mod:`repro.core.blocked`, :mod:`repro.core.tsqr`,
    :mod:`repro.core.tilegraph` and :mod:`repro.kernels.ops` /
    ``tile_ops`` self-register at import.  New backends plug in here
    instead of growing another ``if method == ...`` chain.
  * :func:`plan` — resolve ``(shape, dtype, config)`` to a concrete
    :class:`QRSolver`, applying the ``method="auto"`` heuristics
    (tall-skinny => TSQR with planner-chosen ``nblocks``, large
    near-square => tiled task-graph, near-square past the single-device
    tiled ceiling with more than one device => sharded_tiled,
    panel-fits-VMEM on TPU => kernel-backed ``geqrf_ht``, single-panel
    problems => unblocked MHT) and the kernel dispatch policy.
  * :class:`QRSolver` — ``solve`` / ``factor`` / ``lstsq`` on concrete
    shapes, with batched inputs (``a.ndim > 2``) handled by a vmap rule.

Tiled QR task graph
-------------------
``method="tiled"`` (:mod:`repro.core.tilegraph`) decomposes the
factorization into a DAG of tile tasks (GEQRT / TSQRT / LARFB / SSRFB)
over an nb x nb tile grid, levelizes it statically, and executes the
schedule through the wavefront macro-op engine
(:mod:`repro.core.engine`): with ``use_kernel=True`` each level's
same-kind task batch is a **single in-place Pallas dispatch** over a
``(p, q, nb, nb)`` tile workspace (macro-op bodies from the unified
:mod:`repro.kernels.macro_ops` library; interpret mode off-TPU), and
with ``use_kernel=False`` the bitwise-identical vmapped jnp oracle of
the same bodies — cross-panel parallelism the blocked methods serialize
away either way.  On the kernel path ``QRConfig.dispatch_mode`` selects
the engine lowering: ``"wavefront"`` (per-level dispatches) or
``"megakernel"`` (the whole schedule as ONE persistent Pallas call over
a scalar-prefetched task table with double-buffered tile DMA); ``None``
lets the planner pick megakernel whenever the table and the working set
fit the ``"macro_ops"`` policy budgets.  ``QRConfig.block`` doubles as
the tile size; the ``method="auto"`` heuristic routes large near-square
matrices (dims in [256, 2048], aspect < 4 — the upper bound keeps the
symbolic DAG small at the default tile) there.  The engine's VMEM and
task-table accounting is the ``"macro_ops"`` kernel policy.

Sharded tiled QR (multi-device)
-------------------------------
``method="sharded_tiled"`` (:mod:`repro.core.distgraph`) distributes
the tile grid across a 1-D device mesh: each device runs domain-local
wavefronts on its contiguous row-block of tiles under ``shard_map``,
and the per-domain R factors merge through a TSQR-style butterfly tree
(cross-device critical path O(p/d + 2q + log d) wavefronts).
``QRConfig.ndomains`` requests the domain count (default: all local
devices; execution rounds down to a power of two and caps at the
tile-row count — ``ndomains=1`` IS the tiled backend, bit for bit).
``method="auto"`` routes near-square matrices past the single-device
tiled ceiling there when more than one device is available.  Runs on
CPU with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

VMEM budget
-----------
Kernel backends register a :class:`KernelPolicy` carrying their VMEM
working-set estimator *and* the budget they enforce, so the planner's
fits-in-VMEM decisions and the kernel wrappers' runtime guards agree on
one number (:data:`DEFAULT_VMEM_BUDGET`, via :func:`kernel_vmem_budget`).

:mod:`repro.core.api` provides the thin user-facing wrappers
(``qr`` / ``orthogonalize`` / ``lstsq`` / ``qr_algorithm_eig``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.observability import metrics as _metrics

Array = jax.Array

__all__ = [
    "QRConfig",
    "MethodSpec",
    "KernelPolicy",
    "QRSolver",
    "PlanExplain",
    "RouteDecision",
    "plan",
    "select_method",
    "register_method",
    "unregister_method",
    "register_kernel_policy",
    "get_method",
    "available_methods",
    "kernel_vmem_budget",
    "kernel_table_budget",
    "DEFAULT_VMEM_BUDGET",
    "DEFAULT_TABLE_BUDGET",
    "f32_products",
    "sign_fix_qr",
    "sign_fix_r",
]

_MODES = ("reduced", "r", "full")
_Q_METHODS = ("formq", "solve")

# The single VMEM working-set budget (half of v5e VMEM, double-buffer
# room).  Kernel backends register policies carrying this value, so the
# planner's fits-in-VMEM checks and the kernel wrappers' runtime guards
# cannot drift apart.
DEFAULT_VMEM_BUDGET = 8 * 1024 * 1024

# Scoped VMEM the panel and trailing kernels ask the TPU compiler for.
# Its default (16 MiB on v5e, of 128 MiB) is too small for a working set
# at the budget above once Pallas double-buffers the blocks and the body
# adds its temporaries: wy_trailing at m=4096 needs 16.7 MiB.
KERNEL_VMEM_LIMIT = 4 * DEFAULT_VMEM_BUDGET

# Scalar-prefetch (SMEM) budget for persistent task tables — the limit
# the engine's megakernel dispatch mode must fit its flattened schedule
# into.  A v5e core has 1 MiB of SMEM (the limit its compiler reports);
# the largest square grid this budget admits, 21x21 (480 KiB), compiles
# for v5e (tests/test_tpu_compile.py).
DEFAULT_TABLE_BUDGET = 512 * 1024

# Matrices at least this large on their short side (and near-square, see
# select_method) route to the tiled task-graph backend under "auto".  The
# upper bound keeps the symbolic task DAG tractable: task count grows as
# O(p q min(p, q)) in the tile-grid dims, so unboundedly large inputs
# stay on the blocked path unless the caller opts into tiled explicitly
# (with a correspondingly larger tile).
_TILED_MIN_DIM = 256
_TILED_MAX_DIM = 2048
_TILED_MAX_ASPECT = 4.0

# On CPU the tiled backend runs through the jnp task-graph oracle and
# has to beat multithreaded LAPACK geqrf, which it only does once the
# wavefront is wide enough to amortize per-task overhead: at 256^2 the
# measured wall is ~2.2x geqrf (see ROADMAP smoke table), crossing over
# near 512.  Keep the 256 floor where the kernel path exists.
# NOTE this constant is now the *fallback* behind the measured tuning
# cache (repro.tuning): on swept shape classes the first-priority
# "tuned" rule routes by real wall times and this guess never fires —
# it only governs cache misses and use_tuning_cache=False plans.
_TILED_MIN_DIM_CPU = 512

# Near-square matrices past the single-device tiled ceiling route to the
# multi-device sharded_tiled backend when more than one device is
# available: each device owns a contiguous row-block domain of the tile
# grid (its local DAG stays within the single-device budget) and the
# domains merge through a TSQR-style reduction tree over R factors.
_SHARDED_MAX_DOM_FACTOR = 8  # auto ceiling: _TILED_MAX_DIM * min(d, factor)


@dataclasses.dataclass(frozen=True)
class QRConfig:
    """Hashable description of a QR realization (``jax.jit``-static safe).

    Fields left at their "decide for me" default (``method="auto"``,
    ``use_kernel=None``, ``nblocks=None``) are resolved by :func:`plan`
    into concrete values on the returned solver's ``config``.

    method:     registry name, or ``"auto"`` for shape/hardware heuristics
    block:      WY panel width for blocked methods (local QR block in TSQR)
    use_kernel: Pallas kernel policy — True force, False never,
                None => auto (TPU and the panel working set fits VMEM)
    nblocks:    TSQR tree leaf count; None => planner picks a divisor of m
    precision:  optional compute-dtype override, e.g. ``"float32"``
    sign_fix:   multiply Q columns (and R rows) by sign(diag R) so the
                factor is a deterministic, continuous function of the input
    mode:       Q mode — "reduced" (thin Q, R), "r" (R only), "full"
    q_method:   how thin Q materializes — "formq" (reflector accumulation,
                exact even for singular input) or "solve" (Q = A R^{-1},
                one dense op; tall matrices only)
    refine:     CQR2-style second pass for TSQR thin-Q orthogonality
    ndomains:   device-domain count for ``sharded_tiled`` (row-block
                domains of the tile grid, one per device); None => the
                planner uses every local device.  Execution rounds down
                to a power of two and caps at the available device count
                and the tile-row count; ``ndomains=1`` is exactly the
                single-device tiled backend.
    dispatch_mode: kernel lowering of the wavefront engine's schedule
                (tiled / sharded_tiled on their kernel paths) —
                "wavefront" (one in-place Pallas dispatch per DAG
                level), "megakernel" (the whole schedule as ONE
                persistent Pallas call over a scalar-prefetched task
                table with double-buffered tile DMA), or None => the
                planner resolves it (megakernel when the task table and
                the double-buffered working set fit the "macro_ops"
                policy budgets, wavefront otherwise).  Both lowerings
                are bitwise-identical to the jnp oracle.
    use_tuning_cache: consult the measured tuning cache
                (:mod:`repro.tuning`) before the static ``method="auto"``
                heuristics.  On a cache hit the measured best config
                overrides exactly the knobs the caller left at their
                defaults (method, block, dispatch_mode, q_method,
                use_kernel); on a miss — or with False — routing falls
                through to the heuristic rules, recording why.
    verify:     post-dispatch health checks (relative residual +
                orthogonality defect against the conformance tolerance
                rule, :mod:`repro.robustness.verify`) with escalation
                down the degradation ladder on failure.  Tri-state:
                True/False force it; None (default) defers to the
                ``REPRO_VERIFY`` environment default.  Resolution is
                host-side and skipped under traces, so the off (and
                traced) paths are jaxpr-identical to an unchecked
                solve — pinned in tests/test_robustness.py.
    """

    method: str = "auto"
    block: int = 32
    use_kernel: Optional[bool] = None
    nblocks: Optional[int] = None
    precision: Optional[str] = None
    sign_fix: bool = False
    mode: str = "reduced"
    q_method: str = "formq"
    refine: bool = True
    ndomains: Optional[int] = None
    dispatch_mode: Optional[str] = None
    use_tuning_cache: bool = True
    verify: Optional[bool] = None

    def __post_init__(self):
        if self.mode not in _MODES:
            raise ValueError(f"unknown mode {self.mode!r}; expected one of {_MODES}")
        if self.dispatch_mode not in (None, "wavefront", "megakernel"):
            raise ValueError(
                f"unknown dispatch_mode {self.dispatch_mode!r}; expected "
                "'wavefront', 'megakernel', or None (auto)")
        if self.q_method not in _Q_METHODS:
            raise ValueError(
                f"unknown q_method {self.q_method!r}; expected one of {_Q_METHODS}")
        if self.block < 1:
            raise ValueError(f"block must be >= 1, got {self.block}")
        if self.nblocks is not None and self.nblocks < 1:
            raise ValueError(f"nblocks must be >= 1, got {self.nblocks}")
        if self.ndomains is not None and self.ndomains < 1:
            raise ValueError(f"ndomains must be >= 1, got {self.ndomains}")
        if self.verify not in (None, True, False):
            raise ValueError(
                f"verify must be True, False, or None (env default), "
                f"got {self.verify!r}")

    def replace(self, **changes) -> "QRConfig":
        return dataclasses.replace(self, **changes)


@dataclasses.dataclass(frozen=True)
class MethodSpec:
    """Capability metadata + entry points for one registered realization.

    factor:  ``(a, cfg) -> (packed, taus)`` in LAPACK packed layout, or
             None when the method has no packed form (e.g. TSQR).
    solve:   ``(a, cfg) -> (q, r) | r`` honoring cfg.mode/sign_fix; when
             None the planner derives it from ``factor``.
    solve_batched: optional native batched realization
             ``(a_bmn, cfg) -> (q, r) | r`` over one leading batch axis.
             When present, :meth:`QRSolver.solve` hands 3-D inputs here
             instead of vmapping ``solve`` — the tiled backend uses it to
             factor a whole stack through ONE
             :func:`repro.core.engine.factor_tiles_batched` dispatch
             (megakernel mode: one ``pallas_call`` for the stack).
             Deeper batch dims still vmap down to this rule.
    resolve: optional ``(m, n, cfg, *, dtype, explain, backend) -> cfg``
             hook filling method-specific fields (TSQR uses it to pick
             ``nblocks``; the tiled backends use ``dtype`` — the planned
             element width — to resolve the engine dispatch mode, and
             ``backend`` to align the kernel tile on TPU).  It may append
             :class:`RouteDecision` records to ``explain`` (a list, or
             None when no trail is kept).
    vmem_bytes: optional ``(m, n, cfg) -> bytes`` working-set estimator
             used by the kernel dispatch policy.
    kernel_policy: name of the :class:`KernelPolicy` whose budget gates
             this method's kernel dispatch (default "mht_panel").
    min_aspect: required m/n ratio (TSQR needs tall-skinny input).
    """

    name: str
    factor: Optional[Callable] = None
    solve: Optional[Callable] = None
    solve_batched: Optional[Callable] = None
    resolve: Optional[Callable] = None
    supports_full_q: bool = True
    min_aspect: float = 0.0
    batched: bool = True
    kernel_backed: bool = False
    vmem_bytes: Optional[Callable] = None
    kernel_policy: str = "mht_panel"
    description: str = ""


@dataclasses.dataclass(frozen=True)
class KernelPolicy:
    """Dispatch policy registered by a kernel backend (kernels.ops).

    table_budget: scalar-prefetch (SMEM) bytes available for persistent
    task tables; 0 means the backend has no megakernel-style lowering.
    """

    name: str
    vmem_bytes: Callable  # (m, b) -> working-set bytes
    vmem_budget: int
    default_interpret: Optional[Callable] = None  # () -> bool
    table_budget: int = 0


@dataclasses.dataclass(frozen=True)
class RouteDecision:
    """One machine-readable routing (or resolve) decision.

    rule:    stable slug — the routing rule or fallback reason
             ("tsqr_tall_skinny", "tiled_min_dim_cpu_floor",
             "megakernel_over_budget", ...)
    outcome: "selected" (this rule chose the method), "rejected" (rule
             evaluated and declined), "fallback" (a silent-degradation
             site fired — also counted in ``planner.fallbacks``), or
             "resolved" (a resolve hook recorded a concrete choice)
    reason:  the concrete threshold/budget arithmetic that fired
    """

    rule: str
    outcome: str
    reason: str


@dataclasses.dataclass(frozen=True)
class PlanExplain:
    """Why :func:`plan` chose what it chose — ``plan(..., explain=True)``.

    ``decisions`` holds every rule evaluated, in evaluation order;
    ``fallback_reasons`` are the ``rule`` slugs of the fallback-outcome
    decisions (the silent degradations the planner now surfaces — each
    also increments the ``planner.fallbacks{reason=...}`` counter).
    All fields are hashable; the record rides on the solver without
    affecting its equality or jit-static identity.
    """

    shape: Tuple[int, int]
    dtype: str
    backend: str
    ndevices: int
    requested_method: str
    method: str
    use_kernel: bool
    dispatch_mode: Optional[str]
    decisions: Tuple[RouteDecision, ...]
    fallback_reasons: Tuple[str, ...]

    def decision(self, rule: str) -> Optional[RouteDecision]:
        """The first decision recorded for ``rule`` (None if absent)."""
        for d in self.decisions:
            if d.rule == rule:
                return d
        return None

    @property
    def selected(self) -> Optional[RouteDecision]:
        """The decision that chose the method."""
        for d in self.decisions:
            if d.outcome == "selected":
                return d
        return None


_REGISTRY: Dict[str, MethodSpec] = {}
_KERNEL_POLICIES: Dict[str, KernelPolicy] = {}
_BUILTINS_LOADED = False


def _ensure_builtins() -> None:
    """Import the built-in realizations so they self-register.

    Registration happens at module import (each module calls
    :func:`register_method` at its bottom); this just guarantees the
    imports happened before a lookup, whatever the caller imported first.
    """
    global _BUILTINS_LOADED
    if _BUILTINS_LOADED:
        return
    _BUILTINS_LOADED = True
    import repro.core.householder  # noqa: F401
    import repro.core.mht  # noqa: F401
    import repro.core.blocked  # noqa: F401
    import repro.core.tsqr  # noqa: F401
    import repro.core.tilegraph  # noqa: F401
    import repro.core.distgraph  # noqa: F401
    try:
        import repro.kernels.ops  # noqa: F401  (kernel policy registration)
        import repro.kernels.tile_ops  # noqa: F401
    except ImportError:  # Pallas toolchain unavailable — jnp paths only.
        pass


def register_method(spec: MethodSpec) -> MethodSpec:
    """Register (or overwrite) a realization under ``spec.name``."""
    _REGISTRY[spec.name] = spec
    return spec


def unregister_method(name: str) -> None:
    _REGISTRY.pop(name, None)


def register_kernel_policy(policy: KernelPolicy) -> KernelPolicy:
    _KERNEL_POLICIES[policy.name] = policy
    return policy


def get_method(name: str) -> MethodSpec:
    _ensure_builtins()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown method {name!r}; expected one of {available_methods()}"
        ) from None


def available_methods() -> Tuple[str, ...]:
    _ensure_builtins()
    return tuple(sorted(_REGISTRY))


def kernel_vmem_budget(policy: str = "mht_panel") -> int:
    """The VMEM budget the named kernel backend enforces (its registered
    :class:`KernelPolicy`), falling back to :data:`DEFAULT_VMEM_BUDGET`."""
    pol = _KERNEL_POLICIES.get(policy)
    return pol.vmem_budget if pol is not None else DEFAULT_VMEM_BUDGET


def kernel_table_budget(policy: str) -> int:
    """Scalar-prefetch task-table budget of the named kernel policy —
    what the engine's ``dispatch_mode=None`` auto rule checks the
    flattened megakernel schedule against (0: no megakernel lowering)."""
    pol = _KERNEL_POLICIES.get(policy)
    return pol.table_budget if pol is not None else 0


# ---------------------------------------------------------------------------
# sign fixing (shared by the default solve path and TSQR)
# ---------------------------------------------------------------------------

def _pad_signs(signs: Array, size: int, dtype) -> Array:
    if size == signs.shape[0]:
        return signs.astype(dtype)
    return jnp.concatenate(
        [signs.astype(dtype), jnp.ones((size - signs.shape[0],), dtype)])


def sign_fix_qr(q: Array, r: Array) -> Tuple[Array, Array]:
    """Flip Q columns / R rows so diag(R) >= 0 (Q R product unchanged)."""
    signs = jnp.where(jnp.diagonal(r) >= 0, 1.0, -1.0)
    q = q * _pad_signs(signs, q.shape[1], q.dtype)[None, :]
    r = r * _pad_signs(signs, r.shape[0], r.dtype)[:, None]
    return q, r


def sign_fix_r(r: Array) -> Array:
    signs = jnp.where(jnp.diagonal(r) >= 0, 1.0, -1.0)
    return r * _pad_signs(signs, r.shape[0], r.dtype)[:, None]


# ---------------------------------------------------------------------------
# degenerate (zero-dim) shapes — jnp.linalg.qr semantics
# ---------------------------------------------------------------------------

def _solve_degenerate(a: Array, cfg: QRConfig):
    """QR of an empty matrix, matching ``jnp.linalg.qr`` exactly:
    with k = min(m, n) == 0, reduced Q is the (m, 0) identity slice and
    R is the (0, n) empty triangle; full Q is I_m with R all-zero.
    Every backend's tile/panel machinery divides by these extents, so
    the planner routes here before any of them can."""
    m, n = a.shape
    k = min(m, n)
    if cfg.mode == "r":
        return jnp.zeros((k, n), a.dtype)
    if cfg.mode == "reduced":
        return jnp.eye(m, k, dtype=a.dtype), jnp.zeros((k, n), a.dtype)
    return jnp.eye(m, dtype=a.dtype), jnp.zeros((m, n), a.dtype)


register_method(MethodSpec(
    name="degenerate",
    solve=_solve_degenerate,
    supports_full_q=True,
    batched=True,
    description="trivial zero-dim (m == 0 or n == 0) factorization with "
                "jnp.linalg.qr semantics — the planner's early-return for "
                "empty matrices",
))


# ---------------------------------------------------------------------------
# planning
# ---------------------------------------------------------------------------

# The "decide for me" defaults the tuned overlay respects: a measured
# config only overrides knobs the caller left untouched.
_DEFAULT_CONFIG = QRConfig()


def _apply_tuned_config(resolved: "QRConfig", requested: "QRConfig",
                        entry, decisions: List["RouteDecision"]
                        ) -> "QRConfig":
    """Overlay the measured best config onto the knobs the caller left at
    their defaults — explicit knobs always win over the cache.  Records a
    ``tuned_config`` resolve decision when anything changed."""
    best = entry.best
    applied = []
    if (requested.block == _DEFAULT_CONFIG.block
            and best.block != resolved.block):
        resolved = dataclasses.replace(resolved, block=best.block)
        applied.append(f"block={best.block}")
    if (requested.dispatch_mode is None and resolved.use_kernel
            and best.dispatch_mode is not None
            and best.dispatch_mode != resolved.dispatch_mode):
        resolved = dataclasses.replace(resolved,
                                       dispatch_mode=best.dispatch_mode)
        applied.append(f"dispatch_mode={best.dispatch_mode}")
    if (requested.q_method == _DEFAULT_CONFIG.q_method
            and best.q_method != resolved.q_method):
        resolved = dataclasses.replace(resolved, q_method=best.q_method)
        applied.append(f"q_method={best.q_method}")
    if requested.use_kernel is None and resolved.use_kernel:
        applied.append("use_kernel=True")
    if applied:
        decisions.append(RouteDecision(
            "tuned_config", "resolved",
            "measured config applied: " + ", ".join(applied)))
    return resolved

def _kernel_fits(spec: MethodSpec, m: int, n: int, cfg: QRConfig,
                 dtype=jnp.float32) -> bool:
    if spec.vmem_bytes is None:
        return False
    try:
        est = spec.vmem_bytes(m, n, cfg)
    except ImportError:  # kernel backend unavailable — jnp paths only
        return False
    # Estimators are written for fp32; scale to the planned element width.
    scale = np.dtype(dtype).itemsize / 4.0
    return est * scale <= kernel_vmem_budget(spec.kernel_policy)


# Canonical auto-routing rule order.  Trail-completeness contract
# (tests/test_plan.py): an auto plan's non-fallback decisions are exactly
# the prefix of this sequence ending at the selected rule — every rule
# evaluated before the winner records a "rejected" decision, on every
# path.  ("tiled_min_dim_cpu_floor" fallbacks and resolve-hook decisions
# interleave without participating in the prefix.)
_ROUTE_RULES = ("degenerate_empty", "explicit", "tuned", "tsqr_tall_skinny",
                "tiled_near_square", "sharded_past_ceiling",
                "tpu_kernel_panel_fits", "single_panel", "blocked_default")


def _tuned_lookup(m: int, n: int, dtype, config: QRConfig, backend: str,
                  batched: bool):
    """Consult the measured tuning cache: ``(decision, entry-or-None)``.

    A hit must also pass the capability guards the selected method will
    face in :func:`plan` (mode/batched/aspect) — an incompatible measured
    pick records a rejected decision and routing falls through, rather
    than planning a method that will raise."""
    if not config.use_tuning_cache:
        return RouteDecision(
            "tuned", "rejected",
            "use_tuning_cache=False pins the heuristic rules"), None
    from repro.tuning import cache as _tcache

    cache = _tcache.active_cache()
    if len(cache) == 0:
        return RouteDecision(
            "tuned", "rejected",
            f"no tuning cache loaded (source: {cache.source}) — "
            f"heuristic rules apply"), None
    cls = _tcache.shape_class(m, n)
    entry = cache.lookup(backend=backend, m=m, n=n, dtype=np.dtype(dtype))
    if entry is None:
        return RouteDecision(
            "tuned", "rejected",
            f"cache miss: no measured entry for shape-class "
            f"{cls[0]}x{cls[1]} ({backend}, {np.dtype(dtype)}) — "
            f"heuristic rules apply"), None
    best = entry.best
    spec = _REGISTRY.get(best.method)
    why_unfit = (
        f"tuned pick {best.method!r} is not registered" if spec is None else
        f"tuned pick {best.method!r} is thin-only vs mode='full'"
        if config.mode == "full" and not spec.supports_full_q else
        f"tuned pick {best.method!r} does not support batched inputs"
        if batched and not spec.batched else
        f"tuned pick {best.method!r} needs m >= {spec.min_aspect:g}n"
        if spec.min_aspect > 0 and m < spec.min_aspect * n else None)
    if why_unfit is not None:
        return RouteDecision("tuned", "rejected", why_unfit), None
    knobs = f"block={best.block}"
    if best.use_kernel:
        knobs += f", dispatch={best.dispatch_mode}"
    return RouteDecision(
        "tuned", "selected",
        f"measured: {best.method}[{knobs}] {entry.best_us:.0f} us vs "
        f"heuristic {entry.heuristic_method} {entry.heuristic_us:.0f} us "
        f"on {entry.backend}/{entry.device_kind} shape-class "
        f"{cls[0]}x{cls[1]} ({entry.dtype})"), entry


def _route(shape, dtype, config: QRConfig, backend: Optional[str],
           ndevices: Optional[int]):
    """The routing table with its reasoning:
    ``(method, decisions, tuned_entry)``.

    Rules evaluate in :data:`_ROUTE_RULES` order; EVERY rule evaluated
    before the winner records a :class:`RouteDecision` (selected or
    rejected) on every path, and the silent-degradation sites (the CPU
    tiled floor here; dispatch-mode and domain-count degradations in the
    resolve hooks) additionally record ``outcome="fallback"`` decisions
    (counted once per plan in :func:`plan` — this function is a pure
    query).  ``tuned_entry`` is the measured cache entry when the
    ``"tuned"`` rule won, else None.
    """
    _ensure_builtins()
    dec: List[RouteDecision] = []
    m, n = int(shape[-2]), int(shape[-1])

    if min(m, n) == 0:
        why = (f"zero-dim input {m}x{n} — trivial factorization with "
               f"jnp.linalg.qr semantics")
        if config.method not in ("auto", "degenerate"):
            why += (f" (overrides config.method={config.method!r}: no "
                    f"backend factors an empty matrix)")
        dec.append(RouteDecision("degenerate_empty", "selected", why))
        return "degenerate", dec, None
    if config.method != "auto":
        dec.append(RouteDecision(
            "explicit", "selected",
            f"config.method={config.method!r} bypasses auto routing"))
        return config.method, dec, None
    backend = jax.default_backend() if backend is None else backend
    ndevices = jax.local_device_count() if ndevices is None else int(ndevices)
    aspect = m / n if n else float("inf")

    tuned_dec, tuned = _tuned_lookup(m, n, dtype, config, backend,
                                     batched=len(shape) > 2)
    dec.append(tuned_dec)
    if tuned is not None:
        return tuned.best.method, dec, tuned

    tspec = _REGISTRY.get("tsqr")
    if (tspec is not None and config.mode != "full" and n >= 1 and m >= 8
            and m >= tspec.min_aspect * n):
        dec.append(RouteDecision(
            "tsqr_tall_skinny", "selected",
            f"aspect {aspect:.2f} >= {tspec.min_aspect:g} "
            f"({m}x{n}, mode={config.mode!r})"))
        return "tsqr", dec, None
    if tspec is not None:
        dec.append(RouteDecision(
            "tsqr_tall_skinny", "rejected",
            f"mode='full' needs full Q (tsqr is thin-only)"
            if config.mode == "full" else
            f"aspect {aspect:.2f} < {tspec.min_aspect:g} (or m={m} < 8)"))

    tiled_floor = _TILED_MIN_DIM_CPU if backend == "cpu" else _TILED_MIN_DIM
    near_square = (min(m, n) >= tiled_floor
                   and max(m, n) < _TILED_MAX_ASPECT * min(m, n))
    # Silent-degradation site: shapes that would route tiled on an
    # accelerator but sit under the measured CPU crossover floor.
    if (backend == "cpu" and "tiled" in _REGISTRY
            and _TILED_MIN_DIM <= min(m, n) < _TILED_MIN_DIM_CPU
            and max(m, n) < _TILED_MAX_ASPECT * min(m, n)
            and max(m, n) <= _TILED_MAX_DIM):
        dec.append(RouteDecision(
            "tiled_min_dim_cpu_floor", "fallback",
            f"min dim {min(m, n)} >= {_TILED_MIN_DIM} routes tiled "
            f"off-CPU, but < CPU floor {_TILED_MIN_DIM_CPU} (measured "
            f"LAPACK geqrf crossover) — falling through to blocked"))
    if "tiled" in _REGISTRY and near_square and max(m, n) <= _TILED_MAX_DIM:
        dec.append(RouteDecision(
            "tiled_near_square", "selected",
            f"min dim {min(m, n)} >= floor {tiled_floor} "
            f"({backend}), aspect {max(m, n) / min(m, n):.2f} < "
            f"{_TILED_MAX_ASPECT:g}, max dim {max(m, n)} <= "
            f"{_TILED_MAX_DIM}"))
        return "tiled", dec, None
    if "tiled" in _REGISTRY:
        dec.append(RouteDecision(
            "tiled_near_square", "rejected",
            f"min dim {min(m, n)} < floor {tiled_floor} ({backend})"
            if min(m, n) < tiled_floor else
            f"aspect {max(m, n) / min(m, n):.2f} >= {_TILED_MAX_ASPECT:g}"
            if max(m, n) >= _TILED_MAX_ASPECT * min(m, n) else
            f"max dim {max(m, n)} > single-device ceiling {_TILED_MAX_DIM}"))

    sharded_ceiling = _TILED_MAX_DIM * min(ndevices, _SHARDED_MAX_DOM_FACTOR)
    if ("sharded_tiled" in _REGISTRY and near_square and config.mode != "full"
            and len(shape) == 2  # no batched support (shard_map under vmap)
            and m >= n and ndevices > 1
            and max(m, n) <= sharded_ceiling):
        dec.append(RouteDecision(
            "sharded_past_ceiling", "selected",
            f"near-square {m}x{n} <= sharded ceiling {sharded_ceiling} "
            f"({ndevices} devices x {_TILED_MAX_DIM})"))
        return "sharded_tiled", dec, None
    if "sharded_tiled" in _REGISTRY:
        # Record the evaluation on EVERY path (a near-square shape under
        # the ceiling with one device used to silently omit this rule).
        dec.append(RouteDecision(
            "sharded_past_ceiling", "rejected",
            f"not near-square at floor {tiled_floor} (min dim "
            f"{min(m, n)}, aspect {max(m, n) / min(m, n):.2f})"
            if not near_square else
            "batched input (no shard_map under vmap)"
            if len(shape) != 2 else
            "mode='full' needs full Q (sharded merge is thin-only)"
            if config.mode == "full" else
            f"wide matrix ({m}x{n}): row-domain sharding needs m >= n"
            if m < n else
            f"single device available (ndevices={ndevices})"
            if ndevices <= 1 else
            f"max dim {max(m, n)} > sharded ceiling {sharded_ceiling}"
            if max(m, n) > sharded_ceiling else
            f"max dim {max(m, n)} <= single-device tiled ceiling "
            f"{_TILED_MAX_DIM} — tiled declined for its own reason"))

    gspec = _REGISTRY.get("geqrf_ht")
    if gspec is not None:
        if (backend == "tpu" and config.use_kernel is not False
                and _kernel_fits(gspec, m, n, config, dtype)):
            dec.append(RouteDecision(
                "tpu_kernel_panel_fits", "selected",
                f"backend=tpu and geqrf_ht panel working set fits VMEM "
                f"budget {kernel_vmem_budget(gspec.kernel_policy)}"))
            return "geqrf_ht", dec, None
        dec.append(RouteDecision(
            "tpu_kernel_panel_fits", "rejected",
            f"backend={backend} is not tpu" if backend != "tpu" else
            "use_kernel=False pins the jnp path"
            if config.use_kernel is False else
            f"geqrf_ht panel working set exceeds VMEM budget "
            f"{kernel_vmem_budget(gspec.kernel_policy)} at {m}x{n}"))
    if min(m, n) <= config.block:
        dec.append(RouteDecision(
            "single_panel", "selected",
            f"min dim {min(m, n)} <= block {config.block} — one "
            f"unblocked panel (geqr2_ht)"))
        return "geqr2_ht", dec, None
    dec.append(RouteDecision(
        "single_panel", "rejected",
        f"min dim {min(m, n)} > block {config.block} — needs blocking"))
    dec.append(RouteDecision(
        "blocked_default", "selected",
        f"no specialized rule matched {m}x{n} on {backend} — blocked "
        f"geqrf_ht default"))
    return "geqrf_ht", dec, None


def select_method(shape, dtype, config: QRConfig, *, backend: Optional[str] = None,
                  ndevices: Optional[int] = None) -> str:
    """The ``method="auto"`` routing table (trailing two dims of shape).

    0. zero-dim input (m == 0 or n == 0) -> ``degenerate`` (the trivial
       jnp.linalg.qr-style factorization; overrides explicit methods —
       no backend factors an empty matrix); then a measured tuning-cache
       hit for this shape class (:mod:`repro.tuning`, unless
       ``use_tuning_cache=False``) -> the measured best method, with the
       real wall times as the decision reason;
    1. tall-skinny (aspect >= tsqr's min_aspect, default 4:1) -> TSQR,
       with ``nblocks`` chosen by the planner;
    2. large near-square (256 <= dims <= 2048, aspect < 4) -> ``tiled``
       task-graph (cross-panel wavefront parallelism); on CPU the floor
       is 512 — below that multithreaded LAPACK geqrf wins and the
       request falls through to rule 6 (surfaced as the
       ``tiled_min_dim_cpu_floor`` fallback in the explain record);
    3. near-square but past the single-device tiled ceiling, with more
       than one device available (``ndevices``, default
       ``jax.local_device_count()``) -> ``sharded_tiled``: per-device
       row-block domains + a TSQR-style R merge tree, up to
       ``_TILED_MAX_DIM * min(ndevices, 8)`` on the long side;
    4. TPU and the geqrf_ht panel working set fits VMEM -> kernel-backed
       ``geqrf_ht``;
    5. single-panel problems (min(m, n) <= block) -> unblocked ``geqr2_ht``;
    6. otherwise blocked ``geqrf_ht``.

    ``plan(..., explain=True)`` returns the full decision trail as a
    :class:`PlanExplain` record on the solver.

    This function is a pure query: it mirrors :func:`plan`'s routing
    without emitting metrics (fallback counters fire once per plan, in
    :func:`plan` itself).
    """
    return _route(shape, dtype, config, backend, ndevices)[0]


def plan(shape, dtype=jnp.float32, config: Optional[QRConfig] = None, *,
         backend: Optional[str] = None,
         ndevices: Optional[int] = None,
         explain: bool = False) -> "QRSolver":
    """Resolve ``(shape, dtype, config)`` to a concrete :class:`QRSolver`.

    ``shape`` may carry leading batch dims; planning uses the trailing
    matrix dims and the solver vmaps over the rest.  ``backend`` overrides
    ``jax.default_backend()`` for the kernel policy, ``ndevices``
    overrides ``jax.local_device_count()`` for the sharded routing (both
    useful in tests).  ``explain=True`` attaches a :class:`PlanExplain`
    record to the solver: the full routing-decision trail, the resolved
    dispatch mode, and every fallback reason — machine-readable, and
    mirrored into the ``planner.*`` metrics either way.
    """
    _ensure_builtins()
    cfg = QRConfig() if config is None else config
    if len(shape) < 2:
        raise ValueError(f"qr plan expects a matrix shape, got {tuple(shape)}")
    m, n = int(shape[-2]), int(shape[-1])
    batched = len(shape) > 2
    backend = jax.default_backend() if backend is None else backend

    name, decisions, tuned = _route(shape, dtype, cfg, backend, ndevices)
    # Fallback counters for _route-level decisions fire HERE, once per
    # plan — _route/select_method are pure queries, so explain=True (or
    # a select_method probe) cannot double-count a fallback.  Resolve
    # hooks run after this loop and emit their own counters for the
    # decisions they append.
    for d in decisions:
        if d.outcome == "fallback":
            _metrics.counter("planner.fallbacks", reason=d.rule).inc()
    spec = get_method(name)
    if name == "degenerate" and min(m, n) > 0:
        raise ValueError(
            f"method 'degenerate' handles zero-dim shapes only "
            f"(m == 0 or n == 0), got {m}x{n}")

    if batched and not spec.batched:
        raise ValueError(f"method {name!r} does not support batched inputs")
    if cfg.mode == "full" and not spec.supports_full_q:
        raise ValueError(f"method {name!r} produces thin Q only")
    if spec.min_aspect > 0 and m < spec.min_aspect * n:
        raise ValueError(
            f"method {name!r} expects tall-skinny input "
            f"(m >= {spec.min_aspect:g}n, got {m}x{n})")

    use_kernel = cfg.use_kernel
    if use_kernel is None:
        if tuned is not None:
            use_kernel = bool(tuned.best.use_kernel) and spec.kernel_backed
        else:
            use_kernel = (backend == "tpu" and spec.kernel_backed
                          and _kernel_fits(spec, m, n, cfg, dtype))
    elif use_kernel and not spec.kernel_backed:
        raise ValueError(f"method {name!r} has no kernel-backed realization")

    resolved = dataclasses.replace(cfg, method=name, use_kernel=bool(use_kernel))
    if tuned is not None:
        resolved = _apply_tuned_config(resolved, cfg, tuned, decisions)
    if spec.resolve is not None:
        # Resolve hooks may append RouteDecisions (dispatch-mode choices,
        # tile alignment, domain degradations).
        resolved = spec.resolve(m, n, resolved, dtype=np.dtype(dtype),
                                explain=decisions, backend=backend)
    _metrics.counter("planner.plans", method=name).inc()
    record = None
    if explain:
        record = PlanExplain(
            shape=(m, n), dtype=str(np.dtype(dtype)), backend=backend,
            ndevices=(jax.local_device_count() if ndevices is None
                      else int(ndevices)),
            requested_method=cfg.method, method=name,
            use_kernel=bool(use_kernel),
            dispatch_mode=resolved.dispatch_mode,
            decisions=tuple(decisions),
            fallback_reasons=tuple(d.rule for d in decisions
                                   if d.outcome == "fallback"))
    return QRSolver(shape=(m, n), dtype=np.dtype(dtype), config=resolved,
                    spec=spec, explain=record)


# ---------------------------------------------------------------------------
# solver
# ---------------------------------------------------------------------------

def f32_products(fn: Callable) -> Callable:
    """Trace a QR entry point with float32 products at full float32
    precision.  XLA on TPU runs a float32 product as one bfloat16 pass
    unless told otherwise, which leaves the residual and the
    orthogonality of Q near bfloat16's epsilon instead of float32's.
    The setting is read at trace time, so a solve traced inside an outer
    ``jit`` (the optimizer step) gets it too.  CPU float32 and every
    float64 product are unaffected."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return wrapped


def _default_solve(spec: MethodSpec, a: Array, cfg: QRConfig):
    """Derive per-mode output from a packed ``factor`` realization."""
    from repro.core import householder

    m, n = a.shape
    k = min(m, n)
    packed, taus = spec.factor(a, cfg)
    r = householder.unpack_r(packed, n)
    if cfg.mode == "r":
        return sign_fix_r(r) if cfg.sign_fix else r
    if cfg.mode == "reduced":
        if cfg.q_method == "solve" and m >= n:
            from repro.core.tsqr import triangular_inverse_apply

            q = triangular_inverse_apply(a, r[:n, :n])
        else:
            q = householder.form_q(packed, taus)
        return sign_fix_qr(q, r) if cfg.sign_fix else (q, r)
    # mode == "full": Q is (m, m); R padded to (m, n) with zero rows.
    q = householder.form_q(packed, taus, full=True)
    if m > k:
        r = jnp.vstack([r, jnp.zeros((m - k, n), r.dtype)])
    return sign_fix_qr(q, r) if cfg.sign_fix else (q, r)


@dataclasses.dataclass(frozen=True)
class QRSolver:
    """A planned QR factorization for one matrix shape.

    ``config`` is fully resolved (concrete method / kernel flag / nblocks);
    the solver is hashable and may be closed over or passed as a
    ``jax.jit`` static argument.  ``explain`` (populated by
    ``plan(..., explain=True)``) carries the :class:`PlanExplain`
    decision trail; it is excluded from equality/hashing so explained
    and unexplained solvers are jit-cache-identical.
    """

    shape: Tuple[int, int]
    dtype: np.dtype
    config: QRConfig
    spec: MethodSpec
    explain: Optional[PlanExplain] = dataclasses.field(default=None,
                                                       compare=False)

    # -- internals ---------------------------------------------------------

    def _check(self, a: Array) -> None:
        if a.ndim < 2 or tuple(a.shape[-2:]) != self.shape:
            raise ValueError(
                f"solver planned for {self.shape}, got input shape {a.shape}")
        if np.dtype(a.dtype) != self.dtype:
            raise ValueError(
                f"solver planned for dtype {self.dtype}, got {a.dtype}; "
                "re-plan or cast (kernel/VMEM decisions are dtype-dependent)")
        if a.ndim > 2 and not self.spec.batched:
            raise ValueError(
                f"method {self.config.method!r} does not support batched inputs")

    def _batched(self, f: Callable, a: Array):
        for _ in range(a.ndim - 2):
            f = jax.vmap(f)
        return f(a)

    def _cast(self, a: Array) -> Array:
        if self.config.precision is not None:
            return a.astype(self.config.precision)
        return a

    def _solve2d(self, a: Array):
        cfg = self.config
        a = self._cast(a)
        if self.spec.solve is not None:
            return self.spec.solve(a, cfg)
        return _default_solve(self.spec, a, cfg)

    def _factor2d(self, a: Array):
        return self.spec.factor(self._cast(a), self.config)

    # -- public ------------------------------------------------------------

    @f32_products
    def solve(self, a: Array):
        """Factorize per ``config.mode``: (Q, R), R only, or full (Q, R).

        Inputs with leading batch dims are vmapped over those dims —
        except that a method registering ``solve_batched`` receives the
        innermost ``(B, m, n)`` stack natively (the tiled backend turns
        it into ONE batched engine dispatch instead of B vmapped ones).
        """
        self._check(a)
        if self.spec.solve_batched is not None and a.ndim >= 3:
            f = functools.partial(self.spec.solve_batched, cfg=self.config)
            for _ in range(a.ndim - 3):
                f = jax.vmap(f)
            return f(self._cast(a))
        return self._batched(self._solve2d, a)

    @f32_products
    def factor(self, a: Array):
        """LAPACK packed form ``(packed, taus)`` (methods that have one)."""
        if self.spec.factor is None:
            raise ValueError(
                f"method {self.config.method!r} has no packed factored form")
        self._check(a)
        return self._batched(self._factor2d, a)

    def orthogonalize(self, a: Array):
        """Sign-fixed thin Q (the optimizer primitive) of tall input."""
        solver = self if (self.config.sign_fix and self.config.mode == "reduced") \
            else dataclasses.replace(
                self, config=self.config.replace(sign_fix=True, mode="reduced"))
        q, _ = solver.solve(a)
        return q

    @f32_products
    def lstsq(self, a: Array, b: Array) -> Array:
        """Least-squares solve ``min ||a x - b||`` via this realization."""
        from jax.scipy.linalg import solve_triangular

        m, n = self.shape
        if m < n:
            raise ValueError("lstsq expects m >= n")
        if a.ndim != 2:
            raise ValueError("lstsq expects a single matrix")
        b2 = b if b.ndim == 2 else b[:, None]
        if self.spec.factor is not None:
            from repro.core import householder

            packed, taus = self.factor(a)
            qtb = householder.apply_q(packed, taus, b2, transpose=True)
            r = householder.unpack_r(packed, n)[:n, :n]
            x = solve_triangular(r, qtb[:n], lower=False)
        else:
            cfg = self.config.replace(mode="reduced", sign_fix=False)
            q, r = dataclasses.replace(self, config=cfg).solve(a)
            x = solve_triangular(r[:n, :n], q.T @ self._cast(b2), lower=False)
        return x[:, 0] if b.ndim == 1 else x
