"""Batched optimizer-step orthogonalization: one dispatch per shape class.

The paper's thesis — rearrange the computation to expose more parallel
work per DAG level — applied one level up: a Muon optimizer step
orthogonalizes dozens of independent momentum matrices, and running them
one leaf at a time is the same missed opportunity the tile DAG fixes
inside a single factorization.  This module collects every 2-D momentum
matrix of an update step, groups them into **shape classes** with the
serving layer's bucketing machinery
(:func:`repro.serving.bucketing.group_shape_classes`, under a
tile-granularity optimizer policy — see ``DEFAULT_ORTHO_POLICY``;
measured tuning-cache routings still govern each class plan, because the
planner's tuned rule maps any shape through the cache's own
``shape_class`` edges at lookup), zero-pads
and stacks each class, plans the stack ONCE through
:func:`repro.core.plan.plan`, and factors the whole class in one
dispatch — on the tiled route that is one
:func:`repro.core.engine.factor_tiles_batched` call (a single
``pallas_call`` in megakernel mode); other methods vmap inside one
compiled program.  Q forms batched, the unpadded slices scatter back,
and the per-step QR dispatch count drops from O(number of 2-D params) to
O(shape classes).

Zero padding is numerically free: Householder QR proceeds left to right,
so trailing zero columns never touch the leading ``n`` columns of Q, and
zero rows factor to zero reflector entries — the ``[:m, :n]`` slice of
the padded sign-fixed thin Q IS the sign-fixed thin Q of the member (the
same invariant the serving layer's buckets rely on).

Routing per class is recorded in an :class:`OrthoPlan`:

  * ``"batched"``  — the class stacked and planned as one ``(B, M, N)``
    problem; the planner's full explain trail rides on the class plan.
  * ``"leafwise"`` — fallback to per-matrix
    :func:`repro.optim.qr_muon.qr_orthogonalize_2d`: singleton classes
    (a batch of one amortizes nothing — and the B=1 stacked program is a
    different jit cache entry per step count for no benefit) and shapes
    whose class plan fails capability checks.

:func:`plan_batched_ortho` is a pure, trace-free query over static
shapes — benchmarks and tests count dispatches from it without running
anything.  :func:`batched_orthogonalize` executes the plan (inside jit:
all grouping is static, only the padded stacks are traced), emitting
``optim.*`` counters through the observability registry (no spans: a
span inside jit would time the trace, not the step).
:func:`repro.optim.qr_muon.muon_update` rides on it behind the
``batched_ortho=True`` knob.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.plan import PlanExplain, QRConfig, plan as qr_plan
from repro.observability import metrics as _metrics
from repro.serving.bucketing import (
    BucketKey, BucketingPolicy, group_shape_classes)

Array = jax.Array

__all__ = [
    "DEFAULT_ORTHO_POLICY",
    "OrthoClassPlan",
    "OrthoPlan",
    "batched_orthogonalize",
    "plan_batched_ortho",
]

# Optimizer-side bucketing: tile 16, tile-granularity padding only
# (max_waste=0).  Serving pads to pow2-ish edges because open-ended
# traffic needs a logarithmic bucket count; an optimizer step's shapes
# are a small STATIC set in which classes form from exactly repeated
# layer shapes, so coarser edges buy no extra merging — they only burn
# cubic flops (the serving default would pad 48 -> 64 and 576 -> 768,
# ~2.4x the QR work per matrix).  Tuned routings still apply to the
# class plan: the planner's tuned rule maps ANY (m, n) through the
# tuning cache's own ``shape_class`` edges at lookup.  max_batch is
# per-class; an optimizer step's class population is bounded by the
# parameter count, not arrival rate.
DEFAULT_ORTHO_POLICY = BucketingPolicy(tile=16, max_waste=0.0,
                                       max_batch=512)


@dataclasses.dataclass(frozen=True)
class OrthoClassPlan:
    """Routing for one shape class of the step: which flat members it
    owns, whether they run as one stacked dispatch or leafwise, and why.

    ``key`` is the padded, tall-oriented shape class (wide members are
    transposed before classing, exactly as ``qr_orthogonalize_2d``
    transposes wide inputs).  ``explain`` is the planner's full decision
    trail for the stacked plan (batched classes only)."""

    key: BucketKey
    members: Tuple[int, ...]          # flat member indices, step order
    route: str                        # "batched" | "leafwise"
    reason: str
    method: Optional[str] = None      # resolved method (batched only)
    dispatch_mode: Optional[str] = None
    explain: Optional[PlanExplain] = dataclasses.field(default=None,
                                                       compare=False)

    @property
    def dispatches(self) -> int:
        return 1 if self.route == "batched" else len(self.members)


@dataclasses.dataclass(frozen=True)
class OrthoPlan:
    """The step's full dispatch plan: every 2-D matrix of every leaf
    assigned to exactly one shape class.  Member index space is flat:
    leaf ``i``'s lead dims unroll row-major, leaves concatenate in input
    order; ``member_leaf[j]`` maps member ``j`` back to its leaf."""

    classes: Tuple[OrthoClassPlan, ...]
    n_leaves: int
    n_matrices: int
    member_leaf: Tuple[int, ...]

    @property
    def dispatches(self) -> int:
        """QR dispatches one step issues under this plan."""
        return sum(c.dispatches for c in self.classes)

    @property
    def batched_matrices(self) -> int:
        return sum(len(c.members) for c in self.classes
                   if c.route == "batched")

    @property
    def leafwise_matrices(self) -> int:
        return sum(len(c.members) for c in self.classes
                   if c.route == "leafwise")


def _member_geometry(shape, dtype):
    """Oriented 2-D geometry of one leaf's members: ``(lead, m, n,
    transpose, compute_dtype)`` — lead is the unrolled stack depth."""
    m, n = int(shape[-2]), int(shape[-1])
    lead = int(np.prod(shape[:-2], dtype=np.int64)) if len(shape) > 2 else 1
    transpose = m < n
    if transpose:
        m, n = n, m
    compute = jnp.promote_types(np.dtype(dtype), jnp.float32)
    return lead, m, n, transpose, np.dtype(compute)


def plan_batched_ortho(leaves: Sequence[Tuple], *,
                       policy: Optional[BucketingPolicy] = None,
                       config: Optional[QRConfig] = None,
                       backend: Optional[str] = None) -> OrthoPlan:
    """Pure shape-class routing for one step's orthogonalization.

    ``leaves`` is a sequence of ``(shape, dtype)`` pairs, one per >=2-D
    momentum leaf (lead dims unroll into members).  No arrays are
    touched: benchmarks count ``plan.dispatches`` and tests assert
    routes from this alone.  ``config`` seeds the per-class
    :func:`repro.core.plan.plan` call (mode/sign_fix pinned to the
    orthogonalization contract); ``backend`` overrides the routing
    backend as in ``plan``.
    """
    policy = DEFAULT_ORTHO_POLICY if policy is None else policy
    base = QRConfig() if config is None else config
    base = base.replace(mode="reduced", sign_fix=True)

    member_shapes: List[Tuple[int, int, np.dtype]] = []
    member_leaf: List[int] = []
    for li, (shape, dtype) in enumerate(leaves):
        if len(shape) < 2:
            raise ValueError(
                f"orthogonalization needs matrix leaves, got shape {shape}")
        lead, m, n, _, compute = _member_geometry(shape, dtype)
        member_shapes.extend([(m, n, compute)] * lead)
        member_leaf.extend([li] * lead)

    classes: List[OrthoClassPlan] = []
    for key, members in group_shape_classes(member_shapes, policy).items():
        b = len(members)
        if b == 1:
            classes.append(OrthoClassPlan(
                key=key, members=tuple(members), route="leafwise",
                reason="singleton_class: a batch of one amortizes no "
                       "dispatch — per-leaf qr_orthogonalize_2d"))
            continue
        try:
            solver = qr_plan((b, key.m, key.n), np.dtype(key.dtype), base,
                             backend=backend, explain=True)
        except (ValueError, ImportError) as e:
            classes.append(OrthoClassPlan(
                key=key, members=tuple(members), route="leafwise",
                reason=f"plan_failed: {e}"))
            continue
        sel = solver.explain.selected
        classes.append(OrthoClassPlan(
            key=key, members=tuple(members), route="batched",
            reason=f"{sel.rule}: {sel.reason}" if sel is not None else
                   "planned", method=solver.config.method,
            dispatch_mode=solver.config.dispatch_mode,
            explain=solver.explain))
    return OrthoPlan(classes=tuple(classes), n_leaves=len(leaves),
                     n_matrices=len(member_shapes),
                     member_leaf=tuple(member_leaf))


def _default_fallback(a: Array) -> Array:
    from repro.optim.qr_muon import qr_orthogonalize_2d

    return qr_orthogonalize_2d(a)


def _post_dispatch(q_stack: Array, label: str, *,
                   verify: Optional[bool]):
    """Robustness seam of one batched class dispatch: the chaos
    output-corruption hook, then (verify knob on, eager values only —
    host-side resolution never fires under a trace, keeping the
    verify-off jit path jaxpr-identical) a per-slice orthogonality
    health check.  Returns ``(q_stack, bad_slots)``; flagged slots
    escalate batched -> leafwise in the caller, each hop counted under
    ``robustness.escalations{from=batched, to=leafwise}``."""
    if isinstance(q_stack, jax.core.Tracer):
        return q_stack, frozenset()
    from repro.robustness import inject as _inject

    if _inject.enabled():
        q_stack = _inject.corrupt_output(q_stack, f"ortho:{label}")
    from repro.robustness.verify import check_ortho_batch, verify_enabled

    if not verify_enabled(verify):
        return q_stack, frozenset()
    from repro.robustness import escalate as _escalate

    bad = set()
    reports = check_ortho_batch(q_stack)
    for slot, rep in enumerate(reports):
        if rep.ok:
            continue
        bad.add(slot)
        _escalate.record(
            "batched", "leafwise", "health_check_failed",
            f"class {label} slot {slot}: {rep.reason} "
            f"defect={rep.ortho_defect:.3e} tol={rep.tol:.3e}")
        _metrics.counter("optim.ortho_escalations", bucket=label).inc()
    return q_stack, bad


def batched_orthogonalize(leaves: Sequence[Array], *,
                          policy: Optional[BucketingPolicy] = None,
                          config: Optional[QRConfig] = None,
                          fallback: Optional[Callable] = None,
                          backend: Optional[str] = None,
                          ortho_plan: Optional[OrthoPlan] = None
                          ) -> List[Array]:
    """Sign-fixed thin Q of every matrix in ``leaves``, dispatched per
    shape class.

    Each leaf is a >=2-D array (lead dims are independent stacked
    matrices, as in ``muon_update``); the result list matches input
    shapes and dtypes.  Safe (and intended) to call inside ``jit`` — the
    routing is a static function of shapes; only padding, stacking, and
    the factorizations trace.  ``fallback`` handles leafwise-routed
    members (default: :func:`repro.optim.qr_muon.qr_orthogonalize_2d`
    with its defaults); ``ortho_plan`` reuses a precomputed plan (it
    must have been built from these leaves' shapes/dtypes).
    """
    leaves = list(leaves)
    policy = DEFAULT_ORTHO_POLICY if policy is None else policy
    if ortho_plan is None:
        ortho_plan = plan_batched_ortho(
            [(tuple(l.shape), l.dtype) for l in leaves],
            policy=policy, config=config, backend=backend)
    base = QRConfig() if config is None else config
    base = base.replace(mode="reduced", sign_fix=True)
    fallback = _default_fallback if fallback is None else fallback

    # Flat member views, in the plan's member index space.
    members: List[Array] = []
    geom: List[Tuple[int, int, bool]] = []   # oriented (m, n, transposed)
    for leaf in leaves:
        lead, m, n, transpose, _ = _member_geometry(leaf.shape, leaf.dtype)
        stack = leaf.reshape((lead,) + leaf.shape[-2:])
        for s in range(lead):
            mat = stack[s]
            members.append(mat.T if transpose else mat)
            geom.append((m, n, transpose))

    out: List[Optional[Array]] = [None] * len(members)
    for cls in ortho_plan.classes:
        _metrics.counter("optim.ortho_classes", route=cls.route).inc()
        _metrics.counter("optim.ortho_dispatches",
                         route=cls.route).inc(cls.dispatches)
        _metrics.counter("optim.ortho_matrices",
                         route=cls.route).inc(len(cls.members))
        label = f"{cls.key.m}x{cls.key.n}"
        if cls.route == "leafwise":
            for j in cls.members:
                m, n, transpose = geom[j]
                q = fallback(members[j].T if transpose else members[j])
                out[j] = q.T if transpose else q
            continue
        compute = np.dtype(cls.key.dtype)
        solver = qr_plan((len(cls.members), cls.key.m, cls.key.n),
                         compute, base, backend=backend)
        stacked = jnp.stack([
            jnp.pad(members[j].astype(compute),
                    ((0, cls.key.m - geom[j][0]),
                     (0, cls.key.n - geom[j][1])))
            for j in cls.members])
        q_stack = solver.orthogonalize(stacked)
        q_stack, bad = _post_dispatch(q_stack, label, verify=base.verify)
        for slot, j in enumerate(cls.members):
            m, n, transpose = geom[j]
            if slot in bad:
                # Per-slice escalation: the batched dispatch's flagged
                # slice alone re-solves leafwise; its class-mates ship
                # as-is.
                q = fallback(members[j].astype(compute)).astype(
                    leaves[ortho_plan.member_leaf[j]].dtype)
                out[j] = q.T if transpose else q
                continue
            q = q_stack[slot, :m, :n].astype(leaves[
                ortho_plan.member_leaf[j]].dtype)
            out[j] = q.T if transpose else q

    # Scatter members back into leaf-shaped stacks.
    results: List[Array] = []
    pos = 0
    for leaf in leaves:
        lead, _, _, _, _ = _member_geometry(leaf.shape, leaf.dtype)
        mats = out[pos:pos + lead]
        pos += lead
        results.append(jnp.stack(mats).reshape(leaf.shape) if lead > 1
                       or len(leaf.shape) > 2 else mats[0])
    return results
