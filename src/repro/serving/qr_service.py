"""QR-as-a-service: shape-bucketed batched factorization serving.

The engine factors one matrix per dispatch; production traffic is many
concurrent heterogeneous ``(m, n, dtype, mode)`` requests.  The tiled
DAG's tasks are independent across matrices exactly as they are across
tiles, so throughput comes from keeping the accelerator saturated with
macro-op work: :class:`QRService` buckets submissions by padded shape
class (:mod:`repro.serving.bucketing`), zero-pads and stacks each
bucket, and factors it in ONE dispatch through
:func:`repro.core.engine.factor_tiles_batched` — on the megakernel path
that is literally one ``pallas_call`` per bucket, batch axis on the
grid, one task table shared across the batch.

The pipeline per :meth:`QRService.flush`:

    requests -> admission -> bucketize -> (plan cache: BucketKey x batch
    -> compiled executable) -> stage bucket i+1's host->device transfer
    while bucket i computes (donated input buffers), and start bucket i's
    device->host copy as soon as it is dispatched -> one host fetch per
    output stack (padding slots included) + health check -> unpad into
    NumPy views + scatter results back

Answers are host ``np.ndarray`` views into their chunk's fetched
stack: no device program and no device->host transfer per request.

**Compiled-plan cache.**  Plans are AOT-compiled
(``jax.jit(...).lower(...).compile()``) and kept in an LRU keyed on
``(BucketKey, padded_batch, rung)``; hits, misses, evictions, and
compiles are exposed via :meth:`QRService.stats`, so a steady-state
stream (warmed cache) performs ZERO recompilations — asserted in
tests/test_qr_service.py, measured by benchmarks/bench_qr_serving.py.
The LRU is additionally keyed on the active measured tuning cache's
fingerprint (:func:`repro.tuning.cache.active_cache_info`): bucket
executables bake in tuned dispatch-mode routing, so installing a fresh
sweep invalidates every cached plan (``plan_invalidations`` counter) and
they recompile lazily under the new measurements.

**Failure hardening** (:mod:`repro.robustness`).  Three lines of
defense, each named and counted:

  * *Admission* — :meth:`submit` runs the finite/shape/dtype guard
    (``admission`` policy); a rejected payload is **quarantined** (its
    :class:`QRResult` carries ``error="quarantined:<reason>"``) instead
    of poisoning the padded bucket it would have shared.
  * *Verification* — with the ``verify`` knob on (``$REPRO_VERIFY``
    default), every synced bucket is health-checked **per slice**
    (residual + orthogonality against the conformance tolerance); only
    the failing slices re-solve, the healthy bucket-mates ship as-is.
  * *Escalation* — a failed AOT compile, dispatch, or health check
    walks the degradation ladder megakernel -> wavefront -> oracle ->
    lapack (:mod:`repro.robustness.escalate`), recording
    ``robustness.escalations{from, to, reason}``.  A bucket that
    escalates ``breaker_threshold`` times trips its **circuit
    breaker**: its compiled plans are evicted and the bucket pins to
    the lapack fallback until the tuning fingerprint changes.

Flush is failure-atomic: if an exception does escape (escalation
disabled, or a non-recoverable error), every request that has not been
resolved into a result is restored to the pending queue before the
exception propagates — no request is silently dropped.

Zero padding is numerically free (padded rows/cols factor to
exactly-zero reflectors), and the batched engine is bitwise-equal per
slice to independent single-matrix runs, so serving answers are the
answers the per-request path would have produced.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import time
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

import jax

from repro.observability import metrics as _metrics
from repro.observability import trace as _trace
from repro.robustness import escalate as _escalate
from repro.robustness import guards as _guards
from repro.robustness import inject as _inject
from repro.robustness import verify as _verify
from repro.serving.bucketing import (
    BucketKey, BucketingPolicy, bucketize, pad_batch)

Array = jax.Array

__all__ = ["QRRequest", "QRResult", "QRService"]

# Distinguishes each QRService instance's series in the process-global
# metrics registry, so a fresh service starts from zero counts.
_SERVICE_IDS = itertools.count()


@dataclasses.dataclass(frozen=True)
class QRRequest:
    """One queued factorization: the payload plus its bucket identity."""

    rid: int
    a: np.ndarray
    mode: str
    t_submit: float = 0.0      # monotonic clock at submit (queue-wait base)

    @property
    def shape(self) -> Tuple[int, int]:
        return self.a.shape  # type: ignore[return-value]

    @property
    def dtype(self):
        return self.a.dtype


@dataclasses.dataclass(frozen=True)
class QRResult:
    """Unpadded per-request answer; ``q`` is None for mode="r".

    ``q`` and ``r`` are host ``np.ndarray``s on every path.  From a
    batched chunk they are read-only views into that chunk's host copy
    of its padded output stacks (fetched once per chunk): a caller that
    keeps one answer alone and wants the rest of the chunk freed copies
    it (``np.array(res.q)``).

    ``error`` is None for a healthy result; a quarantined or
    unrecoverable request carries the named reason
    (``"quarantined:nonfinite_input"``, ``"escalation_exhausted"``,
    ...) and ``q``/``r`` may be None."""

    rid: int
    q: Optional[np.ndarray]
    r: Optional[np.ndarray]
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.error is None


def _host(x) -> Optional[np.ndarray]:
    return None if x is None else np.asarray(x)


def _tuning_fingerprint() -> Tuple:
    """Identity of the active measured tuning cache (source + contents
    summary).  Compiled bucket plans bake in tuned routing decisions
    (dispatch mode per shape class), so a cache refresh — a new sweep
    installed via ``set_active_cache`` or ``$REPRO_TUNING_CACHE`` — must
    invalidate them; the plan LRU is keyed on this fingerprint."""
    from repro.tuning import cache as _tcache

    info = _tcache.active_cache_info()
    return (info["source"], info["entries"], tuple(info["classes"]))


@dataclasses.dataclass(frozen=True)
class _BucketPlan:
    """One AOT-compiled bucket executable (the plan-cache value)."""

    key: BucketKey
    batch: int                 # padded batch the executable expects
    grid: Tuple[int, int]      # (p, q) tile grid
    nb: int
    dispatch_mode: Optional[str]
    rung: str                  # ladder rung this plan executes at
    fn: object                 # jax compiled executable


def _solve_bucket(stacked: Array, *, p: int, q: int, nb: int, mode: str,
                  use_kernel: bool, interpret: bool,
                  dispatch_mode: Optional[str]):
    """The traced bucket program: one batched engine dispatch for the
    whole stack via the shared :func:`repro.core.tilegraph
    ._factor_stack_padded` lowering (the same program the optimizer's
    shape-class dispatch lowers through).  Runs on PADDED shapes and
    returns FULL padded factors (the donated staged buffer can alias an
    output); per-request unpadding happens host-side."""
    from repro.core.tilegraph import _factor_stack_padded

    return _factor_stack_padded(stacked, p=p, q=q, nb=nb, mode=mode,
                                use_kernel=use_kernel, interpret=interpret,
                                dispatch_mode=dispatch_mode)


class QRService:
    """Batched QR serving: submit heterogeneous requests, get per-request
    factors back from shape-bucketed single-dispatch execution.

        service = QRService()                       # auto kernel policy
        rid = service.submit(a, mode="reduced")     # queue
        out = service.flush()[rid]                  # bucket + dispatch
        results = service.submit_many(arrays)       # pipelined stream

    Parameters
    ----------
    policy:        bucketing policy (tile size, waste cap, max batch).
    use_kernel:    engine Pallas lowering — None resolves like the
                   planner (kernel on TPU, jnp oracle elsewhere).
    dispatch_mode: engine kernel lowering per bucket; None lets the
                   engine's budget rule pick (megakernel when the shared
                   task table + batched working set fit).
    cache_size:    max resident compiled bucket plans (LRU).
    admission:     input guard run at submit (None disables; default:
                   finite 2-D float — :mod:`repro.robustness.guards`).
    verify:        post-dispatch per-slice health checks — True/False
                   force, None defers to ``$REPRO_VERIFY``.
    escalate:      walk the degradation ladder on failures (False keeps
                   the raise-through behavior; flush stays atomic).
    breaker_threshold: escalations a bucket tolerates before its
                   circuit breaker opens (plans evicted, bucket pinned
                   to the lapack fallback until the tuning fingerprint
                   changes).
    """

    def __init__(self, *, policy: Optional[BucketingPolicy] = None,
                 use_kernel: Optional[bool] = None,
                 dispatch_mode: Optional[str] = None,
                 interpret: Optional[bool] = None,
                 cache_size: int = 32,
                 admission: Optional[_guards.AdmissionPolicy] =
                 _guards.DEFAULT_ADMISSION,
                 verify: Optional[bool] = None,
                 escalate: bool = True,
                 breaker_threshold: int = 3):
        if cache_size < 1:
            raise ValueError(f"cache_size must be >= 1, got {cache_size}")
        if breaker_threshold < 1:
            raise ValueError(
                f"breaker_threshold must be >= 1, got {breaker_threshold}")
        self.policy = BucketingPolicy() if policy is None else policy
        self.use_kernel = (jax.default_backend() == "tpu"
                           if use_kernel is None else bool(use_kernel))
        if self.use_kernel and jax.default_backend() == "tpu":
            # Compiled kernels need a lane-aligned tile (the planner's
            # ``tpu_tile_lane_aligned`` rule); buckets pad to it.
            from repro.core.engine import lane_aligned_tile

            self.policy = dataclasses.replace(
                self.policy, tile=lane_aligned_tile(self.policy.tile))
        self.dispatch_mode = dispatch_mode
        self.interpret = interpret
        self.cache_size = cache_size
        self.admission = admission
        self.verify = verify
        self.escalate = escalate
        self.breaker_threshold = breaker_threshold
        self._plans: "collections.OrderedDict[Tuple[BucketKey, int, str], _BucketPlan]" \
            = collections.OrderedDict()
        self._pending: List[QRRequest] = []
        self._quarantined: Dict[int, str] = {}    # rid -> named reason
        self._esc_counts: Dict[BucketKey, int] = {}
        self._breaker_open: Set[BucketKey] = set()
        self.escalations: List[_escalate.Escalation] = []
        self._tuning_fp = _tuning_fingerprint()
        self._next_rid = 0
        # Counters live in the process-global metrics registry under this
        # instance's ``service`` label; stats() is a view over them.
        self._sid = f"qr{next(_SERVICE_IDS)}"
        # Ids of this service's flushes: the ``flush`` label that ties
        # the spans of one flush together.
        self._flush_ids = itertools.count()

    # ---------------------------------------------------- metrics plumbing

    def _count(self, name: str, amount: int = 1) -> None:
        _metrics.counter(f"serving.{name}", service=self._sid).inc(amount)

    def _count_value(self, name: str) -> int:
        return int(_metrics.counter_value(f"serving.{name}", service=self._sid))

    def _observe(self, name: str, value: float, **labels: object) -> None:
        _metrics.histogram(f"serving.{name}", service=self._sid,
                           **labels).observe(value)

    def _verify_on(self) -> bool:
        return _verify.verify_enabled(self.verify)

    # ------------------------------------------------------------ intake

    def submit(self, a, mode: str = "reduced") -> int:
        """Queue one matrix; returns the request id :meth:`flush` keys
        results on.  The array is copied to host memory at submit time
        (the service owns staging; donation consumes staged buffers).

        Admission runs here — a payload the guard rejects is
        quarantined (``flush()`` returns an error-carrying
        :class:`QRResult` for it) rather than stacked into a bucket
        where its NaNs would contaminate every bucket-mate."""
        arr = np.asarray(a)
        if arr.ndim != 2:
            raise ValueError(f"expected one matrix, got shape {arr.shape}")
        if mode not in ("reduced", "r"):
            raise ValueError(
                f"serving modes are 'reduced' and 'r', got {mode!r}")
        rid = self._next_rid
        self._next_rid += 1
        self._count("requests")
        if _inject.enabled():
            arr = _inject.corrupt_input(
                arr, f"{arr.shape[0]}x{arr.shape[1]}")
        if self.admission is not None:
            try:
                _guards.admit(arr, policy=self.admission)
            except _guards.AdmissionError as e:
                self._quarantined[rid] = e.reason
                self._count("quarantined")
                _metrics.counter("robustness.quarantined",
                                 reason=e.reason).inc()
                return rid
        self._pending.append(QRRequest(rid=rid, a=arr, mode=mode,
                                       t_submit=time.monotonic()))
        return rid

    def submit_many(self, arrays: Sequence, mode: str = "reduced"
                    ) -> List[QRResult]:
        """Submit a homogeneous-mode stream and flush it; results come
        back in submission order.  Buckets are dispatched back-to-back
        with the NEXT bucket's host->device transfer staged while the
        current one computes (see :meth:`flush`).  Every answer's Q and
        R are already on the host: read-only NumPy views into their
        chunk's host copy (see :class:`QRResult`; copy one to free the
        rest of its chunk).

        Spans (host time, never blocking; labels ``service`` and
        ``flush``): ``serving.submit`` around the call, holding
        ``serving.admit`` (the :meth:`submit` loop) and the flush's own
        spans."""
        fid = next(self._flush_ids)
        with _trace.span("serving.submit", service=self._sid, flush=fid,
                         requests=len(arrays)):
            with _trace.span("serving.admit", service=self._sid, flush=fid):
                rids = [self.submit(a, mode=mode) for a in arrays]
            results = self._flush(fid)
            return [results[rid] for rid in rids]

    # --------------------------------------------------------- plan cache

    def _check_tuning(self) -> None:
        """Tuning-cache refresh detection: every cached executable may
        have been built under routing the new measurements contradict —
        drop them all (they recompile lazily on next use).  An open
        circuit breaker also resets: the new measurements may route the
        bucket around whatever kept failing."""
        fp = _tuning_fingerprint()
        if fp == self._tuning_fp:
            return
        self._tuning_fp = fp
        if self._plans:
            self._count("plan_invalidations")
            self._count("cache_evictions", len(self._plans))
            self._plans.clear()
        if self._breaker_open or self._esc_counts:
            self._count("breaker_resets", len(self._breaker_open) or 1)
            self._breaker_open.clear()
            self._esc_counts.clear()

    def _initial_rung(self, key: BucketKey) -> str:
        """The ladder rung a fresh bucket plan starts at: the tuned /
        budget-resolved dispatch mode on the kernel path, "oracle" on
        the jnp path."""
        if not self.use_kernel:
            return "oracle"
        if self.dispatch_mode is not None:
            return self.dispatch_mode
        from repro.core import engine
        from repro.tuning import cache as _tcache

        nb = min(self.policy.tile, key.m, key.n)
        p, q = -(-key.m // nb), -(-key.n // nb)
        # Measured tuning entries (same pow2-ish shape classes as the
        # bucket edges) take precedence over the engine's budget rule —
        # this is what the fingerprint invalidation protects.
        entry = _tcache.active_cache().lookup(
            backend=jax.default_backend(), m=key.m, n=key.n,
            dtype=np.dtype(key.dtype))
        if (entry is not None and entry.best.use_kernel
                and entry.best.dispatch_mode is not None):
            return entry.best.dispatch_mode
        return engine.resolve_dispatch_mode(
            p, q, nb, np.dtype(key.dtype).itemsize)

    def _plan_for(self, key: BucketKey, batch: int, *,
                  rung: str) -> _BucketPlan:
        self._check_tuning()
        cache_key = (key, batch, rung)
        plan = self._plans.get(cache_key)
        if plan is not None:
            self._plans.move_to_end(cache_key)
            self._count("cache_hits")
            return plan
        self._count("cache_misses")
        plan = self._build_plan(key, batch, rung=rung)
        self._plans[cache_key] = plan
        if len(self._plans) > self.cache_size:
            self._plans.popitem(last=False)
            self._count("cache_evictions")
        return plan

    def _build_plan(self, key: BucketKey, batch: int, *,
                    rung: str) -> _BucketPlan:
        """AOT-compile one bucket executable at ``rung``.  The ONLY site
        that compiles — ``stats()["compiles"]`` counts exactly these,
        which is what makes the steady-state zero-recompilation claim
        testable."""
        from repro.kernels import macro_ops

        _inject.check("compile", f"{key.m}x{key.n}:{rung}")
        use_kernel = rung in ("megakernel", "wavefront")
        dispatch_mode = rung if use_kernel else None
        nb = min(self.policy.tile, key.m, key.n)
        p, q = -(-key.m // nb), -(-key.n // nb)
        interpret = (macro_ops.default_interpret()
                     if self.interpret is None else self.interpret)
        fn = jax.jit(
            functools.partial(
                _solve_bucket, p=p, q=q, nb=nb, mode=key.mode,
                use_kernel=use_kernel, interpret=interpret,
                dispatch_mode=dispatch_mode),
            donate_argnums=(0,))
        shape = jax.ShapeDtypeStruct((batch, key.m, key.n),
                                     np.dtype(key.dtype))
        t0 = time.monotonic()
        with jax.default_matmul_precision("highest"):  # as QRSolver.solve
            compiled = fn.lower(shape).compile()
        self._count("compiles")
        self._observe("compile_seconds", time.monotonic() - t0)
        return _BucketPlan(key=key, batch=batch, grid=(p, q), nb=nb,
                           dispatch_mode=dispatch_mode, rung=rung,
                           fn=compiled)

    def _plan_with_escalation(
            self, key: BucketKey, batch: int
            ) -> Tuple[Optional[_BucketPlan], str]:
        """Resolve a bucket's plan, walking the ladder on compile
        failures.  Returns ``(plan, rung)``; ``plan=None`` means the
        lapack rung (per-request fallback, nothing to compile)."""
        if key in self._breaker_open:
            self._count("breaker_pinned_dispatches")
            return None, "lapack"
        rung = self._initial_rung(key)
        while True:
            try:
                return self._plan_for(key, batch, rung=rung), rung
            except Exception as e:  # noqa: BLE001 — every rung failure degrades
                if not self.escalate:
                    raise
                below = _escalate.ladder_below(rung)
                nxt = below[0] if below else "lapack"
                self._record_escalation(key, _escalate.record(
                    rung, nxt, _escalate.classify(e, "compile"), str(e)))
                if nxt == "lapack":
                    return None, "lapack"
                rung = nxt

    # ------------------------------------------------- failure machinery

    def _record_escalation(self, key: BucketKey,
                           esc: _escalate.Escalation) -> None:
        self.escalations.append(esc)
        del self.escalations[:-200]            # bounded history
        self._count("escalations")
        self._esc_counts[key] = self._esc_counts.get(key, 0) + 1
        if (self._esc_counts[key] >= self.breaker_threshold
                and key not in self._breaker_open):
            self._breaker_open.add(key)
            self._count("breaker_trips")
            _metrics.counter("robustness.breaker_open",
                             bucket=f"{key.m}x{key.n}").inc()
            stale = [ck for ck in self._plans if ck[0] == key]
            for ck in stale:
                del self._plans[ck]
            if stale:
                self._count("cache_evictions", len(stale))
            self.escalations.append(_escalate.Escalation(
                rung_from=esc.rung_to, rung_to="lapack",
                rule="breaker_open",
                reason=f"bucket {key.m}x{key.n} escalated "
                       f"{self._esc_counts[key]} times "
                       f"(threshold {self.breaker_threshold}); pinned to "
                       f"lapack until the tuning fingerprint changes"))

    def _recover_request(self, req: QRRequest, key: BucketKey,
                         start: str) -> QRResult:
        """Re-solve ONE request below ``start`` on its raw, unpadded
        payload (the per-slice recovery path)."""
        try:
            q, r, rung, escs = _escalate.solve_below(
                req.a, mode=key.mode, start=start,
                verify=self._verify_on(), tag=f"{key.m}x{key.n}")
        except _escalate.EscalationExhausted as e:
            for esc in e.escalations:
                self._record_escalation(key, esc)
            return QRResult(rid=req.rid, q=None, r=None,
                            error="escalation_exhausted")
        for esc in escs:
            self._record_escalation(key, esc)
        return QRResult(rid=req.rid, q=None if key.mode == "r" else _host(q),
                        r=_host(r))

    def _recover_chunk(self, key: BucketKey, chunk: List[QRRequest],
                       rung: str, e: Exception, site: str,
                       results: Dict[int, QRResult]) -> None:
        """Record ``e``, raised at ``site`` for a whole chunk, and
        recover each of its requests below ``rung``."""
        self._record_escalation(key, _escalate.record(
            rung, "per-request", _escalate.classify(e, site), str(e)))
        for req in chunk:
            results[req.rid] = self._recover_request(req, key, rung)

    def _lapack_chunk(self, key: BucketKey, chunk: List[QRRequest]
                      ) -> Dict[int, QRResult]:
        """The breaker-pinned / bottom-rung chunk path: per-request
        ``jnp.linalg.qr`` on the raw payloads — no padding, no
        compiled plan, nothing left to fail but the input itself."""
        out: Dict[int, QRResult] = {}
        for req in chunk:
            q, r = _escalate.lapack_qr(req.a, key.mode)
            out[req.rid] = QRResult(rid=req.rid, q=_host(q), r=_host(r))
        return out

    # ---------------------------------------------------------- execution

    def _chunks(self) -> List[Tuple[BucketKey, List[QRRequest]]]:
        """Bucketize pending requests and split buckets into
        max_batch-sized dispatch chunks (submission order preserved)."""
        reqs, self._pending = self._pending, []
        out: List[Tuple[BucketKey, List[QRRequest]]] = []
        for key, rs in bucketize(reqs, self.policy).items():
            for i in range(0, len(rs), self.policy.max_batch):
                out.append((key, rs[i:i + self.policy.max_batch]))
        return out

    def _stage(self, key: BucketKey, chunk: List[QRRequest],
               batch: int, fid: int) -> Array:
        """Zero-pad and stack one chunk, then start its host->device
        transfer.  Unfilled batch slots stay zero — a zero matrix
        factors to zero reflectors, so padding slots are compute waste
        only, priced by the fill-ratio stat, never a correctness risk."""
        with _trace.span("serving.stage", service=self._sid, flush=fid,
                         bucket=f"{key.m}x{key.n}", batch=batch):
            buf = np.zeros((batch, key.m, key.n), np.dtype(key.dtype))
            for s, req in enumerate(chunk):
                m, n = req.shape
                buf[s, :m, :n] = req.a
            return jax.device_put(buf)

    def flush(self) -> Dict[int, QRResult]:
        """Execute every pending request; returns ``{rid: QRResult}``.

        Software pipeline over dispatch chunks: while chunk i's batched
        factorization computes (async dispatch), chunk i+1's stacked
        buffer is already staging host->device; each staged buffer is
        donated into its executable (compiled with ``donate_argnums``),
        so steady state holds one in-flight compute and one in-flight
        transfer, not a growing buffer population.  Each chunk's output
        stacks start their device->host copy as soon as it is
        dispatched, overlapping the next chunk's staging and compute;
        after every dispatch has been issued, each stack is fetched to
        the host once, whole, and every request's answer is a NumPy view
        into it (no per-request device program or transfer).  Health
        checks and escalations happen then too — a failing slice never
        stalls the healthy pipeline, and a device error that surfaces
        at a chunk's fetch escalates that chunk's requests like a
        dispatch error.

        Failure-atomic: if an exception escapes (escalation disabled or
        non-recoverable), every request not yet resolved to a result is
        restored to the pending queue before the exception propagates."""
        return self._flush(next(self._flush_ids))

    def _flush(self, fid: int) -> Dict[int, QRResult]:
        """:meth:`flush`, its spans labelled with flush id ``fid``."""
        self._check_tuning()
        with _trace.span("serving.bucketize", service=self._sid, flush=fid):
            work = self._chunks()
        results: Dict[int, QRResult] = {}
        try:
            if work:
                self._flush_work(work, results, fid)
        except BaseException:
            done = set(results)
            self._pending = [req for _, chunk in work for req in chunk
                             if req.rid not in done] + self._pending
            raise
        for rid, reason in self._quarantined.items():
            results[rid] = QRResult(rid=rid, q=None, r=None,
                                    error=f"quarantined:{reason}")
        self._quarantined.clear()
        return results

    def _flush_work(self, work, results: Dict[int, QRResult],
                    fid: int) -> None:
        with _trace.span("serving.plan", service=self._sid, flush=fid,
                         chunks=len(work)):
            planned = [self._plan_with_escalation(
                key, pad_batch(len(chunk), max_batch=self.policy.max_batch))
                for key, chunk in work]
        verify_on = self._verify_on()
        kernel_chunks = [i for i, (plan, _) in enumerate(planned)
                        if plan is not None]
        staged: Dict[int, Array] = {}
        if kernel_chunks:
            i0 = kernel_chunks[0]
            staged[i0] = self._stage(work[i0][0], work[i0][1],
                                     planned[i0][0].batch, fid)
        outs: Dict[int, object] = {}
        for pos, i in enumerate(kernel_chunks):
            plan, rung = planned[i]
            key, chunk = work[i]
            if pos + 1 < len(kernel_chunks):
                j = kernel_chunks[pos + 1]
                staged[j] = self._stage(work[j][0], work[j][1],
                                        planned[j][0].batch, fid)
            tag = f"{key.m}x{key.n}:{rung}"
            with _trace.span("serving.dispatch", service=self._sid,
                             flush=fid, bucket=f"{key.m}x{key.n}",
                             batch=plan.batch, fill=len(chunk), rung=rung):
                try:
                    _inject.sleep(tag)
                    _inject.check("dispatch", tag)
                    out = plan.fn(staged.pop(i))  # async; donates buffer
                    outs[i] = _inject.corrupt_output(out, tag)
                    for x in outs[i]:
                        x.copy_to_host_async()
                except Exception as e:  # noqa: BLE001
                    if not self.escalate:
                        raise
                    # Dispatch raised before results existed.
                    staged.pop(i, None)
                    self._recover_chunk(key, chunk, rung, e, "dispatch",
                                        results)
                    planned[i] = (None, "recovered")
                    continue
            self._count("dispatches")
            self._count("matrices_served", len(chunk))
            self._count("padded_slots", plan.batch - len(chunk))
            now = time.monotonic()
            for req in chunk:
                self._observe("queue_wait_seconds", now - req.t_submit)
            self._observe("bucket_fill", len(chunk) / plan.batch)
            real = sum(m * n for m, n in (r.shape for r in chunk))
            waste = 1.0 - real / (plan.batch * key.m * key.n)
            self._observe("padding_waste", waste, bucket=f"{key.m}x{key.n}")
        with _trace.span("serving.unpad", service=self._sid, flush=fid):
            # Every fetch comes before any answer, so a fetch error that
            # raises (escalation off) leaves no request resolved.
            hosts: Dict[int, Tuple[np.ndarray, ...]] = {}
            for i in outs:
                plan, rung = planned[i]
                key, chunk = work[i]
                try:
                    hosts[i] = self._fetch(key, plan, outs[i], fid)
                except Exception as e:  # noqa: BLE001
                    if not self.escalate:
                        raise
                    # A device error deferred past dispatch surfaces here.
                    self._recover_chunk(key, chunk, rung, e, "fetch",
                                        results)
                    planned[i] = (None, "recovered")
            for i, (key, chunk) in enumerate(work):
                plan, rung = planned[i]
                if rung == "recovered":
                    continue
                if plan is None:               # breaker-pinned / lapack
                    results.update(self._lapack_chunk(key, chunk))
                    self._count("dispatches")
                    self._count("matrices_served", len(chunk))
                    continue
                host = hosts[i]
                bad: Set[int] = set()
                if verify_on:
                    bad = self._verify_chunk(key, chunk, outs[i], rung)
                now = time.monotonic()
                for s, req in enumerate(chunk):
                    if s in bad:
                        results[req.rid] = self._recover_request(
                            req, key, rung)
                        continue
                    m, n = req.shape
                    k = min(m, n)
                    if key.mode == "r":
                        q_mat, r_mat = None, host[0][s, :k, :n]
                    else:
                        q_mat, r_mat = host[0][s, :m, :k], host[1][s, :k, :n]
                    results[req.rid] = QRResult(rid=req.rid, q=q_mat,
                                                r=r_mat)
                    self._observe("latency_seconds", now - req.t_submit)

    def _fetch(self, key: BucketKey, plan: _BucketPlan, out,
               fid: int) -> Tuple[np.ndarray, ...]:
        """Materialize one chunk's padded output stacks on the host, each
        once and whole (padding slots included), waiting only for the
        copies :meth:`_flush_work` started at dispatch."""
        nbytes = sum(int(x.nbytes) for x in out)
        with _trace.span("serving.fetch", service=self._sid, flush=fid,
                         bucket=f"{key.m}x{key.n}", batch=plan.batch,
                         bytes=nbytes):
            host = tuple(np.asarray(x) for x in out)
        self._count("host_fetches", len(host))
        self._count("fetch_bytes", nbytes)
        return host

    def _verify_chunk(self, key: BucketKey, chunk: List[QRRequest],
                      out, rung: str) -> Set[int]:
        """Per-slice health check of one synced bucket: ONE vmapped
        stats program over the padded stack, host-side verdicts.  A
        failing slice is recorded (and escalated by the caller) alone —
        its bucket-mates are unaffected."""
        a_stack = np.zeros((out[0].shape[0], key.m, key.n),
                           np.dtype(key.dtype))
        for s, req in enumerate(chunk):
            m, n = req.shape
            a_stack[s, :m, :n] = req.a
        kp = min(key.m, key.n)   # factors come back fully padded
        with _trace.span("serving.verify", service=self._sid,
                         bucket=f"{key.m}x{key.n}"):
            if key.mode == "r":
                reports = _verify.check_batch(
                    a_stack, None, out[0][:, :kp, :key.n])
            else:
                reports = _verify.check_batch(
                    a_stack, out[0][:, :, :kp], out[1][:, :kp, :key.n])
        bad: Set[int] = set()
        for s in range(len(chunk)):
            rep = reports[s]
            if rep.ok:
                continue
            bad.add(s)
            self._count("health_check_failures")
            self._record_escalation(key, _escalate.record(
                rung, "per-request", "health_check_failed",
                f"slice {s} ({chunk[s].shape[0]}x{chunk[s].shape[1]}): "
                f"{rep.reason} residual={rep.residual:.3e} "
                f"defect={rep.ortho_defect:.3e} tol={rep.tol:.3e}"))
        return bad

    # -------------------------------------------------------------- stats

    def stats(self) -> Dict[str, object]:
        """Serving counters: cache behavior, dispatch economy, padding
        waste, failure hardening.  ``bucket_fill_ratio`` is matrices
        served over batch slots dispatched (1.0 = every slot carried a
        real request); ``cache_hit_rate`` is plan-cache hits over
        lookups; ``breaker_open`` counts buckets currently pinned to the
        fallback path; ``host_fetches`` counts output stacks fetched to
        the host (one per output of each dispatched chunk, never one per
        request) and ``fetch_bytes`` the bytes they moved.

        Counters are a view over this instance's ``serving.*`` series in
        the process-global metrics registry (``service=<id>`` label)."""
        served = self._count_value("matrices_served")
        padded = self._count_value("padded_slots")
        hits = self._count_value("cache_hits")
        slots = served + padded
        lookups = hits + self._count_value("cache_misses")
        return dict(
            requests=self._count_value("requests"),
            matrices_served=served,
            dispatches=self._count_value("dispatches"),
            compiles=self._count_value("compiles"),
            cache_hits=hits,
            cache_misses=self._count_value("cache_misses"),
            cache_evictions=self._count_value("cache_evictions"),
            plan_invalidations=self._count_value("plan_invalidations"),
            plans_cached=len(self._plans),
            padded_slots=padded,
            bucket_fill_ratio=(served / slots) if slots else 1.0,
            cache_hit_rate=(hits / lookups) if lookups else 0.0,
            quarantined=self._count_value("quarantined"),
            escalations=self._count_value("escalations"),
            health_check_failures=self._count_value(
                "health_check_failures"),
            breaker_trips=self._count_value("breaker_trips"),
            breaker_open=len(self._breaker_open),
            host_fetches=self._count_value("host_fetches"),
            fetch_bytes=self._count_value("fetch_bytes"),
        )
