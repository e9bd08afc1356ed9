"""Observability layer tests (repro.observability).

The layer's contract has two halves, and both are load-bearing:

  * **Disabled (the default) is free.**  ``span()`` hands back one
    shared no-op singleton — no clock reads, no allocation, no
    ``block_until_ready`` — and the per-call cost is held to < 1% of
    even a small (256²) tiled solve by an explicit budget assertion.
    The jaxpr-pin twin of this guarantee (annotations add zero
    equations to the megakernel lowering) lives in tests/test_engine.py.
  * **Enabled is truthful and changes nothing.**  Spans nest correctly
    across the thread-local stack, a profiler capture records them into
    its host plane (labels as stats) with the layer itself off, a span
    never waits for the device, so traced and untraced serving give the
    same answers, the Chrome-trace export round-trips through JSON with
    the schema chrome://tracing loads, and the metrics registry stays
    exact under concurrent writers — including real
    ``QRService.submit_many`` traffic from threads.
"""

import json
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import observability as obs
from repro.core import QRConfig, plan
from repro.observability import instrument, metrics, trace


@pytest.fixture(autouse=True)
def _clean_observability():
    """Each test starts disabled with an empty registry/span buffer and
    leaves the process the same way (the layer is process-global)."""
    instrument.disable()
    metrics.reset()
    trace.clear()
    yield
    instrument.disable()
    metrics.reset()
    trace.clear()


# ------------------------------------------------------------------ metrics

def test_counter_labels_and_totals():
    metrics.counter("t.requests", route="a").inc()
    metrics.counter("t.requests", route="a").inc(2)
    metrics.counter("t.requests", route="b").inc(5)
    assert metrics.counter_value("t.requests", route="a") == 3
    assert metrics.counter_value("t.requests", route="b") == 5
    assert metrics.counter_value("t.requests", route="zzz") == 0
    assert metrics.counter_total("t.requests") == 8


def test_counter_rejects_negative():
    with pytest.raises(ValueError):
        metrics.counter("t.bad").inc(-1)


def test_gauge_set_inc_dec():
    g = metrics.gauge("t.depth", tree="x")
    g.set(4)
    g.inc()
    g.dec(2)
    assert metrics.snapshot()["gauges"]["t.depth"][0]["value"] == 3


def test_histogram_percentiles_and_snapshot():
    h = metrics.histogram("t.lat")
    for v in [1.0] * 90 + [100.0] * 10:
        h.observe(v)
    snap = metrics.snapshot()["histograms"]["t.lat"][0]
    assert snap["count"] == 100
    assert snap["min"] == 1.0 and snap["max"] == 100.0
    # log-bucketed CDF: p50 lands in the 1.0 bucket, p99 near the top
    assert h.percentile(50) < 5.0
    assert h.percentile(99) > 50.0
    assert 1.0 < h.mean < 100.0


def test_prometheus_export_format():
    metrics.counter("serve.reqs", route="a").inc(3)
    metrics.histogram("serve.lat").observe(0.5)
    text = metrics.to_prometheus()
    assert '# TYPE serve_reqs_total counter' in text
    assert 'serve_reqs_total{route="a"} 3' in text
    assert '# TYPE serve_lat histogram' in text
    assert 'serve_lat_bucket{le="+Inf"} 1' in text
    assert "serve_lat_count 1" in text


def test_registry_thread_safety_raw_counters():
    n_threads, n_incs = 8, 5000

    def worker():
        for _ in range(n_incs):
            metrics.counter("t.contended", shared="yes").inc()

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert metrics.counter_value("t.contended",
                                 shared="yes") == n_threads * n_incs


def test_registry_thread_safety_under_submit_many():
    """Concurrent serving traffic from threads keeps every service's
    registry-backed stats exact (the counters behind ``stats()`` share
    one process-global registry)."""
    from repro.serving import BucketingPolicy, QRService

    rng = np.random.default_rng(0)
    waves = [[rng.standard_normal((12, 12), dtype=np.float32)
              for _ in range(6)] for _ in range(4)]
    services = [QRService(policy=BucketingPolicy(tile=16, max_batch=4),
                          use_kernel=False) for _ in range(4)]
    errs = []

    def worker(svc, wave):
        try:
            svc.submit_many(wave)
        except Exception as e:  # pragma: no cover - surfaced below
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(svc, wave))
               for svc, wave in zip(services, waves)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    for svc in services:
        s = svc.stats()
        assert s["requests"] == s["matrices_served"] == 6
    assert metrics.counter_total("serving.requests") >= 24


def test_fresh_service_instances_start_at_zero():
    from repro.serving import QRService

    a = np.eye(8, dtype=np.float32)
    s1 = QRService(use_kernel=False)
    s1.submit_many([a])
    s2 = QRService(use_kernel=False)
    assert s1.stats()["requests"] == 1
    assert s2.stats()["requests"] == 0


# ------------------------------------------------------------------- tracer

def test_span_disabled_is_shared_noop_singleton():
    s1, s2 = trace.span("a"), trace.span("b", k=1)
    assert s1 is s2  # no allocation on the disabled path
    with s1 as sp:
        sp.set(more="labels")
    assert trace.spans() == []


def test_span_nesting_and_ordering():
    with obs.enabled_scope():
        with trace.span("outer", wave=0) as outer:
            with trace.span("inner.a") as a:
                pass
            with trace.span("inner.b") as b:
                pass
    done = trace.spans()
    assert [s.name for s in done] == ["inner.a", "inner.b", "outer"]
    assert a.parent_sid == outer.sid and b.parent_sid == outer.sid
    assert a.depth == b.depth == 1 and outer.depth == 0
    assert outer.t_start <= a.t_start <= a.t_end <= b.t_start <= outer.t_end
    assert "outer" in trace.tree() and "  inner.a" in trace.tree()


def test_chrome_trace_round_trip(tmp_path):
    with obs.enabled_scope():
        with trace.span("parent", bucket="64x64"):
            with trace.span("child"):
                time.sleep(0.001)
    path = trace.export_chrome_trace(str(tmp_path / "trace.json"))
    doc = json.loads(open(path).read())
    events = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    assert [e["name"] for e in events] == ["parent", "child"]  # ts-sorted
    for e in events:
        assert e["ph"] == "X"
        assert set(e) >= {"name", "ph", "ts", "dur", "pid", "tid", "args"}
        assert e["dur"] >= 0
    assert events[0]["args"] == {"bucket": "64x64"}
    child, parent = events[1], events[0]
    assert parent["ts"] <= child["ts"]
    assert child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 1e-3


def test_enabled_scope_restores_prior_state():
    assert not instrument.tracing_enabled()
    with obs.enabled_scope():
        assert instrument.tracing_enabled()
        assert instrument.annotations_enabled()
    assert not instrument.tracing_enabled()
    instrument.enable(tracing=False, annotations=True)
    with obs.enabled_scope():
        pass
    assert instrument.annotations_enabled()
    assert not instrument.tracing_enabled()


def _host_events(log_dir, name):
    """``(plane, stats)`` of every host-plane event named ``name`` in the
    ``.xplane.pb`` a profiler session wrote under ``log_dir``."""
    import glob

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return [(plane.name, dict(ev.stats)) for plane in pd.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events if ev.name == name]


def test_profiler_capture_records_spans_with_layer_off(tmp_path):
    """A span opened under ``jax.profiler.start_trace`` lands in the
    trace's host plane under its own name, labels as stats, while the
    observability layer itself stays disabled; it is kept in memory too."""
    assert not instrument.tracing_enabled()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span("t.capture", bucket="64x64", batch=8) as sp:
            sp.set(fill=5)
    finally:
        jax.profiler.stop_trace()
    ((plane, stats),) = _host_events(tmp_path, "t.capture")
    assert plane.startswith("/host:")
    assert stats == {"bucket": "64x64", "batch": 8, "fill": 5}
    (kept,) = trace.spans()
    assert kept.name == "t.capture" and kept.duration_us >= 0
    assert kept.labels == {"bucket": "64x64", "batch": 8, "fill": 5}


def test_no_span_recorded_when_neither_switch_is_on(tmp_path):
    """Neither tracing nor a profiler session: qr() and a serving flush
    record nothing; after a capture ends spans are off again."""
    from repro.core import qr
    from repro.serving import BucketingPolicy, QRService

    a = jnp.asarray(np.random.default_rng(3).standard_normal((32, 32)),
                    jnp.float32)
    svc = QRService(policy=BucketingPolicy(tile=16, max_batch=4),
                    use_kernel=False)
    jax.profiler.start_trace(str(tmp_path))
    jax.profiler.stop_trace()
    jax.block_until_ready(qr(a))
    svc.submit_many([np.eye(8, dtype=np.float32)] * 3)
    assert trace.span("t.off") is trace.span("t.off2")
    assert trace.spans() == []


class _SyncProbe:
    """Duck-typed array: records whether block_until_ready ran."""

    def __init__(self):
        self.blocked = False

    def block_until_ready(self):
        self.blocked = True
        return self


def test_enabled_span_never_blocks(monkeypatch):
    """A recording span waits for no device value: not for a value it
    carries, and not in a traced serving flush (whose unpad used to
    block only while tracing)."""
    from repro.serving import BucketingPolicy, QRService

    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(x) or real(x))
    probe = _SyncProbe()
    svc = QRService(policy=BucketingPolicy(tile=16, max_batch=4),
                    use_kernel=False)
    wave = [np.random.default_rng(4).standard_normal((12, 10))
            .astype(np.float32) for _ in range(5)]
    with obs.enabled_scope():
        with trace.span("t.probe", value=probe):
            pass
        svc.submit_many(wave)
    assert not probe.blocked
    assert calls == []
    assert any(s.name == "serving.unpad" for s in trace.spans())


def test_qr_yields_call_with_plan_nested():
    from repro.core import qr

    a = jnp.asarray(np.random.default_rng(5).standard_normal((48, 40)),
                    jnp.float32)
    with obs.enabled_scope(annotations=False):
        q, r = qr(a)
    spans = trace.spans()
    assert [s.name for s in spans] == ["qr.plan", "qr.call"]
    plan_sp, call = spans
    assert call.labels == {"shape": (48, 40)} and call.depth == 0
    assert plan_sp.parent_sid == call.sid and plan_sp.depth == 1
    assert call.t_start <= plan_sp.t_start <= plan_sp.t_end <= call.t_end
    np.testing.assert_allclose(np.asarray(q @ r), np.asarray(a), atol=1e-4)


def test_submit_many_spans_share_one_flush_and_change_no_answer():
    """One traced ``submit_many`` yields ``serving.submit`` holding admit,
    bucketize, plan, stage, dispatch and unpad, with one ``serving.fetch``
    per dispatched chunk inside unpad, all with one ``flush`` label; the
    answers are bitwise those of an untraced flush."""
    from repro.serving import BucketingPolicy, QRService

    rng = np.random.default_rng(6)
    wave = [rng.standard_normal(s).astype(np.float32)
            for s in [(12, 10), (30, 30), (12, 12), (40, 20), (9, 9)]]
    svc = QRService(policy=BucketingPolicy(tile=16, max_batch=2),
                    use_kernel=False)
    off = svc.submit_many(wave)
    assert trace.spans() == []
    with obs.enabled_scope(annotations=False):
        on = svc.submit_many(wave)
    spans = trace.spans()
    (sub,) = [s for s in spans if s.name == "serving.submit"]
    assert sub.labels["requests"] == len(wave) and sub.depth == 0
    (unpad,) = [s for s in spans if s.name == "serving.unpad"]
    fetches = [s for s in spans if s.name == "serving.fetch"]
    kids = [s for s in spans if s is not sub and s.name != "serving.fetch"]
    assert {s.name for s in kids} == {
        "serving.admit", "serving.bucketize", "serving.plan",
        "serving.stage", "serving.dispatch", "serving.unpad"}
    assert all(s.parent_sid == sub.sid for s in kids)
    assert all(s.parent_sid == unpad.sid for s in fetches)
    assert len(fetches) == sum(1 for s in kids
                               if s.name == "serving.dispatch")
    assert {s.labels["flush"] for s in spans} == {sub.labels["flush"]}
    assert {s.labels["service"] for s in spans} == {svc._sid}
    stages = [s for s in kids if s.name == "serving.stage"]
    assert len(stages) == sum(1 for s in kids
                              if s.name == "serving.dispatch") > 1
    for a, b in zip(off, on):
        assert a.ok and b.ok
        np.testing.assert_array_equal(np.asarray(a.q), np.asarray(b.q))
        np.testing.assert_array_equal(np.asarray(a.r), np.asarray(b.r))


def test_flush_ids_count_per_service():
    from repro.serving import QRService

    a = np.eye(8, dtype=np.float32)
    svc = QRService(use_kernel=False)
    with obs.enabled_scope(annotations=False):
        svc.submit_many([a])
        svc.submit(a)
        svc.flush()
        svc.submit_many([a])
    flushes = [s.labels["flush"] for s in trace.spans()
               if s.name == "serving.bucketize"]
    assert flushes == [0, 1, 2]


def test_profiler_capture_leaves_annotations_off(tmp_path):
    """A capture turns spans on but not profiler annotations: a program
    lowered during a capture carries no scope name, the same program as
    one lowered outside it."""
    from repro.observability import profiler

    def lowered(debug_info):
        def f(x):
            with profiler.annotate("t.scope"):
                return jnp.sin(x) * 2.0
        return jax.jit(f).lower(jnp.ones((4,))).as_text(
            debug_info=debug_info)

    outside = lowered(False)
    jax.profiler.start_trace(str(tmp_path))
    try:
        inside, inside_debug = lowered(False), lowered(True)
    finally:
        jax.profiler.stop_trace()
    assert inside == outside and "t.scope" not in inside_debug
    with obs.enabled_scope():
        assert "t.scope" in lowered(True)  # the switch that turns them on


# ----------------------------------------------------------------- overhead

def test_disabled_overhead_budget():
    """The disabled-mode budget: one labelled span (what a hot serving /
    ``qr()`` boundary adds: two flag tests) must cost < 1% of even a
    small tiled 256² solve.  Generous on both sides — the null path is
    well under 1 µs, the solve is ms."""
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((256, 256), dtype=np.float32))
    solver = plan(a.shape, a.dtype,
                  QRConfig(method="tiled", mode="r", block=64,
                           use_kernel=False))
    jax.block_until_ready(solver.solve(a))  # warm the jit cache
    t0 = time.perf_counter()
    jax.block_until_ready(solver.solve(a))
    solve_s = time.perf_counter() - t0

    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        with trace.span("overhead.probe", mode="megakernel"):
            pass
    per_call_s = (time.perf_counter() - t0) / n
    assert per_call_s < 0.01 * solve_s, (
        f"disabled span costs {per_call_s * 1e6:.2f} us/call, "
        f"> 1% of the {solve_s * 1e3:.2f} ms tiled 256^2 solve")


# ------------------------------------------------------- planner / pipeline

def test_planner_emits_plan_and_fallback_counters():
    # use_tuning_cache=False pins the heuristic table — this test asserts
    # the heuristic pick's counter labels, not the measured cache's.
    plan((512, 512), jnp.float32, QRConfig(use_tuning_cache=False),
         backend="cpu")
    assert metrics.counter_value("planner.plans", method="tiled") == 1
    plan((300, 280), jnp.float32, QRConfig(), backend="cpu")
    assert metrics.counter_value(
        "planner.fallbacks", reason="tiled_min_dim_cpu_floor") == 1


def test_engine_emits_dispatch_and_dma_series():
    from repro.core import engine

    p = q = 3
    nb = 8
    rng = np.random.default_rng(1)
    tiles = jnp.asarray(
        rng.standard_normal((p, q, nb, nb), dtype=np.float32))
    jax.block_until_ready(engine.factor_tiles(
        tiles, p=p, q=q, nb=nb, use_kernel=True, interpret=True,
        dispatch_mode="megakernel").tiles)
    assert metrics.counter_value("engine.dispatches", mode="megakernel",
                                 phase="execute") == 1
    st = engine.schedule_stats(p, q, nb)
    assert metrics.counter_value(
        "engine.modeled_dma_bytes", mode="megakernel",
        phase="execute") == st["megakernel"]["modeled_dma_bytes"]


def test_end_to_end_capture_covers_serving_pipeline(tmp_path):
    """A traced serving run yields Chrome-trace spans covering the full
    bucketize -> plan -> dispatch -> unpad pipeline plus the serving
    histograms — the acceptance shape of the observability PR."""
    from repro.serving import BucketingPolicy, QRService

    rng = np.random.default_rng(2)
    svc = QRService(policy=BucketingPolicy(tile=16, max_batch=4),
                    use_kernel=False)
    with obs.enabled_scope():
        svc.submit_many([rng.standard_normal((12, 10), dtype=np.float32)
                         for _ in range(3)])
    names = {s.name for s in trace.spans()}
    assert {"serving.bucketize", "serving.plan", "serving.dispatch",
            "serving.unpad"} <= names
    doc = trace.chrome_trace()
    assert len(doc["traceEvents"]) == len(trace.spans())
    snap = metrics.snapshot()
    for h in ("serving.queue_wait_seconds", "serving.latency_seconds",
              "serving.bucket_fill", "serving.padding_waste"):
        assert h in snap["histograms"], h
    assert metrics.counter_value("serving.dispatches",
                                 service=svc._sid) == 1
