"""The readers of the per-layer metrics that come from the program's own
spans (``"source": "program_span"``): each sums the host time of one span
name over the traced window and divides by the calls or the requests;
each reads None where the window recorded no such span."""

import tempfile
import types

import numpy as np
import pytest

import harness
from repro.observability import trace

DENSE = {"qr.host_us.dense": "qr.call", "qr.plan_us.dense": "qr.plan"}
SERVE = {"serve.host_us": "serving.submit", "serve.admit_us": "serving.admit",
         "serve.stage_us": "serving.stage", "serve.unpad_us": "serving.unpad"}


def reader(name):
    return harness.load_module("metrics", name).read


def made(name, start_us, dur_us, parent=None, **labels):
    """A completed span as the tracer keeps it, on a hand-made clock."""
    sp = trace.Span(name, labels)
    sp.t_start, sp.t_end = start_us * 1e-6, (start_us + dur_us) * 1e-6
    if parent is not None:
        sp.parent_sid, sp.depth = parent.sid, parent.depth + 1
    return sp


def ctx(requests=0):
    return types.SimpleNamespace(counters={"requests": requests})


@pytest.fixture()
def window(monkeypatch):
    """Puts a hand-made span list in place of the tracer's."""
    def put(spans):
        monkeypatch.setattr(trace, "spans", lambda: list(spans))
    return put


def test_program_span_metrics_have_readers_and_entries():
    spec = {c: harness.resolve(harness.ROOT, c)["per_layer"]
            for c in ("dense-2048", "dense-4096", "serve-batch100")}
    for cell, names in (("dense-2048", DENSE), ("dense-4096", DENSE),
                        ("serve-batch100", SERVE)):
        mine = {m["name"]: m for m in spec[cell]
                if m["source"] == "program_span"}
        assert set(mine) == set(names)
        for m in mine.values():
            assert m["unit"] == "us" and m["better"] == "lower"
            assert callable(reader(m["name"]))


@pytest.mark.parametrize("name", sorted(DENSE) + sorted(SERVE))
def test_an_empty_window_reads_none(name, window):
    window([])
    assert reader(name)(ctx(requests=100)) is None


@pytest.mark.parametrize("name", sorted(SERVE))
def test_serving_readers_need_answered_requests(name, window):
    window([made(SERVE[name], 0, 50.0)])
    assert reader(name)(ctx(requests=0)) is None


def test_dense_readers_divide_by_the_calls(window):
    spans = []
    for k, (call_us, plan_us) in enumerate([(1000.0, 200.0),
                                            (3000.0, 400.0)]):
        call = made("qr.call", 10_000 * k, call_us, shape=(2048, 2048))
        spans += [made("qr.plan", 10_000 * k + 5, plan_us, parent=call), call]
    spans.append(made("bench.other", 0, 9e6))
    window(spans)
    assert reader("qr.host_us.dense")(ctx()) == pytest.approx(2000.0)
    assert reader("qr.plan_us.dense")(ctx()) == pytest.approx(300.0)


def test_plan_reads_none_without_a_call(window):
    window([made("qr.plan", 0, 200.0)])
    assert reader("qr.plan_us.dense")(ctx()) is None


def test_serving_readers_divide_by_the_requests(window):
    """Two flushes of 100 requests; the nested spans count for their own
    names only, and a second stage span of a flush adds to the first."""
    spans = []
    for f, base in enumerate((0.0, 1e6)):
        sub = made("serving.submit", base, 9000.0, flush=f, requests=100)
        kids = [("serving.admit", 100.0), ("serving.bucketize", 10.0),
                ("serving.plan", 20.0), ("serving.stage", 300.0),
                ("serving.stage", 200.0), ("serving.dispatch", 50.0),
                ("serving.unpad", 4000.0)]
        t = base
        for name, dur in kids:
            spans.append(made(name, t, dur, parent=sub, flush=f))
            t += dur
        spans.append(sub)
    window(spans)
    c = ctx(requests=200)
    assert reader("serve.host_us")(c) == pytest.approx(90.0)
    assert reader("serve.admit_us")(c) == pytest.approx(1.0)
    assert reader("serve.stage_us")(c) == pytest.approx(5.0)
    assert reader("serve.unpad_us")(c) == pytest.approx(40.0)


def test_readers_read_what_a_profiler_capture_records():
    """The program records its spans while a profiler session captures,
    with the observability layer itself left off, and every reader then
    reads a positive host time."""
    import jax
    import jax.numpy as jnp

    from repro.core import qr
    from repro.observability import instrument
    from repro.serving import BucketingPolicy, QRService

    assert not instrument.tracing_enabled()
    a = jnp.asarray(np.random.default_rng(0).standard_normal((64, 64)),
                    jnp.float32)
    svc = QRService(policy=BucketingPolicy(tile=16, max_batch=4),
                    use_kernel=False)
    wave = [np.random.default_rng(1).standard_normal((12, 10))
            .astype(np.float32) for _ in range(6)]
    jax.block_until_ready(qr(a))
    svc.submit_many(wave)
    trace.clear()
    with tempfile.TemporaryDirectory() as log_dir:
        jax.profiler.start_trace(log_dir)
        try:
            jax.block_until_ready(qr(a))
            svc.submit_many(wave)
        finally:
            jax.profiler.stop_trace()
    try:
        for name in sorted(DENSE):
            assert reader(name)(ctx()) > 0, name
        for name in sorted(SERVE):
            assert reader(name)(ctx(requests=len(wave))) > 0, name
    finally:
        trace.clear()
