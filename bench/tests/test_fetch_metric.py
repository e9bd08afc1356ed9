"""The reader of ``serve.fetch_us``: the summed ``serving.fetch`` spans
(one per chunk, nested in ``serving.unpad``) over the requests answered,
None without such a span or without answered requests, and positive on
what a profiler capture of a real serving flush records."""

import tempfile
import types

import numpy as np
import pytest

import harness
from repro.observability import trace


def read(requests):
    ctx = types.SimpleNamespace(counters={"requests": requests})
    return harness.load_module("metrics", "serve.fetch_us").read(ctx)


def made(name, start_us, dur_us, parent=None, **labels):
    """A completed span as the tracer keeps it, on a hand-made clock."""
    sp = trace.Span(name, labels)
    sp.t_start, sp.t_end = start_us * 1e-6, (start_us + dur_us) * 1e-6
    if parent is not None:
        sp.parent_sid, sp.depth = parent.sid, parent.depth + 1
    return sp


@pytest.fixture()
def window(monkeypatch):
    """Puts a hand-made span list in place of the tracer's."""
    def put(spans):
        monkeypatch.setattr(trace, "spans", lambda: list(spans))
    return put


def test_entry_names_the_serving_cell():
    (entry,) = [m for m in harness.resolve(harness.ROOT, "serve-batch100")
                ["per_layer"] if m["name"] == "serve.fetch_us"]
    assert (entry["source"], entry["unit"], entry["better"],
            entry["layer"], entry["moves"]) == (
        "program_span", "us", "lower", "serving", "qr_per_s")
    for cell in ("dense-2048", "dense-4096"):
        assert all(m["name"] != "serve.fetch_us"
                   for m in harness.resolve(harness.ROOT, cell)["per_layer"])


def test_reads_none_without_a_fetch_span(window):
    window([made("serving.unpad", 0, 4000.0)])     # as on the parent
    assert read(100) is None
    window([made("serving.fetch", 0, 50.0)])
    assert read(0) is None


def test_sums_the_fetches_of_every_flush_over_the_requests(window):
    """Two flushes of 100 requests, two chunks each: the fetches nested
    in unpad count, the unpad around them does not."""
    spans = []
    for f, base in enumerate((0.0, 1e6)):
        unpad = made("serving.unpad", base, 4000.0, flush=f)
        spans += [made("serving.fetch", base + 10, 1500.0, parent=unpad,
                       flush=f, bucket="512x512", batch=64),
                  made("serving.fetch", base + 1600, 500.0, parent=unpad,
                       flush=f, bucket="512x512", batch=64),
                  unpad]
    window(spans)
    assert read(200) == pytest.approx(20.0)


def test_reads_what_a_profiler_capture_records():
    import jax

    from repro.serving import BucketingPolicy, QRService

    svc = QRService(policy=BucketingPolicy(tile=16, max_batch=4),
                    use_kernel=False)
    wave = [np.random.default_rng(1).standard_normal((12, 10))
            .astype(np.float32) for _ in range(6)]
    svc.submit_many(wave)
    trace.clear()
    with tempfile.TemporaryDirectory() as log_dir:
        jax.profiler.start_trace(log_dir)
        try:
            svc.submit_many(wave)
        finally:
            jax.profiler.stop_trace()
    try:
        fetches = [s for s in trace.spans() if s.name == "serving.fetch"]
        # Chunks of 4 and 2 in the 16x16 bucket: Q and R, float32.
        assert sorted(s.labels["bytes"] for s in fetches) == [
            b * 16 * (16 + 16) * 4 for b in (2, 4)]
        assert read(len(wave)) > 0
    finally:
        trace.clear()
