"""The reduction from a profiler trace to device numbers.

``data/dense-2048-tiny.xplane.pb.gz`` is a trace recorded on a TPU v5
lite chip: ``bench/run.py --workload dense-2048 --seconds 0.05 --trace 1``,
four calls of ``qr()`` on 2048^2 float32 through the tile DAG megakernel.
"""

import os

import numpy as np
import pytest

import tracereduce

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "dense-2048-tiny.xplane.pb.gz")


@pytest.fixture(scope="module")
def pd():
    return tracereduce.load(DATA)


def test_union_and_gaps_by_hand():
    iv = [(5, 7), (0, 2), (1, 3), (6, 9), (12, 20)]
    busy = tracereduce.union(iv, 0, 15)
    assert busy == [(0, 3), (5, 9), (12, 15)]
    assert tracereduce.gaps(busy, 0, 15) == [(3, 5), (9, 12)]
    assert tracereduce.gaps([], 2, 4) == [(2, 4)]
    assert tracereduce.union([(0, 1)], 2, 4) == []


def test_gaps_take_the_innermost_open_span():
    spans = [(0, 100, "bench.window"), (10, 50, "bench.wave"),
             (20, 30, "bench.fetch"), (60, 90, "bench.wave")]
    names = tracereduce.name_gaps(spans, [(22, 26), (40, 44), (55, 57),
                                          (70, 80), (92, 96)])
    assert names == ["bench.fetch", "bench.wave", "bench.window",
                     "bench.wave", "bench.window"]


def test_op_names_drop_the_fingerprint():
    name = tracereduce.op_name(
        "jit_tiled_qr(1547057566250136823)",
        "%_factor_impl.1 = (f32[16,16,128,128]{3,2,1,0:T(8,128)S(1)}, ...)")
    assert name == "jit_tiled_qr:%_factor_impl.1 = (f32[16,16,128,128]"


def test_recorded_trace(pd):
    red = tracereduce.reduce(pd)
    spans = tracereduce.host_spans(pd)
    lo, hi = next((s, e) for s, e, n in spans if n == "bench.window")
    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert red["window_s"] == pytest.approx(0.054149415)
    assert sum(n == "bench.call" for _, _, n in spans) == 4
    # Busy time again on a 10 ns grid, independently of the interval
    # arithmetic.
    ops = tracereduce.device_ops(pd)["/device:TPU:0"]
    grid = np.zeros(int((hi - lo) / 10) + 1, bool)
    for s, e, _ in ops:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            grid[int(round((a - lo) / 10)):int(round((b - lo) / 10))] = True
    assert red["busy_s"] == pytest.approx(grid.sum() * 1e-8, rel=1e-3)
    assert red["busy_s"] == pytest.approx(0.048551014)
    assert red["devices"] == 1
    top, seconds = red["device_ops"][0]
    assert top == "jit_tiled_qr:%_factor_impl.1 = (f32[16,16,128,128]"
    assert seconds == pytest.approx(0.042659427)
    assert len(red["device_ops"]) == 10
    assert all(d >= red["device_ops"][i + 1][1]
               for i, (_, d) in enumerate(red["device_ops"][:-1]))
    # Every gap lies inside a call or between calls; their sum is the
    # idle time.
    assert sum(d for _, d in red["idle_by_span"]) == pytest.approx(
        red["window_s"] - red["busy_s"])
    assert {n for n, _ in red["idle_gaps"]} <= {"bench.call",
                                                 "bench.window"}
    # One program per call; the megakernel lies inside it, and the
    # program spans its ops and the short waits between them.
    assert [n for n, _ in red["programs"]] == ["jit_tiled_qr"]
    assert seconds < red["programs"][0][1] < red["window_s"]
    assert red["programs"][0][1] == pytest.approx(red["busy_s"], rel=1e-3)


def test_a_trace_without_the_window_is_refused(tmp_path):
    import jax

    jax.profiler.start_trace(str(tmp_path))
    jax.numpy.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(ValueError, match="bench.window"):
        tracereduce.reduce(tracereduce.load(
            tracereduce.find_xplane(str(tmp_path))))
