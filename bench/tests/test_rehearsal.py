"""A run rehearsed on the CPU: it refuses a platform other than TPU, and
with the platform check skipped it drives a whole run of each traffic
kind at a small size and comes out correct."""

import io
import json
import os
import subprocess
import sys
import time

import pytest

import harness


def small_cell(name: str) -> dict:
    """The cell's own workload file, at a size a CPU run can hold."""
    with open(os.path.join(harness.BENCH_DIR, "workloads",
                           f"{name}.json")) as f:
        cell = json.load(f)
    t = cell["traffic"]
    if t["kind"] == "dense_closed":
        t.update(m=512, n=512, pool=2, compare_calls=2)
    else:
        t.update(sizes=[[64, 64], [32, 32], [64, 32], [48, 48]], batch=8)
    return cell


@pytest.fixture()
def cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def run_small(name, seed=2 ** 31 + 12345, **kw):
    out, err = io.StringIO(), io.StringIO()
    res = harness.run_cell(name, seed, 0.3, False, t_start=time.monotonic(),
                           require_tpu=False, cell=small_cell(name),
                           out=out, err=err, **kw)
    lines = out.getvalue().splitlines()
    assert json.loads(lines[-1]) == json.loads(json.dumps(res))
    return res, lines, err.getvalue().splitlines()


def test_a_run_off_the_tpu_exits_without_a_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH_DIR, "run.py"),
         "--workload", "dense-2048", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=harness.ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert "not tpu" in proc.stderr
    assert proc.stdout.strip() == ""


@pytest.mark.parametrize("name", ["dense-2048", "serve-batch100"])
def test_small_run_is_correct(name, cache):
    res, lines, err = run_small(name)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) >= {"setup_s"}
    for k in ("platform", "kind", "count", "memory_peak_bytes"):
        assert k in res["device"]
    info = [json.loads(x) for x in lines[:-1]]
    assert [i["bench"] for i in info] == ["setup", "window", "compare"]
    names = [x.split(":")[0] for x in err if x.startswith("check ")]
    limits = small_cell(name)["limits"]
    assert names[:len(limits)] == [f"check {k}" for k in limits]
    assert names[-2:] == ["check escalations", "check planner_fallbacks"]
    assert err[-len(names):] == [x for x in err if x.startswith("check ")]
    assert all(c["value"] == 0 for k, c in res["checks"].items()
               if c["limit"] == 0)


def test_inputs_follow_the_seed():
    import numpy as np

    mod = harness.load_module("traffic", "serve_waves")
    cell = small_cell("serve-batch100")
    spec = harness.resolve(harness.ROOT, "serve-batch100")
    one, two, other = (mod.Driver(spec["config"], cell, s).make_inputs()
                       for s in (2 ** 33 + 1, 2 ** 33 + 1, 2 ** 33 + 2))
    assert all(np.array_equal(a, b) for a, b in zip(one, two))
    assert not all(np.array_equal(a, b) for a, b in zip(one, other))
    assert [a.shape for a in one] == [a.shape for a in other]
    assert [a.shape for a in one[::8]] == [(64, 64), (32, 32), (64, 32),
                                           (48, 48)]


def test_one_wave_of_each_size_is_compared(cache):
    res, lines, _ = run_small("serve-batch100")
    info = {json.loads(x)["bench"]: json.loads(x) for x in lines[:-1]}
    waves = info["compare"]["compared_waves"]
    sizes = len(small_cell("serve-batch100")["traffic"]["sizes"])
    assert sorted(w % sizes for w in waves) == list(range(sizes))
    assert all(w < info["window"]["waves"] for w in waves)
    assert info["compare"]["compared"] == 8 * sizes
