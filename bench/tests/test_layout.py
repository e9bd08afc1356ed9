"""Everything a cell, configuration, traffic kind or metric needs is found
by its name, and BENCHMARK.json keeps to the contract's shape."""

import json
import os
import re

import pytest

import compare
import harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    assert isinstance(bench["run_seconds"], int)


def test_names_units_and_bounds(bench):
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in bench[k]}) == len(bench[k])
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_every_cell_resolves_by_name(bench):
    pairs = set()
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        pairs.add((w["config"], w["traffic"]))
        spec = harness.resolve(ROOT, w["name"])
        cell = spec["cell"]
        assert harness.load_module("traffic", cell["traffic"]["kind"]).Driver
        names = set(cell["limits"])
        assert {"residual", "orthogonality"} <= names <= set(compare.NUMBERS)
        assert names & {"r_vs_ref", "r_vs_ref_cond"}
        cfg = spec["config"]
        for ref in (cfg["reference"], cfg["control"]):
            harness.load_module("references", ref)
        e2e = {m["name"] for m in spec["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert spec["per_layer"], w["name"]
        for m in spec["per_layer"]:
            assert m["moves"] in e2e
            assert callable(harness.load_module("metrics", m["name"]).read)
    assert len(pairs) == len(bench["workloads"])


def test_every_config_has_its_file(bench):
    for c in bench["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        assert any(w["config"] == c["name"] for w in bench["workloads"])


def test_unknown_names_are_refused():
    with pytest.raises(harness.BenchError, match="no workload"):
        harness.resolve(ROOT, "no-such-cell")
    with pytest.raises(harness.BenchError, match="bench/metrics/nothing.py"):
        harness.load_module("metrics", "nothing")


def test_bench_files_are_named_from_name_characters():
    for dirpath, _, files in os.walk(harness.BENCH_DIR):
        if "__pycache__" in dirpath:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(dirpath, f), ROOT)
            assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel


def test_host_malloc_settings_apply():
    config = harness.load_json(harness.BENCH_DIR, "configs",
                               "batched-small-qr.json")
    assert set(config["host_malloc"]) <= set(harness.MALLOPT)
    harness.set_host_malloc(config["host_malloc"])
