"""One run of one benchmark cell, driven by data.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration, traffic kind or
per-layer metric sits in a file of its own, found by name:

  BENCHMARK.json                 cells, metrics, bounds
  bench/workloads/<cell>.json    the cell: its config, its traffic mix
                                 (``traffic.kind`` and the kind's
                                 parameters) and its correctness limits
  bench/configs/<config>.json    the configuration as it is run
  bench/traffic/<kind>.py        the driver that generates and serves a
                                 traffic kind (class ``Driver``)
  bench/metrics/<metric>.py      the reader of one per-layer metric
                                 (``read(ctx) -> float | None``)
  bench/references/<ref>.py      the plain reference a config names

A run sets up (inputs from the seed, every shape of the cell warmed),
measures for ``--seconds`` seconds with nothing compiling, compares what
the window produced with the reference, and prints one JSON line last on
standard output.  With ``--trace 1`` the window runs under the JAX
profiler and the line carries the per-layer metrics read from the trace;
otherwise it carries the end-to-end metrics.  Earlier lines (JSON, key
``bench``) carry what the last line may not: route and tile, compiles in
set-up and in the window, planner fallbacks, escalations, padding.

A run is correct when every compared answer meets its limits and the
window had no error, no escalation and no planner fallback: an answer
that the degradation ladder or a fallback route gave is not the
configuration's answer.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import types
from typing import Dict, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, unknown cell)."""


# ------------------------------------------------------------ lookups

def load_json(*parts: str) -> dict:
    path = os.path.join(*parts)
    if not os.path.isfile(path):
        raise BenchError(f"missing {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str) -> types.ModuleType:
    """``bench/<kind>/<name>.py`` as a module."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise BenchError(f"missing bench/{kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(root: str, workload: str) -> dict:
    """The cell's entries from BENCHMARK.json and its files, by name."""
    bench = load_json(root, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    cell = load_json(BENCH_DIR, "workloads", f"{workload}.json")
    if (cell["config"] != entry["config"]
            or cell["traffic"]["name"] != entry["traffic"]):
        raise BenchError(f"bench/workloads/{workload}.json names "
                         f"{cell['config']}/{cell['traffic']['name']}, "
                         f"BENCHMARK.json {entry['config']}/"
                         f"{entry['traffic']}")

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "entry": entry, "cell": cell,
        "config": load_json(BENCH_DIR, "configs", f"{cell['config']}.json"),
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


# ------------------------------------------------------- bookkeeping

class Compiles:
    """Backend compiles and persistent-cache hits, from JAX's own
    monitoring events (copied from the program's ``chip_smoke.py``)."""

    def __init__(self):
        from jax import monitoring

        self.count, self.seconds, self.cache_hits = 0, 0.0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return self.count, self.seconds, self.cache_hits


def program_counters() -> Dict[str, float]:
    from repro.observability import metrics

    return {name: metrics.counter_total(name)
            for name in ("planner.fallbacks", "robustness.escalations")}


def device_info(jax, chips: int) -> dict:
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def host_cpu() -> Dict[str, float]:
    """This process's CPU seconds: shows whether a slow window was spent
    on the host."""
    return {"process_cpu_s": time.process_time()}


#: mallopt(3) parameters of glibc, by the names a configuration uses.
MALLOPT = {"arena_max": -8, "trim_threshold": -1, "mmap_threshold": -3}


def set_host_malloc(settings: Dict[str, int]) -> None:
    """Applies a configuration's ``host_malloc`` (glibc ``mallopt``) to
    this process.  Called before JAX starts its threads, so that the
    arena limit holds for them too."""
    import ctypes

    libc = ctypes.CDLL("libc.so.6")
    for name, value in settings.items():
        if libc.mallopt(MALLOPT[name], int(value)) != 1:
            raise BenchError(f"mallopt {name}={value} refused")


def emit(stream, **fields) -> None:
    print(json.dumps(fields), file=stream, flush=True)


# --------------------------------------------------------------- run

def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             cell: Optional[dict] = None, keep_trace: Optional[str] = None,
             out=sys.stdout, err=sys.stderr) -> dict:
    """One run; returns the result line's object (also printed last).

    ``require_tpu=False`` and ``cell`` (a workload file's contents in
    place of the file) exist for the benchmark's own tests, which drive a
    run on the CPU at a small size."""
    spec = resolve(ROOT, workload)
    if cell is not None:
        spec["cell"] = cell
    cell, config = spec["cell"], spec["config"]
    chips = int(spec["entry"]["chips"])

    if "host_malloc" in config:
        set_host_malloc(config["host_malloc"])
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax

    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # Every program goes to the persistent cache, the eager ones that
    # compile in milliseconds too, so that only a cell's first run in a
    # checkout compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise BenchError(f"platform is {devices[0].platform!r}, not tpu")
    if len(devices) < chips:
        raise BenchError(f"{len(devices)} device(s); the cell needs {chips}")
    if trace:
        import peaks
        import tracereduce

        pk = peaks.peaks(devices[0].device_kind)

    compiles = Compiles()
    driver_mod = load_module("traffic", cell["traffic"]["kind"])
    driver = driver_mod.Driver(config, cell, seed)
    prog0 = program_counters()
    info = driver.setup()
    setup_c = compiles.mark()
    setup_s = time.monotonic() - t_start
    emit(out, bench="setup", workload=workload, seed=seed,
         device_kind=devices[0].device_kind, jax=jax.__version__,
         compile_cache=cache_dir, setup_s=setup_s,
         setup_compiles=setup_c[0], setup_compile_s=setup_c[1],
         setup_cache_hits=setup_c[2], **info)

    counters0 = driver.counters()
    host0 = host_cpu()
    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while time.perf_counter() < deadline:
            driver.step()
        window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    win_c = compiles.mark()
    host = {k: v - host0[k] for k, v in host_cpu().items()}
    counters = {k: v - counters0.get(k, 0)
                for k, v in driver.counters().items()}
    prog = {k: v - prog0[k] for k, v in program_counters().items()}
    device = device_info(jax, chips)
    e2e = driver.end_to_end(window_s)
    emit(out, bench="window", workload=workload, window_s=window_s,
         window_compiles=win_c[0] - setup_c[0],
         window_compile_s=win_c[1] - setup_c[1],
         window_cache_hits=win_c[2] - setup_c[2],
         planner_fallbacks=prog["planner.fallbacks"],
         escalations=prog["robustness.escalations"], **host,
         **driver.window_info(counters), **e2e)

    t0 = time.monotonic()
    driver.release()
    checks = driver.compare(cell["limits"])
    emit(out, bench="compare", workload=workload, compared=checks.compared,
         failed=checks.failed, compare_s=time.monotonic() - t0,
         **driver.compare_info())
    health = {**driver.health(counters),
              "escalations": prog["robustness.escalations"],
              "planner_fallbacks": prog["planner.fallbacks"]}

    result = {"correct": checks.correct and not any(health.values()),
              "attempted": driver.attempted, "failed": driver.failed}
    metrics: Dict[str, dict] = {}
    breakdown = None
    if trace:
        try:
            red = tracereduce.reduce(tracereduce.load(
                tracereduce.find_xplane(log_dir)))
            if keep_trace:
                shutil.copytree(log_dir, keep_trace, dirs_exist_ok=True)
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        ctx = types.SimpleNamespace(trace=red, work=driver.work(),
                                    counters=counters, peaks=pk)
        for m in spec["per_layer"]:
            value = load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        emit(out, bench="trace", workload=workload, gaps=red["gaps"],
             idle_by_span=red["idle_by_span"][:10],
             programs=red["programs"], **breakdown)
    else:
        for m in spec["end_to_end"]:
            value = setup_s if m["name"] == "setup_s" else e2e[m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result.update(metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {**checks.checks(),
                        **{k: {"value": v, "limit": 0}
                           for k, v in health.items()}}
    for name, c in checks.checks().items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} "
              f"(units of eps32*max(m,n); worst of {checks.compared})",
              file=err, flush=True)
    for name, v in health.items():
        print(f"check {name}: {v!r} limit 0 (count in the window)",
              file=err, flush=True)
    print(json.dumps(result), file=out, flush=True)
    return result
