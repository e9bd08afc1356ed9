"""Tuning-cache and sweep tests (PR 8).

Covers the cache layer (shape classes, JSON round-trip, lookup), the
planner's "tuned" routing rule against both the committed CPU cache and
synthetic caches, the overlay semantics (explicit knobs beat measured
ones), the CI gate (check_cache), and a miniature end-to-end sweep.
"""

import json
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import QRConfig, qr
from repro.core.plan import plan, select_method
from repro.tuning import cache as tcache
from repro.tuning.cache import (DEFAULT_CACHE_PATH, TunedConfig, TuningCache,
                                TuningEntry, shape_class, set_active_cache)


@pytest.fixture(autouse=True)
def _restore_active_cache():
    """Every test leaves the process-wide active cache as it found it."""
    prev = set_active_cache(None)
    yield
    set_active_cache(prev)


def _entry(m=2048, n=2048, method="tiled", block=64, backend="cpu",
           device_kind="cpu", dtype="float32", best_us=100.0,
           heuristic_us=200.0, **kw):
    return TuningEntry(
        backend=backend, device_kind=device_kind, shape_class=(m, n),
        dtype=dtype,
        best=TunedConfig(method=method, block=block, **kw),
        best_us=best_us, heuristic_method="geqrf_ht",
        heuristic_us=heuristic_us,
        timings=tuple(sorted(((f"{method}[b{block}]", best_us),
                              ("geqrf_ht", heuristic_us)))))


# ------------------------------------------------------------ shape classes

def test_shape_class_matches_serving_buckets():
    from repro.serving.bucketing import pad_dim

    for m, n in ((256, 256), (300, 280), (511, 500), (1023, 1000)):
        assert shape_class(m, n) == (pad_dim(m, tile=32, max_waste=0.25),
                                     pad_dim(n, tile=32, max_waste=0.25))
    # the classes the routing-table edge shapes collapse into
    assert shape_class(255, 255) == (256, 256)
    assert shape_class(511, 500) == (512, 512)
    assert shape_class(300, 280) == (384, 288)


def test_shape_class_rejects_zero_dims():
    with pytest.raises(ValueError, match="nonempty"):
        shape_class(0, 5)
    with pytest.raises(ValueError, match="nonempty"):
        shape_class(5, 0)


# -------------------------------------------------------- cache round-trip

def test_cache_json_roundtrip(tmp_path):
    e = _entry(use_kernel=True, dispatch_mode="wavefront")
    c = TuningCache([e], source="test")
    path = str(tmp_path / "cache.json")
    c.save(path)
    c2 = TuningCache.load(path)
    assert c2.source == path and len(c2) == 1
    got = c2.lookup(backend="cpu", m=2048, n=2048, dtype=jnp.float32)
    assert got == e  # frozen dataclasses: full value equality
    assert got.best.dispatch_mode == "wavefront"
    assert got.timings_dict["tiled[b64]"] == 100.0


def test_cache_schema_mismatch_raises(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text(json.dumps({"schema": "qr-tuning-v0", "entries": []}))
    with pytest.raises(ValueError, match="schema"):
        TuningCache.load(str(path))


def test_cache_lookup_prefers_exact_device_kind():
    a = _entry(device_kind="cpu", best_us=10.0)
    b = _entry(device_kind="TPU v4", best_us=20.0, method="geqrf")
    c = TuningCache([a, b])
    assert len(c) == 2  # same key, different device_kind: both kept
    hit = c.lookup(backend="cpu", m=2048, n=2048, dtype=jnp.float32,
                   device_kind="TPU v4")
    assert hit.best.method == "geqrf"
    # unknown device_kind falls back to any same-backend entry
    any_hit = c.lookup(backend="cpu", m=2048, n=2048, dtype=jnp.float32,
                       device_kind="mystery")
    assert any_hit in (a, b)
    assert c.lookup(backend="cpu", m=0, n=2048, dtype=jnp.float32) is None


def test_cache_add_replaces_same_device_kind():
    c = TuningCache([_entry(best_us=10.0)])
    c.add(_entry(best_us=5.0, method="geqrf"))
    assert len(c) == 1
    assert c.lookup(backend="cpu", m=2048, n=2048,
                    dtype=jnp.float32).best.method == "geqrf"


# ------------------------------------------- the committed CPU default cache

def test_committed_default_cache_loads():
    c = TuningCache.load(DEFAULT_CACHE_PATH)
    assert len(c) >= 3
    for e in c.entries():
        assert e.backend == "cpu" and np.isfinite(e.best_us)
        assert e.timings_dict  # provenance: every candidate's wall time
        assert e.provenance_dict.get("mode") == "r"


def test_tuned_256_cpu_crossover_regression():
    """The pinned PR-8 regression: at 256^2 on CPU the measured cache
    must route the blocked LAPACK-style family (geqrf/geqrf_ht), never
    the tiled task graph the old 256-floor heuristic would have picked
    on an accelerator-tuned threshold.  (The committed sweep measured
    tiled ~2.4x slower there.)"""
    c = TuningCache.load(DEFAULT_CACHE_PATH)
    e = c.lookup(backend="cpu", m=256, n=256, dtype=jnp.float32)
    assert e is not None
    assert e.best.method in ("geqrf", "geqrf_ht")
    assert e.best.method != "tiled"
    # and the planner actually consults it
    set_active_cache(c)
    solver = plan((256, 256), jnp.float32, QRConfig(), backend="cpu",
                  explain=True)
    assert solver.config.method == e.best.method
    sel = solver.explain.selected
    assert sel.rule == "tuned" and "measured:" in sel.reason
    assert "us" in sel.reason  # cites real microseconds, not a threshold


def test_tuned_512_cpu_overrides_heuristic_tiled():
    """512^2 is where the heuristics say tiled on CPU; the committed
    measurements say the blocked family is >2x faster.  The cache must
    win and the trail must show tiled was never reached."""
    c = TuningCache.load(DEFAULT_CACHE_PATH)
    set_active_cache(c)
    solver = plan((512, 512), jnp.float32, QRConfig(), backend="cpu",
                  explain=True)
    assert solver.config.method in ("geqrf", "geqrf_ht")
    heur = select_method((512, 512), jnp.float32,
                         QRConfig(use_tuning_cache=False), backend="cpu")
    assert heur == "tiled"  # the displaced heuristic pick


def test_tuned_solver_still_matches_oracle():
    """Routing through the cache changes the method, not the answer."""
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((256, 256), dtype=np.float32))
    set_active_cache(TuningCache.load(DEFAULT_CACHE_PATH))
    q, r = qr(a)
    rn = jnp.linalg.qr(a)[1]
    s = jnp.sign(jnp.diagonal(r)) * jnp.sign(jnp.diagonal(rn))
    np.testing.assert_allclose(np.asarray(r * s[:, None]), np.asarray(rn),
                               atol=2e-3)
    np.testing.assert_allclose(np.asarray(q @ r), np.asarray(a), atol=1e-4)


# ------------------------------------------------- planner integration

def test_cache_miss_falls_to_heuristics_with_recorded_decision():
    set_active_cache(TuningCache(source="test-empty"))
    solver = plan((300, 280), jnp.float32, QRConfig(), backend="cpu",
                  explain=True)
    assert solver.config.method == "geqrf_ht"  # the heuristic pick
    d = solver.explain.decision("tuned")
    assert d is not None and d.outcome == "rejected"
    solver2 = plan((4096, 32), jnp.float32, QRConfig(), backend="cpu",
                   explain=True)
    assert solver2.config.method == "tsqr"  # heuristics fully intact


def test_use_tuning_cache_false_pins_heuristics():
    set_active_cache(TuningCache.load(DEFAULT_CACHE_PATH))
    solver = plan((512, 512), jnp.float32,
                  QRConfig(use_tuning_cache=False), backend="cpu",
                  explain=True)
    assert solver.config.method == "tiled"
    d = solver.explain.decision("tuned")
    assert d.outcome == "rejected" and "use_tuning_cache=False" in d.reason


def test_tuned_overlay_respects_explicit_knobs():
    """A measured config only fills knobs the caller left at defaults:
    tuned block applies under QRConfig(), but an explicit block wins."""
    set_active_cache(TuningCache([_entry(method="tiled", block=64)]))
    tuned = plan((2048, 2048), jnp.float32, QRConfig(), backend="cpu",
                 explain=True)
    assert tuned.config.method == "tiled" and tuned.config.block == 64
    d = tuned.explain.decision("tuned_config")
    assert d is not None and d.outcome == "resolved"
    pinned = plan((2048, 2048), jnp.float32, QRConfig(block=48),
                  backend="cpu")
    assert pinned.config.method == "tiled" and pinned.config.block == 48


def test_explicit_method_beats_tuned():
    set_active_cache(TuningCache([_entry(method="tiled", block=64)]))
    solver = plan((2048, 2048), jnp.float32, QRConfig(method="geqrf"),
                  backend="cpu", explain=True)
    assert solver.config.method == "geqrf"
    assert solver.explain.selected.rule == "explicit"


def test_tuned_entry_with_unfit_method_rejected():
    """A cache entry naming a method that cannot serve this plan (here:
    unregistered) records a rejection and falls through — a stale cache
    must degrade to heuristics, never crash the planner."""
    set_active_cache(TuningCache([_entry(method="not_a_method")]))
    solver = plan((2048, 2048), jnp.float32, QRConfig(), backend="cpu",
                  explain=True)
    assert solver.config.method == "tiled"  # heuristic pick
    d = solver.explain.decision("tuned")
    assert d.outcome == "rejected"


def test_tuned_lookup_is_backend_keyed():
    """CPU measurements must not leak onto TPU plans."""
    set_active_cache(TuningCache.load(DEFAULT_CACHE_PATH))
    solver = plan((512, 512), jnp.float32, QRConfig(), backend="tpu",
                  explain=True)
    assert solver.config.method == "tiled"  # TPU heuristic, no cpu entry
    assert solver.explain.decision("tuned").outcome == "rejected"


def test_env_var_cache_loads(tmp_path, monkeypatch):
    path = str(tmp_path / "env_cache.json")
    TuningCache([_entry(method="geqrf", block=32)]).save(path)
    monkeypatch.setenv(tcache.ENV_VAR, path)
    set_active_cache(None)  # force a fresh lazy load
    c = tcache.active_cache()
    assert c.source == path and len(c) == 1
    info = tcache.active_cache_info()
    assert info["source"] == path and info["entries"] == 1
    assert info["schema"] == tcache.SCHEMA


# ----------------------------------------------------------- the CI gate

def test_check_cache_passes_on_consistent_entries():
    from repro.tuning.sweep import check_cache

    fresh = TuningCache([_entry(best_us=100.0, heuristic_us=150.0)])
    assert check_cache(fresh) == []
    assert check_cache(fresh, baseline=fresh) == []


def test_check_cache_flags_tuned_slower_than_heuristic():
    from repro.tuning.sweep import check_cache

    fresh = TuningCache([_entry(best_us=200.0, heuristic_us=100.0)])
    problems = check_cache(fresh)
    assert len(problems) == 1 and "slower than heuristic" in problems[0]


def test_check_cache_flags_baseline_drift():
    from repro.tuning.sweep import check_cache

    baseline = TuningCache([_entry(best_us=10.0, heuristic_us=20.0)])
    fresh = TuningCache([_entry(best_us=100.0, heuristic_us=200.0)])
    problems = check_cache(fresh, baseline, drift_tol=5.0)
    assert len(problems) == 1 and "regressed" in problems[0]
    assert check_cache(fresh, baseline, drift_tol=20.0) == []


# ------------------------------------------------- miniature end-to-end sweep

def test_sweep_small_shape_end_to_end(tmp_path):
    """A real (tiny) sweep: measures candidates, records the heuristic
    pick, emits tuning.* metrics, and the planner consults the result."""
    from repro.observability import metrics
    from repro.tuning.sweep import check_cache, sweep_shapes

    sweeps0 = metrics.counter_value("tuning.sweeps", backend="cpu")
    measured0 = metrics.counter_value("tuning.candidates", status="measured")
    cache = sweep_shapes([(64, 64)], reps=1, backend="cpu")
    assert len(cache) == 1
    e = cache.entries()[0]
    assert e.shape_class == (64, 64) and np.isfinite(e.heuristic_us)
    assert e.heuristic_method in e.timings_dict or any(
        lb.startswith("heuristic:") for lb in e.timings_dict)
    assert metrics.counter_value("tuning.sweeps", backend="cpu") == sweeps0 + 1
    assert metrics.counter_value("tuning.candidates",
                                 status="measured") > measured0
    # argmin construction: the gate passes on a fresh sweep by design
    assert check_cache(cache) == []
    # the planner consults what the sweep wrote
    path = str(tmp_path / "swept.json")
    cache.save(path)
    set_active_cache(TuningCache.load(path))
    solver = plan((64, 64), jnp.float32, QRConfig(), backend="cpu",
                  explain=True)
    assert solver.explain.selected.rule == "tuned"
    assert solver.config.method == e.best.method


def test_roofline_peaks_are_keyed_by_device_kind():
    """Peaks come from one table keyed by ``device_kind``; a kind not in
    it raises instead of borrowing another chip's numbers.  The tuner's
    relative pruning names the entry it models, so it works on CPU."""
    from repro.launch import roofline
    from repro.tuning.sweep import modeled_bound_us

    v5e = roofline.peaks("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    assert "TPU v5e" in v5e.source
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.peaks("cpu")
    with pytest.raises(KeyError):
        roofline.modeled_seconds(1.0, 1.0, device_kind="cpu")
    assert modeled_bound_us(QRConfig(method="geqrf"), 256, 256,
                            np.float32) > 0
