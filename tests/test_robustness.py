"""Chaos suite for the robustness layer: every injectable fault class
fired against every consumer (serving, batched-ortho, plain ``qr()``),
plus the contracts the layer promises when OFF (verify-off is
jaxpr-identical to an unchecked solve) and the satellite fixes that
ride with it (flush atomicity, true watchdog median, the train_lm
fault-tolerance drill).

The acceptance scenario from the PR issue is the end-to-end test at the
bottom: one flush carrying (a) a NaN request in a mixed bucket, (b) a
compile failure on one bucket, and (c) a failed health check on a
dispatch — every uncorrupted request must come back
conformance-correct, the corrupted one quarantined with a named reason,
and the expected ``robustness.escalations`` counters fired.
"""

import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import gaussian
from repro.core.api import qr, QRConfig, plan
from repro.observability import metrics
from repro.robustness import escalate, guards, inject, verify
from repro.serving.bucketing import BucketingPolicy
from repro.serving.qr_service import QRService


@pytest.fixture(autouse=True)
def _disarm():
    """No fault leaks across tests."""
    inject.reset()
    yield
    inject.reset()


def _svc(**kw):
    kw.setdefault("policy", BucketingPolicy(tile=16, max_batch=4))
    kw.setdefault("use_kernel", False)
    return QRService(**kw)


def _randn(m, n, seed=0):
    return np.asarray(
        np.random.default_rng(seed).standard_normal((m, n)), np.float32)


def _resid(a, q, r):
    a, q, r = map(np.asarray, (a, q, r))
    return np.linalg.norm(a - q @ r) / max(np.linalg.norm(a), 1e-30)


def _tol(a):
    return verify.tolerance(np.asarray(a).dtype, *np.asarray(a).shape)


# ------------------------------------------------------------- admission

class TestAdmission:
    def test_rejects_nonfinite_with_named_reason(self):
        a = _randn(8, 4)
        a[2, 1] = np.nan
        with pytest.raises(guards.AdmissionError) as ei:
            guards.admit(a)
        assert ei.value.reason == "nonfinite_input"

    def test_rejects_bad_ndim_and_dtype(self):
        with pytest.raises(guards.AdmissionError) as ei:
            guards.admit(np.zeros(3, np.float32))
        assert ei.value.reason == "bad_ndim"
        with pytest.raises(guards.AdmissionError) as ei:
            guards.admit(np.zeros((3, 3), np.int32))
        assert ei.value.reason == "non_float_dtype"

    def test_condition_guard_is_opt_in(self):
        a = np.eye(4, dtype=np.float32)
        a[3, 3] = 1e-12                       # cond ~ 1e12
        guards.admit(a)                       # default: no cond check
        with pytest.raises(guards.AdmissionError) as ei:
            guards.admit(a, policy=guards.AdmissionPolicy(max_cond=1e6))
        assert ei.value.reason == "ill_conditioned"
        assert guards.estimate_condition(np.eye(3)) == pytest.approx(1.0)

    def test_service_quarantines_bad_request_in_mixed_bucket(self):
        svc = _svc(verify=True)
        good = [_randn(24, 12, seed=s) for s in range(3)]
        bad = good[1].copy()
        bad[0, 0] = np.inf
        rids = [svc.submit(good[0]), svc.submit(bad), svc.submit(good[2])]
        res = svc.flush()
        assert res[rids[1]].error == "quarantined:nonfinite_input"
        assert res[rids[1]].q is None and not res[rids[1]].ok
        for rid, a in ((rids[0], good[0]), (rids[2], good[2])):
            assert res[rid].ok
            assert _resid(a, res[rid].q, res[rid].r) < _tol(a)
        assert svc.stats()["quarantined"] == 1

    def test_flush_with_only_quarantined_requests(self):
        svc = _svc()
        bad = _randn(8, 4)
        bad[:] = np.nan
        rid = svc.submit(bad)
        res = svc.flush()
        assert set(res) == {rid} and not res[rid].ok
        assert svc.flush() == {}              # delivered exactly once


# ---------------------------------------------------------------- verify

class TestVerify:
    def test_tolerance_matches_conformance_rule(self):
        from test_conformance import _tol as conf_tol
        for dtype in (np.float32, np.float64):
            for m, n in ((64, 32), (8, 128)):
                assert verify.tolerance(dtype, m, n) == conf_tol(dtype, m, n)

    def test_healthy_factorization_passes(self):
        a = gaussian(32, 16, seed=3)
        q, r = jnp.linalg.qr(a)
        rep = verify.check_qr(a, q, r)
        assert rep.ok and rep.reason is None

    def test_corrupt_q_fails_with_reason(self):
        a = gaussian(32, 16, seed=3)
        q, r = jnp.linalg.qr(a)
        rep = verify.check_qr(a, q.at[0, 0].set(jnp.nan), r)
        assert not rep.ok and rep.reason == "nonfinite_output"
        rep = verify.check_qr(a, 2.0 * q, r)
        assert not rep.ok and rep.reason in ("residual_exceeds_tol",
                                             "ortho_defect_exceeds_tol")

    def test_r_only_gram_check(self):
        a = gaussian(32, 16, seed=4)
        r = jnp.linalg.qr(a, mode="r")
        assert verify.check_r(a, r).ok
        bad = verify.check_r(a, 1.5 * r)
        assert not bad.ok and bad.reason == "gram_residual_exceeds_tol"

    def test_batch_identifies_single_bad_slice(self):
        a = jnp.stack([gaussian(16, 8, seed=s) for s in range(4)])
        q, r = jax.vmap(jnp.linalg.qr)(a)
        q = q.at[2].set(jnp.nan)
        reports = verify.check_batch(a, q, r)
        assert [rep.ok for rep in reports] == [True, True, False, True]
        assert reports[2].reason == "nonfinite_output"

    def test_env_default_resolution(self, monkeypatch):
        monkeypatch.delenv("REPRO_VERIFY", raising=False)
        assert verify.verify_enabled(None) is False
        monkeypatch.setenv("REPRO_VERIFY", "1")
        assert verify.verify_enabled(None) is True
        assert verify.verify_enabled(False) is False   # explicit wins
        monkeypatch.setenv("REPRO_VERIFY", "off")
        assert verify.verify_enabled(None) is False

    def test_qrconfig_verify_validation(self):
        with pytest.raises(ValueError, match="verify"):
            QRConfig(verify="yes")
        assert QRConfig(verify=True).verify is True


# ------------------------------------------------------------ escalation

class TestEscalation:
    def test_ladder_is_monotone(self):
        assert escalate.ladder_below("megakernel") == (
            "wavefront", "oracle", "lapack")
        assert escalate.ladder_below("lapack") == ()
        # unknown pseudo-rungs land on the safe kernel-free tail
        assert escalate.ladder_below("planned") == ("oracle", "lapack")

    def test_classify_keeps_injected_site(self):
        assert escalate.classify(
            inject.InjectedFault("compile", "x"), "compile") \
            == "injected_compile"
        assert escalate.classify(ValueError("x"), "dispatch") \
            == "dispatch_failed"

    def test_record_fires_counter(self):
        before = metrics.counter_value(
            "robustness.escalations",
            **{"from": "megakernel", "to": "wavefront", "reason": "t"})
        esc = escalate.record("megakernel", "wavefront", "t", "detail")
        assert esc.rule == "t" and esc.reason == "detail"
        assert metrics.counter_value(
            "robustness.escalations",
            **{"from": "megakernel", "to": "wavefront",
               "reason": "t"}) == before + 1

    def test_solve_below_recovers_and_exhausts(self):
        a = _randn(20, 10, seed=5)
        q, r, rung, escs = escalate.solve_below(a, start="megakernel")
        assert rung in ("oracle", "lapack") and escs == []
        assert _resid(a, q, r) < _tol(a)
        # every remaining rung faulted -> exhausted, hops preserved
        with inject.active(inject.Fault(site="dispatch", times=None)):
            with pytest.raises(escalate.EscalationExhausted) as ei:
                escalate.solve_below(a, start="megakernel")
        assert len(ei.value.escalations) == 2   # oracle, lapack both raise

    def test_lapack_verify_failure_returns_factors(self):
        # a pathological input: lapack is the last word even if the
        # health check dislikes the answer (the input is the suspect)
        a = _randn(12, 6, seed=6)
        q, r, rung, _ = escalate.solve_below(a, start="oracle")
        assert rung == "lapack" or rung == "oracle"


# ------------------------------------------------------------- injection

class TestInjection:
    def test_poison_is_deterministic(self):
        a = _randn(16, 16, seed=7)
        p1 = inject.poison(a, kind="nan", frac=0.1, seed=3)
        p2 = inject.poison(a, kind="nan", frac=0.1, seed=3)
        assert np.array_equal(np.isnan(p1), np.isnan(p2))
        assert np.isnan(p1).sum() == max(1, int(0.1 * a.size))

    def test_times_gating_and_scoping(self):
        f = inject.Fault(site="compile", times=2)
        with inject.active(f):
            assert inject.enabled()
            for _ in range(2):
                with pytest.raises(inject.InjectedFault):
                    inject.check("compile", "anything")
            inject.check("compile", "anything")   # disarmed after 2
        assert not inject.enabled()
        inject.check("compile", "anything")       # out of scope: no-op

    def test_match_is_substring_on_tag(self):
        with inject.active(inject.Fault(site="dispatch", match="64x64")):
            inject.check("dispatch", "32x32:oracle")      # no match
            with pytest.raises(inject.InjectedFault) as ei:
                inject.check("dispatch", "64x64:megakernel")
        assert ei.value.site == "dispatch"

    def test_input_corruption_exercises_admission(self):
        svc = _svc()
        with inject.active(inject.Fault(site="input", match="24x12")):
            rid = svc.submit(_randn(24, 12, seed=8))
        res = svc.flush()
        assert res[rid].error == "quarantined:nonfinite_input"
        assert metrics.counter_value("robustness.faults_injected",
                                     site="input") >= 1


# -------------------------------------------------- service chaos matrix

class TestServiceChaos:
    def test_compile_fault_escalates_to_working_rung(self):
        svc = _svc(verify=True)
        arrs = [_randn(24, 12, seed=s) for s in range(3)]
        with inject.active(inject.Fault(site="compile", match="32x16")):
            outs = svc.submit_many(arrs)
        assert all(o.ok for o in outs)
        for a, o in zip(arrs, outs):
            assert _resid(a, o.q, o.r) < _tol(a)
        rules = [e.rule for e in svc.escalations]
        assert "injected_compile" in rules

    def test_dispatch_fault_recovers_per_request(self):
        svc = _svc(verify=True)
        arrs = [_randn(24, 12, seed=s) for s in range(3)]
        with inject.active(inject.Fault(site="dispatch", match="32x16")):
            outs = svc.submit_many(arrs)
        assert all(o.ok for o in outs)
        for a, o in zip(arrs, outs):
            assert _resid(a, o.q, o.r) < _tol(a)

    def test_output_corruption_caught_and_healed_per_slice(self):
        svc = _svc(verify=True)
        arrs = [_randn(24, 12, seed=s) for s in range(3)]
        with inject.active(inject.Fault(site="output", match="32x16",
                                        slice_index=1)):
            outs = svc.submit_many(arrs)
        assert all(o.ok for o in outs)
        for a, o in zip(arrs, outs):
            assert np.isfinite(np.asarray(o.q)).all()
            assert _resid(a, o.q, o.r) < _tol(a)
        assert svc.stats()["health_check_failures"] >= 1
        assert any(e.rule == "health_check_failed"
                   for e in svc.escalations)

    def test_vmem_fault_walks_megakernel_to_wavefront(self):
        svc = QRService(policy=BucketingPolicy(tile=8, max_batch=2),
                        use_kernel=True, interpret=True,
                        dispatch_mode="megakernel", verify=True)
        arrs = [_randn(16, 8, seed=s) for s in range(2)]
        with inject.active(inject.Fault(site="vmem", match="megakernel")):
            outs = svc.submit_many(arrs)
        assert all(o.ok for o in outs)
        for a, o in zip(arrs, outs):
            assert _resid(a, o.q, o.r) < _tol(a)
        hops = [(e.rung_from, e.rung_to) for e in svc.escalations]
        assert ("megakernel", "wavefront") in hops

    def test_latency_fault_only_slows(self):
        svc = _svc()
        with inject.active(inject.Fault(site="latency", delay_s=0.05)):
            outs = svc.submit_many([_randn(12, 6, seed=9)])
        assert outs[0].ok

    def test_mode_r_verify_and_recovery(self):
        svc = _svc(verify=True)
        arrs = [_randn(24, 12, seed=s) for s in range(2)]
        with inject.active(inject.Fault(site="output", match="32x16",
                                        slice_index=0)):
            outs = svc.submit_many(arrs, mode="r")
        assert all(o.ok and o.q is None for o in outs)
        for a, o in zip(arrs, outs):
            r = np.asarray(o.r)
            gram = np.linalg.norm(a.T @ a - r.T @ r) \
                / np.linalg.norm(a) ** 2
            assert gram < _tol(a)


# -------------------------------------------------------- circuit breaker

class TestCircuitBreaker:
    def test_trips_evicts_and_pins(self):
        svc = _svc(verify=True, breaker_threshold=2)
        fault = inject.Fault(site="dispatch", match="32x16", times=None)
        with inject.active(fault):
            for s in range(2):
                svc.submit_many([_randn(24, 12, seed=s)])
        st = svc.stats()
        assert st["breaker_trips"] == 1 and st["breaker_open"] == 1
        assert not any(ck[0].m == 32 and ck[0].n == 16
                       for ck in svc._plans)   # plans evicted
        # pinned: lapack serves the bucket even with the fault still armed
        with inject.active(inject.Fault(site="dispatch", match="32x16",
                                        times=None)):
            outs = svc.submit_many([_randn(24, 12, seed=11)])
        assert outs[0].ok
        assert svc.stats()["breaker_open"] == 1

    def test_resets_on_tuning_fingerprint_change(self):
        from repro.tuning.cache import TuningCache, active_cache, \
            set_active_cache
        svc = _svc(verify=True, breaker_threshold=1)
        with inject.active(inject.Fault(site="dispatch", match="32x16")):
            svc.submit_many([_randn(24, 12, seed=12)])
        assert svc.stats()["breaker_open"] == 1
        prev = active_cache()
        try:
            set_active_cache(TuningCache(source="test:breaker-reset"))
            svc.submit_many([_randn(24, 12, seed=13)])
            assert svc.stats()["breaker_open"] == 0
        finally:
            set_active_cache(prev)


# -------------------------------------------------------- flush atomicity

class TestFlushAtomicity:
    def test_error_restores_unprocessed_requests(self):
        svc = _svc(escalate=False)             # failures raise through
        arrs = [_randn(24, 12, seed=s) for s in range(3)]
        rids = [svc.submit(a) for a in arrs]
        with inject.active(inject.Fault(site="dispatch", match="32x16")):
            with pytest.raises(inject.InjectedFault):
                svc.flush()
        assert len(svc._pending) == 3          # nothing dropped
        res = svc.flush()                      # fault disarmed: succeeds
        for rid, a in zip(rids, arrs):
            assert res[rid].ok
            assert _resid(a, res[rid].q, res[rid].r) < _tol(a)

    def test_compile_error_restores_requests(self):
        svc = _svc(escalate=False)
        rid = svc.submit(_randn(24, 12, seed=14))
        with inject.active(inject.Fault(site="compile", match="32x16")):
            with pytest.raises(inject.InjectedFault):
                svc.flush()
        assert [r.rid for r in svc._pending] == [rid]
        assert svc.flush()[rid].ok


# ------------------------------------------------------------- plain qr()

class TestCheckedQr:
    def test_output_corruption_recovered(self):
        a = gaussian(20, 10, seed=15)
        with inject.active(inject.Fault(site="output", match="qr:20x10")):
            q, r = qr(a, config=QRConfig(verify=True))
        assert np.isfinite(np.asarray(q)).all()
        assert _resid(a, q, r) < _tol(a)

    def test_mode_r_recovery(self):
        a = gaussian(20, 10, seed=16)
        with inject.active(inject.Fault(site="output", match="qr:20x10")):
            r = qr(a, config=QRConfig(mode="r", verify=True))
        rr = np.asarray(r)
        assert np.isfinite(rr).all()

    def test_batched_input_heals_only_bad_slice(self):
        a = jnp.stack([gaussian(16, 8, seed=s) for s in range(3)])
        with inject.active(inject.Fault(site="output", match="qr:3x16x8",
                                        slice_index=2)):
            q, r = qr(a, config=QRConfig(verify=True))
        q, r = np.asarray(q), np.asarray(r)
        assert np.isfinite(q).all() and np.isfinite(r).all()
        for i in range(3):
            ai = np.asarray(a[i])
            assert _resid(ai, q[i], r[i]) < _tol(ai)

    def test_verify_off_is_jaxpr_identical(self):
        """The pin: the verify knob must not touch the traced program.
        Off, on, and no-knob all trace to the direct solver.solve jaxpr
        (under a trace the input is abstract, so the host-side check
        never fires)."""
        a = gaussian(32, 16, seed=17)

        def traced(cfg):
            return str(jax.make_jaxpr(
                lambda x: qr(x, config=cfg))(a))

        base = str(jax.make_jaxpr(
            plan(a.shape, a.dtype, QRConfig()).solve)(a))
        assert traced(QRConfig(verify=False)) == base
        assert traced(QRConfig(verify=True)) == base
        assert traced(QRConfig()) == base

    def test_verify_off_adds_zero_equations_eager_path(self):
        """Off-knob eager calls never import/resolve the checker into
        the compute: result is bitwise-identical to solver.solve."""
        a = gaussian(16, 8, seed=18)
        cfg = QRConfig(verify=False)
        q1, r1 = qr(a, config=cfg)
        q2, r2 = plan(a.shape, a.dtype, cfg).solve(a)
        assert np.array_equal(np.asarray(q1), np.asarray(q2))
        assert np.array_equal(np.asarray(r1), np.asarray(r2))


# ------------------------------------------------------ batched ortho path

class TestBatchedOrthoChaos:
    def test_corrupt_slice_escalates_to_leafwise(self):
        from repro.optim.batched_ortho import batched_orthogonalize
        leaves = [jnp.asarray(np.random.default_rng(19)
                              .standard_normal((3, 32, 16)), jnp.float32)]
        before = metrics.counter_total("optim.ortho_escalations")
        with inject.active(inject.Fault(site="output", match="ortho:32x16",
                                        slice_index=1)):
            outs = batched_orthogonalize(
                leaves, config=QRConfig(use_kernel=False, verify=True))
        q = np.asarray(outs[0])
        assert np.isfinite(q).all()
        for i in range(3):
            defect = np.linalg.norm(q[i].T @ q[i] - np.eye(16))
            assert defect < verify.tolerance(np.float32, 32, 16)
        assert metrics.counter_total("optim.ortho_escalations") \
            == before + 1

    def test_verify_off_matches_baseline(self):
        from repro.optim.batched_ortho import batched_orthogonalize
        leaves = [jnp.asarray(np.random.default_rng(20)
                              .standard_normal((2, 24, 8)), jnp.float32)]
        a = batched_orthogonalize(leaves,
                                  config=QRConfig(use_kernel=False))
        b = batched_orthogonalize(
            leaves, config=QRConfig(use_kernel=False, verify=False))
        assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))


# ------------------------------------------------------ watchdog satellite

class TestWatchdogMedian:
    def test_even_window_uses_true_median(self):
        from repro.distributed.fault_tolerance import StepWatchdog, _median
        assert _median([1.0, 2.0, 3.0, 10.0]) == 2.5   # not 3.0
        assert _median([1.0, 2.0, 3.0]) == 2.0
        wd = StepWatchdog()
        wd._times = [1.0, 1.0, 1.0, 9.0]
        assert wd.median == 1.0

    def test_straggler_counter_fires(self):
        from repro.distributed.fault_tolerance import StepWatchdog
        wd = StepWatchdog(threshold=2.0)
        before = metrics.counter_value("fault.straggler_steps")
        wd._times = [0.1] * 6
        wd._t0 = __import__("time").monotonic() - 1.0   # 1s step vs 0.1 median
        assert wd.stop(step=7) > 0.5
        assert wd.straggler_steps == [7]
        assert metrics.counter_value("fault.straggler_steps") == before + 1


# ----------------------------------------------- end-to-end acceptance

class TestAcceptance:
    def test_three_simultaneous_fault_classes_one_flush(self):
        """(a) NaN request in a mixed bucket, (b) compile failure on one
        bucket, (c) health-check failure on a dispatch — all armed at
        once; one flush must quarantine (a), escalate (b) and (c), and
        return conformance-correct results for every clean request."""
        svc = _svc(verify=True)
        small = [_randn(24, 12, seed=s) for s in range(3)]     # 32x16
        large = [_randn(40, 24, seed=s + 10) for s in range(2)]  # 48x32
        poisoned = inject.poison(small[1], kind="nan", seed=0)
        esc_before = metrics.counter_total("robustness.escalations")
        with inject.active(
                inject.Fault(site="compile", match="48x32"),       # (b)
                inject.Fault(site="output", match="32x16",
                             slice_index=0)):                      # (c)
            rids_small = [svc.submit(small[0]), svc.submit(poisoned),
                          svc.submit(small[2])]                    # (a)
            rids_large = [svc.submit(a) for a in large]
            res = svc.flush()
        # (a) quarantined, named
        assert res[rids_small[1]].error == "quarantined:nonfinite_input"
        # every clean request conformance-correct
        clean = [(rids_small[0], small[0]), (rids_small[2], small[2]),
                 (rids_large[0], large[0]), (rids_large[1], large[1])]
        for rid, a in clean:
            assert res[rid].ok, res[rid].error
            assert np.isfinite(np.asarray(res[rid].q)).all()
            assert _resid(a, res[rid].q, res[rid].r) < _tol(a)
        # (b) + (c) each fired a named escalation counter
        rules = {e.rule for e in svc.escalations}
        assert "injected_compile" in rules
        assert "health_check_failed" in rules
        assert metrics.counter_total("robustness.escalations") \
            > esc_before
        st = svc.stats()
        assert st["quarantined"] == 1 and st["escalations"] >= 2


# ------------------------------------------- train_lm FT drill (slow)

# The straggler lands at step 11: the post-restore watchdog needs its
# five-step warm-up (restore at 6 -> steps 6..10 recorded) before the
# straggler rule may fire.
_FT_SCRIPT_ARGS = [
    "examples/train_lm.py", "--smoke", "--steps", "12", "--seq", "16",
    "--batch", "2", "--optimizer", "adamw", "--fault-tolerance",
    "--checkpoint-every", "4", "--crash-at", "6",
    "--inject-straggler-at", "11", "--watchdog-threshold", "2.0",
]


@pytest.mark.slow
def test_train_lm_fault_tolerance_drill(tmp_path):
    """The ROADMAP item: watchdog + checkpoint-restore wired into the
    example driver.  Injects a synthetic straggler and a simulated
    crash/restore; asserts the sentinels."""
    res = subprocess.run(
        [sys.executable] + _FT_SCRIPT_ARGS
        + ["--checkpoint-dir", str(tmp_path / "ckpt")],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": "src", "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache"),
             "PATH": "/usr/bin:/bin:/usr/local/bin"},
        cwd=__file__.rsplit("/", 2)[0])
    out = res.stdout
    assert "CRASH_SIMULATED step=6" in out, res.stderr[-3000:]
    assert "[trainer] restored step 6" in out, out
    assert "[watchdog] straggler step 11" in out, out
    assert "STRAGGLERS=[11]" in out, out
    assert "FT_OK" in out, out
