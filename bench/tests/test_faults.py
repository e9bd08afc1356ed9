"""The comparison catches a broken timed path.

Each test drives a whole run at a small size on the CPU (the platform
check skipped) with the path under the window broken, and sees
``correct`` come out false.  The faults a QR cell can have: an answer
altered where it is produced; a call that returns its input unchanged;
half of a wave left out; a request answered with an error, or by the
degradation ladder, outside the waves that are compared.  (No cell spans
chips, so there is no exchange between chips to leave out.)
"""

import json

import numpy as np
import pytest

import repro.core
import repro.serving
from test_rehearsal import cache, run_small  # noqa: F401  (fixture)


def _dense(monkeypatch, broken):
    real = repro.core.qr
    monkeypatch.setattr(repro.core, "qr", lambda a, **kw: broken(real, a))


def _serve(monkeypatch, broken):
    cls = repro.serving.QRService
    real = cls.submit_many
    monkeypatch.setattr(cls, "submit_many",
                        lambda self, arrays, **kw: broken(
                            real(self, arrays, **kw)))


def altered_answer(real, a):
    q, r = real(a)
    return q, r.at[0, -1].add(1e-4 * float(abs(r[0, 0])))


def input_unchanged(real, a):
    import jax.numpy as jnp

    m, n = a.shape
    return jnp.eye(m, min(m, n), dtype=a.dtype), a[:min(m, n)]


@pytest.mark.parametrize("fault", [altered_answer, input_unchanged])
def test_dense_fault_is_caught(fault, cache, monkeypatch):  # noqa: F811
    _dense(monkeypatch, fault)
    res, _, _ = run_small("dense-2048")
    assert res["correct"] is False
    assert res["failed"] > 0


def one_answer_altered(results):
    res = results[len(results) // 2]
    r = np.array(res.r)
    r[0, -1] += 1e-4 * abs(r[0, 0])
    results[len(results) // 2] = type(res)(rid=res.rid, q=res.q, r=r)
    return results


def half_left_out(results):
    out = list(results)
    for i in range(len(out) // 2, len(out)):
        res = out[i]
        out[i] = type(res)(rid=res.rid, q=np.zeros(np.shape(res.q)),
                           r=np.zeros(np.shape(res.r)))
    return out


@pytest.mark.parametrize("fault", [one_answer_altered, half_left_out])
def test_serving_fault_is_caught(fault, cache, monkeypatch):  # noqa: F811
    _serve(monkeypatch, fault)
    res, _, _ = run_small("serve-batch100")
    assert res["correct"] is False
    assert res["failed"] > 0


def one_error(results):
    res = results[-1]
    results[-1] = type(res)(rid=res.rid, q=None, r=None,
                            error="escalation_exhausted")
    return results


def test_an_error_outside_the_comparison_is_caught(cache, monkeypatch):  # noqa: F811,E501
    # One request of every wave comes back with an error.  The compared
    # answers are all sound, so only the count of errors can fail the run.
    _serve(monkeypatch, one_error)
    res, lines, _ = run_small("serve-batch100")
    window = next(json.loads(x) for x in lines if '"window"' in x)
    assert res["correct"] is False
    checks = res["checks"]
    assert all(checks[k]["value"] <= checks[k]["limit"]
               for k in res["checks"] if checks[k]["limit"] > 0)
    assert checks["errors"]["value"] == window["waves"] > 0
    assert window["requests"] == 7 * window["waves"]
    assert res["attempted"] == 8 * window["waves"]
    assert res["failed"] == window["waves"]


def test_an_escalation_is_caught(cache, monkeypatch):  # noqa: F811
    # Every wave's first dispatch fails and the ladder answers it: each
    # answer is sound, but it is not the batched route's.
    from repro.robustness import inject

    cls = repro.serving.QRService
    real = cls.submit_many

    def submit_many(self, arrays, **kw):
        with inject.active(inject.Fault(site="dispatch", times=1)):
            return real(self, arrays, **kw)

    monkeypatch.setattr(cls, "submit_many", submit_many)
    res, _, _ = run_small("serve-batch100")
    assert res["correct"] is False
    assert res["checks"]["escalations"]["value"] > 0
