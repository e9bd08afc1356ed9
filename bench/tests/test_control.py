"""The control comes out not correct.

The control is the reference algorithm (``references/householder_high``)
in the program's place with products one precision step below the
configurations' (three bfloat16 passes for float32 at ``highest``).  On
the chip, at each cell's own size, it missed the limits while the same
algorithm with float32 products met them (PERF.md).  Here, at a size a
test run holds, the cell's own comparison has to find it not correct.
"""

import numpy as np
import pytest

import calibrate
import compare
import harness
from test_rehearsal import small_cell


def test_three_passes_are_not_folded_away():
    control = harness.load_module("references", "householder_high")
    err = calibrate.product_check(control)
    assert 1e-6 < err["passes_3"] < 3e-5
    assert err["passes_6"] < 2e-6


@pytest.mark.parametrize("name", ["dense-2048", "serve-batch100", "dense-4096"])
def test_control_misses_a_limit(name):
    spec = harness.resolve(harness.ROOT, name)
    cell = small_cell(name)
    control = harness.load_module("references", spec["config"]["control"])
    driver = harness.load_module("traffic", cell["traffic"]["kind"]).Driver(
        spec["config"], cell, 2 ** 32 + 5)
    limits = spec["cell"]["limits"]
    worst = calibrate.control_readings(driver, control, 3, limits)
    assert worst.compared == len(driver.make_inputs())
    assert all(np.isfinite(v) for v in worst.worst.values())
    assert worst.correct is False, (worst.checks(), limits)
    assert any(worst.worst[k] > limits[k] for k in worst.names)


def test_witness_is_correct():
    spec = harness.resolve(harness.ROOT, "dense-2048")
    cell = small_cell("dense-2048")
    control = harness.load_module("references", spec["config"]["control"])
    driver = harness.load_module("traffic", "dense_closed").Driver(
        spec["config"], cell, 2 ** 32 + 6)
    worst = calibrate.control_readings(driver, control, 6,
                                       spec["cell"]["limits"])
    assert worst.correct is True, worst.checks()


def test_r_error_over_condition():
    rng = np.random.default_rng(7)
    u, _ = np.linalg.qr(rng.standard_normal((48, 32)))
    v, _ = np.linalg.qr(rng.standard_normal((32, 32)))
    a = (u * np.logspace(0, -3, 32)) @ v.T
    r_ref = np.linalg.qr(a, mode="r")
    q, r = np.linalg.qr(a.astype(np.float32))
    e = compare.errors(a, q, r, r_ref, compare.NUMBERS)
    assert e["r_vs_ref"] > 0
    assert np.isclose(e["r_vs_ref_cond"], e["r_vs_ref"] / 1e3, rtol=1e-6)
    assert set(compare.errors(a, q, r, r_ref)) == set(compare.NUMBERS[:3])
