"""Readings that the correctness limits of a cell are set from.

    python3 bench/calibrate.py --workload <name> --seeds 12 --first-seed <n> \\
        [--seconds 2] [--control-seeds 3]

In one process on the chip, for one cell:

  program  runs the cell (``harness.run_cell``, end-to-end mode) on each
           seed with a short window and keeps each run's worst reading of
           every compared number: the lower readings;
  control  the configuration's control (``bench/references/<control>.py``:
           the reference algorithm with products one precision step
           below the configuration's) in the program's place, on every
           input of the cell's pool for each control seed, judged by the
           run's own comparison (``compare.Worst`` under the cell's
           limits): the upper readings, and ``correct``, which has to
           read false;
  witness  the same algorithm at the configuration's own precision on
           the first control seed: it has to meet the limits.

``--seeds 0`` reads the control and the witness alone.  Prints one JSON
line per reading and a summary: per number, the largest program reading,
the smallest control reading and their ratio.  The
benchmark's own runs never run this; PERF.md keeps what it printed.
"""

from __future__ import annotations

import argparse
import io
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import compare  # noqa: E402
import harness  # noqa: E402

#: Off only in the benchmark's own tests, which rehearse on the CPU.
REQUIRE_TPU = True


def padded(d: int, step: int = 128) -> int:
    return -(-d // step) * step


def control_readings(driver, control, passes: int,
                     limits: dict) -> compare.Worst:
    """The cell's comparison (worst reading of each number, and whether
    every answer met ``limits``) over the cell's inputs with the control
    in the program's place.  Inputs of one padded shape are zero-padded
    (which leaves their factors unchanged) and factored as one stack."""
    import jax
    import numpy as np

    ref = harness.load_module("references", driver.config["reference"])
    worst = compare.Worst(limits)
    groups = {}
    for a in driver.make_inputs():
        m, n = a.shape
        groups.setdefault((padded(m), padded(n)), []).append(a)
    for (pm, pn), mats in sorted(groups.items()):
        stack = np.zeros((len(mats), pm, pn), np.float32)
        for i, a in enumerate(mats):
            stack[i, :a.shape[0], :a.shape[1]] = np.asarray(a)
        q, r = jax.block_until_ready(control.householder_qr(
            jax.device_put(stack), passes=passes))
        q, r = np.asarray(q), np.asarray(r)
        for i, a in enumerate(mats):
            a = np.asarray(a)
            m, n = a.shape
            k = min(m, n)
            worst.add(compare.errors(a, q[i, :m, :k], r[i, :k, :n],
                                     ref.reference_r(a), worst.names))
    return worst


def product_check(control) -> dict:
    """Largest relative error of one 1024 x 1024 product, each way, against
    float64 on the host: shows the three-pass split was not folded away."""
    import jax
    import numpy as np

    rng = np.random.default_rng(0)
    x, y = (rng.standard_normal((1024, 1024)).astype(np.float32)
            for _ in range(2))
    exact = x.astype(np.float64) @ y.astype(np.float64)
    out = {}
    for passes in (3, 6):
        got = np.asarray(jax.jit(control.dot, static_argnums=2)(x, y, passes))
        out[f"passes_{passes}"] = float(np.abs(got - exact).max()
                                        / np.abs(exact).max())
    return out


def main(argv=None, cell=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)

    spec = harness.resolve(harness.ROOT, args.workload)
    if cell is not None:
        spec["cell"] = cell
    names = compare.Worst(spec["cell"]["limits"]).names
    lower = {name: 0.0 for name in names}
    sink = io.StringIO()
    for s in range(args.first_seed, args.first_seed + args.seeds):
        res = harness.run_cell(args.workload, s, args.seconds, False,
                               t_start=time.monotonic(), out=sink, err=sink,
                               require_tpu=REQUIRE_TPU, cell=spec["cell"])
        read = {name: c["value"] for name, c in res["checks"].items()}
        for name in names:
            lower[name] = max(lower[name], read[name])
        print(json.dumps({"program": s, "correct": res["correct"],
                          "attempted": res["attempted"], **read}),
              flush=True)

    control = harness.load_module("references", spec["config"]["control"])
    print(json.dumps({"product_check": product_check(control)}), flush=True)
    driver_mod = harness.load_module("traffic",
                                     spec["cell"]["traffic"]["kind"])
    limits = spec["cell"]["limits"]
    upper = {name: float("inf") for name in names}
    seeds = range(args.first_seed + 1000,
                  args.first_seed + 1000 + args.control_seeds)
    for i, s in enumerate(seeds):
        driver = driver_mod.Driver(spec["config"], spec["cell"], s)
        t0 = time.monotonic()
        worst = control_readings(driver, control, 3, limits)
        for name in names:
            upper[name] = min(upper[name], worst.worst[name])
        print(json.dumps({"control": s, "seconds": time.monotonic() - t0,
                          "correct": worst.correct, "compared":
                          worst.compared, "failed": worst.failed,
                          **worst.worst}), flush=True)
        if i == 0:
            w = control_readings(driver, control, 6, limits)
            print(json.dumps({"witness": s, "correct": w.correct,
                              **w.worst}), flush=True)
    print(json.dumps({"summary": args.workload, "lower": lower,
                      "upper": upper,
                      "ratio": {n: upper[n] / lower[n] if lower[n] else None
                                for n in names},
                      "limits_now": limits}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
