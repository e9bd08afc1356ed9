"""Traffic kind ``dense_closed``: one caller factoring dense matrices.

A closed loop of one caller: each call of the configuration's entry
(``qr(a)``) on an m x n float32 matrix ends in ``block_until_ready`` on
Q and R before the next starts.  The matrices are a pool of ``pool``
standard normal matrices, made on the device from the seed in one jitted
call during set-up and taken in turn.

Parameters (the workload file's ``traffic``): ``m``, ``n``, ``pool``,
and ``compare_calls``, the number of calls of the window whose Q and R
are compared with the reference: a uniform sample of all the window's
calls, drawn from the seed (reservoir sampling).
"""

from __future__ import annotations

import importlib
import time
from typing import Dict, List, Tuple

import numpy as np


def entry_point(spec: str):
    """``"package.module:attr"`` -> the attribute, looked up at call time
    so that a test can put a broken entry in its place."""
    mod, attr = spec.split(":")
    module = importlib.import_module(mod)
    return lambda *a, **k: getattr(module, attr)(*a, **k)


def seed_words(seed: int, stream: int) -> np.ndarray:
    """Two 32-bit words from any non-negative seed, one stream per use."""
    return np.random.SeedSequence([seed, stream]).generate_state(2)


class Driver:
    def __init__(self, config: dict, cell: dict, seed: int):
        t = cell["traffic"]
        self.config, self.seed = config, seed
        self.m, self.n = int(t["m"]), int(t["n"])
        self.pool_size = int(t["pool"])
        self.keep = int(t["compare_calls"])
        self.rng = np.random.default_rng(seed_words(seed, 1))
        self.calls = 0
        self.kept: List[Tuple[int, object, object]] = []
        self.failed = 0

    # -------------------------------------------------------- set-up
    def make_inputs(self) -> list:
        """The pool of input matrices, on the device."""
        import jax
        import jax.numpy as jnp

        m, n, p = self.m, self.n, self.pool_size
        key = jax.random.wrap_key_data(
            jnp.asarray(seed_words(self.seed, 0), jnp.uint32))

        @jax.jit
        def make(key):
            return tuple(jax.random.normal(k, (m, n), jnp.float32)
                         for k in jax.random.split(key, p))

        self.pool = jax.block_until_ready(make(key))
        return list(self.pool)

    def setup(self) -> Dict[str, object]:
        import jax

        from repro.core import QRConfig, plan

        self.jax = jax
        self.entry = entry_point(self.config["entry"])
        m, n, p = self.m, self.n, self.pool_size
        self.make_inputs()
        cfg = plan((m, n), np.float32, QRConfig(), explain=True).config
        t0 = time.monotonic()
        jax.block_until_ready(self.entry(self.pool[0]))
        first = time.monotonic() - t0
        t0 = time.monotonic()
        for a in self.pool:
            jax.block_until_ready(self.entry(a))
        return {"shape": [m, n], "route": cfg.method,
                "dispatch_mode": cfg.dispatch_mode, "tile": cfg.block,
                "use_kernel": cfg.use_kernel, "pool": p,
                "first_call_s": first,
                "warm_call_s": (time.monotonic() - t0) / p}

    # -------------------------------------------------------- window
    def step(self) -> None:
        jax = self.jax
        i = self.calls
        with jax.profiler.TraceAnnotation("bench.call"):
            q, r = self.entry(self.pool[i % self.pool_size])
            jax.block_until_ready((q, r))
        self.calls += 1
        if len(self.kept) < self.keep:
            self.kept.append((i, q, r))
        else:
            j = int(self.rng.integers(0, i + 1))
            if j < self.keep:
                self.kept[j] = (i, q, r)

    def counters(self) -> Dict[str, float]:
        return {"calls": self.calls}

    def health(self, counters) -> Dict[str, float]:
        """Window counts that have to read 0 for a correct run: none of
        its own (a failing call raises)."""
        return {}

    def end_to_end(self, window_s: float) -> Dict[str, float]:
        return {"call_ms": 1e3 * window_s / self.calls}

    def window_info(self, counters) -> Dict[str, object]:
        return {"calls": counters["calls"]}

    @property
    def attempted(self) -> int:
        return self.calls

    def work(self) -> Tuple[float, float]:
        import work

        f, b = work.qr_work(self.m, self.n)
        return self.calls * f, self.calls * b

    # ---------------------------------------------------- comparison
    def release(self) -> None:
        """Bring the sampled answers and their inputs to the host and
        drop everything the program holds on the device."""
        self.kept = [(i, np.asarray(q), np.asarray(r))
                     for i, q, r in sorted(self.kept, key=lambda t: t[0])]
        used = {i % self.pool_size for i, _, _ in self.kept}
        self.inputs = {j: np.asarray(self.pool[j]) for j in used}
        self.pool = None

    def compare(self, limits: Dict[str, float]):
        import compare
        import harness

        ref = harness.load_module("references", self.config["reference"])
        worst = compare.Worst(limits)
        refs = {j: ref.reference_r(a) for j, a in self.inputs.items()}
        for i, q, r in self.kept:
            j = i % self.pool_size
            worst.add(compare.errors(self.inputs[j], q, r, refs[j],
                                     worst.names))
        self.failed = worst.failed
        return worst

    def compare_info(self) -> Dict[str, object]:
        return {"compared_calls": [i for i, _, _ in self.kept]}
