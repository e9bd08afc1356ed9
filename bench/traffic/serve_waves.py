"""Traffic kind ``serve_waves``: one client sending batches of small QRs.

The protocol of MAGMA's batched QR tester (``testing_sgeqrf_batched``):
each size given on its command line (``-N m,n``, repeatable) is run as
one batch of ``--batch`` independent matrices of that size, the sizes in
the order given.  Here each batch is a wave: its ``batch`` requests go
to the service in one ``submit_many`` call, and the next wave is sent
once every Q and R of this one is on the host (a closed loop of one
client).  One step of the window serves every size once, in order, so
that a window holds whole passes over the sizes: cut inside a pass, the
rate and the tail would swing with where the cut fell.

A request's latency runs from the ``submit_many`` call that carries it
until its Q and R are on the host (fetched in submission order).  Only
answered requests count: a request that comes back with an error counts
in ``errors`` and not in the rate, the latencies or the work, and any
error makes the run not correct.

Parameters (the workload file's ``traffic``): ``sizes``, a list of
``[m, n]`` with n <= m, and ``batch``.  The seed makes the matrices
(standard normal, float32, on the host, where a client's requests come
from); every seed serves the same sizes in the same order.  Every answer
of one wave of each size is compared with the reference: for each size,
a uniform sample of one of the window's waves of that size, drawn from
the seed (reservoir sampling).  A sampled wave's answers are copied into
host buffers that set-up allocates and fills, and every fetched array is
dropped when its wave ends, so that what the process holds is the same
for every seed.  Holding the fetched arrays instead, and dropping them
when a later wave replaced them in the sample, changed the speed of the
waves that followed under glibc's default malloc (on a TPU v5 lite host,
512 x 512 waves took 0.44 or 0.70 s by whether a held wave had just been
dropped): the rate and the tail followed the seed's draws.  The
configuration's ``host_malloc`` (applied by the harness) keeps freed
host memory for reuse, which made every such wave take the 0.44 s.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import numpy as np


def seed_words(seed: int, stream: int) -> np.ndarray:
    return np.random.SeedSequence([seed, stream]).generate_state(2)


class Driver:
    def __init__(self, config: dict, cell: dict, seed: int):
        t = cell["traffic"]
        self.config, self.seed = config, seed
        self.sizes = [(int(m), int(n)) for m, n in t["sizes"]]
        if any(n > m for m, n in self.sizes):
            raise ValueError(f"sizes need n <= m: {self.sizes}")
        self.batch = int(t["batch"])
        self.rng = np.random.default_rng(seed_words(seed, 1))
        self.waves = 0
        self.latencies: List[float] = []
        self.requests = 0
        self.errors = 0
        self.flops = self.nbytes = 0.0
        # Per size: waves seen in the window, and the sampled wave's index
        # with each request's state (``_copy``).
        self.seen = [0] * len(self.sizes)
        self.kept: List[Optional[Tuple[int, list]]] = [None] * len(self.sizes)
        self.failed = 0

    # -------------------------------------------------------- set-up
    def make_inputs(self) -> list:
        """One wave of ``batch`` matrices per size, in order, on the host."""
        gen = np.random.default_rng(seed_words(self.seed, 0))
        self.pool = [[gen.standard_normal(s, dtype=np.float32)
                      for _ in range(self.batch)] for s in self.sizes]
        return [a for wave in self.pool for a in wave]

    def setup(self) -> Dict[str, object]:
        import importlib

        import jax

        self.jax = jax
        self.make_inputs()
        mod, attr = self.config["service"].split(":")
        self.svc = getattr(importlib.import_module(mod), attr)()
        t0 = time.monotonic()
        self.buffers = []
        for (m, n), wave in zip(self.sizes, self.pool):
            out, _ = self._serve(wave)
            k = min(m, n)
            q = np.zeros((self.batch, m, k), np.float32)
            r = np.zeros((self.batch, k, n), np.float32)
            self.buffers.append((q, r))
            self._copy(len(self.buffers) - 1, out)
        warm = time.monotonic() - t0
        return {"route": "QRService.submit_many", "sizes": self.sizes,
                "batch": self.batch, "warm_pass_s": warm,
                "tile": self.svc.policy.tile,
                "use_kernel": self.svc.use_kernel,
                "plans": sorted(f"{k.m}x{k.n}/b{b}:{rung}"
                                for k, b, rung in self.svc._plans),
                "service_compiles": self.svc.stats()["compiles"]}

    def _serve(self, wave):
        """Answers (None for an error) and latencies, in request order."""
        t0 = time.perf_counter()
        out, lat = [], []
        for res in self.svc.submit_many(wave):
            if res.ok:
                out.append((np.asarray(res.q), np.asarray(res.r)))
            else:
                out.append(None)
            lat.append(time.perf_counter() - t0)
        return out, lat

    # -------------------------------------------------------- window
    def step(self) -> None:
        """One pass: a wave of each size, in order."""
        for _ in self.pool:
            self._wave()

    def _wave(self) -> None:
        import work

        i = self.waves
        wave = self.pool[i % len(self.pool)]
        with self.jax.profiler.TraceAnnotation("bench.wave"):
            out, lat = self._serve(wave)
        self.waves += 1
        done = [k for k, o in enumerate(out) if o is not None]
        self.latencies.extend(lat[k] for k in done)
        self.requests += len(done)
        self.errors += len(out) - len(done)
        f, b = work.total_work(wave[k].shape for k in done)
        self.flops += f
        self.nbytes += b
        s = i % len(self.pool)
        self.seen[s] += 1
        if self.seen[s] == 1 or self.rng.integers(0, self.seen[s]) == 0:
            self.kept[s] = (i, self._copy(s, out))

    def _copy(self, s: int, out) -> List[Optional[bool]]:
        """Copies a wave's answers into size ``s``'s buffers (the writes
        in set-up also map every page of them).  Per request: True when
        copied, False for an answer of the wrong shape, None for an
        error."""
        q, r = self.buffers[s]
        state: List[Optional[bool]] = []
        for k, o in enumerate(out):
            if o is None:
                state.append(None)
            elif o[0].shape == q.shape[1:] and o[1].shape == r.shape[1:]:
                q[k], r[k] = o
                state.append(True)
            else:
                state.append(False)
        return state

    def counters(self) -> Dict[str, float]:
        from repro.observability import metrics

        st = self.svc.stats()
        waste = [h for h in metrics.snapshot()["histograms"].get(
                     "serving.padding_waste", [])
                 if h["labels"].get("service") == self.svc._sid]
        return {"waves": self.waves, "requests": self.requests,
                "errors": self.errors,
                "waste_sum": sum(h["sum"] for h in waste),
                "waste_dispatches": sum(h["count"] for h in waste),
                "served": st["matrices_served"],
                "padded_slots": st["padded_slots"],
                "dispatches": st["dispatches"],
                "service_compiles": st["compiles"],
                "service_escalations": st["escalations"]}

    def health(self, counters) -> Dict[str, float]:
        """Window counts that have to read 0 for a correct run."""
        return {"errors": counters["errors"],
                "service_escalations": counters["service_escalations"]}

    def end_to_end(self, window_s: float) -> Dict[str, float]:
        lat = np.asarray(self.latencies)
        return {"qr_per_s": self.requests / window_s,
                "p95_ms": 1e3 * float(np.percentile(lat, 95)),
                "p50_ms": 1e3 * float(np.percentile(lat, 50))}

    def window_info(self, counters) -> Dict[str, object]:
        slots = counters["served"] + counters["padded_slots"]
        n = counters["waste_dispatches"]
        return {**counters,
                "fill": counters["served"] / slots if slots else None,
                "mean_padding_waste": counters["waste_sum"] / n if n else None}

    @property
    def attempted(self) -> int:
        return self.requests + self.errors

    def work(self) -> Tuple[float, float]:
        return self.flops, self.nbytes

    # ---------------------------------------------------- comparison
    def release(self) -> None:
        self.svc = None

    def compare(self, limits: Dict[str, float]):
        """Every answer of the sampled waves against the reference.  An
        error is not an answer: it is counted in ``failed`` through
        ``errors``, wherever in the window it came."""
        import compare
        import harness

        ref = harness.load_module("references", self.config["reference"])
        worst = compare.Worst(limits)
        for s, kept in enumerate(self.kept):
            if kept is None:
                continue
            q, r = self.buffers[s]
            for k, (a, ok) in enumerate(zip(self.pool[s], kept[1])):
                if ok:
                    worst.add(compare.errors(a, q[k], r[k],
                                             ref.reference_r(a), worst.names))
                elif ok is False:
                    worst.add(dict.fromkeys(worst.names, float("inf")))
        self.failed = worst.failed + self.errors
        return worst

    def compare_info(self) -> Dict[str, object]:
        return {"compared_waves": [k[0] for k in self.kept if k is not None]}
