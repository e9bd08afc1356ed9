"""Smoke run of the QR stack on TPU: the main path once, end to end.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # four chips: the sharded 4096^2 path

One chip, each phase through the entry point a user calls, in float32:

  qr-512      ``qr()`` with ``QRConfig()`` -> tiled, megakernel
  qr-2048     ``qr()`` -> tiled, megakernel on a 16x16 tile grid
  qr-4096     ``qr()`` -> geqrf_ht with the Pallas panel/trailing kernels
  qr-8192x128 ``qr()`` -> tsqr (kernel-backed leaves)
  serving     ``QRService(escalate=False)``: three waves of the serving
              benchmark's full request mix, bucketed into batched
              megakernel dispatches
  muon        three ``Trainer`` steps of smollm-135m at published widths
              with muon-qr and batched orthogonalization, then one
              optimizer update's orthogonalized directions, batched
              route against the leafwise route

Four chips (``--chips 4``): ``qr()`` of a 4096^2 input routed to
``sharded_tiled`` over four row domains, one per device, compared with the
host reference and with the one-chip route for the same input.

Every result is compared on the host with float64 LAPACK: the relative
residual ||QR - A||_F / ||A||_F, the orthogonality ||Q^T Q - I||_F and R
against the reference R up to row signs, each within
``4 * eps(float32) * max(m, n)``.  Why 4: on the host the repo's float32
routes reach at most 0.6 eps max(m, n) on these inputs, so 4 leaves six
times that; a float32 product computed as one bfloat16 pass (relative
error 2^-9, about 16000 eps per product) misses it by orders of
magnitude.  The optimizer's two routes are held to twice the rule
between each other.

Each phase prints one JSON line: route, ``use_kernel``, ``interpret``,
dispatch mode, the errors with their tolerances, compiles and compile
seconds (persistent-cache hits included), and the escalations and
planner fallbacks it caused.  A phase fails, and the script exits non-zero
without a result line, when the platform is not TPU, a kernel would run
in interpret mode, an escalation or planner fallback fires, or a
comparison misses its tolerance.  The last line, printed only when every
phase passed, is ``{"ok": true, "device": {...}}``.  This is a smoke run:
it times nothing and claims no speed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# Float32 tolerance: TOL_FACTOR * eps * max(m, n) (module doc).
TOL_FACTOR = 4.0
EPS32 = float(np.finfo(np.float32).eps)


def _tol(m: int, n: int) -> float:
    return TOL_FACTOR * EPS32 * max(m, n)


class SmokeFailure(RuntimeError):
    pass


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ------------------------------------------------------ host comparison

def _errors(a, q, r, r_ref):
    """(residual, orthogonality, R vs reference up to row signs), float64
    host arithmetic; ``q`` may be None (R-only)."""
    a = np.asarray(a, np.float64)
    r = np.asarray(r, np.float64)
    k = r_ref.shape[0]
    sign = np.sign(np.diag(r)[:k]) * np.sign(np.diag(r_ref))
    sign[sign == 0] = 1.0
    rdiff = (np.linalg.norm(sign[:, None] * r[:k] - r_ref)
             / np.linalg.norm(r_ref))
    if q is None:
        return None, None, float(rdiff)
    q = np.asarray(q, np.float64)
    resid = np.linalg.norm(q @ r - a) / np.linalg.norm(a)
    orth = np.linalg.norm(q.T @ q - np.eye(q.shape[1]))
    return float(resid), float(orth), float(rdiff)


def _reference(a):
    """Float64 LAPACK R of ``a`` on the host."""
    return np.linalg.qr(np.asarray(a, np.float64), mode="r")


def _compare(a, q, r, r_ref, label):
    tol = _tol(*a.shape)
    out = {"tol": tol}
    for name, e in zip(("residual", "orthogonality", "r_vs_ref"),
                       _errors(a, q, r, r_ref)):
        if e is None:
            continue
        out[name] = e
        _check(bool(np.isfinite(e)) and e <= tol,
               f"{label}: {name} {e:.3e} > tolerance {tol:.3e}")
    return out


# ----------------------------------------------------- phase bookkeeping

class _Compiles:
    """Backend compiles (persistent-cache reads included) and cache hits,
    from JAX's own monitoring events."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        from jax import monitoring

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


def _counter_total(name):
    from repro.observability import metrics

    return metrics.counter_total(name)


class Phase:
    def __init__(self, name, compiles):
        self.name = name
        self.compiles = compiles
        self.line = {"phase": name}

    def __enter__(self):
        self.c0 = self.compiles.mark()
        self.esc0 = _counter_total("robustness.escalations")
        self.fb0 = _counter_total("planner.fallbacks")
        self.t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False
        c1 = self.compiles.mark()
        esc = _counter_total("robustness.escalations") - self.esc0
        fb = _counter_total("planner.fallbacks") - self.fb0
        self.line.update(
            compiles=c1[0] - self.c0[0],
            compile_s=round(c1[1] - self.c0[1], 3),
            cache_hits=c1[2] - self.c0[2],
            escalations=int(esc), planner_fallbacks=int(fb),
            phase_s=round(time.monotonic() - self.t0, 3))
        print(json.dumps(self.line), flush=True)
        _check(esc == 0, f"{self.name}: {esc:.0f} escalation(s) fired")
        _check(fb == 0, f"{self.name}: {fb:.0f} planner fallback(s) fired")
        return False


def _no_interpret():
    from repro.kernels import macro_ops

    interpret = macro_ops.default_interpret()
    _check(not interpret, "kernels would run in interpret mode")
    return interpret


# -------------------------------------------------------------- phases

def _phase_qr(compiles, rng, m, n, expect_method, expect_mode=None):
    from repro.core import QRConfig, plan, qr

    a = rng.standard_normal((m, n)).astype(np.float32)
    r_ref = _reference(a)
    with Phase(f"qr-{m}x{n}", compiles) as ph:
        solver = plan(a.shape, a.dtype, QRConfig(), explain=True)
        cfg = solver.config
        ph.line.update(route=cfg.method, use_kernel=cfg.use_kernel,
                       interpret=_no_interpret(),
                       dispatch_mode=cfg.dispatch_mode, tile=cfg.block)
        _check(cfg.method == expect_method,
               f"{m}x{n} routed to {cfg.method}, expected {expect_method}")
        _check(cfg.use_kernel, f"{m}x{n}: kernel path not planned")
        _check(cfg.dispatch_mode == expect_mode,
               f"{m}x{n}: dispatch {cfg.dispatch_mode}, expected "
               f"{expect_mode}")
        q, r = jax.block_until_ready(qr(jnp.asarray(a)))
        ph.line.update(_compare(a, np.asarray(q), np.asarray(r), r_ref,
                                f"qr {m}x{n}"))
    return np.asarray(q), np.asarray(r)


def _phase_serving(compiles, rng, waves=3):
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "benchmarks"))
    from bench_qr_serving import _FULL_MIX
    from repro.serving import QRService

    stream = [[rng.standard_normal(s).astype(np.float32) for s in _FULL_MIX]
              for _ in range(waves)]
    refs = [[_reference(a) for a in wave] for wave in stream]
    with Phase("serving", compiles) as ph:
        svc = QRService(escalate=False)
        _check(svc.use_kernel, "QRService did not pick the kernel path")
        interp = (_no_interpret() if svc.interpret is None
                  else bool(svc.interpret))
        _check(not interp, "QRService kernels would run in interpret mode")
        worst = {}
        for wave, wrefs in zip(stream, refs):
            for res, a, r_ref in zip(svc.submit_many(wave), wave, wrefs):
                _check(res.ok, f"serving request {res.rid}: {res.error}")
                errs = _compare(a, np.asarray(res.q), np.asarray(res.r),
                                r_ref, f"serving request {res.rid}")
                for key in ("residual", "orthogonality", "r_vs_ref"):
                    share = errs[key] / errs["tol"]
                    if share >= worst.get(key + "_of_tol", -1.0):
                        worst[key + "_of_tol"] = share
        stats = svc.stats()
        rungs = sorted({p.rung for p in svc._plans.values()})
        buckets = sorted({f"{p.key.m}x{p.key.n}/b{p.batch}"
                          for p in svc._plans.values()})
        ph.line.update(route="qr_service", use_kernel=svc.use_kernel,
                       interpret=interp, dispatch_mode=rungs, tile=svc.policy.tile,
                       buckets=buckets, requests=stats["requests"],
                       dispatches=stats["dispatches"],
                       service_escalations=stats["escalations"], **worst)
        _check(rungs == ["megakernel"], f"serving rungs {rungs}")
        _check(stats["escalations"] == 0, "serving escalated")


def _phase_muon(compiles, seed, steps=3):
    from repro.configs import get_config
    from repro.core import QRConfig, plan
    from repro.data import DataConfig
    from repro.optim.batched_ortho import batched_orthogonalize, \
        plan_batched_ortho
    from repro.optim.qr_muon import _orthogonalize_leaf, is_muon_param
    from repro.training import RunConfig, TrainConfig, Trainer

    cfg = get_config("smollm-135m")
    with Phase("muon-train", compiles) as ph:
        trainer = Trainer(
            cfg, TrainConfig(optimizer="muon-qr", lr=0.02, microbatch=4,
                             batched_ortho=True),
            RunConfig(total_steps=steps, warmup_steps=1, log_every=1,
                      checkpoint_dir=None, seed=seed),
            DataConfig(vocab_size=cfg.vocab_size, seq_len=256,
                       global_batch=8, seed=seed),
            log_fn=lambda s: print(s, file=sys.stderr))
        params = trainer.state.params
        leaves = [leaf for path, leaf in
                  jax.tree_util.tree_flatten_with_path(params)[0]
                  if is_muon_param(path, leaf)]
        oplan = plan_batched_ortho([(tuple(x.shape), x.dtype)
                                    for x in leaves])
        routes = []
        for c in oplan.classes:
            shape = f"{c.key.m}x{c.key.n}"
            _check(c.route == "batched",
                   f"Muon class {shape} routed {c.route}: {c.reason}")
            use_kernel = plan((len(c.members), c.key.m, c.key.n),
                              c.key.dtype, QRConfig(mode="reduced",
                                                    sign_fix=True)
                              ).config.use_kernel
            _check(use_kernel, f"Muon class {shape}: kernel path not planned")
            routes.append(f"{shape}x{len(c.members)}:{c.method}:"
                          f"{c.dispatch_mode}:use_kernel={use_kernel}")
        result = trainer.run(resume=False)
        losses = [h["loss"] for h in result["history"]]
        ph.line.update(route="muon-qr/batched", interpret=_no_interpret(),
                       layers=cfg.n_layers, d_model=cfg.d_model,
                       d_ff=cfg.d_ff, vocab=cfg.vocab_size,
                       classes=routes, steps=result["final_step"],
                       losses=losses)
        _check(result["final_step"] == steps, "training stopped early")
        _check(all(np.isfinite(losses)), f"non-finite loss {losses}")

    with Phase("muon-update", compiles) as ph:
        # One optimizer update's orthogonalized directions (seeded
        # Gaussian, at the Muon leaf shapes), batched vs leafwise route.
        key = jax.random.PRNGKey(seed + 1)
        dirs = [jax.random.normal(jax.random.fold_in(key, i), x.shape,
                                  jnp.float32)
                for i, x in enumerate(leaves)]
        q_bat = jax.jit(lambda d: batched_orthogonalize(d))(dirs)
        q_leaf = jax.jit(lambda d: [_orthogonalize_leaf(x, "qr", None)
                                    for x in d])(dirs)
        worst_ratio, worst = -1.0, {}
        for d, qb, ql in zip(dirs, q_bat, q_leaf):
            d = np.asarray(d).reshape((-1,) + d.shape[-2:])
            qb = np.asarray(qb).reshape(d.shape)
            ql = np.asarray(ql).reshape(d.shape)
            for i in range(d.shape[0]):
                tol = 2 * _tol(*d.shape[1:])
                diff = (np.linalg.norm(qb[i].astype(np.float64) - ql[i])
                        / np.linalg.norm(ql[i].astype(np.float64)))
                _check(np.isfinite(diff) and diff <= tol,
                       f"muon update {d.shape[1:]}[{i}]: batched vs "
                       f"leafwise {diff:.3e} > {tol:.3e}")
                if diff / tol > worst_ratio:
                    worst_ratio = diff / tol
                    worst = {"batched_vs_leafwise": float(diff),
                             "batched_vs_leafwise_tol": float(tol),
                             "worst_shape": list(d.shape[1:])}
        ph.line.update(route="muon-qr update", matrices=sum(
            int(np.prod(x.shape[:-2])) for x in leaves), **worst)


def _phase_sharded(compiles, rng, n=4096):
    from repro.core import QRConfig, plan, qr

    a = rng.standard_normal((n, n)).astype(np.float32)
    r_ref = _reference(a)
    with Phase(f"sharded-{n}x{n}", compiles) as ph:
        solver = plan(a.shape, a.dtype, QRConfig(), explain=True)
        cfg = solver.config
        ph.line.update(route=cfg.method, use_kernel=cfg.use_kernel,
                       interpret=_no_interpret(),
                       dispatch_mode=cfg.dispatch_mode, tile=cfg.block,
                       ndomains=cfg.ndomains)
        _check(cfg.method == "sharded_tiled" and cfg.ndomains == 4,
               f"{n}^2 planned {cfg.method} over {cfg.ndomains} domains")
        _check(cfg.use_kernel, "sharded path planned without kernels")
        q, r = jax.block_until_ready(qr(jnp.asarray(a)))
        shards = sorted((s.device.id, s.index[0].start or 0,
                         s.index[0].stop or n) for s in q.addressable_shards)
        ph.line.update(q_shards=[f"dev{d}:rows[{lo}:{hi}]"
                                 for d, lo, hi in shards])
        _check(len({d for d, _, _ in shards}) == 4
               and len({(lo, hi) for _, lo, hi in shards}) == 4,
               f"Q is not split into four row domains on four devices: "
               f"{shards}")
        q, r = np.asarray(q), np.asarray(r)
        ph.line.update(_compare(a, q, r, r_ref, f"sharded {n}^2"))

    with Phase(f"one-chip-{n}x{n}", compiles) as ph:
        one = plan(a.shape, a.dtype, QRConfig(), ndevices=1, explain=True)
        ph.line.update(route=one.config.method,
                       use_kernel=one.config.use_kernel,
                       interpret=_no_interpret(),
                       dispatch_mode=one.config.dispatch_mode)
        q1, r1 = jax.block_until_ready(
            one.solve(jax.device_put(a, jax.devices()[0])))
        q1, r1 = np.asarray(q1), np.asarray(r1)
        ph.line.update(_compare(a, q1, r1, r_ref, f"one-chip {n}^2"))
        _, _, r_diff = _errors(a, None, r, r1.astype(np.float64))
        ph.line.update(sharded_vs_one_chip_r=r_diff)
        _check(r_diff <= 2 * _tol(n, n),
               f"sharded vs one-chip R {r_diff:.3e} > {2 * _tol(n, n):.3e}")


# ---------------------------------------------------------------- main

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded four-chip path")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every input and of the model weights")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    _check(dev.platform == "tpu", f"platform is {dev.platform!r}, not tpu")
    _check(len(devices) >= args.chips,
           f"{len(devices)} device(s), --chips {args.chips} needs more")
    from repro.tuning import cache as tuning_cache

    print(json.dumps({"phase": "setup", "device_kind": dev.device_kind,
                      "devices": len(devices), "jax": jax.__version__,
                      "compile_cache": cache_dir,
                      "tuning_cache": tuning_cache.active_cache_info()[
                          "source"]}), flush=True)
    compiles = _Compiles()
    rng = np.random.default_rng(args.seed)
    if args.chips == 4:
        _phase_sharded(compiles, rng)
    else:
        _phase_qr(compiles, rng, 512, 512, "tiled", "megakernel")
        _phase_qr(compiles, rng, 2048, 2048, "tiled", "megakernel")
        _phase_qr(compiles, rng, 4096, 4096, "geqrf_ht")
        _phase_qr(compiles, rng, 8192, 128, "tsqr")
        _phase_serving(compiles, rng)
        _phase_muon(compiles, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
