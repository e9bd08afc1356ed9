"""Quickstart: the MHT QR library in five minutes.

    PYTHONPATH=src python examples/quickstart.py

The one idea to take away: factorizations are *planned*.  A hashable
``QRConfig`` names what you want (or ``method="auto"`` to let the planner
route by shape/hardware), ``plan()`` resolves it against the method
registry, and the returned ``QRSolver`` does the work — batched, jittable,
kernel-dispatched.
"""

import numpy as np
import jax.numpy as jnp

from repro.core import QRConfig, lstsq, orthogonalize, plan, qr
from repro.core.dag import phase_model_theta, theta_curve
from repro.core.plan import available_methods, get_method


def main():
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((512, 128)), jnp.float32)

    # 1. every realization the paper discusses, via the method registry
    for method in available_methods():
        if method == "geqrf_fori":
            continue  # optimizer-internal variant (needs padded shapes)
        if method == "degenerate":
            continue  # zero-dim-only route (auto-selected for empty inputs)
        q, r = qr(a, config=QRConfig(method=method))
        rec = float(jnp.linalg.norm(q @ r - a) / jnp.linalg.norm(a))
        orth = float(jnp.linalg.norm(q.T @ q - jnp.eye(q.shape[1])))
        print(f"{method:10s} reconstruction={rec:.2e} orthogonality={orth:.2e}"
              f"   [{get_method(method).description}]")

    # 2. method="auto": the planner routes by shape and hardware.
    #    Tall-skinny goes to TSQR with a planner-chosen tree; large
    #    near-square matrices go to the tiled task graph; on TPU,
    #    panel-fits-VMEM shapes go to the kernel-backed blocked MHT.
    for shape in [(1024, 32), (512, 512), (512, 128), (24, 16)]:
        solver = plan(shape, jnp.float32, QRConfig())
        print(f"auto {shape}: -> {solver.config.method}"
              f" (use_kernel={solver.config.use_kernel},"
              f" nblocks={solver.config.nblocks})")

    # 2b. the tiled task-graph backend: the factorization becomes a DAG
    #     of tile tasks (GEQRT/TSQRT/LARFB/SSRFB), levelized statically;
    #     the wavefront macro-op engine (repro.core.engine) executes each
    #     level — use_kernel=True lowers it to ONE in-place Pallas
    #     dispatch over the tile workspace (interpret mode on CPU),
    #     use_kernel=False runs the bitwise-identical jnp oracle.  block
    #     doubles as the tile size.
    from repro.core import wavefront_count
    from repro.core.dag import analyze_mht, analyze_tiled

    qt, rt = qr(a, config=QRConfig(method="tiled", block=64))
    rec = float(jnp.linalg.norm(qt @ rt - a) / jnp.linalg.norm(a))
    print(f"{'tiled':10s} reconstruction={rec:.2e} "
          f"wavefronts={wavefront_count(512 // 64, 128 // 64)} "
          f"(vs {128} sequential columns unblocked)")
    beta_gain = analyze_tiled(128, 16).beta / analyze_mht(128).beta
    print(f"tiled ops/DAG-level vs MHT at n=128: {beta_gain:.0f}x")

    # the engine knob: the Pallas path is bitwise-equal to the oracle
    # (wavefront mode pinned — auto would pick megakernel here)
    qe, re_ = qr(a, config=QRConfig(method="tiled", block=64,
                                    use_kernel=True,
                                    dispatch_mode="wavefront"))
    print(f"{'engine':10s} bitwise_vs_oracle="
          f"{bool((qe == qt).all()) and bool((re_ == rt).all())} "
          f"(one Pallas dispatch per DAG level, in-place workspace)")

    # the dispatch-mode knob: "megakernel" collapses the whole schedule
    # into ONE persistent Pallas dispatch — the grid walks a
    # scalar-prefetched task table, switching on task kind, with task
    # t+1's tile DMA overlapping task t's compute (double buffering).
    # None (the default) picks it automatically whenever the table and
    # the working set fit the budgets; bitwise-equal either way.
    from repro.core.engine import schedule_stats

    qm, rm = qr(a, config=QRConfig(method="tiled", block=64,
                                   use_kernel=True,
                                   dispatch_mode="megakernel"))
    stats = schedule_stats(512 // 64, 128 // 64, nb=64)
    print(f"{'megakernel':10s} bitwise_vs_oracle="
          f"{bool((qm == qt).all()) and bool((rm == rt).all())} "
          f"(dispatches {stats['wavefront']['dispatches']} -> "
          f"{stats['megakernel']['dispatches']}, table "
          f"{stats['megakernel']['table_bytes']} B, auto={stats['auto']})")

    # 2c. the multi-device sharded tiled backend: the tile grid splits
    #     into per-device row-block domains (shard_map), each runs its
    #     own wavefronts, and the per-domain R factors merge through a
    #     TSQR-style butterfly tree — critical path O(p/d + 2q + log d).
    #     Works on CPU without accelerators: run with
    #         XLA_FLAGS=--xla_force_host_platform_device_count=8
    #     On one device it degenerates to the tiled backend bit-for-bit.
    import jax

    from repro.core.tilegraph import sharded_wavefront_count

    ndev = jax.local_device_count()
    solver = plan((512, 512), jnp.float32,
                  QRConfig(method="sharded_tiled", block=64))
    big = jnp.asarray(rng.standard_normal((512, 512)), jnp.float32)
    qs, rs = solver.solve(big)
    rec = float(jnp.linalg.norm(qs @ rs - big) / jnp.linalg.norm(big))
    d = solver.config.ndomains
    print(f"{'sharded':10s} reconstruction={rec:.2e} devices={ndev} "
          f"domains={d} wavefronts={sharded_wavefront_count(8, 8, d)} "
          f"(vs {8 + 2 * 8 - 2} single-device)")

    # 3. the Pallas-kernel-backed blocked MHT (interpret mode on CPU)
    q, r = qr(a, config=QRConfig(method="geqrf_ht", use_kernel=True, block=64))
    print(f"{'kernels':10s} reconstruction="
          f"{float(jnp.linalg.norm(q @ r - a) / jnp.linalg.norm(a)):.2e}")

    # 4. batched QR: leading dims vmap through the same solver
    stack = jnp.asarray(rng.standard_normal((4, 64, 32)), jnp.float32)
    qs, rs = qr(stack, config=QRConfig(method="geqrf_ht", block=16))
    print("batched:", qs.shape, rs.shape)

    # 4b. QR-as-a-service: heterogeneous request streams batch through
    #     shape buckets — each bucket is zero-padded, stacked, and
    #     factored in ONE engine dispatch (factor_tiles_batched; on the
    #     megakernel path a whole bucket is a single pallas_call), with
    #     compiled bucket plans cached so steady-state traffic never
    #     recompiles.  Answers are bitwise what the per-request path
    #     would have produced.
    from repro.serving import BucketingPolicy, QRService

    service = QRService(policy=BucketingPolicy(tile=16, max_batch=8),
                        use_kernel=False)
    mix = [rng.standard_normal(s).astype(np.float32)
           for s in [(48, 48), (45, 41), (96, 32), (48, 48), (37, 23)]]
    results = service.submit_many(mix)       # bucket -> pad -> dispatch
    worst = max(float(jnp.linalg.norm(res.q @ res.r - a_i)
                      / jnp.linalg.norm(a_i))
                for a_i, res in zip(mix, results))
    service.submit_many(mix)                 # warm cache: no new compiles
    s = service.stats()
    print(f"{'serving':10s} requests={s['requests']} "
          f"dispatches={s['dispatches']} compiles={s['compiles']} "
          f"cache_hit_rate={s['cache_hit_rate']:.2f} "
          f"fill={s['bucket_fill_ratio']:.2f} worst_rec={worst:.2e}")

    # 4b'. robustness: the service survives a poisoned batch.  Admission
    #     quarantines the NaN request (named reason, bucket-mates
    #     untouched), and with verify=True every dispatch is
    #     health-checked against the conformance tolerance — failures
    #     walk the escalation ladder megakernel -> wavefront -> oracle
    #     -> lapack, each hop counted.
    from repro.robustness import inject

    hardened = QRService(policy=BucketingPolicy(tile=16, max_batch=8),
                         use_kernel=False, verify=True)
    poisoned = list(mix)
    poisoned[1] = inject.poison(poisoned[1], kind="nan")  # seeded corruption
    hres = hardened.submit_many(poisoned)
    hs = hardened.stats()
    clean_ok = all(res.ok for i, res in enumerate(hres) if i != 1)
    print(f"{'robust':10s} poisoned request -> {hres[1].error} "
          f"(clean {sum(r.ok for r in hres)}/{len(hres)} ok={clean_ok}, "
          f"quarantined={hs['quarantined']}, "
          f"escalations={hs['escalations']})")

    # 4c. observability: plan(explain=True) attaches the machine-readable
    #     routing trail (why THIS method, every fallback by name), and
    #     the off-by-default tracer records nested host spans — never
    #     blocking on the device, exportable as Chrome trace JSON, and
    #     recorded into any jax.profiler capture on the device trace's
    #     clock — while the always-on metrics registry holds
    #     planner/engine/serving counters.  Disabled, the layer is free:
    #     the megakernel jaxpr is identical either way (pinned in tests).
    from repro import observability as obs

    #     On swept shape classes the first decision is the autotuner's:
    #     the committed measured cache (src/repro/tuning/default_cpu.json)
    #     routes by real microseconds, and the reason cites them —
    #     use_tuning_cache=False pins the pure heuristic table.
    explained = plan((512, 512), jnp.float32, QRConfig(), backend="cpu",
                     explain=True)
    print(f"{'explain':10s} method={explained.config.method} "
          f"<- {explained.explain.selected.rule}: "
          f"{explained.explain.selected.reason}")
    heur = plan((512, 512), jnp.float32, QRConfig(use_tuning_cache=False),
                backend="cpu", explain=True)
    print(f"{'explain':10s} heuristics alone would pick "
          f"{heur.config.method} <- {heur.explain.selected.rule}")
    fb = plan((300, 280), jnp.float32, QRConfig(), backend="cpu",
              explain=True)
    print(f"{'explain':10s} (300,280)@cpu -> {fb.config.method} "
          f"(tuned: {fb.explain.decision('tuned').reason}) "
          f"fallbacks={list(fb.explain.fallback_reasons)}")
    with obs.enabled_scope():                    # tracing + annotations on
        service.submit_many(mix)
    print(f"{'tracing':10s} {len(obs.spans())} spans "
          f"(serving.submit: admit -> bucketize -> plan -> stage -> "
          f"dispatch -> unpad); "
          f"obs.export_chrome_trace('trace.json') renders in "
          f"chrome://tracing, `python -m repro.observability.report "
          f"--capture DIR` bundles trace + metrics")

    # 5. the optimizer primitive: orthogonalize a momentum matrix
    #    (auto config routes this tall-skinny input through TSQR)
    o = orthogonalize(jnp.asarray(rng.standard_normal((256, 64)), jnp.float32),
                      config=QRConfig())
    print("orthogonalize:", o.shape,
          float(jnp.linalg.norm(o.T @ o - jnp.eye(64))))

    # 5b. batched optimizer-step orthogonalization: a Muon step holds
    #     dozens of momentum matrices in a few repeated shapes — group
    #     them into shape classes and factor each class in ONE dispatch
    #     instead of one per leaf (muon_update(batched_ortho=True) rides
    #     on this).  plan_batched_ortho is a pure shape query: it counts
    #     dispatches and carries the planner's explain trail per class.
    from repro.optim import plan_batched_ortho

    step_shapes = [((3, 48, 48), jnp.float32)] * 4 + \
        [((3, 96, 48), jnp.float32), ((3, 48, 96), jnp.float32),
         ((40, 24), jnp.float32)]
    oplan = plan_batched_ortho(step_shapes)
    print(f"{'batched':10s} {oplan.n_matrices} matrices / "
          f"{oplan.n_leaves} leaves -> {oplan.dispatches} dispatches "
          f"({len(oplan.classes)} shape classes)")
    for cls in oplan.classes:
        trail = (f"{cls.method} <- {cls.explain.selected.rule}"
                 if cls.route == "batched" else cls.reason.split(":")[0])
        print(f"{'':10s} class {cls.key.m}x{cls.key.n} "
              f"b={len(cls.members)}: {cls.route} ({trail})")

    # 6. least squares (Kalman-filter building block, paper §1)
    x = lstsq(a, a @ jnp.ones((128,), jnp.float32), config=QRConfig())
    print("lstsq residual:", float(jnp.linalg.norm(x - 1.0)))

    # 7. the paper's parallelism claim (fig 9)
    print("theta (4-wide RDP model, n=512):",
          round(phase_model_theta(512)["theta"], 4), "~ paper 0.749")


if __name__ == "__main__":
    main()
