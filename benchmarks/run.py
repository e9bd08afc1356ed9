"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig9,...] [--smoke]
                                            [--json BENCH_qr.json]

Prints ``name,us_per_call,derived`` CSV rows, and serializes the QR
method sweep (method x shape x dtype -> wall time / effective GFLOPs) to
``BENCH_qr.json`` so the perf trajectory is tracked across PRs.

``--smoke`` runs only the QR sweeps (methods + serving stream) on a
reduced grid (including the Pallas kernel paths in interpret mode) —
the CI hook that catches kernel regressions on CPU.  The serving
records (bench_qr_serving: latency percentiles, matrices/sec, bucket
fill, cache hit rate) merge into the same BENCH_qr.json.  The dry-run/roofline results
(launch/dryrun.py + launch/roofline.py) are the TPU-side counterpart;
these benches cover the paper's algorithmic claims on the host.
"""

import argparse
import json
import sys
import traceback

_MODULES = [
    ("fig9_parallelism", "benchmarks.bench_parallelism"),
    ("fig11_qr_variants", "benchmarks.bench_qr_variants"),
    ("fig13_kernel_traffic", "benchmarks.bench_kernel_traffic"),
    ("fig14e_scaling", "benchmarks.bench_scaling"),
    ("optim_beyond_paper", "benchmarks.bench_optim"),
    ("qr_methods", "benchmarks.bench_qr_methods"),
    ("qr_serving", "benchmarks.bench_qr_serving"),
]

# Modules whose sweep() records merge into the BENCH_qr.json trajectory
# (qr-bench-v2 rows; serving rows carry extra latency/throughput fields,
# optimizer rows carry dispatch-economy twins — batched vs leafwise).
_QR_RECORD_MODULES = ("qr_methods", "qr_serving", "optim_beyond_paper")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated prefixes to run")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced QR sweep only (CI kernel smoke)")
    ap.add_argument("--chaos", action="store_true",
                    help="append the serving chaos record (verify on, "
                         "~5%% injected-fault mix — latency percentiles "
                         "plus escalation/quarantine counts) to the "
                         "BENCH_qr.json trajectory")
    ap.add_argument("--json", default="BENCH_qr.json", metavar="PATH",
                    help="where to write the QR sweep records")
    args = ap.parse_args()
    if args.smoke and args.only:
        ap.error("--smoke and --only are mutually exclusive")
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    only = list(_QR_RECORD_MODULES) if args.smoke else (
        args.only.split(",") if args.only else None)

    print("name,us_per_call,derived")
    failures = 0
    qr_records = None
    for label, modname in _MODULES:
        if only and not any(label.startswith(o) for o in only):
            continue
        try:
            import importlib

            mod = importlib.import_module(modname)
            if label in _QR_RECORD_MODULES:
                if label == "qr_serving":
                    records = mod.sweep(smoke=args.smoke, chaos=args.chaos)
                else:
                    records = mod.sweep(smoke=args.smoke)
                qr_records = (qr_records or []) + records
                rows = mod.rows(records)
            else:
                rows = mod.run()
            for name, us, derived in rows:
                print(f"{name},{us:.1f},{derived}")
        except Exception:
            failures += 1
            print(f"{label},ERROR,{traceback.format_exc().splitlines()[-1]}",
                  file=sys.stderr)

    if qr_records is not None and args.json:
        from repro.observability import metrics as obs_metrics
        from repro.tuning import cache as tuning_cache

        with open(args.json, "w") as f:
            # v2: records carry a dispatch_mode field (engine lowering:
            # "wavefront" / "megakernel" / null on jnp-oracle paths) and
            # a per-record "metrics" dict on engine/serving rows; the
            # top-level "metrics" key is the process-global registry
            # snapshot at the end of the run (planner explain/fallback
            # counters, engine dispatch/DMA series, serving histograms).
            # "tuning" records which measured planner cache (if any)
            # governed the auto-routed rows, so a trajectory diff can
            # tell a code change from a cache change.
            json.dump({"schema": "qr-bench-v2", "smoke": args.smoke,
                       "records": qr_records,
                       "tuning": tuning_cache.active_cache_info(),
                       "metrics": obs_metrics.snapshot()}, f, indent=1)
        print(f"wrote {len(qr_records)} records to {args.json}",
              file=sys.stderr)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
