"""Run one benchmark cell once on the chip this process holds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python3 bench/run.py --self-test      # the benchmark's own tests, CPU

Run from the root of a checkout.  See ``bench/harness.py`` for what a run
does and prints.  Exits non-zero, with no result line, when JAX finds no
TPU or fewer chips than the cell asks for.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the profiler trace of a --trace 1 run here")
    ap.add_argument("--self-test", action="store_true",
                    help="run bench/tests on the CPU and exit")
    args = ap.parse_args()
    if args.self_test:
        import subprocess

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        return subprocess.call(
            [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
             os.path.join(harness.BENCH_DIR, "tests")],
            cwd=harness.ROOT, env=env)
    if args.workload is None or args.seed is None or args.seconds is None:
        ap.error("--workload, --seed and --seconds are required")
    try:
        harness.run_cell(args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start=T_START,
                         keep_trace=args.keep_trace)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
