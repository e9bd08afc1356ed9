"""End-to-end driver: train the ~135M smollm config for a few hundred
steps with the QR-Muon optimizer (paper technique in production position).

    PYTHONPATH=src python examples/train_lm.py [--steps 300] [--full]

Default uses seq 256 / batch 8 on CPU with the FULL 135M architecture
(30 layers, d=576) — a real ~100M-class model, runnable on the host.

Fault-tolerance drill (``--fault-tolerance``): wires the step watchdog
(straggler detection at ``--watchdog-threshold`` x median step time)
and checkpoint-restore into the loop, with two chaos knobs for proving
the machinery end to end —

    --inject-straggler-at N   sleep one step so the watchdog must flag it
    --crash-at N              stop at step N, rebuild the trainer from
                              scratch, and resume from the last committed
                              checkpoint (prints CRASH_SIMULATED / the
                              restored step / FT_OK sentinels the smoke
                              test in tests/test_robustness.py asserts)
"""

import argparse
import time

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, get_smoke_config
from repro.data import DataConfig
from repro.distributed import StepWatchdog
from repro.training import RunConfig, TrainConfig, Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config instead of the full 135M")
    ap.add_argument("--optimizer", default="muon-qr",
                    choices=["muon-qr", "muon-ns", "adamw"])
    ap.add_argument("--batched-ortho", action="store_true",
                    help="batch the Muon orthogonalizations per shape "
                         "class: one QR dispatch per class instead of "
                         "one per layer (repro.optim.batched_ortho)")
    ap.add_argument("--checkpoint-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--checkpoint-every", type=int, default=100)
    ap.add_argument("--fault-tolerance", action="store_true",
                    help="straggler watchdog + crash/restore drill "
                         "(repro.distributed.fault_tolerance)")
    ap.add_argument("--watchdog-threshold", type=float, default=2.5,
                    help="straggler rule: flag steps slower than "
                         "THRESHOLD x median step time")
    ap.add_argument("--inject-straggler-at", type=int, default=None,
                    help="chaos: sleep through step N so the watchdog "
                         "must flag it (requires --fault-tolerance)")
    ap.add_argument("--crash-at", type=int, default=None,
                    help="chaos: stop at step N and restart from the "
                         "last committed checkpoint (requires "
                         "--fault-tolerance)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = (get_smoke_config if args.smoke else get_config)("smollm-135m")
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=args.batch)

    def build_trainer():
        watchdog = None
        if args.fault_tolerance:
            watchdog = StepWatchdog(
                threshold=args.watchdog_threshold,
                on_straggler=lambda s, dt, med: print(
                    f"[watchdog] straggler step {s}: {dt:.2f}s "
                    f"vs median {med:.2f}s"))
        trainer = Trainer(
            cfg,
            TrainConfig(optimizer=args.optimizer, lr=0.02, microbatch=4,
                        batched_ortho=args.batched_ortho),
            RunConfig(total_steps=args.steps, warmup_steps=20,
                      log_every=10, checkpoint_every=args.checkpoint_every,
                      checkpoint_dir=args.checkpoint_dir),
            data,
            watchdog=watchdog,
        )
        if args.inject_straggler_at is not None:
            # Delay scaled off the live median so the straggler rule must
            # fire regardless of how fast this host steps.
            real_step = trainer._step

            def slow_step(state, batch, lr, _real=real_step):
                if trainer.step_idx == args.inject_straggler_at:
                    wd = trainer.watchdog
                    time.sleep(max(0.5, 2.0 * wd.threshold * wd.median))
                return _real(state, batch, lr)

            trainer._step = slow_step
        return trainer

    trainer = build_trainer()
    if args.fault_tolerance and args.crash_at is not None:
        partial = trainer.run(stop_at=args.crash_at)
        print(f"CRASH_SIMULATED step={partial['final_step']}")
        # A real crash loses the process; rebuilding the trainer from
        # scratch and resuming is exactly the restart path.
        trainer = build_trainer()
    result = trainer.run()
    hist = result["history"]
    print(f"\nfirst logged loss {hist[0]['loss']:.3f} -> "
          f"final {hist[-1]['loss']:.3f} over {result['final_step']} steps")
    if args.fault_tolerance:
        print(f"STRAGGLERS={trainer.watchdog.straggler_steps}")
        print("FT_OK")


if __name__ == "__main__":
    main()
