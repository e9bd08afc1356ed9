"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --steps 200 --batch 8 --seq 512 --optimizer muon-qr \
        --checkpoint-dir /tmp/ckpt [--smoke] [--mesh d,m] [--grad-compression]

``--smoke`` selects the reduced config (CPU-friendly); otherwise the full
assigned architecture is built (needs a real TPU slice).  ``--mesh d,m``
builds a (data, model) mesh over the visible devices and applies the
production sharding rules — on CPU combine with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` for local
multi-device runs.
"""

from __future__ import annotations

import argparse
import json

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import ARCHS, get_config, get_smoke_config
from repro.data import DataConfig
from repro.distributed.sharding import MeshRules, activation_policy
from repro.training import RunConfig, TrainConfig, Trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS), required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--lr", type=float, default=0.02)
    ap.add_argument("--optimizer", default="muon-qr",
                    choices=["muon-qr", "muon-ns", "adamw"])
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--mesh", default=None,
                    help="data,model sizes, e.g. 4,2")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = rules = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split(","))
        mesh = jax.make_mesh((d, m), ("data", "model"))
        rules = MeshRules(mesh=mesh, data_axes=("data",))

    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                          global_batch=args.batch, seed=args.seed,
                          embedding_input=cfg.embedding_input,
                          d_model=cfg.d_model)
    train_cfg = TrainConfig(optimizer=args.optimizer, lr=args.lr,
                            microbatch=args.microbatch,
                            grad_compression=args.grad_compression)
    run_cfg = RunConfig(total_steps=args.steps, warmup_steps=args.warmup,
                        checkpoint_dir=args.checkpoint_dir,
                        checkpoint_every=args.checkpoint_every,
                        seed=args.seed)

    trainer = Trainer(cfg, train_cfg, run_cfg, data_cfg, mesh=mesh,
                      rules=rules)
    if mesh is not None:
        with mesh, activation_policy(rules):
            result = trainer.run()
    else:
        result = trainer.run()
    print(json.dumps({"final_step": result["final_step"],
                      "last": result["history"][-1] if result["history"]
                      else None}))
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
