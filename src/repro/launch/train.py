"""End-to-end training driver: corpus → data pipeline → fault-tolerant
trainer → WAP publish, all catalog-backed.

  PYTHONPATH=src python -m repro.launch.train --arch paper-demo \
      --lake /tmp/lake --steps 200 --seq-len 256 --batch 8
"""

from __future__ import annotations

import argparse

from repro.configs import full_config, smoke_config
from repro.core import Lake
from repro.data import build_data_pipeline, seed_corpus
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-demo")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-friendly)")
    ap.add_argument("--lake", required=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--n-docs", type=int, default=512)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--run-name", default="run0")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--publish", action="store_true")
    ap.add_argument("--schedule", default="cosine",
                    choices=["cosine", "wsd", "constant"])
    args = ap.parse_args()
    enable_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else full_config(args.arch)
    lake = Lake(args.lake)
    if "data.main" not in lake.catalog.branches():
        lake.catalog.create_branch("data.main", "main", author="data")
        seed_corpus(lake, "data.main", n_docs=args.n_docs, seed=args.seed,
                    vocab_size=cfg.vocab_size, author="data")
        lake.run(build_data_pipeline(args.seq_len), branch="data.main",
                 author="data")

    tcfg = TrainerConfig(
        arch=args.arch, seq_len=args.seq_len, global_batch=args.batch,
        n_steps=args.steps, ckpt_every=args.ckpt_every, seed=args.seed,
        schedule=args.schedule,
        schedule_kw={"peak_lr": 3e-4, "warmup_steps": max(args.steps // 10, 1),
                     "total_steps": args.steps}
        if args.schedule == "cosine" else
        {"peak_lr": 3e-4, "warmup_steps": max(args.steps // 10, 1),
         "stable_steps": args.steps // 2, "decay_steps": args.steps // 2},
        author="trainer")
    trainer = Trainer(lake, cfg, tcfg, data_branch="data.main",
                      run_name=args.run_name)
    out = trainer.run(resume=args.resume)
    print(f"final loss: {out['losses'][-1]:.4f} "
          f"(first: {out['losses'][0]:.4f}), "
          f"stragglers: {trainer.straggler_events}")
    if args.publish:
        head = trainer.publish("main")
        print(f"published run branch to main @ {head[:12]}")


if __name__ == "__main__":
    main()
