"""Serving driver: load checkpoint commits from the lake and serve batched
requests (weights pinned to immutable catalog refs).

Single engine against one ref (legacy surface):

  PYTHONPATH=src python -m repro.launch.serve --lake /tmp/lake \
      --ref trainer.run-run0 --arch paper-demo --smoke --requests 8

Replica fleet watching the production serving tag (deployment = tag flip,
see docs/serving.md):

  PYTHONPATH=src python -m repro.launch.serve --lake /tmp/lake \
      --replicas 2 --watch-tag serving/prod --arch paper-demo --smoke

Also reachable as `repro serve --replicas N` / `repro rollout` /
`repro rollback`.
"""

from __future__ import annotations

import argparse

import numpy as np

from repro.configs import full_config, smoke_config
from repro.core import Lake
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import (BatchedServer, FixedBatchedServer, ServeEngine,
                           ServingFleet)


def run_single(lake: Lake, cfg, ref: str, *, batch_size: int = 4,
               max_len: int = 128, requests: int = 8, gen_tokens: int = 16,
               mode: str = "continuous", seed: int = 0) -> dict:
    """Serve a synthetic workload from one engine pinned to ``ref``."""
    from repro.checkpoint import latest_checkpoint

    commit = latest_checkpoint(lake, ref) or ref
    engine = ServeEngine.from_catalog(lake, commit, cfg, max_len=max_len,
                                      batch_size=batch_size)
    server = (BatchedServer(engine) if mode == "continuous"
              else FixedBatchedServer(engine))
    rng = np.random.default_rng(seed)
    for rid in range(requests):
        plen = int(rng.integers(4, max_len - gen_tokens))
        prompt = rng.integers(3, cfg.vocab_size, size=plen).astype(np.int32)
        server.submit(rid, prompt, gen_tokens)
    served = 0
    while server.pending:
        served += server.step()
    return {"served": served, "commit": engine.model_commit,
            "completed": server.completed}


def run_fleet(lake: Lake, cfg, *, replicas: int = 2, slots: int = 4,
              max_len: int = 128, watch_tag: str = "serving/prod",
              poll_every: int = 4, mode: str = "continuous",
              requests: int = 16, gen_tokens: int = 8,
              seed: int = 0) -> ServingFleet:
    """Serve a synthetic workload from a tag-watching replica fleet."""
    fleet = ServingFleet(lake, cfg, replicas=replicas, slots=slots,
                         max_len=max_len, watch_tag=watch_tag,
                         poll_every=poll_every, mode=mode)
    rng = np.random.default_rng(seed)
    for rid in range(requests):
        plen = int(rng.integers(4, max_len - gen_tokens))
        prompt = rng.integers(3, cfg.vocab_size, size=plen).astype(np.int32)
        fleet.submit(rid, prompt, int(rng.integers(1, gen_tokens + 1)))
    fleet.drain()
    return fleet


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--lake", required=True)
    ap.add_argument("--ref", default=None,
                    help="branch / tag / commit with a checkpoint "
                         "(single-engine mode)")
    ap.add_argument("--arch", default="paper-demo")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--gen-tokens", type=int, default=16)
    ap.add_argument("--mode", choices=["continuous", "fixed"],
                    default="continuous")
    ap.add_argument("--replicas", type=int, default=None,
                    help="fleet mode: number of replicas watching the tag")
    ap.add_argument("--watch-tag", default="serving/prod")
    ap.add_argument("--poll-every", type=int, default=4)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else full_config(args.arch)
    lake = Lake(args.lake)
    if args.replicas:
        fleet = run_fleet(lake, cfg, replicas=args.replicas,
                          slots=args.batch_size, max_len=args.max_len,
                          watch_tag=args.watch_tag,
                          poll_every=args.poll_every, mode=args.mode,
                          requests=args.requests,
                          gen_tokens=args.gen_tokens)
        print(f"fleet of {args.replicas} served {len(fleet.completed)} "
              f"requests from tag {args.watch_tag!r} "
              f"(target {fleet.target[:12]}, {fleet.steps} steps, "
              f"{fleet.rollouts} rollouts)")
        return
    if not args.ref:
        raise SystemExit("--ref is required without --replicas")
    out = run_single(lake, cfg, args.ref, batch_size=args.batch_size,
                     max_len=args.max_len, requests=args.requests,
                     gen_tokens=args.gen_tokens, mode=args.mode)
    print(f"served {out['served']} requests from model commit "
          f"{out['commit'][:12]}")
    for rid in sorted(out["completed"])[:3]:
        res = out["completed"][rid]
        print(f"  req {rid}: {res.tokens[0][:8].tolist()}...")


if __name__ == "__main__":
    main()
