#!/usr/bin/env python3
"""Readings for a cell's limits: the program against the plain reference
on many seeds in one process, with the reference at float8 and, for
training, the planted faults read beside it on some of them.

  python3 benchmarks/chip/check.py --workload <cell> --seeds 11,12,13 \\
      --seconds 5 [--extra 11,12] [--int8]

Each seed is a short run of the cell (same set-up, same timed path, a
short window); one line per seed gives every number compared.  On the
seeds named by ``--extra`` the control is read, and for training the
faults of half the batch and of a step that returns its state unchanged.
``--int8`` runs the program with its own int8 gradient compression
switched on: the train cells' control.  The benchmark's own runs never
run any of these.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402
import serve  # noqa: E402
import spec  # noqa: E402
import train  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--extra", default="",
                    help="seeds on which to read the control and the faults")
    ap.add_argument("--int8", action="store_true",
                    help="the program with int8 gradient compression on")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    extra = {int(s) for s in args.extra.split(",") if s}
    if args.int8:
        cell.traffic["optimizer"] = dict(cell.traffic["optimizer"],
                                         compress_grads=True)
    for seed in [int(s) for s in args.seeds.split(",")]:
        train.EXTRA = ("fp8", "half", "frozen") if seed in extra else ()
        serve.EXTRA = ("fp8",) if seed in extra else ()
        try:
            r = run.execute(cell, seed, args.seconds, False,
                            t_start=time.perf_counter())
        except run.harness.NoChip as e:
            print(f"check.py: {e}", file=sys.stderr)
            return run.EXIT_NO_CHIP
        checks = {c.name: c.value for c in r.pop("_checks")}
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "readings": checks,
                          "metrics": {k: v["value"]
                                      for k, v in r["metrics"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
