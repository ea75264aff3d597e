#!/usr/bin/env python3
"""Find a serving cell's knee: run its mix at several fixed rates in one
process, one short window each, and print the tails at each rate.

  python3 benchmarks/chip/sweep.py --workload <cell> --rates 1,2,3 \\
      --seconds 25

The knee is the highest rate whose queue does not grow through the window
(the drain after it stays short and the TTFT tail stays flat).  The cell's
traffic file then holds a fixed rate below it; the benchmark never
searches for one.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    serve.compare = lambda *a, **k: []  # tails only: no reference here
    for rate in [float(r) for r in args.rates.split(",")]:
        cell.traffic["rate_per_s"] = rate
        t0 = time.perf_counter()
        try:
            r = run.execute(cell, args.seed, args.seconds, False,
                            t_start=t0)
        except run.harness.NoChip as e:
            print(f"sweep.py: {e}", file=sys.stderr)
            return run.EXIT_NO_CHIP
        r.pop("_checks")
        print(json.dumps({"rate_per_s": rate, "attempted": r["attempted"],
                          "unfinished": r["failed"],
                          "wall_s": time.perf_counter() - t0,
                          **{k: v["value"] for k, v in r["metrics"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
