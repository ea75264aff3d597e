#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
      --seconds <run_seconds> --trace <0|1>

The cell's configuration, traffic and per-layer metrics are found by name
from ``BENCHMARK.json`` (see ``spec.py``).  The run sets up, measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON line last on stdout: with ``--trace 0`` the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics from
a device trace of the window.  Without a TPU (or with fewer chips than
the cell asks for) it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import serve  # noqa: E402
import spec  # noqa: E402
import train  # noqa: E402

sys.path.insert(1, spec.SRC)
RUNNERS = {"train": train, "serve": serve}

EXIT_NO_CHIP = 3


def load_metric(name: str):
    return spec.load_module(os.path.join("metrics", name + ".py")).read


def limits_of(cell_name: str) -> dict:
    with open(os.path.join(HERE, "limits", cell_name + ".json")) as f:
        return json.load(f)["limits"]


def execute(cell, seed: int, seconds: float, trace: bool, *,
            require_tpu: bool = True, t_start: float = T_START,
            limits: dict = None) -> dict:
    """One run of ``cell``; returns the result line (with its checks)."""
    import jax

    import peaks as peak_table

    if require_tpu:
        devices = harness.require_chips(jax, cell.chips)
    else:
        devices = jax.devices()[:cell.chips]
    cache = harness.enable_compile_cache(jax)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    harness.log(f"device {devices[0].platform} {devices[0].device_kind} "
                f"x{len(devices)}; jax {jax.__version__}; cache {cache}")
    compiles = harness.Compiles(jax)
    spans = harness.Spans(jax)
    profiler = harness.Profiler(jax, trace, spans)
    runner = RUNNERS[cell.traffic["kind"]]
    try:
        out = runner.run(cell, seed, seconds, profiler, spans, compiles,
                         devices, t_start, limits or limits_of(cell.name))
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": out.memory_peak_bytes}
        result = {"correct": all(c.ok for c in out.checks),
                  "attempted": out.attempted, "failed": out.failed}
        if trace:
            rec = out.record
            rec.peaks = peak_table.peaks(devices[0].device_kind)
            t0 = time.perf_counter()
            rec.trace, rec.trace_window = profiler.load(
                [*runner.TRACE_SPANS])
            rec.device = rec.trace.devices[0]
            metrics = {}
            for m in cell.per_layer:
                try:
                    value = load_metric(m["name"])(rec)
                except Exception as e:  # one reader's fault loses its metric
                    harness.log(f"metric {m['name']}: {type(e).__name__}: {e}")
                    value = None
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            device["busy_s"] = rec.busy_s()
            device["window_s"] = rec.window_s()
            import devtrace

            lo, hi = rec.trace_window
            result["metrics"] = metrics
            result["device"] = device
            result["breakdown"] = {
                "device_ops": [list(x) for x in
                               devtrace.top_ops(rec.trace, rec.device)],
                "idle_gaps": [list(x) for x in devtrace.idle_breakdown(
                    rec.trace, rec.device, lo, hi)]}
            harness.log(f"trace read in {time.perf_counter() - t0:.1f} s")
        else:
            result["metrics"] = {m["name"]: {"value": out.e2e[m["name"]],
                                             "unit": m["unit"]}
                                 for m in cell.end_to_end}
            result["device"] = device
        result["_checks"] = out.checks
        return result
    finally:
        profiler.cleanup()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    try:
        result = execute(cell, args.seed, args.seconds, bool(args.trace))
    except harness.NoChip as e:
        harness.log(f"run.py: {e}")
        return EXIT_NO_CHIP
    checks = result.pop("_checks")
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
