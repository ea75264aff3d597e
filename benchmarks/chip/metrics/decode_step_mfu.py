"""Serving decode, the whole step's share of the chip's peak: the least
time an interval needs (the larger of its FLOPs at the bf16 peak and its
needed bytes at the HBM peak; needed bytes are the weights once plus the
K/V of the live positions of occupied slots, not the whole cache) over
the decode executable's device time, in percent, over the traced window.
"""

import flops
import harness


def read(rec):
    ivals = rec.modules("jit_row")
    steps = [live for t, live in rec.counters["intervals"]
             if rec.window[0] <= t <= rec.window[1]]
    if not ivals or not steps:
        return None
    bounds = {"compute": 0, "memory": 0}
    least = 0.0
    work = flops.of(rec)
    for live in steps:
        w = work.decode_interval(rec.cfg, live)
        t, bound = flops.roofline_s(w["flops"], w["bytes"], rec.peaks)
        least += t
        bounds[bound] += 1
    harness.log(f"decode_step_mfu: intervals bound by {bounds}")
    device = sum(e.end - e.start for e in ivals) / len(ivals) / 1e9
    return 100.0 * (least / len(steps)) / device
