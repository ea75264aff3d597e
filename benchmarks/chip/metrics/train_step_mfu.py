"""Train step: model FLOPs of one step (forward and backward, no
recompute, from the configuration's shapes) over the step's device time
times the chip's bf16 peak, in percent."""

import flops


def read(rec):
    steps = rec.modules("jit_train_step")
    if not steps:
        return None
    t = sum(e.end - e.start for e in steps) / len(steps) / 1e9
    c = rec.counters
    work = flops.of(rec).train_step_flops(rec.cfg, c["batch"], c["seq_len"])
    return 100.0 * work / (t * rec.peaks["bf16_flops"])
