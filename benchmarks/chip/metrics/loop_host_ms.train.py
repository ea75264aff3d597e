"""Train loop, host: mean milliseconds per ``train.step`` wholly inside the
window of the step's time outside its ``train.sync`` (the feed, the
dispatch and the record, during which the device waits for the host)."""

import progspans


def read(rec):
    return progspans.loop_host_ms(rec, "train.step", "train.sync")
