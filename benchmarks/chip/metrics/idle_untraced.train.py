"""Device: the share of the traced window, in percent, in which no
operation runs on the chip and no span is open, neither the program's
(placed on the trace's clock by the window's start) nor the benchmark's."""

import progspans


def read(rec):
    return progspans.idle_untraced(rec)
