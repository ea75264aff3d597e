"""Device: the share of the traced window in which no operation ran on the
chip, in percent (1 - union of op intervals / window)."""


def read(rec):
    return 100.0 * (1.0 - rec.busy_s() / rec.window_s())
