"""Serving decode: mean device time of the per-row decode executable
(``jit_row``, every slot one token) per interval in the traced window."""


def read(rec):
    ivals = rec.modules("jit_row")
    if not ivals:
        return None
    return sum(e.end - e.start for e in ivals) / len(ivals) / 1e6
