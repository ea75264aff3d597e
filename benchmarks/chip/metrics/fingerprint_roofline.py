"""Kernel ``kernels/fingerprint``: the bytes of the params digested at each
commit in the traced window, read once at the HBM peak, over the summed
device time of the digest executables (``jit__digest``: word packing,
kernel and lane fold), in percent.  Memory-bound: the digest does a few
integer operations per 4-byte word."""


def read(rec):
    events = rec.modules("jit__digest")
    c = rec.counters
    if not events or not c.get("param_leaves"):
        return None
    commits = len(events) / c["param_leaves"]
    t = sum(e.end - e.start for e in events) / 1e9
    least = commits * c["param_bytes"] / rec.peaks["hbm_bytes_per_s"]
    return 100.0 * least / t
