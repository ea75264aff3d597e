"""Checkpoint commit, one stage: mean seconds per commit that began in the
window of tensorfile encoding, per-column stats and msgpack packing
(``table.encode``), from the program's spans under each ``ckpt.save``."""

import progspans


def read(rec):
    return progspans.commit_stage(rec, "encode")
