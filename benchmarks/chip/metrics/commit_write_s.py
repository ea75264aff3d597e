"""Checkpoint commit, one stage: mean seconds per commit that began in the
window of the write-then-rename of each new object (``store.write``), from
the program's spans under each ``ckpt.save``."""

import progspans


def read(rec):
    return progspans.commit_stage(rec, "write")
