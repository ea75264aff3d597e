"""Checkpoint commit, one stage: mean seconds per commit that began in the
window of compression of each new blob (``store.compress``), from the
program's spans under each ``ckpt.save``."""

import progspans


def read(rec):
    return progspans.commit_stage(rec, "compress")
