"""Checkpoint commit, the rest: mean seconds per commit that began in the
window of ``ckpt.save``'s time outside the six stage spans anywhere in its
subtree (the catalog commit and the glue between stages).  With the six
stages it sums to the ``ckpt.save`` time."""

import progspans


def read(rec):
    return progspans.commit_stage(rec, "other")
