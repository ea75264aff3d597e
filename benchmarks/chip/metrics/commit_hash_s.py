"""Checkpoint commit, one stage: mean seconds per commit that began in the
window of sha256 of each blob put to the object store, and its dedup look-up
(``store.hash``), from the program's spans under each ``ckpt.save``."""

import progspans


def read(rec):
    return progspans.commit_stage(rec, "hash")
