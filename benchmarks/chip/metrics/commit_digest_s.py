"""Checkpoint commit, one stage: mean seconds per commit that began in the
window of the device-side fingerprint of the params and its host syncs
(``ckpt.digest``), from the program's spans under each ``ckpt.save``."""

import progspans


def read(rec):
    return progspans.commit_stage(rec, "digest")
