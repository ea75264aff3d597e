"""Checkpoint commit, one stage: mean seconds per commit that began in the
window of the device-to-host copy of the params and AdamW trees
(``ckpt.copy``), from the program's spans under each ``ckpt.save``."""

import progspans


def read(rec):
    return progspans.commit_stage(rec, "copy")
