"""Checkpoint: mean host time of ``repro.checkpoint.save`` per commit that
began in the window (device -> host copy, digest, encode, put, commit)."""


def read(rec):
    spans = rec.spans.within("commit", *rec.window)
    if not spans:
        return None
    return sum(e - s for s, e in spans) / len(spans)
