"""Lake -> device feed: mean host time of ``trainer.loader.batch`` per step
in the window (the benchmark's span around the program's call)."""


def read(rec):
    spans = rec.spans.within("feed", *rec.window)
    if not spans:
        return None
    return sum(e - s for s, e in spans) / len(spans) * 1e3
