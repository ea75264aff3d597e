"""Train step: device time of the train-step executable (``jit_train_step``)
in the traced window, per step."""


def read(rec):
    steps = rec.modules("jit_train_step")
    if not steps:
        return None
    return sum(e.end - e.start for e in steps) / len(steps) / 1e6
