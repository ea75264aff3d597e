"""Serving admit: device time per admitted request in the traced window,
the B=1 prefill at the prompt's length (``jit_prefill_tok``) plus the slot
write (``jit_write_slot``)."""


def read(rec):
    pre = rec.modules("jit_prefill_tok")
    if not pre:
        return None
    write = rec.modules("jit_write_slot")
    t = sum(e.end - e.start for e in pre + write)
    return t / len(pre) / 1e6
