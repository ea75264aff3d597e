"""Serving loop, host: mean milliseconds per ``serve.step`` wholly inside
the window of the step's time outside its ``serve.fetch`` spans (the
token log's host transfers): admits, the decode dispatch, retirement."""

import progspans


def read(rec):
    return progspans.loop_host_ms(rec, "serve.step", "serve.fetch")
