"""How much of the traced window the chip sat idle."""

import trace_reduce


def read(run, params):
    return trace_reduce.idle_share(run.trace)
