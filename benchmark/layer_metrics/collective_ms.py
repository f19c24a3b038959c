"""How long the gradient wire is in flight, a step."""

import trace_reduce


def read(run, params):
    seconds = trace_reduce.collective_seconds(run.trace)
    return None if seconds is None else seconds[0] / run.steps * 1e3
