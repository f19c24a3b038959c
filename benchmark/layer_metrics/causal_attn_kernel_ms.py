"""Device time of the causal attention kernels, a step."""

import trace_reduce


def read(run, params):
    seconds = trace_reduce.kernel_seconds(run.trace, params["kernel_names"])
    return None if seconds is None else seconds / run.steps * 1e3
