"""Device time of the attention forward kernels, a step."""

import program_spans


def read(run, params):
    found = program_spans.device(run)
    return None if found is None else found.attn_fwd_ms
