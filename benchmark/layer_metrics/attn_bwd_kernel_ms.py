"""Device time of the attention backward kernels, a step."""

import program_spans


def read(run, params):
    found = program_spans.device(run)
    return None if found is None else found.attn_bwd_ms
