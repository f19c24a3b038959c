"""Device time of the gradient wire outside its collectives, a step."""

import program_spans


def read(run, params):
    found = program_spans.device(run)
    return None if found is None else found.wire_pack_ms
