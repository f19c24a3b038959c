"""What the factory wrapper's hooks cost the host, a call."""

import program_spans


def read(run, params):
    found = program_spans.host(run)
    return None if found is None else found.hooks_ms
