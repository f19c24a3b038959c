"""What dispatching the compiled step costs the host, a call."""

import program_spans


def read(run, params):
    found = program_spans.host(run)
    return None if found is None else found.dispatch_ms
