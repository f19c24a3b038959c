"""Device idle time that opened while the host was inside the step."""

import program_spans


def read(run, params):
    found = program_spans.host(run)
    return None if found is None else found.idle_in_step_ms
