"""Device time of the optimizer's update, a step."""

import program_spans


def read(run, params):
    found = program_spans.device(run)
    return None if found is None else found.phases.get("hvd.optimizer")
