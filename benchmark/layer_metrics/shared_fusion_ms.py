"""Device time in fusions that several owners share, a step."""

import owners


def read(run, params):
    found = owners.of(run)
    return None if found is None else found.shared_fusion_ms
