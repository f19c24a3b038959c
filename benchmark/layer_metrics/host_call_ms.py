"""What one call into the factory's step costs the host."""

import statistics


def read(run, params):
    return statistics.median(run.call_s) * 1e3
