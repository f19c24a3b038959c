"""The buffers' part of the memory taken."""


def read(run, params):
    if not run.memory:
        return None
    return max(row["bytes_in_use"] for row in run.memory) / 2.0 ** 30
