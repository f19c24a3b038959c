"""The buffers' high-water mark, which set-up sets."""


def read(run, params):
    if not run.memory:
        return None
    return max(row["peak_bytes_in_use"] for row in run.memory) / 2.0 ** 30
