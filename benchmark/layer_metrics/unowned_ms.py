"""Device time under no scope the program opened, own or inside, a step."""

import owners


def read(run, params):
    found = owners.of(run)
    if found is None:
        return None
    print(f"unowned_ms: {found.unowned_ms:.3f} of {found.busy_ms:.3f} ms "
          f"busy a step ({100 * found.unowned_ms / found.busy_ms:.2f}%)",
          flush=True)
    return found.unowned_ms
