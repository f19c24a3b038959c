"""Device time of the gated short convolution beside its two projections, a
step: the first gate, the taps and the second gate of every ``conv``
layer."""

import cells

scope_ms = cells.load_code(
    cells.HERE, "layer_metrics", "linattn_scan_ms.py").scope_ms


def read(run, params):
    return scope_ms(run, params["scopes"])
