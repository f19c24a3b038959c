"""Device time of a Mamba-2 mixer beside its projections and the scan, a
step: the short convolution with what follows it, and the gated norm of
the scan's output."""

import cells

scope_ms = cells.load_code(
    cells.HERE, "layer_metrics", "linattn_scan_ms.py").scope_ms


def read(run, params):
    parts = {scope: scope_ms(run, [scope]) for scope in params["scopes"]}
    parts = {scope: ms for scope, ms in parts.items() if ms is not None}
    if not parts:
        return None
    print("ssd_mix_ms: " + ", ".join(
        f"{scope} {ms:.3f} ms" for scope, ms in parts.items()), flush=True)
    return scope_ms(run, params["scopes"])
