"""Device time of a linear-attention layer beside its projections and the
delta rule, a step: the short convolutions with what follows them, and the
gated norm of the rule's output."""

import cells

scan = cells.load_code(cells.HERE, "layer_metrics", "linattn_scan_ms.py")


def read(run, params):
    parts = {scope: scan.scope_ms(run, [scope]) for scope in params["scopes"]}
    parts = {scope: ms for scope, ms in parts.items() if ms is not None}
    if not parts:
        return None
    print("linattn_mix_ms: " + ", ".join(
        f"{scope} {ms:.3f} ms" for scope, ms in parts.items()), flush=True)
    return scan.scope_ms(run, params["scopes"])
