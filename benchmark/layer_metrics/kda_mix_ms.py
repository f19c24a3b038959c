"""Device time of a Kimi-Delta-Attention mixer beside its projections and
the rule, a step: the short convolutions with what follows them, and the
gated norm of the rule's output."""

import cells

scope_ms = cells.load_code(
    cells.HERE, "layer_metrics", "linattn_scan_ms.py").scope_ms


def read(run, params):
    parts = {scope: scope_ms(run, [scope]) for scope in params["scopes"]}
    parts = {scope: ms for scope, ms in parts.items() if ms is not None}
    if not parts:
        return None
    print("kda_mix_ms: " + ", ".join(
        f"{scope} {ms:.3f} ms" for scope, ms in parts.items()), flush=True)
    return scope_ms(run, params["scopes"])
