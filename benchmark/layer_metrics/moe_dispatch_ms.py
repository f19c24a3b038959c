"""Device time of a mixture-of-experts layer outside its experts, a step:
the router and its slots, the tokens' way into the slots and the way back."""

import program_spans


def read(run, params):
    found = program_spans.device(run)
    if found is None:
        return None
    parts = {scope: found.phases[scope] for scope in params["scopes"]
             if scope in found.phases}
    if not parts:
        return None
    print("moe_dispatch_ms: " + ", ".join(
        f"{scope} {ms:.3f} ms" for scope, ms in parts.items()), flush=True)
    return sum(parts.values())
