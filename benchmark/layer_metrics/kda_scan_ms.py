"""Device time of Kimi Delta Attention's rule, a step: the operations under
the program's ``hvd.linattn.scan`` scope, forward, recomputed and backward,
as the union of their intervals (``linattn_scan_ms.py``'s reduction: the
state crosses the chunks in a loop, whose own event and the events inside
it count once)."""

import cells
import program_spans

scope_ms = cells.load_code(
    cells.HERE, "layer_metrics", "linattn_scan_ms.py").scope_ms


def read(run, params):
    ms = scope_ms(run, params["scopes"])
    if ms is not None:
        summed = sum(program_spans.device(run).phases.get(scope, 0.0)
                     for scope in params["scopes"])
        print(f"kda_scan_ms: {ms:.3f} ms a step as the union of the "
              f"operations' intervals; their plain sum is {summed:.3f}",
              flush=True)
    return ms
