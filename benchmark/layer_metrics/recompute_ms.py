"""Device time of the forward pass run again inside the backward pass, a
step: the operations of the most idle device whose name stack holds JAX's
own component for what a ``jax.checkpoint`` recomputes, as the union of
their intervals (``linattn_scan_ms.py``'s way)."""

import cells
import trace_reduce

kernels = cells.load_code(cells.HERE, "layer_metrics",
                          "window_attn_kernel_ms.py")


def read(run, params):
    table = kernels.scoped.instruction_scopes(run)
    if table is None:
        return None
    busy = trace_reduce.busy_seconds(run.trace)
    ops = [op for op in run.trace.devices[min(busy, key=busy.get)]
           if params["scope"] in kernels.components(table.get(op.name, ""))]
    if not ops:
        return None
    return trace_reduce.total(trace_reduce.spans(ops)) / run.steps * 1e3
