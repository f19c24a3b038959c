"""Device time of the window layers' flash attention kernels, a step.

A model with two kinds of attention layer (``models/smallthinker.py``)
names all its kernels ``flash_attention``; a windowed call's sit under the
scope ``hvd.attn.window`` as well (outside ``hvd.attn.fwd`` / ``.bwd``), and
that is read from the program's own text, instruction by instruction, as
the linear-attention readers do. ``kernel_seconds`` is this file's and the
three sibling readers' way to the trace: the kernels' summed device seconds
by kind, on the device where all of them together took longest."""

import cells
import trace_reduce

scoped = cells.load_code(cells.HERE, "layer_metrics", "linattn_scan_ms.py")


def components(scope: str) -> list:
    """The names of a name stack, each freed of the transformations
    wrapped around it (``transpose(jvp(hvd.attn.window))``)."""
    return [part.rsplit("(", 1)[-1].rstrip(")") for part in scope.split("/")]


def kernel_seconds(run, params) -> dict | None:
    """``{"window": seconds, "full": seconds}`` of the operations named
    ``kernel_names``, told apart by ``window_scope`` in their name stack;
    ``None`` where no such operation ran or the program's text cannot be
    asked (no device plane, a program without ``step_texts``)."""
    table = scoped.instruction_scopes(run)
    if table is None:
        return None
    found = max(
        (trace_reduce.matching(ops, params["kernel_names"])
         for ops in run.trace.devices.values()),
        key=lambda ops: sum(op.end - op.start for op in ops))
    if not found:
        return None
    seconds = {"window": 0.0, "full": 0.0}
    for op in found:
        windowed = params["window_scope"] in components(
            table.get(op.name, ""))
        seconds["window" if windowed else "full"] += op.end - op.start
    return seconds


def layers(config: dict) -> dict:
    """How many of the layers kept are of each kind."""
    kept = config["sliding_window_layout"][:config["num_hidden_layers"]]
    return {"window": sum(kept), "full": len(kept) - sum(kept)}


def kind_ms(run, params, kind: str) -> float | None:
    seconds = kernel_seconds(run, params)
    if seconds is None or not seconds[kind]:
        return None
    return seconds[kind] / run.steps * 1e3


def read(run, params):
    return kind_ms(run, params, "window")
