"""Device time of the block-diffusion attention kernels, a step.

``ops.attention.block_diffusion_attention`` opens ``hvd.attn.blockdiff``
around its two calls of the multi-tile flash kernels (the clean stream's
block-causal triangle, the noisy stream's clean past) and around what joins
the noisy stream's two sources beside them. ``scoped_ops`` is this file's
and its two siblings' way to the trace: the operations of the device where
the flash kernels took longest, split into the Pallas calls under that
scope and everything else under it, the scope read from the program's own
text, instruction by instruction, as ``window_attn_kernel_ms.py`` does."""

import re

import cells
import trace_reduce

window = cells.load_code(cells.HERE, "layer_metrics",
                         "window_attn_kernel_ms.py")


def scoped_ops(run, params) -> tuple | None:
    """``(kernels, glue)``: the operations whose name stack holds
    ``params["scope"]``, those named ``kernel_names`` and the others;
    ``None`` where the program's text cannot be asked (no device plane, a
    program without ``step_texts``) or holds no such scope (a program
    older than the scope)."""
    table = window.scoped.instruction_scopes(run)
    if table is None:
        return None
    ops = max(run.trace.devices.values(), key=lambda ops: sum(
        op.end - op.start
        for op in trace_reduce.matching(ops, params["kernel_names"])))
    under = [op for op in ops if params["scope"] in window.components(
        table.get(op.name, ""))]
    if not under:
        return None
    named = re.compile(params["kernel_names"])
    return ([op for op in under if named.search(op.name)],
            [op for op in under if not named.search(op.name)])


def kernel_seconds(run, params) -> float | None:
    found = scoped_ops(run, params)
    if found is None or not found[0]:
        return None
    return sum(op.end - op.start for op in found[0])


def read(run, params):
    seconds = kernel_seconds(run, params)
    return None if seconds is None else seconds / run.steps * 1e3
