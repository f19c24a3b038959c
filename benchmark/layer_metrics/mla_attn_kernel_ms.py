"""Device time of the latent-attention layers' flash attention kernels, a
step.

Every Pallas call of ``ops/attention.py`` is named ``flash_attention``; a
call whose values are not as wide as its keys sits under the scope
``hvd.attn.mla`` as well (outside ``hvd.attn.fwd`` / ``.bwd``, as a windowed
call under ``hvd.attn.window``), and that is read from the program's own
text, instruction by instruction. ``kernel_seconds`` is this file's and the
roofline's way to the trace: the kernels' summed device seconds, on the
device where they took longest. A program without the scope (one older than
the two-width kernels) gives nothing."""

import cells
import trace_reduce

window = cells.load_code(cells.HERE, "layer_metrics",
                         "window_attn_kernel_ms.py")


def kernel_seconds(run, params) -> float | None:
    table = window.scoped.instruction_scopes(run)
    if table is None:
        return None
    seconds = max(
        sum(op.end - op.start
            for op in trace_reduce.matching(ops, params["kernel_names"])
            if params["mla_scope"] in window.components(
                table.get(op.name, "")))
        for ops in run.trace.devices.values())
    return seconds or None


def read(run, params):
    seconds = kernel_seconds(run, params)
    return None if seconds is None else seconds / run.steps * 1e3
