"""The Mamba-2 scan's share of its roofline.

FLOPs and bytes are what the **recurrence** needs for one layer's call,
from the layer's shapes alone and whatever implements it: ``rows``
sequences of ``seq`` tokens, ``heads`` heads of ``width`` inputs and a
state of ``width x state``, ``groups`` groups of ``B`` and ``C``, in a
type of ``itemsize`` bytes. Forward, a token and head makes three ``width x
state`` products in multiply-adds (the state's decay, the rank-one update
``dt x B^T`` and the read ``h C``) and the traffic is the operands once:
x, B, C and the float32 step read, y written. The backward pass is twice
those operations (every product has two gradients) and reads x, B, C, the
step and y's gradient and writes four gradients. Nothing of the chunk
size: the decay matrices, the chunk-by-chunk products and the masked
halves a chunked form computes count for nothing, and neither does the
forward pass a recomputed layer runs again, so an implementation can only
do more and a later change of chunk or a kernel cannot make the count
stale. The states (``width x state`` float32 a head) count for nothing
either: they may stay in fast memory. Bound: ``flash_attn_roofline.py``'s.
"""

import cells

scope_ms = cells.load_code(
    cells.HERE, "layer_metrics", "linattn_scan_ms.py").scope_ms
least_seconds = cells.load_code(
    cells.HERE, "layer_metrics", "flash_attn_roofline.py").least_seconds

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def forward_cost(rows, seq, heads, width, state, groups, itemsize):
    macs = 3.0 * heads * width * state
    nbytes = (2 * heads * width + 2 * groups * state) * itemsize + heads * 4.0
    return rows * seq * 2.0 * macs, rows * seq * nbytes


def backward_cost(rows, seq, heads, width, state, groups, itemsize):
    flops, _ = forward_cost(rows, seq, heads, width, state, groups, itemsize)
    nbytes = (3 * heads * width + 4 * groups * state) * itemsize \
        + 2 * heads * 4.0
    return 2 * flops, rows * seq * nbytes


def read(run, params):
    ms = scope_ms(run, params["scopes"])
    if ms is None or run.peak is None:
        return None
    config, job = run.cell.config, run.cell.job
    shape = (job["rows_per_chip"], job["seq_len"], config["mamba_n_heads"],
             config["mamba_d_head"], config["mamba_d_state"],
             config["mamba_n_groups"],
             ITEMSIZE[config["training"]["compute_dtype"]])
    forward, forward_bound = least_seconds(forward_cost(*shape), run.peak)
    backward, backward_bound = least_seconds(backward_cost(*shape), run.peak)
    layers = config["layer_types"].count("mamba")
    print(f"ssd_scan_roofline: least {forward * 1e3:.4f} ms forward "
          f"({forward_bound}-bound) + {backward * 1e3:.4f} ms backward "
          f"({backward_bound}-bound) a layer, {layers} layers; took "
          f"{ms:.3f} ms a step", flush=True)
    return 100.0 * layers * (forward + backward) * 1e3 / ms
