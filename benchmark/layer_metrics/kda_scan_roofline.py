"""Kimi Delta Attention's rule against its roofline.

FLOPs and bytes are what the **recurrence** needs for one layer's call,
from the layer's shapes alone and whatever implements it: ``rows``
sequences of ``seq`` tokens, ``heads`` heads of ``dim`` key and value
channels, in a type of ``itemsize`` bytes. Forward, a token and head makes
four ``dim x dim`` products in multiply-adds (the decay of the state's
rows, ``S'^T k``, the rank-one update and the read ``S^T q``) and the
traffic is the operands once: q, k, v read and o written in the compute
type, the log-decays ``g`` (one a key channel) and ``beta`` read in
float32. The backward pass is twice those operations (every product has two
gradients) and reads q, k, v, g, beta and o's gradient and writes five
gradients. Nothing of the chunk or the sub-block: the pair terms, the
solve, the masked halves a chunked form computes count for nothing, and
neither does the forward pass a recomputed layer runs again, so an
implementation can only do more and a later change of chunk or a kernel
cannot make the count stale. The states (``dim x dim`` float32 a head)
count for nothing either: they may stay in fast memory. Bound:
``flash_attn_roofline.py``'s.
"""

import cells

scope_ms = cells.load_code(
    cells.HERE, "layer_metrics", "linattn_scan_ms.py").scope_ms
least_seconds = cells.load_code(
    cells.HERE, "layer_metrics", "flash_attn_roofline.py").least_seconds

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def forward_cost(rows, seq, heads, dim, itemsize):
    macs = 4.0 * heads * dim * dim
    nbytes = 4 * heads * dim * itemsize + heads * dim * 4.0 + heads * 4.0
    return rows * seq * 2.0 * macs, rows * seq * nbytes


def backward_cost(rows, seq, heads, dim, itemsize):
    flops, _ = forward_cost(rows, seq, heads, dim, itemsize)
    nbytes = 7 * heads * dim * itemsize + 2 * (heads * dim + heads) * 4.0
    return 2 * flops, rows * seq * nbytes


def read(run, params):
    ms = scope_ms(run, params["scopes"])
    if ms is None or run.peak is None:
        return None
    config, job = run.cell.config, run.cell.job
    linear = config["linear_attn_config"]
    shape = (job["rows_per_chip"], job["seq_len"], linear["num_heads"],
             linear["head_dim"],
             ITEMSIZE[config["training"]["compute_dtype"]])
    forward, forward_bound = least_seconds(forward_cost(*shape), run.peak)
    backward, backward_bound = least_seconds(backward_cost(*shape), run.peak)
    layers = len(linear["kda_layers"])
    print(f"kda_scan_roofline: least {forward * 1e3:.4f} ms forward "
          f"({forward_bound}-bound) + {backward * 1e3:.4f} ms backward "
          f"({backward_bound}-bound) a layer, {layers} layers; took "
          f"{ms:.3f} ms a step", flush=True)
    return 100.0 * layers * (forward + backward) * 1e3 / ms
