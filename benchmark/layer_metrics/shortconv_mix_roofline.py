"""The gated short convolution's share of its roofline.

FLOPs and bytes are what ``y = C * conv(B * x)`` needs for one layer's call,
from the layer's shapes alone and whatever implements it: ``rows`` sequences
of ``seq`` tokens, ``channels`` channels, ``taps`` taps, in a type of
``itemsize`` bytes. Forward, a channel and token makes ``taps``
multiply-adds and two products (the gates), and the traffic is the operands
once: ``B``, ``C`` and ``x`` read, ``y`` written (the taps themselves are
``channels x taps`` numbers: nothing). The backward pass is twice those
operations (every product has two gradients) and reads ``B``, ``C``, ``x``
and ``dy`` and writes ``dB``, ``dC`` and ``dx``. Nothing recomputed counts,
though a recomputed layer runs the forward pass again, and no intermediate
(``B * x``, the convolution's result) is counted as traffic: they may stay
in fast memory. So an implementation can only do more, and the share can
only read low. Bound: ``flash_attn_roofline.py``'s.
"""

import cells

scope_ms = cells.load_code(
    cells.HERE, "layer_metrics", "linattn_scan_ms.py").scope_ms
least_seconds = cells.load_code(
    cells.HERE, "layer_metrics", "flash_attn_roofline.py").least_seconds

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def forward_cost(rows, seq, channels, taps, itemsize):
    positions = rows * seq * channels
    return positions * (2.0 * taps + 2.0), positions * 4.0 * itemsize


def backward_cost(rows, seq, channels, taps, itemsize):
    flops, _ = forward_cost(rows, seq, channels, taps, itemsize)
    return 2 * flops, rows * seq * channels * 7.0 * itemsize


def read(run, params):
    ms = scope_ms(run, params["scopes"])
    if ms is None or run.peak is None:
        return None
    config, job = run.cell.config, run.cell.job
    shape = (job["rows_per_chip"], job["seq_len"], config["hidden_size"],
             config["conv_L_cache"],
             ITEMSIZE[config["training"]["compute_dtype"]])
    forward, forward_bound = least_seconds(forward_cost(*shape), run.peak)
    backward, backward_bound = least_seconds(backward_cost(*shape), run.peak)
    layers = config["layer_types"].count("conv")
    print(f"shortconv_mix_roofline: least {forward * 1e3:.4f} ms forward "
          f"({forward_bound}-bound) + {backward * 1e3:.4f} ms backward "
          f"({backward_bound}-bound) a layer, {layers} layers; took "
          f"{ms:.3f} ms a step", flush=True)
    return 100.0 * layers * (forward + backward) * 1e3 / ms
