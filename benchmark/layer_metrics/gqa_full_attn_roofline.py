"""The full causal, grouped-key/value flash attention kernels' share of
their roofline: ``window_attn_roofline.py``'s costs over the whole causal
triangle, ``S (S + 1) / 2`` pairs a query head."""

import cells

window = cells.load_code(cells.HERE, "layer_metrics",
                         "window_attn_roofline.py")


def read(run, params):
    return window.roofline(run, params, "full", "gqa_full_attn_roofline")
