"""Device time of the full causal layers' flash attention kernels in a
model that also has windowed ones, a step."""

import cells

window = cells.load_code(cells.HERE, "layer_metrics",
                         "window_attn_kernel_ms.py")


def read(run, params):
    return window.kind_ms(run, params, "full")
