"""Device time of the ``full_attention`` layers' flash attention kernels, a
step: causal attention at 64 lanes a head whose four query heads share a
key/value head. Every Pallas call of ``ops/attention.py`` is named
``flash_attention`` and this model has one kind of attention layer, so the
kernels are told by name alone: ``gqa16_attn_kernel_ms.py``'s reader, loaded
from there."""

import cells

read = cells.load_code(
    cells.HERE, "layer_metrics", "gqa16_attn_kernel_ms.py").read
