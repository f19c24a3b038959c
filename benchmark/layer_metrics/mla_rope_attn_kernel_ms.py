"""Device time of the flash attention kernels of latent attention with its
rotary split, a step: ``mla_attn_kernel_ms.py``'s reader (the
``flash_attention.<n>`` events under ``hvd.attn.mla``) under a name of this
cell's own (Kimi Linear's test holds ``mla_attn_kernel_ms`` to Kimi Linear's
cell with ``==``)."""

import cells

read = cells.load_code(cells.HERE, "layer_metrics",
                       "mla_attn_kernel_ms.py").read
