"""Device time of the ``*`` layers' flash attention kernels, a step: causal
attention whose sixteen query heads share a key/value head. Every Pallas
call of ``ops/attention.py`` is named ``flash_attention`` and this model has
one kind of attention layer, so the kernels are told by name alone, as
``causal_attn_kernel_ms.py`` tells OLMoE's."""

import trace_reduce


def read(run, params):
    seconds = trace_reduce.kernel_seconds(run.trace, params["kernel_names"])
    return None if seconds is None else seconds / run.steps * 1e3
