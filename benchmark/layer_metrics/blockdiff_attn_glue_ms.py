"""Device time a step of what block-diffusion attention costs beside its
kernels: the operations under ``hvd.attn.blockdiff`` that are no Pallas
call (the split of the doubled stream, the noisy queries' own block of
``block_length`` keys in plain XLA, the merge through the log-sum-exp, the
concatenation, and the layout copies XLA puts around them), forward and
backward, as the union of their intervals (``recompute_ms.py``'s way)."""

import cells
import trace_reduce

kernels = cells.load_code(cells.HERE, "layer_metrics",
                          "blockdiff_attn_kernel_ms.py")


def read(run, params):
    found = kernels.scoped_ops(run, params)
    if found is None or not found[1]:
        return None
    return trace_reduce.total(trace_reduce.spans(found[1])) / run.steps * 1e3
