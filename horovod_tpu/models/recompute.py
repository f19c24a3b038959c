"""What a recomputed decoder layer keeps: the ``jax.checkpoint`` policy that
``models/smallthinker.py``, ``models/sdar.py`` and ``models/granite.py``
wrap their layers in (``nn.remat(DecoderLayer, policy=...)``). The first of
the decoders' shared parts to live outside one of the decoders (ROADMAP
D13)."""

from __future__ import annotations

import jax


def save_kernels_and_projections(prim, *args, **params) -> bool:
    """The ``jax.checkpoint`` policy of a recomputed layer. Beside its
    input the forward pass keeps what a Pallas kernel returned (the only
    kernel of a layer's forward pass is the flash forward kernel, whose
    output and log-sum-exp are the residuals the dq and dkv kernels want,
    so it never runs again) and the results of the matrix products without
    a batch dimension (the four attention projections and the router:
    0.2 GiB a layer at 16,384 tokens for 4 ms of recomputation each).
    Norms, RoPE, the slots' gathers, the experts' batched products and the
    combine are computed again."""
    return prim.name == "pallas_call" or (
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable(
            prim, *args, **params))
