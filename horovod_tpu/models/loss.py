"""The token cross entropy of the decoders, with a differentiation rule of
its own.

``-take_along_axis(log_softmax(logits), labels).mean()`` differentiated by
JAX writes a second logits-sized array (``logp``), then builds a third for
the pick's transpose: zeros of the logits' shape with the labels scattered
into them, re-laid out for the products that consume it. None of it is
needed: the gradient with respect to the logits is ``softmax - one-hot``,
one elementwise expression of the logits, the row's log-sum-exp and the
label. :func:`token_cross_entropy` is that as a ``jax.custom_vjp``: its
residuals are the logits, a float32 log-sum-exp a position, the labels and
the weights, its backward holds no ``scatter`` and no zeros, and the
compiler fuses ``dlogits`` into the two products that read it.

Both halves open ``hvd.block.head`` themselves, so the backward's device
time is the head's in ``benchmark/owners.py``'s table.

``models/smallthinker.py``, ``models/sdar.py`` and ``models/granite.py``
call it. ``models/olmoe.py`` and ``models/olmo_hybrid.py`` keep their
``log_softmax`` copies until ROADMAP D14 lifts the sha256 pin on their
lowered toy steps (``tests/benchmark/test_benchmark_smallthinker.py``);
they join here after it (ROADMAP D13).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..attribution import SCOPE_BLOCK_HEAD
from ..profiler import annotate_collective


def _one_hot(logits, labels):
    """``[k == label]`` as a comparison against an iota along the
    vocabulary: a mask the compiler fuses, never an array of its own."""
    vocab = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                     logits.ndim - 1)
    return vocab == labels[..., None].astype(jnp.int32)


def _forward(logits, labels, weights):
    """``(loss, lse)``: one pass over the logits for the row maximum, one
    for the log-sum-exp and the label's logit."""
    with annotate_collective(SCOPE_BLOCK_HEAD):
        x = logits.astype(jnp.float32)
        top = x.max(axis=-1, keepdims=True)
        lse = jnp.log(jnp.exp(x - top).sum(axis=-1)) + top[..., 0]
        picked = jnp.where(_one_hot(x, labels), x, 0.0).sum(axis=-1)
        loss = lse - picked
        if weights is not None:
            loss = weights.astype(jnp.float32) * loss
        return loss.mean(), lse


@jax.custom_vjp
def token_cross_entropy(logits, labels, weights=None):
    """Mean cross entropy of ``logits [..., S, V]`` against ``labels [...,
    S]`` over all positions, in float32; with ``weights [..., S]`` the mean
    of ``weights * (lse - picked)`` over all positions (zero where nothing
    is scored). The same mathematics as ``-take_along_axis(log_softmax(
    logits), labels[..., None], -1).mean()`` to float32 round-off. Labels
    and weights get no gradient."""
    return _forward(logits, labels, weights)[0]


def _token_cross_entropy_fwd(logits, labels, weights):
    loss, lse = _forward(logits, labels, weights)
    return loss, (logits, lse, labels, weights)


def _token_cross_entropy_bwd(res, g):
    logits, lse, labels, weights = res
    with annotate_collective(SCOPE_BLOCK_HEAD):
        # A position's share of the mean, times the cotangent.
        share = g.astype(jnp.float32) / lse.size
        if weights is not None:
            share = share * weights.astype(jnp.float32)
        softmax = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
        dlogits = share[..., None] * jnp.where(
            _one_hot(logits, labels), softmax - 1.0, softmax)
    return dlogits.astype(logits.dtype), None, None


token_cross_entropy.defvjp(_token_cross_entropy_fwd, _token_cross_entropy_bwd)
