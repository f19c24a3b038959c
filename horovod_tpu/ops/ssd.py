"""The Mamba-2 state-space scan (state-space duality) — the framework's
second recurrence over time.

A head keeps a state ``h [P, N]`` (zero before the first token), decays it
by a scalar a token and adds the token's input along its ``B`` (Dao & Gu
2024, "Transformers are SSMs", arXiv:2405.21060; ``mamba_ssm``'s
``ssd_minimal`` / ``mamba_chunk_scan_combined``)::

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T
    y_t = h_t C_t + D x_t

``A < 0`` is a number a head, ``dt_t > 0`` the token's step (softplus
upstream), ``x_t [P]`` the head's input; ``B_t`` and ``C_t`` ``[N]`` are
shared by the heads of a group (one group: by all of them). That
recurrence is the definition, and ``tests/test_ssd_scan.py`` holds this
file to it; token by token it is ``S`` sequential steps.
:func:`ssd_scan` is the chunk-parallel form: inside a chunk of ``Q`` tokens
everything is a product of ``Q``-row matrices, and only the state crosses
chunks. With ``alpha`` the running sum of ``dt A`` inside a chunk and
``h0`` the state that enters it::

    L_ij = exp(alpha_i - alpha_j)                       j <= i, else 0
    Y    = ((C B^T) * L) (dt * X) + exp(alpha) * (C h0^T) + D X
    h1   = exp(alpha_Q) h0 + ((exp(alpha_Q - alpha) dt) * X)^T B

``C B^T`` is one ``[Q, Q]`` product a chunk and group, whatever the number
of heads; the per-head work is the ``[Q, Q]`` decay matrix ``L`` (64 heads
x 16 chunks x 256 x 256 float32 is 268 MB at 4,096 tokens) and two
products with it. The operands of the products are in ``x``'s type with
float32 accumulation; ``dt``, ``alpha``, ``L`` and the carried state are
float32 (``alpha_i - alpha_j`` is masked to ``j <= i`` before the
exponential: above the diagonal it is positive and overflows), and the
states cross the chunks in a ``lax.scan`` of float32 multiply-adds (a
float32 product at the TPU's default precision would round them to
bfloat16). Plain JAX, differentiated by JAX: no kernel yet. The scope
``hvd.ssm.scan`` is around all of it, forward and backward, and the gauge
``hvd_ssm_chunks_last{chunk,heads}`` says at trace time how many chunks a
sequence the step that runs scans.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from ..attribution import SCOPE_SSM_SCAN
from ..profiler import annotate_collective


def ssd_scan(x, dt, a, b, c, d=None, chunk: int = 256):
    """``x [B, S, H, P]``, ``dt [B, S, H]`` (the steps, positive), ``a [H]``
    (negative), ``b`` and ``c`` ``[B, S, G, N]`` with ``G`` dividing ``H``
    (head ``h`` reads group ``h // (H / G)``), ``d [H]`` or ``None`` →
    ``y [B, S, H, P]`` in ``x``'s type, from a zero state. ``S`` must be a
    multiple of ``chunk``."""
    batch, seq, heads, width = x.shape
    groups, state = b.shape[2:]
    if seq % chunk:
        raise ValueError(
            f"ssd_scan: a sequence of {seq} is no multiple of the chunk of "
            f"{chunk}; pad it upstream")
    if heads % groups:
        raise ValueError(
            f"ssd_scan: {heads} heads do not share {groups} groups of B and "
            f"C evenly")
    count, share = seq // chunk, heads // groups
    dtype, f32 = x.dtype, jnp.float32
    _record_chunks(count, chunk, heads)

    def chunks(t):  # [B, S, ...] -> [B, chunks, chunk, ...]
        return t.reshape((batch, count, chunk) + t.shape[2:])

    def product(spec, left, right):
        return jnp.einsum(spec, left.astype(dtype), right.astype(dtype),
                          preferred_element_type=f32)

    with annotate_collective(SCOPE_SSM_SCAN):
        xs = chunks(x).reshape((batch, count, chunk, groups, share, width))
        bs, cs = chunks(b), chunks(c)
        # [B, n, G, R, Q]: a head's tokens along the lanes
        steps = jnp.moveaxis(chunks(dt.astype(f32)), 2, -1).reshape(
            (batch, count, groups, share, chunk))
        rate = a.astype(f32).reshape((groups, share, 1))
        alpha = jnp.cumsum(steps * rate, -1)
        lower = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay = jnp.exp(jnp.where(
            lower, alpha[..., :, None] - alpha[..., None, :], -jnp.inf))
        grow = jnp.exp(alpha)                         # from the chunk's start
        rest = jnp.exp(alpha[..., -1:] - alpha)       # to its end
        kept = grow[..., -1]                          # the whole chunk's decay

        def by_token(t):  # [B, n, G, R, Q] -> [B, n, Q, G, R, 1]
            return jnp.moveaxis(t, -1, 2)[..., None]

        # what a token adds to the state, and to the later tokens' outputs
        stepped = xs.astype(f32) * by_token(steps)
        inside = product("bnigs,bnjgs->bngij", cs, bs)[:, :, :, None] * decay
        out = product("bngrij,bnjgrp->bnigrp", inside, stepped)
        added = product("bnjgrp,bnjgs->bngrps",
                        stepped * by_token(rest), bs)

        def one_chunk(entering, xs):
            added, kept = xs
            return kept[..., None, None] * entering + added, entering

        # the state that enters each chunk: [n, B, G, R, P, N], float32
        _, entering = lax.scan(
            one_chunk, jnp.zeros((batch, groups, share, width, state), f32),
            (jnp.moveaxis(added, 1, 0), jnp.moveaxis(kept, 1, 0)))
        out = out + by_token(grow) * product(
            "bnigs,nbgrps->bnigrp", cs, entering)
        out = out.reshape((batch, seq, heads, width))
        if d is not None:
            out = out + d.astype(f32)[:, None] * x.astype(f32)
        return out.astype(dtype)


def _record_chunks(count: int, chunk: int, heads: int) -> None:
    """At trace time, as ``ops.linear_attention._record_chunks``: the step
    that runs scans this many chunks a sequence."""
    from .. import metrics

    metrics.SSM_CHUNKS_LAST.set(count, chunk=str(chunk), heads=str(heads))
