"""Linear attention by the gated delta rule — the framework's first
recurrence over time.

A head keeps a state ``S [d_k, d_v]`` (zero before the first token) and at
every token decays it, corrects what it holds under the token's key
towards the token's value, and reads it with the query (Yang et al. 2024,
"Gated Delta Networks", arXiv:2412.06464; ``flash-linear-attention``'s
``gated_delta_rule``)::

    S' = exp(g_t) * S
    S_t = S' + beta_t * k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

``g_t <= 0`` is the log of the decay and ``beta_t`` the step of the
correction (in (0, 2) where negative eigenvalues are allowed). That
recurrence is the definition, and ``tests/test_linear_attention.py`` holds
this file to it; run token by token it is ``S`` sequential steps, which on
a TPU measures the loop. :func:`gated_delta_rule` is the chunk-parallel
form: inside a chunk of ``C`` tokens everything is a product of ``C``-row
matrices on the MXU, and only the state crosses chunks, in one
``lax.scan`` of ``S / C`` steps. With ``gamma_i`` the running sum of ``g``
inside the chunk and ``S0`` the state that enters it::

    A_ij = beta_i (k_i . k_j) exp(gamma_i - gamma_j)     j < i, else 0
    (I + A) [U | W] = beta * [V | exp(gamma) * K]        forward substitution
    V' = U - W S0
    O  = (Q * exp(gamma)) S0 + tril(Q K^T * exp(gamma_i - gamma_j)) V'
    S1 = exp(gamma_C) S0 + (K * exp(gamma_C - gamma))^T V'

The operands of the chunk products are in the inputs' type with float32
accumulation; ``gamma``, the decays, the triangular solve and the carried
state are float32 (``gamma_i - gamma_j`` is masked to ``j <= i`` before
the exponential: above the diagonal it is positive and overflows). Plain
JAX, differentiated by JAX: no kernel yet. The scope
``hvd.linattn.scan`` is around all of it, forward and backward, and the
gauge ``hvd_linattn_chunks_last{chunk,heads_here}`` says at trace time how
many chunks a sequence the step that runs scans.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..attribution import SCOPE_LINATTN_SCAN
from ..profiler import annotate_collective


def short_conv(x, w):
    """Depth-wise causal convolution over time: ``x [B, S, channels]``,
    ``w [channels, width]`` → ``y_t = Σ_i w[:, i] · x_{t - (width - 1) +
    i}`` with zeros to the left of the sequence (``w[:, -1]`` weighs the
    token itself; ``torch.nn.Conv1d(groups=channels, padding=width - 1)``
    cut to the sequence). Float32 accumulation, ``x``'s type out."""
    width, seq = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(padded[:, i:i + seq].astype(jnp.float32) * w[:, i]
              for i in range(width))
    return out.astype(x.dtype)


def gated_delta_rule(q, k, v, g, beta, chunk: int = 64):
    """``q``, ``k`` ``[B, S, H, d_k]``, ``v [B, S, H, d_v]``, ``g`` and
    ``beta`` ``[B, S, H]`` → ``o [B, S, H, d_v]`` in ``v``'s type, from a
    zero state. ``q`` and ``k`` come as the rule reads them (normalised
    and scaled by the caller). ``S`` must be a multiple of ``chunk``."""
    batch, seq, heads, d_v = v.shape
    if seq % chunk:
        raise ValueError(
            f"gated_delta_rule: a sequence of {seq} is no multiple of the "
            f"chunk of {chunk}; pad it upstream")
    count, dtype, f32 = seq // chunk, v.dtype, jnp.float32
    _record_chunks(count, chunk, heads)

    def chunks(x):  # [B, S, H, ...] -> [B, H, chunks, chunk, ...]
        x = x.reshape((batch, count, chunk) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    def product(spec, a, b):
        return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                          preferred_element_type=f32)

    with annotate_collective(SCOPE_LINATTN_SCAN):
        q, k, v = chunks(q), chunks(k), chunks(v)
        beta = chunks(beta.astype(f32))[..., None]
        gamma = jnp.cumsum(chunks(g.astype(f32)), -1)      # [B, H, N, C]
        lower = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay = jnp.exp(jnp.where(
            lower, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
        grow = jnp.exp(gamma)[..., None]                   # from the chunk's start
        rest = jnp.exp(gamma[..., -1:] - gamma)[..., None]  # to its end

        a = jnp.tril(beta * product("bhnic,bhnjc->bhnij", k, k) * decay, -1)
        solved = lax.linalg.triangular_solve(
            a, beta * jnp.concatenate([v.astype(f32), k * grow], -1),
            left_side=True, lower=True, unit_diagonal=True)
        u, w = solved[..., :d_v], solved[..., d_v:]
        inside = product("bhnic,bhnjc->bhnij", q, k) * decay

        def one_chunk(state, xs):
            u, w, inside, q_in, k_out, kept = xs
            new = u - product("bhck,bhkv->bhcv", w, state)
            out = (product("bhck,bhkv->bhcv", q_in, state)
                   + product("bhij,bhjv->bhiv", inside, new))
            state = kept * state + product("bhck,bhcv->bhkv", k_out, new)
            return state, out.astype(dtype)

        # (rest and the chunk's whole decay are decay's last row and grow's
        # last entry; sliced out of those the v5e's step took 1.1 ms longer)
        per_chunk = (u, w, inside, q * grow, k * rest,
                     jnp.exp(gamma[..., -1])[..., None, None])
        state = jnp.zeros((batch, heads, k.shape[-1], d_v), f32)
        _, out = lax.scan(one_chunk, state, jax.tree.map(
            lambda x: jnp.moveaxis(x, 2, 0), per_chunk))
        # [N, B, H, C, d_v] -> [B, S, H, d_v]
        return out.transpose(1, 0, 3, 2, 4).reshape(batch, seq, heads, d_v)


def _record_chunks(count: int, chunk: int, heads: int) -> None:
    """At trace time, as ``models.olmoe._record_slots``: the step that
    runs scans this many chunks a sequence."""
    from .. import metrics

    metrics.LINATTN_CHUNKS_LAST.set(
        count, chunk=str(chunk), heads_here=str(heads))
