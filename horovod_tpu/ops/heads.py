"""A function of each head of tokens-major arrays, a head after another where
the arrays lie.

A projection writes ``[B, S, heads * D]``: a head is ``D`` lanes of every
row, a 128-lane block of its own at ``D = 128``. What works on a head alone
(RoPE, a sum over a head's lanes) is written in JAX on a ``[B, S, heads,
D]`` view, and on a TPU that view is another tiling of the array (``heads``
rows of ``D`` lanes a tile where the projection wrote 8 positions of 128):
XLA copies the array on the way in and on the way out, forward, recomputed
and backward (PERF.md, PR 40). ``map_heads`` runs the function in a loop
over the heads instead, on slices of whole lane blocks read and written in
place: one fused instruction a head, whose code exists once (written out a
head in Python, SDAR's step was 0.35 GB of code in HBM), with what every
head shares kept where the loop finds it. Its gradient is the same loop
over the function's own ``jax.vjp``, a head at a time from the arguments as
they were given: nothing is kept a head, and nothing is stacked.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _block(array, index, width):
    return jax.lax.dynamic_slice_in_dim(array, index * width, width,
                                        array.ndim - 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _map_heads(fn, heads, rows_out, blocks, constants):
    width = blocks[0].shape[-1] // heads

    def one(h):
        return fn(*(_block(a, h, width) for a in blocks), *constants)

    first = jax.eval_shape(one, 0)
    if rows_out:
        out = jnp.zeros(first.shape[:1] + (heads,) + first.shape[1:],
                        first.dtype)
        return jax.lax.fori_loop(0, heads, lambda h, out: (
            jax.lax.dynamic_update_index_in_dim(out, one(h), h, 1)), out)
    out_width = first.shape[-1]
    out = jnp.zeros(first.shape[:-1] + (heads * out_width,), first.dtype)
    return jax.lax.fori_loop(0, heads, lambda h, out: (
        jax.lax.dynamic_update_slice_in_dim(out, one(h), h * out_width,
                                            out.ndim - 1)), out)


def _map_heads_fwd(fn, heads, rows_out, blocks, constants):
    return _map_heads(fn, heads, rows_out, blocks, constants), (blocks,
                                                                constants)


def _map_heads_bwd(fn, heads, rows_out, residuals, g):
    blocks, constants = residuals
    width = blocks[0].shape[-1] // heads

    def one(h, d_blocks):
        _, pull_back = jax.vjp(
            lambda *head: fn(*head, *constants),
            *(_block(a, h, width) for a in blocks))
        if rows_out:
            got = pull_back(jax.lax.dynamic_index_in_dim(g, h, 1, False))
        else:
            got = pull_back(_block(g, h, g.shape[-1] // heads))
        return tuple(
            jax.lax.dynamic_update_slice_in_dim(total, part, h * width,
                                                total.ndim - 1)
            for total, part in zip(d_blocks, got))

    d_blocks = jax.lax.fori_loop(
        0, heads, one, tuple(jnp.zeros_like(a) for a in blocks))
    return d_blocks, tuple(None for _ in constants)


_map_heads.defvjp(_map_heads_fwd, _map_heads_bwd)


def map_heads(fn, heads: int, blocks, constants=(), rows_out: bool = False):
    """``fn(*(head h of each of blocks), *constants)`` for ``h = 0 .. heads
    - 1``, the results side by side.

    ``blocks``: arrays ``[..., heads * width]``, tokens major, of which
    head ``h`` reads lanes ``h * width`` to ``(h + 1) * width``.
    ``constants`` go to every head whole and get no gradient (tables of
    positions). The results, all of one shape: ``[..., w]`` become ``[...,
    heads * w]``, or with ``rows_out`` ``[B, ...]`` become ``[B, heads,
    ...]`` (a row a head, as a log-sum-exp lies).

    A loop, forward and backward (the module's docstring says why), so
    ``fn`` is traced once however many heads there are; it may close over
    nothing that is traced. Differentiable in ``blocks``. A trip of the
    loop costs about 30 us on a v5e whatever it moves (32 trips over 4 MB
    each took 1 ms), and the loop unrolled to four heads a trip was slower
    still (SmallThinker's step 469.6 ms against 466.5: PERF.md, PR 40).
    """
    blocks = tuple(blocks)
    widths = {a.shape[-1] for a in blocks}
    if len(widths) != 1 or widths.pop() % heads:
        raise ValueError(
            f"{heads} heads cannot share arrays of "
            f"{[a.shape[-1] for a in blocks]} lanes evenly")
    return _map_heads(fn, heads, rows_out, blocks, tuple(constants))
