"""Latent attention's operands in one pass (``models/latent.py``'s rotary
split): the queries, keys and values laid out head-major for the flash
kernels by the same kernels that turn the rotary lanes, forward and back.

A head of latent attention's queries is ``n + r`` lanes (128 kept, 64
turned by RoPE: 192, one and a half lane tiles), so ``[B, S, H * 192] ->
[B, H, S, 192]`` is no view on the chip: XLA copies the array twice to get
it head-major, and with the turn beside it (float32 arithmetic, the pairs'
swap as a product) it writes a float32 copy of the queries and re-tiles
that one too. Here a grid step reads ``[tile, 2 * 192]`` lanes where the
projection wrote them (three whole lane tiles, two heads), turns the last
``r`` lanes of each head in registers and writes the two heads' ``[tile,
192]`` blocks of the head-major array; the keys are written ``[k_n |
turn(k_r)]`` from ``kv_b``'s ``[B, S, H * (n + v)]`` and the one ``k_r``,
the values beside them. The backward kernels are the transposes: they read
``dq``, ``dk``, ``dv`` head-major as the flash backward kernels wrote them,
turn the rotary lanes back and write ``[B, S, H * d]`` where the
projections' backward reads it; ``d k_r`` is summed over the heads in a
float32 accumulator.

**The arithmetic is ``models/latent.py::turn``'s**, forward and backward,
rounding for rounding: a turned lane is ``x * cos + partner(x) * sin`` in
float32 from the stored type and rounded once; its cotangent is ``round(dy *
cos) + partner(round(dy * sin))`` summed in float32 and rounded, which is
what differentiating ``turn`` gives (the two casts' transposes round before
the sum). The partner of lane ``2i`` is ``2i + 1`` and back (the source's
interleaved pairs, ``sin`` carrying the sign): a lane rotate each way and a
select on the lane's parity, exact. The ``n`` lanes that are kept and the
values pass through as they are.

Each pass is a primitive of its own (``kernel_parts.where_lowered``):
the kernels in a program lowered for a TPU (anywhere, interpreted, where
the tests say ``interpret``), the plain form on any other platform; a
recomputed layer's policy sees no ``pallas_call`` whose results it would
keep, so the head-major operands are formed again in the backward pass as
the transposes were. The kernels are named ``mla_rope_heads``: the
benchmark finds the attention kernels by ``flash_attention``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..attribution import SCOPE_MLA_ROPE
from ..profiler import annotate_collective
from .attention import LANES
from .kernel_parts import where_lowered

KERNEL_NAME = "mla_rope_heads"
TOKENS_A_STEP = 512
KV_HEADS_A_STEP = 4


def tokens_a_step(x, heads: int, nope: int, rope: int, v_dim: int):
    """Rows of a grid step for queries ``x [B, S, heads * (nope + rope)]``,
    or ``None`` where the shapes fill no tiles: the kept lanes and the
    values whole lane tiles, a pair of heads whole lane tiles, the rotary
    lanes in pairs inside one tile, an even number of heads and the rows
    whole sublane tiles of ``x``'s type."""
    tile = math.gcd(x.shape[1], TOKENS_A_STEP)
    fits = (nope % LANES == 0 and v_dim % LANES == 0
            and 2 * rope == LANES and heads % 2 == 0
            and tile % (32 // x.dtype.itemsize) == 0)
    return tile if fits else None


def _partner(x):
    """``x [T, 128]`` float32 with lanes ``2i`` and ``2i + 1`` swapped."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane % 2 == 0, pltpu.roll(x, LANES - 1, 1),
                     pltpu.roll(x, 1, 1))


def _turn(x, cos, sin):
    """``turn`` of a whole lane tile ``x [T, 128]``, in float32 (the
    caller rounds, and keeps the half of the lanes that are rotary)."""
    x = x.astype(jnp.float32)
    return x * cos + _partner(x) * sin


def _turn_back(dy, cos, sin):
    """The cotangent of :func:`_turn` rounded to ``dy``'s type, with the
    roundings of ``turn``'s own transpose: each product rounded, the sum
    in float32."""
    f32 = jnp.float32
    dy32 = dy.astype(f32)
    kept = (dy32 * cos).astype(dy.dtype).astype(f32)
    swapped = _partner((dy32 * sin).astype(dy.dtype).astype(f32))
    return kept + swapped


def _queries_kernel(x_ref, cos_ref, sin_ref, o_ref, *, nope):
    """``x_ref [1, T, 2 * d]`` -> ``o_ref [1, 2, T, d]``, ``d = nope + 64``;
    ``cos_ref``, ``sin_ref [T, 128]``: the 64 lanes' tables twice."""
    d = o_ref.shape[-1]
    half = LANES // 2
    cos, sin = cos_ref[...], sin_ref[...]
    # the first head's lanes lie on whole tiles: the tile after its kept
    # lanes holds its rotary lanes and the second head's first kept ones
    o_ref[0, 0, :, :nope] = x_ref[0, :, :nope]
    o_ref[0, 0, :, nope:] = _turn(
        x_ref[0, :, nope:nope + LANES], cos, sin).astype(o_ref.dtype)[
            :, :half]
    # the second head's lie half a tile on: its rotary lanes end the block
    o_ref[0, 1, :, :nope] = x_ref[0, :, d:d + nope]
    o_ref[0, 1, :, nope:] = _turn(
        x_ref[0, :, 2 * d - LANES:], cos, sin).astype(o_ref.dtype)[:, half:]


def _queries_back_kernel(dy_ref, cos_ref, sin_ref, o_ref, *, nope):
    """``dy_ref [1, 2, T, d]`` -> ``o_ref [1, T, 2 * d]``."""
    d = dy_ref.shape[-1]
    dtype = o_ref.dtype
    half = LANES // 2
    # both heads' rotary lanes side by side: one whole tile turned back
    both = jnp.concatenate([dy_ref[0, 0, :, nope:], dy_ref[0, 1, :, nope:]],
                           1)
    both = _turn_back(both, cos_ref[...], sin_ref[...]).astype(dtype)
    o_ref[0, :, :nope] = dy_ref[0, 0, :, :nope]
    o_ref[0, :, nope:d] = both[:, :half]
    o_ref[0, :, d:d + nope] = dy_ref[0, 1, :, :nope]
    o_ref[0, :, d + nope:] = both[:, half:]


def _keys_kernel(up_ref, shared_ref, cos_ref, sin_ref, k_ref, v_ref, *,
                 nope):
    """``up_ref [1, T, heads * (nope + v)]``, ``shared_ref [1, T, 64]`` ->
    ``k_ref [1, heads, T, nope + 64]``, ``v_ref [1, heads, T, v]``."""
    heads, width = k_ref.shape[1], nope + v_ref.shape[-1]
    shared = shared_ref[0]
    turned = _turn(jnp.concatenate([shared, shared], 1), cos_ref[...],
                   sin_ref[...]).astype(k_ref.dtype)[:, :LANES // 2]
    for head in range(heads):
        k_ref[0, head, :, :nope] = up_ref[0, :, head * width:
                                          head * width + nope]
        k_ref[0, head, :, nope:] = turned
        v_ref[0, head] = up_ref[0, :, head * width + nope:
                                (head + 1) * width]


def _keys_back_kernel(dk_ref, dv_ref, cos_ref, sin_ref, up_ref, shared_ref,
                      sum_ref, *, nope):
    """``dk_ref [1, heads, T, nope + 64]``, ``dv_ref [1, heads, T, v]`` ->
    ``up_ref [1, T, heads * (nope + v)]`` and, after the last heads of a
    tile of tokens, ``shared_ref [1, T, 64]``; ``sum_ref [T, 64]`` float32
    holds the heads' sum till then."""
    heads, width = dk_ref.shape[1], nope + dv_ref.shape[-1]
    step = pl.program_id(2)

    @pl.when(step == 0)
    def _():
        sum_ref[...] = jnp.zeros_like(sum_ref)

    total = sum_ref[...]
    for head in range(heads):
        up_ref[0, :, head * width:head * width + nope] = dk_ref[
            0, head, :, :nope]
        up_ref[0, :, head * width + nope:(head + 1) * width] = dv_ref[0, head]
        total = total + dk_ref[0, head, :, nope:].astype(jnp.float32)
    sum_ref[...] = total

    @pl.when(step == pl.num_programs(2) - 1)
    def _():
        summed = total.astype(shared_ref.dtype)  # the plain sum's rounding
        shared_ref[0] = _turn_back(
            jnp.concatenate([summed, summed], 1), cos_ref[...],
            sin_ref[...]).astype(shared_ref.dtype)[:, :LANES // 2]


def _tokens_major(tile, lanes):
    return pl.BlockSpec((1, tile, lanes), lambda b, s, h: (b, s, h))


def _head_major(step, tile, lanes):
    return pl.BlockSpec((1, step, tile, lanes), lambda b, s, h: (b, h, s, 0))


def _every_head(tile, lanes):
    return pl.BlockSpec((1, tile, lanes), lambda b, s, h: (b, s, 0))


def _call(kernel, operands, specs, results, out_specs, grid, *, nope,
          interpret, scratch=(), **_):
    """One grid over ``(batch, tiles of tokens, steps of heads)``, the
    heads innermost (a tile's tables and its shared key are fetched once);
    the two ``[tile, 128]`` tables follow ``operands``."""
    tile = specs[0].block_shape[-2]
    tables = pl.BlockSpec((tile, LANES), lambda b, s, h: (s, 0))
    return pl.pallas_call(
        functools.partial(kernel, nope=nope), grid=grid,
        in_specs=list(specs) + [tables, tables], out_specs=out_specs,
        out_shape=results, scratch_shapes=list(scratch),
        interpret=interpret, name=KERNEL_NAME)(*operands)


def _queries_by_kernel(x, cos, sin, *, heads, tile, **how):
    batch, seq, lanes = x.shape
    d = lanes // heads
    return _call(
        _queries_kernel, (x, cos, sin), [_tokens_major(tile, 2 * d)],
        [jax.ShapeDtypeStruct((batch, heads, seq, d), x.dtype)],
        [_head_major(2, tile, d)], (batch, seq // tile, heads // 2), **how)


def _queries_back_by_kernel(dy, cos, sin, *, heads, tile, **how):
    batch, _, seq, d = dy.shape
    return _call(
        _queries_back_kernel, (dy, cos, sin), [_head_major(2, tile, d)],
        [jax.ShapeDtypeStruct((batch, seq, heads * d), dy.dtype)],
        [_tokens_major(tile, 2 * d)], (batch, seq // tile, heads // 2),
        **how)


def _keys_by_kernel(up, shared, cos, sin, *, heads, nope, tile, **how):
    batch, seq, lanes = up.shape
    width, rope = lanes // heads, shared.shape[-1]
    step = math.gcd(heads, KV_HEADS_A_STEP)
    return _call(
        _keys_kernel, (up, shared, cos, sin),
        [_tokens_major(tile, step * width), _every_head(tile, rope)],
        [jax.ShapeDtypeStruct((batch, heads, seq, lanes), up.dtype)
         for lanes in (nope + rope, width - nope)],
        [_head_major(step, tile, nope + rope),
         _head_major(step, tile, width - nope)],
        (batch, seq // tile, heads // step), nope=nope, **how)


def _keys_back_by_kernel(dk, dv, cos, sin, *, heads, nope, tile, **how):
    batch, _, seq, d = dk.shape
    v_dim, rope = dv.shape[-1], d - nope
    step = math.gcd(heads, KV_HEADS_A_STEP)
    return _call(
        _keys_back_kernel, (dk, dv, cos, sin),
        [_head_major(step, tile, d), _head_major(step, tile, v_dim)],
        [jax.ShapeDtypeStruct((batch, seq, heads * (nope + v_dim)), dk.dtype),
         jax.ShapeDtypeStruct((batch, seq, rope), dk.dtype)],
        [_tokens_major(tile, step * (nope + v_dim)), _every_head(tile, rope)],
        (batch, seq // tile, heads // step), nope=nope,
        scratch=[pltpu.VMEM((tile, rope), jnp.float32)], **how)


def _pairs_swapped(x):
    """Lanes ``2i`` and ``2i + 1`` of the last axis swapped."""
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    return pairs[..., ::-1].reshape(x.shape)


def _rotary_tables(x, cos, sin):
    """The ``[S, 128]`` tables' first ``r`` columns, broadcast over what
    lies between the positions and the lanes of ``x [B, S, ..., r]``."""
    rope = x.shape[-1]
    return (t[:, :rope].reshape((-1,) + (1,) * (x.ndim - 3) + (rope,))
            for t in (cos, sin))


def _turn_plain(x, cos, sin):
    """The kernels' :func:`_turn` of rotary lanes alone, ``x [B, S, ...,
    r]``, the swap a reversal of the pairs."""
    f32 = jnp.float32
    cos, sin = _rotary_tables(x, cos, sin)
    return (x.astype(f32) * cos
            + _pairs_swapped(x).astype(f32) * sin).astype(x.dtype)


def _turn_back_plain(dy, cos, sin):
    """The kernels' :func:`_turn_back`, as :func:`_turn_plain`."""
    f32 = jnp.float32
    cos, sin = _rotary_tables(dy, cos, sin)
    dy32 = dy.astype(f32)
    kept = (dy32 * cos).astype(dy.dtype).astype(f32)
    swapped = _pairs_swapped((dy32 * sin).astype(dy.dtype)).astype(f32)
    return (kept + swapped).astype(dy.dtype)


def _heads_apart(x, heads):
    return x.reshape(x.shape[:2] + (heads, -1))


def _heads_together(x):
    return x.reshape(x.shape[:2] + (-1,))


def _queries_plain(x, cos, sin, *, heads, nope, **_):
    x = _heads_apart(x, heads)
    return [jnp.concatenate([
        x[..., :nope], _turn_plain(x[..., nope:], cos, sin)],
        -1).transpose(0, 2, 1, 3)]


def _queries_back_plain(dy, cos, sin, *, nope, **_):
    dy = dy.transpose(0, 2, 1, 3)
    return [_heads_together(jnp.concatenate([
        dy[..., :nope], _turn_back_plain(dy[..., nope:], cos, sin)], -1))]


def _keys_plain(up, shared, cos, sin, *, heads, nope, **_):
    up = _heads_apart(up, heads)
    turned = _turn_plain(shared, cos, sin)[:, :, None]
    keys = jnp.concatenate([up[..., :nope], jnp.broadcast_to(
        turned, up.shape[:3] + turned.shape[3:])], -1)
    return [keys.transpose(0, 2, 1, 3), up[..., nope:].transpose(0, 2, 1, 3)]


def _keys_back_plain(dk, dv, cos, sin, *, nope, **_):
    dk, dv = dk.transpose(0, 2, 1, 3), dv.transpose(0, 2, 1, 3)
    summed = dk[..., nope:].astype(jnp.float32).sum(2).astype(dk.dtype)
    return [_heads_together(jnp.concatenate([dk[..., :nope], dv], -1)),
            _turn_back_plain(summed, cos, sin)]


def _head_major_like(x, lanes, heads):
    return x.update(shape=(x.shape[0], heads, x.shape[1], lanes))


def _tokens_major_like(x, lanes):
    return x.update(shape=(x.shape[0], x.shape[2], x.shape[1] * lanes))


_queries_p = where_lowered(
    "hvd_mla_rope_queries",
    lambda x, cos, sin, *, heads, **_: [
        _head_major_like(x, x.shape[-1] // heads, heads)],
    _queries_by_kernel, _queries_plain)
_queries_back_p = where_lowered(
    "hvd_mla_rope_queries_backward",
    lambda dy, cos, sin, **_: [_tokens_major_like(dy, dy.shape[-1])],
    _queries_back_by_kernel, _queries_back_plain)
_keys_p = where_lowered(
    "hvd_mla_rope_keys",
    lambda up, shared, cos, sin, *, heads, nope, **_: [
        _head_major_like(up, nope + shared.shape[-1], heads),
        _head_major_like(up, up.shape[-1] // heads - nope, heads)],
    _keys_by_kernel, _keys_plain)
_keys_back_p = where_lowered(
    "hvd_mla_rope_keys_backward",
    lambda dk, dv, cos, sin, *, nope, **_: [
        _tokens_major_like(dk, nope + dv.shape[-1]),
        dk.update(shape=(dk.shape[0], dk.shape[2], dk.shape[-1] - nope))],
    _keys_back_by_kernel, _keys_back_plain)


def _bound(primitive, *operands, **how):
    with annotate_collective(SCOPE_MLA_ROPE):
        return primitive.bind(*operands, **how)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _turned_queries(x, cos, sin, how):
    return _bound(_queries_p, x, cos, sin, **dict(how))[0]


def _queries_forward(x, cos, sin, how):
    return _turned_queries(x, cos, sin, how), (cos, sin)


def _queries_backward(how, tables, dy):
    return (*_bound(_queries_back_p, dy, *tables, **dict(how)), None, None)


_turned_queries.defvjp(_queries_forward, _queries_backward)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _turned_keys(up, shared, cos, sin, how):
    return tuple(_bound(_keys_p, up, shared, cos, sin, **dict(how)))


def _keys_forward(up, shared, cos, sin, how):
    return _turned_keys(up, shared, cos, sin, how), (cos, sin)


def _keys_backward(how, tables, bars):
    return (*_bound(_keys_back_p, *bars, *tables, **dict(how)), None, None)


_turned_keys.defvjp(_keys_forward, _keys_backward)


def head_major_operands(q, up, shared, cos, sin, heads: int, nope: int,
                        tile: int, interpret: bool = False):
    """Latent attention's ``(q [B, H, S, n + r], k [B, H, S, n + r], v [B,
    H, S, v])`` for the flash kernels from ``q [B, S, H * (n + r)]`` and
    ``up [B, S, H * (n + v)]`` as ``q_b`` and ``kv_b`` wrote them and the
    one ``shared [B, S, r]`` key every head reads, the last ``r`` lanes of
    every head's query and ``shared`` turned by ``cos``, ``sin [S, r]``
    (float32, the sign in ``sin``: ``latent.rotary_split_tables``'s rotary
    columns): one pass over each array, forward and backward (a
    ``custom_vjp`` each for the queries and for the keys with the values),
    at shapes :func:`tokens_a_step` accepts, ``tile`` its answer."""
    how = tuple(dict(heads=heads, nope=nope, tile=tile,
                     interpret=interpret).items())
    with annotate_collective(SCOPE_MLA_ROPE):
        # a whole lane tile's: two heads' rotary lanes side by side
        cos, sin = (jnp.concatenate([t, t], -1) for t in (cos, sin))
    return (_turned_queries(q, cos, sin, how),
            *_turned_keys(up, shared, cos, sin, how))
