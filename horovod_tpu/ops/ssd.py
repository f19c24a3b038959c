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
float32 accumulation; ``dt``, ``alpha``, ``L``, ``grow = exp(alpha)``,
``rest = exp(alpha_Q - alpha)`` and the carried state are float32
(``alpha_i - alpha_j`` is masked to ``j <= i`` before the exponential:
above the diagonal it is positive and overflows), and the state crosses
the chunks as float32 multiply-adds (a float32 product at the TPU's
default precision would round it to bfloat16).

Two forms of it, one function (:func:`ssd_scan`), chosen by what the code
can observe, the shapes and the platform the program is lowered for:

* :func:`_chunk_form`, plain JAX differentiated by JAX: the decay matrices
  of every chunk and head at once, the states across the chunks in a
  ``lax.scan``. What any platform but a TPU runs, what a shape that fills
  no tile (:func:`_heads_a_step`: the toys) traces anywhere, and what the
  tests hold the kernels to.
* :func:`ssd_scan_kernel`, two Pallas kernels named ``ssd_chunk_scan``
  joined by a ``jax.custom_vjp``, in a program lowered for a TPU (each
  pass a primitive whose lowering the platform chooses:
  ``kernel_parts.where_lowered``). The grid is ``(B, chunks, H / 8)``:
  a sequence's chunks in order (the backward kernel's index maps turn
  them), the heads in steps of eight that share a group innermost. **In
  VMEM**: a step's ``x [Q, 8 P]`` read where it lies in ``[B, S, H P]``,
  its group's ``B`` and ``C [Q, N]`` from ``[B, S, G N]`` (the block stays
  while the steps stay in the group), the steps and ``alpha [8, Q]``
  heads-major (tokens along the lanes, turned once a step to a token a
  row), ``C B^T`` once a group, a head's ``L`` and ``(C B^T * L)(dt X)``
  (two heads of 64 on a lane block share the product's operand and a
  select), and every head's state ``[N, P]`` (``[H / 8, N, 8 P]`` float32
  scratch, 2 MB), read (``C h0^T``) and stepped (``B^T (rest dt X)``) for
  the step's eight heads in one product each. **In HBM**: ``y [B, S, H
  P]`` and, where a backward pass follows (the forward pass of a
  recomputed layer writes none: a rule of its own drops the unread
  result), the float32 states that enter each chunk, ``[B, chunks, N, H
  P]``: with the operands the backward kernel's residuals; it forms ``L``,
  ``C B^T`` and the factors again, carries the states' cotangent in
  scratch, and sums a group's ``dB`` and ``dC`` over its heads in VMEM
  (the heads are innermost, the group's last step makes the two ``[Q, Q]
  x [Q, N]`` products once). No ``L``, no ``inside``, no ``stepped``, no
  loop and no re-layout copy reach HBM. ``alpha`` is summed outside the
  kernels, a float32 product with a triangle of ones at
  ``Precision.HIGHEST`` that JAX differentiates (:func:`_running_sum`; as
  ``jnp.cumsum`` it is a ``reduce-window``). Same rounding points as the
  plain form; the sums' order differs.

The scope ``hvd.ssm.scan`` is around all of either form, forward and
backward. The program says which form it holds: the primitive
``hvd_ssd_chunk_scan`` in its jaxpr where the shapes fill the tiles, the
kernels' name in the text lowered for a TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.interpreters import partial_eval as pe

from ..attribution import SCOPE_SSM_SCAN
from ..profiler import annotate_collective
from .kernel_parts import (MASKED, NT, TN, chunk_grid_call, dot, running_sum,
                           where_lowered)


def ssd_scan(x, dt, a, b, c, d=None, chunk: int = 256):
    """``x [B, S, H, P]``, ``dt [B, S, H]`` (the steps, positive), ``a [H]``
    (negative), ``b`` and ``c`` ``[B, S, G, N]`` with ``G`` dividing ``H``
    (head ``h`` reads group ``h // (H / G)``), ``d [H]`` or ``None`` →
    ``y [B, S, H, P]`` in ``x``'s type, from a zero state. ``S`` must be a
    multiple of ``chunk``. Where the shapes fill a TPU's tiles
    (:func:`_heads_a_step`) the chunk form is :func:`ssd_scan_kernel`'s in
    a program lowered for a TPU; :func:`_chunk_form` anywhere else."""
    seq, heads = x.shape[1:3]
    groups = b.shape[2]
    if seq % chunk:
        raise ValueError(
            f"ssd_scan: a sequence of {seq} is no multiple of the chunk of "
            f"{chunk}; pad it upstream")
    if heads % groups:
        raise ValueError(
            f"ssd_scan: {heads} heads do not share {groups} groups of B and "
            f"C evenly")
    if _heads_a_step(x, b, chunk):
        skip = jnp.zeros((heads,), jnp.float32) if d is None else d
        return ssd_scan_kernel(x, dt, a, b, c, skip, chunk)
    return _chunk_form(x, dt, a, b, c, d, chunk)


def _chunk_form(x, dt, a, b, c, d, chunk: int):
    """The chunk form in plain JAX, differentiated by JAX: what any
    platform but a TPU runs, what a shape that fills no tile traces
    anywhere, and what the tests hold the kernels to."""
    batch, seq, heads, width = x.shape
    groups, state = b.shape[2:]
    count, share = seq // chunk, heads // groups
    dtype, f32 = x.dtype, jnp.float32

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


# The kernels' name: not ``flash_attention``, by which the benchmark finds
# the attention kernels. XLA names the custom call's instruction after it.
SCAN_KERNEL_NAME = "ssd_chunk_scan"
SCAN_HEADS_A_STEP = 8  # of a grid step: the sublanes of a float32 tile


def _heads_a_step(x, b, chunk: int) -> int:
    """The heads a grid step of the kernels takes, or 0 where the shapes
    fill no tile and the plain form is traced: the step's heads share a
    group and their lanes are whole 128-lane blocks of ``x [B, S, H * P]``
    in which a head does not straddle a block, ``N`` is whole lane blocks
    of ``b`` and ``c``, and ``chunk`` whole lane blocks of the steps
    ``[B, H, S]``."""
    heads, width = x.shape[2:]
    groups, state = b.shape[2:]
    step = SCAN_HEADS_A_STEP
    fills = ((heads // groups) % step == 0 and (step * width) % 128 == 0
             and (128 % width == 0 or width % 128 == 0)
             and state % 128 == 0 and chunk % 128 == 0)
    return step if fills else 0


def _tiles(heads: int, width: int):
    """``(lanes, first head, heads)`` of each lane tile of a grid step's
    ``heads * width`` lanes: a 128-lane block of several narrow heads, or a
    wide head's whole blocks. The kernels work a tile at a time, so that
    what lies between two products stays near the registers."""
    tile = max(width, 128)
    per = tile // width
    return [(slice(t * tile, (t + 1) * tile), t * per, per)
            for t in range(heads // per)]


def _on_lanes(cols, first: int, per: int, width: int):
    """``cols [Q, 128]``: columns ``first .. first + per`` written along
    their heads' ``width`` lanes of one tile, ``[Q, per * width]``: one
    lane gather a register, whatever the heads a tile."""
    rows = cols.shape[0]
    if per == 1:
        return jnp.broadcast_to(cols[:, first:first + 1], (rows, width))
    lane = lax.broadcasted_iota(jnp.int32, cols.shape, 1)
    return jnp.take_along_axis(cols, first + lane // width, axis=1)


def _off_lanes(wide, per: int, width: int):
    """The sums of a tile ``wide [rows, per * width]`` over each head's
    lanes, a head a row: ``[per, rows]``. Turned first, so that a head's
    lanes are whole sublane tiles summed register by register (a sum along
    the lanes is a pass through the XLU a register and a mask a head)."""
    return wide.T.reshape(per, width, wide.shape[0]).sum(1)


def _factors(steps_ref, alpha_ref):
    """A grid step's ``alpha [R, Q]`` (tokens along the lanes, as its
    block lies) and ``cols [Q, 128]``, a token a row: column ``n * R + r``
    is head ``r`` of the steps (``n`` = 0), ``alpha`` (1), ``grow`` (2) and
    ``rest`` (3): all float32, exponentials of nothing positive."""
    steps, alpha = steps_ref[...], alpha_ref[...]
    heads, size = alpha.shape
    rows = [steps, alpha, jnp.exp(alpha),
            jnp.exp(alpha[:, size - 1:size] - alpha),
            jnp.zeros((128 - 4 * heads, size), jnp.float32)]
    return alpha, jnp.concatenate(rows, 0).T


def _decay(alpha, cols, r: int, lower):
    """Head ``r``'s ``L [Q, Q]``, masked before the exponential."""
    heads = alpha.shape[0]
    return jnp.exp(jnp.where(
        lower, cols[:, heads + r:heads + r + 1] - alpha[r:r + 1, :], MASKED))


def _lower_triangle(size: int):
    return (lax.broadcasted_iota(jnp.int32, (size, size), 0)
            >= lax.broadcasted_iota(jnp.int32, (size, size), 1))


def _head_lanes(tile, k: int, per: int, width: int, other):
    """Head ``k``'s lanes of ``tile [Q, per * width]``, ``other``'s
    elsewhere."""
    if per == 1:
        return tile
    lane = lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    return jnp.where((lane >= k * width) & (lane < (k + 1) * width), tile,
                     other)


def _scan_forward_kernel(x_ref, steps_ref, alpha_ref, b_ref, c_ref, skip_ref,
                         y_ref, *rest, width, per_group):
    """One chunk of the ``R`` heads of a grid step, which share a group:
    ``x [Q, R * P]`` where it lies in ``[B, S, H * P]``, the group's ``b``
    and ``c [Q, N]``, the steps and ``alpha [R, Q]``. ``C B^T`` once a
    group (``cb_ref``); then a lane tile at a time (:func:`_tiles`): a
    head's ``L`` and ``(C B^T * L)(dt X)``, the heads of a tile sharing
    the product's operand and a select, and the tile of the state ``[N, R *
    P]`` (a head's ``h^T``; ``state_ref`` across the chunks) read (``C
    h0^T``) and stepped (``B^T (rest dt X)``). The last of ``rest`` but
    the scratch, where there is one, takes the state that enters the
    chunk."""
    *enter_ref, state_ref, cb_ref = rest
    n, h = pl.program_id(1), pl.program_id(2)
    heads, size = alpha_ref.shape
    dtype, f32 = x_ref.dtype, jnp.float32

    @pl.when(n == 0)
    def _():
        state_ref[h] = jnp.zeros(state_ref.shape[1:], state_ref.dtype)

    @pl.when(h % per_group == 0)
    def _():
        cb_ref[...] = dot(c_ref[...], b_ref[...], NT)

    alpha, cols = _factors(steps_ref, alpha_ref)
    lower = _lower_triangle(size)
    for lanes, first, per in _tiles(heads, width):
        steps, grow, rest = (_on_lanes(cols, n * heads + first, per, width)
                             for n in (0, 2, 3))
        x = x_ref[:, lanes].astype(f32)
        stepped = x * steps
        operand = stepped.astype(dtype)
        entering = state_ref[h, :, lanes]
        within = jnp.zeros_like(x)
        for k in range(per):
            inside = (cb_ref[...] * _decay(alpha, cols, first + k, lower)
                      ).astype(dtype)
            within = _head_lanes(dot(inside, operand), k, per, width, within)
        y_ref[:, lanes] = (
            within + grow * dot(c_ref[...], entering.astype(dtype))
            + skip_ref[:, lanes] * x).astype(y_ref.dtype)
        for ref in enter_ref:
            ref[:, lanes] = entering.astype(ref.dtype)
        state_ref[h, :, lanes] = (grow[size - 1:size] * entering + dot(
            b_ref[...], (stepped * rest).astype(dtype), TN)).astype(
                state_ref.dtype)


def _scan_backward_kernel(x_ref, steps_ref, alpha_ref, b_ref, c_ref, skip_ref,
                          enter_ref, y_bar_ref, x_bar_ref, b_bar_ref,
                          c_bar_ref, steps_bar_ref, alpha_bar_ref,
                          skip_bar_ref, ahead_ref, cb_ref, cb_bar_ref,
                          b_sum_ref, c_sum_ref, *, width, per_group):
    """The same tiles, the chunks last to first (the index maps turn
    them), with ``dy``: ``ahead_ref [N, R * P]`` a head block is the
    cotangent of the state that leaves the chunk. ``L``, ``C B^T`` and the
    factors are formed again; the state that entered comes from HBM. The
    group's sums over its heads live in VMEM: ``cb_bar_ref = sum_heads (dY
    (dt X)^T * L)`` and the two state terms of ``dB`` and ``dC`` in
    ``b_sum_ref`` / ``c_sum_ref`` across the group's grid steps (the
    heads are the grid's innermost dimension and the blocks of ``dB`` and
    ``dC`` stay where they are while the group lasts), and the group's last
    step makes the two ``[Q, Q] x [Q, N]`` products once and writes both.
    A token's ``d(dt)``, ``d(alpha)`` and ``dD`` terms are sums over a
    head's lanes, written a head a row (:func:`_off_lanes`). Cotangents of
    ``dtype`` operands go into their products rounded to ``dtype``, as the
    plain form's transposed products take them."""
    n, h = pl.program_id(1), pl.program_id(2)
    heads, size = alpha_ref.shape
    dtype, f32 = x_ref.dtype, jnp.float32

    @pl.when(n == 0)
    def _():
        ahead_ref[h] = jnp.zeros(ahead_ref.shape[1:], ahead_ref.dtype)

    @pl.when(h % per_group == 0)
    def _():
        cb_ref[...] = dot(c_ref[...], b_ref[...], NT)
        cb_bar_ref[...] = jnp.zeros_like(cb_bar_ref)
        b_sum_ref[...] = jnp.zeros_like(b_sum_ref)
        c_sum_ref[...] = jnp.zeros_like(c_sum_ref)

    alpha, cols = _factors(steps_ref, alpha_ref)
    lower = _lower_triangle(size)
    cb_bar, b_sum, c_sum = cb_bar_ref[...], b_sum_ref[...], c_sum_ref[...]
    for lanes, first, per in _tiles(heads, width):
        steps, grow, rest = (_on_lanes(cols, n * heads + first, per, width)
                             for n in (0, 2, 3))
        x, bar = x_ref[:, lanes].astype(f32), y_bar_ref[:, lanes]
        y_bar = bar.astype(f32)
        stepped = x * steps
        operand = stepped.astype(dtype)
        leaving = stepped * rest
        entering, ahead = enter_ref[:, lanes], ahead_ref[h, :, lanes]
        entered, left, led = (t.astype(dtype)
                              for t in (entering, leaving, ahead))
        kept = grow[size - 1:size]
        # y = within + grow * (C h0^T) + D x: the entering state's term
        read = grow * dot(c_ref[...], entered)
        read_bar = (grow * y_bar).astype(dtype)
        c_sum = c_sum + dot(read_bar, entered, NT)
        ahead_ref[h, :, lanes] = (
            kept * ahead + dot(c_ref[...], read_bar, TN)).astype(
                ahead_ref.dtype)
        # h1 = kept * h0 + B^T leaving
        leaving_bar = dot(b_ref[...], led)
        b_sum = b_sum + dot(left, led, NT)
        # within = (C B^T * L) stepped, a head at a time. alpha_i adds and
        # alpha_j takes away the SAME pair term G_ij: both sums are read
        # off one float32 matrix (G^T - G down the rows), because alpha's
        # cotangent is summed backwards over the chunk and two roundings of
        # one total do not cancel there (as rest's terms and their sum,
        # below)
        within_bar, pairs = jnp.zeros_like(x), []
        for k in range(per):
            decay = _decay(alpha, cols, first + k, lower)
            inside = (cb_ref[...] * decay).astype(dtype)
            inside_bar = dot(
                _head_lanes(bar, k, per, width, jnp.zeros_like(bar)),
                operand, NT) * decay
            cb_bar = cb_bar + inside_bar
            pair = inside_bar * cb_ref[...]
            pairs.append(jnp.sum(pair.T - pair, 0, keepdims=True))
            within_bar = _head_lanes(dot(inside, bar, TN), k, per, width,
                                     within_bar)
        stepped_bar = rest * leaving_bar + within_bar
        x_bar_ref[:, lanes] = (
            skip_ref[:, lanes] * y_bar + steps * stepped_bar).astype(
                x_bar_ref.dtype)
        # a token's sums over its head's lanes, a head a row; alpha's last
        # token is read by every token's rest (their sum) and by kept
        own = slice(first, first + per)
        steps_bar_ref[own, :] = _off_lanes(stepped_bar * x, per, width)
        skip_bar_ref[own, :] = _off_lanes(y_bar * x, per, width)
        rested = leaving_bar * leaving
        last = (jnp.sum(rested, 0, keepdims=True)
                + kept * jnp.sum(ahead * entering, 0, keepdims=True))
        rows = _off_lanes(y_bar * read - rested, per, width) + jnp.where(
            lax.broadcasted_iota(jnp.int32, (per, size), 1) == size - 1,
            _off_lanes(jnp.broadcast_to(last, (8,) + last.shape[1:]), per,
                       width)[:, :1], 0.0)
        for k, pair in enumerate(pairs):
            alpha_bar_ref[first + k:first + k + 1, :] = rows[k:k + 1] + pair
    cb_bar_ref[...], b_sum_ref[...], c_sum_ref[...] = cb_bar, b_sum, c_sum

    @pl.when(h % per_group == per_group - 1)
    def _():
        summed = cb_bar.astype(dtype)
        b_bar_ref[...] = (b_sum + dot(summed, c_ref[...], TN)).astype(
            b_bar_ref.dtype)
        c_bar_ref[...] = (c_sum + dot(summed, b_ref[...])).astype(
            c_bar_ref.dtype)


def _running_sum(dt, a, chunk: int):
    """The steps heads-major and ``alpha``, their running sum times the
    rate inside each chunk, both ``[B, H, S]`` float32: tokens along the
    lanes, as the kernels' blocks take them. The sum is
    ``kernel_parts.running_sum``'s product, batch and head its batch
    dimensions: the sums come out heads-major as the steps lie."""
    f32 = jnp.float32
    batch, seq, heads = dt.shape
    steps = jnp.moveaxis(dt.astype(f32), 1, 2)
    alpha = running_sum("bhij,bhnj->bhni", (batch, heads), chunk)(
        (steps * a.astype(f32)[:, None]).reshape(batch, heads, -1, chunk))
    return steps, alpha.reshape(batch, heads, seq)


def _scan_call(kernel, operands, results, scratch, *, shape, turned, step,
               chunk, interpret):
    """``kernel`` over the grid ``(B, chunks, H / step)``, the heads
    innermost (``kernel_parts.chunk_grid_call``), for ``shape = (B, S, H,
    P, G, N)``. ``operands`` and ``results`` are ``(kind, array or
    dtype)``, a block of each kind one chunk of one step's heads: ``tokens
    [B, S, H * P]`` (``x``, ``y`` and their cotangents, read where they
    lie: PR 40's ``_block_at``), ``rows
    [B, H, S]`` (float32, tokens along the lanes), ``group [B, S, G * N]``
    (the block stays while the steps stay in the group), ``skip [1, H *
    P]`` and ``states [B, chunks, N, H * P]``. ``turned``: the chunks last
    to first."""
    batch, seq, heads, width, groups, state = shape
    count, lanes, per_group = seq // chunk, step * width, heads // groups // step
    kinds = {
        "tokens": ((batch, seq, heads * width), (None, chunk, lanes),
                   lambda i, n, h: (i, n, h)),
        "rows": ((batch, heads, seq), (None, step, chunk),
                 lambda i, n, h: (i, h, n)),
        "group": ((batch, seq, groups * state), (None, chunk, state),
                  lambda i, n, h: (i, n, h // per_group)),
        "skip": ((1, heads * width), (1, lanes), lambda i, n, h: (0, h)),
        "states": ((batch, count, state, heads * width),
                   (None, None, state, lanes), lambda i, n, h: (i, n, 0, h)),
    }
    return chunk_grid_call(
        functools.partial(kernel, width=width, per_group=per_group), kinds,
        operands, results,
        [pltpu.VMEM((heads // step, state, lanes), jnp.float32),
         pltpu.VMEM((chunk, chunk), jnp.float32)] + scratch,
        grid=(batch, count, heads // step), turned=turned,
        interpret=interpret, name=SCAN_KERNEL_NAME)


def _kernel_operands(x, steps, alpha, b, c, d):
    """As :func:`_scan_call` takes the six that both kernels read."""
    return [("tokens", x), ("rows", steps), ("rows", alpha),
            ("group", b.astype(x.dtype)), ("group", c.astype(x.dtype)),
            ("skip", jnp.repeat(d.astype(jnp.float32), x.shape[3]))]


def _forward_by_kernel(x, dt, a, b, c, d, *, states, chunk, **how):
    f32 = jnp.float32
    with annotate_collective(SCOPE_SSM_SCAN):
        y, *entering = _scan_call(
            _scan_forward_kernel,
            _kernel_operands(x, *_running_sum(dt, a, chunk), b, c, d),
            [("tokens", x.dtype)] + [("states", f32)] * states, [],
            shape=x.shape + b.shape[2:], turned=False, chunk=chunk, **how)
        return [y.reshape(x.shape)] + entering


def _backward_by_kernel(x, dt, a, b, c, d, entering, y_bar, *, chunk, **how):
    f32 = jnp.float32
    size, state = chunk, b.shape[3]
    with annotate_collective(SCOPE_SSM_SCAN):
        (steps, alpha), pull = jax.vjp(
            lambda dt, a: _running_sum(dt, a, chunk), dt, a)
        x_bar, b_bar, c_bar, steps_bar, alpha_bar, skip_bar = _scan_call(
            _scan_backward_kernel,
            _kernel_operands(x, steps, alpha, b, c, d)
            + [("states", entering), ("tokens", y_bar.astype(x.dtype))],
            [("tokens", x.dtype), ("group", b.dtype), ("group", c.dtype)]
            + [("rows", f32)] * 3,
            [pltpu.VMEM((size, size), f32), pltpu.VMEM((size, state), f32),
             pltpu.VMEM((size, state), f32)],
            shape=x.shape + b.shape[2:], turned=True, chunk=chunk, **how)
        dt_bar, a_bar = pull((steps_bar, alpha_bar))
        return [x_bar.reshape(x.shape), dt_bar.astype(dt.dtype),
                a_bar.astype(a.dtype), b_bar.reshape(b.shape),
                c_bar.reshape(c.shape), skip_bar.sum((0, 2)).astype(d.dtype)]


def _states_shape(x, b, chunk: int):
    """Of the float32 states that enter each chunk, a head's ``h^T``."""
    batch, seq, heads, width = x.shape
    return batch, seq // chunk, b.shape[3], heads * width


def _forward_plain(x, dt, a, b, c, d, *, states, chunk, **_):
    # the plain backward differentiates the plain form and reads no states
    unread = [jnp.zeros(_states_shape(x, b, chunk), jnp.float32)] * states
    return [_chunk_form(x, dt, a, b, c, d, chunk)] + unread


def _backward_plain(x, dt, a, b, c, d, entering, y_bar, *, chunk, **_):
    return jax.vjp(lambda *xs: _chunk_form(*xs, chunk),
                   x, dt, a, b, c, d)[1](y_bar)


def _forward_results(x, dt, a, b, c, d, *, states, chunk, **_):
    return [x] + [x.update(shape=_states_shape(x, b, chunk),
                           dtype=jnp.float32)] * states


_scan_forward_p = where_lowered(
    "hvd_ssd_chunk_scan", _forward_results, _forward_by_kernel,
    _forward_plain)
_scan_backward_p = where_lowered(
    "hvd_ssd_chunk_scan_backward",
    lambda x, dt, a, b, c, d, entering, y_bar, **_: [x, dt, a, b, c, d],
    _backward_by_kernel, _backward_plain)


def _unread_states(used, eqn):
    """The forward pass of a recomputed layer reads ``y`` alone (the
    policy keeps nothing of this primitive): it then writes no states."""
    if eqn.params["states"] and not used[1]:
        eqn = eqn.replace(outvars=eqn.outvars[:1],
                          params=dict(eqn.params, states=False))
    return [any(used)] * len(eqn.invars), eqn if any(used) else None


pe.dce_rules[_scan_forward_p] = _unread_states


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def ssd_scan_kernel(x, dt, a, b, c, d, chunk, interpret=False):
    """:func:`_chunk_form` as one Pallas kernel and its backward pass as
    another in a program lowered for a TPU (anywhere, interpreted, where
    the tests say ``interpret``; the plain form itself on any other
    platform), at shapes :func:`_heads_a_step` accepts. The grid walks a
    sequence's chunks in order, the heads in steps of eight innermost;
    ``C B^T``, a head's ``L``, the factors and the heads' states stay in
    VMEM, and HBM sees ``x``, ``b``, ``c`` where they lie, the steps and
    ``alpha`` heads-major, ``y`` and, where a backward pass follows, the
    float32 states that enter each chunk (``[B, chunks, N, H * P]``): the
    backward kernel's residuals with the operands. Same rounding points
    as the plain form. Each pass is a primitive of its own
    (``kernel_parts.where_lowered``), so a recomputed layer's policy
    sees no ``pallas_call`` whose results it would keep: ``y`` and the
    states are formed again in the backward pass."""
    return _scan_forward_p.bind(
        x, dt, a, b, c, d, states=False, **_how(x, b, chunk, interpret))[0]


def _how(x, b, chunk, interpret):
    return dict(step=_heads_a_step(x, b, chunk), chunk=chunk,
                interpret=interpret)


def _scan_forward(x, dt, a, b, c, d, chunk, interpret):
    y, entering = _scan_forward_p.bind(
        x, dt, a, b, c, d, states=True, **_how(x, b, chunk, interpret))
    return y, (x, dt, a, b, c, d, entering)


def _scan_backward(chunk, interpret, kept, y_bar):
    return tuple(_scan_backward_p.bind(
        *kept, y_bar, **_how(kept[0], kept[3], chunk, interpret)))


ssd_scan_kernel.defvjp(_scan_forward, _scan_backward)
