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
    (I + A) [U | W] = beta * [V | exp(gamma) * K]
    V' = U - W S0
    O  = (Q * exp(gamma)) S0 + tril(Q K^T * exp(gamma_i - gamma_j)) V'
    S1 = exp(gamma_C) S0 + (K * exp(gamma_C - gamma))^T V'

The operands of the chunk products are in the inputs' type with float32
accumulation; ``gamma``, the decays, the solve and the carried state are
float32 (``gamma_i - gamma_j`` is masked to ``j <= i`` before the
exponential: above the diagonal it is positive and overflows).

The solve is no forward substitution (``C`` dependent rows a system,
which the v5e ran in 1.8 ms a layer): :func:`solve_unit_lower` builds
``(I + A)^-1`` by block doubling, ``log2 C`` levels of float32
multiply-adds with the systems along the lanes, applies it by one float32
product at ``Precision.HIGHEST``, and is differentiated by a rule of its
own that keeps the inverse and the solution and makes two such products.
The loop's left operands are rounded to the compute type once, outside
it. The rest of the scalar rule is plain JAX, differentiated by JAX. The
scope ``hvd.linattn.scan`` is around all of it, forward and backward.

:func:`kimi_delta_rule` is the same recurrence with **a decay a key
channel** (``g [B, S, H, d_k]``: ``S' = Diag(exp(g_t)) S``; Kimi Delta
Attention, arXiv:2510.26692, ``flash-linear-attention``'s ``kda``). Its
chunk form is the one above with ``gamma [C, d_k]``, but the decay now
sits inside the contraction, ``A_ij = beta_i sum_c k_ic k_jc exp(gamma_ic -
gamma_jc)``, so ``(k k^T) * decay`` is no more and the pair terms are
:func:`_pair_terms`'s. The plain solve, the plain scan over chunks and
the scope are shared. Where the shapes fill a TPU's
tiles (``d_k`` whole 128-lane blocks, ``sub`` whole sublane tiles) the pair
terms are a primitive whose lowering the platform chooses
(``kernel_parts.where_lowered``): for a TPU
:func:`pair_terms_kernel`'s Pallas kernel, which forms them in VMEM, with a
backward kernel of its own, :func:`_chunks_a_step` chunks a grid step; for
anything else, and at any other shape, the plain :func:`_pair_terms`.
Its solve and its loop over the chunks are a primitive of the same kind
(``d_k`` and ``d_v`` whole lane blocks, the chunk a power of two of whole
sublane tiles, eight heads a grid step: :func:`_scan_heads_a_step`): for a
TPU :func:`chunk_scan_kernel`'s forward and backward kernels, in which a
head's float32 state stays in VMEM over its chunks and ``(I + A)`` is
solved in VMEM; for anything else the plain :func:`_chunk_scan`, which is
the scalar rule's :func:`solve_unit_lower` and ``lax.scan``. The program
says which form it holds: the primitives ``hvd_kda_pair_terms`` and
``hvd_kda_chunk_scan`` in its jaxpr, the kernels' names in its text.
Its ``gamma`` is a float32 product of the chunk's lower triangle of ones
with ``g`` at ``Precision.HIGHEST``: summed as ``jnp.cumsum`` over the rows
of ``[C, d_k]`` it is a ``reduce-window``, which the v5e runs at a
fourteenth of its memory's pace.
The four kernels read ``q``, ``k``, ``v``, ``gamma`` and write ``o`` and
every ``d``-wide cotangent as ``[B, S, H * d]``, where the projections
wrote them: a head is a lane block of a chunk's rows through the index
maps, and nothing ``d`` wide is transposed on the way in or out; only the
plain forms take the head-major view (:func:`_by_head`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.interpreters import partial_eval as pe

from ..attribution import SCOPE_LINATTN_SCAN
from ..profiler import annotate_collective
from .kernel_parts import (MASKED, NN, NT, TN, chunk_grid_call, dot,
                           running_sum, where_lowered)


def short_conv(x, w, bias=None):
    """Depth-wise causal convolution over time: ``x [B, S, channels]``,
    ``w [channels, width]`` → ``y_t = Σ_i w[:, i] · x_{t - (width - 1) +
    i}`` with zeros to the left of the sequence (``w[:, -1]`` weighs the
    token itself; ``torch.nn.Conv1d(groups=channels, padding=width - 1)``
    cut to the sequence), plus ``bias [channels]`` where there is one.
    Float32 accumulation, ``x``'s type out."""
    width, seq = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(padded[:, i:i + seq].astype(jnp.float32) * w[:, i]
              for i in range(width))
    if bias is not None:
        out = out + bias
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
        solved = solve_unit_lower(
            a, beta * jnp.concatenate([v.astype(f32), k * grow], -1))
        u, w = solved[..., :d_v], solved[..., d_v:]
        inside = product("bhnic,bhnjc->bhnij", q, k) * decay

        def one_chunk(state, xs):
            u, w, inside, q_in, k_out, kept = xs
            new = u - product("bhck,bhkv->bhcv", w, state)
            out = (product("bhck,bhkv->bhcv", q_in, state)
                   + product("bhij,bhjv->bhiv", inside, new))
            state = kept * state + product("bhck,bhcv->bhkv", k_out, new)
            return state, out.astype(dtype)

        # The products' left operands go in rounded to the compute type:
        # the rounding an iteration made, made once. (Stacked in pairs
        # that share a right operand they lose 5 ms a step to the
        # stacking, and JAX's transposition then rounds one summed
        # cotangent where it rounded two. rest and the chunk's whole decay
        # are decay's last row and grow's last entry; sliced out of those
        # the v5e's step took 1.1 ms longer.)
        per_chunk = (u, w.astype(dtype), inside.astype(dtype),
                     (q * grow).astype(dtype), (k * rest).astype(dtype),
                     jnp.exp(gamma[..., -1])[..., None, None])
        state = jnp.zeros((batch, heads, k.shape[-1], d_v), f32)
        _, out = lax.scan(one_chunk, state, jax.tree.map(
            lambda x: jnp.moveaxis(x, 2, 0), per_chunk))
        # [N, B, H, C, d_v] -> [B, S, H, d_v]
        return out.transpose(1, 0, 3, 2, 4).reshape(batch, seq, heads, d_v)


def _pair_terms(q, k, gamma, sub: int, dtype):
    """``(sum_c q_ic k_jc e_ijc, sum_c k_ic k_jc e_ijc)`` with ``e_ijc =
    exp(gamma_ic - gamma_jc)`` for ``j <= i`` and zero above the diagonal:
    ``q``, ``k`` ``[..., C, d]``, ``gamma`` (float32, falling along ``C``)
    alike, both results ``[..., C, C]`` float32.

    **No exponent is positive.** The cheap factorisation ``(k e^gamma)(k
    e^-gamma)^T`` overflows: ``-gamma`` grows all through a chunk (at 1.6
    a token it passes float32 within 56 tokens). So the chunk is cut into
    sub-blocks of ``sub`` rows. A pair whose rows lie in different
    sub-blocks goes through a reference row between them, the first row
    ``r`` of ``i``'s sub-block: ``exp(gamma_i - gamma_r)`` and
    ``exp(gamma_r - gamma_j)`` are both at most 1 and each side is one
    operand of a matrix product on the MXU (the left one ``[C, d]``, the
    right one ``[C / sub, C, d]``: a sub-block's own view of the rows
    before it). A factor that underflows to zero stands for a pair whose
    decay is smaller still. Pairs inside a sub-block are summed directly,
    ``sub x sub x d`` exponentials of masked differences in float32.
    This is the plain form: what any platform but a TPU runs (under
    ``jax.checkpoint``: the backward pass forms the factors and the
    sub-blocks' cubes again from ``q``, ``k`` and ``gamma`` instead of
    keeping them) and what the tests hold :func:`pair_terms_kernel` to."""
    f32 = jnp.float32
    size, width = k.shape[-2:]
    lead, count = k.shape[:-2], size // sub

    def blocks(x):  # [..., C, d] -> [..., C / sub, sub, d]
        return x.reshape(lead + (count, sub, width))

    gamma_b = blocks(gamma)
    first = gamma_b[..., :1, :]                           # a sub-block's row r
    left = jnp.exp(gamma_b - first)                       # rows i >= r
    before = (jnp.arange(size)[None, :]
              < (jnp.arange(count) * sub)[:, None])[..., None]
    right = jnp.exp(jnp.where(                            # rows j < r
        before, first - gamma[..., None, :, :], -jnp.inf))
    k_right = (k[..., None, :, :] * right).astype(dtype)  # [..., n, C, d]
    inside = jnp.exp(jnp.where(                           # one sub-block's
        jnp.tril(jnp.ones((sub, sub), bool))[..., None],
        gamma_b[..., :, None, :] - gamma_b[..., None, :, :], -jnp.inf))
    k_b = blocks(k).astype(f32)
    own = jnp.eye(count, dtype=f32)[:, None, :, None]

    def pairs(a):
        far = jnp.einsum(
            "...nid,...njd->...nij", (blocks(a) * left).astype(dtype),
            k_right, preferred_element_type=f32)
        near = (blocks(a).astype(f32)[..., :, None, :]
                * k_b[..., None, :, :] * inside).sum(-1)  # [..., n, sub, sub]
        near = near[..., :, :, None, :] * own             # on the diagonal
        return far.reshape(lead + (size, size)) + near.reshape(
            lead + (size, size))

    return pairs(q), pairs(k)


# The kernels' name: not ``flash_attention``, by which the benchmark finds
# the attention kernels. XLA names the custom call's instruction after it.
PAIR_KERNEL_NAME = "kda_pair_terms"
PAIR_CHUNKS_A_STEP = 4  # of a grid step, where that many divide the chunks


def _by_head(x, chunk: int):
    """``[B, S, H, ...]`` as the plain forms take it: ``[B, H, chunks,
    chunk, ...]``. No kernel's operand goes through here."""
    x = x.reshape((x.shape[0], -1, chunk) + x.shape[2:])
    return jnp.moveaxis(x, 3, 1)


def _one_chunk_of_a_head(ref, t, heads: int):
    """Pair ``t`` of a grid step's chunks and heads in a block ``[chunks,
    C, heads * d]`` of ``[B, S, H * d]``: ``(c, r, lanes)``, the heads
    innermost."""
    width = ref.shape[-1] // heads
    c, r = t // heads, t % heads
    return c, r, pl.ds(pl.multiple_of(r * width, width), width)


def _sub_block(q_ref, k_ref, gamma_ref, c, lanes, lo, hi, dtype):
    """Sub-block ``[lo, hi)`` of chunk ``c``, of the head on ``lanes``, of
    a grid step's blocks: its rows of
    ``q`` and ``k`` in float32; the cube ``e_ijc`` of its own pairs in
    pieces ``(first row, [rows, columns, d])`` of eight rows (a float32
    tile's) against the columns up to their last, zero above the
    diagonal: the triangle's tiles alone; and for the pairs with
    the rows before it, through its first row ``r``: ``(q_i e^(gamma_i -
    gamma_r), k_i e^(gamma_i - gamma_r))`` stacked and ``k_j e^(gamma_r -
    gamma_j)``, rounded to ``dtype``, with the two float32 factors
    (``None`` for the first sub-block). No exponent is positive."""
    f32 = jnp.float32

    def read(ref, start, stop):
        return ref[c, start:stop, lanes]

    gamma = read(gamma_ref, lo, hi)
    q, k = read(q_ref, lo, hi).astype(f32), read(k_ref, lo, hi).astype(f32)
    pieces, rows = [], min(8, hi - lo)
    for top in range(0, hi - lo, rows):
        shape = (rows, top + rows, gamma.shape[-1])
        lower = (lax.broadcasted_iota(jnp.int32, shape, 1)
                 <= lax.broadcasted_iota(jnp.int32, shape, 0) + top)
        pieces.append((top, jnp.exp(jnp.where(
            lower, gamma[top:top + rows][:, None, :]
            - gamma[:top + rows][None, :, :], MASKED))))
    if not lo:
        return q, k, pieces, None
    left = jnp.exp(gamma - gamma[:1])
    right = jnp.exp(gamma[:1] - read(gamma_ref, 0, lo))
    far = (jnp.concatenate([(q * left).astype(dtype),
                            (k * left).astype(dtype)], 0),
           (read(k_ref, 0, lo).astype(f32) * right).astype(dtype), left,
           right)
    return q, k, pieces, far


def _pair_forward_kernel(q_ref, k_ref, gamma_ref, inside_ref, a_ref, *,
                         sub, dtype):
    """``inside`` and ``a`` of the grid step's chunks and heads, ``[heads,
    chunks, C, C]`` float32, from ``q``, ``k``, ``gamma`` as they lie in
    ``[B, S, H * d]`` (``[chunks, C, heads * d]``), sub-block by sub-block
    of rows: the columns before it one product of ``dtype`` operands for
    both, its own the cube's lane sums, those after a row's piece zero."""
    heads, chunks, size, _ = inside_ref.shape
    f32 = jnp.float32

    def one_chunk(t, carry):
        c, r, lanes = _one_chunk_of_a_head(q_ref, t, heads)
        for lo in range(0, size, sub):
            hi = lo + sub
            q, k, pieces, far = _sub_block(q_ref, k_ref, gamma_ref, c, lanes,
                                           lo, hi, dtype)
            if far is not None:
                both = dot(far[0], far[1], NT)
                inside_ref[r, c, lo:hi, 0:lo] = both[:sub]
                a_ref[r, c, lo:hi, 0:lo] = both[sub:]
            for top, cube in pieces:
                rows, columns = cube.shape[:2]
                here = slice(lo + top, lo + top + rows)
                near = cube * k[:columns][None, :, :]
                inside_ref[r, c, here, lo:lo + columns] = (
                    near * q[top:top + rows][:, None, :]).sum(-1)
                a_ref[r, c, here, lo:lo + columns] = (
                    near * k[top:top + rows][:, None, :]).sum(-1)
                if lo + columns < size:
                    above = jnp.zeros((rows, size - lo - columns), f32)
                    inside_ref[r, c, here, lo + columns:size] = above
                    a_ref[r, c, here, lo + columns:size] = above
        return carry

    lax.fori_loop(0, chunks * heads, one_chunk, 0)


def _pair_backward_kernel(q_ref, k_ref, gamma_ref, inside_bar_ref, a_bar_ref,
                          q_bar_ref, k_bar_ref, gamma_bar_ref, right_ref, *,
                          sub, dtype):
    """``dq``, ``dk``, ``dgamma`` of the grid step's chunks and heads, where
    they lie in ``[B, S, H * d]``, from the two cotangents: the forward's
    factors formed again, sub-blocks last to
    first, so that a row's cotangent as a pair's right side (``right_ref``,
    float32 scratch) is whole when its own sub-block is done. The sums run
    over ``i`` or ``j``, never over lanes. By term ``dgamma_i = q_i dq_i +
    k_i (dk_i as the left side - dk_i as the right side)``: no cube of its
    own. Cotangents of ``dtype`` operands go into their products rounded
    to ``dtype``, as the plain form's transposed products take them."""
    heads, chunks, size, _ = inside_bar_ref.shape

    def one_chunk(t, carry):
        c, r, lanes = _one_chunk_of_a_head(q_ref, t, heads)
        right_ref[...] = jnp.zeros_like(right_ref)
        for lo in reversed(range(0, size, sub)):
            hi = lo + sub
            q, k, pieces, far = _sub_block(q_ref, k_ref, gamma_ref, c, lanes,
                                           lo, hi, dtype)
            q_bar, as_left = [], []
            for top, cube in pieces:
                rows, columns = cube.shape[:2]
                here = slice(lo + top, lo + top + rows)
                by_inside = inside_bar_ref[r, c, here, lo:lo + columns][
                    :, :, None] * cube
                by_a = a_bar_ref[r, c, here, lo:lo + columns][
                    :, :, None] * cube
                q_bar.append((by_inside * k[:columns][None, :, :]).sum(1))
                as_left.append((by_a * k[:columns][None, :, :]).sum(1))
                right_ref[lo:lo + columns, :] += (
                    by_inside * q[top:top + rows][:, None, :]
                    + by_a * k[top:top + rows][:, None, :]).sum(0)
            q_bar, as_left = jnp.concatenate(q_bar), jnp.concatenate(as_left)
            if far is not None:
                stacked, k_right, left, right = far
                bars = jnp.concatenate([inside_bar_ref[r, c, lo:hi, 0:lo],
                                        a_bar_ref[r, c, lo:hi, 0:lo]],
                                       0).astype(dtype)
                to_left = dot(bars, k_right)
                q_bar = q_bar + to_left[:sub] * left
                as_left = as_left + to_left[sub:] * left
                right_ref[0:lo, :] += right * dot(bars, stacked, TN)
            as_right = right_ref[lo:hi, :]
            q_bar_ref[c, lo:hi, lanes] = q_bar.astype(q_bar_ref.dtype)
            k_bar_ref[c, lo:hi, lanes] = (as_left + as_right).astype(
                k_bar_ref.dtype)
            gamma_bar_ref[c, lo:hi, lanes] = (
                q * q_bar + k * (as_left - as_right))
        return carry

    lax.fori_loop(0, chunks * heads, one_chunk, 0)


def _chunks_a_step(count: int) -> int:
    """The largest divisor of ``count`` chunks up to
    ``PAIR_CHUNKS_A_STEP``."""
    return max(n for n in range(1, PAIR_CHUNKS_A_STEP + 1) if not count % n)


def _pair_call(kernel, operands, results, scratch=(), *, chunk, step, heads,
               sub, dtype, interpret):
    """``kernel`` over the grid ``(B, chunks / step, H / heads)``, the heads
    innermost. ``operands`` and ``results`` are ``(kind, array or dtype)``,
    a block of each kind ``step`` chunks of ``heads`` heads: ``tokens [B, S,
    H * d]`` (``q``, ``k``, ``gamma`` and their cotangents **where the
    projections wrote them and the convolution's backward reads them**:
    ``step`` chunks' rows of ``heads * d`` lanes, taken as ``[B, N, C, H *
    d]``, which splits whole sublane tiles off ``S`` and moves nothing;
    nothing is transposed on either side) and ``pairs [B, H, N, C, C]``
    (kernel to kernel)."""
    batch, seq, all_heads, width = operands[0][1].shape
    count = seq // chunk
    kinds = {
        "tokens": ((batch, count, chunk, all_heads * width),
                   (None, step, chunk, heads * width),
                   lambda i, n, h: (i, n, 0, h)),
        "pairs": ((batch, all_heads, count, chunk, chunk),
                  (None, heads, step, chunk, chunk),
                  lambda i, n, h: (i, h, n, 0, 0)),
    }
    out = chunk_grid_call(
        functools.partial(kernel, sub=sub, dtype=dtype), kinds, operands,
        results, scratch, grid=(batch, count // step, all_heads // heads),
        turned=False, interpret=interpret, name=PAIR_KERNEL_NAME)
    return [x.reshape(operands[0][1].shape) if kind == "tokens" else x
            for (kind, _), x in zip(results, out)]


def _forward_by_kernel(q, k, gamma, **how):
    return _pair_call(
        _pair_forward_kernel,
        [("tokens", q), ("tokens", k), ("tokens", gamma)],
        [("pairs", jnp.float32)] * 2, **how)


def _backward_by_kernel(q, k, gamma, inside_bar, a_bar, *, chunk, **how):
    return _pair_call(
        _pair_backward_kernel,
        [("tokens", q), ("tokens", k), ("tokens", gamma),
         ("pairs", inside_bar), ("pairs", a_bar)],
        [("tokens", q.dtype), ("tokens", k.dtype), ("tokens", jnp.float32)],
        [pltpu.VMEM((chunk, k.shape[-1]), jnp.float32)], chunk=chunk, **how)


def _forward_plain(q, k, gamma, *, chunk, sub, dtype, **_):
    return _pair_terms(*(_by_head(x, chunk) for x in (q, k, gamma)), sub,
                       dtype)


def _backward_plain(q, k, gamma, *bars, **how):
    # the factors and the cubes again from the operands, as jax.checkpoint's
    return jax.vjp(functools.partial(_forward_plain, **how),
                   q, k, gamma)[1](bars)


def _pair_avals(q, k, gamma, *, chunk, **_):
    batch, seq, heads, _ = k.shape
    return [gamma.update(shape=(batch, heads, seq // chunk, chunk, chunk))] * 2


_pair_forward_p = where_lowered(
    "hvd_kda_pair_terms", _pair_avals, _forward_by_kernel, _forward_plain)
_pair_backward_p = where_lowered(
    "hvd_kda_pair_terms_backward", lambda *kept, **_: list(kept[:3]),
    _backward_by_kernel, _backward_plain)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def pair_terms_kernel(q, k, gamma, chunk, sub, dtype, interpret=False):
    """:func:`_pair_terms` of every chunk and head of ``q``, ``k``,
    ``gamma [B, S, H, d]`` (which the calls read as ``[B, S, H * d]``, as
    the projections wrote them) as one Pallas kernel giving ``[B, H, N, C,
    C]`` twice, and its backward pass as
    another, in a program lowered for a TPU (anywhere, interpreted, where
    the tests say ``interpret``; the plain form itself on any other
    platform): a grid step takes some chunks of eight heads' ``q``, ``k``
    and ``gamma`` into VMEM and nothing between them and the two ``[C, C]``
    results is written to HBM (the plain form writes ``k_right``, four
    times ``k``, and the sub-blocks' cubes). The same reference rows, the same
    rounding points: a pair in different sub-blocks is a product of
    ``dtype`` operands with float32 accumulation, a pair inside one is
    float32 throughout. The residuals are the operands; the backward
    kernel forms the factors again in VMEM and writes ``dq``, ``dk``,
    ``dgamma`` as ``[B, S, H * d]`` too. Each pass is a primitive of
    its own (``kernel_parts.where_lowered``), so a recomputed layer's
    policy sees no ``pallas_call`` whose results it would keep (134 MB a
    layer at 8,192 tokens that no backward kernel wants): they are formed
    again in the backward pass, as the plain form's under
    ``jax.checkpoint``."""
    return _pair_forward(q, k, gamma, chunk, sub, dtype, interpret)[0]


def _how(k, chunk, sub, dtype, interpret):
    return dict(chunk=chunk, step=_chunks_a_step(k.shape[1] // chunk),
                heads=_heads_a_step(k.shape[2]), sub=sub,
                dtype=jnp.dtype(dtype), interpret=interpret)


def _pair_forward(q, k, gamma, chunk, sub, dtype, interpret):
    out = _pair_forward_p.bind(q, k, gamma,
                               **_how(k, chunk, sub, dtype, interpret))
    return tuple(out), (q, k, gamma)


def _pair_backward(chunk, sub, dtype, interpret, kept, bars):
    return tuple(_pair_backward_p.bind(
        *kept, *bars, **_how(kept[1], chunk, sub, dtype, interpret)))


pair_terms_kernel.defvjp(_pair_forward, _pair_backward)


def _pair_terms_where_lowered(q, k, gamma, chunk, sub, dtype):
    """The pair terms ``[B, H, N, C, C]`` of ``q``, ``k``, ``gamma [B, S,
    H, d_k]`` by :func:`pair_terms_kernel` where a TPU's tiles are
    filled (``d_k`` whole lanes, ``sub`` whole sublanes of ``q``'s and
    ``k``'s type), so that the program lowered for a TPU holds the kernels
    and any other the plain form; at any other shape the plain form under
    ``jax.checkpoint`` whatever the platform."""
    rows = 32 // min(q.dtype.itemsize, k.dtype.itemsize)  # a tile's sublanes
    if k.shape[-1] % 128 == 0 and sub % rows == 0:
        return pair_terms_kernel(q, k, gamma, chunk, sub, dtype)
    return jax.checkpoint(functools.partial(
        _forward_plain, chunk=chunk, sub=sub, dtype=dtype))(q, k, gamma)


def kimi_delta_rule(q, k, v, g, beta, chunk: int = 64, sub: int = 16):
    """The delta rule with a decay a key channel: ``q``, ``k``, ``g``
    ``[B, S, H, d_k]``, ``v [B, S, H, d_v]``, ``beta [B, S, H]`` -> ``o
    [B, S, H, d_v]`` in ``v``'s type, from a zero state::

        S' = Diag(exp(g_t)) S        (row c of S decays by exp(g_tc))
        S_t = S' + beta_t * k_t (v_t - S'^T k_t)^T
        o_t = S_t^T q_t

    ``q`` and ``k`` come as the rule reads them. ``S`` must be a multiple
    of ``chunk`` and ``chunk`` of ``sub``, the sub-block of
    :func:`_pair_terms`. Types as :func:`gated_delta_rule`: the products'
    operands in ``v``'s type with float32 accumulation; ``g``, ``gamma``,
    the decays, the solve and the carried state float32. ``gamma = L g`` a
    chunk and head, ``L`` the ``[C, C]`` lower triangle of ones, one
    float32 product at ``Precision.HIGHEST`` (float32's sum in another
    order; the backward pass's reverse sum is the product with ``L^T``):
    as a ``reduce-window`` it was 50 ms of the v5e's step, the products
    are 11.

    **Nothing ``d`` wide is transposed.** ``q``, ``k``, ``v``, ``gamma``
    and ``o`` stay tokens-major, ``[B, S, H * d]`` as the projections wrote
    them and the output's gate reads them, and so do ``dq``, ``dk``, ``dv``,
    ``dg``: the four kernels take a head as a lane block of a chunk's rows
    through their index maps (:func:`_pair_call`, :func:`_scan_call`).
    Head-major are only ``beta`` (one number a token) and the pair terms
    ``[B, H, N, C, C]``, which go from kernel to kernel; the plain forms
    (any platform but a TPU, and shapes that fill no tile) take
    :func:`_by_head`'s view of everything."""
    seq = v.shape[1]
    if seq % chunk or chunk % sub:
        raise ValueError(
            f"kimi_delta_rule: a sequence of {seq} is no multiple of the "
            f"chunk of {chunk}, or the chunk none of the sub-block of "
            f"{sub}; pad it upstream")
    with annotate_collective(SCOPE_LINATTN_SCAN):
        beta = _by_head(beta.astype(jnp.float32), chunk)[..., None]
        gamma = _running_sums(g.astype(jnp.float32), chunk)
        inside, a = _pair_terms_where_lowered(q, k, gamma, chunk, sub,
                                              v.dtype)
        return _chunk_scan_where_lowered(q, k, v, gamma, beta, inside, a)


def _triangles_product(spec, x, chunk):
    """``x [B, S, H, d]`` float32 summed along each chunk's rows
    (``kernel_parts.running_sum``) as ``spec`` says: ``ij`` the triangle,
    ``b`` and ``n`` batch and chunk, ``x`` the heads' lanes side by side.
    Batch and chunk are the product's batch dimensions, and a product's
    batch dimensions lead its result, so the sums come out ``[B, N, C, H *
    d]``, which is ``[B, S, H * d]`` where the kernels read it (with the
    head as a dimension of its own, batch or free, the v5e's compiler lays
    the result out head-major and copies it back; with the chunk alone as
    a batch dimension chunk-major: 32 ms of its step in copies)."""
    batch, seq = x.shape[:2]
    return running_sum(spec, (batch, seq // chunk), chunk)(
        x.reshape((batch, seq // chunk, chunk, -1))).reshape(x.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _running_sums(g, chunk):
    """``gamma = L g`` a chunk, head and channel, ``g [B, S, H, d_k]``
    float32 where it lies; its cotangent is the product with ``L^T`` where
    ``dgamma`` lies (JAX's own transposition of the product gives the same
    sums as ``[B, N, H * d, C]`` and transposes them)."""
    return _triangles_product("bnij,bnjx->bnix", g, chunk)


_running_sums.defvjp(
    lambda g, chunk: (_triangles_product("bnij,bnjx->bnix", g, chunk), None),
    lambda chunk, _, bar: (
        _triangles_product("bnij,bnix->bnjx", bar, chunk),))


def _chunk_scan(q, k, v, gamma, beta, inside, a):
    """The solve and the loop over the chunks of :func:`kimi_delta_rule`,
    plain JAX differentiated by JAX, on the head-major view
    (:func:`_by_head`): ``q``, ``k``, ``gamma`` ``[B, H, N, C,
    d_k]``, ``v [B, H, N, C, d_v]``, ``beta [B, H, N, C, 1]`` and the pair
    terms ``inside``, ``a`` ``[B, H, N, C, C]`` -> ``o [B, S, H, d_v]`` in
    ``v``'s type. What any platform but a TPU runs, what the toys' widths
    trace and what the tests hold :func:`chunk_scan_kernel` to
    (:func:`_chunk_scan_of_tokens`)."""
    batch, heads, count, chunk, d_v = v.shape
    dtype, f32 = v.dtype, jnp.float32

    def product(spec, a, b):
        return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                          preferred_element_type=f32)

    grow = jnp.exp(gamma)                              # from the chunk's start
    rest = jnp.exp(gamma[..., -1:, :] - gamma)         # to its end
    solved = solve_unit_lower(
        jnp.tril(beta * a, -1),
        beta * jnp.concatenate([v.astype(f32), k * grow], -1))
    u, w = solved[..., :d_v], solved[..., d_v:]

    def one_chunk(state, xs):
        u, w, inside, q_in, k_out, kept = xs
        new = u - product("bhck,bhkv->bhcv", w, state)
        out = (product("bhck,bhkv->bhcv", q_in, state)
               + product("bhij,bhjv->bhiv", inside, new))
        state = kept * state + product("bhck,bhcv->bhkv", k_out, new)
        return state, out.astype(dtype)

    # as gated_delta_rule: left operands rounded once, outside the loop
    per_chunk = (u, w.astype(dtype), inside.astype(dtype),
                 (q * grow).astype(dtype), (k * rest).astype(dtype),
                 jnp.exp(gamma[..., -1, :])[..., None])
    state = jnp.zeros((batch, heads, k.shape[-1], d_v), f32)
    _, out = lax.scan(one_chunk, state, jax.tree.map(
        lambda x: jnp.moveaxis(x, 2, 0), per_chunk))
    return out.transpose(1, 0, 3, 2, 4).reshape(
        batch, count * chunk, heads, d_v)


def _exact(a, b):
    """``a @ b`` over the last two axes, float32 at full precision (a
    float32 product at the TPU's default is one bfloat16 pass)."""
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _unit_lower_inverse(a):
    """``(I + A)^-1`` for strictly lower triangular float32 ``a [..., C,
    C]``, by block doubling: the diagonal blocks' inverses at width 1 are
    1, and two neighbours ``T11``, ``T22`` of width ``b`` with the ``A21``
    between them make the next level's ``[[T11, 0], [-T22 A21 T11,
    T22]]``, which is ``[[L11, 0], [A21, L22]]^-1``: the block form of
    forward substitution, ``log2 C`` levels (not the product of ``I +
    (-A)^(2^j)``, whose powers grow without bound when keys align and
    ``beta`` nears 2). The systems lie along the lanes (``[C, C,
    systems]``) and a level's two products are float32 multiply-adds over
    them: as products on the MXU the levels are memory passes or fill a
    corner of it each (``PERF.md`` §6, PR 31). A ``C`` that is no power of
    two is padded to one with zeros: the padded matrix's inverse holds
    the wanted one in its corner."""
    size, lead = a.shape[-1], a.shape[:-2]
    full = 1 << (size - 1).bit_length()
    a = jnp.moveaxis(a.reshape((-1, size, size)), 0, -1)
    a = jnp.pad(a, [(0, full - size)] * 2 + [(0, 0)])

    def times(left, right):  # [pairs, i, j, systems] x [pairs, j, k, systems]
        return (left[:, :, :, None] * right[:, None]).sum(2)

    # top down: every diagonal block gives its A21 and its two halves
    below, parts = [], a[None]
    while parts.shape[1] > 1:
        half = parts.shape[1] // 2
        below.append(parts[:, half:, :half])
        parts = jnp.stack([parts[:, :half, :half], parts[:, half:, half:]],
                          1).reshape((-1, half, half, a.shape[-1]))
    # bottom up: neighbours' inverses and their A21 make the pair's
    blocks = jnp.ones_like(parts)
    for a21 in reversed(below):
        halves = blocks.reshape((-1, 2) + blocks.shape[1:])
        t11, t22 = halves[:, 0], halves[:, 1]
        t21 = -times(times(t22, a21), t11)
        blocks = jnp.concatenate([
            jnp.concatenate([t11, jnp.zeros_like(t11)], 2),
            jnp.concatenate([t21, t22], 2)], 1)
    return jnp.moveaxis(blocks[0, :size, :size], -1, 0).reshape(
        lead + (size, size))


@jax.custom_vjp
def solve_unit_lower(a, rhs):
    """``X`` of ``(I + A) X = rhs`` for strictly lower triangular ``a
    [..., C, C]`` and ``rhs [..., C, n]``, float32: the inverse by
    :func:`_unit_lower_inverse`, applied by one product. Differentiated by
    its own rule, which keeps the inverse and ``X`` and solves nothing:
    JAX's would keep every level's intermediates for the backward pass."""
    return _solve_forward(a, rhs)[0]


def _solve_forward(a, rhs):
    t = _unit_lower_inverse(a)
    x = _exact(t, rhs)
    return x, (t, x)


def _solve_backward(kept, x_bar):
    t, x = kept
    rhs_bar = _exact(jnp.swapaxes(t, -1, -2), x_bar)
    return -jnp.tril(_exact(rhs_bar, jnp.swapaxes(x, -1, -2)), -1), rhs_bar


solve_unit_lower.defvjp(_solve_forward, _solve_backward)


# The chunk loop's kernels' name (not ``flash_attention``, nor the pair
# kernels'): XLA names the custom call's instruction after it.
SCAN_KERNEL_NAME = "kda_chunk_scan"
SCAN_HEADS_A_STEP = 8  # of a grid step: their chains of products overlap


def _scan_heads_a_step(k, v, chunk: int) -> int:
    """The heads a grid step of :func:`chunk_scan_kernel` takes, the
    largest divisor of the heads up to ``SCAN_HEADS_A_STEP``, where the
    shapes fill a TPU's tiles, or 0 where they do not and the plain form is
    traced: ``d_k`` and ``d_v`` whole 128-lane blocks of ``k``, ``v [B, S,
    H, d]``, the chunk a power of two (the solve halves it) of whole
    sublane tiles of their type, and a step's heads whole sublane tiles of
    ``beta``'s rows."""
    heads = k.shape[2]
    rows = 32 // min(k.dtype.itemsize, v.dtype.itemsize)
    step = _heads_a_step(heads)
    fills = (k.shape[-1] % 128 == 0 and v.shape[-1] % 128 == 0
             and chunk % rows == 0 and chunk & (chunk - 1) == 0
             and step % 8 == 0)
    return step if fills else 0


def _heads_a_step(heads: int) -> int:
    return max(n for n in range(1, SCAN_HEADS_A_STEP + 1) if not heads % n)


def _terms(x):
    """Float32 ``x`` as the sum of three float32 terms that are each a
    bfloat16 number, largest first: a term is the top eight significant
    bits of what the ones before left (a mask and a subtraction a term: 24
    bits in all, nothing rounded); a bfloat16 ``x`` is its own one term."""
    f32, top = jnp.float32, jnp.uint32(0xFFFF0000)
    if x.dtype == jnp.bfloat16:
        return [x.astype(f32)]
    terms, x = [], x.astype(f32)
    for _ in range(2):
        terms.append(lax.bitcast_convert_type(
            lax.bitcast_convert_type(x, jnp.uint32) & top, f32))
        x = x - terms[-1]
    return terms + [x]


def _dot32(left, right, dims=NN):
    """A product of float32 operands at full precision, as :func:`_exact`:
    the six products of their bfloat16 terms that ``Precision.HIGHEST`` is
    on a TPU (``i + j <= 2``; three where an operand is bfloat16 as it
    stands), the smallest first, as ONE product whose contraction runs
    over all of them: the MXU takes each operand's terms once and sums in
    float32 (asked for by ``precision`` the kernel pays six passes, each
    with its own float32 operands pushed and results popped). The terms are
    laid side by side as float32 and rounded to bfloat16 together, which
    rounds nothing."""
    (over_left,), (over_right,) = dims[0]
    a, b = _terms(left), _terms(right)
    pairs = sorted(((i, j) for i in range(len(a)) for j in range(len(b))
                    if i + j <= 2), key=lambda p: -sum(p))
    return dot(
        jnp.concatenate([a[i] for i, _ in pairs], over_left).astype(
            jnp.bfloat16),
        jnp.concatenate([b[j] for _, j in pairs], over_right).astype(
            jnp.bfloat16), dims)


def _iota(shape, axis: int):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _turned(x):
    """A row ``[1, n]`` as the column ``[n, 1]``, or a column as the row:
    the diagonal of its broadcast summed the other way, one term a sum."""
    n = max(x.shape)
    return jnp.sum(
        jnp.where(_iota((n, n), 0) == _iota((n, n), 1), x, 0.0),
        axis=1 if x.shape[0] == 1 else 0, keepdims=True)


NARROW = 16  # the diagonal blocks solved a column at a time, not doubled


def _inverses_in_vmem(matrices):
    """``(I + A)^-1`` of each strictly lower triangular float32 ``a [C, P *
    C]`` of ``matrices``, ``P`` heads' matrices side by side along the
    lanes. The diagonal blocks of ``NARROW`` rows first, all of a matrix
    at once, block ``b`` of rows on its own lanes (``[NARROW, P * C]``):
    forward substitution a column at a time, float32 multiply-adds (``X =
    I``, then ``X_i -= A_ij X_j`` for the rows under ``j``: fifteen steps of
    a lane gather, a row's broadcast and a multiply-add a register). Then
    :func:`_unit_lower_inverse`'s block doubling: with ``D`` the inverses
    of the diagonal blocks of one width and ``A21`` the blocks between
    neighbours, ``D - D A21 D`` holds those of twice the width (``-T22 A21
    T11`` below each pair), a level two float32 products at full precision
    whose right operand holds the heads' matrices down its diagonal, so
    that the heads share the MXU's passes. Level by level over all the
    matrices: their chains are independent and overlap."""
    size, lanes = matrices[0].shape
    narrow = min(NARROW, size)
    row, col = _iota((size, lanes), 0), _iota((size, lanes), 1) & (size - 1)

    def diagonal(w):  # [C, P C] -> [P C, P C], head p's matrix at (p, p)
        if lanes == size:
            return w
        tall = jnp.concatenate([w] * (lanes // size), 0)
        same = (_iota(tall.shape, 0) // size == _iota(tall.shape, 1) // size)
        return jnp.where(same, tall, 0.0)

    own = row // narrow == col // narrow  # the diagonal blocks
    lane = _iota((narrow, lanes), 1)
    blocks = [jnp.where(own, a, 0.0).reshape(-1, narrow, lanes).sum(0)
              for a in matrices]
    eye = jnp.where(_iota((narrow, lanes), 0) == lane % narrow, 1.0, 0.0)
    solved = [eye] * len(matrices)
    for j in range(narrow - 1):
        solved = [x - jnp.take_along_axis(
            under, lane - lane % narrow + j, 1) * x[j:j + 1]
                  for x, under in zip(solved, blocks)]
    inverses = [jnp.where(own, jnp.concatenate([x] * (size // narrow), 0),
                          0.0) for x in solved]
    for level in range(narrow.bit_length() - 1, size.bit_length() - 1):
        below = ((row >> level) & 1 == 1) & (
            (col >> level) == (row >> level) - 1)
        halves = [_dot32(inverse, diagonal(jnp.where(below, a, 0.0)))
                  for inverse, a in zip(inverses, matrices)]
        inverses = [inverse - _dot32(half, diagonal(inverse))
                    for inverse, half in zip(inverses, halves)]
    return inverses


def _solved_heads(beta_ref, a_ref, inverse_ref=None):
    """``(r, beta [1, C], (I + A)^-1 [C, C])`` of every head ``r`` of a grid
    step, ``A = tril(beta * a, -1)``: the inverses read back
    (``inverse_ref``) or formed, as many heads side by side as fill a lane
    block."""
    heads, size, _ = a_ref.shape
    first = pl.multiple_of(pl.program_id(2) * heads, heads)
    betas = beta_ref[pl.ds(first, heads), :]
    beta = [betas[r:r + 1, :] for r in range(heads)]
    if inverse_ref is not None:
        return [(r, beta[r], inverse_ref[r]) for r in range(heads)]
    lower = _iota((size, size), 0) > _iota((size, size), 1)
    side = math.gcd(heads, max(1, 128 // size))
    inverses = _inverses_in_vmem([
        jnp.concatenate([jnp.where(lower, _turned(beta[p]) * a_ref[p], 0.0)
                         for p in range(r, r + side)], 1)
        for r in range(0, heads, side)])
    return [(r, beta[r],
             inverses[r // side][:, r % side * size:(r % side + 1) * size])
            for r in range(heads)]


def _lanes(ref, r: int, heads: int):
    """Head ``r``'s lanes of a block ``[C, heads * d]`` of ``[B, S, H *
    d]``."""
    width = ref.shape[-1] // heads
    return slice(r * width, (r + 1) * width)


def _chunks_of_the_heads(q_ref, k_ref, v_ref, gamma_ref, inside_ref, solved,
                         states):
    """Every head's chunk up to the state's step, all that both kernels
    form alike from the operands (``q``, ``k``, ``v``, ``gamma`` a head a
    lane block of the chunk's rows), ``solved`` (:func:`_solved_heads`) and
    the float32 ``states`` that enter: the plain form's values at its
    rounding points, a dict a head. ``beta`` scales the inverse's columns
    where the plain form scales the right side's rows (``T (beta x) = (T
    Diag(beta)) x``), so that ``v`` goes into its product as it stands: one
    term where it is bfloat16. **A stage over all the heads, then the next
    stage**: the heads' chains of products are independent, and the
    compiler overlaps what it finds side by side."""
    f32, dtype = jnp.float32, v_ref.dtype
    heads, count = [], len(solved)
    for (r, beta, inverse), state in zip(solved, states):
        gamma = gamma_ref[:, _lanes(gamma_ref, r, count)]
        size = gamma.shape[0]
        q = q_ref[:, _lanes(q_ref, r, count)].astype(f32)
        k = k_ref[:, _lanes(k_ref, r, count)].astype(f32)
        grow = jnp.exp(gamma)
        rest = jnp.exp(gamma[size - 1:size] - gamma)
        heads.append(dict(
            q=q, k=k, grow=grow, rest=rest, grown=k * grow,
            kept=grow[size - 1:size], scaled=inverse * beta,
            entered=state.astype(dtype), q_in=(q * grow).astype(dtype),
            k_out=(k * rest).astype(dtype),
            inside=inside_ref[r].astype(dtype)))
    for (r, _, _), c in zip(solved, heads):
        c["u"] = _dot32(c["scaled"], v_ref[:, _lanes(v_ref, r, count)])
        c["w"] = _dot32(c["scaled"], c["grown"])
        c["rounded"] = c["w"].astype(dtype)
    for c in heads:
        c["new"] = (c["u"] - dot(c["rounded"], c["entered"])).astype(dtype)
    return heads


def _scan_forward_kernel(q_ref, k_ref, v_ref, gamma_ref, beta_ref, inside_ref,
                         a_ref, o_ref, *rest):
    """One chunk of the heads of a grid step: ``q``, ``k``, ``v``, ``gamma``
    ``[C, R * d]`` where they lie in ``[B, S, H * d]``, the pair terms ``[R,
    C, C]``, ``beta [H, C]`` (a head a row). The solve
    (:func:`_inverses_in_vmem` and two float32 products),
    then :func:`_chunk_scan`'s ``one_chunk`` on the head's float32 state
    ``[d_k, d_v]``, which ``state_ref`` keeps across the chunks. ``o`` goes
    where it lies in ``[B, S, H * d_v]``. The last of ``rest`` but the
    scratch, where a backward pass follows, take the state that enters the
    chunk and the inverse."""
    *kept_refs, state_ref = rest
    n, h = pl.program_id(1), pl.program_id(2)

    @pl.when(n == 0)
    def _():
        state_ref[h] = jnp.zeros(state_ref.shape[1:], state_ref.dtype)

    # the heads' states are read together and written together: the stages
    # between run over all the heads
    states, solved = state_ref[h], _solved_heads(beta_ref, a_ref)
    heads = _chunks_of_the_heads(q_ref, k_ref, v_ref, gamma_ref, inside_ref,
                                 solved, states)
    for (r, _, inverse), c, state in zip(solved, heads, states):
        out = dot(c["q_in"], c["entered"]) + dot(c["inside"], c["new"])
        o_ref[:, _lanes(o_ref, r, len(solved))] = out.astype(o_ref.dtype)
        for ref, value in zip(kept_refs, (state, inverse)):
            ref[r] = value
    state_ref[h] = jnp.stack([
        _turned(c["kept"]) * state + dot(c["k_out"], c["new"], TN)
        for c, state in zip(heads, states)])


def _scan_backward_kernel(q_ref, k_ref, v_ref, gamma_ref, beta_ref,
                          inside_ref, a_ref, enter_ref, inverse_ref,
                          o_bar_ref, q_bar_ref, k_bar_ref, v_bar_ref,
                          gamma_bar_ref, beta_bar_ref, inside_bar_ref,
                          a_bar_ref, ahead_ref):
    """The same chunk, the chunks last to first (the index maps turn
    them), with ``dO``: ``ahead_ref [d_k, d_v]`` a head is the cotangent of
    the state that leaves the chunk. The entering state and the inverse come
    from HBM, the solution and the loop's operands are formed again. The
    solve's rule is :func:`_solve_backward`'s (``T^T dX`` and ``-tril(. X^T,
    -1)``, float32 at full precision). Cotangents of ``dtype`` operands go
    into their products rounded to ``dtype`` and come out of them rounded to
    ``dtype``, as the plain form's transposed products take and give them.
    What ``rest`` takes away from a row of ``gamma`` and gives its last is
    one float32 number."""
    n, h = pl.program_id(1), pl.program_id(2)
    heads, size, _ = a_ref.shape
    d_k = k_ref.shape[-1] // heads
    dtype, f32 = v_ref.dtype, jnp.float32

    @pl.when(n == 0)
    def _():
        ahead_ref[h] = jnp.zeros(ahead_ref.shape[1:], ahead_ref.dtype)

    lower = _iota((size, size), 0) > _iota((size, size), 1)
    last = _iota((size, d_k), 0) == size - 1
    first = pl.multiple_of(h * heads, heads)

    def given(x):  # a dtype operand's cotangent, as JAX hands it on
        return x.astype(dtype).astype(f32)

    states, aheads = enter_ref[...], ahead_ref[h]
    solved = _solved_heads(beta_ref, a_ref, inverse_ref)
    chunks = _chunks_of_the_heads(q_ref, k_ref, v_ref, gamma_ref, inside_ref,
                                  solved, states)
    # stage by stage over the heads, as the forward's
    for (r, beta, _), c, ahead in zip(solved, chunks, aheads):
        c["by_row"] = _turned(beta)
        c["o_bar"] = o_bar_ref[:, _lanes(o_bar_ref, r, heads)].astype(dtype)
        c["led"] = ahead.astype(dtype)
    for (r, _, _), c in zip(solved, chunks):
        # o = q_in entered + inside new; leaving = kept state + k_out^T new
        c["q_in_bar"] = given(dot(c["o_bar"], c["entered"], NT))
        inside_bar_ref[r] = given(dot(c["o_bar"], c["new"], NT))
        c["k_out_bar"] = given(dot(c["new"], c["led"], NT))
        c["new_bar"] = (given(dot(c["inside"], c["o_bar"], TN))
                        + given(dot(c["k_out"], c["led"])))
        c["sent"] = c["new_bar"].astype(dtype)
    leaving = []
    for c, ahead in zip(chunks, aheads):
        # new = u - rounded entered
        c["w_bar"] = (-dot(c["sent"], c["entered"], NT)).astype(dtype)
        leaving.append(
            _turned(c["kept"]) * ahead
            + given(dot(c["q_in"], c["o_bar"], TN))
            + given(-dot(c["rounded"], c["sent"], TN)))
    ahead_ref[h] = jnp.stack(leaving)
    for (_, _, inverse), c in zip(solved, chunks):
        # (I + A) [u | w] = beta [v | k grow]
        c["v_side"] = _dot32(inverse, c["new_bar"], TN)
        c["k_side"] = _dot32(inverse, c["w_bar"], TN)
    beta_bars = []
    for (r, _, _), c, state, ahead in zip(solved, chunks, states, aheads):
        v_side, k_side, by_row = c["v_side"], c["k_side"], c["by_row"]
        a_bar = jnp.where(lower, -(_dot32(v_side, c["u"], NT)
                                   + _dot32(k_side, c["w"], NT)), 0.0)
        a_bar_ref[r] = by_row * a_bar
        beta_bars.append(_turned(
            jnp.sum(a_bar * a_ref[r], 1, keepdims=True)
            + jnp.sum(v_side * v_ref[:, _lanes(v_ref, r, heads)].astype(f32),
                      1, keepdims=True)
            + jnp.sum(k_side * c["grown"], 1, keepdims=True)))
        v_bar_ref[:, _lanes(v_bar_ref, r, heads)] = (by_row * v_side).astype(
            v_bar_ref.dtype)
        grown_bar, keys = by_row * k_side, _lanes(k_bar_ref, r, heads)
        q_bar_ref[:, keys] = (c["q_in_bar"] * c["grow"]).astype(
            q_bar_ref.dtype)
        k_bar_ref[:, keys] = (grown_bar * c["grow"] + c["k_out_bar"]
                              * c["rest"]).astype(k_bar_ref.dtype)
        # gamma's last row is in every row's rest and in kept
        rested = c["k_out_bar"] * c["k"] * c["rest"]
        to_last = (jnp.sum(rested, 0, keepdims=True) + c["kept"] * _turned(
            jnp.sum(ahead * state, 1, keepdims=True)))
        gamma_bar_ref[:, keys] = (
            (grown_bar * c["k"] + c["q_in_bar"] * c["q"]) * c["grow"] - rested
            + jnp.where(last, to_last, 0.0))
    beta_bar_ref[pl.ds(first, heads), :] = jnp.concatenate(beta_bars, 0)


def _scan_call(kernel, operands, results, scratch, *, shape, turned, step,
               interpret):
    """``kernel`` over the grid ``(B, chunks, H / step)``, the heads
    innermost (``kernel_parts.chunk_grid_call``), for ``shape = (B, H, N,
    C, d_k, d_v)``. ``operands`` and ``results`` are ``(kind, array or
    dtype)``, a block of each kind one chunk of one step's heads: ``keys``
    / ``values [B, S, H * d_k | d_v]``
    (``q``, ``k``, ``gamma`` / ``v``, ``o`` and all their cotangents **where
    the projections wrote them and the gate and the convolution's backward
    read them**: the chunk's rows of the step's lane blocks, a head
    ``ref[:, r * d:(r + 1) * d]``, nothing transposed on either side),
    ``pairs [B, H, N, C, C]`` (kernel to kernel), ``rows [B, N, H, C]``
    (``beta``: every head's row of the chunk, which stays while the chunk
    lasts), ``states [B, N, H, d_k, d_v]``. ``turned``: the chunks last to
    first."""
    batch, heads, count, chunk, d_k, d_v = shape

    def tokens(width):
        return ((batch, count * chunk, heads * width),
                (None, chunk, step * width), lambda i, n, h: (i, n, h))

    kinds = {
        "keys": tokens(d_k), "values": tokens(d_v),
        "pairs": ((batch, heads, count, chunk, chunk),
                  (None, step, None, chunk, chunk),
                  lambda i, n, h: (i, h, n, 0, 0)),
        "rows": ((batch, count, heads, chunk), (None, None, heads, chunk),
                 lambda i, n, h: (i, n, 0, 0)),
        "states": ((batch, count, heads, d_k, d_v),
                   (None, None, step, d_k, d_v),
                   lambda i, n, h: (i, n, h, 0, 0)),
    }
    return chunk_grid_call(
        kernel, kinds, operands, results,
        [pltpu.VMEM((heads // step, step, d_k, d_v), jnp.float32)] + scratch,
        grid=(batch, count, heads // step), turned=turned,
        interpret=interpret, name=SCAN_KERNEL_NAME)


def _scan_shape(k, v, chunk):
    batch, seq, heads, d_k = k.shape
    return batch, heads, seq // chunk, chunk, d_k, v.shape[-1]


def _scan_operands(q, k, v, gamma, beta, inside, a):
    """As :func:`_scan_call` takes the seven that both kernels read:
    ``beta [B, H, N, C, 1]`` a head a row."""
    return [("keys", q), ("keys", k), ("values", v), ("keys", gamma),
            ("rows", jnp.swapaxes(beta[..., 0], 1, 2)), ("pairs", inside),
            ("pairs", a)]


_KEPT = ("states", "pairs")  # the entering states and the inverses


def _scan_forward_by_kernel(*operands, states, chunk, **how):
    k, v = operands[1:3]
    f32 = jnp.float32
    o, *kept = _scan_call(
        _scan_forward_kernel, _scan_operands(*operands),
        [("values", v.dtype)] + [(kind, f32) for kind in _KEPT] * states, [],
        shape=_scan_shape(k, v, chunk), turned=False, **how)
    return [o.reshape(v.shape)] + kept


def _scan_backward_by_kernel(q, k, v, gamma, beta, inside, a, entering,
                             inverse, o_bar, *, chunk, **how):
    f32 = jnp.float32
    bars = list(_scan_call(
        _scan_backward_kernel,
        _scan_operands(q, k, v, gamma, beta, inside, a)
        + [("states", entering), ("pairs", inverse), ("values", o_bar)],
        [("keys", q.dtype), ("keys", k.dtype), ("values", v.dtype),
         ("keys", f32), ("rows", f32), ("pairs", f32), ("pairs", f32)], [],
        shape=_scan_shape(k, v, chunk), turned=True, **how))
    bars[:4] = [bar.reshape(x.shape) for bar, x in zip(bars, (q, k, v, gamma))]
    bars[4] = jnp.swapaxes(bars[4], 1, 2)[..., None]
    return bars


def _kept_avals(k, v, chunk):
    """Of the float32 states that enter each chunk and of the inverses."""
    batch, heads, count, chunk, d_k, d_v = _scan_shape(k, v, chunk)
    return [k.update(shape=(batch, count, heads, d_k, d_v),
                     dtype=jnp.float32),
            k.update(shape=(batch, heads, count, chunk, chunk),
                     dtype=jnp.float32)]


def _chunk_scan_of_tokens(q, k, v, gamma, *more):
    """:func:`_chunk_scan` of ``q``, ``k``, ``v``, ``gamma [B, S, H, d]``."""
    chunk = more[-1].shape[-1]
    return _chunk_scan(*(_by_head(x, chunk) for x in (q, k, v, gamma)), *more)


def _scan_forward_plain(*operands, states, chunk, **_):
    # the plain backward differentiates the plain form and reads neither
    k, v = operands[1:3]
    unread = [jnp.zeros(x.shape, x.dtype)
              for x in _kept_avals(k, v, chunk)] * states
    return [_chunk_scan_of_tokens(*operands)] + unread


def _scan_backward_plain(*operands, **_):
    *operands, entering, inverse, o_bar = operands
    return jax.vjp(_chunk_scan_of_tokens, *operands)[1](o_bar)


def _scan_forward_results(q, k, v, *more, states, chunk, **_):
    return [v] + _kept_avals(k, v, chunk) * states


_scan_forward_p = where_lowered(
    "hvd_kda_chunk_scan", _scan_forward_results, _scan_forward_by_kernel,
    _scan_forward_plain)
_scan_backward_p = where_lowered(
    "hvd_kda_chunk_scan_backward", lambda *kept, **_: list(kept[:7]),
    _scan_backward_by_kernel, _scan_backward_plain)


def _unread_residuals(used, eqn):
    """The forward pass of a recomputed layer reads ``o`` alone (the
    policy keeps nothing of this primitive): it then writes neither the
    states nor the inverses."""
    if eqn.params["states"] and not any(used[1:]):
        eqn = eqn.replace(outvars=eqn.outvars[:1],
                          params=dict(eqn.params, states=False))
    return [any(used)] * len(eqn.invars), eqn if any(used) else None


pe.dce_rules[_scan_forward_p] = _unread_residuals


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def chunk_scan_kernel(q, k, v, gamma, beta, inside, a, interpret=False):
    """:func:`_chunk_scan` as one Pallas kernel and its backward pass as
    another in a program lowered for a TPU (anywhere, interpreted, where
    the tests say ``interpret``; the plain form itself on any other
    platform), at shapes :func:`_scan_heads_a_step` accepts. The grid walks
    a sequence's chunks in order, the heads in steps of eight innermost; a
    head's float32 state ``[d_k, d_v]`` stays in VMEM over its chunks, the
    solve is float32 in VMEM, and HBM sees ``q``, ``k``, ``v``, ``gamma [B,
    S, H, d]`` and ``o`` as ``[B, S, H * d]`` (their cotangents too: nothing
    is transposed around either kernel), ``beta [B, H, N, C, 1]`` and the
    pair terms ``[B, H, N, C, C]`` as :func:`kimi_delta_rule` hands them and,
    where a backward pass follows, the float32 states that enter each chunk
    and the inverses: the backward kernel's residuals with the operands.
    Same rounding points as the plain form. Each pass is a primitive of its
    own (``kernel_parts.where_lowered``), so a recomputed layer's policy
    sees no ``pallas_call`` whose results it would keep."""
    return _scan_forward_p.bind(q, k, v, gamma, beta, inside, a, states=False,
                                **_scan_how(k, a, interpret))[0]


def _scan_how(k, a, interpret):
    return dict(step=_heads_a_step(k.shape[2]), chunk=a.shape[-1],
                interpret=interpret)


def _scan_forward(q, k, v, gamma, beta, inside, a, interpret):
    operands = (q, k, v, gamma, beta, inside, a)
    o, *kept = _scan_forward_p.bind(*operands, states=True,
                                    **_scan_how(k, a, interpret))
    return o, operands + tuple(kept)


def _scan_backward(interpret, kept, o_bar):
    return tuple(_scan_backward_p.bind(
        *kept, o_bar, **_scan_how(kept[1], kept[6], interpret)))


chunk_scan_kernel.defvjp(_scan_forward, _scan_backward)


def _chunk_scan_where_lowered(q, k, v, gamma, beta, inside, a):
    """The solve and the loop by :func:`chunk_scan_kernel` where a TPU's
    tiles are filled, so that the program lowered for a TPU holds the
    kernels and any other the plain form; at any other shape the plain
    form whatever the platform."""
    chunk = a.shape[-1]
    if _scan_heads_a_step(k, v, chunk):
        return chunk_scan_kernel(q, k, v, gamma, beta, inside, a)
    return _chunk_scan_of_tokens(q, k, v, gamma, beta, inside, a)
