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
scope ``hvd.linattn.scan`` is around all of it, forward and backward, and
the gauge ``hvd_linattn_chunks_last{chunk,heads_here}`` says at trace time
how many chunks a sequence the step that runs scans.

:func:`kimi_delta_rule` is the same recurrence with **a decay a key
channel** (``g [B, S, H, d_k]``: ``S' = Diag(exp(g_t)) S``; Kimi Delta
Attention, arXiv:2510.26692, ``flash-linear-attention``'s ``kda``). Its
chunk form is the one above with ``gamma [C, d_k]``, but the decay now
sits inside the contraction, ``A_ij = beta_i sum_c k_ic k_jc exp(gamma_ic -
gamma_jc)``, so ``(k k^T) * decay`` is no more and the pair terms are
:func:`_pair_terms`'s. The solve, the scan over chunks, the scope and the
gauge are shared; ``hvd_linattn_decay_width_last`` says which rule the
step that runs holds (1, or ``d_k``). Where the shapes fill a TPU's tiles
(``d_k`` whole 128-lane blocks, ``sub`` whole sublane tiles) the pair terms
are a primitive whose lowering the platform chooses: for a TPU
:func:`pair_terms_kernel`'s Pallas kernel, which forms them in VMEM, with a
backward kernel of its own; for anything else, and at any other shape, the
plain :func:`_pair_terms` (``hvd_linattn_pair_kernel_last`` says, as the
program is lowered, the chunks a grid step takes, or 0 for the plain form).
Its ``gamma`` is a float32 product of the chunk's lower triangle of ones
with ``g`` at ``Precision.HIGHEST``: summed as ``jnp.cumsum`` over the rows
of ``[C, d_k]`` it is a ``reduce-window``, which the v5e runs at a
fourteenth of its memory's pace.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend.core import Primitive
from jax.interpreters import mlir

from ..attribution import SCOPE_LINATTN_SCAN
from ..profiler import annotate_collective


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
PAIR_CHUNKS_A_STEP = 8  # of a grid step, where that many divide the chunks
_MASKED = -1e30  # an exponent above the diagonal: exp gives 0, never a nan
_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_TN = (((0,), (0,)), ((), ()))  # a^T @ b
_NN = (((1,), (0,)), ((), ()))  # a @ b


def _sub_block(q_ref, k_ref, gamma_ref, c, lo, hi, dtype):
    """Sub-block ``[lo, hi)`` of chunk ``c`` of a grid step: its rows of
    ``q`` and ``k`` in float32; the cube ``e_ijc`` of its own pairs in
    pieces ``(first row, [rows, columns, d])`` of eight rows (a float32
    tile's) against the columns up to their last, zero above the
    diagonal: the triangle's tiles alone; and for the pairs with
    the rows before it, through its first row ``r``: ``(q_i e^(gamma_i -
    gamma_r), k_i e^(gamma_i - gamma_r))`` stacked and ``k_j e^(gamma_r -
    gamma_j)``, rounded to ``dtype``, with the two float32 factors
    (``None`` for the first sub-block). No exponent is positive."""
    f32 = jnp.float32
    gamma = gamma_ref[c, lo:hi, :]
    q, k = q_ref[c, lo:hi, :].astype(f32), k_ref[c, lo:hi, :].astype(f32)
    pieces, rows = [], min(8, hi - lo)
    for top in range(0, hi - lo, rows):
        shape = (rows, top + rows, gamma.shape[-1])
        lower = (lax.broadcasted_iota(jnp.int32, shape, 1)
                 <= lax.broadcasted_iota(jnp.int32, shape, 0) + top)
        pieces.append((top, jnp.exp(jnp.where(
            lower, gamma[top:top + rows][:, None, :]
            - gamma[:top + rows][None, :, :], _MASKED))))
    if not lo:
        return q, k, pieces, None
    left = jnp.exp(gamma - gamma[:1])
    right = jnp.exp(gamma[:1] - gamma_ref[c, 0:lo, :])
    far = (jnp.concatenate([(q * left).astype(dtype),
                            (k * left).astype(dtype)], 0),
           (k_ref[c, 0:lo, :].astype(f32) * right).astype(dtype), left, right)
    return q, k, pieces, far


def _pair_forward_kernel(q_ref, k_ref, gamma_ref, inside_ref, a_ref, *,
                         sub, dtype):
    """``inside`` and ``a`` of the grid step's chunks, ``[chunks, C, C]``
    float32, sub-block by sub-block of rows: the columns before it one
    product of ``dtype`` operands for both, its own the cube's lane sums,
    those after a row's piece zero."""
    chunks, size, _ = q_ref.shape
    f32 = jnp.float32

    def one_chunk(c, carry):
        for lo in range(0, size, sub):
            hi = lo + sub
            q, k, pieces, far = _sub_block(q_ref, k_ref, gamma_ref, c, lo,
                                           hi, dtype)
            if far is not None:
                both = lax.dot_general(far[0], far[1], _NT,
                                       preferred_element_type=f32)
                inside_ref[c, lo:hi, 0:lo] = both[:sub]
                a_ref[c, lo:hi, 0:lo] = both[sub:]
            for top, cube in pieces:
                rows, columns = cube.shape[:2]
                here = slice(lo + top, lo + top + rows)
                near = cube * k[:columns][None, :, :]
                inside_ref[c, here, lo:lo + columns] = (
                    near * q[top:top + rows][:, None, :]).sum(-1)
                a_ref[c, here, lo:lo + columns] = (
                    near * k[top:top + rows][:, None, :]).sum(-1)
                if lo + columns < size:
                    above = jnp.zeros((rows, size - lo - columns), f32)
                    inside_ref[c, here, lo + columns:size] = above
                    a_ref[c, here, lo + columns:size] = above
        return carry

    lax.fori_loop(0, chunks, one_chunk, 0)


def _pair_backward_kernel(q_ref, k_ref, gamma_ref, inside_bar_ref, a_bar_ref,
                          q_bar_ref, k_bar_ref, gamma_bar_ref, right_ref, *,
                          sub, dtype):
    """``dq``, ``dk``, ``dgamma`` of the grid step's chunks from the two
    cotangents: the forward's factors formed again, sub-blocks last to
    first, so that a row's cotangent as a pair's right side (``right_ref``,
    float32 scratch) is whole when its own sub-block is done. The sums run
    over ``i`` or ``j``, never over lanes. By term ``dgamma_i = q_i dq_i +
    k_i (dk_i as the left side - dk_i as the right side)``: no cube of its
    own. Cotangents of ``dtype`` operands go into their products rounded
    to ``dtype``, as the plain form's transposed products take them."""
    chunks, size, _ = q_ref.shape
    f32 = jnp.float32

    def one_chunk(c, carry):
        right_ref[...] = jnp.zeros_like(right_ref)
        for lo in reversed(range(0, size, sub)):
            hi = lo + sub
            q, k, pieces, far = _sub_block(q_ref, k_ref, gamma_ref, c, lo,
                                           hi, dtype)
            q_bar, as_left = [], []
            for top, cube in pieces:
                rows, columns = cube.shape[:2]
                here = slice(lo + top, lo + top + rows)
                by_inside = inside_bar_ref[c, here, lo:lo + columns][
                    :, :, None] * cube
                by_a = a_bar_ref[c, here, lo:lo + columns][:, :, None] * cube
                q_bar.append((by_inside * k[:columns][None, :, :]).sum(1))
                as_left.append((by_a * k[:columns][None, :, :]).sum(1))
                right_ref[lo:lo + columns, :] += (
                    by_inside * q[top:top + rows][:, None, :]
                    + by_a * k[top:top + rows][:, None, :]).sum(0)
            q_bar, as_left = jnp.concatenate(q_bar), jnp.concatenate(as_left)
            if far is not None:
                stacked, k_right, left, right = far
                bars = jnp.concatenate([inside_bar_ref[c, lo:hi, 0:lo],
                                        a_bar_ref[c, lo:hi, 0:lo]],
                                       0).astype(dtype)
                to_left = lax.dot_general(bars, k_right, _NN,
                                          preferred_element_type=f32)
                q_bar = q_bar + to_left[:sub] * left
                as_left = as_left + to_left[sub:] * left
                right_ref[0:lo, :] += right * lax.dot_general(
                    bars, stacked, _TN, preferred_element_type=f32)
            as_right = right_ref[lo:hi, :]
            q_bar_ref[c, lo:hi, :] = q_bar.astype(q_bar_ref.dtype)
            k_bar_ref[c, lo:hi, :] = (as_left + as_right).astype(
                k_bar_ref.dtype)
            gamma_bar_ref[c, lo:hi, :] = q * q_bar + k * (as_left - as_right)
        return carry

    lax.fori_loop(0, chunks, one_chunk, 0)


def _chunks_a_step(count: int) -> int:
    """The largest divisor of ``count`` chunks up to
    ``PAIR_CHUNKS_A_STEP``."""
    return max(n for n in range(1, PAIR_CHUNKS_A_STEP + 1) if not count % n)


def _pair_call(kernel, operands, widths, dtypes, scratch=(), *, step, sub,
               dtype, interpret):
    """``kernel`` over ``operands [chunks, C, width]`` in grid steps of
    ``step`` chunks, giving ``[chunks, C, widths[n]]`` in ``dtypes[n]``."""
    count, size = operands[0].shape[:2]

    def block(width):
        return pl.BlockSpec((step, size, width), lambda n: (n, 0, 0))

    return pl.pallas_call(
        functools.partial(kernel, sub=sub, dtype=dtype),
        grid=(count // step,),
        in_specs=[block(x.shape[-1]) for x in operands],
        out_specs=[block(width) for width in widths],
        out_shape=[jax.ShapeDtypeStruct((count, size, width), kind)
                   for width, kind in zip(widths, dtypes)],
        scratch_shapes=scratch,
        interpret=interpret,
        name=PAIR_KERNEL_NAME,
    )(*operands)


def _forward_by_kernel(q, k, gamma, **how):
    size = k.shape[-2]
    return _pair_call(_pair_forward_kernel, [q, k, gamma], [size, size],
                      [jnp.float32] * 2, **how)


def _backward_by_kernel(q, k, gamma, inside_bar, a_bar, **how):
    size, width = k.shape[-2:]
    return _pair_call(
        _pair_backward_kernel, [q, k, gamma, inside_bar, a_bar], [width] * 3,
        [q.dtype, k.dtype, jnp.float32],
        [pltpu.VMEM((size, width), jnp.float32)], **how)


def _forward_plain(q, k, gamma, *, sub, dtype, **_):
    return _pair_terms(q, k, gamma, sub, dtype)


def _backward_plain(q, k, gamma, *bars, sub, dtype, **_):
    # the factors and the cubes again from the operands, as jax.checkpoint's
    return jax.vjp(lambda *xs: _pair_terms(*xs, sub, dtype),
                   q, k, gamma)[1](bars)


def _where_lowered(name, results, by_kernel, plain, record):
    """The primitive ``name`` whose lowering for a TPU is ``by_kernel`` and
    for any other platform ``plain`` (``by_kernel`` interpreted where the
    tests say ``interpret``), each called with the primitive's parameters:
    the lowering platform is what the code can observe, and a trace does
    not know it (``benchmark/aot.py`` lowers for a v5e from a CPU;
    ``jax.default_backend()`` would say ``cpu`` there). Only the chosen form
    is ever traced, and ``record(kernel, **how)`` says which (a gauge) as it
    is lowered. ``results(*avals, **how)`` are the results' abstract values."""
    primitive = Primitive(name)
    primitive.multiple_results = True
    primitive.def_abstract_eval(lambda *avals, **how: results(*avals, **how))

    @functools.cache
    def alone(**how):  # called outside any trace
        return jax.jit(functools.partial(primitive.bind, **how))

    primitive.def_impl(lambda *xs, **how: alone(**how)(*xs))

    def lowering(on_tpu):
        def form(*xs, interpret, **how):
            kernel = on_tpu or interpret
            record(kernel, **how)
            return (by_kernel if kernel else plain)(
                *xs, interpret=interpret, **how)

        return mlir.lower_fun(form, multiple_results=True)

    mlir.register_lowering(primitive, lowering(True), platform="tpu")
    mlir.register_lowering(primitive, lowering(False))
    return primitive


_pair_forward_p = _where_lowered(
    "hvd_kda_pair_terms", lambda q, k, gamma, **_: [
        gamma.update(shape=k.shape[:-1] + k.shape[-2:-1])] * 2,
    _forward_by_kernel, _forward_plain, lambda *a, **k: _pair_form(*a, **k))
_pair_backward_p = _where_lowered(
    "hvd_kda_pair_terms_backward", lambda *kept, **_: list(kept[:3]),
    _backward_by_kernel, _backward_plain,
    lambda *a, **k: _pair_form(*a, **k))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def pair_terms_kernel(q, k, gamma, sub, dtype, interpret=False):
    """:func:`_pair_terms` as one Pallas kernel, and its backward pass as
    another, in a program lowered for a TPU (anywhere, interpreted, where
    the tests say ``interpret``; the plain form itself on any other
    platform): a grid step takes some chunks' ``q``, ``k`` and ``gamma``
    into VMEM and nothing between them and the two ``[C, C]`` results is
    written to HBM (the plain form writes ``k_right``, four times ``k``,
    and the sub-blocks' cubes). The same reference rows, the same
    rounding points: a pair in different sub-blocks is a product of
    ``dtype`` operands with float32 accumulation, a pair inside one is
    float32 throughout. The residuals are the operands; the backward
    kernel forms the factors again in VMEM. Each pass is a primitive of
    its own (:func:`_where_lowered`), so a recomputed layer's policy sees
    no ``pallas_call`` whose results it would keep (134 MB a layer at
    8,192 tokens that no backward kernel wants): they are formed again in
    the backward pass, as the plain form's under ``jax.checkpoint``."""
    return _pair_forward(q, k, gamma, sub, dtype, interpret)[0]


def _flat(x):  # [..., C, width] -> [chunks, C, width]
    return x.reshape((-1,) + x.shape[-2:])


def _how(k, sub, dtype, interpret):
    return dict(step=_chunks_a_step(math.prod(k.shape[:-2])), sub=sub,
                dtype=jnp.dtype(dtype), interpret=interpret)


def _pair_forward(q, k, gamma, sub, dtype, interpret):
    inside, a = _pair_forward_p.bind(
        _flat(q), _flat(k), _flat(gamma), **_how(k, sub, dtype, interpret))
    lead = k.shape[:-1] + k.shape[-2:-1]
    return (inside.reshape(lead), a.reshape(lead)), (q, k, gamma)


def _pair_backward(sub, dtype, interpret, kept, bars):
    k = kept[1]
    out = _pair_backward_p.bind(
        *(_flat(x) for x in kept + tuple(bars)),
        **_how(k, sub, dtype, interpret))
    return tuple(x.reshape(k.shape) for x in out)


pair_terms_kernel.defvjp(_pair_forward, _pair_backward)


def _pair_terms_where_lowered(q, k, gamma, sub, dtype):
    """The pair terms by :func:`pair_terms_kernel` where a TPU's tiles are
    filled (``d_k`` whole lanes, ``sub`` whole sublanes of ``q``'s and
    ``k``'s type), so that the program lowered for a TPU holds the kernels
    and any other the plain form; at any other shape the plain form under
    ``jax.checkpoint`` whatever the platform."""
    rows = 32 // min(q.dtype.itemsize, k.dtype.itemsize)  # a tile's sublanes
    if k.shape[-1] % 128 == 0 and sub % rows == 0:
        return pair_terms_kernel(q, k, gamma, sub, dtype)
    _record_pair_path(0, sub)
    return jax.checkpoint(
        lambda q, k, gamma: _pair_terms(q, k, gamma, sub, dtype))(q, k, gamma)


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
    are 11."""
    batch, seq, heads, d_v = v.shape
    if seq % chunk or chunk % sub:
        raise ValueError(
            f"kimi_delta_rule: a sequence of {seq} is no multiple of the "
            f"chunk of {chunk}, or the chunk none of the sub-block of "
            f"{sub}; pad it upstream")
    count, dtype, f32 = seq // chunk, v.dtype, jnp.float32
    _record_chunks(count, chunk, heads, decay_width=k.shape[-1])

    def chunks(x):  # [B, S, H, ...] -> [B, H, chunks, chunk, ...]
        x = x.reshape((batch, count, chunk) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    def product(spec, a, b):
        return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                          preferred_element_type=f32)

    with annotate_collective(SCOPE_LINATTN_SCAN):
        q, k, v = chunks(q), chunks(k), chunks(v)
        beta = chunks(beta.astype(f32))[..., None]
        # Batch, head and chunk are batch dimensions of the running sum's
        # product, the triangle broadcast (XLA never writes it out): a
        # recomputed layer's policy keeps every product without one, and
        # gamma would stay, 134 MB a layer; with the chunk alone as one
        # gamma comes out chunk-major and the v5e's step is 32 ms longer.
        ones = jnp.broadcast_to(jnp.tril(jnp.ones((chunk, chunk), f32)),
                                (batch, heads, count, chunk, chunk))
        gamma = jnp.einsum(                                # [B, H, N, C, d_k]
            "bhnij,bhnjd->bhnid", ones, chunks(g.astype(f32)),
            precision=lax.Precision.HIGHEST, preferred_element_type=f32)
        grow = jnp.exp(gamma)                              # from the chunk's start
        rest = jnp.exp(gamma[..., -1:, :] - gamma)         # to its end

        inside, a = _pair_terms_where_lowered(q, k, gamma, sub, dtype)
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
        return out.transpose(1, 0, 3, 2, 4).reshape(batch, seq, heads, d_v)


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


def _record_chunks(count: int, chunk: int, heads: int,
                   decay_width: int = 1) -> None:
    """At trace time, as ``models.experts._record_slots``: the step that
    runs scans this many chunks a sequence, under a decay that many wide
    a head (1: :func:`gated_delta_rule`'s scalar)."""
    from .. import metrics

    metrics.LINATTN_CHUNKS_LAST.set(
        count, chunk=str(chunk), heads_here=str(heads))
    metrics.LINATTN_DECAY_WIDTH_LAST.set(decay_width)


def _record_pair_path(chunks_a_step: int, sub: int) -> None:
    """As the program is lowered (at trace time where the shapes alone
    decide): the form of :func:`kimi_delta_rule`'s pair terms it holds,
    the kernels' chunks a grid step or 0 for the plain form."""
    from .. import metrics

    metrics.LINATTN_PAIR_KERNEL_LAST.set(chunks_a_step, sub=str(sub))


def _pair_form(kernel: bool, step: int, sub: int, **_) -> None:
    """:func:`_where_lowered`'s ``record`` for the pair terms (down here,
    and called late: no line above the rule's moves, so the positions in
    the kernels' bodies stay, ``tools/lowered_sha.py``)."""
    _record_pair_path(step if kernel else 0, sub)
