"""Flash (blockwise, online-softmax) attention — the local compute of the
sequence-parallel schemes, and the framework's hot-op Pallas deliverable.

No reference counterpart: the reference (Horovod) predates long-context
training and never partitions attention (SURVEY.md §6 "Long-context /
sequence parallelism: absent"); this subsystem is the TPU-native extension
the north star requires. Design sources are the public blockwise-attention
recipes (PAPERS.md): tile K/V, keep running max ``m``, normalizer ``l`` and
un-normalized output ``o`` in fp32, rescale on each new tile.

Two implementations, one semantics:
- ``flash_attention``: Pallas TPU kernels (MXU-tiled, fp32 accumulators in
  VMEM scratch); ``interpret=True`` makes them runnable on the CPU dev
  mesh. A sequence that is one tile runs a direct-softmax forward and one
  fused backward kernel on a grid of (batch*heads // G,): a grid step
  takes a group of G slices and computes them batched, because one
  128 x 64 slice alone is a chain of small dependent steps that leaves
  the units idle. ``_group_size`` picks G from the call's shapes (the
  largest divisor of batch*heads whose blocks and scores fit
  ``GROUP_BUDGET_BYTES`` of VMEM). The same two kernels
  take operands where a projection wrote them, tokens major
  (``flash_attention_tokens_major``: ``[B, S, H * D]``): a block is then
  the lanes of whole heads, two at D = 64, of a group of batch rows, the
  grid (B // G, lane blocks), and each head is computed out of its lanes
  of the block (``_head_lanes``); ``_heads_per_block`` says
  how many. Nothing is transposed in HBM on the way in or out. Longer
  sequences run
  (batch*heads, Q blocks, K blocks) with K innermost in the forward and
  the dq kernel and (batch*heads, K blocks, Q blocks) with Q innermost in
  the dk/dv kernel, one K/V (or Q/dO) tile resident a step. These too
  take tokens-major operands where a head is whole 128-lane blocks (``D %
  128 == 0``): grids, bodies and the order of tiles stay, a head is the
  lane block the index maps find (``_block_at``), so the results are the
  head-major call's bits (a call's lowered text shows in its operands'
  shapes which way it was fed). Differentiable:
  a ``jax.custom_vjp`` supplies the backward kernels from saved
  (out, logsumexp) residuals, so ring attention trains end-to-end.
- ``blockwise_attention_reference``: pure-jnp same math; the numerics
  oracle in tests. The kernel requires block-divisible sequence lengths
  (raises otherwise) — pad upstream, or call the reference directly for
  ragged shapes.

Causal masking uses GLOBAL positions: ``q_offset``/``k_offset`` give the
global position of element 0 of the Q/K sequences. With ``Sq != Sk`` and
both offsets 0 the intended alignment is ambiguous (top-left vs the
decode-style bottom-right), so ``flash_attention`` raises and asks for
explicit offsets rather than silently picking one. The multi-tile causal
kernels tell from a grid step's block indices and the static offsets
whether the mask leaves anything of its tile (``_tile_visible``): a tile
it leaves nothing of is neither computed nor fetched. Without a window
the grid itself stays whole, so a skipped tile still costs its (empty)
grid step (0.13 to 0.2 us on a v5e: PERF.md, PR 33).

A causal call may also be *windowed* (``window=W``): query ``i`` sees the
keys ``j`` with ``0 <= i - j < W``, a band under the diagonal. The same
predicate then throws away the tiles behind the band as well as those
ahead of the diagonal, and the innermost grid dimension runs over the
band alone: ``band_kb`` steps a query tile (``band_qb`` a key tile in the
dk/dv kernel), the most tiles the band leaves of any row (column),
reckoned at trace time (``_tile_plan``). Step ``jj`` of query tile ``i``
stands for key tile ``_first_k_block(i) + jj``, in the kernel and in the
index map, which stops at ``_last_k_block(i)``; the dk/dv kernel is the
mirror. Every visible tile is visited once and in the whole grid's order,
so the results are the same bits. What stays empty is the corner where
the band has not yet left the sequence's start: 36 of 288 steps a slice
at SmallThinker's shapes, where the whole grid had 772 of 1,024.
``_tile_plan`` says how many tile pairs a call computes and how far its
grids' innermost dimensions run.

And ``k``, ``v`` may have fewer heads than ``q`` (*grouped* keys and
values: query head ``n`` reads key/value head ``n // group``): the
key/value block is found through the index map, nothing is repeated in
HBM, and the dk/dv kernel sums over a group's query heads in its float32
accumulators (a grid axis of its own inside the K block's reduction).
Both go through the multi-tile kernels, whatever the length.

A causal call may round its diagonal to *blocks* instead
(``block_length=B``): query ``i`` sees the keys of the blocks up to its own
(``j // B <= i // B``) or, with ``before_block``, of the blocks before it.
``B`` divides tiles and offsets, so the tile plan is the causal one and only
the mask inside the diagonal tiles differs. ``block_diffusion_streams``
puts the two together for block-diffusion training, whose step attends over
a noisy and a clean copy of a sequence side by side: two calls over the
clean keys, each on the grid of an ``S x S`` causal call, and the noisy
queries' own block of ``B`` keys in plain XLA, merged through the
log-sum-exp. No array of ``2S x 2S`` exists. It takes the two streams apart
and returns them apart (a model cuts them where a row is narrowest);
``block_diffusion_attention`` is the same over one array. Under the scope
``hvd.attn.blockdiff``.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..attribution import (SCOPE_ATTN_BLOCKDIFF, SCOPE_ATTN_BWD,
                           SCOPE_ATTN_FWD, SCOPE_ATTN_MLA,
                           SCOPE_ATTN_WINDOW)
from .heads import map_heads

# Every ``pallas_call`` here carries this one name. XLA names a custom
# call's instruction after the innermost component of its name stack, a
# device trace names the event after the instruction, and the benchmark
# finds the kernels there as ``flash_attention.<n>``: until now only
# because they sat right under ``jit(flash_attention)``. Which of them is
# a forward and which a backward kernel is the scope one level up
# (``hvd.attn.fwd`` / ``hvd.attn.bwd``), read from the step's text.
KERNEL_NAME = "flash_attention"

NEG_INF = -1e30
# logsumexp sentinel for fully-masked rows: exp(s - BIG) == 0 for any
# representable s, so backward P/dq come out exactly 0 for those rows.
LSE_MASKED = 1e30


def _attend_block(q, k, v, m, l, o, mask=None, scale=1.0):
    """One online-softmax step: fold K/V tile (k, v) into (m, l, o).

    q: [Sq, D]; k, v: [Sk, D]; m, l: [Sq]; o: [Sq, D] (fp32).
    """
    s = (q.astype(jnp.float32) @ k.astype(jnp.float32).T) * scale  # [Sq, Sk]
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # All-masked rows keep m at NEG_INF; exp(NEG_INF - NEG_INF) would be 1,
    # so clamp the correction to stay a no-op for untouched rows.
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[:, None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    l_new = l * corr + p.sum(axis=-1)
    o_new = o * corr[:, None] + p @ v.astype(jnp.float32)
    return m_new, l_new, o_new


def _finalize(l, o):
    # Rows that saw no unmasked key (l == 0) return 0, not NaN.
    safe_l = jnp.where(l == 0.0, 1.0, l)
    return o / safe_l[:, None]


def blockwise_attention_reference(q, k, v, causal=False, block_size=128,
                                  q_offset=0, k_offset=0):
    """Numerics oracle: [B, H, S, D] blockwise attention in pure jnp.

    ``q_offset``/``k_offset`` are the global positions of element 0 — the
    hook ring attention uses to apply a causal mask across shards. With
    defaults and ``Sq != Sk`` the mask is top-left aligned (both sequences
    start at global position 0); pass ``q_offset=Sk - Sq`` for the
    decode-style bottom-right alignment.
    """
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    scale = 1.0 / (D ** 0.5)
    nq = max(1, (Sq + block_size - 1) // block_size)

    def one_head(qh, kh, vh):
        outs = []
        for i in range(nq):
            qs = i * block_size
            qb = qh[qs:qs + block_size]
            m = jnp.full((qb.shape[0],), NEG_INF, jnp.float32)
            l = jnp.zeros((qb.shape[0],), jnp.float32)
            o = jnp.zeros((qb.shape[0], D), jnp.float32)
            nk = max(1, (Sk + block_size - 1) // block_size)
            for j in range(nk):
                ks = j * block_size
                kb = kh[ks:ks + block_size]
                vb = vh[ks:ks + block_size]
                mask = None
                if causal:
                    qpos = q_offset + qs + jnp.arange(qb.shape[0])
                    kpos = k_offset + ks + jnp.arange(kb.shape[0])
                    mask = qpos[:, None] >= kpos[None, :]
                m, l, o = _attend_block(qb, kb, vb, m, l, o, mask, scale)
            outs.append(_finalize(l, o))
        return jnp.concatenate(outs, axis=0)

    fn = jax.vmap(jax.vmap(one_head))
    return fn(q, k, v).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------


def _auto_block(seq_len: int) -> int:
    """Largest MXU-friendly block that divides the sequence. Bigger blocks
    amortize grid/revisit overhead (measured on v5e at BERT-Large shapes:
    512-blocks are ~33% faster than 128-blocks fwd+bwd); 512x512 f32
    scores (1 MB) sit comfortably in VMEM. Short sequences (< 128, the
    dev/interpret regime) run as one block; longer non-multiple-of-128
    sequences fall back to 128 so the divisibility check still raises
    with its pad-upstream guidance instead of a VMEM blowup. A sequence
    that is one block leaves a grid over (batch x head) slices only;
    ``_group_size`` says how many of them a step of that grid takes."""
    for cand in (512, 256, 128):
        if seq_len % cand == 0:
            return cand
    return seq_len if seq_len < 128 else 128


LANES = 128  # of a vector register, and of a tile of any array in HBM

# What a grid step of a single-tile kernel may hold in VMEM, as
# ``_group_footprint`` counts it: three quarters of the 16 MiB a kernel
# gets by default, the rest left for what the count does not see.
# ``_group_size`` reads nothing else. Set on a v5e at BERT-Large's two
# shapes (PERF.md, PR 29).
GROUP_BUDGET_BYTES = 12 * 1024 * 1024

# What a slice of each single-tile kernel holds, as ``_group_footprint``
# counts it. Forward: q, o / k, v / lse; the scores and their exponentials.
_FWD_SLICE = dict(q_blocks=2, k_blocks=2, rows=1, temporaries=2)
# Fused backward: q, dO, dq / k, v, dk, dv / lse, delta, g_lse; the
# probabilities, dP and dS beside their casts to the stored dtype.
_BWD_SLICE = dict(q_blocks=3, k_blocks=4, rows=3, temporaries=4)
# The same from the forward's output (``_flash_dqkv_from_out_kernel``): q,
# dO, O, dq / k, v, dk, dv / lse.
_BWD_FROM_OUT_SLICE = dict(q_blocks=4, k_blocks=4, rows=1, temporaries=4)


def _vmem_bytes(rows: int, cols: int, itemsize: int) -> int:
    """Bytes a [rows, cols] block takes in VMEM: rows padded to the
    dtype's sublane tile (8 of float32, 16 of bf16), cols to 128 lanes."""
    sublanes = 8 * max(1, 4 // itemsize)
    return (-(-rows // sublanes) * sublanes) * (-(-cols // 128) * 128) \
        * itemsize


def _group_footprint(group, block_q, block_k, d, itemsize, q_blocks,
                     k_blocks, rows, temporaries, heads=1) -> int:
    """VMEM bytes of one grid step that takes ``group`` slices of ``heads``
    heads each, ``d`` the lanes of a slice (all its heads'): per slice
    ``q_blocks`` [block_q, d] and ``k_blocks`` [block_k, d] blocks of the
    stored dtype and ``rows`` float32 [heads, block_q] rows, all held twice
    (the pipeline fetches the next step's while this one computes), and
    ``temporaries`` float32 [block_q, block_k] arrays a head, which the
    batched computation keeps for every slice of the group at once (and,
    counted here, for every head of a slice: their chains are independent
    too)."""
    blocks = (q_blocks * _vmem_bytes(block_q, d, itemsize)
              + k_blocks * _vmem_bytes(block_k, d, itemsize)
              + rows * _vmem_bytes(heads, block_q, 4))
    return group * (2 * blocks + heads * temporaries
                    * _vmem_bytes(block_q, block_k, 4))


def _group_size(slices, block_q, block_k, d, itemsize,
                **slice_counts) -> int:
    """How many slices a grid step of a single-tile kernel takes: the
    largest divisor of ``slices`` whose ``_group_footprint`` fits
    ``GROUP_BUDGET_BYTES``, and 1 where none does. A slice is a (batch x
    head) pair of a head-major call and a batch row's block of whole
    heads' lanes (``heads=`` of them) of a tokens-major one. What a
    128 x 64 slice costs alone is its own chain of product, softmax and
    product, each waiting for the last; the slices of a group are
    independent chains in one block of code, which the scheduler
    interleaves (0.76 -> 0.25 ms a forward call at BERT-Large's S=128, in
    groups of 24). At S=512 a head's scores are 1 MB a temporary and the
    group is 3 or 2 heads, or one or two pairs."""
    fits = GROUP_BUDGET_BYTES // _group_footprint(
        1, block_q, block_k, d, itemsize, **slice_counts)
    return max((group for group in range(1, min(slices, fits) + 1)
                if slices % group == 0), default=1)


def _heads_per_block(d: int, heads: int) -> int | None:
    """How many heads a block of a tokens-major single-tile call takes:
    the fewest whose lanes fill whole 128-lane tiles, two at D = 64 and
    one at D = 128. ``None`` where heads of ``d`` lanes cannot: a width
    that neither divides 128 nor is its multiple, or a count of heads
    that leaves a block short (such a call transposes instead). More than
    128 lanes of several heads are not taken: the kernels contract over a
    block's every lane, which is one pass of a 128 x 128 array and no
    more only up to there."""
    if d % LANES == 0:
        return 1
    if LANES % d == 0 and heads % (LANES // d) == 0:
        return LANES // d
    return None


def _group_index(ref):
    """The index of a grid step's slices in its blocks' leading dimension:
    a group of one is slice 0, the program these kernels were before they
    took groups; a larger one is all of it, and the kernel's products and
    reductions run batched over that dimension."""
    return 0 if ref.shape[0] == 1 else slice(None)


def _dot(a, b, a_dim, b_dim):
    """Contract dimension ``a_dim`` of ``a`` with ``b_dim`` of ``b``, both
    counted from a slice's own two ([rows, cols]); float32 accumulation.
    Operands with a leading dimension of slices give one product a slice."""
    lead = a.ndim - 2
    batch = tuple(range(lead))
    return jax.lax.dot_general(
        a, b, (((a_dim + lead,), (b_dim + lead,)), (batch, batch)),
        preferred_element_type=jnp.float32)


def _head_lanes(x, head, heads):
    """``x`` ([..., rows, lanes of ``heads`` heads]) with every lane but
    those of head ``head`` zeroed: a product that contracts over all the
    lanes then contracts over that head's alone, and one whose other
    operand it is comes out zero outside them. A block of one head is
    itself."""
    if heads == 1:
        return x
    return _put_head_lanes(jnp.zeros_like(x), x, head, heads)


def _put_head_lanes(into, x, head, heads):
    """``into`` with the lanes of head ``head`` taken from ``x``; ``x``
    itself for the first head (``into`` is ``None``) and where a block is
    one head. A select a lane: no lane moves."""
    if heads == 1 or into is None:
        return x
    d = x.shape[-1] // heads
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return jnp.where((lane >= head * d) & (lane < (head + 1) * d), x, into)


def _group_specs(qr, block_q, block_k, slice_counts, heads=None):
    """The grid of a single-tile call, the heads a block of it holds, the
    block specs of its operands and the shape of its float32 rows (the
    log-sum-exp and its like): ``(grid, heads a block, q spec, k/v spec,
    row spec, rows' shape)``.

    Head-major operands ``[BH, S, D]`` (``heads`` is ``None``): a slice is
    a head, a step takes ``G`` of them, blocks ``[G, S, D]``, rows
    ``[BH, 1, S]`` in blocks ``[G, 1, S]``, grid ``(BH // G,)``.
    Tokens-major operands ``[B, S, H * D]``: a slice is a batch row's
    block of the lanes of ``_heads_per_block`` whole heads, a step takes
    the same lanes of ``G`` rows, blocks ``[G, S, heads a block * D]``,
    rows ``[B, H // heads a block, heads a block, S]`` in blocks ``[G,
    heads a block, S]``, grid ``(B // G, H // heads a block)``: the block
    map does the head split."""
    slices, _, width = qr.shape
    per_block = 1 if heads is None else _heads_per_block(
        width // heads, heads)
    lanes = width if heads is None else per_block * (width // heads)
    group = _group_size(slices, block_q, block_k, lanes, qr.dtype.itemsize,
                        heads=per_block, **slice_counts)
    if heads is None:
        grid, block_at = (slices // group,), lambda i: (i, 0, 0)
        row_spec = pl.BlockSpec((group, 1, block_q), block_at)
        rows = (slices, 1, block_q)
    else:
        lane_blocks = heads // per_block
        grid, block_at = (slices // group, lane_blocks), \
            lambda i, j: (i, 0, j)
        row_spec = pl.BlockSpec((group, None, per_block, block_q),
                                lambda i, j: (i, j, 0, 0))
        rows = (slices, lane_blocks, per_block, block_q)
    return (grid, per_block,
            pl.BlockSpec((group, block_q, lanes), block_at),
            pl.BlockSpec((group, block_k, lanes), block_at),
            row_spec, rows)


def _causal_mask(qi, j, block_q, block_k, q_offset, k_offset, window=None,
                 blocks=None):
    qpos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = k_offset + j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    if blocks is not None:
        # The diagonal rounded to a block of ``length`` positions: down to
        # the query's block's first position (the blocks before its own),
        # or up past its last (its own block too).
        length, before = blocks
        own = qpos - jax.lax.rem(qpos, length)
        return kpos < (own if before else own + length)
    if window is None:
        return qpos >= kpos
    return (qpos >= kpos) & (qpos - kpos < window)


def _behind(blocks) -> int:
    """Positions by which a block mask pulls the tile plan's diagonal
    back: a block's length where a query sees only the blocks *before* its
    own, none where it sees its own as well (tiles and offsets are whole
    blocks, so rounding the diagonal up moves no tile: the causal plan)."""
    return blocks[0] if blocks is not None and blocks[1] else 0


def _tile_visible(qi, kj, block_q, block_k, q_offset, k_offset, window=None,
                  behind=0):
    """Whether the causal mask leaves anything of tile (q block ``qi``, k
    block ``kj``): its last query is at or after its first key (``behind``
    positions after it, under a block mask that hides a query's own block:
    ``_behind``) and, under a ``window``, its first query is less than
    ``window`` past its last key (the differences ``i - j`` of a tile are
    a run of integers, so the two ends decide). The multi-tile causal
    kernels skip every other tile, where what the masked step adds to its
    accumulators is exactly zero. Plain arithmetic, so Python ints, numpy
    grids and traced program ids all do."""
    last_query = q_offset + (qi + 1) * block_q - 1
    if behind:
        last_query = last_query - behind
    ahead = last_query >= k_offset + kj * block_k
    if window is None:
        return ahead
    return ahead & (q_offset + qi * block_q
                    - (k_offset + (kj + 1) * block_k - 1) < window)


def _last_k_block(qi, num_kb, block_q, block_k, q_offset, k_offset,
                  behind=0):
    """The last k block of which q block ``qi`` sees anything, clamped into
    the grid: the index maps of the K-innermost calls stop there, so the
    steps past it name a block that is already resident and fetch nothing."""
    last = (q_offset - k_offset - behind + (qi + 1) * block_q - 1) // block_k
    return jnp.clip(last, 0, num_kb - 1)


def _first_q_block(kj, num_qb, block_q, block_k, q_offset, k_offset,
                   behind=0):
    """The first q block that sees anything of k block ``kj``, clamped into
    the grid: ``_last_k_block``'s mirror for the Q-innermost dk/dv call."""
    first = (k_offset - q_offset + behind + kj * block_k) // block_q
    return jnp.clip(first, 0, num_qb - 1)


def _first_k_block(qi, num_kb, block_q, block_k, q_offset, k_offset, window):
    """The first k block of which q block ``qi`` sees anything under a
    window, clamped into the grid: the block of its first query's oldest
    key, ``window - 1`` back."""
    first = (q_offset - k_offset + qi * block_q - (window - 1)) // block_k
    return jnp.clip(first, 0, num_kb - 1)


def _last_q_block(kj, num_qb, block_q, block_k, q_offset, k_offset, window):
    """The last q block that sees anything of k block ``kj`` under a
    window, clamped into the grid: the block of the query ``window - 1``
    past its last key (``_first_k_block``'s mirror)."""
    last = (k_offset - q_offset + (kj + 1) * block_k - 1
            + (window - 1)) // block_q
    return jnp.clip(last, 0, num_qb - 1)


def _tile_plan(causal, num_qb, num_kb, block_q, block_k, q_offset, k_offset,
               window=None, behind=0):
    """At trace time, by the kernels' own predicate over the whole tile
    grid: ``(pairs, band_kb, band_qb)``, the (q, k) tile pairs a slice of a
    multi-tile call computes and the extent of its grids' innermost
    dimension, K blocks a q block for the forward and dq kernels and Q
    blocks a k block for the dk/dv kernel. Without a window that is the
    whole row or column. Under one it is the most tiles the band leaves
    of any row (column): they are a run that starts at ``_first_k_block``
    (``_first_q_block``), so that many steps from there reach them all."""
    import numpy as np

    if not causal:
        return num_qb * num_kb, num_kb, num_qb
    visible = _tile_visible(
        np.arange(num_qb)[:, None], np.arange(num_kb)[None, :], block_q,
        block_k, q_offset, k_offset, window, behind)
    pairs = int(visible.sum())
    if window is None:
        return pairs, num_kb, num_qb
    return (pairs, max(1, int(visible.sum(1).max())),
            max(1, int(visible.sum(0).max())))


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                      acc_scr, *, causal: bool, scale: float, block_q: int,
                      block_k: int, q_offset: int, k_offset: int,
                      window: int | None = None, num_kb: int,
                      blocks: tuple | None = None):
    # Grid (BH, num_q_blocks, K steps), K innermost: only ONE [block_k, D]
    # K/V tile is VMEM-resident per step (long sequences never exceed
    # VMEM); scratch carries (m, l, acc) across the K dimension. A step is
    # a k block, and under a window the k block that many past the first
    # one its q block sees (``num_kb`` is then the sequence's count).
    qi = pl.program_id(1)
    step = pl.program_id(2)
    steps = pl.num_programs(2)
    j = step if window is None else step + _first_k_block(
        qi, num_kb, block_q, block_k, q_offset, k_offset, window)

    @pl.when(step == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # A causal call computes only the tiles its mask leaves something of.
    # _init and _finalize_block stay outside: the last steps of a q block
    # are the skipped ones, and it still has to write its output.
    visible = _tile_visible(qi, j, block_q, block_k, q_offset, k_offset,
                            window, _behind(blocks)) if causal else True
    if window is not None:
        visible &= j < num_kb  # a step may pass the sequence's last block

    @pl.when(visible)
    def _step():
        q = q_ref[0]       # [block_q, D]
        k_tile = k_ref[0]  # [block_k, D]
        v_tile = v_ref[0]
        # Matmuls take the STORED dtype (bf16 in production) with f32 MXU
        # accumulation — upcasting bf16 operands to f32 first adds no
        # precision (they were already rounded) and runs the MXU at 1/4
        # rate; this one change moved BERT-Large flash fwd+bwd ~2x.
        s = jax.lax.dot_general(
            q, k_tile,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [block_q, block_k]
        if causal:
            mask = _causal_mask(qi, j, block_q, block_k, q_offset, k_offset,
                                window, blocks)
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        if causal:
            p = jnp.where(mask, p, 0.0)
        l_scr[:, 0] = l_scr[:, 0] * corr + p.sum(axis=-1)
        # P rounds to the value dtype for the MXU pass (the standard flash
        # trade: probabilities in bf16, accumulation in f32).
        acc_scr[:] = acc_scr[:] * corr[:, None] + jax.lax.dot_general(
            p.astype(v_tile.dtype), v_tile,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:, 0] = m_new

    @pl.when(step == steps - 1)
    def _finalize_block():
        l = l_scr[:, 0]
        empty = l == 0.0
        safe_l = jnp.where(empty, 1.0, l)
        o_ref[0] = (acc_scr[:] / safe_l[:, None]).astype(o_ref.dtype)
        # lse block is the FULL row [1, Sq] (TPU tiling requires the last
        # two block dims be (8,128)-divisible or whole-array); each q-block
        # writes its slice dynamically.
        lse = jnp.where(empty, LSE_MASKED, m_scr[:, 0] + jnp.log(safe_l))
        lse_ref[0, 0, pl.ds(qi * block_q, block_q)] = lse


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     glse_ref, dq_ref, dq_scr, *, causal: bool,
                     scale: float, block_q: int, block_k: int,
                     q_offset: int, k_offset: int,
                     window: int | None = None, num_kb: int,
                     blocks: tuple | None = None):
    """dQ pass. Grid (BH, num_q_blocks, K steps), K innermost as in the
    forward; accumulates dq for one Q tile across the K tiles it sees.

    P_ij = exp(s_ij - lse_i); dS = P * (dO @ V^T - delta_i + g_lse_i);
    dQ_i = scale * sum_j dS_ij K_j. The g_lse term is the cotangent of the
    logsumexp output (dlse_i/ds_ij = P_ij) — ring attention's partial
    merge weights differentiate through lse, so it is NOT discardable.
    """
    qi = pl.program_id(1)
    step = pl.program_id(2)
    steps = pl.num_programs(2)
    j = step if window is None else step + _first_k_block(
        qi, num_kb, block_q, block_k, q_offset, k_offset, window)

    @pl.when(step == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    visible = _tile_visible(qi, j, block_q, block_k, q_offset, k_offset,
                            window, _behind(blocks)) if causal else True
    if window is not None:
        visible &= j < num_kb  # a step may pass the sequence's last block

    @pl.when(visible)
    def _step():
        # Stored-dtype (bf16) matmul operands with f32 MXU accumulation —
        # see the forward kernel's note; f32 upcasts quartered throughput.
        q = q_ref[0]
        k_tile = k_ref[0]
        v_tile = v_ref[0]
        do = do_ref[0]
        # lse/delta blocks are full rows [1, Sq] (TPU tiling); slice our q
        # tile.
        lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q)]
        delta = delta_ref[0, 0, pl.ds(qi * block_q, block_q)]
        glse = glse_ref[0, 0, pl.ds(qi * block_q, block_q)]

        s = jax.lax.dot_general(
            q, k_tile, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            mask = _causal_mask(qi, j, block_q, block_k, q_offset, k_offset,
                                window, blocks)
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(
            do, v_tile, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[:, None] + glse[:, None])
        dq_scr[:] = dq_scr[:] + scale * jax.lax.dot_general(
            ds.astype(k_tile.dtype), k_tile, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(step == steps - 1)
    def _write():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      glse_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                      causal: bool, scale: float, block_q: int,
                      block_k: int, q_offset: int, k_offset: int,
                      window: int | None = None, group: int = 1,
                      num_qb: int, blocks: tuple | None = None):
    """dK/dV pass. Grid (BH, num_k_blocks, Q steps), Q innermost;
    accumulates dk, dv for one K/V tile across the Q tiles that see it. A
    step is a q block, and under a window the q block that many past the
    first one that sees the k block (``num_qb`` is then the sequence's
    count). With grouped keys and values the grid is (B x KV heads,
    num_k_blocks, group, Q steps) and the tile's accumulators also run
    over the ``group`` query heads that read it: their sum is taken here,
    in float32.

    dV_j = sum_i P_ij dO_i; dK_j = scale * sum_i dS_ij Q_i.
    """
    kj = pl.program_id(1)
    if group == 1:
        step = pl.program_id(2)
        steps = pl.num_programs(2)
    else:
        head, step = pl.program_id(2), pl.program_id(3)
        steps = pl.num_programs(3)
    behind = _behind(blocks)
    i = step if window is None else step + _first_q_block(
        kj, num_qb, block_q, block_k, q_offset, k_offset)

    def at_end(inner, head_wanted):
        """This K tile's first (0, head 0) or last inner step."""
        return inner if group == 1 else inner & (head == head_wanted)

    @pl.when(at_end(step == 0, 0))
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    # The first q tiles of a k block are the skipped ones: _init zeroes
    # dk_scr / dv_scr all the same.
    visible = _tile_visible(i, kj, block_q, block_k, q_offset, k_offset,
                            window, behind) if causal else True
    if window is not None:
        visible &= i < num_qb  # a step may pass the sequence's last block

    @pl.when(visible)
    def _step():
        # Stored-dtype (bf16) matmul operands with f32 MXU accumulation —
        # see the forward kernel's note; f32 upcasts quartered throughput.
        q = q_ref[0]
        k_tile = k_ref[0]
        v_tile = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0, pl.ds(i * block_q, block_q)]
        delta = delta_ref[0, 0, pl.ds(i * block_q, block_q)]
        glse = glse_ref[0, 0, pl.ds(i * block_q, block_q)]

        s = jax.lax.dot_general(
            q, k_tile, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [block_q, block_k]
        if causal:
            mask = _causal_mask(i, kj, block_q, block_k, q_offset, k_offset,
                                window, blocks)
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])  # [block_q, block_k]
        # dV_j += P^T @ dO (P rounds to the stored dtype for the MXU pass)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v_tile, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[:, None] + glse[:, None])
        # dK_j += scale * dS^T @ Q
        dk_scr[:] = dk_scr[:] + scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(at_end(step == steps - 1, group - 1))
    def _write():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_fwd_single_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                             causal: bool, scale: float, block_q: int,
                             block_k: int, q_offset: int, k_offset: int,
                             heads: int = 1):
    """Single-tile forward: when the sequence is ONE (block_q, block_k)
    tile there is nothing to run online-softmax OVER — the running-max
    rescale machinery (scratch init/rw, correction exp, accumulator
    rescale) is pure overhead. Direct softmax, same outputs/sentinels
    as the general kernel. The blocks hold a group of G slices, each
    computed as it was alone, and a slice the lanes of ``heads`` heads
    (one of a head-major call: the kernel this was, text for text). Each
    head is computed as it was alone too, out of the lanes where it lies:
    its scores contract q over all the lanes with the other heads' zeroed
    (``_head_lanes``), and of its [block_q, lanes] context its own lanes
    are kept (``_put_head_lanes``)."""
    g = _group_index(q_ref)
    q = q_ref[g]
    k_tile = k_ref[g]
    v_tile = v_ref[g]
    out, rows = None, []
    for head in range(heads):
        s = _dot(_head_lanes(q, head, heads), k_tile, 1, 1) * scale
        if causal:
            mask = _causal_mask(0, 0, block_q, block_k, q_offset, k_offset)
            s = jnp.where(mask, s, NEG_INF)
        m = s.max(axis=-1)
        p = jnp.exp(s - m[..., None])
        if causal:
            p = jnp.where(mask, p, 0.0)
        l = p.sum(axis=-1)
        empty = l == 0.0
        safe_l = jnp.where(empty, 1.0, l)
        acc = _dot(p.astype(v_tile.dtype), v_tile, 1, 0)
        out = _put_head_lanes(out, acc / safe_l[..., None], head, heads)
        rows.append((empty, m, safe_l))
    o_ref[g] = out.astype(o_ref.dtype)
    for head, (empty, m, safe_l) in enumerate(rows):
        lse_ref[g, head, :] = jnp.where(empty, LSE_MASKED,
                                        m + jnp.log(safe_l))


def _flash_dqkv_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                             delta_ref, glse_ref, dq_ref, dk_ref, dv_ref,
                             *, causal: bool, scale: float, block_q: int,
                             block_k: int, q_offset: int, k_offset: int,
                             heads: int = 1, out_ref=None):
    """Fused single-tile backward: when the whole sequence is ONE
    (block_q, block_k) tile (the BERT-Large shapes), the separate
    dQ and dK/dV passes each recompute the identical s → p → dp → ds
    chain. This kernel computes the chain once and emits all three
    grads — roughly a third of the backward softmax/VPU work saved.
    The blocks are a group of G slices of ``heads`` heads each, as in the
    forward; the callers route here iff nq == nk == 1. A head's five
    products are the ones it had alone: q and dO enter with the other
    heads' lanes zeroed, so the scores and dP contract over its lanes and
    its dV and dK come out zero outside them; of dQ its lanes are kept. A
    gradient is written as soon as its last head is in.

    ``out_ref`` (``_flash_dqkv_from_out_kernel``): the forward's output
    in place of ``delta_ref`` and no ``glse_ref``; delta is then summed
    here, over a head's lanes of dO * O."""
    g = _group_index(q_ref)
    q = q_ref[g]
    k_tile = k_ref[g]
    v_tile = v_ref[g]
    do = do_ref[g]
    if out_ref is not None:
        do_out = do.astype(jnp.float32) * out_ref[g].astype(jnp.float32)
    dq = dk = dv = None
    for head in range(heads):
        last = head == heads - 1
        lse = lse_ref[g, head, :]
        if out_ref is None:
            delta = delta_ref[g, head, :]
            glse = glse_ref[g, head, :]
        q_head = _head_lanes(q, head, heads)
        do_head = _head_lanes(do, head, heads)

        s = _dot(q_head, k_tile, 1, 1) * scale  # [block_q, block_k]
        if causal:
            mask = _causal_mask(0, 0, block_q, block_k, q_offset, k_offset)
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse[..., None])
        pw = p.astype(do.dtype)
        dv = _put_head_lanes(dv, _dot(pw, do_head, 0, 0), head, heads)
        if last:
            dv_ref[g] = dv.astype(dv_ref.dtype)
        dp = _dot(do_head, v_tile, 1, 1)
        if out_ref is None:
            ds = p * (dp - delta[..., None] + glse[..., None])
        else:
            ds = p * (dp - _head_lanes(do_out, head, heads).sum(
                axis=-1, keepdims=True))
        ds = ds.astype(q.dtype)
        dq = _put_head_lanes(dq, scale * _dot(ds, k_tile, 1, 0), head, heads)
        if last:
            dq_ref[g] = dq.astype(dq_ref.dtype)
        dk = _put_head_lanes(dk, scale * _dot(ds, q_head, 0, 0), head, heads)
        if last:
            dk_ref[g] = dk.astype(dk_ref.dtype)


def _flash_dqkv_from_out_kernel(q_ref, k_ref, v_ref, do_ref, out_ref,
                                lse_ref, dq_ref, dk_ref, dv_ref, **static):
    """The fused backward for a caller that has the forward's output and
    no cotangent of the log-sum-exp (the tokens-major entry): a pass of
    XLA's over dO and O to make delta a head would first re-lay both out,
    the copies that entry exists to save."""
    _flash_dqkv_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, None,
                             None, dq_ref, dk_ref, dv_ref, out_ref=out_ref,
                             **static)


# ---------------------------------------------------------------------------
# custom_vjp plumbing (operates on [BH, S, D] collapsed arrays)
# ---------------------------------------------------------------------------


def _kind_scope(window, two_widths=False):
    """A windowed call's kernels sit under ``hvd.attn.window`` as well,
    outside ``hvd.attn.fwd`` / ``hvd.attn.bwd``: a model with several
    kinds of layer tells them apart in its step's text, and whoever sums
    by the innermost phase scope still finds forward and backward. (A
    block-diffusion step's are under ``hvd.attn.blockdiff``, which
    ``block_diffusion_attention`` opens around its kernels and its glue.)
    A call whose values are not as wide as its keys (latent attention's:
    ``two_widths``) sits under ``hvd.attn.mla`` in the same way."""
    from ..profiler import annotate_collective

    if two_widths:
        return annotate_collective(SCOPE_ATTN_MLA)
    if window is None:
        return contextlib.nullcontext()
    return annotate_collective(SCOPE_ATTN_WINDOW)


def _fwd_call(qr, kr, vr, causal, block_q, block_k, q_offset, k_offset,
              interpret, window=None, blocks=None, heads=None):
    from ..profiler import annotate_collective

    with _kind_scope(window, kr.shape[-1] != vr.shape[-1]), \
            annotate_collective(SCOPE_ATTN_FWD):
        return _fwd_kernels(qr, kr, vr, causal, block_q, block_k, q_offset,
                            k_offset, interpret, window, blocks, heads)


def _kv_head(bh, group):
    """The key/value slice that query slice ``bh`` reads: slices are
    (batch, head) pairs in that order and ``group`` query heads share one
    key/value head, so it is ``bh // group`` whatever the batch."""
    return bh if group == 1 else bh // group


def _block_at(heads=None):
    """``(slice, block of its rows) ->`` that block's index in an operand
    of the multi-tile kernels, a slice a (batch, head) pair counted in that
    order. Head-major operands ``[BH, S, D]`` (``heads`` is ``None``) hold
    a slice whole; tokens-major ones ``[B, S, heads * D]`` hold it as the
    ``D`` lanes of head ``slice % heads`` in row ``slice // heads``, a lane
    block of its own where ``D`` is whole 128-lane tiles. The blocks are
    ``(1, block, D)`` either way: the same tiles reach the kernels in the
    same order, so what they return is the same bits."""
    if heads is None:
        return lambda n, block: (n, block, 0)
    return lambda n, block: (n // heads, block, n % heads)


def _kv_index_map(causal, num_kb, block_q, block_k, q_offset, k_offset,
                  window=None, group=1, behind=0, at=_block_at()):
    """K/V block of grid step (bh, q block i, K step j), K innermost. A
    causal call stops at the last tile q block ``i`` computes: a
    ``pl.when`` alone would still have the pipeline fetch the skipped
    steps' blocks, and a block index that does not change fetches nothing.
    Under a window step ``j`` is the k block ``j`` past the first that
    ``i`` sees, as in the kernels, stopped at the last in the same way.
    ``at`` is the keys' and values' ``_block_at``.
    """
    if not causal:
        return lambda bh, i, j: at(_kv_head(bh, group), j)

    def block(i, j):
        last = _last_k_block(i, num_kb, block_q, block_k, q_offset, k_offset,
                             behind)
        if window is None:
            return jnp.minimum(j, last)
        return jnp.minimum(j + _first_k_block(
            i, num_kb, block_q, block_k, q_offset, k_offset, window), last)

    return lambda bh, i, j: at(_kv_head(bh, group), block(i, j))


def _q_block(causal, num_qb, block_q, block_k, q_offset, k_offset,
             window=None, behind=0):
    """``(k block j, Q step i) ->`` the Q-side block (q, dO) of a grid
    step of the Q-innermost dk/dv call: a causal call starts at the first
    tile k block ``j`` computes (``_kv_index_map``'s mirror); under a
    window step ``i`` counts on from there and stops at the last."""
    if not causal:
        return lambda j, i: i

    def block(j, i):
        first = _first_q_block(j, num_qb, block_q, block_k, q_offset,
                               k_offset, behind)
        if window is None:
            return jnp.maximum(i, first)
        return jnp.minimum(i + first, _last_q_block(
            j, num_qb, block_q, block_k, q_offset, k_offset, window))

    return block


def _single_tile(Sq, Sk, block_q, block_k, window, group,
                 blocks=None) -> bool:
    """The direct-softmax forward and the fused backward take one-tile
    sequences with a head of keys and values a query head, no window and
    no block mask; anything else is the multi-tile kernels', on a grid of
    one tile if need be."""
    return (Sq == block_q and Sk == block_k and window is None
            and group == 1 and blocks is None)


def _single_tile_fwd(qr, kr, vr, causal, block_q, block_k, q_offset,
                     k_offset, interpret, heads=None):
    """The direct-softmax forward on operands of one tile: head-major
    ``[BH, S, D]``, or with ``heads`` tokens-major ``[B, S, heads * D]``
    (``_group_specs`` has the two grids). ``(out, lse)``, the output in
    the operands' layout. Single-tile sequences skip the online-softmax
    machinery, and a grid step takes a group of them."""
    grid, per_block, q_spec, kv_spec, row_spec, rows = _group_specs(
        qr, block_q, block_k, _FWD_SLICE, heads)
    return pl.pallas_call(
        functools.partial(
            _flash_fwd_single_kernel, causal=causal,
            scale=1.0 / ((qr.shape[2] // (heads or 1)) ** 0.5),
            block_q=block_q, block_k=block_k,
            q_offset=q_offset, k_offset=k_offset, heads=per_block,
        ),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct(qr.shape, qr.dtype),
            jax.ShapeDtypeStruct(rows, jnp.float32),
        ],
        interpret=interpret,
        name=KERNEL_NAME,
    )(qr, kr, vr)


def _single_tile_bwd(qr, kr, vr, do, lse, delta, g_lse, causal, block_q,
                     block_k, q_offset, k_offset, interpret, heads=None,
                     out=None):
    """The fused backward on operands of one tile (BERT-Large with
    auto-block), laid out as ``_single_tile_fwd``'s and the rows as it
    returned ``lse``: one kernel computes dq, dk, dv for a group of slices
    a grid step — the two-pass split of the multi-tile kernels exists only
    to bound VMEM for many-tile sequences. A tokens-major caller hands
    the forward's output ``out`` and neither ``delta`` nor ``g_lse``."""
    from_out = out is not None
    grid, per_block, q_spec, kv_spec, row_spec, _ = _group_specs(
        qr, block_q, block_k,
        _BWD_FROM_OUT_SLICE if from_out else _BWD_SLICE, heads)
    return tuple(pl.pallas_call(
        functools.partial(
            _flash_dqkv_from_out_kernel if from_out
            else _flash_dqkv_fused_kernel, causal=causal,
            scale=1.0 / ((qr.shape[2] // (heads or 1)) ** 0.5),
            block_q=block_q, block_k=block_k, q_offset=q_offset,
            k_offset=k_offset, heads=per_block,
        ),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, q_spec] + (
            [q_spec, row_spec] if from_out else [row_spec] * 3),
        out_specs=[q_spec, kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct(qr.shape, qr.dtype),
            jax.ShapeDtypeStruct(kr.shape, kr.dtype),
            jax.ShapeDtypeStruct(vr.shape, vr.dtype),
        ],
        interpret=interpret,
        name=KERNEL_NAME,
    )(qr, kr, vr, do, *((out, lse) if from_out else (lse, delta, g_lse))))


def _tiled_shapes(qr, kr, heads):
    """``(slices BH, Sq, Sk, D, query heads a key/value head)`` of the
    multi-tile kernels' operands, head-major ``[BH, S, D]`` or, with
    ``heads``, tokens-major ``[B, S, heads * D]`` (``_block_at``)."""
    if heads is None:
        BH, Sq, D = qr.shape
        return BH, Sq, kr.shape[1], D, BH // kr.shape[0]
    B, Sq, width = qr.shape
    return (B * heads, Sq, kr.shape[1], width // heads,
            width // kr.shape[2])


def _value_width(kr, vr, d: int) -> int:
    """The lanes of a head's values, and of its context and their
    gradients: ``d``, the queries' and keys', unless ``v`` comes narrower
    or wider than ``k`` (latent attention reads keys of 192 lanes and
    values of 128). The multi-tile kernels then take ``v``, give the
    context, take ``dO`` and give ``dv`` in blocks of that width; their
    bodies read every width off their blocks. Head-major operands only: a
    tokens-major head is a lane block of one width, and ``_flash_tokens``
    reads a ``v`` row of another width as another number of heads, which
    ``_prepare_flash`` refuses."""
    return d * vr.shape[-1] // kr.shape[-1]


def _fwd_kernels(qr, kr, vr, causal, block_q, block_k, q_offset, k_offset,
                 interpret, window=None, blocks=None, heads=None):
    """``(out, lse)``: the output in the operands' layout, head-major
    ``[BH, S, D]`` or with ``heads`` tokens-major ``[B, S, heads * D]``
    (the multi-tile kernels alone: a one-tile tokens-major call is
    ``_flash_tokens_major``'s), the log-sum-exp ``[BH, 1, S]`` rows."""
    BH, Sq, Sk, D, group = _tiled_shapes(qr, kr, heads)
    Dv = _value_width(kr, vr, D)
    scale = 1.0 / (D ** 0.5)
    if heads is None and D == Dv and _single_tile(
            Sq, Sk, block_q, block_k, window, group, blocks):
        return _single_tile_fwd(qr, kr, vr, causal, block_q, block_k,
                                q_offset, k_offset, interpret)
    num_qb, num_kb = Sq // block_q, Sk // block_k
    kernel = functools.partial(
        _flash_fwd_kernel, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k,
        q_offset=q_offset, k_offset=k_offset, window=window, num_kb=num_kb,
        blocks=blocks,
    )
    behind = _behind(blocks)
    _, band_kb, _ = _tile_plan(causal, num_qb, num_kb, block_q, block_k,
                               q_offset, k_offset, window, behind)
    q_at = _block_at(heads)
    kv_at = _kv_index_map(causal, num_kb, block_q, block_k, q_offset,
                          k_offset, window, group, behind,
                          _block_at(heads and heads // group))
    return pl.pallas_call(
        kernel,
        grid=(BH, num_qb, band_kb),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, i, j: q_at(bh, i)),
            pl.BlockSpec((1, block_k, D), kv_at),
            pl.BlockSpec((1, block_k, Dv), kv_at),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, Dv), lambda bh, i, j: q_at(bh, i)),
            pl.BlockSpec((1, 1, Sq), lambda bh, i, j: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(
                qr.shape[:-1] + (qr.shape[-1] // D * Dv,), qr.dtype),
            jax.ShapeDtypeStruct((BH, 1, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),  # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),  # normalizer l
            pltpu.VMEM((block_q, Dv), jnp.float32),  # fp32 accumulator
        ],
        interpret=interpret,
        name=KERNEL_NAME,
    )(qr, kr, vr)


def _flash_bwd(causal, block_q, block_k, q_offset, k_offset, interpret,
               res, g, g_lse=None, window=None, blocks=None, heads=None):
    from ..profiler import annotate_collective

    with _kind_scope(window, res[1].shape[-1] != res[2].shape[-1]), \
            annotate_collective(SCOPE_ATTN_BWD):
        return _bwd_kernels(causal, block_q, block_k, q_offset, k_offset,
                            interpret, res, g, g_lse, window, blocks, heads)


def _bwd_kernels(causal, block_q, block_k, q_offset, k_offset, interpret,
                 res, g, g_lse, window=None, blocks=None, heads=None):
    """``(dq, dk, dv)`` in the layout of the residuals' q, k, v, which
    ``heads`` says as in ``_fwd_kernels``; the cotangent ``g`` is laid out
    as the output, ``g_lse`` as the log-sum-exp."""
    qr, kr, vr, out, lse = res
    BH, Sq, Sk, D, group = _tiled_shapes(qr, kr, heads)
    Dv = _value_width(kr, vr, D)
    BHkv = BH // group
    scale = 1.0 / (D ** 0.5)
    do = g
    if g_lse is None:
        g_lse = jnp.zeros_like(lse)
    else:
        g_lse = jnp.asarray(g_lse, jnp.float32).reshape(lse.shape)
    # delta_i = rowsum(dO_i * O_i) — the softmax-jacobian correction term;
    # cheap elementwise reduce, XLA fuses it.
    if heads is None:
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)[:, None, :]  # [BH, 1, Sq]
    else:
        # the same sum a head, over the lanes that hold it (``map_heads``:
        # to sum over part of the lanes of a [B, S, H * D] array, XLA
        # re-lays both operands out, 117 MB each at SmallThinker's shapes)
        delta = map_heads(
            lambda do, out: jnp.sum(
                do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1),
            heads, (do, out), rows_out=True).reshape(BH, 1, Sq)

    if heads is None and D == Dv and _single_tile(
            Sq, Sk, block_q, block_k, window, group, blocks):
        return _single_tile_bwd(qr, kr, vr, do, lse, delta, g_lse, causal,
                                block_q, block_k, q_offset, k_offset,
                                interpret)

    num_qb, num_kb = Sq // block_q, Sk // block_k
    behind = _behind(blocks)
    _, band_kb, band_qb = _tile_plan(causal, num_qb, num_kb, block_q,
                                     block_k, q_offset, k_offset, window,
                                     behind)
    q_at, kv_at = _block_at(heads), _block_at(heads and heads // group)
    kv_seen = _kv_index_map(causal, num_kb, block_q, block_k, q_offset,
                            k_offset, window, group, behind, kv_at)
    q_specs = [
        pl.BlockSpec((1, block_q, D), lambda bh, i, j: q_at(bh, i)),
        pl.BlockSpec((1, block_k, D), kv_seen),
        pl.BlockSpec((1, block_k, Dv), kv_seen),
        pl.BlockSpec((1, block_q, Dv), lambda bh, i, j: q_at(bh, i)),
        pl.BlockSpec((1, 1, Sq), lambda bh, i, j: (bh, 0, 0)),
        pl.BlockSpec((1, 1, Sq), lambda bh, i, j: (bh, 0, 0)),
        pl.BlockSpec((1, 1, Sq), lambda bh, i, j: (bh, 0, 0)),
    ]
    dq = pl.pallas_call(
        functools.partial(
            _flash_dq_kernel, causal=causal, scale=scale, block_q=block_q,
            block_k=block_k, q_offset=q_offset, k_offset=k_offset,
            window=window, num_kb=num_kb, blocks=blocks,
        ),
        grid=(BH, num_qb, band_kb),
        in_specs=q_specs,
        out_specs=pl.BlockSpec((1, block_q, D),
                               lambda bh, i, j: q_at(bh, i)),
        out_shape=jax.ShapeDtypeStruct(qr.shape, qr.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
        name=KERNEL_NAME,
    )(qr, kr, vr, do, lse, delta, g_lse)

    q_block = _q_block(causal, num_qb, block_q, block_k, q_offset, k_offset,
                       window, behind)
    if group == 1:
        grid = (BH, num_kb, band_qb)
        q_rows = lambda bh, j, i: q_at(bh, q_block(j, i))  # noqa: E731
        k_rows = lambda bh, j, i: kv_at(bh, j)  # noqa: E731
        row_spec = pl.BlockSpec((1, 1, Sq), lambda bh, j, i: (bh, 0, 0))
    else:
        # One key/value head's tile stays resident while the group's query
        # heads, and every q block of each, go by.
        grid = (BHkv, num_kb, group, band_qb)
        q_rows = lambda bh, j, h, i: q_at(  # noqa: E731
            bh * group + h, q_block(j, i))
        k_rows = lambda bh, j, h, i: kv_at(bh, j)  # noqa: E731
        row_spec = pl.BlockSpec((1, 1, Sq),
                                lambda bh, j, h, i: (bh * group + h, 0, 0))
    q_spec, do_spec = (pl.BlockSpec((1, block_q, width), q_rows)
                       for width in (D, Dv))
    k_spec, v_spec = (pl.BlockSpec((1, block_k, width), k_rows)
                      for width in (D, Dv))
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_dkv_kernel, causal=causal, scale=scale, block_q=block_q,
            block_k=block_k, q_offset=q_offset, k_offset=k_offset,
            window=window, group=group, num_qb=num_qb, blocks=blocks,
        ),
        grid=grid,
        in_specs=[q_spec, k_spec, v_spec, do_spec, row_spec, row_spec,
                  row_spec],
        out_specs=[k_spec, v_spec],
        out_shape=[
            jax.ShapeDtypeStruct(kr.shape, kr.dtype),
            jax.ShapeDtypeStruct(vr.shape, vr.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, Dv), jnp.float32),
        ],
        interpret=interpret,
        name=KERNEL_NAME,
    )(qr, kr, vr, do, lse, delta, g_lse)
    return dq, dk, dv


# custom_vjp over the (out, lse)-returning primal so residuals are exact.
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash_with_lse(qr, kr, vr, causal, block_q, block_k, q_offset,
                    k_offset, interpret, window=None, blocks=None):
    return _fwd_call(qr, kr, vr, causal, block_q, block_k, q_offset,
                     k_offset, interpret, window, blocks)


def _flash_with_lse_fwd(qr, kr, vr, causal, block_q, block_k, q_offset,
                        k_offset, interpret, window, blocks):
    out, lse = _fwd_call(qr, kr, vr, causal, block_q, block_k, q_offset,
                         k_offset, interpret, window, blocks)
    return (out, lse), (qr, kr, vr, out, lse)


def _flash_with_lse_bwd(causal, block_q, block_k, q_offset, k_offset,
                        interpret, window, blocks, res, gs):
    g, g_lse = gs
    # float0 cotangent (lse unused downstream) -> zeros.
    if g_lse is None or g_lse.dtype == jax.dtypes.float0:
        g_lse = None
    return _flash_bwd(causal, block_q, block_k, q_offset, k_offset,
                      interpret, res, g, g_lse, window, blocks)


_flash_with_lse.defvjp(_flash_with_lse_fwd, _flash_with_lse_bwd)


# The single-tile kernels on tokens-major operands: q, k, v, the output and
# every gradient ``[B, S, H * D]``, as a projection writes and reads them.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_tokens_major(q, k, v, heads, causal, block_q, block_k, q_offset,
                        k_offset, interpret):
    return _flash_tokens_major_fwd(q, k, v, heads, causal, block_q, block_k,
                                   q_offset, k_offset, interpret)[0]


def _flash_tokens_major_fwd(q, k, v, heads, causal, block_q, block_k,
                            q_offset, k_offset, interpret):
    from ..profiler import annotate_collective

    with annotate_collective(SCOPE_ATTN_FWD):
        out, lse = _single_tile_fwd(q, k, v, causal, block_q, block_k,
                                    q_offset, k_offset, interpret, heads)
    return out, (q, k, v, out, lse)


def _flash_tokens_major_bwd(heads, causal, block_q, block_k, q_offset,
                            k_offset, interpret, res, do):
    from ..profiler import annotate_collective

    q, k, v, out, lse = res
    with annotate_collective(SCOPE_ATTN_BWD):
        return _single_tile_bwd(q, k, v, do, lse, None, None, causal,
                                block_q, block_k, q_offset, k_offset,
                                interpret, heads, out)


_flash_tokens_major.defvjp(_flash_tokens_major_fwd, _flash_tokens_major_bwd)


# The multi-tile kernels on tokens-major operands whose heads are whole
# 128-lane blocks: q, k, v, the output and every gradient ``[B, S, H * D]``,
# the log-sum-exp and its cotangent ``[BH, 1, S]`` rows as ever. The kernels,
# their grids and their order of tiles are ``_flash_with_lse``'s; only the
# index maps differ (``_block_at``).
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
def _flash_tokens_major_tiles(q, k, v, heads, causal, block_q, block_k,
                              q_offset, k_offset, interpret, window=None,
                              blocks=None):
    return _fwd_call(q, k, v, causal, block_q, block_k, q_offset, k_offset,
                     interpret, window, blocks, heads)


def _flash_tokens_major_tiles_fwd(q, k, v, heads, causal, block_q, block_k,
                                  q_offset, k_offset, interpret, window,
                                  blocks):
    out, lse = _fwd_call(q, k, v, causal, block_q, block_k, q_offset,
                         k_offset, interpret, window, blocks, heads)
    return (out, lse), (q, k, v, out, lse)


def _flash_tokens_major_tiles_bwd(heads, causal, block_q, block_k, q_offset,
                                  k_offset, interpret, window, blocks, res,
                                  gs):
    g, g_lse = gs
    if g_lse is None or g_lse.dtype == jax.dtypes.float0:
        g_lse = None
    return _flash_bwd(causal, block_q, block_k, q_offset, k_offset,
                      interpret, res, g, g_lse, window, blocks, heads)


_flash_tokens_major_tiles.defvjp(_flash_tokens_major_tiles_fwd,
                                 _flash_tokens_major_tiles_bwd)


def _prepare_flash(q, k, v, causal, block_q, block_k, q_offset, k_offset,
                   window=None, blocks=None):
    """Shared validation + block selection for the flash entry points —
    one implementation so the guards cannot drift between them:
    ``(block_q, block_k, window)``, the window ``None`` where it hides
    nothing the causal mask leaves. ``blocks`` is a block mask's
    ``(block length, before the own block)`` or ``None``."""
    Sq, Sk = q.shape[2], k.shape[2]
    if not (q.dtype == k.dtype == v.dtype):
        # The kernels run stored-dtype matmuls (f32 MXU accumulation);
        # dot_general needs uniform operand dtypes — fail with guidance
        # instead of a low-level kernel error.
        raise ValueError(
            f"flash attention operands must share a dtype; got "
            f"q={q.dtype}, k={k.dtype}, v={v.dtype} — cast them to one "
            "dtype")
    if k.shape[:-1] != v.shape[:-1] or k.shape[0] != q.shape[0] \
            or q.shape[1] % k.shape[1] or q.shape[-1] != k.shape[-1]:
        raise ValueError(
            f"flash attention wants k [B, KV heads, S, D] and v [B, KV "
            f"heads, S, Dv] whose heads divide q's [B, H, S, D]; got "
            f"q={q.shape}, k={k.shape}, v={v.shape}")
    block_q = block_q if block_q is not None else _auto_block(Sq)
    block_k = block_k if block_k is not None else _auto_block(Sk)
    if Sq % block_q or Sk % block_k:
        raise ValueError(
            f"sequence lengths ({Sq}, {Sk}) must divide block sizes "
            f"({block_q}, {block_k}); pad to a multiple"
        )
    if causal and Sq != Sk and q_offset == 0 and k_offset == 0:
        raise ValueError(
            f"causal flash attention with Sq={Sq} != Sk={Sk} is ambiguous "
            "without explicit offsets: pass q_offset/k_offset (e.g. "
            f"q_offset={Sk - Sq} for bottom-right/decode alignment, or "
            "q_offset=0, k_offset=0 is top-left — use "
            "blockwise_attention_reference if that is what you want)"
        )
    if window is not None:
        if not causal or window < 1:
            raise ValueError(
                f"window={window} is a band under the diagonal (query i "
                "sees keys j with 0 <= i - j < window): it needs "
                "causal=True and a window of at least 1")
        if window >= q_offset + Sq - k_offset:
            window = None  # no query is that far past any key
    if blocks is not None:
        length = blocks[0]
        if not causal or window is not None or length < 1:
            raise ValueError(
                f"block_length={length} rounds the causal diagonal to "
                "blocks of that many positions: it needs causal=True, a "
                "length of at least 1 and no window")
        if any(size % length for size in (block_q, block_k, q_offset,
                                          k_offset)):
            raise ValueError(
                f"block_length={length} must divide the tiles ({block_q}, "
                f"{block_k}) and the offsets ({q_offset}, {k_offset}): the "
                "kernels take whole blocks a tile")
    return block_q, block_k, window


def _flash(q, k, v, causal, block_q, block_k, q_offset, k_offset, interpret,
           window, block_length=None, before_block=False):
    """``[B, H, Sq, D]``, ``[B, KV heads, Sk, D]`` twice -> ``(out [B, H,
    Sq, D], lse [B, H, Sq])``: both entry points' one way to the kernels."""
    B, H, Sq, D = q.shape
    blocks = _block_mask(block_length, before_block)
    block_q, block_k, window = _prepare_flash(
        q, k, v, causal, block_q, block_k, q_offset, k_offset, window,
        blocks)
    out, lse = _flash_with_lse(
        q.reshape(B * H, Sq, D), k.reshape((-1,) + k.shape[2:]),
        v.reshape((-1,) + v.shape[2:]), causal, block_q, block_k, q_offset,
        k_offset, interpret, window, blocks)
    return out.reshape(B, H, Sq, v.shape[-1]), lse.reshape(B, H, Sq)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "q_offset", "k_offset",
                     "interpret", "window", "block_length", "before_block"),
)
def flash_attention(q, k, v, causal: bool = False, block_q: int | None = None,
                    block_k: int | None = None, q_offset: int = 0,
                    k_offset: int = 0, interpret: bool = False,
                    window: int | None = None,
                    block_length: int | None = None,
                    before_block: bool = False):
    """Pallas flash attention. q: [B, H, S, D], k: [B, KV heads, S, D], v:
    [B, KV heads, S, Dv] → [B, H, S, Dv]. ``Dv`` is ``D`` in every model
    here but one: latent attention scores over 192 lanes and reads values
    of 128, and the multi-tile kernels then move ``v``, the context, ``dO``
    and ``dv`` at their own width (nothing is padded in HBM; the scale is
    ``D ** -0.5``, the queries' width).

    Forward grid: (B*H, Sq/block_q, Sk/block_k), under a ``window`` (B*H,
    Sq/block_q, the K tiles of the band's widest row); each program
    streams K/V tiles from VMEM blocks with fp32 running-max / normalizer
    / accumulator scratch. S must divide by the block sizes (pad upstream
    — XLA-style static shapes). Differentiable via ``jax.custom_vjp``
    with Pallas backward kernels (saved residuals: output + per-row
    logsumexp).

    ``q_offset``/``k_offset``: global positions of element 0 of Q/K (static
    ints) — how ring attention applies a causal mask across shards. When
    ``causal`` and ``Sq != Sk`` you MUST pass offsets making the intended
    alignment explicit (``q_offset=Sk - Sq`` gives decode-style bottom-right
    alignment); with both defaulted the call raises instead of silently
    picking top-left.

    ``window`` (static, needs ``causal``): query ``i`` sees the keys ``j``
    with ``0 <= i - j < window`` in global positions; a window no query
    reaches the far edge of is the causal call itself. ``k`` and ``v`` may
    have fewer heads than ``q``, a divisor of its count: query head ``n``
    reads key/value head ``n // (H // KV heads)``, and their gradients come
    back summed over the group, in the shape they came in.

    ``block_length`` (static, needs ``causal`` and no window; it must
    divide tiles and offsets): the diagonal rounded to blocks of that many
    positions. Query ``i`` sees the keys ``j`` with ``j // block_length <=
    i // block_length``, its own block whole, or with ``before_block`` only
    ``j // block_length < i // block_length``, the blocks before its own (a
    query of the first block then sees nothing: its row is zero, its
    log-sum-exp ``LSE_MASKED``). The two masks of block-diffusion training
    (:func:`block_diffusion_attention`); the tile plan is the causal one.
    """
    return _flash(q, k, v, causal, block_q, block_k, q_offset, k_offset,
                  interpret, window, block_length, before_block)[0]


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "q_offset", "k_offset",
                     "interpret", "window", "block_length", "before_block"),
)
def flash_attention_lse(q, k, v, causal: bool = False,
                        block_q: int | None = None,
                        block_k: int | None = None, q_offset: int = 0,
                        k_offset: int = 0, interpret: bool = False,
                        window: int | None = None,
                        block_length: int | None = None,
                        before_block: bool = False):
    """Like :func:`flash_attention` but also returns the per-row
    logsumexp ``[B, H, Sq]`` (fp32) — the hook ring attention uses to
    merge per-shard partial attentions exactly:
    ``out = Σ_t exp(lse_t - lse_total) * out_t``. Fully-masked rows carry
    the ``LSE_MASKED`` sentinel (treat as -inf when merging).
    Fully differentiable — INCLUDING through lse: its cotangent
    propagates into the backward kernels (dS += P * g_lse), which is what
    makes logsumexp-merged schemes like ring-flash train exactly."""
    return _flash(q, k, v, causal, block_q, block_k, q_offset, k_offset,
                  interpret, window, block_length, before_block)


def _block_mask(block_length, before_block):
    """A block mask's ``(block length, before the own block)``, or
    ``None`` for a call without one."""
    if before_block and block_length is None:
        raise ValueError("before_block=True hides a query's own block: it "
                         "needs a block_length")
    return None if block_length is None else (block_length,
                                              bool(before_block))


def _flash_tokens(q, k, v, num_heads, causal, block_q, block_k, q_offset,
                  k_offset, interpret, window, block_length=None,
                  before_block=False, with_lse=False):
    """``[B, Sq, H * D]``, ``[B, Sk, KV heads * D]`` twice -> ``(out [B,
    Sq, H * D], lse [B, H, Sq])``, the log-sum-exp ``None`` unless asked
    for: the tokens-major entry points' one way to the kernels. What
    decides the way is what the call's shapes show: whether it is one tile
    (``_single_tile``) and whether a head is whole 128-lane blocks."""
    B, Sq, width = q.shape
    if width % num_heads or k.shape[2] % (width // num_heads):
        raise ValueError(
            f"tokens-major flash attention wants q [B, S, heads * D] and "
            f"k, v [B, S, KV heads * D]; got q={q.shape}, k={k.shape} with "
            f"num_heads={num_heads}")
    d = width // num_heads
    blocks = _block_mask(block_length, before_block)

    def head_major(x):
        return x.reshape(x.shape[:2] + (-1, d)).transpose(0, 2, 1, 3)

    tile_q, tile_k, seen = _prepare_flash(
        *(jax.eval_shape(head_major, x) for x in (q, k, v)), causal,
        block_q, block_k, q_offset, k_offset, window, blocks)
    one_tile = _single_tile(Sq, k.shape[1], tile_q, tile_k, seen,
                            width // k.shape[2], blocks)
    if one_tile and not with_lse \
            and _heads_per_block(d, num_heads) is not None:
        return _flash_tokens_major(q, k, v, num_heads, causal, tile_q,
                                   tile_k, q_offset, k_offset,
                                   interpret), None
    if d % LANES == 0 and not one_tile:
        out, lse = _flash_tokens_major_tiles(
            q, k, v, num_heads, causal, tile_q, tile_k, q_offset, k_offset,
            interpret, seen, blocks)
        return out, lse.reshape(B, num_heads, Sq)
    out, lse = _flash(head_major(q), head_major(k), head_major(v), causal,
                      block_q, block_k, q_offset, k_offset, interpret,
                      window, block_length, before_block)
    return out.transpose(0, 2, 1, 3).reshape(B, Sq, width), lse


@functools.partial(
    jax.jit,
    static_argnames=("num_heads", "causal", "block_q", "block_k", "q_offset",
                     "k_offset", "interpret", "window", "block_length",
                     "before_block"),
)
def flash_attention_tokens_major(q, k, v, num_heads: int,
                                 causal: bool = False,
                                 block_q: int | None = None,
                                 block_k: int | None = None,
                                 q_offset: int = 0, k_offset: int = 0,
                                 interpret: bool = False,
                                 window: int | None = None,
                                 block_length: int | None = None,
                                 before_block: bool = False):
    """:func:`flash_attention` for operands where a projection wrote
    them, tokens major: q ``[B, S, H * D]`` with ``H = num_heads``, k, v
    ``[B, S, KV heads * D]`` → ``[B, S, H * D]``, the gradients of q, k, v
    in their layouts too. Which layout an array holds cannot be read from
    its shape, so the caller says it by the entry it calls.

    A sequence that is one tile, with a head of keys and values a query
    head, no window, no block mask and heads whose lanes tile 128
    (``_heads_per_block``), goes to the single-tile kernels as it lies: a
    block is the lanes of whole heads (two at D = 64) of a group of batch
    rows, found by the block's index map. Any other call whose heads are
    whole 128-lane blocks (``D % 128 == 0``) goes to the multi-tile
    kernels as it lies: their grids, bodies and order of tiles are the
    head-major call's and a head is the lane block the index maps find
    (``_block_at``), so the context and every gradient are
    :func:`flash_attention`'s bit for bit, under a window, grouped keys
    and values and a block mask too. Nothing is transposed in HBM either
    way. What is left (several tiles of narrower heads: two
    heads a lane block would want lane masks in three more kernels)
    transposes to ``[B, H, S, D]`` and is :func:`flash_attention`'s, so no
    caller needs to know which it is."""
    return _flash_tokens(q, k, v, num_heads, causal, block_q, block_k,
                         q_offset, k_offset, interpret, window, block_length,
                         before_block)[0]


@functools.partial(
    jax.jit,
    static_argnames=("num_heads", "causal", "block_q", "block_k", "q_offset",
                     "k_offset", "interpret", "window", "block_length",
                     "before_block"),
)
def flash_attention_tokens_major_lse(q, k, v, num_heads: int,
                                     causal: bool = False,
                                     block_q: int | None = None,
                                     block_k: int | None = None,
                                     q_offset: int = 0, k_offset: int = 0,
                                     interpret: bool = False,
                                     window: int | None = None,
                                     block_length: int | None = None,
                                     before_block: bool = False):
    """:func:`flash_attention_tokens_major` that also returns the per-row
    logsumexp ``[B, H, Sq]`` (fp32), as :func:`flash_attention_lse` does
    and differentiable through it in the same way."""
    return _flash_tokens(q, k, v, num_heads, causal, block_q, block_k,
                         q_offset, k_offset, interpret, window, block_length,
                         before_block, with_lse=True)


# ---------------------------------------------------------------------------
# Block-diffusion training: a noisy and a clean stream, three mask terms
# ---------------------------------------------------------------------------


def _keys_of_own_block(x, length):
    """``x [B, KV heads, S, D]`` -> ``length`` arrays of that shape in
    float32, the ``c``-th of which holds in row ``i`` row ``c`` of
    ``i``'s block of ``x``: ``x[i - i % length + c]``. Row ``i`` is at
    place ``r = i % length`` of its block, so that is ``x`` shifted by ``c
    - r`` rows, picked by ``r``: shifts and selects of the *small* arrays
    in the layout they have. (A reshape to ``[S / length, length, D]`` and
    a repeat say the same, and make a TPU pad four rows to eight and copy
    every query-sized array that meets them: 2.1 GB a layer.)"""
    S = x.shape[2]
    x = x.astype(jnp.float32)
    place = (jnp.arange(S) % length)[:, None]

    def shifted(d):  # row i holds x[i + d]; rows past the ends are unused
        pad = jnp.zeros_like(x[:, :, :abs(d)])
        if d > 0:
            return jnp.concatenate([x[:, :, d:], pad], axis=2)
        return jnp.concatenate([pad, x[:, :, :d]], axis=2) if d else x

    shifts = {d: shifted(d) for d in range(1 - length, length)}
    return [sum(jnp.where(place == r, shifts[c - r], 0.0)
                for r in range(length)) for c in range(length)]


def _own_block_attention(q, k, v, length):
    """The block-diagonal term in plain XLA: ``q [B, KV heads, S, D]``,
    one query head of every group, against the ``length`` keys and values
    of its own block (``k``, ``v``: what ``_keys_of_own_block`` made of
    them) -> ``(out [B, KV heads, S, D], lse [B, KV heads, S])`` in
    float32. Four keys a query are no work for a kernel's tiles: the
    query-sized arrays are read where they lie, row by row, multiplied
    elementwise with key ``c`` of the row's block and summed along their
    lanes."""
    q = q.astype(jnp.float32)
    s = [(q * k_c).sum(-1) / (q.shape[-1] ** 0.5) for k_c in k]
    m = functools.reduce(jnp.maximum, s)
    p = [jnp.exp(s_c - m) for s_c in s]
    l = sum(p)
    out = sum((p_c / l)[..., None] * v_c for p_c, v_c in zip(p, v))
    return out, m + jnp.log(l)


def _merge_own_block(q, k, v, past, lse_past, length):
    """The noisy queries' two sources joined: ``past [B, H, S, D]`` and
    ``lse_past [B, H, S]``, what the kernels made of the clean keys before
    each query's block, merged through the log-sum-exp with the query's own
    block of ``k``, ``v [B, KV heads, S, D]`` (``_own_block_attention``),
    as ring attention merges its shards. A query of the first block has no
    clean past: the kernel says so by its sentinel, which merges as minus
    infinity. **A group's query heads one after another**, each against
    the key/value heads as they are: broadcast over the group, XLA writes
    every one of the ``2 x length`` small arrays out at the queries' size
    (134 MB each at SDAR's shapes, forward and recomputed)."""
    B, H, S, D = q.shape
    kv_heads = k.shape[1]
    group = H // kv_heads
    keys, values = (_keys_of_own_block(x, length) for x in (k, v))

    def by_group(x):  # [B, H, S, ...] -> group x [B, KV heads, S, ...]
        # split, whose transpose is one concatenation (a slice a head's
        # is a pad and an add of the whole array a head)
        parts = jnp.split(x.reshape((B, kv_heads, group) + x.shape[2:]),
                          group, axis=2)
        return [part[:, :, 0] for part in parts]

    merged = []
    for q_g, past_g, before in zip(by_group(q), by_group(past),
                                   by_group(lse_past)):
        own, lse_own = _own_block_attention(q_g, keys, values, length)
        before = jnp.where(before >= 0.5 * LSE_MASKED, NEG_INF, before)
        lse = jnp.logaddexp(before, lse_own)
        merged.append(
            jnp.exp(before - lse)[..., None] * past_g.astype(jnp.float32)
            + jnp.exp(lse_own - lse)[..., None] * own)
    return jnp.stack(merged, axis=2).reshape(B, H, S, D).astype(q.dtype)


def block_diffusion_streams(noisy, clean, block_length: int,
                            block_q: int | None = None,
                            block_k: int | None = None,
                            interpret: bool = False):
    """Attention of one block-diffusion training step (BD3-LMs,
    arXiv:2503.09573) over a noisy and a clean copy of the same ``S``
    tokens, each stream given apart as its ``(q [B, H, S, D], k, v [B, KV
    heads, S, D])``, both at positions ``0..S-1`` -> ``(noisy [B, H, S,
    D], clean [B, H, S, D])``. With ``blk(i) = pos(i) // block_length``:

    * a clean query sees the clean keys of ``blk(j) <= blk(i)``;
    * a noisy query sees the clean keys of ``blk(j) < blk(i)`` and the
      noisy keys of its own block;
    * a clean query sees no noisy key.

    Two calls of the multi-tile kernels over the clean keys and values
    alone, each on the causal tile plan of an ``S x S`` square (the mask
    inside the diagonal tiles differs, no tile does), so no tile outside
    the two triangles is computed or fetched and no ``[2S, 2S]`` array
    exists. The noisy queries' second source, their own block, is
    ``block_length`` keys a query: plain XLA (``_merge_own_block``),
    merged with the kernels' result through the log-sum-exp as ring
    attention merges its shards, differentiably on both sides. All of it
    under ``hvd.attn.blockdiff``, which is opened here and nowhere else.
    A caller that holds the two streams in
    one array cuts it before the projections, where a row is narrowest
    (``models/sdar.py``), or calls :func:`block_diffusion_attention`."""
    from ..profiler import annotate_collective

    (q_noisy, k_noisy, v_noisy), (q_clean, k_clean, v_clean) = noisy, clean
    S = q_clean.shape[2]
    if (q_noisy.shape[2] != S or k_noisy.shape[2] != S
            or k_clean.shape[2] != S or S % block_length):
        raise ValueError(
            f"block-diffusion attention wants a noisy and a clean half of "
            f"whole blocks of {block_length}; got q={q_noisy.shape} and "
            f"{q_clean.shape}, k={k_noisy.shape} and {k_clean.shape}")
    with annotate_collective(SCOPE_ATTN_BLOCKDIFF):
        tiles = dict(block_q=block_q, block_k=block_k, interpret=interpret)
        clean = flash_attention(q_clean, k_clean, v_clean, causal=True,
                                block_length=block_length, **tiles)
        past, lse_past = flash_attention_lse(
            q_noisy, k_clean, v_clean, causal=True,
            block_length=block_length, before_block=True, **tiles)
        return _merge_own_block(q_noisy, k_noisy, v_noisy, past, lse_past,
                                block_length), clean


def block_diffusion_attention(q, k, v, block_length: int,
                              block_q: int | None = None,
                              block_k: int | None = None,
                              interpret: bool = False):
    """:func:`block_diffusion_streams` over the doubled stream ``[x_t ;
    x_0]`` in one array: ``q [B, H, 2S, D]``, ``k``, ``v [B, KV heads, 2S,
    D]``, the noisy half first -> ``[B, H, 2S, D]``."""
    S = q.shape[2] // 2
    if q.shape[2] != 2 * S or k.shape[2] != 2 * S:
        raise ValueError(
            f"block-diffusion attention wants a noisy and a clean half of "
            f"whole blocks of {block_length}; got q={q.shape}, k={k.shape}")
    noisy, clean = block_diffusion_streams(
        (q[:, :, :S], k[:, :, :S], v[:, :, :S]),
        (q[:, :, S:], k[:, :, S:], v[:, :, S:]), block_length,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return jnp.concatenate([noisy, clean], axis=2)
