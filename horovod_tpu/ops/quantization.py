"""Int8 quantized allreduce for the gradient wire (EQuARX-style).

Reference context: the reference ships fp16 wire compression
(``horovod/torch/compression.py``); SURVEY §3.6 flags int8 as the
TPU-idiomatic next step (PAPERS.md: EQuARX — blockwise-quantized
all-to-all allreduce inside XLA). A naive int8 AllReduce cannot work —
summing N int8 contributions overflows the wire dtype — so the exchange
changes shape, exactly as in EQuARX:

1. blockwise quantize my gradient shard (per-block f32 scale, stochastic
   rounding) to int8;
2. ``all_to_all`` the int8 chunks + scales (each device receives every
   rank's contribution for ITS chunk — no summation on the wire);
3. dequantize and sum in f32 locally (op=Average divides by N);
4. requantize the reduced chunk, ``all_gather`` int8 + scales;
5. dequantize to the original dtype.

Wire bytes per element: ~2 (one int8 all_to_all + one int8 all_gather)
vs ~4 for a bf16 ring allreduce — half the ICI traffic, at a bounded
quantization cost (per-block scales; the round-trip is tolerance-tested
in ``tests/test_optimizer.py``).

Stochastic rounding is SELF-SEEDED: the rounding offset derives from a
hash of each value's own bits, optionally salted with a caller-threaded
step counter (``salt=``). Unsalted, the offset is deterministic per
VALUE — a gradient element that repeats the same value across steps
(constants, plateaued weights, zero-heavy layers) rounds the same
direction every step, a persistent per-element bias; rounding is
unbiased in expectation only over varying data. The
``DistributedOptimizer`` threads its update counter as the salt so
repeated values decorrelate across steps; see ``_sround``.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

BLOCK = 1024  # elements per quantization scale (EQuARX blockwise scales)


def _sround(x, salt=None):
    """Stochastically round ``x`` (f32) to int8 in [-127, 127].

    The uniform offset comes from a multiplicative hash of the value's
    own mantissa bits, decorrelated from the rounding residual, so
    E[round(x)] tracks x over varying data without a PRNG key threaded
    through the optimizer trace. Unsalted the offset is deterministic
    per VALUE: a value that repeats across steps rounds the same way
    every time (a persistent bias for static data). ``salt`` — a
    caller-threaded step counter (any integer scalar, traced or not) —
    is folded into the hash so repeated values decorrelate across
    steps; callers that can count steps should thread it."""
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    if salt is not None:
        bits = bits ^ (jnp.asarray(salt).astype(jnp.uint32)
                       * np.uint32(0x9E3779B9))
    h = bits * np.uint32(2654435761)
    h = h ^ (h >> 16)
    u = (h >> 8).astype(jnp.float32) * np.float32(2.0**-24)
    return jnp.clip(jnp.floor(x + u), -127, 127).astype(jnp.int8)


#: Saturation bound for non-finite quantizer input (largest finite f32).
_F32_MAX = float(np.finfo(np.float32).max)


def _tripwire_armed() -> bool:
    """Trace-time read of the non-finite tripwire knob: armed, the
    quantizer must PROPAGATE non-finite input detectably instead of
    saturating it away — saturation upstream of the tripwire's
    post-reduce ``isfinite`` check would silently disable the detector
    the moment int8 compression is turned on. One parser for the knob
    (fusion's, imported lazily like this module's other fusion uses) so
    the two planes can never disagree about what "armed" means."""
    from .fusion import nonfinite_action

    return nonfinite_action() is not None


def _quantize_blocks(flat_f32, salt=None):
    """[m] f32 -> (int8 [m], scales f32 [m/BLOCK]); m % BLOCK == 0.

    Non-finite input never poisons a block's scale silently: a NaN
    reaching the per-block ``max(abs(...))`` used to produce a garbage
    scale — every element of that block then dequantized to NaN/garbage
    *silently*, and under the RS/AG halves the garbage shard spread to
    every rank. Instead:

    - Tripwire UNARMED (``HOROVOD_NONFINITE_ACTION`` unset): input is
      SATURATED before the scale is computed (NaN -> 0, ±Inf ->
      ±f32-max), bounding the damage to the bad elements themselves (an
      Inf clamps to the block's ±127 extreme; a NaN contributes zero)
      while the wire never amplifies.
    - Tripwire ARMED: a block containing any non-finite element is
      emitted with ``scale = +Inf`` — every dequantized element of that
      block is ±Inf/NaN, the reduction sums propagate it to EVERY rank
      rank-identically, and the post-reduce ``isfinite`` tripwire fires
      exactly as it does under ``compression=none`` (the tripwire stays
      the authoritative detector; quantization never masks it).

    See the int8 guard table in docs/perf.md.
    """
    rows = flat_f32.reshape(-1, BLOCK)
    saturated = jnp.clip(jnp.nan_to_num(rows, nan=0.0, posinf=_F32_MAX,
                                        neginf=-_F32_MAX),
                         -_F32_MAX, _F32_MAX)
    scale = jnp.max(jnp.abs(saturated), axis=1) / 127.0
    if _tripwire_armed():
        bad = ~jnp.isfinite(rows).all(axis=1)
        scale = jnp.where(bad, jnp.inf, scale)
    safe = jnp.where(jnp.isfinite(scale) & (scale != 0.0), scale, 1.0)
    q = _sround(saturated / safe[:, None], salt)
    return q.reshape(-1), scale


def int8_allreduce_flat(flat, axis_name: str, world_size: int,
                        op: str = "average", prescale_factor: float = 1.0,
                        postscale_factor: float = 1.0, salt=None,
                        groups=None):
    """Quantized allreduce of a flat tensor inside a shard_map trace.

    ``world_size`` must be the axis size as a Python int (shapes depend
    on it). ``salt`` is an optional caller-threaded step counter folded
    into the stochastic-rounding hash (see :func:`_sround`). ``groups``
    scopes the exchange to ``axis_index_groups`` sub-rings of
    ``world_size`` members each (the comms planner's two-level cross
    leg). Returns f32 with ``flat``'s shape; the caller casts back.
    """
    n = int(world_size)
    m = int(flat.size)
    x = flat.astype(jnp.float32)
    if prescale_factor != 1.0:
        x = x * prescale_factor
    if n <= 1:
        # Single member: quantize-dequantize round trip only.
        pad = (-m) % BLOCK
        xp = jnp.pad(x, (0, pad))
        q, scale = _quantize_blocks(xp, salt)
        out = (q.reshape(-1, BLOCK).astype(jnp.float32)
               * scale[:, None]).reshape(-1)[:m]
        return out * postscale_factor
    # Pad so each rank's chunk is whole blocks.
    chunk_elems = -(-m // (n * BLOCK)) * BLOCK
    xp = jnp.pad(x, (0, n * chunk_elems - m))
    q, scale = _quantize_blocks(xp, salt)
    rows_per_chunk = chunk_elems // BLOCK
    q = q.reshape(n, rows_per_chunk, BLOCK)
    scale = scale.reshape(n, rows_per_chunk)
    # No summation on the wire: chunk j (int8 + scales) goes to rank j.
    recv = lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                          tiled=True, axis_index_groups=groups
                          ).reshape(n, rows_per_chunk, BLOCK)
    recv_scale = lax.all_to_all(
        scale[:, :, None], axis_name, split_axis=0, concat_axis=0,
        tiled=True, axis_index_groups=groups).reshape(n, rows_per_chunk)
    # Dequantize + reduce in f32 locally.
    total = jnp.sum(recv.astype(jnp.float32)
                    * recv_scale[:, :, None], axis=0)
    if op == "average":
        total = total / n
    # Requantize MY reduced chunk, share it with everyone.
    q2, scale2 = _quantize_blocks(total.reshape(-1), salt)
    gathered = lax.all_gather(
        q2.reshape(rows_per_chunk, BLOCK), axis_name,
        axis_index_groups=groups)                          # [n, r, B]
    gathered_scale = lax.all_gather(scale2, axis_name,
                                    axis_index_groups=groups)  # [n, r]
    out = (gathered.astype(jnp.float32)
           * gathered_scale[:, :, None]).reshape(-1)[:m]
    if postscale_factor != 1.0:
        out = out * postscale_factor
    return out


def _reduce_scattered_rows(rows, axis_name, n, op, salt, groups=None):
    """Quantized exchange of a ``(n, R')`` block (``R' % BLOCK == 0``):
    each rank ends with row ``r`` REDUCED — the first half of the EQuARX
    allreduce (quantize → all_to_all → dequant-sum), with no requant/
    all_gather tail. Returns the reduced f32 row of length ``R'``.
    ``groups`` scopes the exchange to ``axis_index_groups`` sub-rings of
    size ``n`` (the comms planner's two-level intra-island leg)."""
    rows_per_chunk = rows.shape[1] // BLOCK
    q, scale = _quantize_blocks(rows.reshape(-1), salt)
    q = q.reshape(n, rows_per_chunk, BLOCK)
    scale = scale.reshape(n, rows_per_chunk)
    recv = lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                          tiled=True, axis_index_groups=groups
                          ).reshape(n, rows_per_chunk, BLOCK)
    recv_scale = lax.all_to_all(
        scale[:, :, None], axis_name, split_axis=0, concat_axis=0,
        tiled=True, axis_index_groups=groups).reshape(n, rows_per_chunk)
    total = jnp.sum(recv.astype(jnp.float32)
                    * recv_scale[:, :, None], axis=0)
    if op == "average":
        total = total / n
    return total.reshape(-1)


def int8_two_level_allreduce_flat(flat, axis_name: str, islands,
                                  op: str = "average",
                                  prescale_factor: float = 1.0,
                                  postscale_factor: float = 1.0,
                                  salt=None):
    """Two-level (ICI×DCN) int8 allreduce of a flat tensor, quantized
    PER LEG — the comms planner's ``two_level`` schedule for the int8
    wire (``HOROVOD_COMMS_PLANNER``; see ``ops/comms_planner.py``):

    1. intra-island quantized reduce-scatter (int8 all_to_all over the
       island's ``axis_index_groups`` sub-ring + local dequant-sum) —
       each rank keeps ``1/L`` of the payload;
    2. cross-island quantized allreduce of that shard (the full EQuARX
       exchange over the position-matched cross groups) — only the
       shard crosses DCN, and it crosses at ~1 byte/element;
    3. intra-island int8 allgather (quantize → all_gather int8+scales →
       dequantize).

    Every leg re-quantizes its input with its own blockwise scales, so
    the wire is int8 end to end and the per-leg error is bounded the
    same way the flat EQuARX exchange's is. ``islands`` is the regular
    island layout the plan carries (equal sizes, ≥2 islands). Returns
    f32 with ``flat``'s shape; callers cast."""
    from ..profiler import annotate_collective
    from .comms_planner import two_level_groups

    # One grouping convention for the int8 and f32 wires: the planner's
    # helper owns the (local, cross) construction, so the two schedules
    # can never silently diverge on the position mapping.
    groups, cross = two_level_groups(islands)
    L = len(groups[0])
    G = len(groups)
    m = int(flat.size)
    x = flat.astype(jnp.float32)
    if prescale_factor != 1.0:
        x = x * prescale_factor
    # Pad so each island rank's shard is whole blocks.
    chunk_elems = -(-m // (L * BLOCK)) * BLOCK
    xp = jnp.pad(x, (0, L * chunk_elems - m))
    with annotate_collective("int8_two_level.rs_local"):
        shard = _reduce_scattered_rows(
            xp.reshape(L, chunk_elems), axis_name, L, "sum", salt,
            groups=groups)
    with annotate_collective("int8_two_level.allreduce_cross"):
        shard = int8_allreduce_flat(
            shard, axis_name, G, op="sum", salt=salt, groups=cross)
    if op == "average":
        shard = shard / (L * G)
    with annotate_collective("int8_two_level.ag_local"):
        q, scale = _quantize_blocks(shard.reshape(-1), salt)
        gathered = lax.all_gather(q.reshape(-1, BLOCK), axis_name,
                                  axis_index_groups=groups)
        gathered_scale = lax.all_gather(scale, axis_name,
                                        axis_index_groups=groups)
    out = (gathered.astype(jnp.float32)
           * gathered_scale[:, :, None]).reshape(-1)[:m]
    if postscale_factor != 1.0:
        out = out * postscale_factor
    return out


def int8_alltoall_rows(rows, axis_name: str, salt=None, groups=None,
                       extra=None, a2a=None):
    """Quantized alltoall of per-destination rows — the EQuARX exchange
    extended from the allreduce/RS-AG halves to the MoE dispatch/combine
    wire (``parallel/moe.py``, ``HOROVOD_MOE_COMPRESSION=int8``).

    ``rows`` is ``(n, R)`` f32: row ``d`` is the payload this rank
    addresses to group-member ``d``. Each row is blockwise-quantized
    (per-block f32 scale, stochastic rounding salted by the
    caller-threaded step counter — the :func:`_sround` contract), the
    int8 payload and one f32 side channel ride two all_to_alls, and the
    received rows dequantize locally. No summation ever happens on or
    after the wire, so unlike the allreduce there is no overflow hazard
    — int8 here is purely a 4×→1× payload compression, and the
    round-trip error is bounded by each source block's own scale.

    ``extra`` — optional ``(n, k)`` f32 carried EXACTLY (concatenated
    onto the scale rows' side channel): the MoE dispatch uses it for the
    slot-occupancy mask, which must never quantize (routing correctness
    is not a tolerance question). ``groups`` scopes both exchanges to
    ``axis_index_groups``; ``a2a`` overrides the exchange itself (the
    planner's :func:`~horovod_tpu.ops.comms_planner.two_level_alltoall`
    staged form — both wires MUST ride the same schedule, so one
    callable serves both). Non-finite input follows the
    :func:`_quantize_blocks` tripwire contract: armed, a bad block
    dequantizes non-finite on the RECEIVING rank, so the post-combine
    ``isfinite`` check still fires. Returns ``(recv_rows (n, R) f32,
    recv_extra (n, k) f32 | None)``.
    """
    n, R = rows.shape
    pad = (-R) % BLOCK
    rp = jnp.pad(rows, ((0, 0), (0, pad))) if pad else rows
    q, scale = _quantize_blocks(rp.reshape(-1), salt)
    rows_per_chunk = rp.shape[1] // BLOCK
    q = q.reshape(n, rows_per_chunk, BLOCK)
    scale = scale.reshape(n, rows_per_chunk)
    side = (scale if extra is None
            else jnp.concatenate([scale, extra.astype(jnp.float32)],
                                 axis=1))
    if a2a is None:
        def a2a(x):
            return lax.all_to_all(x, axis_name, split_axis=0,
                                  concat_axis=0, tiled=True,
                                  axis_index_groups=groups)
    recv_q = a2a(q).reshape(n, rows_per_chunk, BLOCK)
    recv_side = a2a(side[:, :, None]).reshape(n, side.shape[1])
    recv_scale = recv_side[:, :rows_per_chunk]
    recv_extra = None if extra is None else recv_side[:, rows_per_chunk:]
    out = (recv_q.astype(jnp.float32)
           * recv_scale[:, :, None]).reshape(n, -1)[:, :R]
    return out, recv_extra


def int8_fused_reducescatter(
    tensors,
    axis_name: str,
    world_size: int,
    op: str = "average",
    threshold_bytes: int | None = None,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    salt=None,
    issue_reversed: bool = False,
):
    """Int8 gradient half of the sharded sync mode: same buckets and
    per-leaf ownership map as ``fusion.fused_reducescatter``, but the
    exchange is the quantized all_to_all + local dequant-sum (the first
    half of :func:`int8_allreduce_flat`, which is itself reduce-scatter +
    allgather in EQuARX form). Each rank keeps only its owned per-leaf
    slices as f32 1-D shards (callers cast). Non-float leaves ride an
    uncompressed allreduce and are sliced locally."""
    from .collective_ops import allreduce_traced
    from .fusion import (
        pack_shard_rows,
        split_shard_row,
        bucket_leaves,
        shard_ownership,
    )
    from ..profiler import annotate_collective

    n = int(world_size)
    tensors = [jnp.asarray(t) for t in tensors]
    sizes = shard_ownership(tensors, n)
    out: list = [None] * len(tensors)
    float_idx = [i for i, t in enumerate(tensors)
                 if jnp.issubdtype(t.dtype, jnp.floating)]
    for i, t in enumerate(tensors):
        if i not in float_idx:
            full = allreduce_traced(
                t, op, axis_name, prescale_factor, postscale_factor)
            s = sizes[i]
            padded = jnp.pad(full.ravel(), (0, n * s - int(full.size)))
            r = lax.axis_index(axis_name)
            out[i] = lax.dynamic_slice(padded, (r * s,), (s,))
    floats = [tensors[i].ravel().astype(jnp.float32) for i in float_idx]
    float_sizes = [sizes[i] for i in float_idx]
    buckets = bucket_leaves(floats, threshold_bytes)
    for bi, bucket in (
            reversed(list(enumerate(buckets))) if issue_reversed
            else enumerate(buckets)):
        bucket_sizes = [float_sizes[j] for j in bucket]
        rows = pack_shard_rows(
            [floats[j] for j in bucket], bucket_sizes, n)
        if prescale_factor != 1.0:
            rows = rows * prescale_factor
        R = rows.shape[1]
        pad = (-R) % BLOCK
        if pad:
            rows = jnp.pad(rows, ((0, 0), (0, pad)))
        with annotate_collective(f"int8_reducescatter.bucket{bi}"):
            row = _reduce_scattered_rows(rows, axis_name, n, op, salt)[:R]
        if postscale_factor != 1.0:
            row = row * postscale_factor
        for j, shard in zip(bucket, split_shard_row(row, bucket_sizes)):
            out[float_idx[j]] = shard
    return out


def int8_fused_allgather_shards(
    shards,
    templates,
    axis_name: str,
    world_size: int,
    threshold_bytes: int | None = None,
    salt=None,
    issue_reversed: bool = False,
):
    """Int8 parameter half of the sharded sync mode: requantize MY
    updated per-leaf shards (one contiguous row per bucket), all_gather
    int8 + scales (the second half of the EQuARX exchange), dequantize,
    and unpack to full tensors (template shapes, f32 — callers cast).
    Non-float templates all_gather uncompressed."""
    from .fusion import bucket_leaves, shard_ownership
    from ..profiler import annotate_collective

    n = int(world_size)
    templates = list(templates)
    sizes = shard_ownership(templates, n)
    out: list = [None] * len(templates)
    # dtype via the attribute, not jnp.asarray: templates may be
    # ShapeDtypeStructs (the deferred-gather path passes shape specs).
    float_idx = [i for i, t in enumerate(templates)
                 if jnp.issubdtype(jnp.dtype(t.dtype), jnp.floating)]
    for i, t in enumerate(templates):
        if i not in float_idx:
            full = lax.all_gather(shards[i], axis_name, axis=0, tiled=True)
            out[i] = full[: int(t.size)].reshape(t.shape)
    f_templates = [templates[i] for i in float_idx]
    f_sizes = [sizes[i] for i in float_idx]
    buckets = bucket_leaves(f_templates, threshold_bytes)
    for bi, bucket in (
            reversed(list(enumerate(buckets))) if issue_reversed
            else enumerate(buckets)):
        bucket_sizes = [f_sizes[j] for j in bucket]
        row = (shards[float_idx[bucket[0]]] if len(bucket) == 1
               else jnp.concatenate(
                   [shards[float_idx[j]] for j in bucket]))
        row = row.astype(jnp.float32)
        R = int(row.size)
        pad = (-R) % BLOCK
        if pad:
            row = jnp.pad(row, (0, pad))
        q, scale = _quantize_blocks(row, salt)
        with annotate_collective(f"int8_allgather.bucket{bi}"):
            gathered = lax.all_gather(
                q.reshape(-1, BLOCK), axis_name)           # [n, r, B]
            gathered_scale = lax.all_gather(scale, axis_name)  # [n, r]
        grid = (gathered.astype(jnp.float32)
                * gathered_scale[:, :, None]).reshape(n, -1)[:, :R]
        offset = 0
        for j, s in zip(bucket, bucket_sizes):
            i = float_idx[j]
            t = templates[i]
            out[i] = (grid[:, offset:offset + s]
                      .reshape(-1)[: int(t.size)].reshape(t.shape))
            offset += s
    return out


def int8_fused_allreduce(
    tensors,
    axis_name: str,
    world_size: int,
    op: str = "average",
    threshold_bytes: int | None = None,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    salt=None,
    issue_reversed: bool = False,
):
    """Bucketed int8 allreduce of a tensor list (the fusion-buffer role:
    same buckets as :func:`ops.fusion.fused_allreduce`, each bucket one
    quantized exchange). Non-float leaves ride an uncompressed allreduce
    — quantizing integer tensors would corrupt them. ``salt`` threads a
    step counter into the stochastic rounding; ``issue_reversed`` emits
    buckets last-first (the overlap scheduler's issue order — gradients
    materialize in reverse layer order during backward)."""
    from .collective_ops import allreduce_traced
    from .fusion import bucket_leaves
    from ..profiler import annotate_collective

    tensors = [jnp.asarray(t) for t in tensors]
    out: list = [None] * len(tensors)
    float_idx = [i for i, t in enumerate(tensors)
                 if jnp.issubdtype(t.dtype, jnp.floating)]
    for i, t in enumerate(tensors):
        if i not in float_idx:
            out[i] = allreduce_traced(
                t, op, axis_name, prescale_factor, postscale_factor)
    # Bucket the POST-CAST f32 view: the exchange is f32-sized whatever
    # the leaf dtype was, and bucketing pre-cast would split buckets at
    # every bf16/f32 boundary in a mixed-precision gradient list.
    floats = [tensors[i].ravel().astype(jnp.float32) for i in float_idx]
    buckets = bucket_leaves(floats, threshold_bytes)
    for bi, bucket in (
            reversed(list(enumerate(buckets))) if issue_reversed
            else enumerate(buckets)):
        flats = [floats[j] for j in bucket]
        packed = flats[0] if len(bucket) == 1 else jnp.concatenate(flats)
        # Comms-planner leg: the int8 wire may take the two-level
        # schedule (per-leg quantization) on a multi-island fabric.
        # ``rhd`` is never a candidate here — the EQuARX exchange is
        # already an all_to_all/all_gather pair, not a ring, so the
        # halving–doubling latency argument does not apply to it. The
        # bucket bytes offered to the planner are the WIRE bytes
        # (~2/element: int8 out + int8 back), matching what the fitted
        # per-algorithm model observes for this exchange.
        from .fusion import bucket_suffix, bucket_plan

        plan = bucket_plan(
            "allreduce", 2 * int(packed.size), axis_name, world_size,
            candidates=("flat", "two_level"))
        with annotate_collective(
                f"int8_allreduce.bucket{bi}{bucket_suffix(plan)}"):
            if plan is not None and plan.algorithm == "two_level":
                reduced = int8_two_level_allreduce_flat(
                    packed, axis_name, plan.islands, op=op,
                    prescale_factor=prescale_factor,
                    postscale_factor=postscale_factor, salt=salt)
            else:
                reduced = int8_allreduce_flat(
                    packed, axis_name, world_size, op=op,
                    prescale_factor=prescale_factor,
                    postscale_factor=postscale_factor, salt=salt)
        offset = 0
        for j in bucket:
            i = float_idx[j]
            size = int(tensors[i].size)
            out[i] = (reduced[offset:offset + size]
                      .reshape(tensors[i].shape).astype(tensors[i].dtype))
            offset += size
    return out
