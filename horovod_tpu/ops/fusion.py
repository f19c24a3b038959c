"""Trace-time tensor fusion: the compiled answer to Horovod's fusion buffer.

The reference packs small tensors into a persistent 64 MiB scratch buffer at
runtime (``horovod/common/fusion_buffer_manager.cc`` + the controller's
``FuseResponses()``), because each NCCL launch has fixed latency, and sends a
tensor over the threshold alone, uncopied. On TPU the grouping can happen
**at trace time**: the gradient pytree is known when the step function is
traced, so we statically group leaves into same-dtype buckets up to
``HOROVOD_FUSION_THRESHOLD`` bytes and emit each bucket together. Inside a
bucket only the leaves under :data:`PACK_CUTOFF_BYTES` are packed: one
concat + one AllReduce + one split for them, and one AllReduce per larger
leaf in its own shape, which the compiler's combiner merges with its
neighbours into an all-reduce that takes its operands where they lie.
The ``sharded`` and ``fsdp`` wires (:func:`fused_reducescatter`,
:func:`fused_allgather_shards`) obey the same rule through the same
split (:func:`_alone_and_small`): a large leaf is scattered and gathered
as itself, the small ones share the bucket's ``(world, R)`` block. A
bucket the comms planner schedules, one on a hierarchical axis tuple and
the int8 exchanges (``ops/quantization.py``) pack every leaf: those work on
one vector, or scale per packed block.

Until PR 25 every leaf was packed, on the expectation that XLA would fuse
the pack/unpack copies into neighboring ops (the role of
``cuda_kernels.cu``'s batched memcpy kernels in the reference). The chip's
trace said it does not: a flat vector and a 2-D leaf are tiled differently
there, so every slice out of a reduced bucket is a re-tiling copy of its
own (PERF.md §5.3), and flattening a ``(world, R)`` block whose ``R`` is no
multiple of a tile is a loop over its rows (PERF.md §6, PR 37).

This "static negotiation" is why no background controller thread exists in
the JAX path: readiness ordering is a dataflow fact inside the compiled
program.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax.numpy as jnp

from ..utils.env import get_int


def fusion_threshold_bytes() -> int:
    # Precedence: explicit autotune decision > init-time config > env >
    # default — the tuner's choice is the most specific fact available
    # (it was measured on THIS model; see autotune.tune_step_fusion).
    from ..autotune import tuned_threshold

    tuned = tuned_threshold()
    if tuned is not None:
        return tuned
    from ..basics import _state

    if _state.initialized and _state.config is not None:
        return _state.config.fusion_threshold_bytes
    return get_int("HOROVOD_FUSION_THRESHOLD", 64 * 1024 * 1024)


def overlap_segments() -> int:
    """Resolve the overlap scheduler's segment count K.

    Precedence mirrors :func:`fusion_threshold_bytes`: a pinned autotune
    decision (the transparent tuner's ``segments`` axis) wins over
    ``HOROVOD_OVERLAP_SEGMENTS`` (default 4). K=1 degenerates to the
    monolithic post-backward reduction.
    """
    from ..autotune import tuned_segments

    tuned = tuned_segments()
    if tuned is not None:
        return max(1, tuned)
    return max(1, get_int("HOROVOD_OVERLAP_SEGMENTS", 4))


def fsdp_segments() -> int:
    """Resolve the fsdp parameter-streaming segment count.

    Precedence: ``HOROVOD_FSDP_SEGMENTS`` > the overlap scheduler's
    resolution (:func:`overlap_segments` — a pinned autotune decision or
    ``HOROVOD_OVERLAP_SEGMENTS``). The two knobs share a default because
    they segment the same leaf list for the same reason (per-segment
    collectives that overlap neighboring compute); the dedicated env
    exists so the gather granularity can diverge from the gradient
    overlap granularity when profiling says so.
    """
    explicit = get_int("HOROVOD_FSDP_SEGMENTS", 0)
    if explicit > 0:
        return explicit
    return overlap_segments()


def segment_leaves(
    leaves: Sequence[Any], num_segments: int
) -> list[list[int]]:
    """Split leaf indices into <= ``num_segments`` contiguous runs of
    roughly equal bytes — the overlap scheduler's stable leaf→segment map.

    The pytree flatten order is the model's layer order, so contiguous
    runs are layer ranges; during backward the LAST run's gradients
    materialize first, and its allreduce can overlap the earlier runs'
    backward compute. Stability contract: the map depends only on the
    leaves' shapes/dtypes/order (never on values or timing), so every
    rank — and every retrace — derives the identical segmentation, which
    the rank-identical collective sequence requires. Empty segments are
    dropped (num_segments > len(leaves) just yields one leaf per run).
    """
    k = max(1, int(num_segments))
    sizes = [int(leaf.size) * jnp.dtype(leaf.dtype).itemsize
             for leaf in leaves]
    total = sum(sizes)
    if not sizes:
        return []
    if total <= 0 or k == 1:
        try:
            from .. import metrics

            metrics.OVERLAP_SEGMENTS.set(1)
        except Exception:  # noqa: BLE001
            pass
        return [list(range(len(sizes)))]
    segments: list[list[int]] = [[] for _ in range(k)]
    cum = 0
    for i, nbytes in enumerate(sizes):
        # Bucket by byte midpoint: monotone in i, so runs stay contiguous.
        mid = cum + nbytes / 2.0
        segments[min(k - 1, int(mid * k / total))].append(i)
        cum += nbytes
    out = [s for s in segments if s]
    try:
        from .. import metrics

        metrics.OVERLAP_SEGMENTS.set(len(out))
    except Exception:  # noqa: BLE001 — instrumentation is best-effort
        pass
    return out


def nonfinite_action() -> str | None:
    """The non-finite tripwire knob, read at TRACE time (like the fusion
    threshold): ``HOROVOD_NONFINITE_ACTION`` = ``warn`` (count/journal),
    ``skip`` (drop the step's update rank-identically), or ``abort``
    (arm the coordinated abort → elastic recovery). Unset/invalid =
    None — the flush traces bit-for-bit as before (no ``is_finite`` HLO
    anywhere)."""
    import os

    action = os.environ.get("HOROVOD_NONFINITE_ACTION", "").strip().lower()
    return action if action in ("warn", "skip", "abort") else None


def all_finite(tree):
    """Scalar bool: every float leaf of ``tree`` is finite — the cheap
    ``isfinite`` reduction the tripwire fuses into the flush (per-bucket
    reductions that XLA folds into the unpack copies it already emits).
    Non-float leaves are finite by definition."""
    import jax

    flags = [jnp.isfinite(leaf).all()
             for leaf in jax.tree.leaves(tree)
             if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating)]
    if not flags:
        return jnp.asarray(True)
    out = flags[0]
    for f in flags[1:]:
        out = jnp.logical_and(out, f)
    return out


def psum_flag(flag, axis_name):
    """Make a per-rank finite flag rank-identical: True only when EVERY
    rank's flag is True (one scalar ``psum`` — the only collective the
    tripwire ever adds, and only on the sharded/fsdp halves, whose
    reduce-scattered gradients differ per rank; the allreduce path's
    reduced buckets are already identical everywhere)."""
    from jax import lax

    bad = jnp.where(flag, 0.0, 1.0).astype(jnp.float32)
    return lax.psum(bad, axis_name) == 0.0


def guard_updates(updates, new_state, old_state, finite):
    """The ``skip`` action: select zero updates and the UN-advanced
    optimizer state when ``finite`` is False — the step's poisoned
    arithmetic is computed and discarded (``where`` is a select, so the
    NaNs in the dead branch never contaminate the kept one). The
    decision is a scalar, identical on every rank by the caller's
    contract, so no state ever diverges."""
    import jax

    guarded_updates = jax.tree.map(
        lambda u: jnp.where(finite, u, jnp.zeros_like(u)), updates)
    guarded_state = jax.tree.map(
        lambda new, old: jnp.where(finite, new, old), new_state, old_state)
    return guarded_updates, guarded_state


def note_finite_traced(finite, action: str, axis_name=None) -> None:
    """Ship the traced finite flag to the host tripwire accountant
    (:func:`horovod_tpu.integrity.note_nonfinite`) via a debug callback.
    The local axis index rides along as a VALUE so the host side counts
    each step once (smallest index seen = this process's own shard) —
    conditioning the callback itself on the index would need a
    partition-id XLA op the SPMD partitioner rejects. Callback emission
    failures are swallowed at trace time: the guard semantics
    (:func:`guard_updates`) never depend on the callback."""
    import jax
    from jax import lax

    from .. import integrity

    try:
        idx = lax.axis_index(axis_name) if axis_name is not None else 0
    except Exception:  # noqa: BLE001 — outside a mapped axis
        idx = 0
    try:
        jax.debug.callback(integrity.note_nonfinite, action, finite, idx)
    except Exception:  # noqa: BLE001 — observability only
        pass


def _wire_bytes(t) -> int:
    return int(t.size) * jnp.dtype(t.dtype).itemsize


def bucket_leaves(
    leaves: Sequence[Any], threshold_bytes: int | None = None
) -> list[list[int]]:
    """Group leaf indices into same-dtype buckets of <= threshold bytes.

    Order-preserving greedy packing (mirrors the controller's first-fit
    response fusion). A leaf larger than the threshold gets its own bucket.
    threshold <= 0 disables fusion (one bucket per leaf).
    """
    if threshold_bytes is None:
        threshold_bytes = fusion_threshold_bytes()
    buckets: list[list[int]] = []
    bucket_dtype = None
    bucket_bytes = 0
    for i, leaf in enumerate(leaves):
        nbytes = _wire_bytes(leaf)
        if (
            threshold_bytes <= 0
            or not buckets
            or bucket_dtype != leaf.dtype
            or bucket_bytes + nbytes > threshold_bytes
        ):
            buckets.append([i])
            bucket_dtype = leaf.dtype
            bucket_bytes = nbytes
        else:
            buckets[-1].append(i)
            bucket_bytes += nbytes
    return buckets


def _bucket_order(buckets, issue_reversed):
    """``(index, bucket)`` pairs in the order they are emitted."""
    pairs = list(enumerate(buckets))
    return reversed(pairs) if issue_reversed else pairs


#: A leaf of at least this many wire bytes rides its collective as itself
#: (an all-reduce, and since PR 37 the reduce-scatter and all-gather of the
#: ``sharded`` and ``fsdp`` wires); smaller ones share a bucket's packed
#: vector. Planned buckets, a hierarchical axis tuple and the int8
#: exchanges do not ask: they pack whole buckets. Packing spares a leaf a
#: collective's fixed cost and charges it a copy in and a copy out, and on
#: a TPU the copy out is a re-tiling (a flat vector and a 2-D leaf are
#: tiled differently), which XLA does not fuse away: cutting 670 MB back
#: into BERT-Large's leaves took 7.8 ms a step on four v5e chips, five
#: times what reading and writing them once would (PERF.md §5.3). Adjacent
#: all-reduces are merged by the compiler where they lie, so an unpacked
#: leaf pays no fixed cost of its own either; the buffer is kept for the
#: biases and norm vectors, kilobytes each and hundreds of them. On that
#: cell 4 MiB (packing the 2 MiB leaves too) cost 3.3 ms a step and 8 KiB
#: changed nothing (PERF.md §6, PR 25). Not an option: nothing about a job
#: but its leaves' sizes decides it.
PACK_CUTOFF_BYTES = 1 << 20


def _note_leaf_sizes(tensors) -> None:
    """Record the flush's leaf layout ``[(nbytes, dtype), ...]`` on the
    communication observatory (trace-time static facts — the input the
    model-guided autotune predictor prices candidate thresholds and
    segment counts against; see ``comms_model.predict_flush_cost``).
    Never raises: observability must not break tracing."""
    try:
        from .. import comms_model

        comms_model.get_model().note_leaf_sizes([
            (int(t.size) * jnp.dtype(t.dtype).itemsize, str(t.dtype))
            for t in tensors
        ])
    except Exception:  # noqa: BLE001 — instrumentation is best-effort
        pass
    try:
        # The memory observatory keeps the element-accurate twin (it
        # shards ELEMENT counts, not bytes — ceil(10/8)*4 != ceil(40/8)):
        # the layout the autotune memory guard prices candidate
        # (sync_mode, segments, mesh) footprints against.
        from .. import memory

        memory.get_observatory().note_layout([
            (int(t.size), jnp.dtype(t.dtype).itemsize, str(t.dtype))
            for t in tensors
        ])
    except Exception:  # noqa: BLE001 — instrumentation is best-effort
        pass


def _reduce_bucket(flat, op, axis_name, prescale_factor, postscale_factor):
    from .collective_ops import allreduce_traced

    return allreduce_traced(flat, op, axis_name, prescale_factor, postscale_factor)


def bucket_plan(op_name: str, nbytes: int, axis_name, world_size,
                 candidates=None):
    """The comms planner's schedule for one bucket, or None (planner
    off, world unknown, hierarchical axis tuple — the two-level mesh
    already owns its schedule). None → the caller keeps its original
    flat code path, which is the ``HOROVOD_COMMS_PLANNER``-unset
    bit-for-bit contract."""
    if world_size is None or isinstance(axis_name, (tuple, list)):
        return None
    from . import comms_planner

    if not comms_planner.enabled():
        return None
    plan = comms_planner.plan_bucket(op_name, int(nbytes), int(world_size),
                                     candidates)
    if plan is None or plan.algorithm == "flat":
        # The flat choice keeps the original emission but still counts
        # in the per-algorithm dispatch ledger (honest labeling); one
        # count per TRACE, the hvd_grad_sync_* contract.
        if plan is not None:
            comms_planner.note_dispatch(op_name, "flat")
        return None
    comms_planner.note_dispatch(op_name, plan.algorithm)
    return plan


def bucket_suffix(plan) -> str:
    """The annotation-name leg naming a non-flat schedule — parsed back
    out by ``comms_model._BUCKET_NAME_RE`` so re-ingested spans feed
    the right per-algorithm fit."""
    return "" if plan is None else f".{plan.algorithm}"


def _reduce_bucket_planned(flat, op, axis_name, prescale_factor,
                           postscale_factor, plan):
    """One planned (non-flat) SUM/Average bucket allreduce: the
    planner's canonical scale-order wrapper (shared with the eager
    builders in ``collective_ops``) owns pre/post scaling and the
    Average divisor, mirroring the flat path's order of operations."""
    from . import comms_planner
    from .collective_ops import Average

    return comms_planner.apply_allreduce_scaled(
        plan, flat, axis_name, op == Average, prescale_factor,
        postscale_factor)


def _alone_and_small(bucket, nbytes, plan=None, whole=False):
    """Split a bucket's leaves into those that ride the collective as
    themselves and those that share its packed vector: ``(alone, small)``,
    each in the bucket's order. ``nbytes[i]`` is leaf ``i``'s bytes on the
    wire. A planned bucket (rhd and two_level are schedules over one flat
    vector) and a ``whole`` one pack every leaf; a vector of one leaf would
    be a copy for nothing, so a lone small leaf goes alone."""
    whole = whole or plan is not None
    small = [i for i in bucket if whole or nbytes[i] < PACK_CUTOFF_BYTES]
    if plan is None and len(small) == 1:
        small = []
    in_vector = set(small)
    return [i for i in bucket if i not in in_vector], small


def _unpack_bucket(reduced, bucket, tensors, out) -> None:
    """Cut a reduced bucket back into its leaves (``out[i]`` for ``i`` in
    ``bucket``), under the wire's unpack scope: the slices and reshapes
    are device work of their own once a bucket holds tens of MB."""
    from ..attribution import SCOPE_WIRE_UNPACK
    from ..profiler import annotate_collective

    with annotate_collective(SCOPE_WIRE_UNPACK):
        offset = 0
        for i in bucket:
            n = tensors[i].size
            out[i] = reduced[offset:offset + n].reshape(tensors[i].shape)
            offset += n


def _fused_allreduce(tensors, op, axis_name, threshold_bytes,
                     prescale_factor, postscale_factor, issue_reversed,
                     world_size):
    """:func:`fused_allreduce`, and the wire bytes of ``tensors`` that went
    through a packed vector (the flush gauge's count, which only the code
    that chose each bucket's schedule can make exactly)."""
    tensors = [jnp.asarray(t) for t in tensors]
    from ..profiler import annotate_collective
    from .collective_ops import Adasum, Average, Sum

    if op == Adasum:
        # Adasum's scale factors are whole-vector dot products — packing
        # tensors into one buffer would couple per-layer factors (the
        # reference computes them per tensor inside its fusion buffer too).
        return [
            _reduce_bucket(t, op, axis_name, prescale_factor, postscale_factor)
            for t in tensors
        ], 0
    _note_leaf_sizes(tensors)
    plannable = op in (Sum, Average)
    # The two-level composition's reduce-scatter leg cuts ONE vector into
    # the local axis's shares, so a bucket on an axis tuple stays whole.
    hierarchical = isinstance(axis_name, (tuple, list))
    out: list[Any] = [None] * len(tensors)
    packed_bytes = 0
    for bi, bucket in _bucket_order(
            bucket_leaves(tensors, threshold_bytes), issue_reversed):
        # Annotation names carry the bucket's static wire bytes so a
        # profile of the step attributes transfer time to sized buckets
        # (the tracing plane's per-collective vocabulary, trace-time leg).
        sizes = {i: _wire_bytes(tensors[i]) for i in bucket}
        nbytes = sum(sizes.values())
        plan = (bucket_plan("allreduce", nbytes, axis_name, world_size)
                if plannable else None)
        alone, small = _alone_and_small(bucket, sizes, plan, hierarchical)
        with annotate_collective(
                f"allreduce.bucket{bi}.{nbytes}B{bucket_suffix(plan)}"):
            # Next to each other, in their own shapes: the compiler's
            # combiner merges neighbouring all-reduces into one that takes
            # its operands where they lie.
            for i in (reversed(alone) if issue_reversed else alone):
                out[i] = _reduce_bucket(
                    tensors[i], op, axis_name, prescale_factor,
                    postscale_factor)
            if small:
                flats = [tensors[i].ravel() for i in small]
                packed = (flats[0] if len(small) == 1
                          else jnp.concatenate(flats))
                packed_bytes += sum(sizes[i] for i in small)
                reduced = (
                    _reduce_bucket(packed, op, axis_name, prescale_factor,
                                   postscale_factor) if plan is None
                    else _reduce_bucket_planned(
                        packed, op, axis_name, prescale_factor,
                        postscale_factor, plan))
        if small:
            _unpack_bucket(reduced, small, tensors, out)
    return out, packed_bytes


def fused_allreduce(
    tensors: Sequence[Any],
    op,
    axis_name: str,
    threshold_bytes: int | None = None,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    issue_reversed: bool = False,
    world_size: int | None = None,
) -> list[Any]:
    """Allreduce a list of tensors with static bucketing (traced regime).

    A bucket (:func:`bucket_leaves`) is what is emitted together under one
    ``hvd.allreduce.bucket<i>.<n>B`` scope: each leaf of at least
    :data:`PACK_CUTOFF_BYTES` reduced as itself, in its own shape, and the
    leaves under it concatenated into one flat vector, reduced, and cut
    back (``hvd.wire.unpack``). A bucket the comms planner schedules, and
    one on a hierarchical axis tuple, packs every leaf: those schedules
    work on one vector.

    ``issue_reversed`` emits the collectives last-bucket-first, and last
    leaf first inside a bucket — the overlap scheduler's issue order:
    inside a backward pass the last leaves' gradients materialize first,
    so reverse emission puts each HLO next to the point its operands
    become ready (results are identical either way; only the program
    order hint changes).

    ``world_size`` (the process-set size as a static int) arms the
    comms planner: with ``HOROVOD_COMMS_PLANNER`` set and the size
    known, each Sum/Average bucket's collective algorithm is chosen per
    bucket (flat ring / recursive halving–doubling / two-level
    ICI×DCN — ``ops/comms_planner.py``); unset or unknown, every bucket
    keeps the flat emission bit-for-bit.
    """
    return _fused_allreduce(
        tensors, op, axis_name, threshold_bytes, prescale_factor,
        postscale_factor, issue_reversed, world_size)[0]


def fused_allreduce_pytree(
    tree,
    op,
    axis_name: str,
    threshold_bytes: int | None = None,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    world_size: int | None = None,
):
    """Allreduce every leaf of a pytree (the gradient pytree) with fusion."""
    import jax

    leaves, treedef = jax.tree.flatten(tree)
    reduced = fused_allreduce(
        leaves,
        op,
        axis_name,
        threshold_bytes=threshold_bytes,
        prescale_factor=prescale_factor,
        postscale_factor=postscale_factor,
        world_size=world_size,
    )
    return jax.tree.unflatten(treedef, reduced)


def shard_ownership(leaves: Sequence[Any], world_size: int) -> list[int]:
    """Per-leaf shard sizes for the sharded sync mode's ownership map.

    Rank ``r`` owns elements ``[r*s : (r+1)*s]`` of every leaf's flat view
    zero-padded to ``world_size * s``, where ``s = ceil(size / world_size)``
    — so ownership is byte-balanced per leaf and every rank's owned bytes
    total ``~1/world_size`` of the model. Same stability contract as
    :func:`segment_leaves`: the map depends only on the leaves'
    shapes/order and the world size (never on values, timing, or rank),
    so every rank — and every retrace — derives the identical ownership,
    which the rank-identical collective sequence and the sharded
    optimizer-state layout both require. Being PER-LEAF (not per-bucket)
    makes the map independent of the fusion threshold and the overlap
    segment count: wire grouping can change (autotune, K) without
    invalidating optimizer state sharded under a different grouping.
    """
    n = max(1, int(world_size))
    return [max(1, -(-int(leaf.size) // n)) for leaf in leaves]


def shard_ownership_2d(leaves: Sequence[Any], batch: int, model: int,
                       ) -> list[tuple[int, int]]:
    """Per-leaf ``(model_share, shard)`` sizes for the 2-D
    ``(batch, model)`` mesh — :func:`shard_ownership` computed per mesh
    axis.

    The flat leaf zero-padded to ``batch*model*shard`` splits first over
    ``model`` into contiguous blocks of ``model_share = batch * shard``
    elements (model coordinate m owns block m — the model-axis gather's
    unit), then each block over ``batch`` into rows of ``shard``
    elements (batch coordinate b owns row b — the batch-axis
    reduce-scatter's unit). Device ``(b, m)`` therefore resident-holds
    flat slice ``(m*batch + b) * shard : +shard`` — and because
    ``ceil(ceil(s/model)/batch) == ceil(s/(model*batch))``, ``shard`` is
    IDENTICAL to the flat :func:`shard_ownership` over
    ``world = batch*model``: the resident row layout (and with it every
    checkpoint, resize hop, and peer replica) is shared between the 1-D
    and 2-D wires, only the gather/reduce schedule differs. Same
    stability contract: a pure function of shapes and axis sizes.
    """
    b = max(1, int(batch))
    m = max(1, int(model))
    shards = shard_ownership(leaves, b * m)
    return [(b * s, s) for s in shards]


def _flat_padded(leaf, length: int):
    """``leaf`` as a flat vector zero-padded to ``length`` elements."""
    flat = leaf.ravel()
    pad = length - int(flat.size)
    return jnp.pad(flat, (0, pad)) if pad else flat


def pack_shard_rows(leaves, shard_sizes, world_size):
    """Pack same-dtype leaves into one ``(world_size, R)`` block whose row
    ``r`` is the concatenation of rank r's per-leaf owned slices — the
    layout under which a tiled reduce-scatter of the flattened block hands
    each rank exactly its owned slices, contiguously."""
    n = world_size
    rows = [_flat_padded(leaf, n * s).reshape(n, s)
            for leaf, s in zip(leaves, shard_sizes)]
    return rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=1)


def split_shard_row(row, shard_sizes):
    """Inverse of one row of :func:`pack_shard_rows`: split a rank's
    contiguous owned run back into per-leaf 1-D shards."""
    out = []
    offset = 0
    for s in shard_sizes:
        out.append(row[offset:offset + s])
        offset += s
    return out


def _fused_reducescatter(tensors, op, axis_name, world_size,
                         threshold_bytes, prescale_factor, postscale_factor,
                         issue_reversed):
    """:func:`fused_reducescatter`, and the wire bytes of ``tensors`` that
    went through a packed block (the flush gauge's count, as
    :func:`_fused_allreduce` makes it)."""
    from jax import lax

    from ..attribution import SCOPE_WIRE_UNPACK
    from ..profiler import annotate_collective
    from .collective_ops import Average, Sum

    if op not in (Sum, Average):
        raise ValueError(f"fused_reducescatter supports Sum/Average, got {op!r}")
    n = int(world_size)
    tensors = [jnp.asarray(t) for t in tensors]
    _note_leaf_sizes(tensors)
    sizes = shard_ownership(tensors, n)
    scale = postscale_factor / n if op == Average else postscale_factor
    out: list[Any] = [None] * len(tensors)
    packed_bytes = 0

    def scatter(flat, plan):
        if prescale_factor != 1.0:
            flat = flat * jnp.asarray(prescale_factor, flat.dtype)
        if plan is not None:
            from . import comms_planner

            row = comms_planner.apply_reducescatter_sum(plan, flat, axis_name)
        else:
            row = lax.psum_scatter(
                flat, axis_name, scatter_dimension=0, tiled=True)
        if scale != 1.0:
            row = row * jnp.asarray(scale, row.dtype)
        return row

    for bi, bucket in _bucket_order(
            bucket_leaves(tensors, threshold_bytes), issue_reversed):
        wire = {i: _wire_bytes(tensors[i]) for i in bucket}
        nbytes = sum(wire.values())
        plan = bucket_plan("reducescatter", nbytes, axis_name, n)
        alone, small = _alone_and_small(bucket, wire, plan)
        small_sizes = [sizes[i] for i in small]
        with annotate_collective(
                f"reducescatter.bucket{bi}.{nbytes}B{bucket_suffix(plan)}"):
            # A leaf alone: the tiled scatter of its flat view is its
            # owned shard, nothing packed and nothing cut.
            for i in (reversed(alone) if issue_reversed else alone):
                out[i] = scatter(
                    _flat_padded(tensors[i], n * sizes[i]), plan)
            if small:
                packed_bytes += sum(wire[i] for i in small)
                row = scatter(pack_shard_rows(
                    [tensors[i] for i in small], small_sizes, n).ravel(),
                    plan)
        if small:
            with annotate_collective(SCOPE_WIRE_UNPACK):
                for i, shard in zip(small,
                                    split_shard_row(row, small_sizes)):
                    out[i] = shard
    return out, packed_bytes


def fused_reducescatter(
    tensors: Sequence[Any],
    op,
    axis_name: str,
    world_size: int,
    threshold_bytes: int | None = None,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    issue_reversed: bool = False,
) -> list[Any]:
    """Reduce a tensor list across ``axis_name`` keeping only the locally
    owned shard of each tensor — the gradient half of the sharded sync
    mode (an allreduce is reduce-scatter + allgather; this emits just the
    first half, so only ~half the wire time sits on the gradient critical
    path).

    Buckets ride :func:`bucket_leaves` exactly like :func:`fused_allreduce`
    (same-dtype, threshold-capped), one ``hvd.reducescatter.bucket<i>.<n>B``
    scope each. Within a bucket a leaf of at least
    :data:`PACK_CUTOFF_BYTES` is scattered as itself (a tiled
    ``psum_scatter`` of its flat view *is* its owned shard: the ownership
    map, :func:`shard_ownership`, is per leaf and contiguous), and the
    smaller ones are packed in the :func:`pack_shard_rows` interleaved
    layout so ONE tiled ``psum_scatter`` hands every rank their owned
    slices. A planned bucket packs every leaf. Returns one 1-D shard per
    input tensor, length ``shard_ownership(tensors, world_size)[i]``.
    """
    return _fused_reducescatter(
        tensors, op, axis_name, world_size, threshold_bytes,
        prescale_factor, postscale_factor, issue_reversed)[0]


def _fused_allgather_shards(shards, templates, axis_name, world_size,
                            threshold_bytes=None, issue_reversed=False):
    """:func:`fused_allgather_shards`, and the wire bytes of the templates
    whose shards went through a packed row."""
    from jax import lax

    from ..attribution import SCOPE_WIRE_UNPACK
    from ..profiler import annotate_collective

    n = int(world_size)
    templates = list(templates)
    sizes = shard_ownership(templates, n)
    out: list[Any] = [None] * len(templates)
    packed_bytes = 0
    for bi, bucket in _bucket_order(
            bucket_leaves(templates, threshold_bytes), issue_reversed):
        itemsize = {i: jnp.dtype(shards[i].dtype).itemsize for i in bucket}
        wire = {i: int(templates[i].size) * itemsize[i] for i in bucket}
        nbytes = sum(n * sizes[i] * itemsize[i] for i in bucket)
        plan = bucket_plan("allgather", nbytes, axis_name, n)
        alone, small = _alone_and_small(bucket, wire, plan)
        small_sizes = [sizes[i] for i in small]
        full = {}
        with annotate_collective(
                f"allgather.bucket{bi}.{nbytes}B{bucket_suffix(plan)}"):
            # A leaf alone: the tiled gather of its shards is its flat
            # view, nothing concatenated and nothing cut out of a grid.
            for i in (reversed(alone) if issue_reversed else alone):
                full[i] = lax.all_gather(
                    shards[i], axis_name, axis=0, tiled=True)
            if small:
                packed_bytes += sum(wire[i] for i in small)
                row = (shards[small[0]] if len(small) == 1
                       else jnp.concatenate([shards[i] for i in small]))
                if plan is not None:
                    from . import comms_planner

                    grid = comms_planner.apply_allgather_row(
                        plan, row, axis_name)
                else:
                    grid = lax.all_gather(row, axis_name, axis=0, tiled=True)
        with annotate_collective(SCOPE_WIRE_UNPACK):
            if small:
                grid = grid.reshape(n, -1)
                offset = 0
                for i, s in zip(small, small_sizes):
                    full[i] = grid[:, offset:offset + s].reshape(-1)
                    offset += s
            for i in bucket:
                t = templates[i]
                out[i] = full[i][: int(t.size)].reshape(t.shape)
    return out, packed_bytes


def fused_allgather_shards(
    shards: Sequence[Any],
    templates: Sequence[Any],
    axis_name: str,
    world_size: int,
    threshold_bytes: int | None = None,
    issue_reversed: bool = False,
) -> list[Any]:
    """Inverse of :func:`fused_reducescatter`: every rank contributes its
    per-leaf owned shards and receives the full tensors (template shapes,
    shard dtype — callers cast). This is the parameter half of the sharded
    sync mode: issued on *updated parameters*, it sits off the gradient
    critical path where XLA can overlap it with neighboring compute.

    Bucketing follows ``bucket_leaves(templates)`` so the grouping is
    derived from the same static facts on every rank; one
    ``hvd.allgather.bucket<i>.<n>B`` scope a bucket. Within a bucket a
    leaf of at least :data:`PACK_CUTOFF_BYTES` on the wire is gathered as
    itself and the smaller ones' shards are concatenated into one row,
    gathered, and cut back out of the ``(world, R)`` grid
    (``hvd.wire.unpack``); a planned bucket packs every leaf.
    """
    return _fused_allgather_shards(
        shards, templates, axis_name, world_size, threshold_bytes,
        issue_reversed)[0]


def pipeline_interleave(n_segments: int, launch, consume):
    """Software-pipeline ``n_segments`` launch→consume pairs so segment
    ``i+1``'s launch is emitted BEFORE segment ``i``'s consume.

    The overlap scheduler's trick, factored out for reuse: inside a
    trace, program order is dataflow order, so emitting
    ``launch(1); consume(0); launch(2); consume(1); ...`` gives XLA's
    latency-hiding scheduler an independent collective to run under
    every compute segment (the expert-parallel MoE wire overlaps its
    dispatch alltoalls with expert FFN compute this way —
    ``parallel/moe.py``; jaxpr-asserted in tests/test_moe_parallel.py).
    ``launch(i)`` starts segment ``i``'s transfer, ``consume(i,
    launched_i)`` turns it into the segment result; returns the list of
    consume results in segment order. Reverse-mode AD transposes both
    and reverses program order, so the backward jaxpr interleaves the
    transposed collectives with the transposed compute for free.
    """
    k = int(n_segments)
    if k <= 0:
        return []
    launched = [launch(0)]
    results = []
    for i in range(1, k):
        launched.append(launch(i))
        results.append(consume(i - 1, launched[i - 1]))
    results.append(consume(k - 1, launched[k - 1]))
    return results


def pad_to_multiple(x, multiple: int, axis: int = 0):
    """Zero-pad `x` along `axis` to a multiple of `multiple`.

    Helper for alltoall/reducescatter whose dim-0 must divide evenly on TPU
    (static shapes); returns (padded, original_size).
    """
    size = x.shape[axis]
    remainder = size % multiple
    if remainder == 0:
        return x, size
    pad = multiple - remainder
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size
