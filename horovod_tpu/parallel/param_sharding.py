"""Full parameter sharding (ZeRO-3 / FSDP): params live sharded at rest.

PR 4's ``sync_mode="sharded"`` sharded the optimizer state (~1/n per
rank) but every rank still held a full parameter copy, capping the
largest trainable model at one device's HBM. This module removes that
cap: under ``sync_mode="fsdp"`` each rank persistently holds only its
byte-balanced parameter shard (the same rank-identical ownership map the
sharded optimizer state rides — :func:`ops.fusion.shard_ownership`), and
full parameters exist only *transiently, per segment*:

- the forward pass allgathers each segment's parameters just ahead of
  the layers that consume them (:func:`gather_params` — the per-segment
  gather HLOs have no cross-segment dependencies, so XLA's
  latency-hiding scheduler runs segment k+1's gather concurrently with
  segment k's compute: the prefetch);
- the backward pass emits each segment's gradient **reduce-scatter
  inside backprop** (the gather boundary is a custom-vjp whose backward
  reduces the full-shaped cotangents straight down to this rank's owned
  shards — the same boundary trick as ``make_overlapped_train_step``,
  with the cotangent landing in the *shard* domain instead of riding a
  zero background);
- the shard-local optimizer update writes back to the resident shard
  with **no trailing full-parameter allgather at all** — the next step's
  forward gather is the only re-materialization.

Wire per step: one parameter allgather (forward) + one gradient
reduce-scatter (backward) = the same bytes as one allreduce — but
resident param+optimizer memory is ~1/n of monolithic, which is the
unlock for models that do not fit one device's HBM. The int8/cast
compression halves ride the same EQuARX RS/AG machinery as the sharded
mode (``ops/quantization.py``).

Layout notes: the resident representation is :class:`ShardedParams` — a
registered pytree whose leaves are per-leaf ``(world, shard)`` stacked
rows (rank r's shard is row r, exactly the sharded optimizer-state
layout) plus static metadata (original tree structure, shapes, dtypes)
so the full tensors can be re-materialized from shards alone.
``shard_ownership`` being a pure function of shapes and world size keeps
every layer that already round-trips the optimizer state (checkpoints,
elastic resize, the peer replica pool) working on parameters with the
same host math.

``HOROVOD_FSDP_RESHARD_AFTER_FORWARD`` (default 1) keeps the
per-segment just-in-time gathers; ``0`` collapses the segmentation to
one up-front gather whose full tensors plausibly stay live across the
whole forward+backward (retain-after-forward: fewer, larger collectives,
higher in-step peak memory). In the compiled regime the in-step residual
lifetime is ultimately XLA's rematerialization decision — compose with
``jax.remat`` over the model for a hard in-step peak bound; the
*resident* (between-step) footprint is ~1/n either way.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Sequence

import numpy as np

import jax
import jax.numpy as jnp


class _Meta(NamedTuple):
    """Static (hashable) metadata of a :class:`ShardedParams`: the
    original tree structure and per-leaf full shapes/dtypes — everything
    needed to re-materialize full tensors from the shard rows."""

    treedef: Any
    shapes: tuple
    dtypes: tuple
    world_size: int


@jax.tree_util.register_pytree_node_class
class ShardedParams:
    """Resident fsdp-mode parameters: per-leaf stacked ``(world, shard)``
    rows + static full-shape metadata.

    Row ``r`` of every leaf is rank r's owned slice of the zero-padded
    flat view (ownership map: :func:`ops.fusion.shard_ownership`), so
    sharding the leading axis over the mesh
    (``data_parallel.shard_state``) leaves each rank holding ~1/n of the
    model at rest. Registered as a pytree: ``jax.tree.map`` /
    ``device_put`` / shard_map specs all treat the rows as ordinary
    leaves and rebuild the wrapper (metadata is aux data, static under
    tracing).
    """

    def __init__(self, rows: Sequence[Any], meta: _Meta):
        self.rows = list(rows)
        self.meta = meta

    def tree_flatten(self):
        return tuple(self.rows), self.meta

    @classmethod
    def tree_unflatten(cls, meta, rows):
        return cls(list(rows), meta)

    # -- static facts --------------------------------------------------------

    @property
    def world_size(self) -> int:
        return self.meta.world_size

    def templates(self) -> list[jax.ShapeDtypeStruct]:
        """Per-leaf full-shape templates, in row order."""
        return [jax.ShapeDtypeStruct(s, d)
                for s, d in zip(self.meta.shapes, self.meta.dtypes)]

    def template_tree(self):
        """The full-parameter pytree of ShapeDtypeStructs."""
        return jax.tree.unflatten(self.meta.treedef, self.templates())

    def shards_tree(self):
        """This object's row leaves re-hung on the ORIGINAL tree
        structure — the plain-pytree view the shard-local optimizer
        state and gradients are congruent to."""
        return jax.tree.unflatten(self.meta.treedef, self.rows)

    def with_rows(self, rows_tree) -> "ShardedParams":
        """A new ShardedParams carrying ``rows_tree``'s leaves (same
        structure as :meth:`shards_tree`) under this metadata."""
        return ShardedParams(jax.tree.leaves(rows_tree), self.meta)

    def row(self, r: int):
        """Rank ``r``'s shard as a pytree (original structure, one 1-D
        host slice per leaf) — what the peer replica record carries.
        Slices BEFORE the host transfer, so only the owned row (~1/n)
        moves device→host, never the full stacked leaf."""
        return jax.tree.unflatten(
            self.meta.treedef, [np.asarray(x[r]) for x in self.rows])


def _resident_bytes(leaves, world_size: int) -> int:
    # size/dtype are static facts — never np.asarray a leaf here (this
    # runs on resize/checkpoint paths; materializing device arrays on
    # the host for a metrics gauge would cost a full model transfer).
    total = sum(int(l.size) * jnp.dtype(l.dtype).itemsize for l in leaves)
    return total // max(1, int(world_size))


def _record_resident(kind: str, sync_mode: str, nbytes: int) -> None:
    try:
        from .. import metrics

        metrics.RESIDENT_BYTES.set(nbytes, kind=kind, sync_mode=sync_mode)
    except Exception:  # noqa: BLE001 — instrumentation is best-effort
        pass
    try:
        # The memory observatory's live accounting rides the same call
        # sites: every (re)materialization of sharded state updates the
        # hvd_hbm_bytes{kind} cell with its exact per-rank nbytes.
        from .. import memory

        memory.note_resident(kind, nbytes)
    except Exception:  # noqa: BLE001 — instrumentation is best-effort
        pass


def _note_param_leaves(params, sizes, world_size: int) -> None:
    """Feed the memory observatory's forensics table: the per-rank
    resident bytes of every named parameter leaf (ownership-map rows,
    not full leaves — the bytes that actually sit in HBM). Never
    raises."""
    try:
        from .. import memory

        flat, _ = jax.tree_util.tree_flatten_with_path(params)
        per_leaf = [
            (jax.tree_util.keystr(path) or "<root>",
             int(s) * np.dtype(leaf.dtype).itemsize)
            for (path, leaf), s in zip(flat, sizes)
        ]
        per_leaf.sort(key=lambda kv: kv[1], reverse=True)
        memory.note_resident(
            "params", sum(b for _, b in per_leaf),
            top_leaves=per_leaf[:memory.top_n()])
    except Exception:  # noqa: BLE001 — instrumentation is best-effort
        pass


def shard_params(params, world_size: int | None = None) -> ShardedParams:
    """Shard a full parameter pytree into the resident fsdp layout.

    Every leaf of ``size m`` becomes ``(n, ceil(m/n))`` rows of its
    zero-padded flat view (per :func:`ops.fusion.shard_ownership` —
    byte-balanced, rank-identical, a pure function of shapes and world
    size). Pure host/jnp math; place the result on the mesh with
    ``data_parallel.shard_state`` so each rank materializes only its
    row. An already-sharded input is re-sharded for ``world_size``.
    """
    from ..ops.fusion import shard_ownership

    if isinstance(params, ShardedParams):
        full = unshard_params(params)
        return shard_params(full, world_size)
    if world_size is None:
        from .. import basics

        world_size = basics.size()
    n = int(world_size)
    if n < 1:
        raise ValueError(
            f"shard_params needs a positive world size, got {world_size!r} "
            "(init() first, or pass world_size=)")
    from ..tracing import place_span

    # What the host pays here is a pad and a reshape a leaf, each a
    # program of its own: the span does not wait for them.
    with place_span("shard_params", params):
        leaves, treedef = jax.tree.flatten(params)
        # jnp.asarray only — size/shape/dtype are static facts; np.asarray
        # here would pull every full leaf device→host on each resize hop.
        leaves = [jnp.asarray(l) for l in leaves]
        sizes = shard_ownership(leaves, n)
        rows = [
            jnp.pad(l.ravel(), (0, n * s - int(l.size))).reshape(n, s)
            for l, s in zip(leaves, sizes)
        ]
        meta = _Meta(
            treedef=treedef,
            shapes=tuple(tuple(l.shape) for l in leaves),
            dtypes=tuple(np.dtype(l.dtype) for l in leaves),
            world_size=n,
        )
        sp = ShardedParams(rows, meta)
        _record_resident("params", "fsdp", _resident_bytes(rows, n))
        _note_param_leaves(params, sizes, n)
    return sp


def unshard_params(sp: ShardedParams):
    """Gather the resident rows back to the full parameter pytree — the
    exact inverse of :func:`shard_params` (padding trimmed, shapes and
    dtypes restored). Pure host/jnp math when the rows are addressable
    (single-controller worlds, host snapshots); non-addressable
    P(axis)-sharded rows are first replicated via the same compiled
    allgather the optimizer-state unshard uses."""
    from ..optimizer import _gather_if_nonaddressable

    if not isinstance(sp, ShardedParams):
        raise TypeError(
            f"unshard_params expects a ShardedParams, got {type(sp).__name__}"
            " (a full pytree is already unsharded)")
    rows = _gather_if_nonaddressable(sp.rows)
    out = []
    for row, shape, dtype in zip(rows, sp.meta.shapes, sp.meta.dtypes):
        row = jnp.asarray(row)
        size = int(np.prod(shape)) if shape else 1
        flat = row.reshape(-1)[:size]
        out.append(flat.reshape(shape).astype(dtype))
    return jax.tree.unflatten(sp.meta.treedef, out)


def reshard_params(params, world_size: int) -> ShardedParams:
    """Re-shard parameters (full pytree or ShardedParams) for a possibly
    new world size — the elastic-resize hop. Ownership re-derives from
    the new size alone, so no coordination is needed (the same contract
    as ``reshard_opt_state``)."""
    return shard_params(params, world_size)


def stack_param_rows(rows_by_rank: Sequence[Any], meta: _Meta,
                     ) -> ShardedParams:
    """Re-materialize a ShardedParams from per-rank row pytrees (the
    peer replica pool's reconstruction path): ``rows_by_rank[r]`` is the
    pytree :meth:`ShardedParams.row` returned for rank r. The stack must
    be complete — exactly ``meta.world_size`` rows, in rank order."""
    if len(rows_by_rank) != meta.world_size:
        raise ValueError(
            f"stack_param_rows needs {meta.world_size} rows (one per rank "
            f"of the recorded world), got {len(rows_by_rank)}")
    stacked = jax.tree.map(
        lambda *xs: np.stack([np.asarray(x) for x in xs]), *rows_by_rank)
    return ShardedParams(jax.tree.leaves(stacked), meta)


def resident_param_bytes(sp: ShardedParams) -> int:
    """Per-rank resident parameter bytes (one row of every leaf)."""
    return _resident_bytes(sp.rows, sp.world_size)


def reshard_after_forward() -> bool:
    """The ``HOROVOD_FSDP_RESHARD_AFTER_FORWARD`` knob (default on):
    per-segment just-in-time gathers. Off collapses the segmentation to
    one up-front gather (retain-after-forward)."""
    import os

    return os.environ.get(
        "HOROVOD_FSDP_RESHARD_AFTER_FORWARD", "1").strip() != "0"


def _wire_itemsize(compression, dtype) -> int:
    """Bytes per element the gather actually puts on the wire."""
    if getattr(compression, "marker", None) == "int8":
        return 1
    try:
        wire, _ = compression.compress(jnp.zeros((1,), dtype))
        return jnp.dtype(wire.dtype).itemsize
    except Exception:  # noqa: BLE001 — fall back to the storage dtype
        return jnp.dtype(dtype).itemsize


def _record_gather(templates, compression, axis: str = "batch",
                   packed_bytes: int | None = None) -> None:
    """Trace-time metrics record of one parameter-gather program segment
    (static wire bytes — the per-trace shape, not a per-step rate, same
    contract as the grad-sync flush counters), labeled by the mesh axis
    the collective runs over: the flat 1-D wire and the 2-D batch leg
    record under ``axis="batch"``, the 2-D intra-layer leg under
    ``axis="model"``. ``packed_bytes`` is the part of those bytes that
    went through a bucket's packed row (``_gather_param_shards`` counts
    it); left out, all of them did. Never raises."""
    try:
        from .. import metrics

        nbytes = sum(
            int(np.prod(t.shape) if t.shape else 1)
            * _wire_itemsize(compression, t.dtype)
            for t in templates)
        metrics.PARAM_GATHER_BYTES.observe(nbytes, axis=axis)
        metrics.PARAM_GATHER_PACKED_BYTES.observe(
            nbytes if packed_bytes is None else packed_bytes, axis=axis)
    except Exception:  # noqa: BLE001 — instrumentation is best-effort
        pass


def _gather_boundary(shard_leaves, templates, seg_index, spec, axis_name,
                     world_size, salt):
    """Shards-in / full-tensors-out boundary for ONE segment, with the
    gradient reduce-scatter riding the custom-vjp backward.

    Forward: allgather this segment's shards to full tensors through the
    optimizer's wire (cast compression halves the gather bytes; int8
    rides the quantized EQuARX gather). Backward: the full-shaped
    cotangents reduce-scatter through the exact wire the
    DistributedOptimizer was built with (op/compression/scaling/
    bucketing — ``optimizer._reducescatter_grads``), landing directly in
    the shard domain: the cotangent of a ``(s,)`` shard input is the
    reduced ``(s,)`` owned slice. Because the boundary sits inside the
    differentiated function, each segment's reduce-scatter is emitted at
    the point its gradients finish accumulating — inside backprop, where
    it overlaps the remaining layers' backward compute (the overlap
    scheduler's contract, inherited).

    ``salt`` (the int8 stochastic-rounding step counter) rides the
    forward as a residual, exactly like ``_segment_sync``.
    """
    from ..optimizer import (
        _gather_param_shards,
        _record_flush,
        _reducescatter_grads,
    )
    from ..profiler import annotate_collective

    n = int(world_size)
    templates = list(templates)

    def gather(ls, s):
        with annotate_collective(f"fsdp.param_gather.seg{seg_index}"):
            full, packed_bytes = _gather_param_shards(
                list(ls), templates, spec.compression, axis_name, n,
                spec.fusion_threshold_bytes, 0, quant_salt=s)
        _record_gather(templates, spec.compression,
                       packed_bytes=packed_bytes)
        return list(full)

    def reduce_cts(cts, s):
        with annotate_collective(f"fsdp.grad_reducescatter.seg{seg_index}"):
            shards = _reducescatter_grads(
                list(cts),
                spec.op,
                axis_name,
                spec.compression,
                spec.prescale_factor,
                spec.postscale_factor,
                spec.fusion_threshold_bytes,
                0,
                world_size=n,
                quant_salt=s,
                issue_reversed=True,
                # One flush record per segment, labeled fsdp — the mode
                # rides down so the wire-view bytes land under the label
                # that actually ran (no phantom 'sharded' series).
                flush_label="fsdp",
            )
        return [jnp.asarray(sh).astype(jnp.asarray(orig).dtype)
                for sh, orig in zip(shards, shard_leaves)]

    if salt is None:

        @jax.custom_vjp
        def boundary(ls):
            return gather(ls, None)

        def fwd(ls):
            return gather(ls, None), None

        def bwd(_, cts):
            return (reduce_cts(cts, None),)

        boundary.defvjp(fwd, bwd)
        return boundary(list(shard_leaves))

    @jax.custom_vjp
    def boundary_salted(ls, s):
        return gather(ls, s)

    def fwd_salted(ls, s):
        return gather(ls, s), s

    def bwd_salted(s, cts):
        return (reduce_cts(cts, s),
                np.zeros(np.shape(s), jax.dtypes.float0))

    boundary_salted.defvjp(fwd_salted, bwd_salted)
    return boundary_salted(list(shard_leaves), salt)


def gather_params(shards_tree, meta: _Meta, spec, axis_name,
                  world_size: int, salt=None,
                  num_segments: int | None = None):
    """Re-materialize the FULL parameter pytree from this rank's shards,
    segment by segment, inside a shard_map trace — the heart of the fsdp
    forward pass.

    ``shards_tree`` holds this rank's per-leaf 1-D owned shards (the
    :meth:`ShardedParams.shards_tree` view with the leading world axis
    stripped). The template leaves are split into K contiguous
    byte-balanced segments (``ops.fusion.segment_leaves`` — layer order)
    and each segment gets a :func:`_gather_boundary`: the forward
    allgathers that segment's parameters (independent HLOs in segment
    order, so XLA overlaps segment k+1's gather with segment k's
    compute), and differentiating through the result yields gradients
    that are ALREADY reduce-scattered to the shard domain, each
    segment's collective emitted inside backprop.

    With ``HOROVOD_FSDP_RESHARD_AFTER_FORWARD=0`` the segmentation
    collapses to one up-front gather (retain-after-forward).
    """
    from ..ops.fusion import fsdp_segments, segment_leaves

    shard_leaves = jax.tree.leaves(shards_tree)
    templates = [jax.ShapeDtypeStruct(s, d)
                 for s, d in zip(meta.shapes, meta.dtypes)]
    if len(shard_leaves) != len(templates):
        raise ValueError(
            f"gather_params: {len(shard_leaves)} shard leaves vs "
            f"{len(templates)} templates — the shards tree must be the "
            "ShardedParams row view of the same parameter pytree")
    if not reshard_after_forward():
        k = 1
    elif num_segments is not None:
        k = max(1, int(num_segments))
    else:
        k = fsdp_segments()
    full: list[Any] = [None] * len(templates)
    for si, idx in enumerate(segment_leaves(templates, k)):
        gathered = _gather_boundary(
            [shard_leaves[i] for i in idx],
            [templates[i] for i in idx],
            si, spec, axis_name, world_size, salt)
        for i, g in zip(idx, gathered):
            full[i] = g
    return jax.tree.unflatten(meta.treedef, full)


# ---------------------------------------------------------------------------
# The 2-D (batch, model) wire: two-leg gathers / reduce-scatters
# ---------------------------------------------------------------------------


def _gather_boundary_2d(shard_leaves, templates, seg_index, spec,
                        batch: int, model: int, salt):
    """The :func:`_gather_boundary` of the 2-D ``(batch, model)`` mesh:
    same shards-in / full-tensors-out custom-vjp contract, with each
    collective split into two legs placed on the links that suit it.

    Forward — resident ``(shard,)`` rows to full tensors in two hops:

    1. **batch leg** (long hops / DCN): the existing bucketed
       ``_gather_param_shards`` machinery allgathers this rank's shard
       over the ``batch`` axis into its model coordinate's contiguous
       ``(batch*shard,)`` block — 1/model of the segment's bytes on the
       slow links, vs the full segment on the flat 1-D wire.
    2. **model leg** (short ICI hops): one plain ``lax.all_gather`` per
       leaf over the ``model`` axis concatenates the blocks into the
       full flat view — the intra-layer collective XLA schedules on the
       fastest links of the mesh.

    Backward reverses the legs: the full-shaped cotangents
    ``psum_scatter`` over ``model`` down to the block domain, then the
    block cotangents ride the SAME bucketed ``_reducescatter_grads``
    wire as the flat mode over the ``batch`` axis (compression, scaling,
    flush accounting — ``flush_label="fsdp"``), landing in the resident
    ``(shard,)`` domain. ``op=Average`` divides by ``batch`` inside the
    batch leg, so the model leg contributes its own ``1/model`` — the
    composition equals the flat wire's ``1/(batch*model)``.

    The two-hop split of :func:`ops.fusion.shard_ownership_2d` keeps the
    resident row layout byte-identical to the flat wire, so the gathered
    full tensors are bit-equal to the 1-D gather; only the gradient
    reduction association differs (two-leg vs flat), which is
    reduction-order noise.
    """
    from jax import lax

    from ..attribution import SCOPE_WIRE
    from ..optimizer import _gather_param_shards, _reducescatter_grads
    from ..ops import collective_ops
    from ..ops.fusion import shard_ownership_2d
    from ..profiler import annotate_collective

    b, m = int(batch), int(model)
    templates = list(templates)
    ownership = shard_ownership_2d(templates, b, m)
    batch_axis, model_axis = "batch", "model"
    block_templates = [
        jax.ShapeDtypeStruct((share,), t.dtype)
        for (share, _s), t in zip(ownership, templates)
    ]

    def gather(ls, s):
        with annotate_collective(
                f"fsdp.param_gather.batch.seg{seg_index}"):
            blocks, packed_bytes = _gather_param_shards(
                list(ls), block_templates, spec.compression, batch_axis,
                b, spec.fusion_threshold_bytes, 0, quant_salt=s)
        _record_gather(block_templates, spec.compression, axis="batch",
                       packed_bytes=packed_bytes)
        # The model legs go leaf by leaf: nothing packed.
        _record_gather(templates, None, axis="model", packed_bytes=0)
        full = []
        # The batch legs are the wire's own functions and scope
        # themselves; the model legs are plain collectives of this file.
        with annotate_collective(SCOPE_WIRE), annotate_collective(
                f"fsdp.param_gather.model.seg{seg_index}"):
            for blk, t in zip(blocks, templates):
                flat = lax.all_gather(jnp.ravel(blk), model_axis,
                                      tiled=True)
                size = int(np.prod(t.shape)) if t.shape else 1
                full.append(flat[:size].reshape(t.shape).astype(t.dtype))
        return full

    def reduce_cts(cts, s):
        blocks = []
        with annotate_collective(SCOPE_WIRE), annotate_collective(
                f"fsdp.grad_reducescatter.model.seg{seg_index}"):
            for ct, (share, shard) in zip(cts, ownership):
                flat = jnp.ravel(jnp.asarray(ct))
                flat = jnp.pad(flat, (0, m * share - int(flat.size)))
                blk = lax.psum_scatter(flat, model_axis, tiled=True)
                if spec.op is collective_ops.Average:
                    # The batch leg divides by `batch`; this leg owes
                    # the remaining 1/model of the flat wire's 1/world.
                    blk = blk / m
                blocks.append(blk)
        with annotate_collective(
                f"fsdp.grad_reducescatter.batch.seg{seg_index}"):
            shards = _reducescatter_grads(
                blocks,
                spec.op,
                batch_axis,
                spec.compression,
                spec.prescale_factor,
                spec.postscale_factor,
                spec.fusion_threshold_bytes,
                0,
                world_size=b,
                quant_salt=s,
                issue_reversed=True,
                flush_label="fsdp",
            )
        return [jnp.asarray(sh).astype(jnp.asarray(orig).dtype)
                for sh, orig in zip(shards, shard_leaves)]

    if salt is None:

        @jax.custom_vjp
        def boundary(ls):
            return gather(ls, None)

        def fwd(ls):
            return gather(ls, None), None

        def bwd(_, cts):
            return (reduce_cts(cts, None),)

        boundary.defvjp(fwd, bwd)
        return boundary(list(shard_leaves))

    @jax.custom_vjp
    def boundary_salted(ls, s):
        return gather(ls, s)

    def fwd_salted(ls, s):
        return gather(ls, s), s

    def bwd_salted(s, cts):
        return (reduce_cts(cts, s),
                np.zeros(np.shape(s), jax.dtypes.float0))

    boundary_salted.defvjp(fwd_salted, bwd_salted)
    return boundary_salted(list(shard_leaves), salt)


def gather_params_2d(shards_tree, meta: _Meta, spec, batch: int,
                     model: int, salt=None,
                     num_segments: int | None = None):
    """:func:`gather_params` on the 2-D ``(batch, model)`` mesh — the
    same per-segment just-in-time schedule, each segment's boundary
    split into the batch-leg (bucketed machinery) and model-leg (plain
    ICI all_gather) collectives of :func:`_gather_boundary_2d`. The
    resident row layout is identical to the flat wire
    (:func:`ops.fusion.shard_ownership_2d`), so a ShardedParams built by
    :func:`shard_params` for ``world = batch*model`` feeds either."""
    from ..ops.fusion import fsdp_segments, segment_leaves

    shard_leaves = jax.tree.leaves(shards_tree)
    templates = [jax.ShapeDtypeStruct(s, d)
                 for s, d in zip(meta.shapes, meta.dtypes)]
    if len(shard_leaves) != len(templates):
        raise ValueError(
            f"gather_params_2d: {len(shard_leaves)} shard leaves vs "
            f"{len(templates)} templates — the shards tree must be the "
            "ShardedParams row view of the same parameter pytree")
    if int(batch) * int(model) != int(meta.world_size):
        raise ValueError(
            f"gather_params_2d: mesh {batch}x{model} does not factor the "
            f"sharded world of {meta.world_size} rows")
    if not reshard_after_forward():
        k = 1
    elif num_segments is not None:
        k = max(1, int(num_segments))
    else:
        k = fsdp_segments()
    full: list[Any] = [None] * len(templates)
    for si, idx in enumerate(segment_leaves(templates, k)):
        gathered = _gather_boundary_2d(
            [shard_leaves[i] for i in idx],
            [templates[i] for i in idx],
            si, spec, batch, model, salt)
        for i, g in zip(idx, gathered):
            full[i] = g
    return jax.tree.unflatten(meta.treedef, full)
