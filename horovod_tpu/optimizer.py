"""DistributedOptimizer: the heart of the "no training-loop changes" API.

Re-design of the reference's gradient-hook machinery
(``horovod/torch/optimizer.py — _DistributedOptimizer`` and
``horovod/tensorflow/__init__.py — DistributedOptimizer/
DistributedGradientTape``) for the compiled world. The reference intercepts
per-parameter autograd hooks at runtime, enqueues async allreduces, and
synchronizes handles in ``step()``; under XLA the same contract — "wrap your
optimizer, gradients arrive averaged" — is a **gradient transformation**:
the wrapped optax optimizer's ``update()`` first runs the fused allreduce
(trace-time bucketing standing in for the fusion buffer; see
``horovod_tpu.ops.fusion``), then applies the inner optimizer. Everything
compiles into one XLA program, so what the reference's background thread
negotiated at runtime is decided once at trace time and overlapped by XLA's
scheduler (latency hiding without a completion-queue thread).

Supported knobs mirror the reference:
- ``op=Average/Sum/Adasum``, ``prescale_factor``/``postscale_factor``
- ``compression=Compression.fp16/bf16`` (wire-dtype cast around the
  collective, ``horovod/torch/compression.py``)
- ``backward_passes_per_step=k``: accumulate k local microbatch gradients
  before one allreduce (``horovod/tensorflow/gradient_aggregation*.py``)
- ``process_set``: scope the reduction to a sub-mesh
- ``num_groups`` / fusion threshold: grouping control (``GroupTable``)

Use inside a shard_map-over-'hvd' step (the production path) or under pmap
with axis_name='hvd'.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from .attribution import (
    SCOPE_OPTIMIZER,
    SCOPE_WIRE,
    SCOPE_WIRE_COMPRESS,
    SCOPE_WIRE_DECOMPRESS,
)
from .compression import Compression
from .exceptions import SyncModeIneligibleError
from .ops import collective_ops
from .ops.fusion import _fused_allreduce


def _tripwire_flag(reduced, axis_name=None, rank_identical=True):
    """Non-finite tripwire entry (``HOROVOD_NONFINITE_ACTION``): returns
    ``(action, finite_flag)`` over the REDUCED gradients, or
    ``(None, None)`` when unarmed — the flush then traces bit-for-bit as
    before. The flag is made rank-identical (one scalar psum) when the
    caller's reduced view differs per rank (the sharded/fsdp halves);
    the allreduce path's output is already identical everywhere, so the
    skip decision needs no extra collective there. The flag also ships
    to the host accountant (counter + journal + optional coordinated
    abort) via a debug callback."""
    from .ops import fusion

    action = fusion.nonfinite_action()
    if action is None:
        return None, None
    flag = fusion.all_finite(reduced)
    if not rank_identical and axis_name is not None:
        flag = fusion.psum_flag(flag, axis_name)
    fusion.note_finite_traced(flag, action, axis_name)
    return action, flag


def _tripwire_guard(action, flag, updates, new_state, old_state):
    """Apply the ``skip`` action (zero updates + un-advanced state) when
    armed; pass-through otherwise."""
    if action != "skip" or flag is None:
        return updates, new_state
    from .ops import fusion

    return fusion.guard_updates(updates, new_state, old_state, flag)


def _record_flush(sync_mode: str, wire_leaves, threshold_bytes,
                  itemsize_override: int | None = None,
                  packed_bytes: int | None = None) -> None:
    """Metrics-plane instrumentation of a gradient-sync flush.

    Runs at TRACE time (the flush is traced machinery), so the counters
    measure distinct compiled flushes and the histograms their static
    wire bytes / bucket counts — the per-trace shape of the fusion
    buffer, not a per-step rate (see docs/observability.md). Shapes are
    static under tracing, so sizes are exact. ``itemsize_override``
    keeps the bytes histogram honest for exchanges whose wire dtype is
    not the leaves' dtype (int8: the leaves passed in are the f32
    bucketing view, but the wire carries 1 byte/element).
    ``packed_bytes`` is the part of those bytes that went through a
    bucket's packed vector; left out, all of them did (the int8 exchanges
    pack whole buckets). Never raises: observability must not break
    tracing."""
    try:
        from . import metrics
        from .ops.fusion import bucket_leaves

        nbytes = sum(
            int(w.size) * (itemsize_override
                           if itemsize_override is not None
                           else jnp.dtype(w.dtype).itemsize)
            for w in wire_leaves)
        nbuckets = len(bucket_leaves(wire_leaves, threshold_bytes))
        metrics.GRAD_SYNC_FLUSHES.inc(sync_mode=sync_mode)
        metrics.GRAD_SYNC_BYTES.observe(nbytes, sync_mode=sync_mode)
        metrics.GRAD_SYNC_BUCKETS.observe(nbuckets, sync_mode=sync_mode)
        metrics.GRAD_SYNC_LAST_BYTES.set(nbytes, sync_mode=sync_mode)
        metrics.GRAD_SYNC_LAST_BUCKETS.set(nbuckets, sync_mode=sync_mode)
        metrics.GRAD_SYNC_LAST_PACKED_BYTES.set(
            nbytes if packed_bytes is None else packed_bytes,
            sync_mode=sync_mode)
    except Exception:  # noqa: BLE001 — instrumentation is best-effort
        pass


def _compress_leaves(compression, leaves):
    """``(wire tensors, contexts)`` of the leaves, the casts under their
    own scope inside the wire's."""
    from .profiler import annotate_collective

    with annotate_collective(SCOPE_WIRE_COMPRESS):
        compressed = [compression.compress(g) for g in leaves]
    return [c[0] for c in compressed], [c[1] for c in compressed]


def _decompress_leaves(compression, reduced, ctxs):
    from .profiler import annotate_collective

    with annotate_collective(SCOPE_WIRE_DECOMPRESS):
        return [compression.decompress(r, ctx)
                for r, ctx in zip(reduced, ctxs)]


def _inner_update(inner, grads, state, params):
    """The wrapped optimizer's own ``update``, under the compiled step's
    optimizer scope."""
    from .profiler import annotate_collective

    with annotate_collective(SCOPE_OPTIMIZER):
        return inner.update(grads, state, params)


def _reduce_grads(
    grads,
    op,
    axis_name,
    compression,
    prescale_factor,
    postscale_factor,
    threshold_bytes,
    num_groups,
    world_size=None,
    quant_salt=None,
    issue_reversed=False,
):
    """Compress -> fused allreduce -> decompress over a gradient pytree.

    ``quant_salt`` threads a step counter into the int8 path's stochastic
    rounding (see ``ops.quantization._sround``); ``issue_reversed`` emits
    bucket collectives last-first (the overlap scheduler's issue order —
    results are identical, only HLO program order changes).

    When the process set is known (at trace time) to have exactly one
    member, the wire machinery — compression casts, bucket concat/split,
    the collective itself — is all identity-with-overhead, so it's skipped
    entirely and only the scale factors are applied. This is the compiled
    analog of the reference short-circuiting single-rank allreduces.
    """
    if world_size == 1 and op in (
        collective_ops.Average,
        collective_ops.Sum,
    ):
        scale = prescale_factor * postscale_factor
        if scale == 1.0:
            return grads
        return jax.tree.map(lambda g: g * jnp.asarray(scale, g.dtype), grads)

    from .profiler import annotate_collective

    # Everything past the short-circuit is the wire: one phase scope in
    # the compiled step, with the casts, each bucket's pack + collective
    # (``ops.fusion``) and the unpacking as its children.
    with annotate_collective(SCOPE_WIRE):
        if getattr(compression, "marker", None) == "int8":
            # Int8 changes the exchange, not just the wire dtype (summing
            # int8 on the wire overflows): quantized all_to_all +
            # dequant-sum + requant + all_gather, bucketed like the fused
            # path. Needs the axis size as a static int for chunk shapes.
            from .ops.quantization import int8_fused_allreduce

            if op not in (collective_ops.Average, collective_ops.Sum):
                raise ValueError(
                    f"Compression.int8 supports op=Average/Sum, got {op!r}")
            if world_size is None:
                raise ValueError(
                    "Compression.int8 needs a known process-set size at "
                    "trace time (init() first)")
            leaves, treedef = jax.tree.flatten(grads)
            if num_groups and num_groups > 0:
                # Same num_groups contract as the cast path: cap buckets
                # at total/num_groups bytes (sized on the f32 exchange
                # view).
                total = sum(int(jnp.asarray(g).size) * 4 for g in leaves)
                threshold_bytes = max(1, total // num_groups)
            # Bucketing rides the f32 exchange view; the wire is int8.
            _record_flush("allreduce", leaves, threshold_bytes,
                          itemsize_override=1)
            reduced = int8_fused_allreduce(
                leaves, axis_name, world_size, op=op,
                threshold_bytes=threshold_bytes,
                prescale_factor=prescale_factor,
                postscale_factor=postscale_factor,
                salt=quant_salt, issue_reversed=issue_reversed)
            return jax.tree.unflatten(treedef, reduced)

        leaves, treedef = jax.tree.flatten(grads)
        wire, ctxs = _compress_leaves(compression, leaves)
        if num_groups and num_groups > 0:
            # Reference's num_groups: split tensors into N groups, fuse
            # within each. Emulate by capping each bucket at
            # total/num_groups bytes.
            total = sum(int(w.size) * jnp.dtype(w.dtype).itemsize
                        for w in wire)
            threshold_bytes = max(1, total // num_groups)
        reduced, packed_bytes = _fused_allreduce(
            wire, op, axis_name, threshold_bytes, prescale_factor,
            postscale_factor, issue_reversed, world_size)
        _record_flush("allreduce", wire, threshold_bytes,
                      packed_bytes=packed_bytes)
        return jax.tree.unflatten(
            treedef, _decompress_leaves(compression, reduced, ctxs))


def _reduce_expert_partitioned(grads, op, axis_name, compression,
                               prescale_factor, postscale_factor,
                               threshold_bytes, num_groups, ps,
                               expert_set, expert_filter, quant_salt=None):
    """Expert-set-aware gradient reduction (``parallel/moe.py``'s sync
    half): leaves ``expert_filter`` names are resident on ONE rank per
    dispatch group, so their gradients allreduce only within that
    expert's data-parallel replica set
    (:func:`process_sets.expert_partition`'s ``replica_groups`` — a
    ``psum`` over ``axis_index_groups``), while every other leaf rides
    the ordinary fused world allreduce. A world-wide allreduce of an
    expert leaf would average each expert's gradient with the OTHER
    experts' (zero) contributions — silently scaling it by 1/E.

    ``expert_filter`` is a predicate over ``jax.tree_util.keystr``
    leaf paths. Expert leaves always exchange f32 (their replica sets
    are small — compression's win is on the dense world wire); the
    dense leaves keep the full compression/bucketing machinery.
    """
    from jax import lax

    from . import process_sets

    if isinstance(axis_name, (tuple, list)):
        raise SyncModeIneligibleError(
            "expert_filter does not compose with the hierarchical "
            "two-level axis tuple: the replica-set psum needs ONE named "
            "axis whose indices the expert partition maps — unset "
            "HOROVOD_HIERARCHICAL_ALLREDUCE or drop expert_filter")
    n = _known_size(ps)
    if n is None:
        raise SyncModeIneligibleError(
            "expert_filter needs a known process-set size at trace time "
            "(init() first)")
    _, replicas = process_sets.expert_partition(expert_set, n)
    groups = [list(g) for g in replicas]
    r = len(groups[0])
    paths, treedef = jax.tree_util.tree_flatten_with_path(grads)
    is_expert = [bool(expert_filter(jax.tree_util.keystr(p)))
                 for p, _ in paths]
    leaves = [leaf for _, leaf in paths]
    dense = [leaf for leaf, ex in zip(leaves, is_expert) if not ex]
    reduced_dense = iter(_reduce_grads(
        dense, op, axis_name, compression, prescale_factor,
        postscale_factor, threshold_bytes, num_groups, world_size=n,
        quant_salt=quant_salt) if dense else [])

    def _expert_reduce(g):
        # Mirrors the flat wire's scale order: prescale → sum →
        # Average divisor (the REPLICA set size, not the world) →
        # postscale.
        out = (g * jnp.asarray(prescale_factor, g.dtype)
               if prescale_factor != 1.0 else g)
        out = lax.psum(out, axis_name, axis_index_groups=groups)
        if op == collective_ops.Average:
            out = out / r
        if postscale_factor != 1.0:
            out = out * jnp.asarray(postscale_factor, out.dtype)
        return out

    merged = [(_expert_reduce(leaf) if ex else next(reduced_dense))
              for leaf, ex in zip(leaves, is_expert)]
    return jax.tree_util.tree_unflatten(treedef, merged)


_VALID_SYNC_MODES = ("allreduce", "sharded", "fsdp")


def resolve_sync_mode(sync_mode: str | None = None) -> str:
    """Resolve the gradient sync mode: explicit argument > pinned autotune
    decision (``autotune.set_tuned_sync_mode``) > ``HOROVOD_SYNC_MODE``
    env > ``"allreduce"``.

    Resolution happens at **optimizer construction** (not trace time, like
    the fusion threshold): the mode fixes the optimizer-state layout
    (monolithic full pytree vs sharded stacked rows), which ``init`` and
    ``update`` must agree on — an already-built optimizer keeps its mode
    even if a tuner pins a different one later.
    """
    if sync_mode is None:
        from .autotune import tuned_sync_mode

        sync_mode = tuned_sync_mode()
    if sync_mode is None:
        import os

        env = os.environ.get("HOROVOD_SYNC_MODE", "").strip().lower()
        sync_mode = env or "allreduce"
    if sync_mode not in _VALID_SYNC_MODES:
        raise ValueError(
            f"unknown sync_mode {sync_mode!r}; expected one of "
            f"{_VALID_SYNC_MODES}")
    return sync_mode


def _sharded_threshold(leaves, threshold_bytes, num_groups):
    """The reference's num_groups contract applied to the sharded wire:
    cap each bucket at total/num_groups bytes (same rule as the
    allreduce path)."""
    if num_groups and num_groups > 0:
        total = sum(int(jnp.asarray(g).size)
                    * jnp.dtype(jnp.asarray(g).dtype).itemsize
                    for g in leaves)
        return max(1, total // num_groups)
    return threshold_bytes


def _reducescatter_grads(
    grads,
    op,
    axis_name,
    compression,
    prescale_factor,
    postscale_factor,
    threshold_bytes,
    num_groups,
    world_size,
    quant_salt=None,
    issue_reversed=False,
    flush_label: str = "sharded",
):
    """Compress -> fused reduce-scatter -> decompress over a gradient
    pytree: the gradient half of ``sync_mode="sharded"``. An allreduce is
    reduce-scatter + allgather; emitting only the first half here leaves
    ~half the wire time on the gradient critical path — the allgather
    moves to the *updated parameters* (:func:`_gather_param_shards`),
    off that path.

    Returns a pytree congruent to ``grads`` whose leaves are this rank's
    owned 1-D shards (sizes per ``ops.fusion.shard_ownership``).
    """
    if isinstance(axis_name, (tuple, list)):
        from .parallel.mesh import MESH2D_AXES

        # The 2-D (batch, model) training mesh IS supported: reducing
        # over the axis tuple enumerates scatter chunks batch-major,
        # which is exactly flat rank order, so the (world, shard) row
        # layout is byte-identical to the 1-D wire. The hierarchical
        # (cross, local) allreduce mesh stays rejected.
        if tuple(axis_name) != MESH2D_AXES:
            raise ValueError(
                "sync_mode='sharded' does not compose with the "
                "hierarchical (cross, local) mesh; use the flat axis — "
                "for ICI x DCN hierarchy on the flat axis, set "
                "HOROVOD_COMMS_PLANNER and the planner's two_level "
                "schedule gives the RS/AG halves the same "
                "intra-island/cross-island composition per bucket "
                "(ops/comms_planner.py)")
    if world_size is None:
        raise ValueError(
            "sync_mode='sharded' needs a known process-set size at trace "
            "time (init() first)")
    if op not in (collective_ops.Average, collective_ops.Sum):
        raise ValueError(
            f"sync_mode='sharded' supports op=Average/Sum, got {op!r}")
    from .ops.fusion import _fused_reducescatter
    from .profiler import annotate_collective

    n = int(world_size)
    leaves, treedef = jax.tree.flatten(grads)
    if getattr(compression, "marker", None) == "int8":
        from .ops.quantization import int8_fused_reducescatter

        sharded_threshold = _sharded_threshold(
            leaves, threshold_bytes, num_groups)
        _record_flush(flush_label, leaves, sharded_threshold,
                      itemsize_override=1)
        with annotate_collective(SCOPE_WIRE), \
                annotate_collective("grad_reducescatter"):
            shards = int8_fused_reducescatter(
                leaves, axis_name, n, op=op,
                threshold_bytes=sharded_threshold,
                prescale_factor=prescale_factor,
                postscale_factor=postscale_factor,
                salt=quant_salt, issue_reversed=issue_reversed)
            shards = [
                s.astype(l.dtype)
                if jnp.issubdtype(jnp.asarray(l).dtype, jnp.floating) else s
                for s, l in zip(shards, leaves)
            ]
        return jax.tree.unflatten(treedef, shards)
    with annotate_collective(SCOPE_WIRE):
        wire, ctxs = _compress_leaves(compression, leaves)
        sharded_threshold = _sharded_threshold(
            wire, threshold_bytes, num_groups)
        with annotate_collective("grad_reducescatter"):
            shards, packed_bytes = _fused_reducescatter(
                wire, op, axis_name, n, sharded_threshold, prescale_factor,
                postscale_factor, issue_reversed)
        _record_flush(flush_label, wire, sharded_threshold,
                      packed_bytes=packed_bytes)
        restored = _decompress_leaves(compression, shards, ctxs)
    return jax.tree.unflatten(treedef, restored)


def _local_shards(tree, axis_name, world_size):
    """Slice this rank's owned shard out of every (replicated) leaf —
    rank r's row of the zero-padded ``(n, s)`` flat view, per the
    :func:`ops.fusion.shard_ownership` map. Traced-regime only (reads
    ``lax.axis_index``)."""
    from jax import lax

    from .ops.fusion import shard_ownership

    n = int(world_size)
    leaves, treedef = jax.tree.flatten(tree)
    sizes = shard_ownership(leaves, n)
    r = lax.axis_index(axis_name)
    out = []
    for leaf, s in zip(leaves, sizes):
        flat = jnp.pad(jnp.asarray(leaf).ravel(),
                       (0, n * s - int(leaf.size)))
        out.append(lax.dynamic_slice(flat, (r * s,), (s,)))
    return jax.tree.unflatten(treedef, out)


def _embed_shards(shards, templates, axis_name, world_size):
    """Place each locally owned shard at its owner offset of a zeros
    full-shape tensor (one per template leaf) — the overlap scheduler's
    bridge into the sharded mode: custom-vjp cotangents must keep the
    primal's shape, so the segment boundary's reduce-scatter result rides
    a zero background and :func:`_local_shards` later recovers exactly
    the shard."""
    from jax import lax

    from .ops.fusion import shard_ownership

    n = int(world_size)
    templates = [jnp.asarray(t) for t in templates]
    sizes = shard_ownership(templates, n)
    r = lax.axis_index(axis_name)
    out = []
    for tmpl, shard, s in zip(templates, shards, sizes):
        full = jnp.zeros((n * s,), shard.dtype)
        full = lax.dynamic_update_slice(full, shard, (r * s,))
        out.append(full[: int(tmpl.size)]
                   .reshape(tmpl.shape).astype(tmpl.dtype))
    return out


def _gather_param_shards(
    shards,
    templates,
    compression,
    axis_name,
    world_size,
    threshold_bytes=None,
    num_groups=0,
    quant_salt=None,
):
    """Allgather per-leaf shards back to full tensors through the
    optimizer's wire (cast compression halves the allgather bytes; int8
    rides the quantized gather — the second half of the EQuARX
    exchange). ``templates`` is a pytree of full-shape leaves (arrays or
    ShapeDtypeStructs); the result matches its structure/shapes/dtypes.
    Returns it with the wire bytes that went through a bucket's packed
    row (None: all of them, the int8 gather), for whoever records the
    gather."""
    from .profiler import annotate_collective

    n = int(world_size)
    t_leaves, treedef = jax.tree.flatten(
        templates, is_leaf=lambda x: hasattr(x, "shape"))
    s_leaves = jax.tree.flatten(shards)[0]
    if getattr(compression, "marker", None) == "int8":
        from .ops.quantization import int8_fused_allgather_shards

        with annotate_collective(SCOPE_WIRE), \
                annotate_collective("param_allgather"):
            full = int8_fused_allgather_shards(
                s_leaves, t_leaves, axis_name, n,
                threshold_bytes=_sharded_threshold(
                    t_leaves, threshold_bytes, num_groups),
                salt=quant_salt)
            full = [f.astype(t.dtype) for f, t in zip(full, t_leaves)]
        return jax.tree.unflatten(treedef, full), None
    from .ops.fusion import _fused_allgather_shards

    with annotate_collective(SCOPE_WIRE):
        wire, ctxs = _compress_leaves(compression, s_leaves)
        with annotate_collective("param_allgather"):
            full, packed_bytes = _fused_allgather_shards(
                wire, t_leaves, axis_name, n,
                _sharded_threshold(t_leaves, threshold_bytes, num_groups))
        restored = [r.astype(t.dtype) for r, t in zip(
            _decompress_leaves(compression, full, ctxs), t_leaves)]
    return jax.tree.unflatten(treedef, restored), packed_bytes


def _known_size(ps) -> int | None:
    """Process-set size if determinable at trace time, else None.

    Only the not-yet-initialized cases map to "unknown" (framework error,
    or the pre-init global set whose rank list is still empty); a
    genuinely broken process set raises — silently disabling the
    single-rank short-circuit would mask it."""
    from .exceptions import HorovodTpuError

    try:
        n = ps.size()
    except HorovodTpuError:
        return None
    return n if n > 0 else None


class _AccumulationState(NamedTuple):
    inner_state: Any
    acc_grads: Any
    counter: jnp.ndarray  # int32 scalar, monotonic (microstep count)


class _SaltState(NamedTuple):
    """int8 wrapper state: the inner optimizer state plus the update
    counter threaded into stochastic rounding as the salt, so repeated
    gradient values decorrelate across steps (ADVICE r5)."""

    inner_state: Any
    counter: jnp.ndarray  # uint32 scalar, increments per update


class ReduceSpec(NamedTuple):
    """The reduction configuration a DistributedOptimizer was built with,
    attached to its ``update`` function so schedulers that must perform
    the reduction THEMSELVES — the overlap scheduler issues it inside the
    backward pass, per parameter segment — can reuse the exact same wire
    (op, compression, scaling, bucketing) and the bare inner optimizer
    for the update. Read it with :func:`reduce_spec_of`."""

    inner: Any  # the wrapped optax GradientTransformation
    op: str
    compression: Any
    prescale_factor: float
    postscale_factor: float
    process_set: Any
    num_groups: int
    fusion_threshold_bytes: int | None
    backward_passes_per_step: int
    sync_mode: str = "allreduce"
    # Expert parallelism (parallel/moe.py): expert-sharded leaves
    # (named by the ``expert_filter`` keystr predicate) allreduce only
    # within their data-parallel replica set derived from
    # ``expert_set`` — see _reduce_expert_partitioned. Both None →
    # byte-identical to the pre-expert wire.
    expert_set: Any = None
    expert_filter: Any = None


def reduce_spec_of(optimizer) -> ReduceSpec | None:
    """The :class:`ReduceSpec` carried by a DistributedOptimizer-built
    transformation, or None for a bare optax optimizer."""
    return getattr(getattr(optimizer, "update", None),
                   "_hvd_reduce_spec", None)


def _spec_of(optimizer) -> ReduceSpec:
    spec = (optimizer if isinstance(optimizer, ReduceSpec)
            else reduce_spec_of(optimizer))
    if spec is None:
        raise ValueError(
            "expected a DistributedOptimizer-built transformation (or its "
            "ReduceSpec); got a bare optax optimizer")
    return spec


def init_sharded_state(optimizer, params, world_size: int | None = None):
    """Materialize the sharded optimizer state for ``sync_mode="sharded"``
    and ``"fsdp"``: rank r's shard-local inner state, stacked on a
    leading world axis.

    Every array leaf of the monolithic state with ``size m`` becomes
    ``(n, ceil(m/n))`` (rows = per-rank shards of the zero-padded flat
    view, per ``ops.fusion.shard_ownership``); scalar leaves become
    ``(n,)``. The factories shard the leading axis over the mesh
    (``in_specs=P(axis)``), so each rank materializes only its ``1/n``
    of the optimizer state — the ZeRO-1 memory win. ``params`` may be
    the full pytree or an already-resident :class:`ShardedParams` (the
    fsdp flow: the rows ARE the per-rank shard slices the inner init
    runs on, so both spellings produce the identical state).
    """
    from .ops.fusion import shard_ownership
    from .parallel.param_sharding import ShardedParams

    spec = _spec_of(optimizer)
    if isinstance(params, ShardedParams):
        if world_size and int(world_size) != params.world_size:
            raise ValueError(
                f"init_sharded_state got world_size={world_size} but the "
                f"ShardedParams rows are sharded for "
                f"{params.world_size} ranks — reshard_params(params, "
                f"{world_size}) first, or drop the world_size argument")
        n = params.world_size
        treedef = params.meta.treedef
        padded = [jnp.asarray(r) for r in params.rows]
    else:
        n = int(world_size) if world_size else _known_size(spec.process_set)
        if not n:
            raise ValueError(
                "init_sharded_state needs a known process-set size "
                "(init() first, or pass world_size=)")
        leaves, treedef = jax.tree.flatten(params)
        sizes = shard_ownership(leaves, n)
        padded = [
            jnp.pad(jnp.asarray(l).ravel(), (0, n * s - int(l.size)))
            .reshape(n, s)
            for l, s in zip(leaves, sizes)
        ]
    per_rank = [
        spec.inner.init(jax.tree.unflatten(treedef, [p[r] for p in padded]))
        for r in range(n)
    ]
    stacked = jax.tree.map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *per_rank)
    from .parallel.param_sharding import _record_resident, _resident_bytes

    _record_resident("opt_state", spec.sync_mode,
                     _resident_bytes(jax.tree.leaves(stacked), n))
    if getattr(spec.compression, "marker", None) == "int8":
        return _SaltState(stacked, jnp.zeros((n,), jnp.uint32))
    return stacked


def _gather_if_nonaddressable(tree):
    """Replicate any jax.Array leaf whose shards span non-addressable
    devices (a multi-controller world's P(axis)-sharded state): a jitted
    identity with replicated out-sharding compiles to the allgather.
    COLLECTIVE in that regime — every process must reach this call at
    the same program point (unshard_opt_state's callers do: checkpoint
    save and elastic sync run on all ranks). Fully-addressable leaves
    (single-controller, or host numpy from a commit snapshot) pass
    through untouched — the pure-host fast path."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as _P

    def gather(leaf):
        if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
            sharding = leaf.sharding
            if not isinstance(sharding, NamedSharding):
                raise ValueError(
                    "cannot gather a non-addressable sharded state leaf "
                    f"with sharding {sharding!r}; re-place it with "
                    "data_parallel.shard_state (NamedSharding) first")
            replicated = NamedSharding(sharding.mesh, _P())
            return jax.jit(lambda x: x, out_shardings=replicated)(leaf)
        return leaf

    return jax.tree.map(gather, tree)


def unshard_opt_state(optimizer, opt_state, params):
    """Gather a sharded optimizer state back to the monolithic layout —
    the exact pytree ``spec.inner.init(params)`` would have (so a
    rank-0 checkpoint of it is layout-identical to a monolithic one).
    Pure host/jnp math when the stacked rows are locally addressable
    (single-controller worlds, host snapshots); in a multi-controller
    world the P(axis)-sharded rows are first replicated via one compiled
    allgather per leaf (collective — call on every process)."""
    import numpy as np

    from .parallel.param_sharding import ShardedParams

    spec = _spec_of(optimizer)
    state = _gather_if_nonaddressable(opt_state)
    salted = isinstance(state, _SaltState)
    counter = None
    if salted:
        counter = state.counter
        state = state.inner_state
    if isinstance(params, ShardedParams):
        # fsdp flow: the resident rows carry the full shapes as static
        # metadata, so the template comes from eval_shape — no transient
        # full-parameter materialization on the recovery path.
        template = jax.eval_shape(spec.inner.init, params.template_tree())
    else:
        template = spec.inner.init(params)

    def un(st, tmpl):
        st = jnp.asarray(st)
        shape = tuple(tmpl.shape)
        dtype = jnp.dtype(tmpl.dtype)
        if not shape:
            return st[0].astype(dtype)
        size = int(np.prod(shape))
        return st.reshape(-1)[:size].reshape(shape).astype(dtype)

    full = jax.tree.map(un, state, template)
    if salted:
        return _SaltState(full, jnp.asarray(counter)[0])
    return full


def reshard_opt_state(optimizer, full_state, params, world_size: int):
    """Re-shard a monolithic-layout optimizer state for a (possibly new)
    world size — the inverse of :func:`unshard_opt_state`. Shard
    ownership is a pure function of the world size and the parameter
    shapes, so an elastic resize re-derives the layout from the synced
    full pytree with no extra coordination."""
    spec = _spec_of(optimizer)
    del params  # ownership derives from each state leaf's own size
    n = int(world_size) if world_size else 0
    if n < 1:
        raise ValueError(
            f"reshard_opt_state needs a positive world size, got "
            f"{world_size!r} (init() first, or pass the size explicitly)")
    state = full_state
    salted = isinstance(state, _SaltState)
    if salted:
        state = full_state.inner_state

    from .ops.fusion import shard_ownership

    def re(fl):
        fl = jnp.asarray(fl)
        if fl.ndim == 0:
            return jnp.zeros((n,), fl.dtype) + fl
        (s,) = shard_ownership([fl], n)
        return jnp.pad(fl.ravel(), (0, n * s - int(fl.size))).reshape(n, s)

    sharded = jax.tree.map(re, state)
    if salted:
        counter = jnp.asarray(full_state.counter).astype(jnp.uint32)
        return _SaltState(sharded, jnp.zeros((n,), jnp.uint32) + counter)
    return sharded


def sharded_step_update(spec, grads, local_state, params, axis_name=None,
                        grads_are_shards: bool = False,
                        gather: bool = True):
    """One sharded-sync-mode optimizer step INSIDE a shard_map trace:
    reduce-scatter the gradients (unless the overlap scheduler already
    did), run the inner update only on the locally owned shard with the
    shard-local state, then allgather the *updated parameter* shards —
    issued immediately after the shard update, off the gradient critical
    path, where XLA can overlap it with neighboring compute.

    ``local_state`` is this rank's row of the stacked sharded state
    (leading world axis stripped — the factories do this). With
    ``grads_are_shards=True``, ``grads`` already holds the per-leaf owned
    shards (the overlap scheduler's extraction). Returns
    ``(new_params, new_local_state)`` — or, with ``gather=False``, the
    still-sharded updated parameters (the deferred-allgather path gathers
    them in its own program).

    Numerical contract: for ELEMENTWISE inner optimizers (SGD/momentum,
    Adam(W), RMSProp, ...) the result is the monolithic allreduce path's
    within reduction-order tolerance. Inner transformations that reduce
    ACROSS a leaf (global-norm clipping, LARS/LAMB trust ratios) see
    only the local shard and will diverge — compose those outside, or
    use sync_mode='allreduce'.
    """
    import optax

    from .ops.collective_ops import _effective_traced_axis

    if axis_name is None:
        axis_name = (_effective_traced_axis(spec.process_set)
                     or spec.process_set.axis_name)
    n = _known_size(spec.process_set)
    if n is None:
        raise ValueError(
            "sync_mode='sharded' needs a known process-set size at trace "
            "time (init() first)")
    int8 = getattr(spec.compression, "marker", None) == "int8"
    if int8:
        inner_local, salt = local_state.inner_state, local_state.counter
    else:
        inner_local, salt = local_state, None
    if grads_are_shards:
        grad_shards = grads
    else:
        grad_shards = _reducescatter_grads(
            grads, spec.op, axis_name, spec.compression,
            spec.prescale_factor, spec.postscale_factor,
            spec.fusion_threshold_bytes, spec.num_groups,
            world_size=n, quant_salt=salt)
    action, flag = _tripwire_flag(grad_shards, axis_name,
                                  rank_identical=False)
    from .profiler import annotate_collective

    param_shards = _local_shards(params, axis_name, n)
    updates, new_inner = _inner_update(
        spec.inner, grad_shards, inner_local, param_shards)
    updates, new_inner = _tripwire_guard(action, flag, updates, new_inner,
                                         inner_local)
    with annotate_collective(SCOPE_OPTIMIZER):
        new_param_shards = optax.apply_updates(param_shards, updates)
    new_local = _SaltState(new_inner, salt + 1) if int8 else new_inner
    if not gather:
        return new_param_shards, new_local
    new_params, _ = _gather_param_shards(
        new_param_shards, params, spec.compression, axis_name, n,
        spec.fusion_threshold_bytes, spec.num_groups, quant_salt=salt)
    return new_params, new_local


def _accounted_init(init, sync_mode: str):
    """``init`` inside an ``hvd.setup.optimizer_init`` span of the set-up
    account: the sync mode, and the leaves and bytes of the state it
    returns (from shapes: nothing is waited for)."""
    from . import tracing
    from .attribution import SPAN_SETUP_OPTIMIZER_INIT

    @functools.wraps(init)
    def accounted(params):
        with tracing.setup_span(SPAN_SETUP_OPTIMIZER_INIT,
                                {"sync_mode": sync_mode}) as span:
            state = init(params)
            span.note_tree(state)
        return state

    return accounted


def DistributedOptimizer(
    optimizer,
    named_parameters=None,
    op: str = collective_ops.Average,
    compression=Compression.none,
    backward_passes_per_step: int = 1,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    process_set=None,
    num_groups: int = 0,
    fusion_threshold_bytes: int | None = None,
    sync_mode: str | None = None,
    expert_set=None,
    expert_filter=None,
):
    """Wrap an optax ``GradientTransformation`` so gradients are
    allreduce-averaged across the process set before the inner update.

    Returns an optax-compatible GradientTransformation. ``named_parameters``
    exists for reference-signature parity and is unused (pytree leaves are
    already named by their path).

    ``sync_mode`` (default: autotune pin > ``HOROVOD_SYNC_MODE`` >
    ``"allreduce"``) selects the gradient exchange:

    - ``"allreduce"``: every rank allreduces every bucket and redundantly
      runs the full inner update (the reference's contract).
    - ``"sharded"`` (ZeRO-1 style): each bucket's allreduce is split into
      its reduce-scatter + allgather halves — ranks update only their
      owned shard (state from :func:`init_sharded_state`: ~1/n optimizer
      compute and state memory per rank) and the allgather moves to the
      *updated parameters*, off the gradient critical path. ``init``
      returns the stacked sharded state; ``update`` must run inside a
      shard_map with this rank's state row (the step factories handle
      both). Needs an elementwise inner optimizer and op=Average/Sum;
      see docs/perf.md.

    ``expert_set`` + ``expert_filter`` make the reduction
    expert-parallel-aware (``parallel/moe.py``): leaves the filter
    matches (a predicate over ``jax.tree_util.keystr`` paths) allreduce
    only within their expert's data-parallel replica set
    (:func:`process_sets.expert_partition`); everything else rides the
    ordinary world wire. Requires sync_mode='allreduce',
    backward_passes_per_step=1, op=Average/Sum.
    """
    import optax

    del named_parameters
    ps = process_set
    if ps is None:
        from .process_sets import global_process_set

        ps = global_process_set
    axis_name = ps.axis_name
    k = int(backward_passes_per_step)
    if k < 1:
        raise ValueError("backward_passes_per_step must be >= 1")
    sync_mode = resolve_sync_mode(sync_mode)
    if sync_mode == "sharded":
        if op not in (collective_ops.Average, collective_ops.Sum):
            raise SyncModeIneligibleError(
                f"sync_mode='sharded' supports op=Average/Sum, got {op!r}")
        if k != 1:
            raise SyncModeIneligibleError(
                "sync_mode='sharded' does not compose with "
                "backward_passes_per_step > 1: accumulation defers the "
                "reduction, and the shard-local state would go stale "
                "between boundaries — accumulate outside the optimizer "
                "or use sync_mode='allreduce'")
    if sync_mode == "fsdp":
        # The fsdp guard table mirrors the sharded one (docs/perf.md),
        # with one addition: num_groups. Every rejection names the fix.
        if op not in (collective_ops.Average, collective_ops.Sum):
            raise SyncModeIneligibleError(
                f"sync_mode='fsdp' supports op=Average/Sum, got {op!r} "
                "(Adasum's whole-vector dot products need the full "
                "tensors resident on every rank — use "
                "sync_mode='allreduce' for Adasum)")
        if k != 1:
            raise SyncModeIneligibleError(
                "sync_mode='fsdp' does not compose with "
                "backward_passes_per_step > 1: accumulation defers the "
                "reduction past the per-segment gather/reduce-scatter "
                "boundaries, and the shard-local state would go stale "
                "between microsteps — accumulate outside the optimizer "
                "or use sync_mode='allreduce'")
        if num_groups and num_groups > 1:
            raise SyncModeIneligibleError(
                f"sync_mode='fsdp' does not compose with num_groups="
                f"{num_groups}: num_groups caps bucket bytes at "
                "total/num_groups of the WHOLE gradient tree, but the "
                "fsdp wire is per-segment gather/reduce-scatter programs "
                "whose totals differ per segment — cap bucket sizes with "
                "fusion_threshold_bytes instead (it applies uniformly to "
                "every segment's buckets)")

    if expert_filter is not None:
        # Expert-partitioned reduction guard table (docs/perf.md
        # "Expert parallelism") — every rejection names the fix.
        if sync_mode != "allreduce":
            raise SyncModeIneligibleError(
                f"expert_filter does not compose with sync_mode="
                f"{sync_mode!r}: the sharded/fsdp ownership maps assume "
                "every rank holds every leaf, but an expert leaf is "
                "resident on one rank per dispatch group — use "
                "sync_mode='allreduce'")
        if k != 1:
            raise SyncModeIneligibleError(
                "expert_filter does not compose with "
                "backward_passes_per_step > 1: the accumulation "
                "boundary's single fused flush cannot split per-leaf "
                "between the world wire and the replica-set psum — "
                "accumulate outside the optimizer or use "
                "backward_passes_per_step=1")
        if op not in (collective_ops.Average, collective_ops.Sum):
            raise SyncModeIneligibleError(
                f"expert_filter supports op=Average/Sum, got {op!r} "
                "(Adasum's whole-vector dot products have no "
                "replica-subset form — use op=Average)")
    elif expert_set is not None:
        raise ValueError(
            "expert_set without expert_filter: pass expert_filter=<"
            "predicate over jax.tree_util.keystr leaf paths> naming "
            "which gradient leaves are expert-sharded")

    int8 = getattr(compression, "marker", None) == "int8"

    def reduce_fn(grads, salt=None):
        # Trace-time axis resolution: inside a step shard_mapped over the
        # hierarchical (cross, local) mesh the reduction takes the two-level
        # form automatically (HOROVOD_HIERARCHICAL_ALLREDUCE's consumer).
        from .ops.collective_ops import _effective_traced_axis

        effective = _effective_traced_axis(ps) or axis_name
        if expert_filter is not None:
            return _reduce_expert_partitioned(
                grads, op, effective, compression, prescale_factor,
                postscale_factor, fusion_threshold_bytes, num_groups,
                ps, expert_set, expert_filter, quant_salt=salt)
        return _reduce_grads(
            grads,
            op,
            effective,
            compression,
            prescale_factor,
            postscale_factor,
            fusion_threshold_bytes,
            num_groups,
            world_size=_known_size(ps),
            quant_salt=salt,
        )

    spec = ReduceSpec(
        inner=optimizer,
        op=op,
        compression=compression,
        prescale_factor=prescale_factor,
        postscale_factor=postscale_factor,
        process_set=ps,
        num_groups=num_groups,
        fusion_threshold_bytes=fusion_threshold_bytes,
        backward_passes_per_step=k,
        sync_mode=sync_mode,
        expert_set=expert_set,
        expert_filter=expert_filter,
    )

    if sync_mode == "fsdp":

        def init_fsdp(params):
            """Shard-local inner state, stacked on the leading world
            axis (identical layout to sync_mode='sharded' — the fsdp
            difference is the PARAMETER residency, not the state).
            Accepts the full parameter pytree or a resident
            ``ShardedParams``."""
            return init_sharded_state(spec, params)

        def update_fsdp(grads, state, params=None):
            """Shard-domain update: under fsdp, parameters, gradients,
            and optimizer state all live in the shard domain — ``grads``
            are this rank's reduce-scattered shards (the
            ``param_sharding.gather_params`` boundary's output),
            ``state`` is this rank's ROW of the stacked state, and
            ``params`` this rank's parameter shards
            (``ShardedParams.shards_tree`` with the world axis
            stripped). Returns shard-shaped updates — there is no
            trailing full-parameter allgather in this mode; the next
            forward's segment gathers are the only re-materialization.
            The step factories (``make_train_step``) wire all of this;
            hand-rolled steps should mirror ``_make_fsdp_train_step``.
            """
            if params is None:
                raise ValueError(
                    "sync_mode='fsdp' update needs params= (this rank's "
                    "parameter shards — the shard-local update reads "
                    "them)")
            from .ops.collective_ops import _effective_traced_axis

            effective = _effective_traced_axis(ps) or axis_name
            # Tripwire on the reduce-scattered shards: per-rank views,
            # so the skip decision rides one scalar psum to stay
            # rank-identical (state divergence would be worse than the
            # NaN it guards against).
            action, flag = _tripwire_flag(grads, effective,
                                          rank_identical=False)
            if int8:
                inner_local, salt = state.inner_state, state.counter
                upd, new_inner = _inner_update(optimizer, grads,
                                               inner_local, params)
                upd, new_inner = _tripwire_guard(action, flag, upd,
                                                 new_inner, inner_local)
                return upd, _SaltState(new_inner, salt + 1)
            upd, new_inner = _inner_update(optimizer, grads, state, params)
            upd, new_inner = _tripwire_guard(action, flag, upd, new_inner,
                                             state)
            return upd, new_inner

        init_fsdp._hvd_reduce_spec = spec
        update_fsdp._hvd_reduce_spec = spec
        return optax.GradientTransformation(
            _accounted_init(init_fsdp, sync_mode), update_fsdp)

    if sync_mode == "sharded":

        def init_sharded(params):
            return init_sharded_state(spec, params)

        def update_sharded(grads, state, params=None):
            """Sharded update: expects this rank's ROW of the stacked
            sharded state (the step factories strip the leading world
            axis) and returns allgathered FULL updates — the optax
            contract preserved — plus the new local state. The factories
            skip this and gather the updated *parameters* directly
            (:func:`sharded_step_update`), saving the full-tree apply."""
            if params is None:
                raise ValueError(
                    "sync_mode='sharded' update needs params= (the "
                    "shard-local update reads this rank's parameter "
                    "shard)")
            from .ops.collective_ops import _effective_traced_axis

            effective = _effective_traced_axis(ps) or axis_name
            n = _known_size(ps)
            if int8:
                inner_local, salt = state.inner_state, state.counter
            else:
                inner_local, salt = state, None
            grad_shards = _reducescatter_grads(
                grads, op, effective, compression, prescale_factor,
                postscale_factor, fusion_threshold_bytes, num_groups,
                world_size=n, quant_salt=salt)
            action, flag = _tripwire_flag(grad_shards, effective,
                                          rank_identical=False)
            param_shards = _local_shards(params, effective, n)
            updates_sh, new_inner = _inner_update(
                optimizer, grad_shards, inner_local, param_shards)
            updates_sh, new_inner = _tripwire_guard(
                action, flag, updates_sh, new_inner, inner_local)
            updates_full, _ = _gather_param_shards(
                updates_sh, params, compression, effective, n,
                fusion_threshold_bytes, num_groups, quant_salt=salt)
            if int8:
                return updates_full, _SaltState(new_inner, salt + 1)
            return updates_full, new_inner

        init_sharded._hvd_reduce_spec = spec
        update_sharded._hvd_reduce_spec = spec
        return optax.GradientTransformation(
            _accounted_init(init_sharded, sync_mode), update_sharded)

    if k == 1:

        def init_fn(params):
            state = optimizer.init(params)
            if int8:
                # Step-counter salt for stochastic rounding: without it a
                # gradient value that repeats across steps rounds the same
                # direction every step (persistent quantization bias).
                return _SaltState(state, jnp.zeros((), jnp.uint32))
            return state

        def update_fn(grads, state, params=None):
            from .ops.collective_ops import _effective_traced_axis

            effective = _effective_traced_axis(ps) or axis_name
            if int8:
                reduced = reduce_fn(grads, salt=state.counter)
                # Allreduce output is rank-identical by construction —
                # the skip decision needs no extra collective.
                action, flag = _tripwire_flag(reduced, effective)
                updates, new_inner = _inner_update(
                    optimizer, reduced, state.inner_state, params)
                updates, new_inner = _tripwire_guard(
                    action, flag, updates, new_inner, state.inner_state)
                return updates, _SaltState(new_inner, state.counter + 1)
            reduced = reduce_fn(grads)
            action, flag = _tripwire_flag(reduced, effective)
            updates, new_inner = _inner_update(optimizer, reduced, state,
                                               params)
            updates, new_inner = _tripwire_guard(action, flag, updates,
                                                 new_inner, state)
            return updates, new_inner

        update_fn._hvd_reduce_spec = spec
        return optax.GradientTransformation(
            _accounted_init(init_fn, sync_mode), update_fn)

    # backward_passes_per_step > 1: accumulate locally, allreduce on the
    # k-th microstep only (the reference's local gradient aggregation).
    def init_acc(params):
        return _AccumulationState(
            inner_state=optimizer.init(params),
            acc_grads=jax.tree.map(jnp.zeros_like, params),
            counter=jnp.zeros((), jnp.int32),
        )

    def update_acc(grads, state, params=None):
        acc = jax.tree.map(jnp.add, state.acc_grads, grads)
        # Monotonic microstep count (boundary = every k-th): the window
        # index (count // k) doubles as the int8 rounding salt, which a
        # counter that reset each window could not provide.
        count = state.counter + 1
        is_boundary = (count % k) == 0

        def at_boundary(operand):
            from .ops.collective_ops import _effective_traced_axis

            acc_g, inner = operand
            mean_g = jax.tree.map(lambda g: g / k, acc_g)
            salt = (count // k).astype(jnp.uint32) if int8 else None
            reduced = reduce_fn(mean_g, salt=salt)
            action, flag = _tripwire_flag(
                reduced, _effective_traced_axis(ps) or axis_name)
            updates, new_inner = _inner_update(optimizer, reduced, inner,
                                               params)
            updates, new_inner = _tripwire_guard(action, flag, updates,
                                                 new_inner, inner)
            return updates, new_inner, jax.tree.map(jnp.zeros_like, acc_g)

        def between(operand):
            acc_g, inner = operand
            zero_updates = jax.tree.map(jnp.zeros_like, acc_g)
            return zero_updates, inner, acc_g

        updates, new_inner, new_acc = jax.lax.cond(
            is_boundary, at_boundary, between, (acc, state.inner_state)
        )
        return updates, _AccumulationState(new_inner, new_acc, count)

    init_acc._hvd_reduce_spec = spec
    update_acc._hvd_reduce_spec = spec
    return optax.GradientTransformation(
        _accounted_init(init_acc, sync_mode), update_acc)


def grad(loss_fn, argnums=0, has_aux=False, **dist_kwargs):
    """`DistributedGradientTape` equivalent: a grad function whose output
    gradients are already allreduce-averaged across the process set.

    Parity: ``hvd.DistributedGradientTape``
    (``horovod/tensorflow/__init__.py``). Use inside the compiled step::

        grad_fn = hvd.grad(loss_fn)
        g = grad_fn(params, batch)          # averaged over 'hvd'
    """
    op = dist_kwargs.pop("op", collective_ops.Average)
    compression = dist_kwargs.pop("compression", Compression.none)
    process_set = dist_kwargs.pop("process_set", None)
    prescale = dist_kwargs.pop("prescale_factor", 1.0)
    postscale = dist_kwargs.pop("postscale_factor", 1.0)
    threshold = dist_kwargs.pop("fusion_threshold_bytes", None)
    if dist_kwargs:
        raise TypeError(f"unknown arguments: {sorted(dist_kwargs)}")
    ps = process_set
    if ps is None:
        from .process_sets import global_process_set

        ps = global_process_set

    base = jax.grad(loss_fn, argnums=argnums, has_aux=has_aux)

    def wrapped(*args, **kwargs):
        out = base(*args, **kwargs)
        grads, aux = (out if has_aux else (out, None))
        reduced = _reduce_grads(
            grads, op, ps.axis_name, compression, prescale, postscale,
            threshold, 0, world_size=_known_size(ps),
        )
        return (reduced, aux) if has_aux else reduced

    return wrapped
