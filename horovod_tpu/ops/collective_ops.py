"""The collective op surface: allreduce / allgather / broadcast / alltoall /
reducescatter (+ grouped variants, barrier).

TPU-native re-design of the reference's op layer (``horovod/common/ops/*`` +
the per-framework ``mpi_ops.py`` wrappers). The reference *invokes* library
collectives (NCCL/MPI/Gloo) at runtime after negotiating readiness; here
collectives are *compiled*: XLA HLO collectives (AllReduce, AllGather,
AllToAll, ReduceScatter, CollectivePermute) over the ICI mesh. Two regimes,
one API:

**Traced regime** — called inside a compiled step (under ``shard_map`` over a
process set's axis). The call lowers directly to the HLO collective; fusion
with neighboring computation is XLA's job. This is the production path: the
DistributedOptimizer's gradient allreduce compiles into the train step, and
the negotiation/fusion machinery of the reference is replaced by trace-time
bucketing (``horovod_tpu.ops.fusion``).

**Eager regime** — called outside any trace, for reference-style scripting
(`hvd.allreduce(np.array(...))`) and tests. Tensors use the
*stacked-rank convention*: a value for a process set of size N is an array of
shape ``(N, *tensor_shape)``, row r holding rank r's tensor (the
single-controller representation of "each rank has a tensor"). The call is
backed by a per-signature compiled executable
(``horovod_tpu.ops.executable_cache``) sharded over the set's sub-mesh.

Reduce op constants mirror ``horovod/common/common.h``'s ``ReduceOp``.
"""

from __future__ import annotations

import collections
import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .executable_cache import global_cache

# Per-kind eager-dispatch counters (allreduce/allgather/broadcast/...):
# the observability counterpart of the reference's per-op timeline
# counts. Compiled-regime collectives are invisible here by design —
# they are HLOs inside the user's step; these count the EAGER surface
# whose executables ride the cache below. Read via :func:`cache_stats`.
_dispatch_counts: "collections.Counter[str]" = collections.Counter()


def cache_stats(reset: bool = False) -> dict:
    """Executable-cache and eager-dispatch counters.

    Parity: the reference's response-cache hit statistics
    (``response_cache.cc``) surfaced through the timeline. Returns::

        {"executable_cache": {"hits", "misses", "size", "capacity",
                              "bytes"},
         "eager_dispatch": {kind: count, ...},
         "compile": {"trace_s", "lower_s", "backend_compile_s",
                     "cache_hits", "cache_misses", "programs",
                     "listening", "steps": {step: {"first_call",
                                                   "recompiles"}}},
         "setup": {"open", "dropped", "spans": [span, ...],
                   "by_name": {name: {"count", "total_s", "self_s"}}}}

    ``compile`` is JAX's own account (``jax.monitoring``) of every
    program this process traced, lowered and compiled since
    ``hvd.init()`` or ``hvd.enable_compile_cache()``, whichever ran
    first (``profiler.CompileAccount``): seconds of jaxpr tracing, of
    lowering to MLIR and of backend compile (on a persistent-cache hit:
    of loading the executable), the persistent cache's hits and misses,
    and per factory step the share of its first call and the number of
    recompiles. ``reset`` leaves it alone.

    ``setup`` is the set-up account (``tracing.SetupAccount``): every
    span recorded from the first line of ``import horovod_tpu`` to the
    end of the first factory-step call in which nothing compiled, whole
    (``name``, ``t``, ``dur``, ``id``, ``parent``, ``args``) and on the
    tracer's clock: the ``hvd.setup.*`` spans of import, ``init``,
    placing and building, one span a tracing, lowering, backend compile
    and cache read that JAX reported meanwhile (a nested jit's tracing a
    child of its caller's), and the ``hvd.step`` calls up to the first
    warm one. ``by_name`` gives each name's count, the union of its
    intervals and its self time; ``open`` says whether the account still
    records, ``dropped`` what its 2,048 places could not hold.
    ``hvd.shutdown()`` and a new ``hvd.init()`` open the next account.
    ``reset`` leaves it alone.

    ``bytes`` is the cache's noted memory cost — the sum of each resident
    entry's serialized-program size, recorded by the dispatch path on the
    compile miss (entries whose size could not be measured contribute 0,
    so it is a lower bound). The same total feeds the memory
    observatory's ``hvd_hbm_bytes{kind="executables"}`` gauge.

    Also surfaced in ``hvd.profiler.summary()``.

    ``reset=True`` zeroes the hit/miss/dispatch counters AFTER collecting
    them (cached executables stay cached) — tests use it so counters do
    not leak between them. The cluster metrics registry resets
    separately via ``metrics.reset_for_testing()``.
    """
    from .. import profiler, tracing

    cache = global_cache()
    stats = {
        "executable_cache": {
            "hits": cache.hits,
            "misses": cache.misses,
            "size": len(cache),
            "capacity": cache.capacity,
            "bytes": cache.nbytes(),
        },
        "eager_dispatch": dict(_dispatch_counts),
        "compile": profiler.compile_account().summary(),
        "setup": tracing.get_tracer().setup_summary(),
    }
    if reset:
        _dispatch_counts.clear()
        cache.reset_stats()
    return stats

# -- Reduce ops (parity: horovod.torch.mpi_ops Average/Sum/Adasum/Min/Max) ---

Average = "average"
Sum = "sum"
Min = "min"
Max = "max"
Product = "product"
Adasum = "adasum"

_VALID_OPS = (Average, Sum, Min, Max, Product, Adasum)


def _resolve_process_set(process_set):
    if process_set is None:
        from ..process_sets import global_process_set

        return global_process_set
    return process_set


def _in_axis_scope(axis_name) -> bool:
    """True when called under shard_map/pmap with `axis_name` bound."""
    from ..basics import in_axis_scope

    return in_axis_scope(axis_name)


def _effective_traced_axis(ps):
    """The axis (name or hierarchical tuple) bound in the current trace.

    Inside a shard_map over the process set's own axis, that's the axis;
    inside a shard_map over the hierarchical ``(cross, local)`` mesh (only
    meaningful for the global set), it's the axis tuple — collectives then
    take the two-level form. None → not in a trace (eager regime).
    """
    if _in_axis_scope(ps.axis_name):
        return ps.axis_name
    if ps.process_set_id == 0:
        from ..parallel.hierarchical import HIERARCHICAL_AXES
        from ..parallel.mesh import MESH2D_AXES

        if _in_axis_scope(HIERARCHICAL_AXES):
            return HIERARCHICAL_AXES
        # The 2-D (batch, model) training mesh: a global-set collective
        # traced inside it reduces over the axis tuple — batch rides the
        # two-level cross leg, model the short-hop local leg.
        if _in_axis_scope(MESH2D_AXES):
            return MESH2D_AXES
    return None


def _axis_size(axis_name: str) -> int:
    return lax.psum(1, axis_name)


# ---------------------------------------------------------------------------
# Traced-regime implementations (inside shard_map) — pure lax.
# ---------------------------------------------------------------------------


def allreduce_traced(x, op, axis_name, prescale_factor, postscale_factor):
    if isinstance(axis_name, (tuple, list)) and len(axis_name) == 2:
        # Hierarchical (cross, local) axes: Sum/Average/Adasum take the
        # two-level ICI+DCN composition (reduce-scatter local → allreduce
        # cross → allgather local); Min/Max/Product fall through — lax
        # reduces over an axis tuple directly.
        if op in (Sum, Average, Adasum):
            from ..parallel.hierarchical import hierarchical_allreduce

            return hierarchical_allreduce(
                x,
                op,
                cross_axis=axis_name[0],
                local_axis=axis_name[1],
                prescale_factor=prescale_factor,
                postscale_factor=postscale_factor,
            )
        axis_name = tuple(axis_name)
    if prescale_factor != 1.0:
        x = x * jnp.asarray(prescale_factor, dtype=x.dtype)
    if op == Sum:
        out = lax.psum(x, axis_name)
    elif op == Average:
        out = lax.pmean(x, axis_name)
    elif op == Min:
        out = lax.pmin(x, axis_name)
    elif op == Max:
        out = lax.pmax(x, axis_name)
    elif op == Product:
        gathered = lax.all_gather(x, axis_name, axis=0)
        out = jnp.prod(gathered, axis=0)
    elif op == Adasum:
        from .adasum import adasum_reduce

        out = adasum_reduce(x, axis_name)
    else:
        raise ValueError(f"unknown reduce op {op!r}; expected one of {_VALID_OPS}")
    if postscale_factor != 1.0:
        out = out * jnp.asarray(postscale_factor, dtype=out.dtype)
    return out


def _allgather_traced(x, axis_name):
    # Horovod allgather concatenates along dim 0 (equal shapes on TPU: XLA
    # requires static uniform shapes; the reference's ragged first dim is
    # supported eagerly via padding in `allgather_object`).
    return lax.all_gather(x, axis_name, axis=0, tiled=True)


def _broadcast_traced(x, root_rank, axis_name):
    # No broadcast HLO is exposed through lax; the idiomatic XLA form is a
    # masked psum, which XLA lowers to a one-to-all on ICI.
    idx = lax.axis_index(axis_name)
    zero = jnp.zeros_like(x)
    contrib = jnp.where(idx == root_rank, x, zero)
    return lax.psum(contrib, axis_name)


def _alltoall_traced(x, axis_name):
    return lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0, tiled=True)


def _reducescatter_traced(x, op, axis_name, prescale_factor, postscale_factor):
    if op not in (Sum, Average):
        raise ValueError(f"reducescatter supports Sum/Average, got {op!r}")
    if prescale_factor != 1.0:
        x = x * jnp.asarray(prescale_factor, dtype=x.dtype)
    out = lax.psum_scatter(x, axis_name, scatter_dimension=0, tiled=True)
    scale = postscale_factor
    if op == Average:
        scale = scale / _axis_size(axis_name)
    if scale != 1.0:
        out = out * jnp.asarray(scale, dtype=out.dtype)
    return out


# ---------------------------------------------------------------------------
# Eager-regime dispatch: stacked-rank arrays over the set's sub-mesh,
# executed via the compiled-executable cache. In multi-controller worlds, a
# host tensor WITHOUT the stacking axis takes the native-runtime host path.
# ---------------------------------------------------------------------------


def _native_world_if_per_process(ps, x):
    """Return the NativeWorld when the reference's per-process scripting
    idiom applies, else None.

    In a multi-controller world (``hvdrun -np N``), ``hvd.allreduce(t)``
    on HOST data (numpy array, list, scalar) means "reduce MY tensor
    across processes" — the reference's most common idiom
    (``horovod.torch.mpi_ops.allreduce``). That cannot compile as one XLA
    program (each controller holds only its own value), so it routes
    through the native C++ runtime's host data plane (negotiation +
    response cache + fusion + TCP ring — the reference's MPI/Gloo role).

    A ``jax.Array`` keeps the compiled stacked-rank path: device data is
    the single-controller/global regime, and jax itself requires it to be
    process-identical. The dispatch is by TYPE, not shape — a shape
    heuristic would misroute host tensors whose leading dim happens to
    equal the device-world size.
    """
    import os

    nprocs = int(os.environ.get("HOROVOD_NUM_PROCESSES", "1") or 1)
    if nprocs <= 1:
        return None
    if isinstance(x, jax.Array):
        return None  # stacked-rank compiled path (global device data)
    from ..parallel.hierarchical import _default_native_world

    return _default_native_world()


def _native_set_for(ps, world) -> int:
    """Map a Python process set to a native-runtime set id.

    Valid when the world runs one device per process (the standard TPU
    deployment shape), where device rank == process id. Registration
    happens for ALL known sets in Python-id order: ids are assigned
    identically on every process (``add_process_set`` /
    ``remove_process_set`` are collective and SPMD programs touch the
    native path at the same program point, as in the reference), so the
    native ids agree without extra coordination — regardless of which set
    each process happens to touch first.
    """
    if ps.process_set_id == 0:
        return 0
    if ps.process_set_id < 0:
        raise ValueError(
            f"process set {ps.ranks} is not registered (removed, or "
            "add_process_set was never called)"
        )
    cache = getattr(world, "_py_ps_map", None)
    if cache is None:
        cache = world._py_ps_map = {}
    mapped = cache.get(ps.process_set_id)
    if mapped is not None:
        return mapped
    import os

    from .. import basics

    nprocs = int(os.environ.get("HOROVOD_NUM_PROCESSES", "1") or 1)
    if basics.size() != nprocs:
        raise ValueError(
            "per-process eager collectives on a non-global process set "
            "need one device per process (device rank == process id); "
            f"this world has {basics.size()} device ranks across {nprocs} "
            "processes — use the stacked-rank convention or a traced "
            "(shard_map) collective"
        )
    from ..process_sets import _table

    for psid in sorted(_table):
        if psid == 0 or psid in cache:
            continue
        cache[psid] = world.register_process_set(_table[psid].ranks)
    return cache[ps.process_set_id]


def _link_class_of(ps) -> str:
    """The worst link class spanned by a process set (the comms model's
    ``link_class`` attribution for its flat eager collectives), from the
    init-time topology; falls back to the process-count heuristic when
    uninitialized. Cached per set id ON the Topology instance — the
    class is static within a world epoch and this sits on the eager
    dispatch hot path; an elastic re-init builds a fresh Topology, so
    the cache dies with the old world (keying a module map by id(topo)
    would alias a recycled address onto stale classes)."""
    try:
        import os

        from ..basics import _state

        topo = _state.topology
        if topo is not None:
            cache = topo.__dict__.setdefault("_link_class_by_set", {})
            # The declared-fabric override participates in the key: the
            # classification is a function of (set, live map), and a
            # test that declares an emulated fabric mid-run must not be
            # served the previous fabric's cached class.
            key = (ps.process_set_id,
                   os.environ.get("HOROVOD_LINK_CLASS_MAP", ""))
            cls = cache.get(key)
            if cls is None:
                cls = topo.set_link_class(ps.ranks)
                cache[key] = cls
            return cls
    except Exception:  # noqa: BLE001 — attribution is best-effort
        pass
    return "dcn" if jax.process_count() > 1 else "ici"


def _eager_dispatch(kind: str, traced_fn, x, process_set, extra_key=(),
                    plan_spec=None):
    ps = _resolve_process_set(process_set)
    mesh = ps.mesh
    axis = ps.axis_name
    n = ps.size()
    x = jnp.asarray(x)
    if x.ndim < 1 or x.shape[0] != n:
        raise ValueError(
            f"eager {kind} expects the stacked-rank convention: leading axis "
            f"of size {n} (= process set size); got shape {x.shape}. Inside "
            f"a compiled step, call this op under shard_map over axis "
            f"{axis!r} instead."
        )
    nbytes = int(x.size) * x.dtype.itemsize
    # Comms-planner leg (``ops/comms_planner.py``): ops that supply a
    # ``plan_spec`` — ``(op_name, builder)`` where ``builder(plan)``
    # yields the planned traced fn — may take a non-flat schedule for
    # this payload on the GLOBAL set (subset axes keep flat: their rank
    # positions do not map onto the topology's island layout). The
    # chosen algorithm joins the executable-cache key (it changes the
    # compiled program) and is what the span/metrics/model see.
    algorithm = "flat"
    planner_live = False
    plan_sig: tuple = ()
    if plan_spec is not None and n > 1 and ps.process_set_id == 0:
        from . import comms_planner

        if comms_planner.enabled():
            planner_live = True
            op_name, builder = plan_spec
            plan = comms_planner.plan_bucket(op_name, nbytes, n)
            if plan is not None and plan.algorithm != "flat":
                algorithm = plan.algorithm
                traced_fn = builder(plan)
                # The island layout joins the key: a two_level
                # executable is compiled FOR a fabric, and a mid-run
                # HOROVOD_LINK_CLASS_MAP change (the supported
                # emulated-fabric flow) must rebuild, not silently
                # reuse the old islands' schedule.
                plan_sig = (plan.islands,)
    key = (kind, x.shape, str(x.dtype), ps.process_set_id, extra_key,
           algorithm) + plan_sig

    def build():
        def shard_fn(v):
            # Each shard is (1, *tensor_shape): strip the stacking axis so the
            # op sees the rank's tensor, then restore it for re-stacking.
            return traced_fn(v[0])[None]

        fn = jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=P(axis),
            out_specs=P(axis),
            check_vma=False,
        )
        return jax.jit(fn)

    import time as _time

    from .. import metrics as _metrics
    from .. import tracing as _tracing
    from ..stall import get_inspector
    from ..timeline import mark_cycle

    mark_cycle()
    _dispatch_counts[kind] += 1
    _metrics.COLLECTIVE_DISPATCH.inc(kind=kind)
    _metrics.COLLECTIVE_BYTES.observe(nbytes, kind=kind)
    if planner_live:
        from . import comms_planner

        comms_planner.note_dispatch(plan_spec[0], algorithm)
    cache = global_cache()
    # Attribution by THIS call's builder running, not by diffing the
    # global miss counter — a concurrent miss on another key inside this
    # call's window would otherwise count a spurious miss (and a bogus
    # near-zero compile sample) against this dispatch.
    build_info: dict = {}

    def instrumented_build():
        t_build = _time.perf_counter()
        result = build()
        build_info["compile_s"] = _time.perf_counter() - t_build
        return result

    compiled = cache.get_or_build(key, instrumented_build)
    missed = "compile_s" in build_info
    _metrics.CACHE_EVENTS.inc(outcome="miss" if missed else "hit")
    if missed:
        _metrics.COLLECTIVE_COMPILE.observe(build_info["compile_s"],
                                            kind=kind)
        try:
            # Note the entry's memory cost once, on the miss that paid
            # the compile: the lowered program text is a serialization
            # proxy for the executable's size (exact device code size is
            # not exposed portably). Feeds cache_stats()["bytes"] and
            # hvd_hbm_bytes{kind="executables"}.
            cache.note_bytes(key, len(compiled.lower(x).as_text()))
        except Exception:  # noqa: BLE001 — the ledger is best-effort
            pass
    sharding = NamedSharding(mesh, P(axis))
    x = jax.device_put(x, sharding)
    # Eager ops are synchronous (reference parity: hvd.allreduce blocks;
    # async flavors live in the runtime backend) — and blocking inside the
    # ticket window is what lets the stall inspector see execution hangs,
    # not just dispatch.
    link_class = _link_class_of(ps)
    ticket = get_inspector().begin(f"{kind}[{x.shape}]")
    t_exec = _time.perf_counter()
    try:
        # tracing.span triple-emits: the host Chrome-trace activity (plus
        # its xprof annotation) AND a cross-rank step-tracer span — the
        # per-collective record the merged /timeline and the skew gauges
        # are built from. The args carry the comms model's attribution
        # vocabulary (bytes / algorithm / link_class) so shipped spans
        # can be re-ingested by comms_model.ingest_steps.
        with _tracing.span(
            kind,
            "collective",
            args={
                "shape": list(x.shape),
                "dtype": str(x.dtype),
                "cache": "miss" if missed else "hit",
                "bytes": nbytes,
                "op": kind,
                "algorithm": algorithm,
                "link_class": link_class,
            },
        ):
            out = compiled(x)
            jax.block_until_ready(out)
            dt = _time.perf_counter() - t_exec
            _metrics.COLLECTIVE_LATENCY.observe(dt, kind=kind)
            if kind == "alltoall":
                # The alltoall wire gets its own per-algorithm latency
                # family (the MoE dispatch/combine probes feed the same
                # one), so planner A/Bs read straight off the scrape.
                _metrics.ALLTOALL_LATENCY.observe(dt, algorithm=algorithm)
            try:
                # Every timed eager dispatch is an alpha-beta sample:
                # one collective of `nbytes` over this set's worst link
                # class took `dt` seconds (compile excluded — t_exec
                # starts after get_or_build). The EXECUTED algorithm is
                # what gets attributed, so each schedule trains its own
                # LinkFit instead of conflating into the flat one.
                from .. import comms_model as _comms_model

                _comms_model.observe(kind, algorithm, link_class, nbytes,
                                     dt)
            except Exception:  # noqa: BLE001 — the model is advisory
                pass
            return out
    finally:
        get_inspector().end(ticket)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def _resolve_op(op, average):
    # `average=` is the reference's deprecated bool form; keep it working.
    if op is None:
        if average is None:
            return Average
        return Average if average else Sum
    if average is not None:
        raise ValueError("specify either op= or average=, not both")
    if op not in _VALID_OPS:
        raise ValueError(f"unknown reduce op {op!r}; expected one of {_VALID_OPS}")
    return op


def allreduce(
    tensor,
    average: bool | None = None,
    op: str | None = None,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    process_set=None,
    name: str | None = None,
):
    """Reduce `tensor` across the process set; every rank gets the result.

    Parity: ``horovod.torch.mpi_ops.allreduce`` /
    ``horovod/common/ops/*_operations.cc`` Allreduce classes. On TPU this is
    one AllReduce HLO over the ICI ring of the set's sub-mesh.
    """
    op = _resolve_op(op, average)
    ps = _resolve_process_set(process_set)
    traced_axis = _effective_traced_axis(ps)
    if traced_axis is not None:
        return allreduce_traced(
            tensor, op, traced_axis, prescale_factor, postscale_factor
        )
    world = _native_world_if_per_process(ps, tensor)
    if world is not None:
        if op not in (Sum, Average, Min, Max):
            raise ValueError(
                f"per-process eager allreduce supports Sum/Average/Min/Max; "
                f"got {op!r} (use the traced regime for {op})"
            )
        import numpy as np

        return world.allreduce(
            np.ascontiguousarray(tensor), name=name, op=op,
            prescale_factor=prescale_factor, postscale_factor=postscale_factor,
            process_set_id=_native_set_for(ps, world),
        )
    del name  # names exist for runtime negotiation; nothing to key here
    traced = functools.partial(
        allreduce_traced,
        op=op,
        axis_name=ps.axis_name,
        prescale_factor=prescale_factor,
        postscale_factor=postscale_factor,
    )
    plan_spec = None
    if op in (Sum, Average):

        def _planned_allreduce(plan):
            def traced_planned(t):
                from . import comms_planner

                out = comms_planner.apply_allreduce_scaled(
                    plan, t.ravel(), ps.axis_name, op == Average,
                    prescale_factor, postscale_factor)
                return out.reshape(t.shape)

            return traced_planned

        plan_spec = ("allreduce", _planned_allreduce)
    return _eager_dispatch(
        "allreduce", traced, tensor, ps,
        (op, prescale_factor, postscale_factor), plan_spec=plan_spec
    )


def grouped_allreduce(
    tensors: Sequence[Any],
    average: bool | None = None,
    op: str | None = None,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    process_set=None,
):
    """Allreduce a list of tensors as one fused operation.

    Parity: ``hvd.grouped_allreduce`` + the reference's ``GroupTable``
    (``horovod/common/group_table.cc``). In the traced regime the fusion pass
    packs the group into same-dtype buckets and emits one AllReduce per
    bucket — the compiled equivalent of the reference's fusion buffer.
    """
    op = _resolve_op(op, average)
    ps = _resolve_process_set(process_set)
    traced_axis = _effective_traced_axis(ps)
    if traced_axis is not None:
        from .fusion import fused_allreduce

        try:
            group_world = ps.size() or None
        except Exception:  # noqa: BLE001 — pre-init: planner stays off
            group_world = None
        return fused_allreduce(
            list(tensors),
            op=op,
            axis_name=traced_axis,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor,
            world_size=group_world,
        )
    tensors = list(tensors)
    # Type-based dispatch (see _native_world_if_per_process): a group of
    # host tensors is per-process; jax.Arrays keep the compiled path. A
    # mixed group follows its first member — splitting one group across
    # two data planes would break the atomicity contract.
    world = _native_world_if_per_process(ps, tensors[0]) if tensors else None
    if world is not None:
        if op not in (Sum, Average, Min, Max):
            raise ValueError(
                f"per-process eager grouped_allreduce supports "
                f"Sum/Average/Min/Max; got {op!r} (use the traced regime)"
            )
        import numpy as np

        # Atomic enqueue of the whole group (GroupTable semantics); the
        # native controller schedules and fuses it as one ring collective.
        return world.grouped_allreduce(
            [np.ascontiguousarray(t) for t in tensors], op=op,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor,
            process_set_id=_native_set_for(ps, world))
    # Contract note (vs the native plane's ATOMIC group enqueue): this
    # eager fallback maps per-tensor. That is sound, not a race, because
    # the single-controller regime has exactly one thread issuing ops in
    # program order — there is no peer whose interleaving could split the
    # group (the hazard GroupTable exists for). The compiled path gets
    # true fusion from fused_allreduce above; the native path gets the
    # atomic group. If a multi-threaded eager issuer is ever added, this
    # fallback must become atomic too.
    return [
        allreduce(
            t,
            op=op,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor,
            process_set=ps,
        )
        for t in tensors
    ]


def allgather(tensor, process_set=None, name: str | None = None):
    """Concatenate each rank's tensor along axis 0 on every rank.

    Parity: ``hvd.allgather``. Ragged first dims (per-rank-different
    dim-0 sizes — the reference contract) are supported on the
    per-process native path (``allgather_v``: size exchange + pad +
    compact). The COMPILED stacked-rank regime requires equal shapes (XLA
    static shapes); pad upstream there or gather eagerly.
    """
    ps = _resolve_process_set(process_set)
    traced_axis = _effective_traced_axis(ps)
    if traced_axis is not None:
        return _allgather_traced(tensor, traced_axis)
    world = _native_world_if_per_process(ps, tensor)
    if world is not None:
        import numpy as np

        # allgather_v: ranks may contribute different dim-0 sizes (the
        # reference's ragged-first-dim contract).
        return world.allgather_v(np.ascontiguousarray(tensor), name=name,
                                 process_set_id=_native_set_for(ps, world))
    del name

    # Eager stacked form: (n, d0, ...) -> (n, n*d0, ...): every row holds the
    # concatenation. all_gather(tiled) inside gives per-shard (n*d0, ...).
    def traced(x):
        return _allgather_traced(x, ps.axis_name)

    def _planned_allgather(plan):
        def traced_planned(t):
            from . import comms_planner

            full = comms_planner.apply_allgather_row(
                plan, t.ravel(), ps.axis_name)
            return full.reshape((plan.world * t.shape[0],) + t.shape[1:])

        return traced_planned

    return _eager_dispatch("allgather", traced, tensor, ps,
                           plan_spec=("allgather", _planned_allgather))


def broadcast(tensor, root_rank: int, process_set=None, name: str | None = None):
    """Broadcast rank `root_rank`'s tensor to every rank in the set.

    Parity: ``hvd.broadcast`` / ``BroadcastOp``; as in the reference,
    `root_rank` is a **global** rank (which must belong to the set), not a
    set-relative index. Compiled as a masked psum, which XLA turns into a
    root-sourced transfer over ICI.
    """
    ps = _resolve_process_set(process_set)
    try:
        relative_root = ps.ranks.index(root_rank)
    except ValueError:
        raise ValueError(
            f"root_rank {root_rank} (a global rank) is not a member of "
            f"process set {ps.ranks}"
        ) from None
    traced_axis = _effective_traced_axis(ps)
    if traced_axis is not None:
        return _broadcast_traced(tensor, relative_root, traced_axis)
    world = _native_world_if_per_process(ps, tensor)
    if world is not None:
        import numpy as np

        # Native world ranks are process ids. The native runtime expects a
        # WORLD rank for broadcast roots; ps.ranks holds global ranks.
        return world.broadcast(np.ascontiguousarray(tensor),
                               root_rank=root_rank, name=name,
                               process_set_id=_native_set_for(ps, world))
    del name

    def traced(x):
        return _broadcast_traced(x, relative_root, ps.axis_name)

    return _eager_dispatch("broadcast", traced, tensor, ps, (relative_root,))


def alltoall(tensor, splits=None, process_set=None, name: str | None = None):
    """Scatter distinct chunks of `tensor` to every rank, gather received.

    Parity: ``hvd.alltoall`` (the collective primitive MoE/expert-parallel
    dispatch builds on). Equal splits compile to one AllToAll HLO — the
    all-to-all rides ICI directly.

    Uneven ``splits`` (the reference's variable-chunk contract) are
    supported outside the traced regime and return the reference's pair
    ``(output, received_splits)``:

    - per-process host path: ``alltoall_v`` recipe — split-table exchange +
      pad-to-max + one equal alltoall + compact (native negotiation
      throughout, subsets included);
    - eager stacked-rank path: pad-to-max into the ONE compiled AllToAll
      HLO, then per-row compaction. ``splits`` may be per-rank ``(n, n)``
      (row r = rank r's split table) or a shared ``(n,)`` vector; the
      ragged per-rank results come back as a list of arrays (row r = rank
      r's received concatenation).

    Inside jit (traced regime) XLA's static shapes make ragged exchange
    unrepresentable — pad to equal chunks upstream.
    """
    ps = _resolve_process_set(process_set)
    traced_axis = _effective_traced_axis(ps)
    if traced_axis is not None:
        if splits is not None:
            raise NotImplementedError(
                "uneven alltoall splits cannot compile inside jit (XLA "
                "static shapes). The jit-compatible path is pad-to-"
                "capacity: route into fixed per-destination slots with "
                "horovod_tpu.parallel.moe.route_to_capacity (the "
                "capacity-factor routing helper — overflow tokens take "
                "the passthrough residual; see docs/perf.md 'Expert "
                "parallelism'), pad raw chunks with "
                "horovod_tpu.ops.fusion.pad_to_multiple, or call the "
                "eager/host flavor outside the trace"
            )
        return _alltoall_traced(tensor, traced_axis)
    world = _native_world_if_per_process(ps, tensor)
    if world is not None:
        import numpy as np

        ps_id = _native_set_for(ps, world)
        if splits is not None:
            return world.alltoall_v(
                np.ascontiguousarray(tensor), splits, name=name,
                process_set_id=ps_id,
                members=ps.ranks if ps_id else None)
        return world.alltoall(np.ascontiguousarray(tensor), name=name,
                              process_set_id=ps_id)
    del name
    if splits is not None:
        return _alltoall_splits_stacked(tensor, splits, ps)

    def traced(x):
        return _alltoall_traced(x, ps.axis_name)

    def _planned_alltoall(plan):
        def traced_planned(t):
            from . import comms_planner

            return comms_planner.apply_alltoall(plan, t, ps.axis_name)

        return traced_planned

    return _eager_dispatch("alltoall", traced, tensor, ps,
                           plan_spec=("alltoall", _planned_alltoall))


def _alltoall_splits_stacked(tensor, splits, ps):
    """Eager stacked-rank uneven alltoall: pad every chunk to the global
    max so the exchange itself is the ONE compiled equal-split AllToAll
    HLO, then compact per row. Returns ``(outputs, received_splits)`` with
    ``outputs`` a list (row r = rank r's ragged result — ragged rows
    cannot stack into one array)."""
    import numpy as np

    n = ps.size()
    x = np.asarray(tensor)
    if x.ndim < 2 or x.shape[0] != n:
        raise ValueError(
            f"eager alltoall(splits=) expects the stacked-rank convention: "
            f"shape (n={n}, d0, ...); got {x.shape}"
        )
    sp = np.asarray(splits, dtype=np.int64)
    if sp.shape == (n,):
        sp = np.tile(sp, (n, 1))
    if sp.shape != (n, n):
        raise ValueError(
            f"splits must be shape ({n},) or ({n}, {n}); got {sp.shape}")
    if not np.all(sp.sum(axis=1) == x.shape[1]):
        raise ValueError(
            f"each rank's splits must sum to dim-0 size {x.shape[1]}; got "
            f"row sums {sp.sum(axis=1).tolist()}"
        )
    from ..runtime import compact_chunks, pad_chunks

    max_c = max(1, int(sp.max()))
    padded = np.stack([pad_chunks(x[r], sp[r], max_c) for r in range(n)])

    def traced(v):
        return _alltoall_traced(v, ps.axis_name)

    exchanged = np.asarray(
        _eager_dispatch("alltoall", traced, padded, ps))
    received = sp.T  # received[i, j] = what rank i got from rank j
    outputs = [compact_chunks(exchanged[i], received[i], max_c)
               for i in range(n)]
    return outputs, received


def reducescatter(
    tensor,
    op: str | None = None,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    process_set=None,
    name: str | None = None,
):
    """Reduce across ranks and scatter: rank r keeps slice r along axis 0.

    Parity: ``hvd.reducescatter`` / ``ReducescatterOp``. One ReduceScatter
    HLO; dim 0 must be divisible by the set size (static shapes).
    """
    op = _resolve_op(op, None) if op is not None else Average
    ps = _resolve_process_set(process_set)
    traced_axis = _effective_traced_axis(ps)
    if traced_axis is not None:
        return _reducescatter_traced(
            tensor, op, traced_axis, prescale_factor, postscale_factor
        )
    world = _native_world_if_per_process(ps, tensor)
    if world is not None:
        if op not in (Sum, Average) or prescale_factor != 1.0 \
                or postscale_factor != 1.0:
            raise ValueError(
                "per-process eager reducescatter supports Sum/Average "
                "without scale factors"
            )
        import numpy as np

        return world.reducescatter(np.ascontiguousarray(tensor), name=name,
                                   op=op,
                                   process_set_id=_native_set_for(ps, world))
    del name

    def traced(x):
        return _reducescatter_traced(
            x, op, ps.axis_name, prescale_factor, postscale_factor
        )

    def _planned_reducescatter(plan):
        def traced_planned(t):
            from . import comms_planner

            row = comms_planner.apply_reducescatter_scaled(
                plan, t.ravel(), ps.axis_name, op == Average,
                prescale_factor, postscale_factor)
            return row.reshape((t.shape[0] // plan.world,) + t.shape[1:])

        return traced_planned

    return _eager_dispatch(
        "reducescatter", traced, tensor, ps,
        (op, prescale_factor, postscale_factor),
        plan_spec=("reducescatter", _planned_reducescatter)
    )


def grouped_reducescatter(tensors: Sequence[Any], op: str | None = None, **kw):
    # Same single-controller contract as grouped_allreduce's eager
    # fallback: a per-tensor loop cannot be split by a peer because one
    # thread issues everything in program order; host-surface callers get
    # the native atomic group via their own grouped_reducescatter.
    return [reducescatter(t, op=op, **kw) for t in tensors]


def grouped_allgather(tensors: Sequence[Any], process_set=None,
                      name: str | None = None):
    """Parity: ``hvd.grouped_allgather``. In the compiled/traced regime
    grouping is a no-op by design — XLA fuses same-cycle collectives — so
    the list maps over :func:`allgather`. In the per-process host-tensor
    regime the group rides the native ATOMIC group machinery with the
    reference's RAGGED dim-0 contract (``grouped_allgather_v``: one
    atomic size-table group + one atomic pad-to-max data group)."""
    tensors = list(tensors)
    ps = _resolve_process_set(process_set)
    world = (
        _native_world_if_per_process(ps, tensors[0])
        if tensors and _effective_traced_axis(ps) is None else None
    )
    if world is not None:
        import numpy as np

        xs = [np.ascontiguousarray(t) for t in tensors]
        return [np.asarray(o) for o in world.grouped_allgather_v(
            xs, name=name, process_set_id=_native_set_for(ps, world))]
    return [allgather(t, process_set=ps, name=name) for t in tensors]


def barrier(process_set=None) -> None:
    """Block until every rank in the set reaches the barrier.

    Parity: ``hvd.barrier``. Eagerly: a scalar psum over the sub-mesh,
    blocked on. (In the compiled regime barriers are meaningless — XLA's
    dataflow order is the synchronization.)
    """
    ps = _resolve_process_set(process_set)
    import os

    if int(os.environ.get("HOROVOD_NUM_PROCESSES", "1") or 1) > 1:
        # Multi-controller: the native runtime's barrier synchronizes the
        # controller processes themselves. Subset barriers release once
        # every MEMBER announced (the world ring only carries execution).
        from ..parallel.hierarchical import _default_native_world

        world = _default_native_world()
        world.barrier(process_set_id=_native_set_for(ps, world))
        return
    token = jnp.ones((ps.size(),), dtype=jnp.int32)
    out = _eager_dispatch(
        "barrier",
        lambda x: lax.psum(x, ps.axis_name),
        token,
        ps,
    )
    jax.block_until_ready(out)


def run_comms_microprobe(process_set=None, sizes=None,
                         repeats: int = 3) -> dict:
    """Seed the communication observatory with an explicit payload sweep
    over a process set — the jax-side driver of
    ``comms_model.microprobe``.

    Runs eager allreduce / reducescatter / allgather / alltoall
    dispatches at each
    payload size (stacked-rank convention, float32); every dispatch's
    measured latency feeds the α–β model automatically through
    ``_eager_dispatch`` (compile time excluded — the first call of each
    signature warms the executable cache before the timed repeats). In
    SPMD worlds this is collective: every rank must call it at the same
    program point, like any eager collective. Returns
    ``{op: {nbytes: samples}}`` with the nbytes as dispatched (the
    stacked payload, matching ``hvd_collective_payload_bytes``).
    """
    import numpy as np

    from .. import comms_model as _comms_model

    import contextlib

    ps = _resolve_process_set(process_set)
    n = ps.size()
    sizes = [int(s) for s in (sizes or _comms_model.DEFAULT_PROBE_SIZES)]
    # With the comms planner live, the sweep runs once per algorithm
    # ELIGIBLE FOR EACH OP (forced pin per pass) so every schedule
    # seeds its own (op, algorithm, link_class) LinkFit — the
    # per-algorithm ground truth plan pricing closes its loop on.
    # Planner off: one flat pass, exactly as before. The RETURNED
    # samples stay flat-only either way: callers take medians per
    # payload size, and mixing schedules with different cost curves
    # into one list would skew them — the non-flat passes exist to feed
    # the model, which reads the per-algorithm attribution straight off
    # the dispatch path.
    planner_live = False
    from . import comms_planner

    if comms_planner.enabled() and n > 1 and ps.process_set_id == 0:
        planner_live = True
        islands = comms_planner._islands_for(n)
    out: dict[str, dict] = {}
    for op_name, run in (
        ("allreduce", lambda a: allreduce(a, op=Sum, process_set=ps)),
        ("reducescatter",
         lambda a: reducescatter(a, op=Sum, process_set=ps)),
        ("allgather", lambda a: allgather(a, process_set=ps)),
        ("alltoall", lambda a: alltoall(a, process_set=ps)),
    ):
        algorithms: tuple = (
            comms_planner.eligible_algorithms(op_name, n, islands)
            if planner_live else (None,))
        per_op: dict[int, list] = {}
        for algorithm in algorithms:
            ctx = (comms_planner.forced(algorithm)
                   if algorithm is not None else contextlib.nullcontext())
            keep = algorithm in (None, "flat")
            with ctx:
                for nbytes in sizes:
                    # Per-rank rows of n*k elements so reducescatter's
                    # dim-0 divisibility holds; stacked payload = n *
                    # row bytes.
                    elems = max(n, (nbytes // 4 // n) * n)
                    x = np.ones((n, elems), np.float32)
                    run(x)  # warm the executable cache
                    import time as _time

                    for _ in range(max(1, int(repeats))):
                        t0 = _time.perf_counter()
                        jax.block_until_ready(run(x))
                        if keep:
                            per_op.setdefault(int(x.size) * 4, []).append(
                                _time.perf_counter() - t0)
        out[op_name] = per_op
    _comms_model.get_model().note_probe()
    return out
