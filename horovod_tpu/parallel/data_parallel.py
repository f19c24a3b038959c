"""Data-parallel training step factory — Horovod's core capability, compiled.

The reference's training contract (SURVEY.md §4.2): forward/backward runs
per-replica, per-parameter gradients are allreduce-averaged by the
background runtime, then the optimizer applies them. The compiled
equivalent builds the whole step as one SPMD program: batch sharded over the
``hvd`` axis, parameters replicated, gradients averaged by the
DistributedOptimizer *inside* the program (one fused AllReduce HLO per
bucket over ICI), optimizer update replicated. XLA overlaps the gradient
allreduce with remaining backprop where dataflow allows — the compiled
analog of Horovod's comm/compute overlap.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
from jax.sharding import PartitionSpec as P

from .. import attribution, autotune, faults, profiler, tracing


class _StallWatchedStep:
    """Default-on stall watch for factory-built train steps.

    The reference's stall inspector watches EVERYTHING submitted,
    unconditionally (``stall_inspector.cc``); requiring users to call
    ``hvd.fetch`` themselves left the exact user the inspector exists
    for — a vanilla training loop hanging inside jit — unwatched. Every
    Kth call (``HOROVOD_STALL_CHECK_STEPS``, default 50; <=0 disables)
    the step's results route through :func:`horovod_tpu.stall.fetch`:
    a local inspector ticket plus, in multi-controller worlds, the
    cross-rank ``stallwatch/<name>`` announcement that NAMES a diverged
    rank. Between check steps the call is a passthrough, so the watch
    costs one pipeline drain per K steps.

    Attribute access delegates to the wrapped callable, so jit surfaces
    (``lower``, ``clear_cache`` — which ``tune_step_fusion`` requires)
    keep working.
    """

    def __init__(self, fn, name_prefix: str):
        from ..utils.env import get_int

        self._fn = fn
        self._prefix = name_prefix
        self._every = get_int("HOROVOD_STALL_CHECK_STEPS", 50)
        # Read where the step is built, like the stall watch's period: a
        # call reads no environment variable.
        self._sample = tracing.sample_every()
        self._calls = 0
        self._trace_calls = 0
        self._world = None  # the world formation _multi / _env_plane are of
        self._multi = self._env_plane = False
        self._abstract_args = None  # shapes and shardings of the first call
        self._compiled = 0  # the compile account's programs, as last seen
        self._compile_before = None

    def _cross_rank_available(self) -> bool:
        """True when the cross-rank stallwatch can ride a host plane
        this deployment actually has: an already-formed native world, or
        the launcher env contract that makes one formable. The world
        object is looked at on every call and NEVER formed here — a
        jax.distributed job that deliberately skips the host plane must
        not have one spun up (or crash on a missing rendezvous) as a
        side effect of the watch. The launcher's variables are read once
        per world formation (``hvd.init``), not once a call."""
        from . import hierarchical

        return hierarchical._host_world is not None or self._env_plane

    def _world_facts(self) -> None:
        """What the launcher's environment says of this world, read anew
        only when ``hvd.init`` has formed another one."""
        import os

        from .. import basics
        from ..process_world import size as _psize

        formation = basics._state.topology  # a new object every init()
        if formation is self._world and formation is not None:
            return
        self._world = formation
        self._multi = _psize() > 1
        self._env_plane = (
            bool(os.environ.get("HOROVOD_NATIVE_PORT"))
            or bool(os.environ.get("HOROVOD_RENDEZVOUS_ADDR")))

    def _step_number(self, cross_rank: bool) -> int:
        """Watch-step counter. In multi-controller worlds the stallwatch
        wire name must be RANK-IDENTICAL, and a process-local counter
        diverges across elastic re-formations (a survivor has called the
        step N times, a fresh worker 0) — so the counter lives on the
        native world object, which every member recreates together at
        each (re-)formation."""
        if cross_rank and self._multi:
            from .hierarchical import _default_native_world

            w = _default_native_world()
            n = getattr(w, "_stepwatch_n", 0) + 1
            w._stepwatch_n = n
            return n
        self._calls += 1
        return self._calls

    @staticmethod
    def _tuning_live() -> bool:
        """True while ANY transparent autotune warmup window is live in
        this process — not just one wrapping our own callable: a co-step
        (built mid-warmup, returned unwrapped) must also defer its drain
        or it biases the first tuner's samples."""
        tuners = autotune._active_tuner
        return bool(tuners and tuners[0]._hvd_tuning)

    def _remember_arguments(self, args, kwargs) -> None:
        """Shapes, dtypes and shardings of the first call's arguments:
        what ``profiler.step_scopes`` lowers the step with, long after
        the arrays themselves were donated."""
        def abstract(leaf):
            if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
                return jax.ShapeDtypeStruct(
                    leaf.shape, leaf.dtype,
                    sharding=getattr(leaf, "sharding", None))
            # Nothing here may keep a buffer alive: a handle that is no
            # pytree (DeferredParams) is not remembered.
            return leaf if isinstance(leaf, (bool, int, float, str)) else None

        self._abstract_args = jax.tree.map(abstract, (args, kwargs))
        profiler.register_step(self)

    def _book_compiles(self, account, rec, call: int) -> None:
        """Something compiled inside this call: its share of the compile
        account goes on the step span, and on any call after the first
        it is a recompile — counted, and journaled with the call."""
        share = account.since(self._compile_before)
        self._compiled = account.programs
        rec.args = {**(rec.args or {}), "compile": share}
        step = account.steps.setdefault(
            self._prefix, {"first_call": None, "recompiles": 0})
        if call == 1:  # of this wrapper: the newest of a name is kept
            step["first_call"] = share
            return
        step["recompiles"] += 1
        step["last_recompile"] = {"call": call, **share}
        from .. import metrics

        metrics.STEP_RECOMPILES.inc(step=self._prefix)
        metrics.event("step_recompiled", step=self._prefix, call=call,
                      **share)

    def __call__(self, *args, **kwargs):
        # Every call is one hvd.step span, opened before anything else
        # here runs: a profiler annotation and a step record in the
        # flight-recorder ring, with the dispatch and (where there is
        # one) the drain as its children, so that the span's self time
        # is what the hooks of this wrapper cost.
        self._trace_calls += 1
        call = self._trace_calls
        tracer = tracing.get_tracer()
        try:
            with tracer.step_scope(
                    attribution.SPAN_STEP,
                    {"kind": self._prefix, "call": call}) as rec:
                return self._watched_call(tracer, rec, call, args, kwargs)
        except Exception as exc:
            # The factory step boundary is the OOM forensics consumer:
            # a RESOURCE_EXHAUSTED surfacing here dumps a memory flight
            # record naming the top resident leaves and the
            # predicted-vs-measured delta, then re-raises untouched
            # (recovery policy belongs to the elastic loop, not here).
            try:
                from .. import memory

                if memory.is_oom_error(exc):
                    memory.dump_oom_record(exc, step=self._prefix)
            except Exception:  # noqa: BLE001 — forensics must not
                pass  # mask the original failure
            raise

    def _watched_call(self, tracer, rec, call: int, args, kwargs):
        if autotune.warmup_aborted():
            # A mid-warmup autotune abort poisons EVERY factory step in
            # the process, not just the tuner's wrapper: co-built steps
            # and steps built post-abort pass through maybe_autotune_step
            # bare, but all of them route through this wrapper — and all
            # of them would trace collective sequences that may diverge
            # from peers that pinned the broadcast winner.
            raise autotune._poison_error()
        tuning = self._tuning_live()
        watch_due = False
        cross = False
        n = 0
        if self._every > 0 and not tuning:
            self._world_facts()
            cross = self._cross_rank_available()
            n = self._step_number(cross)
            watch_due = n % self._every == 0
        # Every HOROVOD_TRACE_SAMPLE-th call OF THIS WRAPPER additionally
        # blocks on the results — real step wall time — and ships its
        # spans to the rendezvous KV for the cross-rank merge.
        # The sampling counter is per-wrapper, not the shared tracer
        # counter: two interleaved factory steps (train + eval) sharing
        # one process counter could alias one of them out of sampling
        # forever. Sampling defers while an autotune warmup is live,
        # exactly like the stall watch: the pipeline drain would bias
        # the tuner's samples.
        sample_due = (not tuning and self._sample > 0
                      and call % self._sample == 0)
        if self._abstract_args is None:
            self._remember_arguments(args, kwargs)
        account = profiler.compile_account()
        if account.programs != self._compiled or call == 1:
            # Programs compiled since this step last looked (another
            # step's, an eager collective's) are not this call's.
            self._compiled = account.programs
            self._compile_before = account.totals()
        if faults.fire(faults.MEMORY_PRESSURE):
            # drop = synthetic device OOM at the step boundary: the
            # deterministic injector behind the memory observatory's
            # forensics tests (caught and dumped by the caller, exactly
            # like a real RESOURCE_EXHAUSTED out of the jitted call).
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: injected memory pressure "
                "(fault point memory.pressure)")
        if watch_due:
            from ..stall import watch

            # The announcement precedes the DISPATCH: on backends
            # that execute synchronously (CPU) a diverged peer hangs
            # this rank inside the jitted call itself, before any
            # post-hoc fetch could announce.
            with watch(name=f"{self._prefix}.{n}", cross_rank=cross):
                with tracer.host_span(attribution.SPAN_STEP_DISPATCH):
                    out = self._fn(*args, **kwargs)
                with tracer.host_span(
                        attribution.SPAN_STEP_DRAIN,
                        {"cause": attribution.DRAIN_STALL_WATCH}):
                    out = jax.block_until_ready(out)
        else:
            with tracer.host_span(attribution.SPAN_STEP_DISPATCH):
                out = self._fn(*args, **kwargs)
            if sample_due:
                with tracer.host_span(
                        attribution.SPAN_STEP_DRAIN,
                        {"cause": attribution.DRAIN_TRACE_SAMPLE}):
                    out = jax.block_until_ready(out)
        rec.synced = watch_due or sample_due
        rec.ship = sample_due
        if account.programs != self._compiled:
            self._book_compiles(account, rec, call)
        elif call > 1 and tracer.setup_open:
            # The first warm call of a factory step: set-up ends with it.
            rec.closes_setup = True
        return out

    @property
    def _hvd_unwatched(self):
        """The bare step callable — timing loops (tune_step_fusion) use
        this so a watch step's pipeline drain cannot bias a candidate."""
        return self._fn

    def __getattr__(self, item):
        if item == "_fn":  # guard: lookup before __init__ must not recurse
            raise AttributeError(item)
        return getattr(self._fn, item)


def _resolve_mesh_axis(mesh, axis_name, hierarchical):
    """Shared factory plumbing: resolve (mesh, axis_name) from the
    explicit arguments, the ``hierarchical`` request, or the env flag
    (``HOROVOD_HIERARCHICAL_ALLREDUCE``). See :func:`make_train_step`
    for the argument contract."""
    from .. import basics

    from_env = hierarchical is None
    if from_env:
        cfg = basics._state.config
        hierarchical = bool(cfg and cfg.hierarchical_allreduce)
    if hierarchical and mesh is not None:
        if not from_env:
            raise ValueError(
                "pass either hierarchical=... or mesh=, not both (an "
                "explicit mesh defines its own axes)"
            )
        # Env flag + explicit mesh: the explicit mesh wins, loudly.
        from ..utils.logging import get_logger

        get_logger().warning(
            "HOROVOD_HIERARCHICAL_ALLREDUCE is set but the step factory "
            "got an explicit mesh; using the explicit mesh (flat reduction)"
        )
        hierarchical = False
    if hierarchical:
        from .hierarchical import HIERARCHICAL_AXES, hierarchical_mesh

        factors = (hierarchical if isinstance(hierarchical, tuple)
                   else (None, None))
        mesh = hierarchical_mesh(*factors)
        axis_name = HIERARCHICAL_AXES
    if mesh is None:
        mesh = basics.global_mesh()
    if axis_name is None:
        axis_name = basics.global_axis_name()
    return mesh, axis_name


class DeferredParams:
    """Handle over the sharded step's updated-parameter allgather (the
    ``deferred_param_gather=True`` eager path).

    The gather program is already DISPATCHED when the handle is returned
    — jax's async dispatch runs the collective while the host does other
    work between steps (data loading, metrics, checkpoint bookkeeping).
    Touch :attr:`params` (or pass the handle straight back into the step)
    to use the gathered tree; :meth:`block_until_ready` waits explicitly.
    """

    def __init__(self, params):
        self._params = params

    @property
    def params(self):
        return self._params

    def block_until_ready(self):
        jax.block_until_ready(self._params)
        return self._params


def _sharded_spec_of(optimizer):
    """The optimizer's ReduceSpec when it was built with
    ``sync_mode='sharded'``, else None."""
    from ..optimizer import reduce_spec_of

    spec = reduce_spec_of(optimizer)
    if spec is not None and getattr(spec, "sync_mode", None) == "sharded":
        return spec
    return None


def _fsdp_spec_of(optimizer):
    """The optimizer's ReduceSpec when it was built with
    ``sync_mode='fsdp'``, else None."""
    from ..optimizer import reduce_spec_of

    spec = reduce_spec_of(optimizer)
    if spec is not None and getattr(spec, "sync_mode", None) == "fsdp":
        return spec
    return None


def _check_flat_axis(axis_name, what: str, sync_mode: str = "sharded"):
    from ..exceptions import SyncModeIneligibleError
    from .mesh import MESH2D_AXES

    if (isinstance(axis_name, (tuple, list))
            and tuple(axis_name) == MESH2D_AXES):
        # The (batch, model) tuple is a flat-rank factorization, not the
        # hierarchical (cross, local) composition — ZeRO-1 reduces over
        # it in flat order (batch major), so the ownership map is intact.
        return
    if not isinstance(axis_name, str):
        raise SyncModeIneligibleError(
            f"sync_mode='{sync_mode}' does not compose with the "
            f"hierarchical (cross, local) mesh in {what}; use the flat "
            f"axis (the two-level reduction already reduce-scatters its "
            f"local leg"
            + (" — and the fsdp shard ownership map is defined over ONE "
               "world axis" if sync_mode == "fsdp" else "")
            + "). For ICI x DCN hierarchy WITH this sync mode, set "
            "HOROVOD_COMMS_PLANNER: the planner's two_level schedule "
            "composes the same legs per bucket on the flat axis "
            "(ops/comms_planner.py)")


def _planner_autotune_candidates():
    """The comms planner's algorithm axis for the transparent tuner —
    non-None only when ``HOROVOD_COMMS_PLANNER=auto`` and more than one
    algorithm is eligible for this world (``comms_planner
    .autotune_candidates``). Guarded: the factories must build even
    when the planner cannot introspect the world yet."""
    try:
        from ..ops.comms_planner import autotune_candidates

        return autotune_candidates()
    except Exception:  # noqa: BLE001
        return None


def shard_state(tree, mesh=None, axis_name: str | None = None):
    """Place a stacked sharded optimizer state (leading world axis, from
    ``hvd.init_sharded_state`` / a sharded optimizer's ``init``) on the
    mesh, sharded along that axis — so each rank holds only its 1/n of
    the state. The sharded counterpart of :func:`replicate`.

    On a 2-D ``(batch, model)`` mesh the leading world axis splits over
    BOTH mesh axes; the default placement is the fsdp row order
    (``("model", "batch")`` — row ``m*batch + b`` on device ``(b, m)``,
    per ``ops.fusion.shard_ownership_2d``). Pass
    ``axis_name=("batch", "model")`` for the ZeRO-1 flat-order layout."""
    from jax.sharding import NamedSharding

    from .. import basics
    from .mesh import MESH2D_ROW_AXES, is_mesh_2d

    if mesh is None:
        mesh = basics.global_mesh()
    if axis_name is None:
        axis_name = (MESH2D_ROW_AXES if is_mesh_2d(mesh)
                     else basics.global_axis_name())
    sharding = NamedSharding(mesh, P(axis_name))
    with tracing.place_span("shard_state", tree):
        return jax.tree.map(partial(jax.device_put, device=sharding), tree)


def _record_mesh_axes(sizes: dict) -> None:
    try:
        from .. import metrics

        for axis, v in sizes.items():
            metrics.MESH_AXIS_SIZE.set(int(v), axis=axis)
    except Exception:  # noqa: BLE001 — instrumentation is best-effort
        pass


def _resolve_mesh_2d(mesh, hierarchical):
    """The 2-D ``(batch, model)`` mesh this factory call compiles
    against, or None for the flat 1-D wire. Precedence: an explicit 2-D
    ``mesh=`` argument > ``HOROVOD_MESH_SHAPE`` > an autotune mesh-shape
    pin. With none of the three (the default) this returns None and the
    factory takes the pre-mesh code path byte for byte — the knob-unset
    inertness contract."""
    from .mesh import is_mesh_2d, mesh_2d, resolve_mesh_shape

    if mesh is not None:
        return mesh if is_mesh_2d(mesh) else None
    shape = resolve_mesh_shape()
    if shape is None:
        return None
    hier = hierarchical
    if hier is None:
        from .. import basics

        cfg = basics._state.config
        hier = bool(cfg and cfg.hierarchical_allreduce)
    if hier:
        raise ValueError(
            "HOROVOD_MESH_SHAPE does not compose with the hierarchical "
            "(cross, local) allreduce: the 2-D (batch, model) mesh "
            "already places each collective leg on its link class "
            "(model on ICI, batch across). Unset one of the two knobs "
            "(docs/perf.md, '2-D mesh' guard table)")
    return mesh_2d(*shape)


def make_train_step(
    loss_fn: Callable[..., Any],
    optimizer,
    mesh=None,
    axis_name: str | None = None,
    donate: bool = True,
    loss_is_averaged: bool = True,
    hierarchical: bool | tuple | None = None,
    deferred_param_gather: bool = False,
):
    """Build a jitted SPMD train step.

    Args:
      loss_fn: ``loss_fn(params, batch) -> scalar`` (per-shard mean loss).
      optimizer: an optax GradientTransformation — wrap with
        ``hvd.DistributedOptimizer`` for gradient averaging; a bare
        optimizer yields single-replica behavior (grads NOT synced).
      mesh: defaults to the global 1-D 'hvd' mesh from ``init()``.
      axis_name: collective axis (defaults to the global axis).
      loss_is_averaged: if True the reported loss is pmean'd across shards.
      hierarchical: two-level (cross, local) sharding — the consumer of
        ``HOROVOD_HIERARCHICAL_ALLREDUCE`` (reference:
        ``NCCLHierarchicalAllreduce``). None → follow the env flag; True →
        mesh from host topology; a ``(cross, local)`` tuple → explicit
        factors. The DistributedOptimizer then reduces gradients
        reduce-scatter(ICI) → allreduce(DCN) → allgather(ICI).
      deferred_param_gather: sharded sync mode only — split the step into
        a core program (reduce-scatter + shard update, returning the
        updated parameter SHARDS) and a separate allgather program whose
        dispatched result rides a :class:`DeferredParams` handle; the
        gather runs while the host does between-step work. The returned
        step accepts either a full params pytree or the previous call's
        handle.

    Returns:
      ``step(params, opt_state, batch) -> (params, opt_state, loss)``,
      compiled; ``batch`` is sharded along its leading axis, params/opt_state
      replicated. A ``sync_mode='sharded'`` DistributedOptimizer switches
      the program to ZeRO-1 form: per-bucket reduce-scatter, shard-local
      inner update (opt_state is the STACKED sharded layout from the
      optimizer's ``init`` — place it with :func:`shard_state`), and an
      allgather of the updated parameter shards issued off the gradient
      critical path.
    """
    with tracing.setup_span(attribution.SPAN_SETUP_BUILD) as span:
        step = _make_train_step(
            loss_fn, optimizer, mesh, axis_name, donate, loss_is_averaged,
            hierarchical, deferred_param_gather)
        span.note(kind=step._prefix)
    return step


def _make_train_step(loss_fn, optimizer, mesh, axis_name, donate,
                     loss_is_averaged, hierarchical, deferred_param_gather):
    """:func:`make_train_step`'s body: the program its optimizer's sync
    mode and the mesh ask for."""
    spec = _sharded_spec_of(optimizer)
    fsdp_spec = _fsdp_spec_of(optimizer)
    mesh2d = _resolve_mesh_2d(mesh, hierarchical)
    if mesh2d is not None:
        return _make_mesh2d_train_step(
            loss_fn, optimizer, spec, fsdp_spec, mesh2d, donate,
            loss_is_averaged, deferred_param_gather)
    mesh, axis_name = _resolve_mesh_axis(mesh, axis_name, hierarchical)
    from ..exceptions import SyncModeIneligibleError

    if deferred_param_gather and fsdp_spec is not None:
        raise SyncModeIneligibleError(
            "deferred_param_gather does not apply to sync_mode='fsdp': "
            "fsdp has NO trailing parameter allgather to defer — the "
            "shard-local update writes back to the resident shard, and "
            "the next forward's per-segment gathers are the only "
            "re-materialization")
    if deferred_param_gather and spec is None:
        raise ValueError(
            "deferred_param_gather requires a DistributedOptimizer built "
            "with sync_mode='sharded' (there is no parameter allgather to "
            "defer in allreduce mode)")
    if fsdp_spec is not None:
        _check_flat_axis(axis_name, "make_train_step", "fsdp")
        return _make_fsdp_train_step(
            loss_fn, fsdp_spec, mesh, axis_name, donate, loss_is_averaged)
    if spec is not None:
        _check_flat_axis(axis_name, "make_train_step")
        return _make_sharded_train_step(
            loss_fn, spec, mesh, axis_name, donate, loss_is_averaged,
            deferred_param_gather)
    return _make_allreduce_train_step(
        loss_fn, optimizer, mesh, axis_name, donate, loss_is_averaged)


def _make_allreduce_train_step(loss_fn, optimizer, mesh, axis_name,
                               donate, loss_is_averaged):
    """The monolithic (allreduce-mode) program — replicated params and
    opt_state, batch sharded over ``axis_name`` (a flat axis, the
    hierarchical (cross, local) tuple, or the 2-D (batch, model) tuple:
    the optimizer's allreduce resolves the bound axis form at trace
    time and takes the matching two-level composition for tuples)."""
    import contextlib

    import optax

    from ..attribution import SCOPE_OPTIMIZER
    from ..optimizer import reduce_spec_of
    from ..profiler import annotate_collective

    # A DistributedOptimizer scopes its wire and its inner update itself;
    # a bare optax optimizer is all update.
    bare = reduce_spec_of(optimizer) is None

    def spmd_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        with (annotate_collective(SCOPE_OPTIMIZER) if bare
              else contextlib.nullcontext()):
            updates, new_opt_state = optimizer.update(
                grads, opt_state, params)
        with annotate_collective(SCOPE_OPTIMIZER):
            new_params = optax.apply_updates(params, updates)
        if loss_is_averaged:
            loss = jax.lax.pmean(loss, axis_name)
        return new_params, new_opt_state, loss

    sharded = jax.shard_map(
        spmd_step,
        mesh=mesh,
        in_specs=(P(), P(), P(axis_name)),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    donate_argnums = (0, 1) if donate else ()
    from ..autotune import maybe_autotune_step

    # Layering: stall watch OUTSIDE the autotuner OUTSIDE the jit — the
    # tuner owns re-tracing (clear_cache) and the watch defers while a
    # tuning window is live so its pipeline drain cannot bias a sample.
    return _StallWatchedStep(
        maybe_autotune_step(
            jax.jit(sharded, donate_argnums=donate_argnums),
            algorithm_candidates=_planner_autotune_candidates()),
        "train_step")


def _make_sharded_train_step(loss_fn, spec, mesh, axis_name, donate,
                             loss_is_averaged, deferred_param_gather):
    """The sync_mode='sharded' program for :func:`make_train_step`:
    reduce-scatter per bucket → inner update on the locally owned shard
    (opt_state sharded over the axis, leading world dim stripped inside)
    → allgather of the UPDATED PARAMETER shards. With
    ``deferred_param_gather`` the allgather compiles as its own program
    whose dispatch rides a :class:`DeferredParams` handle."""
    from ..autotune import maybe_autotune_step
    from ..optimizer import sharded_step_update

    def spmd_step(params, opt_state, batch):
        local_state = jax.tree.map(lambda a: a[0], opt_state)
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        new_params, new_local = sharded_step_update(
            spec, grads, local_state, params, axis_name=axis_name,
            gather=not deferred_param_gather)
        out_state = jax.tree.map(lambda a: a[None], new_local)
        if deferred_param_gather:
            # Updated params are still SHARDS here; stack them on the
            # world axis for the separate gather program.
            new_params = jax.tree.map(lambda a: a[None], new_params)
        if loss_is_averaged:
            loss = jax.lax.pmean(loss, axis_name)
        return new_params, out_state, loss

    donate_argnums = (0, 1) if donate else ()
    if not deferred_param_gather:
        sharded = jax.shard_map(
            spmd_step,
            mesh=mesh,
            in_specs=(P(), P(axis_name), P(axis_name)),
            out_specs=(P(), P(axis_name), P()),
            check_vma=False,
        )
        return _StallWatchedStep(
            maybe_autotune_step(
                jax.jit(sharded, donate_argnums=donate_argnums),
                algorithm_candidates=_planner_autotune_candidates()),
            "train_step")

    core = jax.jit(
        jax.shard_map(
            spmd_step,
            mesh=mesh,
            in_specs=(P(), P(axis_name), P(axis_name)),
            out_specs=(P(axis_name), P(axis_name), P()),
            check_vma=False,
        ),
        donate_argnums=donate_argnums,
    )
    gather_prog: dict = {}
    int8 = getattr(spec.compression, "marker", None) == "int8"

    def step(params, opt_state, batch):
        if isinstance(params, DeferredParams):
            params = params.params
        templates = jax.tree.map(
            lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), params)
        shards, new_state, loss = core(params, opt_state, batch)
        gj = gather_prog.get("jit")
        if gj is None:
            from ..optimizer import _gather_param_shards, _known_size

            n = _known_size(spec.process_set)

            def gather_spmd(stacked, counter=None):
                local = jax.tree.map(lambda a: a[0], stacked)
                # The core already advanced the counter; this step's
                # quantization salt is the PRE-increment value, matching
                # the non-deferred path's rounding exactly.
                salt = counter[0] - 1 if int8 else None
                return _gather_param_shards(
                    local, templates, spec.compression, axis_name, n,
                    spec.fusion_threshold_bytes, spec.num_groups,
                    quant_salt=salt)[0]

            in_specs = ((P(axis_name), P(axis_name)) if int8
                        else (P(axis_name),))
            gj = gather_prog["jit"] = jax.jit(
                jax.shard_map(
                    gather_spmd,
                    mesh=mesh,
                    in_specs=in_specs,
                    out_specs=P(),
                    check_vma=False,
                ),
                # Donate only the shards: the int8 counter rides the
                # live optimizer state.
                donate_argnums=(0,) if donate else (),
            )
        args = (shards, new_state.counter) if int8 else (shards,)
        # Host-visible half of the sharded wire: the updated-parameter
        # allgather dispatch (the program itself runs async while the
        # host does between-step work; the span times the dispatch and
        # marks WHERE the gather sat relative to the step).
        with tracing.span("param_allgather", "collective",
                          args={"deferred": True}):
            deferred = gj(*args)
        return DeferredParams(deferred), new_state, loss

    # No transparent autotune here: the wrapper owns two programs and the
    # tuner's clear_cache contract assumes one jitted callable.
    return _StallWatchedStep(step, "train_step")


def _make_fsdp_train_step(loss_fn, spec, mesh, axis_name, donate,
                          loss_is_averaged, num_segments=None,
                          name_prefix: str = "train_step"):
    """The sync_mode='fsdp' program (ZeRO-3): parameters arrive as a
    :class:`param_sharding.ShardedParams` of stacked ``(world, shard)``
    rows sharded over the axis — each rank resident-holds ~1/n of the
    model. Per segment, the forward allgathers the segment's parameters
    just in time (independent HLOs: XLA overlaps segment k+1's gather
    with segment k's compute), the backward emits the segment's gradient
    reduce-scatter inside backprop (the gather boundary's custom-vjp),
    and the shard-local inner update writes back to the resident shard
    with no trailing allgather.

    ``step(sharded_params, opt_state, batch) -> (sharded_params,
    opt_state, loss)`` — build the resident layout with
    ``hvd.shard_params(params)`` + ``shard_state``, and the stacked
    optimizer state with the fsdp optimizer's ``init``.
    """
    import optax

    from ..attribution import SCOPE_OPTIMIZER
    from ..autotune import maybe_autotune_step
    from ..optimizer import _SaltState, _known_size
    from ..profiler import annotate_collective
    from .param_sharding import ShardedParams, gather_params

    int8 = getattr(spec.compression, "marker", None) == "int8"
    n = _known_size(spec.process_set)
    if n is None:
        raise ValueError(
            "sync_mode='fsdp' needs a known process-set size at step-build "
            "time (init() first)")

    def spmd_step(sharded_params, opt_state, batch):
        if not isinstance(sharded_params, ShardedParams):
            # SyncModeIneligibleError: this is a static-config
            # eligibility fact, and the sync-mode sweep's skip net
            # (autotune.tune_step_sync_mode) skips exactly this class —
            # a builder that feeds replicated params must skip the fsdp
            # candidate, not abort the sweep.
            from ..exceptions import SyncModeIneligibleError

            raise SyncModeIneligibleError(
                "the fsdp train step takes resident ShardedParams (build "
                "with hvd.shard_params(params) and place with "
                f"shard_state), got {type(sharded_params).__name__}")
        meta = sharded_params.meta
        # Strip the leading world axis: inside the shard_map each rank
        # sees its own (1, s) row of every leaf.
        shards = jax.tree.unflatten(
            meta.treedef, [a[0] for a in sharded_params.rows])
        local_state = jax.tree.map(lambda a: a[0], opt_state)
        if int8:
            inner_local, salt = local_state.inner_state, local_state.counter
        else:
            inner_local, salt = local_state, None

        def loss_of(sh):
            full = gather_params(sh, meta, spec, axis_name, n, salt=salt,
                                 num_segments=num_segments)
            return loss_fn(full, batch)

        # Gradients arrive ALREADY reduce-scattered to the shard domain:
        # each segment boundary's backward emitted its reducescatter
        # inside backprop and its cotangent IS the owned (s,) slice.
        loss, grad_shards = jax.value_and_grad(loss_of)(shards)
        with annotate_collective(SCOPE_OPTIMIZER):
            updates, new_inner = spec.inner.update(
                grad_shards, inner_local, shards)
            new_shards = optax.apply_updates(shards, updates)
        new_local = _SaltState(new_inner, salt + 1) if int8 else new_inner
        new_rows = ShardedParams(
            [a[None] for a in jax.tree.leaves(new_shards)], meta)
        new_state = jax.tree.map(lambda a: a[None], new_local)
        if loss_is_averaged:
            loss = jax.lax.pmean(loss, axis_name)
        return new_rows, new_state, loss

    sharded = jax.shard_map(
        spmd_step,
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(axis_name)),
        out_specs=(P(axis_name), P(axis_name), P()),
        check_vma=False,
    )
    donate_argnums = (0, 1) if donate else ()
    return _StallWatchedStep(
        maybe_autotune_step(
            jax.jit(sharded, donate_argnums=donate_argnums),
            algorithm_candidates=_planner_autotune_candidates()),
        name_prefix)


def _make_mesh2d_train_step(loss_fn, optimizer, spec, fsdp_spec, mesh2d,
                            donate, loss_is_averaged,
                            deferred_param_gather):
    """Dispatch a factory call onto the 2-D ``(batch, model)`` mesh:
    fsdp takes the two-leg wire (:func:`_make_fsdp_train_step_2d`),
    ZeRO-1 reduces over the flat-rank axis tuple, and the monolithic
    mode takes the two-level allreduce composition (model leg on ICI,
    batch leg across). Guard table: expert_set x model and the deferred
    parameter gather are unsupported compositions."""
    from ..exceptions import SyncModeIneligibleError
    from ..optimizer import reduce_spec_of
    from .mesh import MESH2D_AXES, mesh_axis_sizes

    sizes = mesh_axis_sizes(mesh2d)
    _record_mesh_axes(sizes)
    any_spec = fsdp_spec or spec or reduce_spec_of(optimizer)
    if any_spec is not None and getattr(any_spec, "expert_set", None):
        raise SyncModeIneligibleError(
            "expert_set x model is an unsupported mesh composition: the "
            "expert alltoall already owns the intra-host links the "
            "model axis would claim, and the expert-partitioned "
            "reduction is defined over the flat world. Run MoE jobs "
            "without HOROVOD_MESH_SHAPE (docs/perf.md, '2-D mesh' "
            "guard table)")
    if deferred_param_gather:
        raise SyncModeIneligibleError(
            "deferred_param_gather x model is an unsupported mesh "
            "composition: the deferred allgather program is built over "
            "the flat axis (docs/perf.md, '2-D mesh' guard table)")
    if fsdp_spec is not None:
        return _make_fsdp_train_step_2d(
            loss_fn, fsdp_spec, mesh2d, donate, loss_is_averaged)
    if spec is not None:
        return _make_sharded_train_step(
            loss_fn, spec, mesh2d, MESH2D_AXES, donate, loss_is_averaged,
            False)
    return _make_allreduce_train_step(
        loss_fn, optimizer, mesh2d, MESH2D_AXES, donate, loss_is_averaged)


def _make_fsdp_train_step_2d(loss_fn, spec, mesh2d, donate,
                             loss_is_averaged, num_segments=None,
                             name_prefix: str = "train_step"):
    """The sync_mode='fsdp' program on the 2-D ``(batch, model)`` mesh.

    The resident layout is byte-identical to the flat wire — the same
    :class:`param_sharding.ShardedParams` stacked ``(world, shard)``
    rows, ``world = batch*model`` (``ops.fusion.shard_ownership_2d``) —
    but the rows place over BOTH mesh axes in model-major order
    (``P(("model", "batch"))``: row ``m*batch + b`` on device
    ``(b, m)``), and each per-segment collective splits into two legs:
    the batch leg rides the existing bucketed RS/AG machinery over the
    long hops, the model leg is a plain ICI all_gather/psum_scatter XLA
    schedules on the shortest links (:func:`param_sharding
    .gather_params_2d`). The batch slice shards over both axes in flat
    rank order, so the loss trajectory matches the 1-D fsdp run to
    reduction-order noise while 1/model of the gather bytes leave the
    slow links.
    """
    import optax

    from ..attribution import SCOPE_OPTIMIZER
    from ..autotune import maybe_autotune_step
    from ..optimizer import _SaltState, _known_size
    from ..profiler import annotate_collective
    from .mesh import MESH2D_AXES, MESH2D_ROW_AXES, mesh_axis_sizes
    from .param_sharding import ShardedParams, gather_params_2d

    int8 = getattr(spec.compression, "marker", None) == "int8"
    sizes = mesh_axis_sizes(mesh2d)
    b, m = sizes["batch"], sizes["model"]
    n = _known_size(spec.process_set)
    if n is None:
        raise ValueError(
            "sync_mode='fsdp' needs a known process-set size at step-build "
            "time (init() first)")
    if n != b * m:
        raise ValueError(
            f"mesh {b}x{m} does not cover the process set of {n} rank(s)")

    def spmd_step(sharded_params, opt_state, batch):
        if not isinstance(sharded_params, ShardedParams):
            from ..exceptions import SyncModeIneligibleError

            raise SyncModeIneligibleError(
                "the fsdp train step takes resident ShardedParams (build "
                "with hvd.shard_params(params) and place with "
                f"shard_state), got {type(sharded_params).__name__}")
        meta = sharded_params.meta
        # Inside the shard_map each device sees its own (1, s) row of
        # every leaf — row m*batch + b under the model-major placement.
        shards = jax.tree.unflatten(
            meta.treedef, [a[0] for a in sharded_params.rows])
        local_state = jax.tree.map(lambda a: a[0], opt_state)
        if int8:
            inner_local, salt = local_state.inner_state, local_state.counter
        else:
            inner_local, salt = local_state, None

        def loss_of(sh):
            full = gather_params_2d(sh, meta, spec, b, m, salt=salt,
                                    num_segments=num_segments)
            return loss_fn(full, batch)

        # Gradients arrive ALREADY reduce-scattered to the shard domain:
        # each segment boundary's backward emitted its model-leg
        # psum_scatter and batch-leg reducescatter inside backprop and
        # its cotangent IS the owned (s,) slice.
        loss, grad_shards = jax.value_and_grad(loss_of)(shards)
        with annotate_collective(SCOPE_OPTIMIZER):
            updates, new_inner = spec.inner.update(
                grad_shards, inner_local, shards)
            new_shards = optax.apply_updates(shards, updates)
        new_local = _SaltState(new_inner, salt + 1) if int8 else new_inner
        new_rows = ShardedParams(
            [a[None] for a in jax.tree.leaves(new_shards)], meta)
        new_state = jax.tree.map(lambda a: a[None], new_local)
        if loss_is_averaged:
            loss = jax.lax.pmean(loss, MESH2D_AXES)
        return new_rows, new_state, loss

    sharded = jax.shard_map(
        spmd_step,
        mesh=mesh2d,
        in_specs=(P(MESH2D_ROW_AXES), P(MESH2D_ROW_AXES), P(MESH2D_AXES)),
        out_specs=(P(MESH2D_ROW_AXES), P(MESH2D_ROW_AXES), P()),
        check_vma=False,
    )
    donate_argnums = (0, 1) if donate else ()
    return _StallWatchedStep(
        maybe_autotune_step(
            jax.jit(sharded, donate_argnums=donate_argnums),
            algorithm_candidates=_planner_autotune_candidates()),
        name_prefix)


def _segment_sync(leaves, seg_index, spec, axis_name, salt):
    """Identity-forward / reduce-backward boundary for ONE segment.

    The forward pass returns the segment's leaves unchanged; the
    custom-vjp backward reduces the segment's COTANGENTS through the
    exact wire the DistributedOptimizer was built with (op, compression,
    scaling, bucketing — via ``optimizer._reduce_grads``). Because the
    boundary sits inside the differentiated function, the collective
    DEPENDS only on this segment's cotangents — for late-layer segments
    those are complete EARLY in the backward, so XLA's latency-hiding
    scheduler can overlap the transfer with the remaining layers'
    backward compute. Data dependence is all the program fixes: where
    the collective prints in the jaxpr (after the whole backward, under
    jax 0.9's trace-order transposition) and when a device issues it are
    not.

    ``salt`` (the int8 stochastic-rounding step counter) rides the
    forward as a residual rather than a closure: custom-vjp rules must
    not close over tracers, and its cotangent is the usual float0
    placeholder for integer primals.

    In the SHARDED sync mode the boundary's backward emits the segment's
    reduce-scatter instead (still inside the backward pass, so it still
    overlaps backward compute); the cotangent contract forces full
    primal shapes, so each reduced shard rides a zero background at its
    owner offset (``optimizer._embed_shards``) and the step extracts the
    shards afterwards (``optimizer._local_shards`` — exact, since
    non-owned positions are zeros it never reads).
    """
    import numpy as np

    from ..optimizer import _known_size, _reduce_grads
    from ..profiler import annotate_collective

    sharded_mode = getattr(spec, "sync_mode", "allreduce") == "sharded"

    def reduce_cts(cts, s):
        with annotate_collective(f"overlap.segment{seg_index}"):
            if sharded_mode:
                from ..optimizer import _embed_shards, _reducescatter_grads

                n = _known_size(spec.process_set)
                shards = _reducescatter_grads(
                    list(cts),
                    spec.op,
                    axis_name,
                    spec.compression,
                    spec.prescale_factor,
                    spec.postscale_factor,
                    spec.fusion_threshold_bytes,
                    spec.num_groups,
                    world_size=n,
                    quant_salt=s,
                    issue_reversed=True,
                )
                return _embed_shards(shards, list(cts), axis_name, n)
            return _reduce_grads(
                list(cts),
                spec.op,
                axis_name,
                spec.compression,
                spec.prescale_factor,
                spec.postscale_factor,
                spec.fusion_threshold_bytes,
                spec.num_groups,
                world_size=_known_size(spec.process_set),
                quant_salt=s,
                issue_reversed=True,
            )

    if salt is None:

        @jax.custom_vjp
        def ident(ls):
            return list(ls)

        def fwd(ls):
            return list(ls), None

        def bwd(_, cts):
            return (reduce_cts(cts, None),)

        ident.defvjp(fwd, bwd)
        return ident(list(leaves))

    @jax.custom_vjp
    def ident_salted(ls, s):
        return list(ls)

    def fwd_salted(ls, s):
        return list(ls), s

    def bwd_salted(s, cts):
        return (reduce_cts(cts, s),
                np.zeros(np.shape(s), jax.dtypes.float0))

    ident_salted.defvjp(fwd_salted, bwd_salted)
    return ident_salted(list(leaves), salt)


def overlap_gradient_sync(
    params,
    spec,
    axis_name=None,
    num_segments: int | None = None,
    salt=None,
):
    """Wrap a parameter pytree so its gradients are reduced SEGMENT BY
    SEGMENT inside the backward pass — the communication-overlap
    scheduler's core primitive.

    The pytree's leaves are split into K contiguous byte-balanced
    segments (``ops.fusion.segment_leaves`` — layer order, so the last
    segment's gradients materialize first during backprop) and each
    segment gets an identity-forward / reduce-backward custom-vjp
    boundary. Differentiating through the wrapped tree yields gradients
    that are ALREADY reduced, with each segment's collective depending
    on that segment's gradients alone instead of sitting behind a global
    post-backward barrier.

    Must be applied INSIDE the differentiated function::

        spec = hvd.reduce_spec_of(dist_optimizer)

        def loss_of(p):
            return loss_fn(hvd.overlap_gradient_sync(p, spec), batch)

        loss, grads = jax.value_and_grad(loss_of)(params)  # reduced
        updates, st = spec.inner.update(grads, inner_state, params)

    Args:
      params: the parameter pytree being differentiated.
      spec: a :class:`horovod_tpu.optimizer.ReduceSpec` (from
        ``reduce_spec_of``) naming the wire to issue per segment.
      axis_name: collective axis (name or hierarchical ``(cross,
        local)`` tuple); defaults to the trace-time resolution for the
        spec's process set, exactly like the DistributedOptimizer.
      num_segments: segment count K; defaults to the autotuned /
        ``HOROVOD_OVERLAP_SEGMENTS`` value
        (``ops.fusion.overlap_segments``). K=1 degenerates to the
        monolithic single-boundary reduction.
      salt: optional int8 stochastic-rounding step counter (see
        ``ops.quantization._sround``).
    """
    from ..ops.fusion import overlap_segments, segment_leaves

    if axis_name is None:
        from ..ops.collective_ops import _effective_traced_axis

        axis_name = (_effective_traced_axis(spec.process_set)
                     or spec.process_set.axis_name)
    k = num_segments if num_segments is not None else overlap_segments()
    leaves, treedef = jax.tree.flatten(params)
    # Note the FULL leaf layout before segmentation: the per-segment
    # wires below note only their subsets, and the model-guided autotune
    # predictor prices candidates against the whole flush.
    import jax.numpy as jnp

    from ..ops.fusion import _note_leaf_sizes

    _note_leaf_sizes([jnp.asarray(l) for l in leaves])
    new_leaves = list(leaves)
    for si, idx in enumerate(segment_leaves(leaves, k)):
        synced = _segment_sync(
            [leaves[i] for i in idx], si, spec, axis_name, salt)
        for i, s in zip(idx, synced):
            new_leaves[i] = s
    return jax.tree.unflatten(treedef, new_leaves)


def make_overlapped_train_step(
    loss_fn: Callable[..., Any],
    optimizer,
    mesh=None,
    axis_name: str | None = None,
    donate: bool = True,
    loss_is_averaged: bool = True,
    hierarchical: bool | tuple | None = None,
    num_segments: int | None = None,
):
    """Build a jitted SPMD train step whose gradient allreduces OVERLAP
    the backward pass — the compiled realization of Horovod's headline
    optimization (the reference's background thread starts reducing
    early-ready gradients while later layers still differentiate).

    Same contract as :func:`make_train_step`, with two differences:

    - ``optimizer`` MUST be a ``hvd.DistributedOptimizer``-wrapped
      transformation: its attached :class:`ReduceSpec` tells the
      scheduler which wire (op / compression / scaling / bucketing) to
      issue per segment, and the step applies the bare inner optimizer
      to the already-reduced gradients.
    - ``num_segments`` fixes the segment count K; by default it follows
      the autotuned decision (the transparent tuner gains a joint
      (threshold, segments) grid under ``HOROVOD_AUTOTUNE=1``) or
      ``HOROVOD_OVERLAP_SEGMENTS``.

    The parameter pytree is split into K contiguous byte-balanced
    segments (during backward the LAST segment's gradients materialize
    first, and its collective needs nothing else), so ICI/DCN transfer
    of segment *i* can run concurrently with backward compute of
    segments *< i* instead of serializing after the full backward.
    Hierarchical (cross, local) meshes compose per segment: each
    segment's buckets take the two-level reduce-scatter →
    cross-allreduce → allgather form, including the int8-compressed
    exchange.
    """
    import optax

    from ..attribution import SCOPE_OPTIMIZER
    from ..optimizer import _SaltState, reduce_spec_of
    from ..profiler import annotate_collective

    spec = reduce_spec_of(optimizer)
    if spec is None:
        raise ValueError(
            "make_overlapped_train_step requires a DistributedOptimizer-"
            "wrapped optimizer (its ReduceSpec tells the scheduler which "
            "wire to issue per segment); got a bare transformation")
    if spec.backward_passes_per_step != 1:
        raise ValueError(
            "the overlap scheduler does not compose with "
            "backward_passes_per_step > 1: accumulation defers the "
            "reduction to every k-th microstep, so most steps have no "
            "communication to overlap — use make_train_step")
    int8 = getattr(spec.compression, "marker", None) == "int8"
    sharded_mode = getattr(spec, "sync_mode", "allreduce") == "sharded"
    mesh2d = _resolve_mesh_2d(mesh, hierarchical)
    if mesh2d is not None:
        if getattr(spec, "sync_mode", "allreduce") != "fsdp":
            from ..exceptions import SyncModeIneligibleError

            raise SyncModeIneligibleError(
                "the overlap scheduler on a 2-D (batch, model) mesh is "
                "only defined for sync_mode='fsdp' (whose gather "
                "boundaries ARE the overlap machinery); allreduce/"
                "sharded overlapped steps run on the flat axis — unset "
                "HOROVOD_MESH_SHAPE or use make_train_step "
                "(docs/perf.md, '2-D mesh' guard table)")
        from .mesh import mesh_axis_sizes

        _record_mesh_axes(mesh_axis_sizes(mesh2d))
        return _make_fsdp_train_step_2d(
            loss_fn, spec, mesh2d, donate, loss_is_averaged,
            num_segments=num_segments,
            name_prefix="overlapped_train_step")
    mesh, axis_name = _resolve_mesh_axis(mesh, axis_name, hierarchical)
    if getattr(spec, "sync_mode", "allreduce") == "fsdp":
        # fsdp's gather boundaries ARE the overlap machinery: each
        # segment's reduce-scatter already rides a custom-vjp backward
        # inside backprop, and the per-segment forward gathers prefetch
        # against neighboring compute — the overlapped factory is the
        # same program, with the requested segment count honored.
        _check_flat_axis(axis_name, "make_overlapped_train_step", "fsdp")
        return _make_fsdp_train_step(
            loss_fn, spec, mesh, axis_name, donate, loss_is_averaged,
            num_segments=num_segments, name_prefix="overlapped_train_step")
    if sharded_mode:
        _check_flat_axis(axis_name, "make_overlapped_train_step")

    def spmd_step(params, opt_state, batch):
        from ..ops.collective_ops import _effective_traced_axis

        effective = (_effective_traced_axis(spec.process_set)
                     or spec.process_set.axis_name)
        if sharded_mode:
            local_state = jax.tree.map(lambda a: a[0], opt_state)
            salt = local_state.counter if int8 else None
        elif int8:
            inner_state, salt = opt_state.inner_state, opt_state.counter
        else:
            inner_state, salt = opt_state, None

        def loss_of(p):
            synced = overlap_gradient_sync(
                p, spec, axis_name=effective,
                num_segments=num_segments, salt=salt)
            return loss_fn(synced, batch)

        loss, grads = jax.value_and_grad(loss_of)(params)
        if sharded_mode:
            # Gradients arrive reduce-SCATTERED: each segment boundary's
            # backward emitted its reducescatter inside backprop and
            # placed this rank's shard on a zero background; slice the
            # shards back out, update only the owned shard, and gather
            # the updated PARAMETER shards — off the gradient path.
            from ..optimizer import _known_size, _local_shards
            from ..optimizer import sharded_step_update

            grad_shards = _local_shards(
                grads, effective, _known_size(spec.process_set))
            new_params, new_local = sharded_step_update(
                spec, grad_shards, local_state, params,
                axis_name=effective, grads_are_shards=True)
            new_state = jax.tree.map(lambda a: a[None], new_local)
            if loss_is_averaged:
                loss = jax.lax.pmean(loss, axis_name)
            return new_params, new_state, loss
        # Gradients arrive REDUCED (the segment collectives ran inside
        # the backward), so the bare inner optimizer applies them. Each
        # leaf's update depends only on its own reduced gradient, so in
        # the compiled program segment i's update can proceed while
        # segment i-1 is still reducing — the monolithic path's global
        # post-backward barrier (one concat depending on every gradient)
        # does not exist here.
        with annotate_collective(SCOPE_OPTIMIZER):
            updates, new_inner = spec.inner.update(
                grads, inner_state, params)
            new_params = optax.apply_updates(params, updates)
        new_state = _SaltState(new_inner, salt + 1) if int8 else new_inner
        if loss_is_averaged:
            loss = jax.lax.pmean(loss, axis_name)
        return new_params, new_state, loss

    opt_spec = P(axis_name) if sharded_mode else P()
    sharded = jax.shard_map(
        spmd_step,
        mesh=mesh,
        in_specs=(P(), opt_spec, P(axis_name)),
        out_specs=(P(), opt_spec, P()),
        check_vma=False,
    )
    donate_argnums = (0, 1) if donate else ()
    from ..autotune import DEFAULT_SEGMENT_CANDIDATES, maybe_autotune_step

    # The transparent tuner gains the segments axis only when K floats;
    # an explicit num_segments is the user's decision, threshold-only.
    seg_cands = (None if num_segments is not None
                 else DEFAULT_SEGMENT_CANDIDATES)
    return _StallWatchedStep(
        maybe_autotune_step(
            jax.jit(sharded, donate_argnums=donate_argnums),
            segment_candidates=seg_cands,
            algorithm_candidates=_planner_autotune_candidates()),
        "overlapped_train_step")


def shard_batch(batch, mesh=None, axis_name: str | None = None):
    """Place a host batch on the mesh, sharded along the leading axis.

    On a 2-D ``(batch, model)`` mesh the leading dim splits over BOTH
    axes in flat rank order (``("batch", "model")`` — rank ``b*model+m``
    gets the same rows it would on the flat 1-D mesh)."""
    from jax.sharding import NamedSharding

    from .. import basics
    from .mesh import MESH2D_AXES, is_mesh_2d

    if mesh is None:
        mesh = basics.global_mesh()
    if axis_name is None:
        axis_name = (MESH2D_AXES if is_mesh_2d(mesh)
                     else basics.global_axis_name())
    sharding = NamedSharding(mesh, P(axis_name))
    with tracing.place_span("shard_batch", batch):
        return jax.tree.map(
            partial(jax.device_put, device=sharding), batch)


def replicate(tree, mesh=None):
    """Place params/opt_state replicated over the mesh.

    Always copies: the result owns fresh buffers, so donating it to a
    jitted step (``donate_argnums``) can never invalidate the caller's
    source arrays. ``jax.device_put`` alone aliases the source into shard 0
    of the replicated array (even with ``may_alias=False``), and a donated
    step then silently deletes the original tree; the explicit ``jnp.copy``
    breaks that alias.
    """
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from .. import basics

    if mesh is None:
        mesh = basics.global_mesh()
    sharding = NamedSharding(mesh, P())

    def _copy_put(leaf):
        leaf = jnp.copy(leaf) if isinstance(leaf, jax.Array) else leaf
        return jax.device_put(leaf, sharding)

    with tracing.place_span("replicate", tree):
        return jax.tree.map(_copy_put, tree)


def make_elastic_train_step(
    loss_fn: Callable[..., Any],
    optimizer,
    mesh=None,
    axis_name: str | None = None,
):
    """Build a train step for ELASTIC multi-process worlds.

    Elastic workers run without ``jax.distributed`` (its coordination
    client aborts survivors on peer death — see docs/elastic.md), so the
    world is two-level: each process's LOCAL devices form a compiled DP
    mesh, and gradients cross processes on the native host data plane
    (which re-forms in-process after failures). This factory compiles the
    local leg (shard_map + local pmean) and performs the cross leg with a
    fused host allreduce each step — the two-level composition of
    ``host_hierarchical_allreduce`` specialized for training.

    Returns ``step(params, opt_state, batch) -> (params, opt_state,
    loss)`` where ``batch`` is this PROCESS's shard (leading dim divisible
    by the local device count). The world size may change between calls
    (the native world re-forms lazily); gradients always average over the
    processes currently in the world.
    """
    import numpy as np
    import jax.numpy as jnp
    import optax

    from .. import basics

    from ..exceptions import SyncModeIneligibleError

    if _sharded_spec_of(optimizer) is not None:
        raise SyncModeIneligibleError(
            "make_elastic_train_step does not support sync_mode='sharded' "
            "(its cross-process leg reduces on the host plane, outside the "
            "compiled shard domain); build the compiled step with "
            "make_train_step and let hvd.elastic.TpuState(...,"
            "sharded_optimizer=...) re-shard state across world changes")
    if _fsdp_spec_of(optimizer) is not None:
        raise SyncModeIneligibleError(
            "make_elastic_train_step does not support sync_mode='fsdp' "
            "(its cross-process leg reduces on the host plane, outside "
            "the compiled shard domain where the per-segment parameter "
            "gathers live); build the compiled step with make_train_step "
            "and let hvd.elastic.PeerShardedState re-shard the resident "
            "parameter and optimizer shards across world changes")
    mesh = mesh or basics.global_mesh()
    axis = axis_name or basics.global_axis_name()

    def local_grads(params, batch):
        def loss_of(p):
            return loss_fn(p, batch)

        loss, grads = jax.value_and_grad(loss_of)(params)
        # Local-device mean: the ICI-compiled leg.
        grads = jax.tree.map(lambda g: jax.lax.pmean(g, axis), grads)
        return jax.lax.pmean(loss, axis), grads

    grad_step = jax.jit(
        jax.shard_map(
            local_grads,
            mesh=mesh,
            in_specs=(P(), P(axis)),
            out_specs=(P(), P()),
            check_vma=False,
        )
    )

    @jax.jit
    def apply_step(params, opt_state, grads):
        updates, new_opt = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_opt

    def step(params, opt_state, batch):
        import os

        # The elastic step's phases ARE host-separable (compiled local
        # leg, host collective leg, compiled apply), so each gets a real
        # span — the per-phase breakdown the cross-rank timeline merges
        # and the attribution plane decomposes. Names come from the one
        # shared vocabulary (attribution.PHASE_SPAN_NAMES).
        with tracing.span(attribution.SPAN_FORWARD_BACKWARD,
                          attribution.CAT_PHASE):
            loss, grads = grad_step(params, batch)
        nprocs = int(os.environ.get("HOROVOD_NUM_PROCESSES", "1") or 1)
        if nprocs > 1 and jax.process_count() == 1:
            # Cross-process leg: fused host allreduce through the native
            # runtime (negotiation + response cache + ring). Failures
            # surface as HorovodInternalError for the elastic retry loop.
            # Skipped when jax.distributed spans the processes — the
            # compiled pmean is already global there.
            #
            # Weighted by each process's LOCAL device count: unequal hosts
            # (4-chip next to 8-chip) must not get equal votes — the cross
            # result is sum(local_mean * n_local) / sum(n_local), the true
            # mean over every device. The loss rides the same fused
            # reduction so every process sees the GLOBAL loss (divergent
            # local losses driving control flow would desynchronize the
            # next collective). Accumulation dtype per leaf: f64 stays
            # f64; f32/bf16/f16 accumulate in f32 and cast back.
            from ..ops.collective_ops import Sum, grouped_allreduce

            with tracing.span(attribution.SPAN_COLLECTIVE,
                              attribution.CAT_COLLECTIVE,
                              args={"plane": "host"}):
                n_local = float(mesh.size)
                leaves, treedef = jax.tree.flatten(grads)
                acc = [np.float64 if np.asarray(l).dtype == np.float64
                       else np.float32 for l in leaves]
                f32_idx = [i for i, a in enumerate(acc) if a == np.float32]
                f64_idx = [i for i, a in enumerate(acc) if a == np.float64]
                # count + loss join the f32 group.
                f32_payload = [np.asarray(leaves[i], np.float32) * n_local
                               for i in f32_idx]
                f32_payload.append(np.asarray([float(loss)], np.float32)
                                   * n_local)
                f32_payload.append(np.asarray([n_local], np.float32))
                red32 = grouped_allreduce(f32_payload, op=Sum)
                total_n = float(np.asarray(red32[-1])[0])
                global_loss = float(np.asarray(red32[-2])[0]) / total_n
                out = list(leaves)
                for i, r in zip(f32_idx, red32[:-2]):
                    out[i] = jnp.asarray(
                        np.asarray(r) / total_n).astype(leaves[i].dtype)
                if f64_idx:
                    red64 = grouped_allreduce(
                        [np.asarray(leaves[i], np.float64) * n_local
                         for i in f64_idx], op=Sum)
                    for i, r in zip(f64_idx, red64):
                        out[i] = jnp.asarray(
                            np.asarray(r) / total_n).astype(leaves[i].dtype)
                grads = jax.tree.unflatten(treedef, out)
                loss = jnp.asarray(global_loss, jnp.float32)
        with tracing.span(attribution.SPAN_OPTIMIZER_UPDATE,
                          attribution.CAT_PHASE):
            params, opt_state = apply_step(params, opt_state, grads)
        return params, opt_state, loss

    return _StallWatchedStep(step, "elastic_train_step")
