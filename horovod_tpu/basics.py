"""Lifecycle + world facts: the ``hvd.init()/rank()/size()`` surface.

TPU-native re-design of the reference's ``horovod/common/basics.py``
(``HorovodBasics``) and the C API it binds
(``horovod/common/operations.cc — horovod_init/_rank/_size/...``).

Key divergence from the reference, by design: JAX is a single-controller SPMD
system — one Python process drives many devices, and collectives are
*compiled into* the step function rather than enqueued to a background
thread. So:

- ``size()`` is the number of **devices** (one rank per chip, like Horovod's
  one rank per GPU), not the number of processes.
- Inside a compiled step (under ``shard_map`` over the hvd axis), ``rank()``
  returns the per-device ``lax.axis_index`` — a traced value.
- Outside compiled code, ``rank()`` returns the first local device's global
  rank: it is 0 exactly on the process that should do rank-0-only work
  (checkpointing, logging), which preserves the reference idiom
  ``if hvd.rank() == 0: save(...)``.
- For input pipelines, shard data by ``process_rank()/process_count()``
  (each controller process feeds its local devices), the JAX-native
  equivalent of the reference's per-rank data sharding.

Multi-host initialization uses ``jax.distributed.initialize`` driven by the
launcher's env (coordinator address from the rendezvous server), replacing
the reference's MPI/Gloo bootstrap.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Sequence

from .exceptions import NotInitializedError
from .topology import Topology
from .utils.env import RuntimeConfig
from .utils.logging import get_logger

_lock = threading.Lock()


class _GlobalState:
    """Singleton runtime state (analog of the reference's
    ``HorovodGlobalState`` in ``horovod/common/global_state.h``), minus the
    background thread: negotiation is compiled away in the JAX path, and the
    native runtime (``horovod_tpu.runtime``) owns its own loop when used.
    """

    def __init__(self) -> None:
        self.initialized = False
        self.topology: Topology | None = None
        self.config: RuntimeConfig | None = None
        self.mesh = None  # global 1-D jax Mesh over all ranks, axis 'hvd'
        self.axis_name = "hvd"
        self.distributed_initialized = False

    def require_init(self) -> "_GlobalState":
        if not self.initialized:
            raise NotInitializedError()
        return self


_state = _GlobalState()


def _maybe_init_distributed() -> None:
    """Multi-host bootstrap over DCN via jax.distributed.

    The launcher (``horovod_tpu.runner``) writes the coordinator address in
    env; on managed TPU slices JAX can also discover it from metadata, in
    which case this is a no-op.
    """
    import jax

    # Elastic mode: the world config lives in the rendezvous KV (it changes
    # across epochs); refresh the env contract before reading it. Env check
    # first so non-elastic workers never import the launcher machinery.
    if os.environ.get("HOROVOD_ELASTIC", "") == "1":
        from .runner.elastic import worker as elastic_worker

        ctx = elastic_worker.get_worker_context()
        if elastic_worker.spare_mode():
            # Warm spare: no assignment exists yet by design. Start the
            # poll loop (advances the generation view so KV writes stay
            # fenced) and the heartbeat sender (the driver's liveness
            # plane watches spares too) FIRST, then park until the driver
            # publishes a world that includes this host — promotion costs
            # one re-rendezvous, not a cold launch.
            ctx.start_polling()
            ctx.start_heartbeat()
            ctx.apply_to_env(ctx.wait_for_assignment())
        else:
            ctx.apply_to_env(ctx.fetch_assignment())
            ctx.start_polling()
            # Liveness plane: publish heartbeats so the driver can tell a
            # hung host (SIGSTOP'd, wedged VM) from a slow one —
            # popen.poll() alone cannot. No-op when
            # HOROVOD_ELASTIC_HEARTBEAT_INTERVAL <= 0.
            ctx.start_heartbeat()

    coord = os.environ.get("HOROVOD_COORDINATOR_ADDR", "")
    nprocs = int(os.environ.get("HOROVOD_NUM_PROCESSES", "0") or 0)
    proc_id = int(os.environ.get("HOROVOD_PROCESS_ID", "-1") or -1)
    if (os.environ.get("HOROVOD_ELASTIC", "") == "1"
            and os.environ.get("HOROVOD_ELASTIC_JAX_DISTRIBUTED", "") != "1"):
        # Elastic default: NO jax.distributed. Its coordination client
        # FATALLY ABORTS the surviving processes when a peer dies (C++
        # terminate, uncatchable) — the exact event elastic exists to
        # survive. Cross-process collectives ride the native host plane,
        # which re-forms in-process (tested); each process keeps a local
        # jax device world. Opt back in with
        # HOROVOD_ELASTIC_JAX_DISTRIBUTED=1 if you accept that any peer
        # death restarts every worker (the driver relaunches them).
        get_logger().info(
            "elastic: skipping jax.distributed (in-process recovery); set "
            "HOROVOD_ELASTIC_JAX_DISTRIBUTED=1 for a global jax world")
        return
    if coord and nprocs > 1 and proc_id >= 0:
        coord = _exchange_coordinator_port(coord, proc_id)
        # Write the resolved address back so downstream consumers (e.g. the
        # native host world, which shares the coordinator host) never see
        # the unresolved 'self' sentinel.
        os.environ["HOROVOD_COORDINATOR_ADDR"] = coord
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=nprocs,
            process_id=proc_id,
        )
        _state.distributed_initialized = True


def _exchange_coordinator_port(coord: str, proc_id: int) -> str:
    """Let process 0 pick the coordinator port ON ITS OWN HOST and publish
    it via the rendezvous KV; everyone else polls for it.

    The launcher cannot probe a free port on a remote coordinator host
    (classic TOCTOU across machines); its port choice is only a fallback
    for worlds launched without a rendezvous server.
    """
    import time

    addr = os.environ.get("HOROVOD_RENDEZVOUS_ADDR", "")
    port = int(os.environ.get("HOROVOD_RENDEZVOUS_PORT", "-1") or -1)
    if not addr or port < 0:
        return coord  # manual launch: trust the env as given
    from .runner.http.kv_server import KVClient, env_generation
    from .runner.network import free_port, routable_addr

    host = coord.rsplit(":", 1)[0]
    if host == "self":
        # Cluster integrations (Ray/Spark) can't know which node rank 0
        # lands on; the sentinel makes process 0 publish its own address.
        host = routable_addr()
    version = os.environ.get("HOROVOD_WORLD_VERSION", "static")
    scope = f"coord/{version}"
    # Generation-fenced: a zombie rank 0 resumed from a pre-abort world
    # must not republish a stale coordinator endpoint.
    kv = KVClient(addr, port, generation_fn=env_generation)
    if proc_id == 0:
        chosen = f"{host}:{free_port()}"
        kv.put(scope, "addr", chosen.encode())
        return chosen
    deadline = time.time() + 60.0
    while time.time() < deadline:
        val = kv.get(scope, "addr")
        if val is not None:
            return val.decode()
        time.sleep(0.05)
    raise TimeoutError(
        f"coordinator address not published to rendezvous KV scope {scope!r}"
    )


def enable_compile_cache() -> str:
    """Give JAX's persistent compilation cache a directory; returns it.

    ``JAX_COMPILATION_CACHE_DIR`` is how the cache is placed from
    outside: when it is set JAX reads it itself and nothing is set here.
    Otherwise the cache lives at ``<checkout>/.jax_cache`` — a fixed
    path, because the path is part of the cache's key and a directory
    that moves never hits. Call before the first compilation.

    The cache's key leaves a program's metadata out (JAX's default), so
    an executable that a tree with other named scopes cached (an older
    checkout sharing the directory) is served with THAT tree's scopes in
    its text and in every profile of it; ``profiler.step_scopes`` then
    fails saying so. Putting the metadata into the key
    (``jax_compilation_cache_include_metadata_in_key``) cures it, at a
    price measured on the v5e (PERF.md, PR 23): the key then holds the
    callers' file names and line numbers, and the same tree started
    through another script, or after an edit that moves a line, compiles
    everything again. That is for a profiling session to set, not for
    every start.
    """
    import jax

    from . import profiler, tracing
    from .attribution import SPAN_SETUP_COMPILE_CACHE

    with tracing.setup_span(SPAN_SETUP_COMPILE_CACHE) as span:
        # What the cache saves is read off the compile account
        # (``hvd.cache_stats()["compile"]``), so it listens from here on.
        profiler.compile_account().listen()
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            checkout = os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))
            jax.config.update("jax_compilation_cache_dir",
                              os.path.join(checkout, ".jax_cache"))
        span.note(dir=jax.config.jax_compilation_cache_dir)
    return jax.config.jax_compilation_cache_dir


def init(devices: Sequence[Any] | None = None) -> None:
    """Initialize the framework: topology, global mesh, process sets.

    Replaces the reference's ``InitializeHorovodOnce()`` — but where that
    spawned a background negotiation thread, this derives the static world:
    sorted device list (ICI order), the global 1-D mesh (axis ``'hvd'``)
    that every collective and the DistributedOptimizer shard over, and the
    global process set. Idempotent.
    """
    import jax
    from jax.sharding import Mesh
    import numpy as np

    from . import tracing
    from .attribution import SPAN_SETUP_INIT

    with _lock:
        if _state.initialized:
            return
        # A world formed anew (a re-formation, a resume) is a set-up of
        # its own; the account open since the import is the first one's.
        tracing.get_tracer().reopen_setup()
        with tracing.setup_span(SPAN_SETUP_INIT) as span:
            # Distributed bootstrap first: in elastic mode it refreshes the
            # env world facts from the KV, which from_env() must then see.
            _maybe_init_distributed()
            config = RuntimeConfig.from_env()
            topo = Topology(devices)
            _state.topology = topo
            _state.config = config
            _state.mesh = Mesh(np.array(topo.devices), (_state.axis_name,))
            _state.initialized = True

            # Register the global process set (id 0) now that the world
            # exists.
            from . import process_sets

            process_sets._reset(topo, _state.mesh)
            # Honor HOROVOD_PROFILER_LOGDIR (xprof capture; the reference's
            # NVTX-activation-by-env contract).
            from . import profiler

            profiler.maybe_start_from_env()
            profiler.compile_account().listen()
            span.note(ranks=topo.size, backend=jax.default_backend())
        get_logger().info(
            "horovod_tpu initialized: %d rank(s), %d host(s), backend=%s",
            topo.size,
            topo.cross_size,
            jax.default_backend(),
        )


def shutdown() -> None:
    """Tear down world state (elastic re-init calls this before re-forming)."""
    with _lock:
        # Distributed teardown runs even when init() died half-way (after
        # jax.distributed came up but before _state.initialized was set) —
        # otherwise the next init() hits "already initialized" forever.
        if _state.distributed_initialized:
            import jax

            try:
                jax.distributed.shutdown()
            except Exception as e:  # broken world: still clear the flag
                get_logger().warning("jax.distributed.shutdown failed: %s", e)
            _state.distributed_initialized = False
        # The native host world (libhvdrt) is per-epoch too: tear it down
        # so elastic re-init forms a fresh one instead of retrying against
        # a dead runtime forever.
        from .parallel import hierarchical

        if hierarchical._host_world is not None:
            try:
                hierarchical._host_world.shutdown()
            except Exception as e:
                get_logger().warning("native world shutdown failed: %s", e)
            hierarchical._host_world = None
        if not _state.initialized:
            return
        from . import process_sets
        from .ops.executable_cache import global_cache

        # Compiled executables are sharded over this epoch's mesh; a new
        # world must not hit them (stale devices / reused process-set ids).
        global_cache().clear()
        process_sets._clear()
        _state.initialized = False
        _state.topology = None
        _state.mesh = None
        _state.config = None
        from . import tracing

        tracing.get_tracer().reopen_setup()


def is_initialized() -> bool:
    return _state.initialized


def in_axis_scope(axis_name) -> bool:
    """True when called under shard_map/pmap with `axis_name` bound.

    The single shared probe used by every dual-regime API (rank(),
    local_rank(), the collective ops) to decide traced vs eager dispatch.
    Accepts a tuple of axis names (the hierarchical ``(cross, local)``
    mesh); all must be bound.
    """
    import jax

    if isinstance(axis_name, (tuple, list)):
        return all(in_axis_scope(a) for a in axis_name)
    try:
        jax.lax.axis_index(axis_name)
        return True
    except (NameError, KeyError, TypeError):
        return False


def _axis_index_or_none(axis_name):
    """Per-device rank if called under a mapped axis, else None.

    Falls back to the hierarchical ``(cross, local)`` axes when the flat
    axis is unbound: ``lax.axis_index`` over the tuple yields the
    flattened (cross-major) index, which is the rank order of the
    hierarchical mesh.
    """
    import jax

    if in_axis_scope(axis_name):
        return jax.lax.axis_index(axis_name)
    if axis_name == _state.axis_name:
        from .parallel.hierarchical import HIERARCHICAL_AXES

        if in_axis_scope(HIERARCHICAL_AXES):
            return jax.lax.axis_index(HIERARCHICAL_AXES)
    return None


def rank(axis_name: str | None = None):
    """Global rank. Traced (per-device) inside shard_map; else process view."""
    st = _state.require_init()
    idx = _axis_index_or_none(axis_name or st.axis_name)
    if idx is not None:
        return idx
    return st.topology.rank


def size() -> int:
    """Total number of ranks (devices) in the world."""
    return _state.require_init().topology.size


def local_rank(axis_name: str | None = None):
    st = _state.require_init()
    idx = _axis_index_or_none(axis_name or st.axis_name)
    if idx is not None:
        import jax.numpy as jnp

        # Table lookup: hosts are not contiguous in ICI rank order.
        return jnp.asarray(st.topology.local_rank_table)[idx]
    return st.topology.local_rank


def local_size() -> int:
    return _state.require_init().topology.local_size


def cross_rank() -> int:
    return _state.require_init().topology.cross_rank


def cross_size() -> int:
    return _state.require_init().topology.cross_size


def process_rank() -> int:
    """This controller process's index — shard input pipelines by this."""
    return _state.require_init().topology.process_index


def process_count() -> int:
    return _state.require_init().topology.process_count


def global_mesh():
    """The global 1-D mesh (axis 'hvd') in canonical ICI rank order."""
    return _state.require_init().mesh


def global_axis_name() -> str:
    return _state.axis_name


def config() -> RuntimeConfig:
    return _state.require_init().config


def is_homogeneous() -> bool:
    """True if every host has the same number of local ranks."""
    topo = _state.require_init().topology
    return topo.size == topo.local_size * topo.cross_size


# -- build/capability introspection (parity: HorovodBasics' *_built/*_enabled
# surface — scripts use these to pick code paths; each answer names the
# TPU-native subsystem playing the reference role) -------------------------


def mpi_enabled() -> bool:
    """False: there is no MPI path — the control plane is the rendezvous
    KV + TCP star (reference's Gloo role); the data plane is XLA/ICI."""
    return False


def mpi_built() -> bool:
    return False


def gloo_enabled() -> bool:
    """True: the native TCP runtime (libhvdrt) plays Gloo's role — the
    CPU/host data plane and the elastic substrate."""
    return True


def gloo_built() -> bool:
    try:
        from .runtime import load_library

        load_library()
        return True
    except Exception:
        return False


def nccl_built() -> bool:
    """True: XLA collectives over ICI play NCCL's role (AllReduce/
    AllGather/AllToAll/ReduceScatter HLOs compiled into the step)."""
    return True


def ddl_built() -> bool:
    return False  # removed upstream too


def ccl_built() -> bool:
    return False


def cuda_built() -> bool:
    """False — and intentionally so: this framework targets TPUs; the
    accelerator data plane is ICI, not CUDA."""
    return False


def rocm_built() -> bool:
    return False


def mpi_threads_supported() -> bool:
    """Parity shim: the native runtime's enqueue API is thread-safe (the
    property this reference check actually gates on)."""
    return True
