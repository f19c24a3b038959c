"""Hierarchical (two-level) allreduce: the ICI+DCN composition.

Reference role: ``NCCLHierarchicalAllreduce``
(``horovod/common/ops/nccl_operations.cc``) — NCCL reduce-scatter within a
node, MPI allreduce across nodes on host, NCCL allgather within the node,
enabled by ``HOROVOD_HIERARCHICAL_ALLREDUCE``. The TPU mapping (SURVEY.md
§6): the fast "intra" leg is the ICI mesh inside a slice, the slow "cross"
leg is DCN between hosts/slices.

Two forms, mirroring the framework's two regimes:

- **Traced**: over a 2-D ``(cross, local)`` mesh —
  ``psum_scatter`` over the local axis → ``psum`` over the cross axis →
  ``all_gather`` over the local axis. Each device moves 1/local_size of
  the payload across the slow axis instead of the whole tensor, which is
  exactly the reference's bandwidth argument for the NCCL+MPI composition.
  Build the mesh with :func:`hierarchical_mesh`; inside a
  ``shard_map`` over both axes every collective op accepts the
  ``(cross, local)`` axis tuple transparently. Rank-order caveat: the
  hierarchical mesh's rank order is host-grouped (cross-major), which on
  interleaved ICI topologies differs from the canonical flat rank order —
  reductions are unaffected, but rank-sensitive ops (allgather
  concatenation, broadcast root, alltoall blocks, ``hvd.rank()``) follow
  the host-grouped order inside a hierarchical step.

- **Host/eager**: each controller process reduces its local shards with
  XLA, then the **cross-process leg runs through the native C++ runtime**
  (``horovod_tpu.runtime.NativeWorld`` — negotiation, fusion, response
  cache, ring TCP), making libhvdrt the DCN leg the way MPI was for the
  reference. See :func:`host_hierarchical_allreduce`.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh

CROSS_AXIS = "hvd_cross"
LOCAL_AXIS = "hvd_local"
HIERARCHICAL_AXES = (CROSS_AXIS, LOCAL_AXIS)


def hierarchical_mesh(cross_size: int | None = None,
                      local_size: int | None = None) -> Mesh:
    """A 2-D ``(cross, local)`` mesh over the world's devices in ICI order.

    Defaults to the topology's host structure (``cross_size`` hosts ×
    ``local_size`` chips per host) so the local axis rides ICI and the
    cross axis spans DCN. The canonical ICI rank order does NOT group a
    host's chips contiguously (``topology.py``), so rows are built by
    grouping devices by host, never by reshaping the flat order — a row
    that mixed hosts would put the full-payload reduce-scatter/allgather
    legs on DCN and invert the optimization. Explicit factors exist for
    tests and for splits that intentionally differ from host boundaries
    (those reshape the canonical order and must multiply to the world
    size).
    """
    from .. import basics

    topo = basics._state.require_init().topology
    if cross_size is None and local_size is None:
        if topo.size == topo.cross_size * topo.local_size:
            # Host-grouped rows: row i = host i's chips in canonical order.
            by_host: dict[int, list] = {}
            for d in topo.devices:
                by_host.setdefault(d.process_index, []).append(d)
            rows = [by_host[p] for p in sorted(by_host)]
            if len({len(r) for r in rows}) != 1:
                rows = [[d] for d in topo.devices]  # ragged: flat cross
            return Mesh(np.array(rows), HIERARCHICAL_AXES)
        # Heterogeneous hosts: fall back to a flat cross axis.
        cross_size, local_size = topo.size, 1
    elif cross_size is None:
        cross_size = topo.size // local_size
    elif local_size is None:
        local_size = topo.size // cross_size
    if cross_size * local_size != topo.size:
        raise ValueError(
            f"hierarchical mesh {cross_size}x{local_size} does not cover "
            f"the {topo.size}-device world"
        )
    devices = np.array(topo.devices).reshape(cross_size, local_size)
    return Mesh(devices, HIERARCHICAL_AXES)


def hierarchical_allreduce(
    x,
    op: str = "average",
    cross_axis: str = CROSS_AXIS,
    local_axis: str = LOCAL_AXIS,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
):
    """Traced two-level allreduce (call under shard_map over both axes).

    Sum/Average take the bandwidth-optimal reduce-scatter → cross-allreduce
    → allgather composition; Min/Max/Product reduce over both axes directly
    (already latency-optimal as one HLO); Adasum mirrors the reference's
    GPU hierarchy — average within the fast domain, Adasum across the slow
    one (``adasum_gpu_operations.cc`` semantics).
    """
    from ..ops.collective_ops import (
        Adasum, Average, Max, Min, Product, Sum, _VALID_OPS,
    )

    if op in (Min, Max, Product):
        from ..ops.collective_ops import allreduce_traced

        return allreduce_traced(
            x, op, (cross_axis, local_axis), prescale_factor, postscale_factor
        )
    if op == Adasum:
        from ..ops.adasum import adasum_reduce

        if prescale_factor != 1.0:
            x = x * jnp.asarray(prescale_factor, x.dtype)
        out = lax.pmean(x, local_axis)
        out = adasum_reduce(out, cross_axis)
        if postscale_factor != 1.0:
            out = out * jnp.asarray(postscale_factor, out.dtype)
        return out
    if op not in (Sum, Average):
        raise ValueError(f"unknown reduce op {op!r}; expected {_VALID_OPS}")

    if prescale_factor != 1.0:
        x = x * jnp.asarray(prescale_factor, x.dtype)
    local_n = lax.psum(1, local_axis)

    shape = x.shape
    flat = x.reshape(-1)
    pad = (-flat.size) % local_n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    # Each device keeps 1/local_n of the payload for the slow-axis hop.
    # The three legs are named so a profile shows which leg of which
    # segment/bucket overlaps which slice of backward compute — the
    # overlap scheduler issues this composition once PER SEGMENT, and
    # the legs keep their relative order within each segment while
    # different segments' legs interleave freely by dataflow.
    from ..profiler import annotate_collective

    with annotate_collective("hier.reduce_scatter_local"):
        shard = lax.psum_scatter(
            flat, local_axis, scatter_dimension=0, tiled=True)
    with annotate_collective("hier.allreduce_cross"):
        shard = lax.psum(shard, cross_axis)
    with annotate_collective("hier.allgather_local"):
        full = lax.all_gather(shard, local_axis, axis=0, tiled=True)
    if pad:
        full = full[: flat.size - pad]
    out = full.reshape(shape)

    scale = postscale_factor
    if op == Average:
        scale = scale / (local_n * lax.psum(1, cross_axis))
    if scale != 1.0:
        out = out * jnp.asarray(scale, out.dtype)
    return out


# ---------------------------------------------------------------------------
# Host/eager form: XLA local leg + native-runtime (libhvdrt) cross leg.
# ---------------------------------------------------------------------------

_host_world = None
_host_world_gen = None  # HOROVOD_WORLD_VERSION the cached world was built in


def _default_native_world():
    """Process-wide NativeWorld from the launcher's env contract.

    The cache is liveness-checked, not just memoized: the native runtime
    state is process-global, so ANY shutdown path (elastic re-init, test
    teardown, another NativeWorld instance) can kill it — in which case the
    next call re-establishes a live world instead of handing back a dead
    one forever.

    In an elastic world, a cached world found dead within the SAME
    generation it was built for is a peer-departure signal (a drained or
    crashed rank's negotiated shutdown), not a rebuild opportunity:
    re-forming from the still-stale env would re-join the dying epoch's
    endpoints (connect-timeout against a drained peer's dead coordinator).
    That case raises ``HorovodInternalError`` so the elastic recovery
    ladder re-rendezvouses with fresh env; once re-init has advanced
    ``HOROVOD_WORLD_VERSION``, rebuilding is legitimate again.
    """
    global _host_world, _host_world_gen
    if _host_world is not None and not _host_world.alive:
        # Initialized-but-dead (fatal control-plane error) or shut down:
        # tear down so re-init can form a fresh world (elastic recovery).
        try:
            _host_world.shutdown()
        except Exception:
            pass
        _host_world = None
        import os

        from ..runner.elastic.worker import elastic_enabled

        env_gen = os.environ.get("HOROVOD_WORLD_VERSION")
        if (elastic_enabled() and env_gen is not None
                and env_gen == _host_world_gen):
            from ..exceptions import HorovodInternalError

            raise HorovodInternalError(
                f"native host world died within generation {env_gen} "
                "(peer drained or crashed); entering elastic recovery"
            )
    if _host_world is None:
        import os

        from ..runner.elastic.worker import elastic_enabled
        from ..runtime import NativeRuntimeError, NativeWorld
        from ..utils.env import get_float

        nprocs = int(os.environ.get("HOROVOD_NUM_PROCESSES", "1") or 1)
        proc_id = int(os.environ.get("HOROVOD_PROCESS_ID", "0") or 0)
        addr = os.environ.get("HOROVOD_COORDINATOR_ADDR", "127.0.0.1")
        addr = addr.rsplit(":", 1)[0]
        port = int(os.environ.get("HOROVOD_NATIVE_PORT", "0") or 0)
        try:
            if nprocs > 1:
                addr, port = _exchange_native_endpoint(proc_id, port)
            if nprocs > 1 and not port:
                raise RuntimeError(
                    "host_hierarchical_allreduce needs HOROVOD_NATIVE_PORT "
                    "(the native runtime's coordinator port) in a "
                    "multi-process world"
                )
            _host_world = NativeWorld(
                proc_id, nprocs, addr, port or 29500,
                timeout_s=get_float("HOROVOD_NATIVE_INIT_TIMEOUT", 30.0))
        except (NativeRuntimeError, TimeoutError) as e:
            if not elastic_enabled():
                raise
            # An elastic epoch can die between this worker's assignment
            # fetch and its native join (a drained peer's coordinator is
            # gone, the endpoint never gets published, ...). That is
            # world churn, not a fatal runtime fault: surface it as the
            # recovery exception so the elastic ladder re-rendezvouses
            # with fresh state instead of the process dying rc=1.
            from ..exceptions import HorovodInternalError

            raise HorovodInternalError(
                f"native host world join failed ({e}); entering elastic "
                "recovery") from e
        _host_world_gen = os.environ.get("HOROVOD_WORLD_VERSION")
        _register_atexit_shutdown()
    return _host_world


_atexit_registered = False


def _register_atexit_shutdown() -> None:
    """Shut the native world down gracefully at interpreter exit: the C
    runtime's shutdown is NEGOTIATED (all ranks agree before the loop
    exits), so an early-exiting process drains cleanly instead of peers
    logging 'Connection reset by peer' at teardown."""
    global _atexit_registered
    if _atexit_registered:
        return
    _atexit_registered = True
    import atexit

    def _shutdown():
        w = _host_world
        if w is not None and w.alive:
            try:
                w.shutdown()
            except Exception:
                pass

    atexit.register(_shutdown)


def _exchange_native_endpoint(proc_id: int, fallback_port: int):
    """Rank 0 picks the native coordinator endpoint ON ITS OWN HOST and
    publishes it via the rendezvous KV; peers poll it.

    The launcher's HOROVOD_NATIVE_PORT is probed free on the LAUNCHER
    host — rank 0 may live elsewhere (Ray/Spark placement, remote -H
    hosts), the same cross-machine TOCTOU the coordinator port solves in
    ``basics._exchange_coordinator_port``. No KV (manual launch) → trust
    the env as given.
    """
    import os
    import time

    kv_addr = os.environ.get("HOROVOD_RENDEZVOUS_ADDR", "")
    kv_port = int(os.environ.get("HOROVOD_RENDEZVOUS_PORT", "-1") or -1)
    coord_host = os.environ.get(
        "HOROVOD_COORDINATOR_ADDR", "127.0.0.1").rsplit(":", 1)[0]
    if not kv_addr or kv_port < 0:
        return coord_host, fallback_port
    from ..runner.http.kv_server import KVClient, env_generation
    from ..runner.network import free_port, routable_addr

    version = os.environ.get("HOROVOD_WORLD_VERSION", "static")
    scope = f"native/{version}"
    # Generation-fenced: a zombie rank 0 must not republish a stale
    # native-coordinator endpoint into the re-formed world's rendezvous.
    kv = KVClient(kv_addr, kv_port, generation_fn=env_generation)
    if proc_id == 0:
        host = routable_addr()
        port = free_port()  # free on rank 0's host, where the bind happens
        kv.put(scope, "addr", f"{host}:{port}".encode())
        return host, port
    deadline = time.time() + 60.0
    while time.time() < deadline:
        val = kv.get(scope, "addr")
        if val is not None:
            host, port = val.decode().rsplit(":", 1)
            return host, int(port)
        time.sleep(0.05)
    raise TimeoutError(
        f"native endpoint not published to rendezvous KV scope {scope!r}"
    )


def host_hierarchical_allreduce(
    stacked,
    name: str,
    op: str = "average",
    world=None,
):
    """Eager hierarchical allreduce across controller processes.

    ``stacked`` follows the eager stacked-rank convention for THIS
    process's local shards: shape ``(local_n, *t)``. The local leg reduces
    those shards with XLA; the cross leg allreduces the partial through the
    native C++ runtime (negotiation + response cache + ring TCP over
    DCN — the reference's MPI role); the result is the full reduction over
    all ``local_n × n_processes`` logical ranks, returned stacked.
    """
    from ..ops.collective_ops import Average, Sum

    if op not in (Sum, Average):
        raise ValueError(f"host hierarchical allreduce supports sum/average, got {op!r}")
    w = world if world is not None else _default_native_world()
    x = jnp.asarray(stacked)
    if x.ndim < 1:
        raise ValueError("expected stacked-rank input (local_n, *shape)")
    local_n = x.shape[0]
    local_sum = jnp.sum(x, axis=0)  # ICI leg (XLA)
    cross = np.asarray(
        w.allreduce(np.asarray(local_sum), name, op="sum")
    )  # DCN leg (libhvdrt)
    if op == Average:
        # Processes may carry different shard counts; the divisor is the
        # true logical rank count, agreed through the same runtime.
        total = float(
            np.asarray(
                w.allreduce(
                    np.asarray([local_n], np.float32), name + "/count",
                    op="sum",
                )
            )[0]
        )
        cross = cross / total
    return jnp.broadcast_to(cross, x.shape)
