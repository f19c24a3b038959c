"""HBM memory observatory: the analytic footprint model, live resident
accounting, per-phase watermarks, and OOM forensics.

Per-core HBM is the binding constraint at pod scale (the MLPerf-on-pods
study, PAPERS.md arXiv:1909.09756): batch/model feasibility is governed
by memory long before FLOPs. The comms observatory (``comms_model.py``)
gave *time* a model, live measurement, and a cluster view; this module
does the same for *bytes*, in the same model/measure/expose/consume
shape:

- **Model** — :func:`predict_footprint` prices a training
  configuration's per-rank bytes analytically: resident params and
  optimizer state under each sync mode's layout (monolithic pytree,
  ZeRO-1 sharded stacked rows, fsdp resident rows — the per-leaf
  ``ceil(size/n)`` ownership map of ``ops.fusion.shard_ownership``
  makes the prediction EXACT, not estimated, including uneven and
  scalar leaves and the 2-D mesh's ceil identity), plus the transient
  peaks (fused gradient buckets, fsdp per-segment gather buffers, the
  2-D model-axis gather leg, MoE dispatch/combine alltoall buffers,
  serving swap staging).
- **Measure** — call sites that materialize resident state
  (``parallel/param_sharding.shard_params``, the sharded optimizer
  init, ``elastic/state.TpuState``) note their exact nbytes here;
  byte *suppliers* (peer replica pool, executable cache) are polled
  live; backend device-memory stats ride along where the platform
  exposes them (``Device.memory_stats``). The tracing plane's span
  exits drive per-step-phase watermark tracking
  (:meth:`MemoryObservatory.note_phase`).
- **Expose** — the zero-materialized gauges ``hvd_hbm_bytes{kind}``,
  ``hvd_hbm_watermark_bytes{phase}``, ``hvd_hbm_headroom_ratio`` and
  ``hvd_hbm_model_residual_bytes`` (predicted − measured: the drift
  alarm), the cluster-merged auth-exempt ``GET /memory`` on the
  rendezvous KV server (heartbeat-piggybacked :meth:`payload`, merged
  by :func:`merge_payloads`, generation-fenced like ``/comms``), and
  ``profiler.summary()["memory"]``.
- **Consume** — the factory step boundary catches
  ``RESOURCE_EXHAUSTED``/OOM errors and dumps a memory flight record
  naming the top-N resident leaves and the predicted-vs-measured delta
  (:func:`oom_flight_fields`); autotune's model-guided pruning rejects
  candidates whose predicted footprint exceeds the measured headroom
  (:func:`check_candidate` — the same rank-identical
  ``SyncModeIneligibleError`` skip discipline as the fsdp guards); the
  multi-tenant scheduler journals ``admission_memory_risk`` when a
  job's predicted footprint exceeds its host set's advertised HBM
  (:func:`admission_check` — advisory, never changes the grant).

Stdlib-only and jax-free at import (like ``comms_model.py``/
``tracing.py``): the rendezvous KV server imports
:func:`merge_payloads` on the driver before any framework init. jax is
imported lazily inside the measurement helpers only.
"""

from __future__ import annotations

import math
import os
import socket
import threading
import time
from typing import Any, Callable, Mapping, Sequence

from .comms_model import bucket_byte_runs, segment_byte_runs
from .utils.env import get_int

#: Canonical resident-state kinds (`kind` label values of
#: ``hvd_hbm_bytes``). ``params``/``opt_state`` are the model kinds the
#: footprint model prices; the rest are framework overheads measured
#: live only.
#: What the loaded programs reserve for their temporaries: no call site
#: notes it, the backend counts it (``bytes_reserved``).
PROGRAM_TEMPORARIES = "program_temporaries"

KINDS = ("params", "opt_state", "grads", "peer_pool", "executables",
         "serving", "other", PROGRAM_TEMPORARIES)

#: The model kinds — the subset :func:`predict_footprint` prices, and
#: the subset the residual (predicted − measured) gauge compares.
MODEL_KINDS = ("params", "opt_state")

#: Watermark phases (`phase` label values of
#: ``hvd_hbm_watermark_bytes``): the attribution plane's shared phase
#: span vocabulary plus the whole-step scope and a catch-all.
PHASES = ("step", "forward_backward", "collective", "optimizer_update",
          "other")

#: Transient-peak kinds in a footprint's ``transient`` section.
TRANSIENT_KINDS = ("grad_buckets", "fsdp_gather", "model_axis_gather",
                   "moe_alltoall", "serve_staging")


def _rank() -> str:
    return os.environ.get("HOROVOD_RANK", "0") or "0"


def _host() -> str:
    return os.environ.get("HOROVOD_HOSTNAME", "") or socket.gethostname()


def top_n() -> int:
    """How many resident leaves a forensics record names."""
    return max(1, get_int("HOROVOD_HBM_TOP_LEAVES", 8))


def ceil_shard(size: int, world_size: int) -> int:
    """Per-rank shard ELEMENTS of a leaf under the ownership map —
    the stdlib mirror of ``ops.fusion.shard_ownership`` for one leaf:
    ``max(1, ceil(size / world_size))``. The 2-D ``(batch, model)``
    mesh shares this number exactly by the ceil identity
    ``ceil(ceil(s/model)/batch) == ceil(s/(batch*model))``
    (``shard_ownership_2d``), so resident rows are mesh-shape
    independent."""
    n = max(1, int(world_size))
    return max(1, -(-int(size) // n))


def capacity_bytes() -> int | None:
    """Per-device HBM capacity, when any source knows it.

    ``HOROVOD_HBM_BYTES_PER_DEVICE`` wins (the operator's declared
    budget — also the only source on CPU smokes, where the backend
    reports no limit); otherwise the backend's ``memory_stats()``
    ``bytes_limit`` where the platform exposes one (TPU does). None
    when neither exists — headroom then reports 0 (= unknown), never a
    guess.
    """
    env = get_int("HOROVOD_HBM_BYTES_PER_DEVICE", 0)
    if env > 0:
        return env
    stats = device_memory_stats()
    if stats:
        limit = stats.get("bytes_limit")
        if isinstance(limit, (int, float)) and limit > 0:
            return int(limit)
    return None


# On a TPU ``bytes_in_use`` counts buffers only. What a loaded program
# needs for its temporaries is ``bytes_reserved``: held for as long as the
# executable is loaded, not only while it runs (v5e, BERT-Large sharded
# step, read after the steps returned: 10.87 GiB beside 2.71 GiB of
# buffers on every device, ``bytes_limit - bytes_in_use - bytes_reserved
# == largest_free_block_bytes``; 0 once the step is dropped; PERF.md). So
# what a chip holds is the two together (:func:`device_taken_bytes`):
# the watermarks fold that, and ``bytes_reserved`` is the observatory's
# ``program_temporaries`` kind.
_DEVICE_STAT_KEYS = ("bytes_in_use", "bytes_limit", "peak_bytes_in_use",
                     "bytes_reserved", "peak_bytes_reserved",
                     "largest_free_block_bytes")

_device_stats_dead = False


def local_device_memory_stats() -> list[dict]:
    """The backend's memory view of EVERY local device, in
    ``jax.local_devices()`` order — one dict per device with ``id``,
    ``kind`` and the allocator counters the platform reports (none on
    CPU)."""
    import jax

    return [
        {"id": d.id, "kind": d.device_kind,
         **{k: int(v) for k, v in (d.memory_stats() or {}).items()
            if k in _DEVICE_STAT_KEYS and isinstance(v, (int, float))}}
        for d in jax.local_devices()
    ]


def _local_stats_or_none() -> list[dict] | None:
    """:func:`local_device_memory_stats`, or None where it cannot be
    asked (no jax: cached) or fails (not cached: it may recover)."""
    global _device_stats_dead
    if _device_stats_dead:
        return None
    try:
        return local_device_memory_stats()
    except ImportError:
        _device_stats_dead = True  # driver-side: jax never appears
        return None
    except Exception:  # noqa: BLE001 — a backend whose stats call fails
        return None


def device_memory_stats() -> dict | None:
    """The per-device memory view this process is bound by
    (``bytes_in_use`` / ``bytes_limit`` / ``peak_bytes_in_use`` where
    present), folded over all local devices: the fullest device's
    usage counters, the smallest limit and free block — so a run that
    piles everything on one device reads as that device, whichever it
    is. None when jax is unavailable (driver-side) or the platform
    exposes nothing (CPU) — and that verdict is cached, so the per-span
    watermark hook never re-probes a statless backend. Never raises:
    heartbeat payloads and OOM flight records call it while something
    else is already going wrong (:func:`local_device_memory_stats` is
    the one that raises)."""
    global _device_stats_dead
    per_device = _local_stats_or_none()
    if per_device is None:
        return None
    folded: dict[str, int] = {}
    for k in _DEVICE_STAT_KEYS:
        values = [stats[k] for stats in per_device if k in stats]
        if values:
            folded[k] = (min(values) if k in (
                "bytes_limit", "largest_free_block_bytes") else max(values))
    if not folded:
        _device_stats_dead = True
        return None
    return folded


def device_taken_bytes() -> tuple[int, int] | None:
    """``(now, at peak)`` of what the fullest local device holds: buffers
    plus the loaded programs' temporaries, ``bytes_in_use +
    bytes_reserved`` and the sum of the two peaks (an upper bound: the
    backend keeps no peak of the sum). None where the backend reports
    nothing; never raises."""
    per_device = _local_stats_or_none()
    if per_device is None:
        return None
    taken = [(d["bytes_in_use"] + d.get("bytes_reserved", 0),
              d.get("peak_bytes_in_use", 0)
              + d.get("peak_bytes_reserved", 0))
             for d in per_device if "bytes_in_use" in d]
    if not taken:
        return None
    return max(now for now, _ in taken), max(peak for _, peak in taken)


# ---------------------------------------------------------------------------
# Leaf descriptors
# ---------------------------------------------------------------------------
#
# The model's unit of account is the leaf descriptor
# ``(size_elems, itemsize[, dtype])``: element counts — not bytes —
# because the ownership map shards ELEMENTS (``ceil(10/8)*4 = 8`` bytes
# per rank for a 10-element f32 leaf, where a byte-level
# ``ceil(40/8) = 5`` would be wrong). ``dtype`` (optional) feeds the
# fusion-bucket mirror's same-dtype packing rule.


def _normalize_leaves(leaves) -> list[tuple[int, int, str]]:
    """Normalize to ``[(size_elems, itemsize, dtype), ...]``. Accepts
    stdlib descriptor lists (2- or 3-tuples) or any jax pytree (lazy
    conversion via :func:`leaf_templates`)."""
    if leaves is None:
        return []
    # A descriptor list must hold (number, number[, dtype]) rows —
    # checking the ELEMENT types matters because pytree namedtuples
    # (optax's 3-field ScaleByAdamState) also satisfy a bare
    # tuple-of-len-3 probe.
    if isinstance(leaves, (list, tuple)) and (
            not leaves or (isinstance(leaves[0], (list, tuple))
                           and len(leaves[0]) in (2, 3)
                           and all(isinstance(v, (int, float))
                                   for v in leaves[0][:2]))):
        out = []
        for entry in leaves:
            size, itemsize = int(entry[0]), int(entry[1])
            dtype = str(entry[2]) if len(entry) > 2 else f"i{itemsize}"
            if size > 0 and itemsize > 0:
                out.append((size, itemsize, dtype))
        return out
    return leaf_templates(leaves)


def leaf_templates(tree) -> list[tuple[int, int, str]]:
    """Leaf descriptors of a jax pytree (arrays or ShapeDtypeStructs):
    ``[(size_elems, itemsize, dtype), ...]`` in flatten order. Lazy
    jax import — do not call driver-side."""
    import jax
    import numpy as np

    out = []
    for leaf in jax.tree.leaves(tree):
        dt = np.dtype(leaf.dtype)
        size = int(np.prod(leaf.shape)) if getattr(leaf, "shape", ()) else 1
        out.append((max(1, size), int(dt.itemsize), str(dt)))
    return out


def tree_nbytes(tree) -> int:
    """Total bytes of a pytree's leaves from static shape/dtype facts
    (never materializes device arrays — same discipline as
    ``param_sharding._resident_bytes``). Lazy jax import."""
    return sum(s * i for s, i, _ in leaf_templates(tree))


def named_leaf_bytes(tree, limit: int | None = None,
                     ) -> list[tuple[str, int]]:
    """``[(path, nbytes), ...]`` for a pytree's leaves, largest first —
    the forensics view an OOM flight record names. Lazy jax import;
    never raises (an unwalkable tree yields ``[]``)."""
    try:
        import jax
        import numpy as np

        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        out = []
        for path, leaf in flat:
            dt = np.dtype(leaf.dtype)
            size = (int(np.prod(leaf.shape))
                    if getattr(leaf, "shape", ()) else 1)
            name = jax.tree_util.keystr(path) or "<root>"
            out.append((name, max(1, size) * int(dt.itemsize)))
        out.sort(key=lambda p: p[1], reverse=True)
        return out[:limit] if limit else out
    except Exception:  # noqa: BLE001 — forensics must not raise
        return []


# ---------------------------------------------------------------------------
# The analytic footprint model
# ---------------------------------------------------------------------------


def _resident_leaf_bytes(leaves: Sequence[tuple[int, int, str]],
                         sharded: bool, world_size: int) -> int:
    """Per-rank resident bytes of a leaf list: full bytes, or the
    per-leaf ``ceil(size/n)`` shard rows. EXACT against the measured
    layouts: a stacked ``(n, s)`` padded row tree measures
    ``sum(n*s*itemsize) // n == sum(s*itemsize)`` per rank
    (``param_sharding._resident_bytes``), which is precisely this
    sum."""
    if not sharded:
        return sum(size * itemsize for size, itemsize, _ in leaves)
    n = max(1, int(world_size))
    return sum(ceil_shard(size, n) * itemsize
               for size, itemsize, _ in leaves)


def predict_footprint(
    param_templates,
    sync_mode: str = "allreduce",
    world_size: int = 1,
    mesh_shape: tuple[int, int] | None = None,
    opt_templates=None,
    opt_slots: int | None = None,
    int8: bool = False,
    num_segments: int | None = None,
    threshold_bytes: int | None = None,
    grad_itemsize: int | None = None,
    expert_set: Mapping | None = None,
    serving_staging: bool = False,
    capacity: int | None = None,
) -> dict:
    """Price one training configuration's per-rank HBM bytes.

    ``param_templates`` / ``opt_templates`` are leaf descriptor lists
    ``[(size_elems, itemsize[, dtype])]`` or jax pytrees (full-shape
    MONOLITHIC templates in both cases — the model derives each sync
    mode's layout itself). Resident pricing is exact:

    - ``monolithic`` (allreduce): full params + full optimizer state.
    - ``sharded`` (ZeRO-1): full params + per-leaf
      ``ceil(size/n)·itemsize`` optimizer rows (the stacked
      ``(n, ceil(size/n))`` layout of ``optimizer.init_sharded_state``;
      scalar leaves — Adam's count, the int8 salt — ride the
      ``max(1, ·)`` floor).
    - ``fsdp`` (ZeRO-3): per-leaf ceil rows for params AND optimizer
      state. A 2-D ``mesh_shape`` changes nothing resident (the ceil
      identity — see :func:`ceil_shard`), only the transient gather
      legs.

    ``opt_templates`` should be the INNER optimizer's monolithic state
    templates (``jax.eval_shape(inner.init, params)``); per-rank
    sharded state equals the per-leaf ceil of those monolithic leaves
    because the shard-local inner init is shape-congruent to its
    ``(ceil(size/n),)`` param shards. Omitted, optimizer state falls
    back to ``opt_slots`` param-sized copies (default
    ``HOROVOD_HBM_OPT_SLOTS`` = 1 — SGD momentum; Adam wants 2) —
    approximate, flagged ``"opt_exact": False``. ``int8`` adds the
    stochastic-rounding salt (one uint32 per rank in every layout).

    Transient peaks (modeled, not exactness-tested):

    - ``grad_buckets`` — 2× the largest packed vector of a gradient
      bucket under ``threshold_bytes`` (in-flight fused buffer +
      collective output), at ``grad_itemsize`` wire bytes per element
      (int8 wire = 1 — ``param_sharding._wire_itemsize``). The
      ``sharded`` / ``fsdp`` / int8 wires pack whole buckets; the flat
      ``allreduce`` wire packs only a bucket's leaves under
      ``ops.fusion.PACK_CUTOFF_BYTES`` and reduces the others where
      they lie (a bucket the comms planner schedules packs whole too:
      not modeled).
    - ``fsdp_gather`` — the largest per-segment just-in-time gather's
      full-leaf bytes (``segment_byte_runs`` over ``num_segments``,
      the stdlib mirror of ``ops.fusion.segment_leaves``).
    - ``model_axis_gather`` — the 2-D wire's intermediate batch-leg
      block (``batch·ceil(size/(batch·model))`` elements per leaf) for
      the largest segment; 0 on a flat mesh.
    - ``moe_alltoall`` — dispatch + combine buffers from
      ``expert_set`` (``{"bytes": ...}`` explicit, or
      ``tokens_per_rank × hidden × itemsize``), ×2 for the two wires.
    - ``serve_staging`` — a full staged replica during a serving
      hot-swap (``serving_staging=True``).

    Returns a per-kind breakdown with ``resident_total``,
    ``transient_peak`` (the max single transient — they do not
    coexist at peak), ``peak_total``, and — when ``capacity`` (or
    :func:`capacity_bytes`) is known — ``predicted_headroom_ratio``.
    """
    params = _normalize_leaves(param_templates)
    mode = (str(sync_mode) or "allreduce").strip().lower()
    n = max(1, int(world_size))
    if mesh_shape:
        b, m = max(1, int(mesh_shape[0])), max(1, int(mesh_shape[1]))
        if b * m != n:
            n = b * m
    else:
        b, m = n, 1

    # -- resident ----------------------------------------------------------
    params_sharded = mode == "fsdp"
    opt_sharded = mode in ("sharded", "fsdp")
    resident_params = _resident_leaf_bytes(params, params_sharded, n)
    opt_exact = opt_templates is not None
    if opt_exact:
        opt_leaves = _normalize_leaves(opt_templates)
        resident_opt = _resident_leaf_bytes(opt_leaves, opt_sharded, n)
    else:
        slots = (max(0, int(opt_slots)) if opt_slots is not None
                 else max(0, get_int("HOROVOD_HBM_OPT_SLOTS", 1)))
        resident_opt = slots * _resident_leaf_bytes(params, opt_sharded, n)
    if int8:
        resident_opt += 4  # the stochastic-rounding salt: a () uint32
        # monolithic, one row of a (n,) uint32 stacked — 4 bytes/rank
        # either way

    # -- transients --------------------------------------------------------
    k = max(1, int(num_segments)) if num_segments else 1
    if threshold_bytes is None:
        threshold_bytes = get_int("HOROVOD_FUSION_THRESHOLD",
                                  64 * 1024 * 1024)
    wire = [(size * (int(grad_itemsize) if grad_itemsize
                     else (1 if int8 else itemsize)), dtype)
            for size, itemsize, dtype in params]
    packed = [bucket for run in segment_byte_runs(wire, k)
              for bucket in bucket_byte_runs(run, int(threshold_bytes))]
    if mode == "allreduce" and not int8:
        from .ops.fusion import PACK_CUTOFF_BYTES

        packed = [[nbytes for nbytes in bucket if nbytes < PACK_CUTOFF_BYTES]
                  for bucket in packed]
        # one small leaf is reduced as itself too: no vector
        packed = [small for small in packed if len(small) > 1]
    grad_buckets = 2 * max(map(sum, packed), default=0)

    fsdp_gather = 0
    model_axis_gather = 0
    if mode == "fsdp" and params:
        runs = segment_byte_runs(
            [(size * itemsize, dtype) for size, itemsize, dtype in params],
            k)
        fsdp_gather = max((sum(nb for nb, _ in run) for run in runs),
                          default=0)
        if m > 1:
            # The batch-leg gather materializes each leaf's model block
            # (batch rows of the resident shard) before the model-axis
            # allgather completes it — price the largest segment's
            # blocks. Segments index the same contiguous runs, so walk
            # leaves through the byte-midpoint rule directly.
            by_leaf = segment_byte_runs(
                [(size * itemsize, f"{i}") for i, (size, itemsize, _)
                 in enumerate(params)], k)
            best = 0
            for run in by_leaf:
                block = sum(
                    b * ceil_shard(params[int(tag)][0], n)
                    * params[int(tag)][1] for _, tag in run)
                best = max(best, block)
            model_axis_gather = best

    moe_alltoall = 0
    if expert_set:
        try:
            explicit = expert_set.get("bytes")
            if explicit is not None:
                moe_alltoall = 2 * int(explicit)
            else:
                tokens = int(expert_set.get("tokens_per_rank", 0))
                hidden = int(expert_set.get("hidden", 0))
                itemsize = int(expert_set.get("itemsize", 4))
                moe_alltoall = 2 * tokens * hidden * itemsize
        except (TypeError, ValueError):
            moe_alltoall = 0

    serve_staging = (sum(size * itemsize for size, itemsize, _ in params)
                     if serving_staging else 0)

    transient = {
        "grad_buckets": int(grad_buckets),
        "fsdp_gather": int(fsdp_gather),
        "model_axis_gather": int(model_axis_gather),
        "moe_alltoall": int(moe_alltoall),
        "serve_staging": int(serve_staging),
    }
    resident = {"params": int(resident_params),
                "opt_state": int(resident_opt)}
    resident_total = sum(resident.values())
    transient_peak = max(transient.values(), default=0)
    out = {
        "sync_mode": mode,
        "world_size": n,
        "mesh_shape": [b, m] if mesh_shape else None,
        "num_segments": k,
        "int8": bool(int8),
        "opt_exact": bool(opt_exact),
        "resident": resident,
        "transient": transient,
        "resident_total": int(resident_total),
        "transient_peak": int(transient_peak),
        "peak_total": int(resident_total + transient_peak),
    }
    cap = capacity if capacity is not None else capacity_bytes()
    if cap:
        out["capacity_bytes"] = int(cap)
        out["predicted_headroom_ratio"] = round(
            max(0.0, 1.0 - out["peak_total"] / float(cap)), 4)
    return out


def footprint_of(optimizer, params, world_size: int | None = None,
                 sync_mode: str | None = None,
                 mesh_shape: tuple[int, int] | None = None,
                 num_segments: int | None = None,
                 **kwargs) -> dict:
    """:func:`predict_footprint` for a live ``(optimizer, params)``
    pair: derives the inner optimizer's monolithic state templates via
    ``jax.eval_shape`` (exact, shape-only — nothing allocates), the
    int8 flag and wire itemsize from the compression, and the sync
    mode / segment count from the reduce spec and live fusion config.
    jax-side only."""
    import jax

    from .optimizer import reduce_spec_of
    from .parallel.param_sharding import ShardedParams, _wire_itemsize

    spec = reduce_spec_of(optimizer)
    if isinstance(params, ShardedParams):
        if world_size is None:
            world_size = params.world_size
        params = params.template_tree()
    if world_size is None:
        from . import basics

        world_size = basics.size()
    if sync_mode is None:
        sync_mode = spec.sync_mode
    if num_segments is None:
        try:
            from .ops.fusion import fsdp_segments

            num_segments = fsdp_segments()
        except Exception:  # noqa: BLE001 — default to unsegmented
            num_segments = 1
    int8 = getattr(spec.compression, "marker", None) == "int8"
    param_leaves = leaf_templates(params)
    opt_templates = jax.eval_shape(spec.inner.init, params)
    grad_itemsize = None
    if param_leaves:
        grad_itemsize = _wire_itemsize(
            spec.compression, param_leaves[0][2])
    return predict_footprint(
        param_leaves, sync_mode=sync_mode, world_size=world_size,
        mesh_shape=mesh_shape, opt_templates=opt_templates, int8=int8,
        num_segments=num_segments, grad_itemsize=grad_itemsize, **kwargs)


# ---------------------------------------------------------------------------
# Live accounting
# ---------------------------------------------------------------------------


class MemoryObservatory:
    """The per-process observatory: exact resident bytes by kind (noted
    by the call sites that materialize state, or polled from registered
    byte suppliers), per-phase watermarks driven by the tracing plane's
    span exits, the last predicted footprint, and the forensics leaf
    table."""

    def __init__(self):
        self._lock = threading.Lock()
        self._resident: dict[str, int] = {}
        self._suppliers: dict[str, Callable[[], int]] = {}
        self._top_leaves: dict[str, list[tuple[str, int]]] = {}
        self._watermarks: dict[str, int] = {}
        self._peak = 0
        self._journaled_peak = 0
        self._predicted: dict | None = None
        self._layout: list[tuple[int, int, str]] = []
        self._phase_notes = 0
        self._oom_dumps = 0

    # -- intake ---------------------------------------------------------------

    def note_resident(self, kind: str, nbytes: int,
                      top_leaves: Sequence[tuple[str, int]] | None = None,
                      ) -> None:
        """Record the exact resident bytes of one kind (a call site
        that just materialized or resized that state). ``top_leaves``
        (``[(path, nbytes)]``, largest first) feeds the OOM forensics
        table. Negative/non-finite values are rejected; never
        raises."""
        try:
            nbytes = int(nbytes)
        except (TypeError, ValueError):
            return
        if nbytes < 0:
            return
        with self._lock:
            self._resident[str(kind)] = nbytes
            if top_leaves:
                self._top_leaves[str(kind)] = [
                    (str(p), int(b)) for p, b in top_leaves][:top_n()]
        self._export_gauges()

    def register_supplier(self, kind: str, fn: Callable[[], int]) -> None:
        """Register a live byte supplier for a kind whose size changes
        outside any noting call site (peer replica pool, executable
        cache). Polled — cheaply, and exception-guarded — on every
        measurement."""
        with self._lock:
            self._suppliers[str(kind)] = fn

    def note_layout(self, leaves) -> None:
        """Remember the model's parameter leaf layout
        ``[(size_elems, itemsize[, dtype])]`` — noted at trace time by
        the fusion pass alongside the comms model's byte layout. The
        largest layout seen wins (segmented flushes note subsets).
        This is the autotune memory guard's pricing input."""
        leaves = _normalize_leaves(leaves)
        if not leaves:
            return
        with self._lock:
            if sum(s * i for s, i, _ in leaves) >= sum(
                    s * i for s, i, _ in self._layout):
                self._layout = leaves

    def layout(self) -> list[tuple[int, int, str]]:
        with self._lock:
            return list(self._layout)

    def note_predicted(self, footprint: Mapping | None) -> None:
        """Pin the model's current prediction (a
        :func:`predict_footprint` result) — the residual gauge compares
        every subsequent measurement against it."""
        with self._lock:
            self._predicted = dict(footprint) if footprint else None
        self._export_gauges()

    # -- measurement ----------------------------------------------------------

    def measured_resident(self) -> dict[str, int]:
        """Per-kind resident bytes: the noted cells, one guarded poll of
        every registered supplier, and — where the backend counts them —
        the loaded programs' temporaries on the fullest device."""
        with self._lock:
            out = dict(self._resident)
            suppliers = dict(self._suppliers)
        for kind, fn in suppliers.items():
            try:
                nbytes = int(fn())
                if nbytes >= 0:
                    out[kind] = nbytes
            except Exception:  # noqa: BLE001 — a dead supplier must
                pass  # not break the measurement
        stats = device_memory_stats()
        if stats and "bytes_reserved" in stats:
            out[PROGRAM_TEMPORARIES] = int(stats["bytes_reserved"])
        return out

    def resident_total(self) -> int:
        return sum(self.measured_resident().values())

    def predicted(self) -> dict | None:
        with self._lock:
            return dict(self._predicted) if self._predicted else None

    def residual_bytes(self) -> int | None:
        """Predicted − measured over the MODEL kinds (params +
        opt_state) — the drift alarm. None until both sides exist."""
        pred = self.predicted()
        if not pred:
            return None
        measured = self.measured_resident()
        model_measured = sum(measured.get(k, 0) for k in MODEL_KINDS)
        if model_measured <= 0:
            return None
        try:
            return int(pred["resident_total"]) - model_measured
        except (KeyError, TypeError, ValueError):
            return None

    def headroom_ratio(self) -> float | None:
        """``1 − resident_total/capacity`` clamped to [0, 1], or None
        when no capacity source exists (the gauge then reads its
        zero-materialized 0 = unknown)."""
        cap = capacity_bytes()
        if not cap:
            return None
        return max(0.0, min(1.0, 1.0 - self.resident_total() / float(cap)))

    def note_phase(self, name: str, cat: str | None = None) -> None:
        """Watermark hook, called where the chip holds what a step
        holds — the close of a SYNCED step scope
        (``tracing.StepTracer._end_step``; a factory step syncs every
        50th call) — and by ``tracing.span.__exit__`` for spans outside
        any step: fold what is resident into the phase's watermark, the
        larger of the noted total and what the backend says the fullest
        device holds, buffers plus the loaded programs' temporaries (now
        and at peak; :func:`device_taken_bytes`). A new process-lifetime
        peak ≥5% above the last journaled one emits an ``hbm_watermark``
        journal event (latched — growth bursts journal once, steady
        state never). It asks every local device, so it is never called
        on an un-synced step. Never raises."""
        try:
            phase = str(name) if str(name) in PHASES else (
                "collective" if cat == "collective" else
                "step" if cat == "step" else "other")
            total = self.resident_total()
            taken = device_taken_bytes()
            if taken:
                total = max(total, *taken)
            journal = False
            with self._lock:
                self._phase_notes += 1
                if total > self._watermarks.get(phase, 0):
                    self._watermarks[phase] = total
                if total > self._peak:
                    self._peak = total
                    if total > self._journaled_peak * 1.05:
                        self._journaled_peak = total
                        journal = True
            try:
                from . import metrics

                metrics.HBM_WATERMARK.set(
                    self._watermarks.get(phase, total), phase=phase)
                if journal:
                    metrics.event("hbm_watermark", phase=phase,
                                  bytes=total)
            except Exception:  # noqa: BLE001 — gauges are advisory
                pass
        except Exception:  # noqa: BLE001 — the span exit must not fail
            pass

    def watermarks(self) -> dict[str, int]:
        with self._lock:
            return dict(self._watermarks)

    def peak_bytes(self) -> int:
        with self._lock:
            return self._peak

    def top_leaves(self, limit: int | None = None) -> list[dict]:
        """The forensics table: the largest noted resident leaves
        across every kind, ``[{"kind", "leaf", "bytes"}, ...]``
        largest first."""
        with self._lock:
            rows = [
                {"kind": kind, "leaf": path, "bytes": nbytes}
                for kind, entries in self._top_leaves.items()
                for path, nbytes in entries
            ]
        rows.sort(key=lambda r: r["bytes"], reverse=True)
        return rows[:limit or top_n()]

    # -- export ---------------------------------------------------------------

    def _export_gauges(self) -> None:
        """Mirror the observatory into the scrape gauges
        (best-effort)."""
        try:
            from . import metrics

            for kind, nbytes in self.measured_resident().items():
                metrics.HBM_BYTES.set(nbytes, kind=kind)
            residual = self.residual_bytes()
            if residual is not None:
                metrics.HBM_RESIDUAL.set(residual)
            ratio = self.headroom_ratio()
            if ratio is not None:
                metrics.HBM_HEADROOM.set(ratio)
        except Exception:  # noqa: BLE001 — gauges are advisory
            pass

    def payload(self) -> dict:
        """The per-rank wire format piggybacked on heartbeats and
        merged by ``GET /memory``. A process that has noted nothing
        resident serves an explicit ``insufficient_samples`` status —
        never an error."""
        measured = self.measured_resident()
        status = "ok" if measured else "insufficient_samples"
        ratio = self.headroom_ratio()
        residual = self.residual_bytes()
        pred = self.predicted()
        with self._lock:
            watermarks = dict(self._watermarks)
            peak = self._peak
        return {
            "rank": _rank(),
            "host": _host(),
            "t": time.time(),
            "status": status,
            "resident": {k: int(v) for k, v in measured.items()},
            "resident_total": int(sum(measured.values())),
            "watermarks": {k: int(v) for k, v in watermarks.items()},
            "peak_bytes": int(peak),
            "predicted": pred,
            "residual_bytes": residual,
            "headroom_ratio": (round(ratio, 4)
                               if ratio is not None else None),
            "capacity_bytes": capacity_bytes(),
            "device": device_memory_stats(),
        }

    def summary(self) -> dict:
        """``profiler.summary()["memory"]``: the process-local view."""
        p = self.payload()
        return {
            "status": p["status"],
            "resident": p["resident"],
            "resident_total": p["resident_total"],
            "watermarks": p["watermarks"],
            "peak_bytes": p["peak_bytes"],
            "predicted": p["predicted"],
            "residual_bytes": p["residual_bytes"],
            "headroom_ratio": p["headroom_ratio"],
            "capacity_bytes": p["capacity_bytes"],
            "top_leaves": self.top_leaves(),
        }

    def flight_summary(self) -> dict | None:
        """The compact section every flight record carries (like
        ``peercheck.pool_summary``): per-kind bytes + watermarks +
        the drift. None when nothing was ever measured (the dump then
        omits the section rather than carrying an empty one)."""
        measured = self.measured_resident()
        if not measured and not self.peak_bytes():
            return None
        return {
            "resident": {k: int(v) for k, v in measured.items()},
            "resident_total": int(sum(measured.values())),
            "watermarks": self.watermarks(),
            "peak_bytes": self.peak_bytes(),
            "residual_bytes": self.residual_bytes(),
        }


# ---------------------------------------------------------------------------
# Singleton + module facade
# ---------------------------------------------------------------------------

_lock = threading.Lock()
_observatory: MemoryObservatory | None = None


def get_observatory() -> MemoryObservatory:
    global _observatory
    with _lock:
        if _observatory is None:
            _observatory = MemoryObservatory()
        return _observatory


def reset_for_testing() -> None:
    """Fresh observatory (``comms_model.reset_for_testing``
    semantics)."""
    global _observatory
    with _lock:
        _observatory = None


def note_resident(kind: str, nbytes: int,
                  top_leaves: Sequence[tuple[str, int]] | None = None,
                  ) -> None:
    get_observatory().note_resident(kind, nbytes, top_leaves)


def note_phase(name: str, cat: str | None = None) -> None:
    get_observatory().note_phase(name, cat)


def summary() -> dict:
    return get_observatory().summary()


def flight_summary() -> dict | None:
    return get_observatory().flight_summary()


# ---------------------------------------------------------------------------
# OOM forensics (the factory step boundary's consumer)
# ---------------------------------------------------------------------------

#: Substrings that identify an out-of-device-memory failure across the
#: backends (XLA's RESOURCE_EXHAUSTED grammar, PJRT allocator messages,
#: and this framework's own injected-pressure marker). Deliberately no
#: bare "oom" — it matches innocent words.
_OOM_MARKERS = ("resource_exhausted", "resource exhausted",
                "out of memory", "out_of_memory", "hbm oom",
                "memory.pressure", "failed to allocate")


def is_oom_error(exc: BaseException) -> bool:
    """Does this exception look like device memory exhaustion? String
    match by design: XLA surfaces OOM as ``XlaRuntimeError`` whose type
    carries no status code portably across jaxlib versions."""
    try:
        text = f"{type(exc).__name__}: {exc}".lower()
    except Exception:  # noqa: BLE001 — an unprintable exception
        return False
    return any(marker in text for marker in _OOM_MARKERS)


def oom_flight_fields(exc: BaseException | None = None) -> dict:
    """The memory forensics fields an OOM flight record carries: the
    top-N resident leaves, the per-kind breakdown, and the
    predicted-vs-measured delta. Never raises."""
    obs = get_observatory()
    fields: dict[str, Any] = {
        "memory_top_leaves": obs.top_leaves(),
        "memory_resident": {k: int(v)
                            for k, v in obs.measured_resident().items()},
        "memory_peak_bytes": obs.peak_bytes(),
    }
    residual = obs.residual_bytes()
    if residual is not None:
        fields["memory_residual_bytes"] = residual
    pred = obs.predicted()
    if pred:
        fields["memory_predicted_total"] = pred.get("resident_total")
    cap = capacity_bytes()
    if cap:
        fields["memory_capacity_bytes"] = cap
    if exc is not None:
        fields["error"] = str(exc)[:500]
    return fields


def dump_oom_record(exc: BaseException, generation: int | None = None,
                    **extra) -> None:
    """Dump the OOM flight record (reason ``oom``) naming the top
    resident leaves and the model drift — the step boundary calls this
    before re-raising. Never raises."""
    try:
        get_observatory()._oom_dumps += 1
        from . import tracing

        tracing.dump_flight_record("oom", generation=generation,
                                   **oom_flight_fields(exc), **extra)
    except Exception:  # noqa: BLE001 — forensics must not mask the OOM
        pass


# ---------------------------------------------------------------------------
# Autotune consumer: the memory guard
# ---------------------------------------------------------------------------


def memory_guard_enabled() -> bool:
    """The autotune memory guard (``HOROVOD_AUTOTUNE_MEMORY_GUARD=1``):
    model-guided pruning additionally rejects (sync_mode, segments,
    mesh-shape) candidates whose predicted footprint exceeds the
    available headroom. Off by default — with the knob unset autotune
    decisions are bit-for-bit unchanged — and inert even when armed
    until a capacity source exists AND a traced flush has noted the
    parameter layout (a cold process prunes nothing)."""
    return os.environ.get(
        "HOROVOD_AUTOTUNE_MEMORY_GUARD", "").strip() == "1"


def candidate_footprint_bytes(sync_mode: str, num_segments: int = 1,
                              mesh_shape: tuple[int, int] | None = None,
                              world_size: int | None = None,
                              observatory: MemoryObservatory | None = None,
                              ) -> int | None:
    """Predicted per-rank peak bytes for one autotune candidate, priced
    from the noted parameter layout (pure and deterministic: the same
    layout + env yields the same number on every rank — the same
    rank-identity contract as ``comms_model.prune_candidates``). None
    when no layout was noted yet."""
    obs = observatory or get_observatory()
    layout = obs.layout()
    if not layout:
        return None
    if world_size is None:
        try:
            world_size = int(os.environ.get("HOROVOD_SIZE", "") or 0)
        except ValueError:
            world_size = 0
        if not world_size:
            try:
                import jax

                world_size = jax.device_count()
            except Exception:  # noqa: BLE001 — driver-side: unknown
                return None
    fp = predict_footprint(layout, sync_mode=sync_mode,
                           world_size=world_size, mesh_shape=mesh_shape,
                           num_segments=num_segments)
    return int(fp["peak_total"])


def check_candidate(sync_mode: str, num_segments: int = 1,
                    mesh_shape: tuple[int, int] | None = None,
                    world_size: int | None = None) -> None:
    """Raise :class:`~horovod_tpu.exceptions.MemoryBudgetExceededError`
    (a ``SyncModeIneligibleError`` — ``tune_step_sync_mode`` skips it
    rank-identically, like the fsdp guards) when the candidate's
    predicted footprint exceeds the device capacity. Inert — returns
    None — when the guard is off, no layout is noted, or no capacity
    source exists."""
    if not memory_guard_enabled():
        return
    cap = capacity_bytes()
    if not cap:
        return
    predicted = candidate_footprint_bytes(
        sync_mode, num_segments=num_segments, mesh_shape=mesh_shape,
        world_size=world_size)
    if predicted is None:
        return
    if predicted > cap:
        from .exceptions import MemoryBudgetExceededError

        raise MemoryBudgetExceededError(
            f"autotune memory guard: sync_mode={sync_mode!r} "
            f"segments={num_segments} mesh_shape={mesh_shape} predicts "
            f"{predicted} bytes/rank against {cap} bytes of device "
            "capacity (HOROVOD_HBM_BYTES_PER_DEVICE / backend limit); "
            "candidate skipped rank-identically")


def filter_candidates(candidates: Sequence[Any],
                      world_size: int | None = None) -> dict:
    """Memory-guard filter over an autotune grid (the model-guided
    pruning's second stage): drop candidates whose predicted peak
    exceeds capacity. Returns ``{"kept", "pruned", "bytes"}`` with
    ``bytes`` aligned to ``candidates`` (None = unpriced). Never
    prunes the whole grid; pure and deterministic like
    ``comms_model.prune_candidates`` (rank 0's kept list is broadcast
    by the caller)."""
    from .comms_model import candidate_axes

    if not memory_guard_enabled():
        return {"kept": list(candidates), "pruned": [], "bytes": []}
    cap = capacity_bytes()
    priced: list[int | None] = []
    for cand in candidates:
        _, segments, sync_mode, _ = candidate_axes(cand)
        priced.append(candidate_footprint_bytes(
            sync_mode, num_segments=segments, world_size=world_size))
    if not cap:
        return {"kept": list(candidates), "pruned": [], "bytes": priced}
    kept, pruned = [], []
    for cand, nbytes in zip(candidates, priced):
        if nbytes is not None and nbytes > cap:
            pruned.append(cand)
        else:
            kept.append(cand)
    if not kept:  # a budget below every candidate cannot rank anything
        return {"kept": list(candidates), "pruned": [], "bytes": priced}
    return {"kept": kept, "pruned": pruned, "bytes": priced}


# ---------------------------------------------------------------------------
# Scheduler consumer: the advisory admission check
# ---------------------------------------------------------------------------


def admission_check(predicted_bytes: int | None,
                    capacity: int | None) -> dict | None:
    """Advisory multi-tenant admission verdict: compare a job's
    predicted per-rank footprint against the host set's advertised
    per-device HBM. Returns the ``admission_memory_risk`` journal
    fields when the prediction EXCEEDS capacity, None otherwise (or
    when either side is unknown). Never changes a scheduling decision
    — the scheduler journals and grants regardless."""
    try:
        predicted_bytes = (int(predicted_bytes)
                           if predicted_bytes is not None else None)
        capacity = int(capacity) if capacity is not None else None
    except (TypeError, ValueError):
        return None
    if not predicted_bytes or not capacity or predicted_bytes <= 0 \
            or capacity <= 0:
        return None
    if predicted_bytes <= capacity:
        return None
    return {
        "predicted_bytes": predicted_bytes,
        "capacity_bytes": capacity,
        "deficit_bytes": predicted_bytes - capacity,
        "ratio": round(predicted_bytes / capacity, 4),
    }


# ---------------------------------------------------------------------------
# Cluster merge (driver-side; the KV server's GET /memory)
# ---------------------------------------------------------------------------


def _clean_int(value, floor: int = 0) -> int:
    try:
        f = float(value)
        if not math.isfinite(f):
            return floor  # NaN/Infinity would poison the /memory JSON
        v = int(f)
    except (TypeError, ValueError, OverflowError):
        return floor
    return v if v >= floor else floor


def merge_payloads(payloads: Mapping[str, Mapping]) -> dict:
    """Cluster-merged view over per-rank
    :meth:`MemoryObservatory.payload` dicts (keyed by host, as the
    heartbeat scope stores them). Malformed payloads are skipped — one
    broken worker must not break the merge. Cluster section: per-kind
    byte SUMS (the pod's total resident footprint), per-phase watermark
    MAXES (the worst rank bounds the pod), the minimum headroom ratio
    (the rank closest to OOM is the one that matters), and the largest
    absolute residual (the worst model drift). A cluster where nothing
    measured yet reports ``status: insufficient_samples`` — never an
    error."""
    ranks: dict[str, dict] = {}
    kind_totals: dict[str, int] = {}
    watermark_max: dict[str, int] = {}
    headroom_min: float | None = None
    residual_worst: int | None = None
    for host, payload in (payloads or {}).items():
        if not isinstance(payload, Mapping):
            continue
        rank = str(payload.get("rank", "?"))
        hostname = str(payload.get("host", host))
        if rank in ranks:
            rank = f"{rank}@{hostname}"  # same collision rule as /comms
        resident_raw = payload.get("resident")
        resident = {}
        if isinstance(resident_raw, Mapping):
            resident = {str(k): _clean_int(v)
                        for k, v in resident_raw.items()}
        watermarks_raw = payload.get("watermarks")
        watermarks = {}
        if isinstance(watermarks_raw, Mapping):
            watermarks = {str(k): _clean_int(v)
                          for k, v in watermarks_raw.items()}
        try:
            ratio = payload.get("headroom_ratio")
            ratio = float(ratio) if ratio is not None else None
            if ratio is not None and not math.isfinite(ratio):
                ratio = None
        except (TypeError, ValueError):
            ratio = None
        residual = payload.get("residual_bytes")
        try:
            residual = int(residual) if residual is not None else None
        except (TypeError, ValueError):
            residual = None
        ranks[rank] = {
            "host": hostname,
            "status": str(payload.get("status", "insufficient_samples")),
            "resident": resident,
            "resident_total": _clean_int(payload.get("resident_total",
                                                     sum(resident.values()))),
            "watermarks": watermarks,
            "peak_bytes": _clean_int(payload.get("peak_bytes", 0)),
            "headroom_ratio": (round(ratio, 4)
                               if ratio is not None else None),
            "residual_bytes": residual,
            "capacity_bytes": (_clean_int(payload.get("capacity_bytes"))
                               or None),
        }
        for kind, nbytes in resident.items():
            kind_totals[kind] = kind_totals.get(kind, 0) + nbytes
        for phase, nbytes in watermarks.items():
            watermark_max[phase] = max(watermark_max.get(phase, 0), nbytes)
        if ratio is not None:
            headroom_min = (ratio if headroom_min is None
                            else min(headroom_min, ratio))
        if residual is not None and (
                residual_worst is None
                or abs(residual) > abs(residual_worst)):
            residual_worst = residual
    status = ("ok" if any(r["status"] == "ok" for r in ranks.values())
              else "insufficient_samples")
    return {
        "status": status,
        "ranks": ranks,
        "cluster": {
            "resident_bytes": kind_totals,
            "resident_total": sum(kind_totals.values()),
            "watermark_bytes": watermark_max,
            "headroom_ratio_min": (round(headroom_min, 4)
                                   if headroom_min is not None else None),
            "residual_bytes_worst": residual_worst,
        },
    }
