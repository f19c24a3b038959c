"""ICI-topology-aware rank assignment.

The reference assigns ranks in host:slot order
(``horovod/runner/common/util/hosts.py — get_host_assignments``). On TPU the
equivalent must be topology-aware: ranks follow the ICI torus coordinates so
that (a) neighboring ranks are ICI neighbors (ring collectives ride ICI links,
not DCN) and (b) replica groups formed from contiguous rank ranges are
ICI-contiguous sub-tori.

This module sorts ``jax.devices()`` into that canonical order and derives the
Horovod world facts (rank / local_rank / cross_rank) from it:

- ``rank``        — index of a device in the canonical topology order.
- ``local_rank``  — index among devices on the same host (process).
- ``cross_rank``  — host index (DCN coordinate), matching the reference's
                    cross-communicator used for hierarchical allreduce.
"""

from __future__ import annotations

import os
from typing import Any, Sequence

import numpy as np

#: Per-link-class α–β seeds ``{class: (alpha_s, beta_s_per_byte)}`` — the
#: comms planner's static crossover inputs before the online model has a
#: ready fit for a key (``ops/comms_planner.py``). Deliberately coarse
#: (ICI ≈ tens of GB/s at µs launch, DCN ≈ single-digit GB/s at tens of
#: µs): the planner only compares candidates against each other, so the
#: RATIO between classes is what the crossover depends on, and the live
#: α–β fit replaces these the moment it is ready.
LINK_CLASS_SEEDS: dict[str, tuple[float, float]] = {
    "ici": (2.0e-6, 1.0 / 45e9),
    "dcn": (50.0e-6, 1.0 / 2.5e9),
    # A flat alltoall on a multi-island fabric is the one wire whose
    # traffic is genuinely part-ICI part-DCN in a single op (every rank
    # pair exchanges a distinct chunk, so no single slowest link carries
    # the whole payload the way a ring hop does). Its seed row sits
    # between the two so the planner's flat-vs-two_level crossover for
    # ``alltoall`` has somewhere honest to price the flat candidate.
    "mixed": (26.0e-6, 1.0 / 4.7e9),
    "self": (0.0, 0.0),
}


def link_seed(link_class: str) -> tuple[float, float]:
    """The seed ``(alpha_s, beta_s_per_byte)`` for a link class (unknown
    classes price as DCN — the conservative choice)."""
    return LINK_CLASS_SEEDS.get(str(link_class), LINK_CLASS_SEEDS["dcn"])


def parse_link_class_map(spec: str) -> list[list[int]] | None:
    """Parse the ``HOROVOD_LINK_CLASS_MAP`` fabric declaration.

    Grammar (docs/perf.md "Algorithm selection"): semicolon-separated ICI
    islands, each a comma-separated list of global ranks and/or ``a-b``
    ranges — ``"0-3;4-7"`` declares two 4-rank slices whose intra-island
    links are ICI and whose cross-island links are DCN. The override
    exists so CPU tests can emulate a multi-slice fabric,
    and so multi-slice worlds whose devices expose no ``slice_index``
    can declare theirs. Returns None for an empty/invalid spec (invalid
    maps must never take down init — the topology falls back to the
    device-derived classification).
    """
    spec = (spec or "").strip()
    if not spec:
        return None
    islands: list[list[int]] = []
    seen: set[int] = set()
    try:
        for part in spec.split(";"):
            ranks: list[int] = []
            for item in part.split(","):
                item = item.strip()
                if not item:
                    continue
                if "-" in item:
                    lo, hi = item.split("-", 1)
                    ranks.extend(range(int(lo), int(hi) + 1))
                else:
                    ranks.append(int(item))
            if not ranks:
                return None
            if seen & set(ranks):
                return None  # overlapping islands: malformed
            seen.update(ranks)
            islands.append(sorted(ranks))
    except ValueError:
        return None
    return islands if islands else None


def _device_sort_key(device: Any):
    """Sort key: (slice, ICI coords z-major, core) with host as tiebreak.

    TPU devices expose ``coords`` (x, y, z on the ICI torus) and
    ``slice_index`` for multi-slice jobs. CPU/other devices fall back to
    ``(process_index, id)`` which preserves JAX's default stable order.
    """
    slice_index = getattr(device, "slice_index", 0) or 0
    coords = getattr(device, "coords", None)
    core = getattr(device, "core_on_chip", 0) or 0
    if coords is not None:
        # z-major ordering keeps x-neighbors adjacent in rank space; on a
        # torus this makes [r, r+1] pairs ICI-linked along the minor axis.
        x, y, z = (list(coords) + [0, 0, 0])[:3]
        return (slice_index, z, y, x, core, device.process_index, device.id)
    return (slice_index, device.process_index, device.id)


def sorted_devices(devices: Sequence[Any] | None = None) -> list[Any]:
    """All devices in canonical ICI-topology order (the rank order)."""
    import jax

    if devices is None:
        devices = jax.devices()
    return sorted(devices, key=_device_sort_key)


class Topology:
    """World facts derived from the device list.

    One instance is built at ``init()`` and owned by ``basics``. It answers
    every rank/size query and provides the canonical device ordering used to
    build meshes (so mesh axis order == rank order == ICI order).
    """

    def __init__(self, devices: Sequence[Any] | None = None):
        import jax

        self.devices: list[Any] = sorted_devices(devices)
        self.num_devices: int = len(self.devices)
        self.process_index: int = jax.process_index()
        self.process_count: int = jax.process_count()

        # Host (process) grouping: local == same process in JAX's model,
        # which on TPU VMs == same host.
        self._local_devices = [
            d for d in self.devices if d.process_index == self.process_index
        ]
        self._device_rank = {id(d): i for i, d in enumerate(self.devices)}

        # Ranks grouped by process, in process order — the cross structure.
        procs = sorted({d.process_index for d in self.devices})
        self._proc_order = {p: i for i, p in enumerate(procs)}

        # Per-rank local/cross index tables. The canonical ICI order does NOT
        # group a host's chips contiguously (a host's 2x2 block interleaves
        # with its torus neighbors), so local_rank(global_rank) must be a
        # table lookup, not arithmetic.
        seen_per_proc: dict[int, int] = {}
        self.local_rank_table: list[int] = []
        self.cross_rank_table: list[int] = []
        for d in self.devices:
            idx = seen_per_proc.get(d.process_index, 0)
            self.local_rank_table.append(idx)
            seen_per_proc[d.process_index] = idx + 1
            self.cross_rank_table.append(self._proc_order[d.process_index])

    # -- Horovod world facts -------------------------------------------------

    def rank_of(self, device: Any) -> int:
        return self._device_rank[id(device)]

    @property
    def local_devices(self) -> list[Any]:
        return self._local_devices

    @property
    def size(self) -> int:
        """Total ranks == total devices (one rank per chip, as in Horovod)."""
        return self.num_devices

    @property
    def local_size(self) -> int:
        return len(self._local_devices)

    @property
    def rank(self) -> int:
        """The first local device's global rank (controller-process view).

        In single-controller SPMD there is no single 'my rank'; per-device
        rank comes from ``lax.axis_index`` inside the compiled step. This
        process-level value exists so rank-0-only idioms (checkpointing,
        logging) from reference-style scripts keep working: it is 0 exactly
        on the process that owns the rank-0 device.
        """
        if not self._local_devices:
            return 0
        return self.rank_of(self._local_devices[0])

    @property
    def local_rank(self) -> int:
        """Process-level view: 0 (the first local device's local index)."""
        return 0

    @property
    def cross_rank(self) -> int:
        return self._proc_order.get(self.process_index, 0)

    @property
    def cross_size(self) -> int:
        return len(self._proc_order)

    def device_coords(self, device: Any) -> tuple | None:
        coords = getattr(device, "coords", None)
        return tuple(coords) if coords is not None else None

    # -- link classification (the comms model's topology leg) ----------------

    def link_class_map(self) -> list[list[int]] | None:
        """The ``HOROVOD_LINK_CLASS_MAP`` islands covering THIS world, or
        None (no/invalid override, or one that names ranks outside the
        world). Read dynamically — tests declare an emulated fabric
        after init — and parse-cached per distinct env value."""
        raw = os.environ.get("HOROVOD_LINK_CLASS_MAP", "")
        cached = getattr(self, "_lcm_cache", None)
        if cached is not None and cached[0] == raw:
            return cached[1]
        islands = parse_link_class_map(raw)
        if islands is not None:
            covered = {r for isl in islands for r in isl}
            if not covered <= set(range(self.num_devices)):
                islands = None  # names ranks this world does not have
        self._lcm_cache = (raw, islands)
        return islands

    def ici_islands(self) -> list[list[int]]:
        """Ranks grouped into ICI islands — the comms planner's
        ``two_level`` grouping (intra-island legs ride ICI, the
        cross-island leg rides DCN). The ``HOROVOD_LINK_CLASS_MAP``
        override wins (ranks it omits become single-rank islands);
        otherwise devices group by slice (coordinate-bearing) or by
        process — the same facts :meth:`link_class` classifies by, so
        the two views can never disagree about which pairs are ICI."""
        mapped = self.link_class_map()
        if mapped is not None:
            covered = {r for isl in mapped for r in isl}
            extras = [[r] for r in range(self.num_devices)
                      if r not in covered]
            return [list(isl) for isl in mapped] + extras
        by_key: dict[Any, list[int]] = {}
        for i, d in enumerate(self.devices):
            coords = self.device_coords(d)
            if coords is not None:
                key = ("slice", getattr(d, "slice_index", 0) or 0)
            else:
                key = ("proc", d.process_index)
            by_key.setdefault(key, []).append(i)
        return [sorted(v) for _, v in sorted(by_key.items())]

    def link_class(self, rank_a: int, rank_b: int) -> str:
        """Classify the rank-pair link: ``"self"`` (same device),
        ``"ici"`` (torus-connected — same host, or coordinate-bearing
        devices on the same slice: on TPU pods ICI spans hosts within a
        slice), or ``"dcn"`` (cross-slice, or cross-host without
        coordinates — the data-center network). This is the
        ``link_class`` label vocabulary of the α–β cost model
        (``horovod_tpu.comms_model``)."""
        if rank_a == rank_b:
            return "self"
        mapped = self.link_class_map()
        if mapped is not None:
            for island in mapped:
                if rank_a in island:
                    return "ici" if rank_b in island else "dcn"
            return "dcn"  # ranks the map omits: conservative cross-class
        da, db = self.devices[rank_a], self.devices[rank_b]
        if da.process_index == db.process_index:
            return "ici"
        slice_a = getattr(da, "slice_index", 0) or 0
        slice_b = getattr(db, "slice_index", 0) or 0
        if (self.device_coords(da) is not None
                and self.device_coords(db) is not None
                and slice_a == slice_b):
            return "ici"
        return "dcn"

    def set_link_class(self, ranks: Sequence[int]) -> str:
        """The WORST link class spanned by a process set's ranks (the
        class its flat collectives are bottlenecked on): ``"dcn"`` if
        any member pair crosses DCN, else ``"ici"``. Degenerate sets
        (zero/one rank — a parked spare's view, a single-device world)
        classify as ``"ici"``: the collective is local or absent."""
        ranks = list(ranks)
        if len(ranks) < 2:
            return "ici"
        anchor = ranks[0]
        for r in ranks[1:]:
            if self.link_class(anchor, r) == "dcn":
                return "dcn"
        return "ici"

    def link_class_matrix(self) -> dict[str, int]:
        """Unordered rank-pair counts by link class — the summary
        :meth:`describe` renders and ``/comms`` consumers use to weight
        per-class fits. Empty for degenerate (<2 rank) worlds."""
        counts: dict[str, int] = {}
        for i in range(self.num_devices):
            for j in range(i + 1, self.num_devices):
                cls = self.link_class(i, j)
                counts[cls] = counts.get(cls, 0) + 1
        return counts

    def _describe_mesh_2d(self) -> list[str]:
        """The configured 2-D ``(batch, model)`` training mesh, with the
        link classes each axis's collectives actually ride — flat rank r
        sits at (r // model, r % model), so a model-axis neighbor is
        r+1 and a batch-axis neighbor is r+model. Empty (no lines) when
        no mesh shape is configured; never raises."""
        try:
            from .parallel.mesh import resolve_mesh_shape

            shape = resolve_mesh_shape()
            if shape is None:
                return []
            b, m = shape
            if b == -1:
                if m < 1 or self.size % m != 0:
                    return [f"mesh: invalid shape -1x{m} for world "
                            f"{self.size}"]
                b = self.size // m
            if b * m != self.size:
                return [f"mesh: invalid shape {b}x{m} for world "
                        f"{self.size}"]

            def _axis_classes(stride: int) -> str:
                classes: set[str] = set()
                for r in range(self.size):
                    q = r + stride
                    # A stride-1 (model) hop must stay in its row of m;
                    # a stride-m (batch) hop stays in its column by
                    # construction.
                    if q < self.size and (stride != 1 or q // m == r // m):
                        classes.add(self.link_class(r, q))
                return "+".join(sorted(classes)) or "none"

            return [
                f"mesh: 2-D (batch, model) = {b}x{m}",
                (f"  batch axis: {m} group(s) of {b} at stride {m}, "
                 f"links {_axis_classes(m)}" if b > 1 else
                 "  batch axis: size 1 (no gradient-sync hops)"),
                (f"  model axis: {b} group(s) of {m} contiguous ranks, "
                 f"links {_axis_classes(1)}" if m > 1 else
                 "  model axis: size 1 (no intra-layer hops)"),
            ]
        except Exception:  # noqa: BLE001 — description must never fail
            return []

    def describe(self) -> str:
        lines = [
            f"world: {self.size} device rank(s) across "
            f"{self.cross_size} host(s)"
        ]
        # Link structure summary: pair counts by class. Degenerate
        # worlds (a parked spare's empty view, a single-device world)
        # must render a valid — if trivial — model, never raise.
        matrix = self.link_class_matrix()
        if matrix:
            pairs = " ".join(f"{cls}={n}"
                             for cls, n in sorted(matrix.items()))
            lines.append(f"links: {pairs}")
        else:
            lines.append("links: none (degenerate single-rank world)")
        if self.link_class_map() is not None:
            lines.append(
                "islands (HOROVOD_LINK_CLASS_MAP): "
                + " ".join("[" + ",".join(map(str, isl)) + "]"
                           for isl in self.ici_islands()))
        lines.extend(self._describe_mesh_2d())
        # Comms-planner view: the chosen collective algorithm per op at a
        # representative payload, with provenance (fitted model vs static
        # crossover) — why a bucket got its schedule. Best-effort: a cold
        # or disabled planner renders a one-liner, never raises.
        try:
            from .ops.comms_planner import describe_plans

            lines.extend(describe_plans(self))
        except Exception:  # noqa: BLE001 — description must never fail
            pass
        for i, d in enumerate(self.devices):
            coords = self.device_coords(d)
            lines.append(
                f"  rank {i}: {d.platform}:{d.id} host={d.process_index}"
                + (f" coords={coords}" if coords else "")
            )
        return "\n".join(lines)
