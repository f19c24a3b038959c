"""Topology-aware per-bucket collective algorithm selection.

ROADMAP item 1's missing half: the sensor planes (the online α–β cost
model of ``comms_model.py``, the per-collective skew of ``tracing.py``,
the exposed-comm attribution of ``attribution.py``) MEASURE what the
interconnect delivers, but every dispatch still shipped one hardcoded
schedule — a flat ring — and the only alternative (the hierarchical
mesh) was a coarse per-job flag the sharded/fsdp modes reject. TACCL
(PAPERS.md, arXiv:2111.04867) shows algorithm choice from a
communication sketch of the topology is worth integer factors on
multi-slice fabrics; the MPI characterization study (arXiv:1810.11112)
shows the crossover points are payload-dependent — per *bucket*, not
per job. This module closes the loop: a per-bucket **algorithm axis**
priced by the live model.

Algorithm vocabulary (the planner's ``algorithm`` label values, joining
``flat`` in the comms model's fit keys):

- ``flat`` — the one-shot XLA collective (psum / psum_scatter /
  all_gather) every dispatch shipped before this module existed. On a
  single-class ICI fabric XLA's own lowering is the roofline, so flat
  is the static table's default there.
- ``rhd`` — recursive halving–doubling: a log2(n) chunked
  ``ppermute`` schedule (reduce-scatter by halving, allgather by
  doubling), with the classic fold-in/fold-out step for
  non-power-of-two worlds. Latency-optimal (2·log2 n launch terms vs
  the ring's 2(n−1)) — the small-payload regime. Never chosen by the
  static table (XLA's native collective is assumed better until the
  model MEASURES otherwise); eligible through a fitted
  ``(op, "rhd", class)`` key, an env pin, or the autotune axis.
- ``two_level`` — the ICI×DCN hierarchical composition ON THE FLAT
  AXIS: intra-island reduce-scatter → cross-island leg → intra-island
  allgather via ``axis_index_groups``, so the slow (DCN) hop carries
  ``1/L`` of the payload. Unlike the per-job hierarchical mesh
  (``parallel/hierarchical.py``), this form composes with
  ``sync_mode="sharded"``/``"fsdp"`` — the axis stays flat, so the
  shard ownership map is untouched.

**Selection** (:func:`plan_bucket`) is per (op, bucket bytes, world):

1. a forced algorithm (:func:`forced` — tests, microprobes);
2. the pinned autotune decision (``autotune.tuned_algorithm()`` — the
   fourth joint-grid axis);
3. an env pin (``HOROVOD_COMMS_PLANNER=flat|rhd|two_level``);
4. model pricing: each eligible candidate priced with the exact-key
   α–β fit (``comms_model.predict_exact`` — every algorithm gets its
   own LinkFit, so the model's own training loop closes);
5. the static crossover table: candidates priced with the per-class
   seeds (``topology.LINK_CLASS_SEEDS``) — on a multi-island fabric
   ``two_level`` wins above the seed crossover, ``flat`` below; on a
   single-island fabric ``flat`` always.

Ineligible candidates (``rhd`` on a non-power-of-two RS/AG half,
``two_level`` on a single island or ragged islands) fall out before
pricing; the fallback is always ``flat``.

**Rank-identity.** The plan must be a pure function of facts every
rank shares, or the mesh deadlocks on divergent traced programs. Bucket
bytes, world size, and the island layout are static trace facts; the
model snapshot is the one per-rank input, so it is exchanged through
the same broadcast-decision machinery the autotuner pins winners with
(:func:`_synced_snapshot` — rank 0's fitted (α, β) table, broadcast
once per world generation). A skewed local fit can therefore never
diverge the mesh. Plans are cached per (key, generation): stable within
a generation, recomputed at the elastic generation fence
(:func:`maybe_replan` — the ``hvd_planner_replans_total`` counter).

``HOROVOD_COMMS_PLANNER`` unset is bit-for-bit inert: the wiring in
``ops/fusion.py``/``collective_ops.py`` consults :func:`plan_bucket`
only after an :func:`enabled` check, and a disabled planner returns
None before touching any state, so every flush traces exactly the HEAD
program.
"""

from __future__ import annotations

import os
import threading
from typing import Any, NamedTuple, Sequence

#: The planner's algorithm vocabulary (``algorithm`` label values).
PLANNER_ALGORITHMS = ("flat", "rhd", "two_level")

#: Ops the planner schedules: the three bucket-flush collectives plus
#: the MoE dispatch/combine wire (``parallel/moe.py``).
PLANNER_OPS = ("allreduce", "reducescatter", "allgather", "alltoall")

#: The gradient-wire subset — what a sync_mode's flush can lower to.
#: The transparent autotuner's algorithm axis intersects eligibility
#: over THESE only: ``alltoall`` (rhd never eligible) is a per-layer
#: wire, not a flush the factories might emit under another name.
_WIRE_OPS = ("allreduce", "reducescatter", "allgather")


class BucketPlan(NamedTuple):
    """One bucket's schedule decision — the unit ``GET /comms`` renders
    and :func:`describe_plans` explains."""

    op: str
    algorithm: str
    nbytes: int
    world: int
    islands: tuple[tuple[int, ...], ...] | None
    provenance: str  # forced|autotune_pin|env_pin|model|static_crossover
    costs: dict  # {algorithm: predicted seconds} (may be empty for pins)


# ---------------------------------------------------------------------------
# Enablement + module state
# ---------------------------------------------------------------------------

_lock = threading.Lock()
_plans: dict[tuple, BucketPlan] = {}
_snapshot: dict[str, tuple[float, float | None]] | None = None
_generation: str | None = None
_replans = 0
_forced: list[str] = []


def planner_mode() -> str | None:
    """None (disabled), ``"auto"`` (price per bucket), or a pinned
    algorithm name. ``HOROVOD_COMMS_PLANNER`` = ``1``/``auto`` → auto;
    ``flat``/``rhd``/``two_level`` → pin; anything else → disabled."""
    raw = os.environ.get("HOROVOD_COMMS_PLANNER", "").strip().lower()
    if raw in ("1", "true", "auto", "on"):
        return "auto"
    if raw in PLANNER_ALGORITHMS:
        return raw
    return None


def enabled() -> bool:
    return planner_mode() is not None


def reset_for_testing() -> None:
    """Forget every plan, the synced snapshot, and the generation fence
    (the ``comms_model.reset_for_testing`` idiom)."""
    global _snapshot, _generation, _replans
    with _lock:
        _plans.clear()
        _snapshot = None
        _generation = None
        _replans = 0
    _forced.clear()


class forced:
    """Context manager pinning every plan to ``algorithm`` — the
    per-algorithm microprobe's hook (``run_comms_microprobe``).
    Nestable; the innermost pin wins."""

    def __init__(self, algorithm: str):
        if algorithm not in PLANNER_ALGORITHMS:
            raise ValueError(
                f"unknown algorithm {algorithm!r}; expected one of "
                f"{PLANNER_ALGORITHMS}")
        self._algorithm = algorithm

    def __enter__(self):
        _forced.append(self._algorithm)
        return self

    def __exit__(self, *exc):
        _forced.pop()
        return False


def _generation_now() -> str:
    return os.environ.get("HOROVOD_WORLD_VERSION", "static") or "static"


def maybe_replan() -> None:
    """Drop every cached plan when the world generation advanced — the
    elastic resize fence: a new world re-derives its schedules from the
    new (size, islands, snapshot) facts, and never mid-generation."""
    global _generation, _snapshot, _replans
    gen = _generation_now()
    with _lock:
        if _generation is None:
            _generation = gen
            return
        if gen == _generation:
            return
        _generation = gen
        _plans.clear()
        _snapshot = None
        _replans += 1
    _note_replan()


def _note_replan() -> None:
    try:
        from .. import metrics

        metrics.PLANNER_REPLANS.inc()
    except Exception:  # noqa: BLE001 — instrumentation is best-effort
        pass


# ---------------------------------------------------------------------------
# World facts (islands, link classes) — static trace-time inputs
# ---------------------------------------------------------------------------


def default_world_size() -> int | None:
    """The initialized world's rank count, or None pre-init — the
    stdlib-side caller's (``comms_model.predict_flush_cost``) world."""
    try:
        from ..basics import _state

        topo = _state.topology
        return topo.size if topo is not None else None
    except Exception:  # noqa: BLE001
        return None


def _islands_for(world_size: int) -> tuple[tuple[int, ...], ...] | None:
    """The ICI island layout covering a ``world_size``-rank world, or
    None when the world's facts are unknowable (pre-init, or the axis
    is a subset whose ranks the topology cannot map). Islands come from
    ``Topology.ici_islands`` (the ``HOROVOD_LINK_CLASS_MAP`` override
    included) and are only meaningful for the FULL world axis."""
    try:
        from ..basics import _state

        topo = _state.topology
        if topo is None or topo.size != int(world_size):
            return None
        islands = topo.ici_islands()
    except Exception:  # noqa: BLE001
        return None
    return tuple(tuple(int(r) for r in isl) for isl in islands)


def _worst_link_class(islands) -> str:
    return "dcn" if islands is not None and len(islands) > 1 else "ici"


def _regular_factors(islands, world) -> tuple[int, int] | None:
    """(num_islands G, island_size L) when the layout is regular (equal
    sizes, G·L = world, ≥2 islands) — ``two_level``'s eligibility."""
    if islands is None or len(islands) < 2:
        return None
    sizes = {len(isl) for isl in islands}
    if len(sizes) != 1:
        return None
    L = sizes.pop()
    G = len(islands)
    if G * L != int(world) or L < 2:
        return None
    return G, L


def eligible_algorithms(op: str, world: int, islands,
                        candidates: Sequence[str] | None = None
                        ) -> tuple[str, ...]:
    """The algorithms a (op, world, islands) bucket may legally take.

    ``rhd`` needs a power-of-two world (the fold-in step covers the
    allreduce, but the RS/AG halves' ownership contract — rank r keeps
    row r — has no fold-in analog) and never applies to ``alltoall``
    (recursive halving reduces; an alltoall only permutes, so the
    staged form is Bruck's algorithm, which XLA's native lowering
    already subsumes); ``two_level`` needs a regular ≥2 island layout.
    ``flat`` is always eligible."""
    out = ["flat"]
    n = int(world)
    pow2 = n >= 2 and (n & (n - 1)) == 0
    if op == "alltoall":
        pass  # rhd never eligible for a pure permutation wire
    elif op == "allreduce":
        if n >= 2:
            out.append("rhd")
    elif pow2:
        out.append("rhd")
    if _regular_factors(islands, n) is not None:
        out.append("two_level")
    if candidates is not None:
        out = [a for a in out if a in candidates]
    return tuple(out)


# ---------------------------------------------------------------------------
# Pricing: fitted exact-key model first, per-class seeds as the static
# crossover table
# ---------------------------------------------------------------------------


def _seed(link_class: str) -> tuple[float, float]:
    from ..topology import link_seed

    return link_seed(link_class)


def _seed_price(op: str, algorithm: str, nbytes: float, world: int,
                islands) -> float | None:
    """The static crossover table: candidate seconds from the per-class
    α–β seeds (``topology.LINK_CLASS_SEEDS``). One α per collective leg
    (matching what a fitted per-collective α means), β·bytes per leg.
    None = this algorithm is never chosen statically (``rhd`` — XLA's
    native collective is assumed to beat a hand ppermute schedule until
    the model MEASURES otherwise)."""
    B = float(nbytes)
    n = int(world)
    worst = _worst_link_class(islands)
    a_w, b_w = _seed(worst)
    halves = 2.0 if op == "allreduce" else 1.0
    if op == "alltoall":
        # A permutation wire, priced differently from the reductions in
        # both terms. β: every rank ships (n-1)/n of its buffer once (no
        # reduction halves), and staging CANNOT shrink the cross-island
        # byte count — two_level's cross leg still carries (G-1)/G of B.
        # α: flat issues a distinct message per peer ((n-1) launches,
        # DCN-priced pairs dominating on a split fabric — the MPI
        # characterization's α-sensitivity result), while two_level
        # aggregates them into (L-1) ICI + (G-1) DCN launches. So the
        # seed crossover runs the OPPOSITE way from the reductions:
        # two_level wins the latency-bound regime, flat the huge
        # bandwidth-bound payloads. Flat on a split fabric prices as the
        # "mixed" class (topology.LINK_CLASS_SEEDS): part of each rank's
        # chunks stay on ICI, so no single DCN link carries the whole
        # payload the way a ring hop does.
        if algorithm == "flat":
            a_f, b_f = _seed("mixed" if worst == "dcn" else worst)
            return a_f * max(n - 1, 1) + b_f * B * (n - 1) / max(n, 1)
        if algorithm == "two_level":
            factors = _regular_factors(islands, n)
            if factors is None:
                return None
            G, L = factors
            a_i, b_i = _seed("ici")
            a_d, b_d = _seed("dcn")
            local = a_i * (L - 1) + b_i * B * (L - 1) / L
            cross = a_d * (G - 1) + b_d * B * (G - 1) / G
            return local + cross
        return None
    if algorithm == "flat":
        return a_w + b_w * B * halves * (n - 1) / max(n, 1)
    if algorithm == "rhd":
        return None
    if algorithm == "two_level":
        factors = _regular_factors(islands, n)
        if factors is None:
            return None
        G, L = factors
        a_i, b_i = _seed("ici")
        a_d, b_d = _seed("dcn")
        local = a_i + b_i * B * (L - 1) / L
        cross = a_d + b_d * (B / L) * halves * (G - 1) / G
        if op == "allreduce":
            return 2.0 * local + cross
        return local + cross  # one local leg + half the cross ring
    return None


def _model_price(snapshot, op: str, algorithm: str, link_class: str,
                 nbytes: float) -> float | None:
    """α + β·bytes from the SYNCED snapshot's exact key, else None."""
    if not snapshot:
        return None
    entry = snapshot.get(f"{op}|{algorithm}|{link_class}")
    if entry is None:
        return None
    alpha, beta = entry
    if beta is None:
        return max(float(alpha), 0.0)
    return max(float(alpha) + float(beta) * float(nbytes), 0.0)


def _decide(op: str, nbytes: int, world: int, islands, snapshot,
            candidates: Sequence[str] | None) -> tuple[str, str, dict]:
    """(algorithm, provenance, costs) — the pure decision function.

    Deterministic in its inputs alone (the rank-identity contract: same
    bucket + world + islands + synced snapshot → same plan on every
    rank). Candidates compete only within ONE pricing regime — a
    measured fit on congested hardware is not commensurate with a
    nominal-seed number, so mixing them would let an unfitted candidate
    win on fantasy prices. When ≥2 eligible candidates have ready
    exact-key fits, the decision ranks the FITTED ones (provenance
    ``model``; unfitted candidates are not competitive until measured —
    the per-algorithm microprobe/dispatch samples get them there);
    otherwise every candidate prices from the seed table
    (``static_crossover``)."""
    elig = eligible_algorithms(op, world, islands, candidates)
    link = _worst_link_class(islands)
    fitted: dict[str, float] = {}
    seeded: dict[str, float] = {}
    for algo in elig:
        cost = _model_price(snapshot, op, algo, link, nbytes)
        if cost is not None:
            fitted[algo] = cost
        cost = _seed_price(op, algo, nbytes, world, islands)
        if cost is not None:
            seeded[algo] = cost
    if len(fitted) >= 2:
        best = min(sorted(fitted), key=lambda a: fitted[a])
        return best, "model", fitted
    if not seeded:
        return "flat", "static_crossover", {}
    best = min(sorted(seeded), key=lambda a: seeded[a])
    return best, "static_crossover", seeded


# ---------------------------------------------------------------------------
# The synced model snapshot (rank 0's, broadcast once per generation)
# ---------------------------------------------------------------------------


def _broadcast_decision(decision):
    """Rank 0's value everywhere — the exact exchange
    ``autotune.AutotuneStep`` pins winners with, so the planner's
    snapshot rides machinery every multi-rank deployment already
    trusts. Single-process worlds pass through."""
    from ..process_world import size as _psize

    if _psize() > 1:
        from ..process_world import broadcast_object_host

        return broadcast_object_host(decision, name="planner/model-snapshot")
    import jax

    if jax.process_count() > 1:
        from ..functions import broadcast_object

        return broadcast_object(decision, name="planner/model-snapshot")
    return decision


def _local_snapshot() -> dict[str, tuple[float, float | None]]:
    from .. import comms_model

    return comms_model.get_model().fit_snapshot(
        ops=PLANNER_OPS, algorithms=PLANNER_ALGORITHMS)


def _synced_snapshot() -> dict[str, tuple[float, float | None]]:
    """The model snapshot every rank plans from: rank 0's ready fits,
    exchanged once per world generation and cached — retraces replan
    from the cache with no further exchange (a per-trace broadcast
    could deadlock a single-rank retrace).

    Only the LOCAL snapshot build is fault-tolerant (a local failure
    degrades to broadcasting {} — rank-identical, since rank 0's value
    is what everyone adopts). A failure of the BROADCAST itself
    propagates: a partial exchange (one rank timing out while its
    peers succeed) would leave ranks planning from different
    snapshots — exactly the divergent-traced-programs deadlock the
    sync exists to prevent — so it must surface as an error, not
    degrade silently."""
    global _snapshot
    with _lock:
        if _snapshot is not None:
            return _snapshot
    try:
        local = _local_snapshot()
    except Exception:  # noqa: BLE001 — only rank 0's value matters, and
        local = {}  # {} is a valid (static-table) snapshot
    snap = _broadcast_decision(local)
    if not isinstance(snap, dict):
        snap = {}
    with _lock:
        if _snapshot is None:
            _snapshot = snap
        return _snapshot


def _peek_snapshot() -> tuple[dict, bool]:
    """(snapshot, synced): the already-synced snapshot when one exists,
    else this rank's LOCAL fits — for rank-local introspection paths
    (``describe_plans``, ``comms_model``'s predictor) that must never
    enter a blocking world collective. Callers must not cache decisions
    made from an unsynced peek (they could differ from the traced
    path's synced ones)."""
    with _lock:
        if _snapshot is not None:
            return _snapshot, True
    try:
        return _local_snapshot(), False
    except Exception:  # noqa: BLE001
        return {}, False


# ---------------------------------------------------------------------------
# plan_bucket — the wiring surface
# ---------------------------------------------------------------------------


def _pinned() -> tuple[str, str] | None:
    """(algorithm, provenance) when a pin short-circuits pricing."""
    if _forced:
        return _forced[-1], "forced"
    try:
        from ..autotune import tuned_algorithm

        pin = tuned_algorithm()
    except Exception:  # noqa: BLE001
        pin = None
    if pin == "auto":
        # The sweep measured the un-pinned per-bucket mode and chose
        # it: fall through to pricing, exactly like no pin.
        return None
    if pin is not None:
        return str(pin), "autotune_pin"
    mode = planner_mode()
    if mode in PLANNER_ALGORITHMS:
        return mode, "env_pin"
    return None


def plan_bucket(op: str, nbytes: int, world_size: int | None,
                candidates: Sequence[str] | None = None,
                sync: bool = True) -> BucketPlan | None:
    """The schedule for one bucket, or None when the planner is
    disabled / the world is unknown / nothing but flat is possible.

    Callers treat None exactly like ``algorithm == "flat"`` — they keep
    their original (HEAD) code path, which is what makes
    ``HOROVOD_COMMS_PLANNER`` unset bit-for-bit inert.

    ``sync=False`` is the rank-local introspection flavor
    (``describe_plans``, the predictor's planned-wire pricing): it
    never enters the snapshot broadcast (a blocking world collective a
    lone rank must not reach), planning from the already-synced
    snapshot when one exists and this rank's local fits otherwise —
    and an unsynced decision is NOT cached, so it can never leak into
    the traced path's rank-identical plan table."""
    if not enabled():
        return None
    if world_size is None or int(world_size) < 2:
        return None
    if op not in PLANNER_OPS:
        return None
    maybe_replan()
    n = int(world_size)
    islands = _islands_for(n)
    pin = _pinned()
    key = (op, int(nbytes), n, islands, pin,
           tuple(candidates) if candidates is not None else None)
    with _lock:
        plan = _plans.get(key)
    if plan is not None:
        return plan
    # Only the SYNCED (traced/eager dispatch) path populates the plan
    # table and the hvd_planner_plans ledger: introspective pricing
    # (the predictor sweeping hypothetical autotune buckets) must not
    # crowd the /comms plan view with buckets that never dispatch.
    cacheable = sync
    if pin is not None:
        algo, provenance = pin
        if algo not in eligible_algorithms(op, n, islands, candidates):
            algo = "flat"  # an ineligible pin degrades loudly-labeled
            provenance += ":ineligible"
        plan = BucketPlan(op, algo, int(nbytes), n, islands, provenance, {})
    else:
        if sync:
            snapshot = _synced_snapshot()
        else:
            snapshot, _synced = _peek_snapshot()
        algo, provenance, costs = _decide(
            op, int(nbytes), n, islands, snapshot, candidates)
        plan = BucketPlan(op, algo, int(nbytes), n, islands, provenance,
                          costs)
    if not cacheable:
        return plan
    with _lock:
        _plans.setdefault(key, plan)
    _note_plan()
    return plan


def planned_algorithm(op: str, nbytes: int, world_size: int | None,
                      candidates: Sequence[str] | None = None,
                      sync: bool = True) -> str:
    """Convenience: the planned algorithm name (``"flat"`` when the
    planner is off or nothing better is eligible)."""
    plan = plan_bucket(op, nbytes, world_size, candidates, sync=sync)
    return plan.algorithm if plan is not None else "flat"


def _note_plan() -> None:
    try:
        from .. import metrics

        metrics.PLANNER_PLANS.inc()
    except Exception:  # noqa: BLE001
        pass


def note_dispatch(op: str, algorithm: str) -> None:
    """Count one planned collective emission (traced: once per TRACE,
    like the ``hvd_grad_sync_*`` family; eager: once per dispatch)."""
    try:
        from .. import metrics

        metrics.PLANNER_DISPATCH.inc(op=op, algorithm=algorithm)
    except Exception:  # noqa: BLE001
        pass


def autotune_candidates(world_size: int | None = None
                        ) -> tuple[str, ...] | None:
    """The algorithm axis the transparent autotuner should sweep, or
    None when the axis is degenerate (planner off, planner pinned, or
    only flat eligible). Consulted by the step factories
    (``parallel/data_parallel.py``) under ``HOROVOD_AUTOTUNE=1``.

    Candidates are the algorithms eligible on EVERY planner op — the
    factories cannot know whether the wire is an allreduce flush or
    the sharded/fsdp RS/AG halves, and a candidate the halves would
    degrade to flat (``rhd`` off power-of-two) would just re-measure
    the flat program under another name. ``"auto"`` leads the axis:
    the un-pinned per-bucket pricing is itself a candidate, so a mixed
    plan (two_level for large buckets, flat for latency-bound ones)
    competes against every uniform pin instead of being unreachable."""
    if planner_mode() != "auto":
        return None
    n = world_size if world_size is not None else default_world_size()
    if n is None or int(n) < 2:
        return None
    islands = _islands_for(int(n))
    elig = set(PLANNER_ALGORITHMS)
    for op in _WIRE_OPS:
        elig &= set(eligible_algorithms(op, int(n), islands))
    ordered = tuple(a for a in PLANNER_ALGORITHMS if a in elig)
    return ("auto",) + ordered if len(ordered) > 1 else None


# ---------------------------------------------------------------------------
# Introspection: /comms payload leg + Topology.describe rendering
# ---------------------------------------------------------------------------

#: Representative payloads describe/summary price plans at (64 KiB — a
#: typical control bucket — and 16 MiB — a typical gradient bucket).
_DESCRIBE_PAYLOADS = (64 * 1024, 16 * 1024 * 1024)


def summary() -> dict:
    """The planner leg of ``comms_model.payload()`` — why buckets get
    their schedules. Always a valid dict (cold/disabled planners report
    so explicitly; ``GET /comms`` must never 500 over this)."""
    mode = planner_mode()
    out: dict[str, Any] = {
        "enabled": mode is not None,
        "mode": mode,
        "generation": _generation,
        "replans": _replans,
    }
    if mode is None:
        return out
    with _lock:
        plans = list(_plans.values())
    out["plans"] = [
        {
            "op": p.op,
            "bytes": p.nbytes,
            "world": p.world,
            "algorithm": p.algorithm,
            "provenance": p.provenance,
            "costs_s": {a: round(c, 9) for a, c in sorted(p.costs.items())},
        }
        for p in plans[:32]  # heartbeat payloads stay bounded
    ]
    out["plans_total"] = len(plans)
    return out


def describe_plans(topology) -> list[str]:
    """Lines for ``Topology.describe()``: the planned algorithm per op
    at representative payloads over THIS topology's islands.

    Pure introspection: plans price rank-locally (``sync=False`` — a
    lone rank calling ``describe()`` must never block in the snapshot
    broadcast) and are NOT cached or counted, so describing a topology
    cannot perturb the live plan table or the ``hvd_planner_plans``
    ledger."""
    mode = planner_mode()
    if mode is None:
        return ["planner: off (HOROVOD_COMMS_PLANNER unset)"]
    n = topology.size
    if n < 2:
        return [f"planner: {mode} (degenerate single-rank world)"]
    lines = [f"planner: {mode}"]
    islands = _islands_for(n)
    link = _worst_link_class(islands)
    snapshot, _ = _peek_snapshot()
    pin = _pinned()
    for op in PLANNER_OPS:
        choices = []
        for nbytes in _DESCRIBE_PAYLOADS:
            if pin is not None:
                algo, provenance = pin
                if algo not in eligible_algorithms(op, n, islands):
                    algo, provenance = "flat", provenance + ":ineligible"
            else:
                algo, provenance, _costs = _decide(
                    op, nbytes, n, islands, snapshot, None)
            kib = nbytes // 1024
            choices.append(f"{kib}KiB->{algo}({provenance})")
        if choices:
            lines.append(f"  {op}@{link}: " + " ".join(choices))
    lines.extend(describe_axis_plans(topology))
    return lines


def _mesh_shape_for(topology) -> tuple[int, int] | None:
    """The configured 2-D (batch, model) shape resolved against THIS
    topology's world, or None (unset/invalid)."""
    try:
        from ..parallel.mesh import resolve_mesh_shape

        shape = resolve_mesh_shape()
    except Exception:  # noqa: BLE001 — introspection must never raise
        return None
    if shape is None:
        return None
    b, m = shape
    n = topology.size
    if b == -1:
        if m < 1 or n % m != 0:
            return None
        b = n // m
    return (b, m) if b * m == n else None


def axis_link_class(topology, axis: str, batch: int, model: int) -> str:
    """The worst link class a collective over ONE 2-D mesh axis rides:
    ``model``-axis hops are contiguous flat ranks (stride 1 within a row
    of ``model``), ``batch``-axis hops stride ``model`` — the placement
    contract of ``parallel.mesh.mesh_2d``. This is what lets the planner
    price the two fsdp gather legs separately: on a split fabric the
    model leg stays inside an ICI island while the batch leg crosses."""
    n = topology.size
    stride = 1 if axis == "model" else model
    order = {"self": 0, "ici": 1, "mixed": 2, "dcn": 3}
    worst = "self"
    for r in range(n):
        q = r + stride
        if q >= n or (stride == 1 and q // model != r // model):
            continue
        cls = topology.link_class(r, q)
        if order.get(cls, 3) > order.get(worst, 0):
            worst = cls
    return worst if worst != "self" else "ici"


def price_axis_gather(axis: str, nbytes: int, batch: int, model: int,
                      topology=None) -> float:
    """Seed-priced seconds of an allgather leg over one 2-D mesh axis —
    the flat-ring formula over that axis's size and ITS link class (not
    the whole-world worst class the 1-D plan prices with). The pricing
    argument for the (batch, model) split in one number: the batch leg
    moves ~1/model of the 1-D gather bytes, and the model leg's bytes
    ride the short-hop class."""
    if topology is None:
        from .. import basics

        topology = basics._state.topology
    k = int(batch) if axis == "batch" else int(model)
    if k < 2:
        return 0.0
    a, b = _seed(axis_link_class(topology, axis, batch, model))
    return a + b * float(nbytes) * (k - 1) / k


def describe_axis_plans(topology) -> list[str]:
    """Per-mesh-axis gather pricing lines for ``Topology.describe()`` —
    empty when no 2-D mesh shape is configured. Rank-local and
    side-effect free, like :func:`describe_plans`."""
    shape = _mesh_shape_for(topology)
    if shape is None:
        return []
    b, m = shape
    lines = []
    for axis, k in (("batch", b), ("model", m)):
        if k < 2:
            lines.append(f"  gather@{axis}: size 1 (no wire)")
            continue
        cls = axis_link_class(topology, axis, b, m)
        prices = " ".join(
            f"{nb // 1024}KiB->"
            f"{price_axis_gather(axis, nb, b, m, topology):.2e}s"
            for nb in _DESCRIBE_PAYLOADS)
        lines.append(f"  gather@{axis}({k} rank(s), {cls}): {prices}")
    return lines


# ---------------------------------------------------------------------------
# Traced algorithm implementations (pure lax; called inside shard_map).
# jax is imported lazily so the module's PLANNING surface stays
# importable wherever comms_model is.
# ---------------------------------------------------------------------------


def _rhd_reduce_scatter_rows(work, axis_name, n: int, r):
    """Recursive-halving reduce-scatter of a ``(n, chunk)`` view: after
    log2(n) pairwise ``ppermute`` exchanges rank r holds row r of the
    fully reduced buffer. ``n`` must be a power of two."""
    import jax.numpy as jnp
    from jax import lax

    size = n
    while size > 1:
        h = size // 2
        keep_upper = (r & h) != 0
        lower = lax.slice_in_dim(work, 0, h, axis=0)
        upper = lax.slice_in_dim(work, h, size, axis=0)
        send = jnp.where(keep_upper, lower, upper)
        keep = jnp.where(keep_upper, upper, lower)
        perm = [(i, i ^ h) for i in range(n)]
        recvd = lax.ppermute(send, axis_name, perm)
        work = keep + recvd
        size = h
    return work  # (1, chunk): row r reduced


def _rhd_allgather_rows(work, axis_name, n: int, r):
    """Recursive-doubling allgather: ``(1, chunk)`` (row r) → the full
    ``(n, chunk)`` buffer in row order on every rank."""
    import jax.numpy as jnp
    from jax import lax

    size = 1
    while size < n:
        perm = [(i, i ^ size) for i in range(n)]
        recvd = lax.ppermute(work, axis_name, perm)
        am_upper = (r & size) != 0
        work = jnp.where(am_upper,
                         jnp.concatenate([recvd, work]),
                         jnp.concatenate([work, recvd]))
        size *= 2
    return work


def rhd_allreduce_sum(flat, axis_name, world_size: int):
    """Recursive halving–doubling SUM allreduce of a flat tensor.

    Power-of-two worlds run the textbook schedule; other worlds take
    the fold-in step — the (n − p) ranks above the largest power of two
    p fold their buffers into partners below, the p-rank schedule runs,
    and the result folds back out. Callers scale for Average."""
    import jax.numpy as jnp
    from jax import lax

    n = int(world_size)
    if n < 2:
        return flat
    m = int(flat.size)
    p = 1 << (n.bit_length() - 1)
    if p == n:
        chunk = -(-m // n)
        buf = jnp.pad(flat, (0, n * chunk - m))
        r = lax.axis_index(axis_name)
        row = _rhd_reduce_scatter_rows(
            buf.reshape(n, chunk), axis_name, n, r)
        full = _rhd_allgather_rows(row, axis_name, n, r)
        return full.reshape(-1)[:m]
    # Fold-in: ranks [p, n) add their buffer into rank (i - p), the
    # power-of-two prefix runs the schedule, fold-out ships the result
    # back. Ranks ≥ p execute the prefix's ppermutes with dead data
    # (ppermute delivers zeros to non-members) — uniform SPMD code.
    chunk = -(-m // p)
    buf = jnp.pad(flat, (0, p * chunk - m))
    r = lax.axis_index(axis_name)
    contrib = lax.ppermute(buf, axis_name,
                           [(i, i - p) for i in range(p, n)])
    buf = buf + contrib
    row = _rhd_reduce_scatter_rows(buf.reshape(p, chunk), axis_name, p, r)
    full = _rhd_allgather_rows(row, axis_name, p, r).reshape(-1)[:m]
    folded = lax.ppermute(full, axis_name,
                          [(i, i + p) for i in range(n - p)])
    return jnp.where(r >= p, folded, full)


def two_level_groups(islands) -> tuple[list[list[int]], list[list[int]]]:
    """(local groups, cross groups) for ``axis_index_groups``: locals
    are the islands; cross group j = position-j ranks across islands."""
    groups = [list(isl) for isl in islands]
    L = len(groups[0])
    cross = [[g[j] for g in groups] for j in range(L)]
    return groups, cross


def two_level_allreduce_sum(flat, axis_name, islands):
    """ICI×DCN hierarchical SUM allreduce on the FLAT axis: intra-island
    reduce-scatter → cross-island allreduce of the 1/L shard →
    intra-island allgather, via ``axis_index_groups`` — the
    ``parallel/hierarchical.py`` composition without the (cross, local)
    mesh, which is what lets the sharded/fsdp wires ride it."""
    import jax.numpy as jnp
    from jax import lax

    from ..profiler import annotate_collective

    groups, cross = two_level_groups(islands)
    L = len(groups[0])
    m = int(flat.size)
    pad = (-m) % L
    buf = jnp.pad(flat, (0, pad)) if pad else flat
    with annotate_collective("planner.two_level.rs_local"):
        shard = lax.psum_scatter(buf, axis_name, scatter_dimension=0,
                                 tiled=True, axis_index_groups=groups)
    with annotate_collective("planner.two_level.allreduce_cross"):
        shard = lax.psum(shard, axis_name, axis_index_groups=cross)
    with annotate_collective("planner.two_level.ag_local"):
        full = lax.all_gather(shard, axis_name, axis=0, tiled=True,
                              axis_index_groups=groups)
    return full[:m] if pad else full


def _two_level_row_perm(islands, world: int):
    """Row permutation for the two-scatter reduce-scatter: placing old
    row ``groups[g][j]`` at new position ``j·G + g`` makes the
    intra-island scatter (over L) then cross-island scatter (over G)
    land rank ``groups[g][j]`` exactly on its own row — the
    ``shard_ownership`` contract preserved through the hierarchy."""
    groups, _ = two_level_groups(islands)
    G, L = len(groups), len(groups[0])
    perm = [0] * world
    for g in range(G):
        for j in range(L):
            perm[j * G + g] = groups[g][j]
    return perm


def two_level_reducescatter_sum(flat, axis_name, world_size: int, islands):
    """Two-level SUM reduce-scatter of a ``(world·s,)`` buffer: rank r
    ends with its own row r (``s`` elements), exactly like the flat
    tiled ``psum_scatter`` — via intra-island then cross-island
    scatters over the pre-permuted row view."""
    import jax.numpy as jnp
    from jax import lax

    from ..profiler import annotate_collective

    n = int(world_size)
    groups, cross = two_level_groups(islands)
    perm = jnp.asarray(_two_level_row_perm(islands, n))
    rows = flat.reshape(n, -1)[perm].reshape(-1)
    with annotate_collective("planner.two_level.rs_local"):
        part = lax.psum_scatter(rows, axis_name, scatter_dimension=0,
                                tiled=True, axis_index_groups=groups)
    with annotate_collective("planner.two_level.rs_cross"):
        row = lax.psum_scatter(part, axis_name, scatter_dimension=0,
                               tiled=True, axis_index_groups=cross)
    return row


def two_level_allgather_row(row, axis_name, world_size: int, islands):
    """Inverse of :func:`two_level_reducescatter_sum`: every rank
    contributes its ``(s,)`` row, receives the full ``(world·s,)``
    buffer in rank-row order — cross-island allgather of the shard,
    intra-island allgather, inverse row permutation."""
    import jax.numpy as jnp
    from jax import lax

    from ..profiler import annotate_collective

    n = int(world_size)
    groups, cross = two_level_groups(islands)
    perm = _two_level_row_perm(islands, n)
    inv = [0] * n
    for pos, src in enumerate(perm):
        inv[src] = pos
    with annotate_collective("planner.two_level.ag_cross"):
        part = lax.all_gather(row, axis_name, axis=0, tiled=True,
                              axis_index_groups=cross)
    with annotate_collective("planner.two_level.ag_local"):
        full = lax.all_gather(part, axis_name, axis=0, tiled=True,
                              axis_index_groups=groups)
    return full.reshape(n, -1)[jnp.asarray(inv)].reshape(-1)


def two_level_alltoall(chunks, axis_name, islands):
    """ICI×DCN staged alltoall of per-destination ``(world, ...)``
    chunks: intra-island exchange of the within-island coordinate, then
    cross-island exchange of the island coordinate, via
    ``axis_index_groups`` — the message-aggregation form ( (L-1) ICI +
    (G-1) DCN launches instead of (n-1) mostly-DCN ones). A pure
    permutation: the result is BITWISE identical to the flat tiled
    ``lax.all_to_all`` (asserted in tests/test_moe_parallel.py), so
    unlike the reduction schedules there is no summation-order caveat.

    Writing destination d of island i at within-island position l as
    (i, l): stage 1 exchanges l among island peers (each rank ends
    holding, for every island peer p, p's chunks for within-island
    position = OUR position), stage 2 exchanges i among position peers
    — after which rank (i, l) holds exactly the chunks every source
    addressed to it, reordered back to source-rank order by the inverse
    of the island-major permutation applied up front."""
    import jax.numpy as jnp
    from jax import lax

    from ..profiler import annotate_collective

    groups, cross = two_level_groups(islands)
    G, L = len(groups), len(groups[0])
    n = G * L
    # Destination-rank rows → [l2, i2] island-major view (rank
    # groups[i][l] is destination (i, l)).
    perm = [groups[i][l] for l in range(L) for i in range(G)]
    inv = [0] * n
    for i in range(G):
        for l in range(L):
            inv[groups[i][l]] = i * L + l
    tail = chunks.shape[1:]
    x = chunks[jnp.asarray(perm)].reshape(L, G, *tail)
    with annotate_collective("planner.two_level.a2a_local"):
        x = lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                           tiled=True, axis_index_groups=groups)
    x = jnp.swapaxes(x, 0, 1)  # [l1, i2] → [i2, l1]
    with annotate_collective("planner.two_level.a2a_cross"):
        x = lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                           tiled=True, axis_index_groups=cross)
    # Rows now [i1, l1] = the chunk source rank groups[i1][l1] sent us;
    # restore source-rank order.
    return x.reshape(n, *tail)[jnp.asarray(inv)]


def rhd_reducescatter_sum(flat, axis_name, world_size: int):
    """Recursive-halving SUM reduce-scatter: ``(world·s,)`` → this
    rank's row r. Power-of-two worlds only (the planner's eligibility
    gate enforces it)."""
    from jax import lax

    n = int(world_size)
    r = lax.axis_index(axis_name)
    row = _rhd_reduce_scatter_rows(flat.reshape(n, -1), axis_name, n, r)
    return row.reshape(-1)


def rhd_allgather_row(row, axis_name, world_size: int):
    """Recursive-doubling allgather of per-rank rows: ``(s,)`` → the
    ``(world·s,)`` concatenation. Power-of-two worlds only."""
    from jax import lax

    n = int(world_size)
    r = lax.axis_index(axis_name)
    full = _rhd_allgather_rows(row.reshape(1, -1), axis_name, n, r)
    return full.reshape(-1)


# -- the one dispatch table the wiring calls --------------------------------


def apply_allreduce_sum(plan: BucketPlan, flat, axis_name):
    """Run the plan's allreduce on a flat SUM payload (callers own
    Average/pre/post scaling — and the dispatch-count note: traced
    wiring counts per trace, eager wiring per dispatch)."""
    if plan.algorithm == "rhd":
        return rhd_allreduce_sum(flat, axis_name, plan.world)
    if plan.algorithm == "two_level":
        return two_level_allreduce_sum(flat, axis_name, plan.islands)
    from jax import lax

    return lax.psum(flat, axis_name)


def apply_allreduce_scaled(plan: BucketPlan, flat, axis_name,
                           average: bool, prescale_factor: float = 1.0,
                           postscale_factor: float = 1.0):
    """The ONE canonical scale-order wrapper around the planned SUM
    allreduce — prescale → sum → (postscale [/ world for Average]) —
    shared by the fused bucket path and the eager builders so the two
    wires can never drift on scaling semantics."""
    import jax.numpy as jnp

    if prescale_factor != 1.0:
        flat = flat * jnp.asarray(prescale_factor, dtype=flat.dtype)
    out = apply_allreduce_sum(plan, flat, axis_name)
    scale = postscale_factor
    if average:
        scale = scale / plan.world
    if scale != 1.0:
        out = out * jnp.asarray(scale, dtype=out.dtype)
    return out


def apply_reducescatter_scaled(plan: BucketPlan, flat, axis_name,
                               average: bool,
                               prescale_factor: float = 1.0,
                               postscale_factor: float = 1.0):
    """Canonical scale-order wrapper for the planned SUM
    reduce-scatter (see :func:`apply_allreduce_scaled`)."""
    import jax.numpy as jnp

    if prescale_factor != 1.0:
        flat = flat * jnp.asarray(prescale_factor, dtype=flat.dtype)
    row = apply_reducescatter_sum(plan, flat, axis_name)
    scale = postscale_factor
    if average:
        scale = scale / plan.world
    if scale != 1.0:
        row = row * jnp.asarray(scale, dtype=row.dtype)
    return row


def apply_reducescatter_sum(plan: BucketPlan, flat, axis_name):
    if plan.algorithm == "rhd":
        return rhd_reducescatter_sum(flat, axis_name, plan.world)
    if plan.algorithm == "two_level":
        return two_level_reducescatter_sum(flat, axis_name, plan.world,
                                           plan.islands)
    from jax import lax

    return lax.psum_scatter(flat, axis_name, scatter_dimension=0,
                            tiled=True)


def apply_allgather_row(plan: BucketPlan, row, axis_name):
    if plan.algorithm == "rhd":
        return rhd_allgather_row(row, axis_name, plan.world)
    if plan.algorithm == "two_level":
        return two_level_allgather_row(row, axis_name, plan.world,
                                       plan.islands)
    from jax import lax

    return lax.all_gather(row, axis_name, axis=0, tiled=True)


def apply_alltoall(plan: BucketPlan, x, axis_name):
    """Run the plan's alltoall on a rank-local buffer whose dim 0 is
    ``plan.world · chunk`` (the flat tiled ``lax.all_to_all``
    contract). Pure permutation — every algorithm returns bitwise the
    same buffer."""
    n = int(plan.world)
    if plan.algorithm == "two_level" and x.shape[0] % n == 0:
        chunks = x.reshape(n, x.shape[0] // n, *x.shape[1:])
        out = two_level_alltoall(chunks, axis_name, plan.islands)
        return out.reshape(x.shape)
    from jax import lax

    return lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                          tiled=True)
