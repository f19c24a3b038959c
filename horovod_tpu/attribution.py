"""Step-time attribution: where did this step's wall time go?

The framework's raw sensors answer narrow questions — per-collective
spans and skew (``tracing.py``), the fitted α–β link cost
(``comms_model.py``), the attempt-level goodput ledger (``metrics.py``)
— but none answers the operator's first one: *how does one step's wall
time decompose, and which rank gated it*. Horovod's timeline existed
precisely for that decomposition (PAPERS.md, arXiv:1802.05799), and the
MLPerf-on-TPU-pods study showed step-time attribution (compute vs
exposed communication vs straggler wait) is the lens every scaling fix
looks through (arXiv:1909.09756). This module is that analysis layer:

1. **Phase vocabulary** — the canonical span names
   (:data:`SPAN_FORWARD_BACKWARD` / :data:`SPAN_COLLECTIVE` /
   :data:`SPAN_OPTIMIZER_UPDATE`) shared by the elastic step
   (``parallel/data_parallel.py``) and this module — one constant set,
   so the two planes cannot drift.
2. **Per-rank decomposition** (:func:`decompose_step`): interval
   arithmetic over one rank's own span timeline splits step wall time
   into ``compute / exposed_comm / straggler_wait / overhead``, where
   *exposed_comm* is collective wall time NOT hidden under concurrent
   compute spans — the direct measurement of what the overlap
   scheduler and the fsdp prefetch actually hide. The four phases sum
   to the step wall time by construction.
3. **Cluster critical path** (:func:`analyze_cluster`): merges all
   ranks' offset-corrected spans for a (generation, step) group and
   walks the longest dependency chain through compute segments and
   collective barriers — naming WHICH rank gated each barrier (the last
   arriver) and how much skew it injected. Per-rank ``straggler_wait``
   (time spent inside a collective waiting for the gating rank) is
   carved out of that rank's exposed-comm total here.
4. **MFU** (:func:`set_model_flops_per_step`): declare the model's
   analytic FLOPs per step once and every synced step exports
   ``hvd_mfu_ratio`` (peak FLOPs detected from the local devices or
   passed explicitly).
5. **Regression sentinel** (:class:`RegressionSentinel`): an EWMA
   baseline per phase with robust drift detection. Worker-side it
   drives the ``hvd_step_regression_score{phase}`` gauge; driver-side
   (``runner/http/kv_server.py``) it journals ``step_regression``
   events naming the suspect rank from the critical path, and surfaces
   as an advisory evidence channel the self-healing policy may consult
   (``HOROVOD_POLICY_STEP_REGRESSION`` — inert unset, like every prior
   channel).

Exposed three ways: ``GET /criticalpath`` on the rendezvous KV
(auth-exempt, merged like ``/timeline``; a cold cluster serves an
explicit ``insufficient_samples`` body), the scrape gauges
``hvd_step_phase_seconds{phase}`` / ``hvd_exposed_comm_seconds`` /
``hvd_overlap_hidden_ratio`` / ``hvd_mfu_ratio`` /
``hvd_step_regression_score{phase}``, and
``profiler.summary()["attribution"]``.

Stdlib-only and jax-free by design (like ``tracing.py`` /
``comms_model.py``): the KV server imports this on the driver before
any framework init. jax is touched only inside
:func:`detect_peak_flops`, when the model's FLOPs are declared.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Mapping, Sequence

from .utils.env import get_float, get_int

# ---------------------------------------------------------------------------
# Phase vocabulary (the one constant set tracing and attribution share)
# ---------------------------------------------------------------------------

#: Canonical phase-span names recorded inside a step scope. The elastic
#: step (``parallel/data_parallel.py``) emits exactly these, so it and
#: the attribution plane can never disagree on vocabulary.
SPAN_FORWARD_BACKWARD = "forward_backward"
SPAN_COLLECTIVE = "collective"
SPAN_OPTIMIZER_UPDATE = "optimizer_update"
PHASE_SPAN_NAMES = (SPAN_FORWARD_BACKWARD, SPAN_COLLECTIVE,
                    SPAN_OPTIMIZER_UPDATE)

#: Host spans every factory step opens, on the ring and, as
#: ``jax.profiler.TraceAnnotation``s, on the profiler's clock
#: (``data_parallel._StallWatchedStep``). ``hvd.step`` is the whole
#: wrapper call; its self time is what the hooks cost.
SPAN_STEP = "hvd.step"
SPAN_STEP_DISPATCH = "hvd.step.dispatch"  # self._fn(...): autotune + jit
SPAN_STEP_DRAIN = "hvd.step.drain"  # block_until_ready, watched/sampled
STEP_SPAN_NAMES = (SPAN_STEP, SPAN_STEP_DISPATCH, SPAN_STEP_DRAIN)
#: ``cause`` argument of :data:`SPAN_STEP_DRAIN`.
DRAIN_STALL_WATCH = "stall_watch"
DRAIN_TRACE_SAMPLE = "trace_sample"

#: Spans of the set-up account (``tracing.StepTracer.open_setup``): what a
#: process does from ``import horovod_tpu`` to its first warm step, opened
#: where the work happens and recorded only while the account is open.
SPAN_SETUP_IMPORT = "hvd.setup.import"  # the package's import
SPAN_SETUP_INIT = "hvd.setup.init"  # basics.init
SPAN_SETUP_COMPILE_CACHE = "hvd.setup.compile_cache"  # enable_compile_cache
SPAN_SETUP_OPTIMIZER_INIT = "hvd.setup.optimizer_init"
SPAN_SETUP_PLACE = "hvd.setup.place"  # replicate, shard_*: the host's share
SPAN_SETUP_BUILD = "hvd.setup.build"  # make_train_step
#: From ``jax.monitoring``'s durations (``profiler.CompileAccount``), each
#: recorded when it ends, with its start computed from its seconds.
SPAN_SETUP_TRACE = "hvd.setup.trace"
SPAN_SETUP_LOWER = "hvd.setup.lower"
SPAN_SETUP_BACKEND_COMPILE = "hvd.setup.backend_compile"
SPAN_SETUP_CACHE_READ = "hvd.setup.cache_read"
SETUP_EVENT_SPAN_NAMES = (SPAN_SETUP_TRACE, SPAN_SETUP_LOWER,
                          SPAN_SETUP_BACKEND_COMPILE, SPAN_SETUP_CACHE_READ)

#: Phase scopes inside the compiled step (``jax.named_scope`` through
#: ``profiler.annotate_collective``, which prepends :data:`SCOPE_PREFIX`):
#: they reach the compiled step's ``metadata={op_name=...}`` and from
#: there a device trace. Collective scopes that predate them
#: (``allreduce.bucket<i>.<n>B``, ``grad_reducescatter``, ...) nest inside
#: ``wire``.
SCOPE_PREFIX = "hvd."
SCOPE_WIRE = "wire"
SCOPE_WIRE_COMPRESS = "wire.compress"
SCOPE_WIRE_UNPACK = "wire.unpack"
SCOPE_WIRE_DECOMPRESS = "wire.decompress"
SCOPE_OPTIMIZER = "optimizer"
SCOPE_ATTN_FWD = "attn.fwd"
SCOPE_ATTN_BWD = "attn.bwd"
#: Around the two above where the call is windowed (``flash_attention(...,
#: window=W)``): ``hvd.attn.window/hvd.attn.fwd/flash_attention``. No phase
#: of its own (the innermost phase scope stays ``attn.fwd`` / ``attn.bwd``,
#: which is what sums by phase read): it tells a window layer's kernels
#: from a full layer's in a model that has both (``models/smallthinker``).
SCOPE_ATTN_WINDOW = "attn.window"
#: Around the same two where the call has a block mask (``flash_attention(
#: ..., block_length=B)``), and around all of
#: ``ops.attention.block_diffusion_attention``: the two kernel calls of a
#: block-diffusion step *and* what joins the noisy stream's two sources
#: beside them (the own block's four keys a query in plain XLA, the
#: log-sum-exp merge, the split and the concatenation). No phase either.
SCOPE_ATTN_BLOCKDIFF = "attn.blockdiff"
#: Around ``attn.fwd`` / ``attn.bwd`` where the call's values are not as
#: wide as its keys (latent attention without its rotary split:
#: ``models/kimi_linear.py`` scores over 192 lanes and reads values of
#: 128), placed as ``attn.window`` is and no phase either: it tells the
#: latent layer's kernels from any other's in the step's text.
SCOPE_ATTN_MLA = "attn.mla"
#: The rotary split of latent attention (``models/latent.py``, the one
#: module that opens it): the turn of the queries' and the shared key's
#: rotary lanes, that key's broadcast over the heads and its join with the
#: lanes that are not turned. A phase: its time is neither a projection's
#: (``block.attn_proj``, which surrounds it) nor a kernel's.
SCOPE_MLA_ROPE = "mla.rope"
#: Around everything a multi-token-prediction module adds to a step
#: (``models/joyai_flash.py``: its norms, its joining projection, its
#: decoder layer, its pass through the shared head and its loss term). No
#: phase and no block (the phases and blocks inside keep their owners): it
#: tells the module's operations from the main stack's in the step's text.
SCOPE_MTP = "mtp"
#: JAX's own name-stack component for the forward operations that a
#: ``jax.checkpoint`` (``nn.remat``) runs again inside the backward pass:
#: ``transpose(jvp(...))/rematted_computation/...``. Not a scope this
#: package opens; named here because it is how the recomputed forward of a
#: model with ``remat`` is told from the first one in the step's text.
SCOPE_RECOMPUTE = "rematted_computation"
#: A mixture-of-experts layer (``parallel/moe.py``): the router's picks and
#: slots, the tokens' way into the slots, the grouped expert matmuls, and
#: the way back. The backward pass's operations carry the same scopes.
SCOPE_MOE_ROUTE = "moe.route"
SCOPE_MOE_DISPATCH = "moe.dispatch"
SCOPE_MOE_EXPERTS = "moe.experts"
SCOPE_MOE_COMBINE = "moe.combine"
#: The shared expert of a mixture that has one (``models/kimi_linear.py``):
#: a dense gated feed-forward every token takes beside its routed experts,
#: the model's own branch and no part of ``parallel/moe.py``'s slots. A
#: phase: its time is neither the routed experts' nor a dense layer's.
SCOPE_MOE_SHARED = "moe.shared"
#: A linear-attention layer (``ops/linear_attention.py``,
#: ``models/olmo_hybrid.py``): what turns the projections into the rule's
#: operands (short convolutions, SiLU, l2-norms, ``beta`` and ``g``), the
#: gated delta rule itself (chunk products, triangular solve, the scan
#: over chunks), and the gated RMSNorm of its output. Backward too.
SCOPE_LINATTN_CONV = "linattn.conv"
SCOPE_LINATTN_SCAN = "linattn.scan"
SCOPE_LINATTN_GATE = "linattn.gate"
#: A state-space layer (``ops/ssd.py``, ``models/granite.py``'s Mamba-2
#: mixer): the short convolution over x, B and C with its bias and SiLU and
#: the steps' softplus, the chunked scan itself (decay matrices, chunk
#: products, the states' way across the chunks), and the gated RMSNorm of
#: its output. Backward too.
SCOPE_SSM_CONV = "ssm.conv"
SCOPE_SSM_SCAN = "ssm.scan"
SCOPE_SSM_GATE = "ssm.gate"
#: A mixer that is a doubly gated short convolution (``models/lfm2.py``,
#: the one module that opens it): ``C * conv(B * x)`` between the mixer's
#: two projections, the first gate, the depth-wise causal taps and the
#: second gate. A phase: its time is no projection's (``block.attn_proj``
#: surrounds it). Backward too.
SCOPE_SHORTCONV_MIX = "shortconv.mix"
PHASE_SCOPE_NAMES = tuple(SCOPE_PREFIX + name for name in (
    SCOPE_WIRE, SCOPE_OPTIMIZER, SCOPE_ATTN_FWD, SCOPE_ATTN_BWD,
    SCOPE_MOE_ROUTE, SCOPE_MOE_DISPATCH, SCOPE_MOE_EXPERTS,
    SCOPE_MOE_COMBINE, SCOPE_LINATTN_CONV, SCOPE_LINATTN_SCAN,
    SCOPE_LINATTN_GATE, SCOPE_SSM_CONV, SCOPE_SSM_SCAN, SCOPE_SSM_GATE,
    SCOPE_MOE_SHARED, SCOPE_MLA_ROPE, SCOPE_SHORTCONV_MIX))
#: Block scopes: the parts of a model (``models/*.py``) that no phase
#: names, forward and backward. A phase inside a block stays the phase's
#: (``profiler.owner_of``: the innermost phase scope, else the innermost
#: block scope), so sums by phase read what they read without them.
#: ``embed``: token / position / type embeddings and their norm. ``norm``:
#: the norms on the residual stream and the residual adds beside them.
#: ``attn_proj``: what an attention layer (softmax or linear) does beside
#: its phases: q/k/v/o and gate projections, QK-norm, RoPE, the transposes
#: and reshapes around the kernel call. ``ffn``: a dense feed-forward.
#: ``head``: the final norm, the projection onto the vocabulary (or the
#: masked positions, or the classes), log-softmax and the loss. ``stem`` /
#: ``stage``: ResNet's first convolution with its pooling, and its
#: bottleneck stages. No block scope sits directly around a
#: ``pallas_call``: XLA would name the trace's event after it.
SCOPE_BLOCK_EMBED = "block.embed"
SCOPE_BLOCK_NORM = "block.norm"
SCOPE_BLOCK_ATTN_PROJ = "block.attn_proj"
SCOPE_BLOCK_FFN = "block.ffn"
SCOPE_BLOCK_HEAD = "block.head"
SCOPE_BLOCK_STEM = "block.stem"
SCOPE_BLOCK_STAGE = "block.stage"
BLOCK_SCOPE_NAMES = tuple(SCOPE_PREFIX + name for name in (
    SCOPE_BLOCK_EMBED, SCOPE_BLOCK_NORM, SCOPE_BLOCK_ATTN_PROJ,
    SCOPE_BLOCK_FFN, SCOPE_BLOCK_HEAD, SCOPE_BLOCK_STEM, SCOPE_BLOCK_STAGE))

#: Span categories. ``phase``-cat spans are host-observable compute
#: segments; ``collective``-cat spans are communication; the ``step``
#: span is the envelope the tracer inserts at step end; ``host``-cat
#: spans are the step wrapper's own parts (neither compute nor wire, so
#: they stay in the decomposition's overhead).
CAT_PHASE = "phase"
CAT_COLLECTIVE = "collective"
CAT_STEP = "step"
CAT_HOST = "host"
COMPUTE_CATS = (CAT_PHASE,)
COMM_CATS = (CAT_COLLECTIVE,)

#: The wall-time decomposition every rank's step splits into. These are
#: the ``phase`` label values of ``hvd_step_phase_seconds`` and
#: ``hvd_step_regression_score`` (zero-materialized in ``metrics.py``).
PHASE_COMPUTE = "compute"
PHASE_EXPOSED_COMM = "exposed_comm"
PHASE_STRAGGLER_WAIT = "straggler_wait"
PHASE_OVERHEAD = "overhead"
STEP_PHASES = (PHASE_COMPUTE, PHASE_EXPOSED_COMM, PHASE_STRAGGLER_WAIT,
               PHASE_OVERHEAD)

#: Extra series the regression sentinel baselines alongside the phases.
PHASE_WALL = "wall"


# ---------------------------------------------------------------------------
# Knobs
# ---------------------------------------------------------------------------


def sentinel_alpha() -> float:
    """EWMA weight of the per-phase regression baseline."""
    a = get_float("HOROVOD_STEP_REGRESSION_ALPHA", 0.2)
    return min(max(a, 0.01), 1.0)


def sentinel_sigma() -> float:
    """Drift threshold: a phase whose deviation-normalized score crosses
    this many sigmas (and whose absolute excess is non-trivial) alarms."""
    return max(get_float("HOROVOD_STEP_REGRESSION_SIGMA", 6.0), 1.0)


def sentinel_min_steps() -> int:
    """Baseline warm-up: observations before the sentinel may alarm."""
    return max(2, get_int("HOROVOD_STEP_REGRESSION_MIN_STEPS", 8))


# ---------------------------------------------------------------------------
# Interval arithmetic
# ---------------------------------------------------------------------------


def _merge(intervals: Sequence[tuple[float, float]]
           ) -> list[tuple[float, float]]:
    """Sorted union of half-open intervals."""
    out: list[tuple[float, float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _length(merged: Sequence[tuple[float, float]]) -> float:
    return sum(e - s for s, e in merged)


def _subtract(a: Sequence[tuple[float, float]],
              b: Sequence[tuple[float, float]]
              ) -> list[tuple[float, float]]:
    """Portions of merged ``a`` not covered by merged ``b``."""
    out: list[tuple[float, float]] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            bs, be = b[k]
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if be >= e:
                break
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# ---------------------------------------------------------------------------
# Per-rank decomposition
# ---------------------------------------------------------------------------


def _span_interval(sp: Mapping) -> tuple[float, float, str, str] | None:
    """(start, end, name, cat) of a span record, or None if malformed."""
    try:
        t = float(sp["t"])
        dur = max(float(sp.get("dur", 0.0)), 0.0)
    except (KeyError, TypeError, ValueError):
        return None
    if t != t or dur != dur:  # NaN guard
        return None
    return (t, t + dur, str(sp.get("name", "?")), str(sp.get("cat", "")))


def decompose_step(steprec: Mapping, offset: float = 0.0) -> dict | None:
    """Decompose one rank's step record into the four wall-time phases.

    The step interval is the ENVELOPE of all recorded spans (which, for
    a real factory step, is the step span itself — it covers every inner
    span); interval arithmetic over the rank's own timeline then yields::

        compute       = |union(compute spans)|
        exposed_comm  = |union(collective spans) − union(compute spans)|
        overhead      = wall − |union(compute ∪ collective)|
        straggler_wait = 0   (carved out of exposed_comm by the cluster
                              merge, which alone knows the gating rank)

    so ``sum(phases) == wall`` exactly. ``overlap_hidden_s`` is the
    collective time that WAS hidden under concurrent compute — the
    direct measurement behind ``hvd_overlap_hidden_ratio``. Returns None
    when the record carries no usable spans. ``offset`` (the rank's
    measured clock offset) shifts the reported absolute times onto the
    server timebase; durations are offset-invariant.
    """
    if not isinstance(steprec, Mapping):
        return None
    spans = [si for sp in steprec.get("spans", ()) or ()
             if isinstance(sp, Mapping)
             and (si := _span_interval(sp)) is not None]
    if not spans:
        return None
    t0 = min(s for s, _, _, _ in spans)
    t1 = max(e for _, e, _, _ in spans)
    wall = t1 - t0
    if not (wall > 0.0):
        return None
    compute_m = _merge([(s, e) for s, e, _, c in spans
                        if c in COMPUTE_CATS])
    comm_m = _merge([(s, e) for s, e, _, c in spans if c in COMM_CATS])
    compute_s = _length(compute_m)
    comm_total = _length(comm_m)
    exposed = _length(_subtract(comm_m, compute_m))
    busy = _length(_merge(list(compute_m) + list(comm_m)))
    overhead = max(wall - busy, 0.0)
    hidden = max(comm_total - exposed, 0.0)
    collectives = [
        {"name": n, "t": round(s + offset, 6), "dur": round(e - s, 6)}
        for s, e, n, c in spans if c in COMM_CATS
    ]
    return {
        "step": steprec.get("step"),
        "kind": steprec.get("kind"),
        "synced": bool(steprec.get("synced")),
        "t_start": round(t0 + offset, 6),
        "wall_s": round(wall, 6),
        "phases": {
            PHASE_COMPUTE: round(compute_s, 6),
            PHASE_EXPOSED_COMM: round(exposed, 6),
            PHASE_STRAGGLER_WAIT: 0.0,
            PHASE_OVERHEAD: round(overhead, 6),
        },
        "comm_total_s": round(comm_total, 6),
        "overlap_hidden_s": round(hidden, 6),
        "overlap_hidden_ratio": (round(hidden / comm_total, 6)
                                 if comm_total > 0 else None),
        "collectives": collectives,
    }


# ---------------------------------------------------------------------------
# Cluster merge: (generation, step) groups + the critical path
# ---------------------------------------------------------------------------


def _gen_key(generation) -> int:
    try:
        return int(generation)
    except (TypeError, ValueError):
        return -1


def group_payloads(payloads: Mapping[str, Mapping],
                   rank: str | None = None) -> dict[tuple, dict]:
    """Group shipped trace payloads by (generation, step).

    Returns ``{(gen, step): {rank: {"host", "offset", "rec"}}}`` over
    SYNCED step records only (un-synced records time async dispatch, not
    wall time — decomposing them would report garbage phases). Matching
    keys on (generation, step) exactly like :func:`tracing.compute_skew`
    — the generation scoping keeps a pre-recovery world's steps from
    grouping with the re-formed world's, and the tracer's step-counter
    rebase at world join keeps counters rank-aligned within one.
    """
    groups: dict[tuple, dict] = {}
    for host, payload in (payloads or {}).items():
        if not isinstance(payload, Mapping):
            continue
        r = str(payload.get("rank", "?"))
        if rank is not None and r != str(rank):
            continue
        try:
            offset = float(payload.get("clock_offset_s", 0.0) or 0.0)
        except (TypeError, ValueError):
            offset = 0.0
        gen = _gen_key(payload.get("generation"))
        extras = {}
        for key in ("model_flops_per_step", "peak_flops_per_rank"):
            try:
                v = float(payload.get(key))
                if v > 0:
                    extras[key] = v
            except (TypeError, ValueError):
                pass
        for steprec in payload.get("steps", ()) or ():
            if not isinstance(steprec, Mapping):
                continue
            if not steprec.get("synced"):
                continue
            try:
                step = int(steprec.get("step"))
            except (TypeError, ValueError):
                continue
            if step < 0:  # ambient/eager pseudo-steps never group
                continue
            members = groups.setdefault((gen, step), {})
            held = members.get(r)
            if held is not None and \
                    len(held["rec"].get("spans") or ()) >= \
                    len(steprec.get("spans") or ()):
                continue  # re-shipped window: keep the richer record
            members[r] = {"host": host, "offset": offset,
                          "rec": steprec, **extras}
    return groups


def analyze_group(members: Mapping[str, Mapping]) -> dict | None:
    """One (generation, step) group's cluster view: per-rank phase
    decomposition (with ``straggler_wait`` carved out of exposed comm)
    and the critical path through compute segments and collective
    barriers.

    The barrier model: a collective instance (matched across ranks by
    name, the tracer's ``#seq``-suffixed names included) cannot complete
    before its LAST rank arrives — that rank *gates* the barrier, and
    every earlier arriver's excess time inside the collective is
    ``straggler_wait``, not transfer. The critical path walks the
    matched barriers in arrival order, attributing each inter-barrier
    segment to the gating rank's compute.
    """
    per_rank: dict[str, dict] = {}
    arrivals: dict[str, list] = {}
    env_start = None
    env_end = None
    end_rank = None
    for r, m in sorted(members.items()):
        d = decompose_step(m.get("rec"), offset=m.get("offset", 0.0))
        if d is None:
            continue
        flops = m.get("model_flops_per_step")
        peak = m.get("peak_flops_per_rank")
        if flops and peak and d["wall_s"] > 0:
            d["mfu"] = round(flops / (d["wall_s"] * peak), 6)
        d["host"] = m.get("host", "")
        per_rank[r] = d
        if env_start is None or d["t_start"] < env_start:
            env_start = d["t_start"]
        t_end = d["t_start"] + d["wall_s"]
        if env_end is None or t_end > env_end:
            env_end = t_end
            end_rank = r
        for c in d["collectives"]:
            # Earliest instance per (rank, name): re-recorded names keep
            # their first arrival, matching compute_skew's contract.
            slot = arrivals.setdefault(c["name"], [])
            if not any(a[0] == r for a in slot):
                slot.append((r, d["host"], c["t"], c["dur"]))
    if not per_rank:
        return None
    # -- critical path -------------------------------------------------------
    instances = sorted(
        ((name, arr) for name, arr in arrivals.items()),
        key=lambda na: min(a[2] for a in na[1]))
    path: list[dict] = []
    cursor = env_start
    gating_counts: dict[str, int] = {}
    waits: dict[str, float] = {}
    for name, arr in instances:
        t_min = min(a[2] for a in arr)
        g_rank, g_host, t_enter, g_dur = max(arr, key=lambda a: a[2])
        exit_t = max(a[2] + a[3] for a in arr)
        if t_enter > cursor:
            path.append({"kind": "compute", "rank": g_rank,
                         "host": g_host,
                         "dur_s": round(t_enter - cursor, 6)})
        path.append({
            "kind": "collective", "name": name,
            "gating_rank": g_rank, "gating_host": g_host,
            "skew_s": round(t_enter - t_min, 6),
            "t_enter_s": round(t_enter - env_start, 6),
            "dur_s": round(max(exit_t - t_enter, 0.0), 6),
            "ranks": len(arr),
        })
        gating_counts[g_rank] = gating_counts.get(g_rank, 0) + 1
        for r, _, t_r, dur_r in arr:
            wait = max(min(t_enter - t_r, dur_r), 0.0)
            if wait > 0:
                waits[r] = waits.get(r, 0.0) + wait
        cursor = max(cursor, exit_t)
    if env_end is not None and env_end > cursor and end_rank is not None:
        path.append({"kind": "compute", "rank": end_rank,
                     "host": per_rank[end_rank]["host"],
                     "dur_s": round(env_end - cursor, 6)})
        cursor = env_end
    # -- straggler_wait: carved out of exposed comm, sum preserved -----------
    for r, d in per_rank.items():
        wait = min(waits.get(r, 0.0), d["phases"][PHASE_EXPOSED_COMM])
        d["phases"][PHASE_STRAGGLER_WAIT] = round(wait, 6)
        d["phases"][PHASE_EXPOSED_COMM] = round(
            d["phases"][PHASE_EXPOSED_COMM] - wait, 6)
    suspect = (max(gating_counts.items(), key=lambda kv: kv[1])[0]
               if gating_counts else None)
    return {
        "ranks": per_rank,
        "critical_path": path,
        "critical_path_s": round((cursor - env_start)
                                 if env_start is not None else 0.0, 6),
        "wall_s": round(max(d["wall_s"] for d in per_rank.values()), 6),
        "suspect_rank": suspect,
        "suspect_host": (per_rank.get(suspect, {}).get("host")
                         if suspect is not None else None),
    }


def analyze_cluster(payloads: Mapping[str, Mapping],
                    steps: int | None = None,
                    rank: str | None = None) -> dict:
    """The driver-side merge behind ``GET /criticalpath``: every
    (generation, step) group the shipped payloads cover (bounded by the
    per-rank ring depth), newest LAST. ``steps``/``rank`` are the query
    filters — last N groups, one rank's decomposition. A world with no
    synced samples yet (cold start, ``HOROVOD_TRACE_SAMPLE=0``) serves
    an explicit ``insufficient_samples`` status, never an error."""
    groups = group_payloads(payloads, rank=rank)
    keys = sorted(groups)
    if steps is not None and steps > 0:
        keys = keys[-steps:]
    out_groups = []
    for key in keys:
        analyzed = analyze_group(groups[key])
        if analyzed is None:
            continue
        analyzed["generation"] = key[0]
        analyzed["step"] = key[1]
        out_groups.append(analyzed)
    return {
        "status": "ok" if out_groups else "insufficient_samples",
        "groups": out_groups,
    }


# ---------------------------------------------------------------------------
# Regression sentinel
# ---------------------------------------------------------------------------


class RegressionSentinel:
    """EWMA baseline per phase with robust drift detection.

    For each observed series (the four phases plus ``wall``) the
    sentinel keeps an EWMA mean and an EWMA mean-absolute-deviation;
    the **score** of a new value is its positive excess over the mean,
    normalized by the deviation (floored at 5% of the mean so a
    deterministic baseline cannot manufacture infinite sigmas). A score
    crossing ``HOROVOD_STEP_REGRESSION_SIGMA`` after the
    ``HOROVOD_STEP_REGRESSION_MIN_STEPS`` warm-up **alarms** — once,
    latched until the score falls below half the threshold, so a step
    regression journals one event, not one per step. ``excess_s`` (the
    raw seconds over baseline) is the magnitude consumers get; it is
    directly comparable to the policy plane's other lateness-seconds
    evidence channels.
    """

    def __init__(self, alpha: float | None = None,
                 sigma: float | None = None,
                 min_steps: int | None = None):
        self._alpha = sentinel_alpha() if alpha is None else alpha
        self._sigma = sentinel_sigma() if sigma is None else sigma
        self._min_steps = (sentinel_min_steps() if min_steps is None
                           else min_steps)
        self._lock = threading.Lock()
        self._mean: dict[str, float] = {}
        self._dev: dict[str, float] = {}
        self._count = 0
        self._alarmed: set[str] = set()
        self._alarms_total = 0

    def observe(self, phases: Mapping[str, float],
                wall: float | None = None) -> dict:
        """Fold one step's phase seconds into the baselines. Returns
        ``{"scores", "excess_s", "alarms"}`` where ``alarms`` lists the
        phases that newly crossed the drift threshold this observation
        (empty during warm-up and while latched)."""
        values = {str(k): float(v) for k, v in phases.items()
                  if isinstance(v, (int, float)) and v == v}
        if wall is not None and wall == wall:
            values[PHASE_WALL] = float(wall)
        scores: dict[str, float] = {}
        excess: dict[str, float] = {}
        alarms: list[str] = []
        a = self._alpha
        with self._lock:
            warmed = self._count >= self._min_steps
            for phase, v in values.items():
                mean = self._mean.get(phase)
                if mean is None:
                    self._mean[phase] = v
                    self._dev[phase] = 0.0
                    scores[phase] = 0.0
                    excess[phase] = 0.0
                    continue
                dev = self._dev.get(phase, 0.0)
                if warmed:
                    floor = max(dev, 0.05 * max(mean, 0.0), 1e-6)
                    score = max(v - mean, 0.0) / floor
                    score = min(score, 1e3)
                    scores[phase] = round(score, 4)
                    excess[phase] = round(max(v - mean, 0.0), 6)
                    if score >= self._sigma:
                        if phase not in self._alarmed:
                            self._alarmed.add(phase)
                            self._alarms_total += 1
                            alarms.append(phase)
                    elif score < self._sigma / 2.0:
                        self._alarmed.discard(phase)
                else:
                    scores[phase] = 0.0
                    excess[phase] = 0.0
                # Baseline update AFTER scoring: drift registers against
                # the pre-update baseline before the EWMA absorbs it
                # (the comms residual's contract).
                self._mean[phase] = mean + a * (v - mean)
                self._dev[phase] = dev + a * (abs(v - mean) - dev)
            self._count += 1
        return {"scores": scores, "excess_s": excess, "alarms": alarms}

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "steps_observed": self._count,
                "baseline_s": {p: round(v, 6)
                               for p, v in sorted(self._mean.items())},
                "deviation_s": {p: round(v, 6)
                                for p, v in sorted(self._dev.items())},
                "alarmed": sorted(self._alarmed),
                "alarms_total": self._alarms_total,
                "sigma": self._sigma,
                "min_steps": self._min_steps,
            }


# ---------------------------------------------------------------------------
# MFU machinery
# ---------------------------------------------------------------------------

#: bf16 dense peak FLOPs/s per chip by device kind substring (no
#: sparsity), so any workload can price MFU.
CHIP_PEAK_FLOPS = {
    "v6e": 918e12,
    "v6 lite": 918e12,
    "v5p": 459e12,
    "v5e": 197e12,
    "v5 lite": 197e12,
    "v4": 275e12,
}


def peak_flops_for_kind(device_kind: str) -> float:
    """Peak bf16 FLOPs/s for a device-kind string. A kind the table
    does not list is an error: a missing peak would turn every MFU
    into ``null`` and an assumed one would make it wrong."""
    kind = str(device_kind or "").lower()
    for key, peak in CHIP_PEAK_FLOPS.items():
        if key in kind:
            return peak
    raise ValueError(
        f"no peak FLOP/s known for device kind {device_kind!r}: add it "
        f"to attribution.CHIP_PEAK_FLOPS (known: "
        f"{', '.join(CHIP_PEAK_FLOPS)})")


def detect_peak_flops() -> float | None:
    """This process's aggregate peak FLOPs/s (per-chip peak × local
    device count). None where there is no chip to have a peak — a CPU
    mesh, or the driver, where jax may not be importable; an
    accelerator the table does not know raises
    (:func:`peak_flops_for_kind`)."""
    try:
        import jax
    except ImportError:
        return None
    devices = jax.local_devices()
    if not devices or devices[0].platform == "cpu":
        return None
    return peak_flops_for_kind(devices[0].device_kind) * len(devices)


# ---------------------------------------------------------------------------
# Worker-side state: model FLOPs, the local sentinel, the last step
# ---------------------------------------------------------------------------

_lock = threading.Lock()
_model_flops: float | None = None
_peak_flops: float | None = None
_sentinel: RegressionSentinel | None = None
_last_step: dict | None = None


def set_model_flops_per_step(flops: float | None,
                             peak_flops: float | None = None) -> None:
    """Declare the model's analytic FLOPs per training step for THIS
    process's devices — the MFU numerator (``hvd_mfu_ratio`` =
    flops / (step wall × peak)). Call it after ``hvd.init()``: the peak
    is resolved HERE, once, from the local devices
    (:func:`detect_peak_flops`) unless ``peak_flops`` overrides it, so
    a chip the table does not know raises in the caller's face and not
    inside the per-step hook, whose guard would swallow it. ``None``
    clears the setting (the gauge stops updating)."""
    global _model_flops, _peak_flops
    with _lock:
        peak = _peak_flops
    if flops is None:
        peak = None
    elif peak_flops is not None:
        peak = float(peak_flops) if peak_flops > 0 else None
    elif peak is None:
        peak = detect_peak_flops()
    with _lock:
        _model_flops = float(flops) if flops else None
        _peak_flops = peak


def model_flops() -> tuple[float | None, float | None]:
    """(flops_per_step, peak_flops_per_process) as declared."""
    with _lock:
        return _model_flops, _peak_flops


def local_sentinel() -> RegressionSentinel:
    global _sentinel
    with _lock:
        if _sentinel is None:
            _sentinel = RegressionSentinel()
        return _sentinel


def reset_for_testing() -> None:
    """Fresh worker-side state (model FLOPs kept out too; env knobs
    re-read on next use)."""
    global _model_flops, _peak_flops, _sentinel, _last_step
    with _lock:
        _model_flops = None
        _peak_flops = None
        _sentinel = None
        _last_step = None


def note_step(steprec: Mapping) -> dict | None:
    """Fold one completed SYNCED step into the worker-side attribution
    plane: decompose it, export the scrape gauges, feed the local
    regression sentinel. Called by :meth:`tracing.StepTracer._end_step`
    on every synced step; cheap (interval math over ≤64 spans) and never
    raises past its caller's guard."""
    global _last_step
    d = decompose_step(steprec)
    if d is None:
        return None
    flops, peak = model_flops()
    if flops and peak and d["wall_s"] > 0:
        d["mfu"] = round(flops / (d["wall_s"] * peak), 6)
    verdict = local_sentinel().observe(d["phases"], wall=d["wall_s"])
    d["regression_scores"] = verdict["scores"]
    with _lock:
        _last_step = d
    try:
        from . import metrics

        for phase in STEP_PHASES:
            metrics.STEP_PHASE_SECONDS.set(
                d["phases"].get(phase, 0.0), phase=phase)
        metrics.EXPOSED_COMM.set(d["phases"][PHASE_EXPOSED_COMM]
                                 + d["phases"][PHASE_STRAGGLER_WAIT])
        ratio = d.get("overlap_hidden_ratio")
        if ratio is not None:
            metrics.OVERLAP_HIDDEN.set(ratio)
        if d.get("mfu") is not None:
            metrics.MFU_RATIO.set(d["mfu"])
        for phase, score in verdict["scores"].items():
            metrics.STEP_REGRESSION_SCORE.set(score, phase=phase)
    except Exception:  # noqa: BLE001 — gauges are advisory
        pass
    return d


def predicted_exposed_comm_s() -> float | None:
    """The α–β model's price for this process's gradient wire under the
    LIVE fusion config (:func:`comms_model.predict_step_comm_s`) — the
    phase-resolved roofline the observed exposed-comm phase is compared
    against. None until the model has fitted and noted a leaf layout."""
    try:
        from . import comms_model

        return comms_model.predict_step_comm_s()
    except Exception:  # noqa: BLE001 — prediction is advisory
        return None


def summary() -> dict:
    """``profiler.summary()["attribution"]``: the last synced step's
    decomposition + MFU, the predicted-vs-observed exposed-comm residual
    (the roofline's phase-resolved channel), the model-FLOPs setting,
    and the local sentinel state."""
    with _lock:
        last = dict(_last_step) if _last_step is not None else None
    flops, peak = (_model_flops, _peak_flops)
    out: dict[str, Any] = {
        "last_step": last,
        "model_flops_per_step": flops,
        "peak_flops_per_rank": peak,
        "sentinel": local_sentinel().snapshot(),
    }
    predicted = predicted_exposed_comm_s()
    out["exposed_comm_predicted_s"] = (round(predicted, 6)
                                       if predicted is not None else None)
    if predicted is not None and last is not None:
        observed = (last["phases"][PHASE_EXPOSED_COMM]
                    + last["phases"][PHASE_STRAGGLER_WAIT])
        out["exposed_comm_residual_s"] = round(observed - predicted, 6)
    else:
        out["exposed_comm_residual_s"] = None
    return out


def rendezvous_endpoint() -> tuple[str, str] | None:
    addr = os.environ.get("HOROVOD_RENDEZVOUS_ADDR", "")
    port = os.environ.get("HOROVOD_RENDEZVOUS_PORT", "")
    return (addr, port) if addr and port else None


def flight_summary(snap: Mapping) -> dict | None:
    """The attribution section a ``flight_record`` dump attaches: the
    last SYNCED step's phase decomposition from the ring, plus — for
    every still-OPEN collective span (the wedge) — the gating rank the
    cluster's partial critical path names, fetched best-effort from
    ``GET /criticalpath`` (a wedged rank can still read; 2s budget).
    Returns None when the ring holds nothing attributable."""
    out: dict[str, Any] = {}
    last = None
    for steprec in reversed(list(snap.get("steps", ()) or ())):
        if isinstance(steprec, Mapping) and steprec.get("synced"):
            last = decompose_step(steprec)
            if last is not None:
                break
    if last is not None:
        last.pop("collectives", None)
        out["last_synced_step"] = last
    wedged = [sp for sp in snap.get("open_spans", ()) or ()
              if isinstance(sp, Mapping)
              and sp.get("cat") in COMM_CATS]
    if wedged:
        gating: dict | None = None
        endpoint = rendezvous_endpoint()
        if endpoint is not None:
            try:
                import json
                from urllib.request import urlopen

                addr, port = endpoint
                with urlopen(f"http://{addr}:{port}/criticalpath",
                             timeout=2.0) as r:
                    cluster = json.loads(r.read())
                gating = {
                    node["name"]: {"rank": node.get("gating_rank"),
                                   "host": node.get("gating_host"),
                                   "skew_s": node.get("skew_s")}
                    for g in cluster.get("groups", ())
                    for node in g.get("critical_path", ())
                    if node.get("kind") == "collective"
                }
            except Exception:  # noqa: BLE001 — the dump must still land
                gating = None
        out["wedged_collectives"] = [
            {
                "name": sp.get("name"),
                "age_s": sp.get("age_s"),
                **({"gating": gating[str(sp.get("name"))]}
                   if gating and str(sp.get("name")) in gating else {}),
            }
            for sp in wedged
        ]
    return out or None
