"""Cross-rank step tracing: clock-aligned spans, skew attribution, and the
flight recorder.

The per-process Chrome timeline (:mod:`horovod_tpu.timeline`) answers
"what did THIS process just do"; the metrics plane (PR 5) answers "what
are the cluster's aggregate numbers". Neither answers the straggler
question ROADMAP item 3 needs: *which rank made the collective slow, and
what was it doing instead*. This module is that sensor layer:

1. **Span API**: :func:`span` records host-observable phases — ``step``,
   ``forward``/``backward`` (where separable), per-collective dispatch,
   ``optimizer_update``, ``param_allgather`` — into a per-rank
   :class:`StepTracer` (ring buffer of the last K steps) AND dual-emits
   onto the per-process Chrome timeline. Factory train steps open a step
   scope per call (``parallel/data_parallel.py``); eager collective
   dispatch (``ops/collective_ops.py``) records per-op spans.
2. **Clock alignment**: :class:`ClockSync` piggybacks NTP-style offset
   estimation on the heartbeat PUTs the elastic worker already sends —
   the server stamps its wall clock into the 200 reply, and the worker's
   send/receive timestamps bound the offset to ±RTT/2. Every rank thus
   carries a server-relative offset ± error bound, shipped with its
   spans so the merge can put all ranks on one timebase.
3. **Trace shipping**: every ``HOROVOD_TRACE_SAMPLE``-th step's spans are
   posted (bounded payload, dedicated background thread, 1-attempt/2s
   client) to ``PUT /trace/<host>`` on the rendezvous KV server, whose
   ``GET /timeline`` serves the merged, offset-corrected Chrome/Perfetto
   JSON with one track per rank and whose ``/metrics`` gains
   ``hvd_collective_skew_seconds{rank}`` / ``hvd_straggler_score{host}``
   from :func:`compute_skew` (see ``runner/http/kv_server.py``).
4. **Flight recorder**: the ring buffer of the last K steps' spans is
   dumped through the lifecycle journal (``flight_record`` event) on
   abort-consume, stall shutdown, deadman exit, and SIGTERM drain — so
   every rung of the recovery ladder leaves a postmortem of what each
   rank was doing when the world wedged.

Knob (see docs/timeline.md): ``HOROVOD_TRACE_SAMPLE`` — ship every Nth
step's spans (0 = default = record locally only, never ship; shipping
syncs the sampled step). The flight recorder's depth (8 steps) and the
per-step span cap (64; overflow is counted, never silently unbounded)
are :class:`StepTracer` constructor arguments.

A step scope and a step's host spans (:meth:`StepTracer.step_scope`,
:meth:`StepTracer.host_span`) are real spans: each is a
``jax.profiler.TraceAnnotation`` — a device trace holds it on the host
plane, on the trace's own clock — **and** a ring record carrying name,
start, duration, an id and its parent span's id, so a span's self time
is computed (:func:`self_times`), not guessed from nesting. With no
profiler session open a span costs two clock reads, one ring append and
an annotation that does nothing: no environment read, no lock beyond the
ring's.

The **set-up account** (:class:`SetupAccount`) is one bounded list beside
the ring for the one stretch the ring cannot keep: from the first line of
``import horovod_tpu`` to the end of the first factory-step call in which
nothing compiled. While it is open every span recorded here is also kept
there whole, and ``profiler.CompileAccount`` adds a span for each tracing,
lowering, backend compile and cache read that ``jax.monitoring`` reports;
once closed it costs one attribute test. ``hvd.cache_stats()["setup"]``
serves it; ``hvd.shutdown()`` / a new ``hvd.init()`` open the next one.

Stdlib-only and jax-free to import by design: the KV server (driver
side, before any framework init) imports :func:`compute_skew` from here.
``jax.profiler`` is imported where the first span opens.
"""

from __future__ import annotations

import collections
import itertools
import json
import os
import socket
import threading
import time
from typing import Any, Callable, Mapping

from .attribution import (CAT_HOST, SPAN_SETUP_LOWER, SPAN_SETUP_PLACE,
                          SPAN_SETUP_TRACE, _length, _merge)
from .utils.env import get_float, get_int

#: KV scope trace payloads ship to (``PUT /trace/<host>``).
TRACE_SCOPE = "trace"


def sample_every() -> int:
    """Ship every Nth step's spans to the rendezvous KV (0 disables
    shipping; local ring recording is always on)."""
    return get_int("HOROVOD_TRACE_SAMPLE", 0)


class _NoAnnotation:
    """Stands in for ``TraceAnnotation`` where jax cannot be imported."""

    def __init__(self, name, **kwargs):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_annotation_cls = None


def annotation(name: str, args: Mapping[str, Any] | None = None):
    """A ``jax.profiler.TraceAnnotation`` named ``name``: with a profiler
    session open it lands on the trace's host plane, ``args`` as the
    event's statistics; with none it costs a fraction of a microsecond.
    The class is looked up at the first span, not at import."""
    global _annotation_cls
    if _annotation_cls is None:
        try:
            from jax.profiler import TraceAnnotation

            _annotation_cls = TraceAnnotation
        except Exception:  # noqa: BLE001 — no jax here: ring records only
            _annotation_cls = _NoAnnotation
    return _annotation_cls(name, **args) if args else _annotation_cls(name)


def _rank() -> str:
    return os.environ.get("HOROVOD_RANK", "0") or "0"


def _host() -> str:
    return os.environ.get("HOROVOD_HOSTNAME", "") or socket.gethostname()


# ---------------------------------------------------------------------------
# Clock alignment
# ---------------------------------------------------------------------------


class ClockSync:
    """NTP-style offset of this process's wall clock vs the rendezvous
    server's, estimated from heartbeat round trips.

    For each exchange the worker records ``t_send``/``t_recv`` on its own
    wall clock and the server stamps ``t_server`` into the reply; the
    classic bound is::

        offset = t_server - (t_send + t_recv) / 2    (server - local)
        error  = (t_recv - t_send) / 2               (half the RTT)

    The estimate is the minimum-error sample over a sliding window (the
    standard NTP minimum-RTT filter: queueing delay only ever inflates
    the RTT, so the tightest round trip is the most truthful). ``clock``
    is injectable so tests can simulate a skewed rank.
    """

    WINDOW = 16

    def __init__(self, clock: Callable[[], float] = time.time):
        self._clock = clock
        self._lock = threading.Lock()
        self._samples: collections.deque = collections.deque(
            maxlen=self.WINDOW)

    def now(self) -> float:
        """This process's wall clock (the one spans are stamped with)."""
        return self._clock()

    def observe(self, t_send: float, t_recv: float,
                t_server: float) -> None:
        rtt = max(float(t_recv) - float(t_send), 0.0)
        sample = (rtt / 2.0,
                  float(t_server) - (float(t_send) + float(t_recv)) / 2.0)
        with self._lock:
            self._samples.append(sample)
        try:
            from . import metrics

            metrics.CLOCK_OFFSET.set(self.offset())
            err = self.error()
            if err is not None:
                metrics.CLOCK_ERROR.set(err)
        except Exception:  # noqa: BLE001 — observability is best-effort
            pass

    def _best(self):
        with self._lock:
            if not self._samples:
                return None
            return min(self._samples, key=lambda s: s[0])

    def offset(self) -> float:
        """Best estimate of (server wall clock − local wall clock), or
        0.0 before any exchange (merge degrades to raw local clocks)."""
        best = self._best()
        return best[1] if best is not None else 0.0

    def error(self) -> float | None:
        """± bound on :meth:`offset` (half the best sample's RTT), or
        None before any exchange."""
        best = self._best()
        return best[0] if best is not None else None

    def synced(self) -> bool:
        return self._best() is not None


# ---------------------------------------------------------------------------
# Step tracer + flight-recorder ring
# ---------------------------------------------------------------------------


class StepRecord:
    """One step's spans. ``synced=True`` means the step was blocked on
    (``block_until_ready``) so its duration is the real step time, not
    just async dispatch; ``ship`` marks it for posting to the KV."""

    __slots__ = ("step", "kind", "t_start", "spans", "dropped",
                 "synced", "ship", "dur", "span_id", "args",
                 "closes_setup")

    def __init__(self, step: int, kind: str, t_start: float,
                 span_id: int | None = None):
        self.step = step
        self.kind = kind
        self.t_start = t_start
        self.spans: list[dict] = []
        self.dropped = 0
        self.synced = False
        self.ship = False
        self.dur: float | None = None
        self.span_id = span_id  # the step span's id: its children's parent
        self.args: Mapping | None = None  # more args for the step span
        self.closes_setup = False  # the first warm call: the account ends

    def as_dict(self) -> dict:
        out = {
            "step": self.step,
            "kind": self.kind,
            "t": self.t_start,
            "synced": self.synced,
            "spans": list(self.spans),
        }
        if self.dur is not None:
            out["dur"] = self.dur
        if self.dropped:
            out["dropped_spans"] = self.dropped
        return out


class SetupAccount:
    """The spans of one set-up, kept past the ring: a bounded list
    (overflow is counted as ``dropped``, as the ring counts it) on the
    tracer's clock. ``short`` counts the tracings and lowerings too brief
    to keep as spans: ``{name: [count, seconds]}``."""

    CAP = 2048
    #: A tracing or lowering shorter than this is counted, not kept:
    #: ``jax.numpy``'s own functions are jitted, and one first call reports
    #: hundreds of them, every one inside the step's own tracing. A
    #: backend compile or a cache read is always kept: one a program.
    SHORT_S = 0.005
    SHORT_NAMES = (SPAN_SETUP_TRACE, SPAN_SETUP_LOWER)

    __slots__ = ("t0", "spans", "dropped", "short", "closed_at")

    def __init__(self, t0: float):
        self.t0 = t0
        self.spans: list[dict] = []
        self.dropped = 0
        self.short: dict[str, list] = {}
        self.closed_at: float | None = None

    def keep(self, sp: dict) -> None:
        if len(self.spans) >= self.CAP:
            self.dropped += 1
        else:
            self.spans.append(sp)

    def by_name(self) -> dict:
        """Per span name: ``count``, ``total_s`` (the union of the name's
        intervals: a nested jit's tracing inside its caller's counts
        once), ``self_s`` (:func:`self_times`, summed) and, where some
        were too brief to keep, ``short``."""
        spans = list(self.spans)
        rows: dict[str, dict] = {}
        for sp, own in zip(spans, self_times(spans)):
            row = rows.setdefault(
                sp["name"], {"count": 0, "total_s": [], "self_s": 0.0})
            row["count"] += 1
            row["total_s"].append((sp["t"], sp["t"] + sp["dur"]))
            row["self_s"] += own
        for row in rows.values():
            row["total_s"] = round(_length(_merge(row["total_s"])), 6)
            row["self_s"] = round(row["self_s"], 6)
        for name, (count, seconds) in self.short.items():
            rows.setdefault(
                name, {"count": 0, "total_s": 0.0, "self_s": 0.0})[
                "short"] = {"count": count, "seconds": round(seconds, 6)}
        return rows

    def summary(self) -> dict:
        return {"open": self.closed_at is None, "dropped": self.dropped,
                "spans": list(self.spans), "by_name": self.by_name()}


class StepTracer:
    """Per-process span recorder: a ring of the last K steps (the flight
    recorder) plus the currently open step and spans. Recording is cheap
    (one dict append under a lock) and always on; only shipping and the
    sampled-step sync are gated by ``HOROVOD_TRACE_SAMPLE``.

    ``ring_steps`` is the flight recorder's depth; ``max_spans`` caps a
    step's spans (overflow is counted as ``dropped_spans``). ``setup`` is
    the newest :class:`SetupAccount`, open or closed, or None where none
    was ever opened."""

    def __init__(self, clock_sync: ClockSync | None = None,
                 ring_steps: int = 8, max_spans: int = 64):
        self.clock = clock_sync or ClockSync()
        self.max_spans = max(1, int(max_spans))
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(
            maxlen=max(1, int(ring_steps)))
        self._current: StepRecord | None = None
        self._ambient: StepRecord | None = None
        self._open: dict[int, tuple] = {}
        self._next_open = 0
        self._step_count = 0
        self._dispatch_seq: dict[str, int] = {}
        # Span ids (next() on a count is atomic) and, per thread, the ids
        # of the spans open on it: the top one is the next span's parent.
        self._ids = itertools.count(1)
        self._tls = threading.local()
        self.setup: SetupAccount | None = None
        self._setup: SetupAccount | None = None  # ``setup`` while open

    # -- the set-up account ---------------------------------------------------

    @property
    def setup_open(self) -> bool:
        return self._setup is not None

    def open_setup(self, t0: float | None = None) -> None:
        """Start a new set-up account at ``t0`` (now, unless the caller
        read the clock earlier: the package's import does, at its first
        line). An account still open is dropped for it."""
        with self._lock:
            self.setup = self._setup = SetupAccount(
                self.clock.now() if t0 is None else t0)

    def reopen_setup(self) -> None:
        """A new account unless one is open: ``hvd.shutdown()`` and a new
        ``hvd.init()`` begin the next set-up (a re-formation, a resume)."""
        if self._setup is None:
            self.open_setup()

    def close_setup(self) -> None:
        """Close the open account for good and journal ``setup_finished``
        once. ``_StallWatchedStep`` marks the first call of a factory
        step in which nothing compiled, and the end of that step closes."""
        with self._lock:
            account = self._setup
            if account is None:
                return
            self._setup = None
            account.closed_at = self.clock.now()
        try:
            from . import metrics

            if metrics.journal() is not None:
                metrics.event(
                    "setup_finished",
                    seconds=round(account.closed_at - account.t0, 6),
                    spans=len(account.spans), dropped=account.dropped,
                    by_name=account.by_name())
        except Exception:  # noqa: BLE001 — journaling is best-effort
            pass

    def setup_summary(self) -> dict:
        """``hvd.cache_stats()["setup"]``."""
        account = self.setup
        if account is None:
            return {"open": False, "dropped": 0, "spans": [], "by_name": {}}
        with self._lock:
            return account.summary()

    def setup_event(self, name: str, seconds: float,
                    args: Mapping[str, Any] | None = None) -> None:
        """``jax.monitoring`` says ``seconds`` of ``name`` ended now: keep
        it in the open account as a span that began ``seconds`` ago, under
        the span open on this thread. The events come innermost first, so
        one that began before earlier ones of the same thread and parent
        becomes their parent: a nested jit's tracing is a child of its
        caller's, and a union or a self time counts it once. A tracing or
        lowering under ``SetupAccount.SHORT_S`` is counted in ``short``
        instead."""
        account = self._setup
        if account is None:
            return
        if seconds < account.SHORT_S and name in account.SHORT_NAMES:
            with self._lock:
                counted = account.short.setdefault(name, [0, 0.0])
                counted[0] += 1
                counted[1] += seconds
            return
        start = round(self.clock.now() - seconds, 6)
        stack = self._stack()
        parent = stack[-1] if stack else None
        sp = {"name": name, "cat": CAT_HOST, "t": start,
              "dur": round(float(seconds), 6), "id": next(self._ids)}
        if parent is not None:
            sp["parent"] = parent
        if args:
            sp["args"] = dict(args)
        # This thread's events that no later one has taken in yet; those
        # under a span that has closed since can no longer be.
        waiting = []
        for earlier in getattr(self._tls, "events", ()):
            above = earlier.get("parent")
            if above == parent and earlier["t"] >= start:
                earlier["parent"] = sp["id"]
            elif above is None or above in stack:
                waiting.append(earlier)
        waiting.append(sp)
        self._tls.events = waiting
        with self._lock:
            if self._setup is account:
                account.keep(sp)

    # -- span recording -----------------------------------------------------

    def _stack(self) -> list:
        try:
            return self._tls.stack
        except AttributeError:
            stack = self._tls.stack = []
            return stack

    def _push(self) -> tuple[int, int | None]:
        """Open a span on this thread: ``(its id, its parent's id)``."""
        stack = self._stack()
        parent = stack[-1] if stack else None
        span_id = next(self._ids)
        stack.append(span_id)
        return span_id, parent

    def _pop(self, span_id: int) -> None:
        stack = self._stack()
        if stack and stack[-1] == span_id:
            stack.pop()
        elif span_id in stack:  # closed out of order: drop it and above
            del stack[stack.index(span_id):]

    def in_step(self) -> bool:
        return self._current is not None

    def begin_span(self, name: str, cat: str, span_id: int | None = None,
                   parent: int | None = None) -> int:
        """Register an in-flight span (so a wedge shows up in the flight
        record as an OPEN span with its age). Returns a token for
        :meth:`end_span`."""
        t0 = self.clock.now()
        with self._lock:
            token = self._next_open
            self._next_open += 1
            self._open[token] = (name, cat, t0, span_id, parent)
        return token

    def end_span(self, token: int,
                 args: Mapping[str, Any] | None = None) -> None:
        now = self.clock.now()
        with self._lock:
            opened = self._open.pop(token, None)
            if opened is None:
                return
            name, cat, t0, span_id, parent = opened
            self._record_locked(name, cat, t0, now - t0, args, span_id,
                                parent)

    def host_span(self, name: str,
                  args: Mapping[str, Any] | None = None) -> "_HostSpan":
        """One part of a step as a real span: ``with
        tracer.host_span(attribution.SPAN_STEP_DISPATCH): ...``."""
        return _HostSpan(self, name, args)

    def record(self, name: str, cat: str, t_start: float, dur: float,
               args: Mapping[str, Any] | None = None) -> None:
        """Record a completed span directly, from its start and
        duration."""
        with self._lock:
            self._record_locked(name, cat, t_start, dur, args)

    def record_dispatch(self, name: str, cat: str = "collective",
                        unique: bool = False) -> None:
        """Record a host-plane collective DISPATCH as a zero-duration
        span, suffixed with a per-name sequence number.

        The native runtime (``horovod_tpu/runtime``) calls this at every
        enqueue — the funnel all torch/TF-surface and hierarchical-leg
        collectives pass through — so eager host-plane workloads feed the
        cross-rank skew attribution, not just compiled factory steps.
        The sequence suffix makes each *instance* of a repeated name
        (``allreduce.weight`` every step) its own matched group: ranks
        run the host plane in lockstep program order, so ``name#k`` pairs
        the k-th dispatch across ranks and the skew gauges track the
        CURRENT lateness instead of the first instance ever seen. The
        counter resets with :meth:`rebase` at world join, keeping
        survivors and replacements aligned within a generation.

        ``unique=True`` marks a name that is already one-per-call
        (auto-generated ``op.N`` counters — lockstep-identical across
        ranks, so they self-match): it is recorded as-is, keeping the
        seq map bounded by the *named* collective vocabulary instead of
        growing one permanent entry per auto-named enqueue.
        """
        t0 = self.clock.now()
        with self._lock:
            if unique:
                self._record_locked(name, cat, t0, 0.0, None)
                return
            seq = self._dispatch_seq.get(name, 0) + 1
            self._dispatch_seq[name] = seq
            self._record_locked(f"{name}#{seq}", cat, t0, 0.0, None)

    def _record_locked(self, name, cat, t_start, dur, args,
                       span_id=None, parent=None) -> None:
        target = self._current
        if target is None:
            # Spans outside any step (eager scripting) collect into an
            # ambient pseudo-step rotated into the ring when full.
            if self._ambient is None:
                self._ambient = StepRecord(-1, "eager", t_start)
            target = self._ambient
        account = self._setup
        full = len(target.spans) >= self.max_spans
        if full:
            target.dropped += 1
        if not full or account is not None:
            sp = {"name": name, "cat": cat,
                  "t": round(float(t_start), 6),
                  "dur": round(float(dur), 6)}
            if span_id is not None:
                sp["id"] = span_id
                sp["step"] = target.step
                if parent is not None:
                    sp["parent"] = parent
            if args:
                sp["args"] = dict(args)
            if not full:
                target.spans.append(sp)
            if account is not None:
                account.keep(sp)
        if (target is self._ambient
                and len(target.spans) >= self.max_spans):
            # Full ambient window: rotate it into the ring so eager-only
            # scripts produce bounded records too (same cap as steps).
            self._ring.append(self._ambient.as_dict())
            self._ambient = None

    # -- step scopes ----------------------------------------------------------

    def step_scope(self, kind: str = "step",
                   args: Mapping[str, Any] | None = None) -> "_StepScope":
        """One step as a span named ``kind``: the envelope of everything
        recorded until it closes. ``args`` ride on the profiler
        annotation and on the ring's step span."""
        return _StepScope(self, kind, args)

    def _begin_step(self, kind: str) -> StepRecord:
        span_id, _ = self._push()
        with self._lock:
            self._step_count += 1
            if self._ambient is not None and self._ambient.spans:
                self._ring.append(self._ambient.as_dict())
            self._ambient = None
            rec = StepRecord(self._step_count, kind, self.clock.now(),
                             span_id)
            self._current = rec
            return rec

    def _end_step(self, rec: StepRecord) -> None:
        rec.dur = self.clock.now() - rec.t_start
        self._pop(rec.span_id)
        with self._lock:
            if self._current is rec:
                self._current = None
            step_span = {
                "name": rec.kind, "cat": "step",
                "t": round(rec.t_start, 6),
                "dur": round(rec.dur, 6),
                "id": rec.span_id, "step": rec.step,
                "args": {"synced": rec.synced, **(rec.args or {})},
            }
            rec.spans.insert(0, step_span)
            self._ring.append(rec.as_dict())
            if self._setup is not None:
                self._setup.keep(step_span)
        if rec.closes_setup:
            self.close_setup()
        if rec.synced:
            # Where a step was blocked on, the chip holds what the step
            # holds: the memory observatory's watermark latch. Never on
            # an un-synced call (it asks every local device).
            try:
                from . import memory

                memory.note_phase(rec.kind, "step")
            except Exception:  # noqa: BLE001 — advisory
                pass
            # Synced steps carry REAL wall time, so they feed the
            # attribution plane: phase decomposition, exposed-comm and
            # MFU gauges, the local regression sentinel. Un-synced
            # steps time async dispatch only and would report garbage.
            try:
                from . import attribution

                attribution.note_step(rec.as_dict())
            except Exception:  # noqa: BLE001 — attribution is advisory
                pass
        if rec.ship:
            ship_async(self.payload())

    def sample_due(self, step: int) -> bool:
        n = sample_every()
        return n > 0 and step % n == 0

    def steps_recorded(self) -> int:
        with self._lock:
            return self._step_count

    def rebase(self) -> None:
        """Zero the step counter (ring kept — flight history across a
        recovery is the point of the recorder). Called when a worker
        (re-)joins a world epoch: skew matching keys spans on
        (generation, step, name), and SPMD lockstep keeps counters
        rank-aligned only if every member of a generation counts from
        the same join point — a survivor at step 500 next to a
        replacement at step 1 would otherwise never match."""
        with self._lock:
            self._step_count = 0
            self._dispatch_seq.clear()

    # -- snapshots ------------------------------------------------------------

    def ring_snapshot(self) -> list[dict]:
        with self._lock:
            out = list(self._ring)
            if self._ambient is not None and self._ambient.spans:
                out.append(self._ambient.as_dict())
            return out

    def flight_snapshot(self) -> dict:
        """The flight record: the ring plus any still-open spans (a
        wedged collective shows up here with its age, which is exactly
        the postmortem question)."""
        now = self.clock.now()
        with self._lock:
            open_spans = [
                {"name": name, "cat": cat, "t": round(t0, 6),
                 "age_s": round(now - t0, 6)}
                for name, cat, t0, _, _ in self._open.values()
            ]
            current = (self._current.as_dict()
                       if self._current is not None else None)
        out = {"steps": self.ring_snapshot(), "open_spans": open_spans}
        if current is not None:
            out["current_step"] = current
        return out

    def payload(self) -> dict:
        """The wire format shipped to ``PUT /trace/<host>`` and merged by
        ``GET /timeline`` / ``GET /criticalpath``. When the model's
        FLOPs-per-step were declared (``hvd.set_model_flops_per_step``)
        they ride along so the driver's critical-path merge can report
        per-rank MFU."""
        from . import metrics

        out = {
            "rank": _rank(),
            "host": _host(),
            "generation": metrics.default_generation(),
            "clock_offset_s": round(self.clock.offset(), 6),
            "clock_error_s": self.clock.error(),
            "t_ship": self.clock.now(),
            "steps": self.ring_snapshot(),
        }
        try:
            from . import attribution

            flops, peak = attribution.model_flops()
            if flops:
                out["model_flops_per_step"] = flops
            if peak:
                out["peak_flops_per_rank"] = peak
        except Exception:  # noqa: BLE001 — attribution is advisory
            pass
        return out


class _StepScope:
    def __init__(self, tracer: StepTracer, kind: str,
                 args: Mapping[str, Any] | None = None):
        self._tracer = tracer
        self._kind = kind
        self._args = args
        self._annotation = None
        self.rec: StepRecord | None = None

    def __enter__(self) -> StepRecord:
        self.rec = self._tracer._begin_step(self._kind)
        self.rec.args = self._args
        self._annotation = annotation(
            self._kind, {"step": self.rec.step, **(self._args or {})})
        self._annotation.__enter__()
        return self.rec

    def __exit__(self, exc_type, exc, tb):
        if exc is not None and self.rec is not None:
            self.rec.spans.append({
                "name": f"error:{getattr(exc_type, '__name__', 'Exception')}",
                "cat": "error",
                "t": round(self._tracer.clock.now(), 6), "dur": 0.0,
            })
        self._annotation.__exit__(exc_type, exc, tb)
        self._tracer._end_step(self.rec)
        return False


class _HostSpan:
    """A span on both legs: a profiler annotation and a ring record with
    id and parent. The hot path of every factory step: two clock reads,
    one append under the ring's lock, nothing else. ``args`` are read
    when the span closes, so what is only known inside it can be added
    there (:meth:`note`, :meth:`note_tree`)."""

    __slots__ = ("_tracer", "_name", "_args", "_annotation", "_t0", "_id",
                 "_parent")

    def __init__(self, tracer: StepTracer, name: str,
                 args: Mapping[str, Any] | None):
        self._tracer = tracer
        self._name = name
        self._args = args

    def __enter__(self):
        tracer = self._tracer
        self._id, self._parent = tracer._push()
        self._annotation = annotation(self._name, self._args)
        self._annotation.__enter__()
        self._t0 = tracer.clock.now()
        return self

    def __exit__(self, *exc):
        tracer = self._tracer
        now = tracer.clock.now()
        self._annotation.__exit__(*exc)
        tracer._pop(self._id)
        with tracer._lock:
            tracer._record_locked(self._name, CAT_HOST, self._t0,
                                  now - self._t0, self._args, self._id,
                                  self._parent)
        return False

    def note(self, **args) -> None:
        self._args = {**(self._args or {}), **args}

    def note_tree(self, tree) -> None:
        """``leaves`` and ``bytes`` of a pytree of arrays, from shapes
        alone: nothing is transferred or waited for."""
        import math

        import jax

        leaves = [leaf for leaf in jax.tree.leaves(tree)
                  if hasattr(leaf, "shape") and hasattr(leaf, "dtype")]
        self.note(leaves=len(leaves), bytes=sum(
            math.prod(leaf.shape) * getattr(leaf.dtype, "itemsize", 0)
            for leaf in leaves))


class _NoSpan:
    """What :func:`setup_span` gives once the account has closed."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **args) -> None:
        pass

    def note_tree(self, tree) -> None:
        pass


_NO_SPAN = _NoSpan()


def setup_span(name: str, args: Mapping[str, Any] | None = None):
    """One of the ``hvd.setup.*`` spans (``attribution.SPAN_SETUP_*``): a
    :meth:`StepTracer.host_span` while the set-up account is open, nothing
    once it has closed, so that a function called every step too
    (``shard_batch``) pays one attribute test for it."""
    tracer = get_tracer()
    return tracer.host_span(name, args) if tracer.setup_open else _NO_SPAN


def place_span(what: str, tree):
    """The ``hvd.setup.place`` span around one of the placing functions
    (``replicate``, ``shard_state``, ``shard_batch``, ``shard_params``):
    what the HOST paid to issue the copies. The functions return before
    the copies land and nothing here waits for them; ``leaves`` and
    ``bytes`` are read off the tree's shapes."""
    tracer = get_tracer()
    if not tracer.setup_open:
        return _NO_SPAN
    span = tracer.host_span(SPAN_SETUP_PLACE, {"what": what})
    span.note_tree(tree)
    return span


def self_times(spans) -> list[float]:
    """Per span of one step record's ``spans``, its self time in seconds:
    its duration less the part of it that its child spans cover (children
    are the spans whose ``parent`` is its ``id``; a span without an id
    has none)."""
    children: dict[int, list] = {}
    for sp in spans:
        parent = sp.get("parent")
        if parent is not None:
            children.setdefault(parent, []).append(
                (sp["t"], sp["t"] + sp["dur"]))
    out = []
    for sp in spans:
        start, end = sp["t"], sp["t"] + sp["dur"]
        covered = _length(_merge([
            (max(s, start), min(e, end))
            for s, e in children.get(sp.get("id"), ()) if e > start
            and s < end]))
        out.append(max(sp["dur"] - covered, 0.0))
    return out


# ---------------------------------------------------------------------------
# Singletons
# ---------------------------------------------------------------------------

# RLock: get_tracer() materializes the clock sync under the same lock.
_lock = threading.RLock()
_clock_sync: ClockSync | None = None
_tracer: StepTracer | None = None


def clock_sync() -> ClockSync:
    global _clock_sync
    with _lock:
        if _clock_sync is None:
            _clock_sync = ClockSync()
        return _clock_sync


def get_tracer() -> StepTracer:
    global _tracer
    tracer = _tracer  # every factory step comes through here: no lock
    if tracer is not None:
        return tracer
    with _lock:
        if _tracer is None:
            _tracer = StepTracer(clock_sync())
        return _tracer


def reset_for_testing(tracer: StepTracer | None = None) -> None:
    """Fresh tracer + clock sync; ``tracer`` installs one built with a
    ring depth or span cap of the test's own."""
    global _tracer, _clock_sync, _last_hb_ship
    with _lock:
        _tracer = tracer
        _clock_sync = tracer.clock if tracer is not None else None
    with _ship_lock:
        _last_hb_ship = 0.0


def record_span(name: str, cat: str, t_start: float, dur: float,
                args: Mapping[str, Any] | None = None) -> None:
    get_tracer().record(name, cat, t_start, dur, args)


class span:
    """Record a host-observable phase: ``with tracing.span('forward',
    'phase'): ...``.

    Triple-emits: a span into the step tracer (ring + shipping), a
    Chrome-trace event on the per-process host timeline, and a
    ``jax.profiler.TraceAnnotation`` range (both via
    :class:`horovod_tpu.timeline.activity`). Never raises — tracing must
    not take down training.
    """

    def __init__(self, name: str, cat: str = "phase",
                 args: Mapping[str, Any] | None = None):
        self.name = name
        self.cat = cat
        self.args = args
        self._token: int | None = None
        self._tracer: StepTracer | None = None
        self._id: int | None = None
        self._act = None

    def __enter__(self):
        try:
            from .timeline import activity

            self._act = activity(self.name, self.cat, self.args)
            self._act.__enter__()
        except Exception:  # noqa: BLE001
            self._act = None
        try:
            self._tracer = get_tracer()
            self._id, parent = self._tracer._push()
            self._token = self._tracer.begin_span(
                self.name, self.cat, self._id, parent)
        except Exception:  # noqa: BLE001
            self._token = None
        return self

    def __exit__(self, *exc):
        in_step = False
        if self._token is not None:
            try:
                self._tracer._pop(self._id)
                in_step = self._tracer.in_step()
                self._tracer.end_span(self._token, self.args)
            except Exception:  # noqa: BLE001
                pass
        if self._act is not None:
            try:
                self._act.__exit__(*exc)
            except Exception:  # noqa: BLE001
                pass
        # Memory-observatory watermark hook, for spans outside any step
        # (inside one, the close of a synced step is the latch: it asks
        # every local device, too dear for every span of every step).
        if not in_step:
            try:
                from . import memory

                memory.note_phase(self.name, self.cat)
            except Exception:  # noqa: BLE001 — tracing must not fail
                pass
        return False


# ---------------------------------------------------------------------------
# Trace shipping (worker -> rendezvous KV)
# ---------------------------------------------------------------------------

_ship_lock = threading.Lock()
_ship_pending: dict | None = None
_ship_event = threading.Event()
_ship_thread: threading.Thread | None = None


def _ship_generation() -> int | None:
    """Generation stamp for trace PUTs: the elastic worker context's
    JOINED generation when one exists (the same source the heartbeat and
    abort clients fence with), else the launcher env, else None
    (static/manual launches stay unfenced)."""
    from .runner.elastic import worker as elastic_worker

    ctx = elastic_worker._context
    if ctx is not None:
        return ctx.joined_version
    from .runner.http.kv_server import env_generation

    return env_generation()


def _shipper_loop() -> None:
    global _ship_pending
    from .utils.logging import get_logger

    while True:
        _ship_event.wait()
        with _ship_lock:
            payload = _ship_pending
            _ship_pending = None
            _ship_event.clear()
        if payload is None:
            continue
        try:
            # Endpoint re-read per payload: elastic re-formations (and
            # tests) can move the rendezvous server; a cached client
            # would strand every later ship on a dead port.
            addr = os.environ.get("HOROVOD_RENDEZVOUS_ADDR", "")
            port = os.environ.get("HOROVOD_RENDEZVOUS_PORT", "")
            if not addr or not port:
                continue
            from .runner.http.kv_server import KVClient

            # Same 1-attempt/2s discipline as the heartbeat client: a
            # slow ship must never back-pressure the train loop (the
            # single pending slot just drops the stale payload). Ships
            # are generation-fenced like every other worker write — a
            # zombie rank resumed from a pre-abort world must not keep
            # repopulating the trace scope the re-formed world's
            # clear_heartbeat() just purged.
            client = KVClient(addr, int(port), timeout=2.0, retries=1,
                              generation_fn=_ship_generation)
            client.put(TRACE_SCOPE, payload.get("host", _host()),
                       json.dumps(payload).encode())
            from . import metrics

            metrics.TRACE_SHIPS.inc()
        except Exception as e:  # noqa: BLE001 — shipping is best-effort
            get_logger().debug("trace ship failed: %s", e)


def ship_async(payload: dict) -> None:
    """Queue a trace payload for the background shipper (single pending
    slot: a new sample replaces an unsent older one — the timeline wants
    the freshest window, not a backlog)."""
    global _ship_thread, _ship_pending
    with _ship_lock:
        _ship_pending = payload
        if _ship_thread is None or not _ship_thread.is_alive():
            _ship_thread = threading.Thread(
                target=_shipper_loop, name="hvd-trace-ship", daemon=True)
            _ship_thread.start()
        _ship_event.set()


def ship_interval_s() -> float:
    """Floor between heartbeat-coupled trace ships (seconds)."""
    return get_float("HOROVOD_TRACE_SHIP_SECONDS", 5.0)


_last_hb_ship = 0.0


def maybe_ship_heartbeat() -> bool:
    """Ship the current tracer window on the heartbeat cadence.

    Step-scoped workloads ship on every sampled step; eager host-plane
    workloads (the torch/TF surfaces) have no step scope, so their spans
    would collect locally and never reach the merged timeline or the
    straggler gauges. The elastic heartbeat sender calls this after each
    successful beat: when shipping is enabled (``HOROVOD_TRACE_SAMPLE >
    0``), the ring + ambient window ships at most once per
    ``HOROVOD_TRACE_SHIP_SECONDS`` — the freshness the self-healing
    policy's skew evidence rides on. Returns True when a ship was queued.
    """
    global _last_hb_ship
    if sample_every() <= 0:
        return False
    now = time.monotonic()
    with _ship_lock:
        if now - _last_hb_ship < ship_interval_s():
            return False
        _last_hb_ship = now
    ship_async(get_tracer().payload())
    return True


# ---------------------------------------------------------------------------
# Flight recorder dump
# ---------------------------------------------------------------------------


def dump_flight_record(reason: str, generation: int | None = None,
                       **fields: Any) -> dict | None:
    """Dump the last-K-steps flight record into the lifecycle journal as
    a ``flight_record`` event. Called on abort-consume, stall shutdown,
    deadman exit, and SIGTERM drain; never raises."""
    try:
        from . import metrics

        snap = get_tracer().flight_snapshot()
        # Replica-pool state rides every dump (abort-consume included):
        # which ranks' shards this process holds, at which step and
        # generation — the first question after a peer-rung recovery.
        try:
            from . import peercheck

            pool = peercheck.pool_summary()
            if pool is not None:
                snap["peer_pool"] = pool
        except Exception:  # noqa: BLE001 — the dump must still land
            pass
        # Integrity-plane state rides too (when it ever engaged): the
        # last staged fingerprint and the tripwire/rewind counters —
        # the first questions after a divergence names this rank.
        try:
            from . import integrity

            isum = integrity.flight_summary()
            if isum is not None:
                snap["integrity"] = isum
        except Exception:  # noqa: BLE001 — the dump must still land
            pass
        # Attribution rides too: the last synced step's phase
        # decomposition (where DID the wall time go before the wedge),
        # and — for a wedged collective still open — the gating rank
        # the cluster's partial critical path names (best-effort fetch
        # from GET /criticalpath; the first postmortem question).
        try:
            from . import attribution

            asum = attribution.flight_summary(snap)
            if asum is not None:
                snap["attribution"] = asum
        except Exception:  # noqa: BLE001 — the dump must still land
            pass
        # Memory snapshot rides EVERY dump: per-kind resident bytes,
        # the phase watermarks, and the footprint model's drift — the
        # first questions when the wedge or abort was memory-shaped.
        try:
            from . import memory

            msum = memory.flight_summary()
            if msum is not None:
                snap["memory"] = msum
        except Exception:  # noqa: BLE001 — the dump must still land
            pass
        metrics.FLIGHT_DUMPS.inc(reason=reason)
        metrics.event(
            "flight_record", generation=generation, reason=reason,
            rank=_rank(), host=_host(), **snap, **fields)
        return snap
    except Exception:  # noqa: BLE001 — postmortems are best-effort
        return None


# ---------------------------------------------------------------------------
# Skew attribution (runs on the driver, over shipped payloads)
# ---------------------------------------------------------------------------

#: Span categories matched across ranks for arrival-skew attribution:
#: eager/host collectives carry cat="collective"; compiled training's
#: cross-rank signal is the step span itself (all ranks enter step N of
#: the same program — a late entrant IS the straggler).
SKEW_CATS = ("collective", "step")


def straggler_warn_skew() -> float:
    """Arrival skew (seconds) past which the server journals a
    ``straggler_detected`` event."""
    return get_float("HOROVOD_STRAGGLER_WARN_SKEW", 1.0)


def compute_skew(payloads: Mapping[str, Mapping]) -> dict:
    """Per-collective arrival-skew attribution over shipped payloads.

    ``payloads`` maps host -> parsed trace payload. Spans are matched
    across ranks by ``(generation, step, name)`` within
    :data:`SKEW_CATS` — the generation scoping keeps a pre-recovery
    world's spans from matching the re-formed world's, and
    :meth:`StepTracer.rebase` (called at world join) keeps the step
    counters rank-aligned within a generation. For each matched instance
    seen by ≥2 ranks, a rank's *lateness* is its offset-corrected span
    start minus the earliest rank's. Returns::

        {"matched": N,
         "ranks": {rank: {"host", "mean_lateness_s", "max_lateness_s",
                          "samples"}},
         "worst": {"name", "step", "skew_s", "last_rank", "last_host"}
                  | None}

    ``worst`` names the single largest-skew instance — the last-arriver
    identity + magnitude the straggler gauges and journal events carry.
    """
    groups: dict[tuple, list[tuple[str, str, float]]] = {}
    rank_host: dict[str, str] = {}
    rank_err: dict[str, float] = {}
    for host, payload in payloads.items():
        if not isinstance(payload, Mapping):
            continue
        rank = str(payload.get("rank", "?"))
        try:
            offset = float(payload.get("clock_offset_s", 0.0) or 0.0)
        except (TypeError, ValueError):
            offset = 0.0
        generation = payload.get("generation")
        contributed = False
        for steprec in payload.get("steps", ()) or ():
            if not isinstance(steprec, Mapping):
                continue
            step = steprec.get("step")
            for sp in steprec.get("spans", ()) or ():
                if not isinstance(sp, Mapping):
                    continue
                if sp.get("cat") not in SKEW_CATS:
                    continue
                try:
                    t = float(sp["t"]) + offset
                except (KeyError, TypeError, ValueError):
                    continue
                key = (generation, step, sp.get("name"))
                groups.setdefault(key, []).append((rank, host, t))
                contributed = True
        # Only a payload that contributed spans may claim a rank's
        # identity: a spanless payload with a stale/default rank label
        # (a worker mid-bootstrap shipping its empty ring) must not
        # steal a real rank's host attribution — the gauges and the
        # policy would then pin the measured lateness on the wrong
        # host (or drop it entirely, hiding a straggler).
        if not contributed:
            continue
        rank_host[rank] = host
        try:
            rank_err[rank] = float(payload.get("clock_error_s") or 0.0)
        except (TypeError, ValueError):
            rank_err[rank] = 0.0
    matched = 0
    lateness: dict[str, list[float]] = {}
    worst: dict | None = None
    for (generation, step, name), arrivals in groups.items():
        ranks_seen = {r for r, _, _ in arrivals}
        if len(ranks_seen) < 2:
            continue
        matched += 1
        # One arrival per rank per instance: earliest wins (re-shipped
        # windows can repeat a step).
        first: dict[str, tuple[str, float]] = {}
        for r, h, t in arrivals:
            if r not in first or t < first[r][1]:
                first[r] = (h, t)
        first_rank, (_, t_min) = min(
            first.items(), key=lambda kv: kv[1][1])
        last_rank, (last_host, t_max) = max(
            first.items(), key=lambda kv: kv[1][1])
        skew = t_max - t_min
        for r, (_, t) in first.items():
            lateness.setdefault(r, []).append(t - t_min)
        if worst is None or skew > worst["skew_s"]:
            # Combined offset-estimation error of the two clocks being
            # differenced: consumers threshold on skew − err so clock
            # uncertainty can never register as phantom straggling.
            err = (rank_err.get(last_rank, 0.0)
                   + rank_err.get(first_rank, 0.0))
            worst = {"name": name, "step": step,
                     "skew_s": round(skew, 6),
                     "err_s": round(err, 6),
                     "last_rank": last_rank, "last_host": last_host}
    ranks = {
        r: {
            "host": rank_host.get(r, ""),
            "mean_lateness_s": round(sum(ls) / len(ls), 6),
            "max_lateness_s": round(max(ls), 6),
            "samples": len(ls),
        }
        for r, ls in lateness.items()
    }
    return {"matched": matched, "ranks": ranks, "worst": worst}


def straggler_summary(fetch_cluster: bool = True) -> dict:
    """This rank's view for ``profiler.summary()["stragglers"]``: the
    local clock-offset estimate + tracer state, plus (best-effort, when a
    rendezvous KV is configured) the server-computed cluster skew from
    ``GET /stragglers``."""
    cs = clock_sync()
    out: dict = {
        "clock_offset_s": round(cs.offset(), 6),
        "clock_error_s": cs.error(),
        "clock_synced": cs.synced(),
        "steps_recorded": get_tracer().steps_recorded(),
        "trace_sample": sample_every(),
    }
    addr = os.environ.get("HOROVOD_RENDEZVOUS_ADDR", "")
    port = os.environ.get("HOROVOD_RENDEZVOUS_PORT", "")
    if fetch_cluster and addr and port:
        try:
            from urllib.request import urlopen

            with urlopen(f"http://{addr}:{port}/stragglers",
                         timeout=2.0) as r:
                out["cluster"] = json.loads(r.read())
        except Exception as e:  # noqa: BLE001 — summary is best-effort
            out["cluster_error"] = str(e)[:200]
    return out
