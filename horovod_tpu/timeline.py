"""Chrome-trace timeline of collective lifecycles.

Parity: ``horovod/common/timeline.cc`` — activated by
``HOROVOD_TIMELINE=/path.json``, viewable in ``chrome://tracing`` /
Perfetto. The reference records per-tensor negotiation phases
(NEGOTIATE → WAIT_FOR_DATA → QUEUE → MEMCPY_IN → NCCL_* → MEMCPY_OUT)
from its background thread. In the compiled world most of those phases
don't exist at runtime — so the TPU timeline records what *does* happen on
the host: eager-collective dispatch (cache hit/miss, compile time, execute
time), trace-time fusion decisions (bucket layouts), and step markers; for
on-device phases, point xprof at the same run and merge in the viewer.

Events are written on a dedicated writer thread (as in the reference, so
the hot path never blocks on file IO) in Chrome trace-event JSON.

Crash safety: the file is **loadable at every flush point**. The writer
keeps the closing ``]`` present after every event (write event → write
trailer → flush → seek back over the trailer for the next event), so a
process that dies without ``stop_timeline()`` — SIGKILL included — leaves
a valid, viewer-loadable JSON array instead of a truncated one. An
``atexit`` hook additionally drains and closes the writer on normal
interpreter exit.
"""

from __future__ import annotations

import atexit
import json
import os
import queue
import threading
import time
from typing import Any

# None: not looked for yet (the next get_timeline() reads
# HOROVOD_TIMELINE); _OFF: looked for and not asked for, so that a span
# costs neither a lock nor an environment read; else the writer.
_OFF = object()
_timeline: "Timeline | object | None" = None
_lock = threading.Lock()


class Timeline:
    #: Trailer kept at the tail after every flush, so the file is a valid
    #: JSON array at ALL times (the crash-safety contract).
    _TRAILER = "\n]\n"

    #: Serialized-event cap. The crash-safety protocol relies on the
    #: whole comma+event+trailer chunk staying in the IO buffer until
    #: the one explicit flush; an event bigger than the buffer (~8KB)
    #: would auto-flush a partial, trailer-less write. Caller-controlled
    #: ``args`` are dropped (with a marker) past this bound.
    _MAX_EVENT_CHARS = 4096

    def __init__(self, path: str):
        self.path = path
        self._queue: "queue.Queue[dict[str, Any] | None]" = queue.Queue()
        self._start = time.perf_counter_ns()
        self._thread = threading.Thread(
            target=self._writer, name="hvd-timeline-writer", daemon=True
        )
        self._file = open(path, "w")
        self._file.write("[\n")
        self._tail = self._file.tell()
        self._file.write(self._TRAILER)
        self._file.flush()  # even a zero-event file loads as []
        self._first = True
        self._dead = False
        self._thread.start()
        # A process that exits without stop_timeline() still drains and
        # closes the writer (the seek/truncate protocol above covers the
        # no-atexit deaths — SIGKILL, os._exit — too).
        atexit.register(self.shutdown)

    def _now_us(self) -> float:
        return (time.perf_counter_ns() - self._start) / 1e3

    def _writer(self) -> None:
        while True:
            event = self._queue.get()
            if event is None:
                break
            # Seek over the trailer, buffer event + fresh trailer, flush
            # once: the on-disk file keeps the OLD trailer until the
            # single flush lands the whole replacement region, so it is a
            # valid JSON array at every instant — a crash loses at most
            # the single event in flight (the journal's per-record
            # durability contract). No truncate(): every write is >= the
            # trailer's length, so the file only ever grows and there are
            # no stale bytes to trim — and truncate() would flush the
            # shrunk, trailer-less file to disk mid-update, re-opening
            # exactly the unloadable window this protocol closes.
            text = json.dumps(event)
            if len(text) > self._MAX_EVENT_CHARS:
                event = {**event, "args": {"dropped": "args exceeded "
                                           "timeline event size cap"}}
                text = json.dumps(event)
            self._file.seek(self._tail)
            if not self._first:
                self._file.write(",\n")
            self._first = False
            self._file.write(text)
            self._tail = self._file.tell()
            self._file.write(self._TRAILER)
            self._file.flush()
        # The trailer is already on disk after the last flush; just close.
        self._file.close()

    def _emit(self, name: str, phase: str, category: str, ts_us: float, dur_us: float = None, args=None):
        if self._dead:
            return
        event = {
            "name": name,
            "ph": phase,
            "cat": category,
            "ts": ts_us,
            "pid": os.getpid(),
            "tid": threading.get_ident() % 1_000_000,
        }
        if dur_us is not None:
            event["dur"] = dur_us
        if args:
            event["args"] = args
        self._queue.put(event)

    def complete(self, name: str, category: str, start_us: float, args=None) -> None:
        """Record a completed activity [start_us, now]."""
        self._emit(
            name, "X", category, start_us, self._now_us() - start_us, args
        )

    def instant(self, name: str, category: str = "marker", args=None) -> None:
        self._emit(name, "i", category, self._now_us(), args=args)

    def now_us(self) -> float:
        return self._now_us()

    def shutdown(self) -> None:
        global _timeline
        if self._dead:
            return
        self._dead = True
        self._queue.put(None)
        self._thread.join(timeout=5)
        try:
            atexit.unregister(self.shutdown)
        except Exception:  # noqa: BLE001 — double-run is harmless anyway
            pass
        with _lock:
            if _timeline is self:
                _timeline = None


def get_timeline() -> Timeline | None:
    """The process timeline, or None when HOROVOD_TIMELINE is unset.
    The variable is read once; :func:`start_timeline` and
    :func:`stop_timeline` are how capture changes after that."""
    global _timeline
    timeline = _timeline
    if timeline is None:
        with _lock:
            if _timeline is None:
                path = os.environ.get("HOROVOD_TIMELINE", "")
                _timeline = Timeline(path) if path else _OFF
            timeline = _timeline
    return None if timeline is _OFF else timeline


def start_timeline(file_path: str, mark_cycles: bool = False) -> None:
    """Start (or re-target) timeline capture at runtime (parity:
    ``hvd.start_timeline`` — the reference's dynamic-activation API,
    equivalent to launching with ``HOROVOD_TIMELINE=<path>``).
    ``mark_cycles`` mirrors ``HOROVOD_TIMELINE_MARK_CYCLES``."""
    global _timeline, _mark_cycles
    # Swap env + globals + the new writer ATOMICALLY: a concurrent
    # collective's get_timeline() between the steps would otherwise
    # materialize a writer at the stale path (truncating a flushed
    # trace). The old writer shuts down outside the lock.
    with _lock:
        old = None if _timeline is _OFF else _timeline
        os.environ["HOROVOD_TIMELINE"] = file_path
        if mark_cycles:
            os.environ["HOROVOD_TIMELINE_MARK_CYCLES"] = "1"
        else:
            os.environ.pop("HOROVOD_TIMELINE_MARK_CYCLES", None)
        _mark_cycles = mark_cycles  # reset the first-use cache
        _timeline = Timeline(file_path)
    if old is not None:
        old.shutdown()


def stop_timeline() -> None:
    """Stop capture and flush the trace file (parity:
    ``hvd.stop_timeline``)."""
    global _timeline, _mark_cycles
    with _lock:
        tl = None if _timeline is _OFF else _timeline
        _timeline = None
        os.environ.pop("HOROVOD_TIMELINE", None)
        os.environ.pop("HOROVOD_TIMELINE_MARK_CYCLES", None)
        _mark_cycles = None
    if tl is not None:
        tl.shutdown()


class activity:
    """Context manager: ``with activity('allreduce.dense_1', 'collective')``.

    Dual-emits: a Chrome-trace event on the host timeline AND a
    ``jax.profiler.TraceAnnotation`` range, so the same activity name shows
    up inside an xprof/TPU-profiler capture of the run (the reference's
    NVTX-range role — one merged view of host scheduling and device work).
    """

    def __init__(self, name: str, category: str = "collective", args=None):
        self.name = name
        self.category = category
        self.args = args
        self._tl = get_timeline()
        self._start = 0.0
        self._annotation = None

    def __enter__(self):
        if self._tl is not None:
            self._start = self._tl.now_us()
        try:
            import jax.profiler

            self._annotation = jax.profiler.TraceAnnotation(self.name)
            self._annotation.__enter__()
        except Exception:  # profiler unavailable: host timeline only
            self._annotation = None
        return self

    def __exit__(self, *exc):
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        if self._tl is not None:
            self._tl.complete(self.name, self.category, self._start, self.args)
        return False


_mark_cycles = None
_cycle_count = 0


def mark_cycles_enabled() -> bool:
    """HOROVOD_TIMELINE_MARK_CYCLES=1 (reference contract): emit an instant
    marker per background/step cycle on the timeline."""
    global _mark_cycles
    if _mark_cycles is None:
        _mark_cycles = os.environ.get(
            "HOROVOD_TIMELINE_MARK_CYCLES", "") == "1"
    return _mark_cycles


def mark_cycle(label: str = "cycle") -> None:
    """Emit a cycle marker if enabled. In the compiled regime a "cycle" is
    a dispatched step/collective (there is no background negotiation loop
    to tick); the native C++ runtime marks its own cycles in-core."""
    global _cycle_count
    if not mark_cycles_enabled():
        return
    tl = get_timeline()
    if tl is not None:
        _cycle_count += 1
        tl.instant(f"{label}.{_cycle_count}", category="cycle")
