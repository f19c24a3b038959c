"""What the program's own spans and scopes say of a traced run.

``trace_reduce`` times the layers from outside: the benchmark's two
annotations, and device operations grouped by what XLA called them. Since
PR 23 the program names its own parts, and this file reads them:

* **host side**: every call into a factory step is an ``hvd.step`` span
  with ``hvd.step.dispatch`` (and, on a synced call, ``hvd.step.drain``)
  inside it, written by ``jax.profiler.TraceAnnotation`` onto the trace's
  host plane, on the trace's own clock. ``host(run)`` reads them from the
  traced run's ``.xplane.pb`` (``run.py`` hands a reader no path: it is
  found from the cell's name, as ``run.py::out_dir`` lays it out, and
  parsed once for all readers), clips them to the window, takes a span's
  self time as its duration less what its children cover, and labels each
  idle gap of the most idle device by the innermost ``hvd.`` span open
  when the gap began.
* **device side**: the compiled step carries the phase scopes
  ``hvd.wire`` / ``hvd.optimizer`` / ``hvd.attn.fwd`` / ``hvd.attn.bwd`` in
  its ``metadata={op_name=...}``. A v5e trace names an operation by its
  instruction (whether the event's statistics hold the name stack too
  has not been looked at), so instruction -> scope is asked of the
  program (``hvd.profiler.step_texts()``: the memoised executable), and
  ``device(run)`` sums device seconds by phase. An operation counts under
  its innermost phase scope wherever that sits in its path, else as
  forward or backward by ``jvp`` / ``transpose``, else as unscoped; a
  fusion counts where its root instruction's scope is.

A program without the spans or the scopes (the parent of PR 23) gives
``None`` and no metric; a program that has them but whose step's text
holds none (a stale executable out of a shared compile cache) fails,
saying so. Everything under ``host`` and ``device`` is arithmetic on plain
intervals and is tested on hand-made ones.

    python benchmark/program_spans.py <file.xplane.pb>

prints the host table of a trace (the device table needs the process that
ran the step).
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
import statistics
import sys

import cells
import trace_reduce

# The host spans by their names, not by the program's constants: a trace
# of a program from before them is read too, and holds none.
STEP = "hvd.step"
DISPATCH = "hvd.step.dispatch"
PREFIX = "hvd."
OUTSIDE = "outside the program"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float  # seconds, on the trace's clock
    end: float
    line: str  # the host thread it was recorded on


# -- the trace's host plane ---------------------------------------------------

def trace_path(cell_name: str) -> str | None:
    """The traced run's file, where ``run.py::trace`` writes it."""
    found = glob.glob(os.path.join(
        cells.ROOT, ".benchmark_out", cell_name, "trace", "plugins",
        "profile", "*", "*.xplane.pb"))
    return found[0] if len(found) == 1 else None


def read_spans(path: str) -> list:
    """Every ``hvd.*`` event of the planes that are no device, by start."""
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(path).planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            spans.extend(
                Span(event.name, event.start_ns * 1e-9,
                     (event.start_ns + event.duration_ns) * 1e-9,
                     f"{plane.name}/{line.name}")
                for event in line.events if event.name.startswith(PREFIX))
    return sorted(spans, key=lambda span: (span.start, -span.end))


def clip(spans, window) -> list:
    low, high = window
    return [dataclasses.replace(span, start=max(span.start, low),
                                end=min(span.end, high))
            for span in spans if span.end > low and span.start < high]


def children_of(span: Span, spans) -> list:
    """The spans of the same thread that lie inside ``span``."""
    return [other for other in spans
            if other is not span and other.line == span.line
            and other.start >= span.start and other.end <= span.end
            and (other.start, other.end) != (span.start, span.end)]


def self_seconds(span: Span, spans) -> float:
    """A span's duration less the part of it that its children cover."""
    covered = trace_reduce.total(
        (child.start, child.end) for child in children_of(span, spans))
    return (span.end - span.start) - covered


def label_at(spans, instant: float) -> str:
    """The innermost ``hvd.`` span open at ``instant``: the one that
    started last among those that hold it."""
    holding = [span for span in spans if span.start <= instant < span.end]
    if not holding:
        return OUTSIDE
    return max(holding, key=lambda span: (span.start, -span.end)).name


def gap_table(spans, gaps) -> collections.Counter:
    """Idle seconds by what the program's host side was in when each gap
    opened."""
    table = collections.Counter()
    for start, end in gaps:
        table[label_at(spans, start)] += end - start
    return table


def idle_gaps(trace) -> list:
    """The intervals of the window in which the most idle device ran
    nothing: ``trace_reduce.breakdown``'s gaps."""
    busy = trace_reduce.busy_seconds(trace)
    if not busy:
        return []
    ops = trace.devices[min(busy, key=busy.get)]
    return trace_reduce.subtract([trace.window], trace_reduce.spans(ops))


@dataclasses.dataclass(frozen=True)
class Host:
    dispatch_ms: float  # median hvd.step.dispatch
    hooks_ms: float  # median self time of hvd.step
    idle_in_step_ms: float | None  # a step; None without a device plane
    gaps: dict  # label -> idle ms a step


def host_side(spans, trace, steps: int) -> Host | None:
    """``None`` where the trace holds no ``hvd.step``: a program from
    before the spans."""
    spans = clip(spans, trace.window)
    calls = [span for span in spans if span.name == STEP]
    dispatches = [span for span in spans if span.name == DISPATCH]
    if not calls or not dispatches:
        return None
    gaps = gap_table(spans, idle_gaps(trace))
    per_step = {name: seconds / steps * 1e3 for name, seconds in gaps.items()}
    in_step = sum(ms for name, ms in per_step.items() if name != OUTSIDE)
    return Host(
        dispatch_ms=statistics.median(
            span.end - span.start for span in dispatches) * 1e3,
        hooks_ms=statistics.median(
            self_seconds(span, spans) for span in calls) * 1e3,
        idle_in_step_ms=in_step if trace.devices else None,
        gaps=per_step)


# -- the device's operations, by the program's phase --------------------------

def phase_of(scope: str | None) -> str:
    """The name a device operation is summed under: the program's own
    word for its innermost phase scope, else the pass, else unscoped."""
    from horovod_tpu import profiler

    if not scope:
        return "unscoped"
    phase = profiler.phase_of(scope)
    if phase:
        return phase
    if "transpose(" in scope:
        return "backward"
    if "jvp(" in scope:
        return "forward"
    return "unscoped"


@dataclasses.dataclass(frozen=True)
class Device:
    phases: dict  # phase -> device ms a step, on the most idle device
    wire_pack_ms: float  # under hvd.wire, in operations that are no collective
    busy_ms: float  # a step: the sum the phases add up to
    attn_fwd_ms: float | None  # kernels, on the device where they took longest
    attn_bwd_ms: float | None


def device_side(trace, steps: int, scopes: dict, kernel_names: str) -> Device:
    busy = trace_reduce.busy_seconds(trace)
    ops = trace.devices[min(busy, key=busy.get)]
    phases = collections.Counter()
    pack = 0.0
    for op in ops:
        phase = phase_of(scopes.get(op.name))
        phases[phase] += op.end - op.start
        if phase == "hvd.wire" and not trace_reduce.COLLECTIVE.match(
                op.opcode):
            pack += op.end - op.start
    # The kernels as attn_kernel_ms takes them: on the device where they
    # took longest, all of them together, then split by their scope.
    kernels = max(
        (trace_reduce.matching(found, kernel_names)
         for found in trace.devices.values()),
        key=lambda found: sum(op.end - op.start for op in found))
    by_phase = collections.Counter()
    for op in kernels:
        by_phase[phase_of(scopes.get(op.name))] += op.end - op.start
    unknown = set(by_phase) - {"hvd.attn.fwd", "hvd.attn.bwd"}
    if unknown:
        raise ValueError(
            f"{len(kernels)} operations match {kernel_names!r} and some "
            f"are under neither hvd.attn.fwd nor hvd.attn.bwd: {unknown}")

    def per_step(seconds):
        return seconds / steps * 1e3

    return Device(
        phases={name: per_step(seconds)
                for name, seconds in phases.most_common()},
        wire_pack_ms=per_step(pack),
        busy_ms=per_step(sum(phases.values())),
        attn_fwd_ms=per_step(by_phase["hvd.attn.fwd"]) if kernels else None,
        attn_bwd_ms=per_step(by_phase["hvd.attn.bwd"]) if kernels else None)


ALL_REDUCE = re.compile(
    r"= \(?((?:\w+\[[\d,]*\][^ ]*,? ?)+)\)? all-reduce(?:-start)?\(")
SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "s8": 1, "u8": 1,
            "s32": 4, "u32": 4, "pred": 1}


def all_reduce_bytes(hlo: str) -> dict:
    """Bytes the step's all-reduces carry, by element type, counted from
    the shapes in the compiled step's text (a combined all-reduce returns
    a tuple)."""
    found = collections.Counter()
    for shapes in ALL_REDUCE.findall(hlo):
        for dtype, dims in SHAPE.findall(shapes):
            count = 1
            for dim in filter(None, dims.split(",")):
                count *= int(dim)
            found[dtype] += count * ITEMSIZE[dtype]
    return dict(found)


# -- one parse a run, shared by the readers -----------------------------------

def once(run, side: str, make):
    """``make()`` the first time a reader asks for this side of ``run``,
    kept on ``run`` itself (``run.py`` builds one for all the readers)."""
    kept = vars(run).setdefault("program_spans", {})
    if side not in kept:
        kept[side] = make()
    return kept[side]


def host(run) -> Host | None:
    """The host side of ``run``'s trace, or ``None`` where there is no
    trace file or no ``hvd.step`` in it."""
    def make():
        path = trace_path(run.cell.name)
        spans = read_spans(path) if path else []
        found = host_side(spans, run.trace, run.steps)
        if found:
            say_host(found, run)
            before, after = around_the_step(
                spans, [span for span in run.trace.host
                        if span[0] == "bench.step_call"])
            if before is not None:
                print(f"program_spans: of a bench.step_call, {before:.3f} ms "
                      f"before hvd.step opens and {after:.3f} ms after it "
                      "closes (medians)", flush=True)
        return found

    return once(run, "host", make)


def device(run) -> Device | None:
    """The device side, or ``None`` off a TPU (no device plane) and for a
    program without ``profiler.step_texts``. A program that has it and
    whose step's text holds no phase scope raises."""
    def make():
        import horovod_tpu as hvd

        texts = getattr(hvd.profiler, "step_texts", None)
        if not run.trace.devices or texts is None:
            return None
        hlo = "\n".join(texts())
        kernel_names = cells.load_json(
            cells.HERE, "layer_metrics",
            "attn_kernel_ms.json")["kernel_names"]
        found = device_side(run.trace, run.steps,
                            hvd.profiler.instruction_scopes(hlo),
                            kernel_names)
        say_device(found, hlo)
        return found

    return once(run, "device", make)


def around_the_step(spans, bench_calls) -> tuple:
    """Median milliseconds of a ``bench.step_call`` before its ``hvd.step``
    opens and after it closes: what the benchmark's own timer holds that
    is not the program's (after: the loop rebinding its state, which
    frees the arrays the step was given)."""
    before, after = [], []
    calls = [span for span in spans if span.name == STEP]
    for _, start, end in bench_calls:
        inside = [span for span in calls
                  if span.start >= start and span.end <= end]
        if len(inside) == 1:
            before.append(inside[0].start - start)
            after.append(end - inside[0].end)
    if not before:
        return None, None
    return (statistics.median(before) * 1e3, statistics.median(after) * 1e3)


def say_host(found: Host, run) -> None:
    print(f"program_spans: hvd.step.dispatch {found.dispatch_ms:.3f} ms + "
          f"hvd.step's own {found.hooks_ms:.3f} ms (medians; step(...) "
          f"returns in {statistics.median(run.call_s) * 1e3:.3f} ms by the "
          "host's clock)", flush=True)
    for name, ms in sorted(found.gaps.items(), key=lambda row: -row[1]):
        print(f"program_spans: idle {ms:9.3f} ms a step in gaps that opened "
              f"in {name}", flush=True)


def say_device(found: Device, hlo: str) -> None:
    for name, ms in found.phases.items():
        print(f"program_spans: device {ms:9.3f} ms a step  {name}",
              flush=True)
    print(f"program_spans: device {found.busy_ms:9.3f} ms a step busy, the "
          f"sum of the above; of hvd.wire {found.wire_pack_ms:.3f} ms in "
          "operations that are no collective", flush=True)
    print(f"program_spans: all-reduce bytes in the step's text "
          f"{all_reduce_bytes(hlo)}", flush=True)


if __name__ == "__main__":
    for found in read_spans(sys.argv[1]):
        print(f"{found.start:.6f} {(found.end - found.start) * 1e3:9.3f} ms "
              f"{found.name}  [{found.line}]")
