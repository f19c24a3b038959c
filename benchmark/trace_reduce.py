"""The one reduction from a profiler trace to numbers.

``read`` turns an ``.xplane.pb`` (``jax.profiler.ProfileData``) into a
``Trace``: per device the operations that ran, the benchmark's own host
annotations (``bench.step_call`` around every call into the step,
``bench.sync`` around every wait), and the window they span. Everything
below ``read`` is arithmetic on plain intervals in seconds and is tested on
hand-made ones. The per-layer metrics' readers call these functions; no
reader parses a trace itself.

    python benchmark/trace_reduce.py <file.xplane.pb> [steps [step.hlo.txt]]

prints what a trace holds (planes, lines, the commonest event names) and
what the reduction makes of it: look at a trace this way before writing a
reader against it.
"""

from __future__ import annotations

import collections
import dataclasses
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"  # what the core ran, one operation at a time
ASYNC_LINE = "Async XLA Ops"  # a -start's name, spanning to its -done
HOST_SPANS = ("bench.step_call", "bench.sync")
# An event is named by its instruction's whole text, cut at some length:
#   %psum.92 = bf16[32536576]{0:T(1024)(128)(2,1)} all-reduce(%fusion.103), ...
# The name is what the compiled step's text calls it; what it does is the
# opcode, the first lower-case word before a parenthesis (JAX names an
# all-reduce "psum.92", so the name alone does not tell a collective).
INSTRUCTION = re.compile(r"^%?(\S+) = .*? ([a-z][a-z0-9-]*)\(")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?$")
HALVES = ("-start", "-done")  # of an asynchronous operation


@dataclasses.dataclass(frozen=True)
class Op:
    name: str  # the HLO instruction's name, as in the compiled step's text
    opcode: str  # "fusion", "custom-call", "all-reduce", "copy-done", ...
    start: float  # seconds
    end: float


@dataclasses.dataclass(frozen=True)
class Trace:
    devices: dict  # device id -> [Op] the core ran, by start
    flights: dict  # device id -> [Op] asynchronous operations, start to done
    host: list  # [(annotation name, start, end)], by start
    window: tuple  # (start, end): first step call to the end of the last sync


def op_of(event) -> Op:
    parsed = INSTRUCTION.match(event.name)
    name, opcode = parsed.groups() if parsed else (event.name, "")
    return Op(name, opcode, event.start_ns * 1e-9,
              (event.start_ns + event.duration_ns) * 1e-9)


def read(path: str) -> Trace:
    """Operations are clipped to the window."""
    from jax.profiler import ProfileData

    host, lines = [], {OPS_LINE: {}, ASYNC_LINE: {}}
    for plane in ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if device and line.name in lines:
                lines[line.name][int(device.group(1))] = [
                    op_of(event) for event in line.events]
            elif not device:
                host.extend(
                    (event.name, event.start_ns * 1e-9,
                     (event.start_ns + event.duration_ns) * 1e-9)
                    for event in line.events if event.name in HOST_SPANS)
    host.sort(key=lambda span: span[1])
    if not host:
        raise ValueError(f"{path}: no {HOST_SPANS} annotation in the trace")
    window = (host[0][1], max(end for _, _, end in host))
    ops, flights = ({device: clip_ops(found, window)
                     for device, found in lines[name].items()}
                    for name in (OPS_LINE, ASYNC_LINE))
    return Trace(ops, flights, host, window)


def clip_ops(ops, window) -> list:
    low, high = window
    return sorted((dataclasses.replace(op, start=max(op.start, low),
                                       end=min(op.end, high))
                   for op in ops if op.end > low and op.start < high),
                  key=lambda op: op.start)


# -- intervals: lists of (start, end) ----------------------------------------

def union(intervals) -> list:
    """The same set of instants as disjoint intervals, in order."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], end))
        elif end > start:
            merged.append((start, end))
    return merged


def total(intervals) -> float:
    return sum(end - start for start, end in union(intervals))


def subtract(intervals, holes) -> list:
    """The part of ``intervals`` that no interval of ``holes`` covers."""
    left = []
    holes = union(holes)
    for start, end in union(intervals):
        for hole_start, hole_end in holes:
            if hole_end <= start or hole_start >= end:
                continue
            if hole_start > start:
                left.append((start, hole_start))
            start = max(start, hole_end)
        if start < end:
            left.append((start, end))
    return left


def spans(ops) -> list:
    return [(op.start, op.end) for op in ops]


# -- what the metrics read ----------------------------------------------------

def busy_seconds(trace: Trace) -> dict:
    """Per device, the seconds of the window in which an operation ran:
    the union of the operations' intervals."""
    return {device: total(spans(ops)) for device, ops in trace.devices.items()}


def idle_share(trace: Trace) -> float | None:
    """One less the busy share of the window, on the device that idled
    most."""
    busy = busy_seconds(trace)
    if not busy:
        return None
    return 1.0 - min(busy.values()) / (trace.window[1] - trace.window[0])


def matching(ops, pattern: str) -> list:
    wanted = re.compile(pattern)
    return [op for op in ops if wanted.search(op.name)]


def kernel_seconds(trace: Trace, pattern: str) -> float | None:
    """Summed device seconds of the operations whose name matches, on the
    device where they took longest; ``None`` where none ran."""
    sums = [sum(op.end - op.start for op in matching(ops, pattern))
            for ops in trace.devices.values()]
    return max(sums) if sums and max(sums) > 0 else None


def collective_spans(ops, flights) -> list:
    """The intervals in which a collective was in flight: a synchronous
    one for as long as its operation ran, an asynchronous one from its
    ``-start`` to its ``-done``, as the trace's own line of asynchronous
    operations spans it."""
    return spans([op for op in ops if COLLECTIVE.match(op.opcode)
                  and not op.opcode.endswith(HALVES)]
                 + [op for op in flights if COLLECTIVE.match(op.opcode)])


def collective_seconds(trace: Trace) -> tuple | None:
    """``(in flight, exposed)`` seconds of the collectives over the
    window, on the device where they were exposed longest. Exposed is the
    part during which the core ran nothing else (waiting in a ``-done`` is
    not something else)."""
    worst = None
    for device, ops in trace.devices.items():
        flying = collective_spans(ops, trace.flights.get(device, []))
        others = spans(op for op in ops if not COLLECTIVE.match(op.opcode))
        pair = (total(flying), total(subtract(flying, others)))
        if flying and (worst is None or pair[1] > worst[1]):
            worst = pair
    return worst


SCOPE = re.compile(
    r'^\s*(?:ROOT )?%?([\w.-]+) = [^\n]*?metadata=\{op_name="([^"]*)"', re.M)


def scopes_of(hlo: str) -> dict:
    """Instruction name -> the JAX operation it was compiled from, as the
    compiled step's text records it (``metadata={op_name="jit(spmd_step)/
    transpose(jvp(Bert))/layer_3/mlp_in/dot_general"``). The trace names an
    operation by its instruction; what it computes is only here."""
    return dict(SCOPE.findall(hlo))


def group_of(op: Op, scopes: dict) -> str:
    """The name a device operation is summed under: a collective by its
    opcode; an instruction the step's text gives a scope by its pass
    (forward under ``jvp``, backward under ``transpose``, else the update)
    and its JAX primitive; anything else by its opcode."""
    if COLLECTIVE.match(op.opcode):
        return op.opcode
    scope = scopes.get(op.name)
    if not scope:
        return op.opcode or op.name
    which = ("backward" if "transpose(" in scope
             else "forward" if "jvp(" in scope else "update")
    return f"{which} {scope.rsplit('/', 1)[-1]}"


def host_label(host: list, instant: float) -> str:
    for name, start, end in host:
        if start <= instant < end:
            return name
    return "outside the benchmark's annotations"


def breakdown(trace: Trace, steps: int, scopes: dict, top: int = 10) -> dict:
    """The result line's ``breakdown``, on the device that idled most: the
    operation groups that took most seconds a step, and the idle seconds a
    step by what the host was doing when the gap opened."""
    busy = busy_seconds(trace)
    device = min(busy, key=busy.get)
    ops = trace.devices[device]
    by_group = collections.Counter()
    for op in ops:
        by_group[group_of(op, scopes)] += op.end - op.start
    gaps = collections.Counter()
    for start, end in subtract([trace.window], spans(ops)):
        gaps[host_label(trace.host, start)] += end - start
    return {
        "device_ops": [[name, seconds / steps]
                       for name, seconds in by_group.most_common(top)],
        "idle_gaps": [[name, seconds / steps]
                      for name, seconds in gaps.most_common(top)]}


def describe(path: str, steps: int, hlo: str) -> None:
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            names = collections.Counter(e.name for e in events)
            print(f"  line {line.name!r}: {len(events)} events; commonest "
                  f"{names.most_common(8)}")
            if events:
                print(f"    first event stats: {dict(events[0].stats)}")
    trace = read(path)
    print(f"window {trace.window[1] - trace.window[0]:.6f} s, busy "
          f"{busy_seconds(trace)}, idle share {idle_share(trace)}")
    print(f"collectives (in flight, exposed): {collective_seconds(trace)}")
    for key, rows in breakdown(trace, steps, scopes_of(hlo)).items():
        print(key)
        for name, seconds in rows:
            print(f"  {seconds * 1e3:10.3f} ms a step  {name}")


if __name__ == "__main__":
    hlo_text = ""
    if len(sys.argv) > 3:
        with open(sys.argv[3]) as f:
            hlo_text = f.read()
    describe(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 1,
             hlo_text)
