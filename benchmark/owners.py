"""Whose device time it is: one partition of a traced step's busy time by
owner, where an owner is a scope the program itself opened.

``program_spans.device`` sums device seconds by phase, a fusion where its
root's name stack is and a loop's own event on top of the events inside it.
Since PR 34 the program names its blocks as well as its phases
(``hvd.block.*``) and reads an owner for every instruction of its step's
text, inside fusions and loops too (``hvd.profiler.instruction_owners``,
booked by ``hvd.profiler.booked_to``: the instruction's own scope, else the
only owner inside it, else ``shared`` where it holds several, else
``unowned``). This file joins that to the trace:

* **by instant**: at every instant the innermost open event of the "XLA
  Ops" line owns it (``self_seconds``), so a ``while``'s own event keeps only
  what the events of its body do not cover, and the rows add up to the
  union of the intervals, which ``partition`` checks to 0.01 ms a step;
* **shared fusions**: a fusion that holds instructions of several owners
  is entered, whoever it is booked to, in a table keyed by the sorted
  owner set (AdamW riding in a weight-gradient matmul is a row there);
* **unowned**: the instructions no scope owns, own or inside, largest
  first, each with its opcode, shape and the owners of its neighbours in
  the text; then all of them summed by opcode and neighbours (thousands
  of small copies make one row).

``of(run)`` is the readers' way in: once a run, on the device that idled
most, ``None`` off a TPU and for a program without ``instruction_owners``
(the parent of PR 34). It prints the tables. ``self_seconds`` and
``partition`` are arithmetic on plain intervals, tested on hand-made ones.
"""

from __future__ import annotations

import collections
import dataclasses
import time

import program_spans
import trace_reduce

TOLERANCE_MS = 0.01  # a step: rows against the union of the intervals
TOP = 10  # rows of the shared and the unowned tables that are printed


def self_seconds(ops) -> list:
    """Per operation, the seconds in which it was the innermost one open:
    its interval less what operations that began inside it cover. Whatever
    the nesting, the figures add up to the union of the intervals."""
    owned = [0.0] * len(ops)
    order = sorted(range(len(ops)),
                   key=lambda i: (ops[i].start, -ops[i].end))
    open_, told = [], float("-inf")  # told: every instant before is booked

    def close_until(instant):
        nonlocal told
        while open_ and ops[open_[-1]].end <= instant:
            last = open_.pop()
            owned[last] += max(0.0, ops[last].end - told)
            told = max(told, ops[last].end)

    for i in order:
        close_until(ops[i].start)
        if open_:
            owned[open_[-1]] += max(0.0, ops[i].start - told)
        told = max(told, ops[i].start)
        open_.append(i)
    close_until(float("inf"))
    return owned


@dataclasses.dataclass(frozen=True)
class Owners:
    booked: dict  # owner, "shared" or "unowned" -> device ms a step
    in_shared: dict  # owner -> ms a step of its booking in shared fusions
    shared_sets: dict  # sorted tuple of owners -> ms a step in such fusions
    unowned: list  # [(ms a step, instruction, opcode, shape, neighbours)]
    unowned_kinds: dict  # (opcode, neighbours) -> ms a step, all such
    busy_ms: float  # a step: the union of the intervals

    @property
    def unowned_ms(self) -> float:
        return sum(ms for ms, *_ in self.unowned)

    @property
    def shared_fusion_ms(self) -> float:
        return sum(self.shared_sets.values())


def partition(ops, steps: int, owners: dict) -> Owners:
    """``ops`` of one device by owner. ``owners`` maps an instruction to
    the program's record of it (``profiler.instruction_owners``), booked
    by the program's own rule; an operation the text does not hold is
    unowned."""
    from horovod_tpu import profiler

    nobody = profiler.Owner(None, frozenset(), (None, None), "", "")
    booked = collections.Counter()
    in_shared = collections.Counter()
    shared_sets = collections.Counter()
    nobodys = collections.Counter()
    for op, mine in zip(ops, self_seconds(ops)):
        record = owners.get(op.name, nobody)
        owner = profiler.booked_to(record)
        booked[owner] += mine
        if record.opcode == "fusion" and len(record.inside) > 1:
            in_shared[owner] += mine
            shared_sets[tuple(sorted(record.inside))] += mine
        if owner == profiler.OWNER_UNOWNED:
            nobodys[op.name] += mine
    union = trace_reduce.total(trace_reduce.spans(ops))
    if abs(sum(booked.values()) - union) * 1e3 > TOLERANCE_MS * steps:
        raise ValueError(
            f"owners: the rows add up to {sum(booked.values()):.6f} s and "
            f"the union of the {len(ops)} operations' intervals is "
            f"{union:.6f} s")

    def per_step(counter):
        return {name: found / steps * 1e3
                for name, found in counter.most_common()}

    kinds = collections.Counter()
    for name, mine in nobodys.items():
        record = owners.get(name, nobody)
        kinds[record.opcode, record.neighbours] += mine
    return Owners(
        booked=per_step(booked), in_shared=per_step(in_shared),
        shared_sets=per_step(shared_sets),
        unowned=[(ms, name, record.opcode, record.shape, record.neighbours)
                 for name, ms in per_step(nobodys).items()
                 for record in [owners.get(name, nobody)]],
        unowned_kinds=per_step(kinds), busy_ms=union / steps * 1e3)


def of(run) -> Owners | None:
    """The partition of ``run``'s trace, or ``None`` off a TPU (no device
    plane) and for a program without ``profiler.instruction_owners``. A
    program that has it and whose step's text holds no block scope (a
    stale executable out of a shared compile cache) raises, saying so."""
    def make():
        import horovod_tpu as hvd

        read = getattr(hvd.profiler, "instruction_owners", None)
        if not run.trace.devices or read is None:
            return None
        t0 = time.perf_counter()
        owners = read("\n".join(hvd.profiler.step_texts()))
        t1 = time.perf_counter()
        busy = trace_reduce.busy_seconds(run.trace)
        ops = run.trace.devices[min(busy, key=busy.get)]
        found = partition(ops, run.steps, owners)
        say(found)
        print(f"owners: {len(owners)} instructions of the step's text read "
              f"in {t1 - t0:.2f} s, {len(ops)} operations of the trace "
              f"partitioned in {time.perf_counter() - t1:.2f} s", flush=True)
        return found

    return program_spans.once(run, "owners", make)


def booked_ms(run, owner: str) -> float | None:
    """What the block readers return: ms a step booked to ``owner``;
    ``None`` where there is no partition or nothing of that owner ran."""
    found = of(run)
    return None if found is None else found.booked.get(owner)


def say(found: Owners) -> None:
    print("owners: device ms a step by the scope the program opened "
          "(booked; of that inside fusions that several owners share)",
          flush=True)
    for owner, ms in found.booked.items():
        print(f"owners: {ms:9.3f} {found.in_shared.get(owner, 0.0):9.3f}  "
              f"{owner}", flush=True)
    print(f"owners: {found.busy_ms:9.3f} ms a step busy, the union of the "
          f"intervals and the sum of the rows; {found.shared_fusion_ms:.3f} "
          "ms in shared fusions, by the owners they hold:", flush=True)
    for held, ms in list(found.shared_sets.items())[:TOP]:
        print(f"owners: shared {ms:9.3f}  {' + '.join(held)}", flush=True)
    for ms, name, opcode, shape, neighbours in found.unowned[:TOP]:
        print(f"owners: unowned {ms:9.3f}  {name} = {shape[:60]} {opcode}; "
              f"operand from {neighbours[0]}, first user {neighbours[1]}",
              flush=True)
    for (opcode, neighbours), ms in list(found.unowned_kinds.items())[:TOP]:
        print(f"owners: unowned, all {ms:9.3f}  {opcode or '(no text)'} "
              f"between {neighbours[0]} and {neighbours[1]}", flush=True)
