"""What the program's set-up account says of a run.

``hvd.cache_stats()["setup"]`` (a program since PR 50) keeps every span the
program recorded from the first line of ``import horovod_tpu`` to the end of
its first warm step: the ``hvd.setup.*`` spans of import, ``init``, placing
and building, one span a tracing, lowering, backend compile and cache read
that JAX reported meanwhile, and the ``hvd.step`` calls. Each span is a
dictionary with ``name``, ``t``, ``dur`` and, where the program gave them,
``id``, ``parent`` and ``args``; a nested jit's tracing is a child of its
caller's. The readers of ``setup_s``'s per-layer metrics are arithmetic on
those spans, and it is here, tested on hand-made accounts: seconds are the
union of intervals, so that what is nested or overlaps counts once.

A program that keeps no account (``"setup"`` is no key of its
``cache_stats()``) gives ``None`` and no metric; an account without a span
of the names asked for gives 0.0.
"""

from __future__ import annotations

import trace_reduce

STEP = "hvd.step"
PREFIX = "hvd.setup."


def spans() -> list | None:
    """The account's spans as the program serves them, or ``None`` from a
    program without one."""
    import horovod_tpu as hvd

    found = hvd.cache_stats().get("setup")
    return None if found is None else found["spans"]


def seconds(found) -> float:
    """The union of the spans' intervals."""
    return float(trace_reduce.total(
        (span["t"], span["t"] + span["dur"]) for span in found))


def named(found, names) -> list:
    return [span for span in found if span["name"] in names]


def ancestors(span, by_id) -> list:
    """The spans above ``span``, nearest first (a parent that the account
    dropped ends the chain)."""
    above = []
    while span is not None and span.get("parent") in by_id:
        span = by_id[span["parent"]]
        above.append(span)
    return above


def first_call(found, step: str) -> dict | None:
    """The ``hvd.step`` span of the first call of the factory step
    ``step``; the newest, where several steps of that name were built."""
    calls = [span for span in found if span["name"] == STEP
             and span.get("args", {}).get("kind") == step
             and span["args"].get("call") == 1]
    return calls[-1] if calls else None


def under(found, top: dict) -> list:
    """The spans of ``found`` below ``top``, at any depth."""
    by_id = {span["id"]: span for span in found if "id" in span}
    return [span for span in found
            if any(above is top for above in ancestors(span, by_id))]


def outside_steps(found) -> list:
    """The spans of ``found`` with no ``hvd.step`` above them."""
    by_id = {span["id"]: span for span in found if "id" in span}
    return [span for span in found
            if not any(above["name"] == STEP
                       for above in ancestors(span, by_id))]


def named_seconds(names, outside: bool = False):
    """The union of the spans called ``names``; with ``outside`` only of
    those with no ``hvd.step`` above them. ``None`` without an account."""
    found = spans()
    if found is None:
        return None
    return seconds(named(outside_steps(found) if outside else found, names))


def first_call_seconds(step: str, names=None, other: bool = False):
    """Of the first call of ``step``: its duration (no ``names``), the
    union of the spans called ``names`` below it, or with ``other`` its
    duration less the union of every ``hvd.setup.*`` span below it. 0.0
    where the account holds no such call; ``None`` without an account."""
    found = spans()
    if found is None:
        return None
    call = first_call(found, step)
    if call is None:
        return 0.0
    below = under(found, call)
    if other:
        return call["dur"] - seconds(
            span for span in below if span["name"].startswith(PREFIX))
    return call["dur"] if names is None else seconds(named(below, names))
