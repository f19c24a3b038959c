"""Device-profiler integration (the reference's NVTX/Nsight role → xprof).

Parity surface: the reference emits NVTX ranges per op
(``horovod/common/ops/nvtx_op_range.*``) so Nsight shows framework
activities against GPU kernels. Here the same role is played by
``jax.profiler``: timeline activities dual-emit ``TraceAnnotation`` ranges
(see :mod:`horovod_tpu.timeline`), and this module owns trace capture:

- ``HOROVOD_PROFILER_LOGDIR=/path`` (env contract, like
  ``HOROVOD_TIMELINE``): ``hvd.init()`` starts a trace there; call
  :func:`stop` (or exit) to finalize. View in TensorBoard/xprof, where
  framework annotations appear above the TPU op stream — one merged view.
- Programmatic: ``hvd.profiler.start(logdir)`` / ``hvd.profiler.stop()``,
  and :func:`trace` as a with-block for scoped capture.
- :func:`annotate_collective` names in-trace collective regions (segment
  allreduces, fusion buckets, hierarchical legs) so comm/compute overlap
  is visible against the TPU op stream in the captured trace.
"""

from __future__ import annotations

import os
import threading

_lock = threading.Lock()
_active_logdir: str | None = None


def start(logdir: str) -> None:
    """Begin a device trace into ``logdir`` (idempotent per process)."""
    global _active_logdir
    import jax.profiler

    with _lock:
        if _active_logdir is not None:
            return
        jax.profiler.start_trace(logdir)
        _active_logdir = logdir


def stop() -> None:
    global _active_logdir
    import jax.profiler

    with _lock:
        if _active_logdir is None:
            return
        jax.profiler.stop_trace()
        _active_logdir = None


def active() -> bool:
    return _active_logdir is not None


def maybe_start_from_env() -> None:
    """Called by ``hvd.init()``: honor HOROVOD_PROFILER_LOGDIR. A trace
    the user asked for by name that cannot start fails ``init()``."""
    logdir = os.environ.get("HOROVOD_PROFILER_LOGDIR", "")
    if logdir:
        start(logdir)


def summary() -> dict:
    """One-call observability snapshot: trace state plus the runtime
    counters callers keep asking the timeline for — executable-cache
    hits/misses/size, per-kind eager-dispatch counts
    (``hvd.cache_stats()``), the elastic goodput ledger (productive
    vs. lost wall time, see ``horovod_tpu.metrics.GoodputTracker``), the
    straggler view from the cross-rank tracing plane (this rank's
    measured clock offset ± error, plus — when a rendezvous KV is
    configured — the server-computed per-collective arrival-skew
    attribution), and the communication observatory's fitted α–β model
    (``"comms"``: per-key fits with sample counts, the
    predicted-vs-observed residual, the efficiency EWMA — reset via
    ``comms_model.reset_for_testing()``), and the step-time attribution
    plane (``"attribution"``: the last synced step's
    compute/exposed_comm/straggler_wait/overhead decomposition, MFU
    when ``hvd.set_model_flops_per_step`` declared the model's FLOPs,
    the predicted-vs-observed exposed-comm residual, and the local
    regression sentinel's state — see docs/observability.md "Step-time
    attribution"), and the HBM memory observatory (``"memory"``:
    per-kind resident bytes, the per-phase watermarks, the footprint
    model's predicted-vs-measured residual, headroom, and the top
    resident leaves — reset via ``memory.reset_for_testing()``).
    ``bench.py`` emits this once per run so every benchmark record
    carries the cache/goodput behavior that produced it.
    """
    from . import (attribution, comms_model, integrity, memory, metrics,
                   tracing)
    from .ops.collective_ops import cache_stats

    return {
        "trace_active": active(),
        "trace_logdir": _active_logdir,
        "goodput": metrics.goodput().summary(),
        "checkpoint": metrics.checkpoint_summary(),
        "stragglers": tracing.straggler_summary(),
        "fsdp": metrics.fsdp_summary(),
        "comms": comms_model.summary(),
        "integrity": integrity.summary(),
        "attribution": attribution.summary(),
        "memory": memory.summary(),
        **cache_stats(),
    }


def annotate_collective(name: str):
    """Name the ops traced inside the scope (``jax.named_scope``) so each
    collective region is identifiable in xprof traces and HLO dumps.

    This is the compiled-regime counterpart of the host timeline's
    ``activity`` ranges (which cannot see inside a jitted program): the
    overlap scheduler wraps every segment allreduce, the fusion pass every
    bucket, and the hierarchical reduction each of its three legs, so a
    profile of the step shows exactly which transfer overlaps which slice
    of backward compute. Safe anywhere — outside a trace the scope only
    prefixes op names of whatever gets traced next, and a backend without
    named-scope support degrades to a no-op."""
    import contextlib

    import jax

    try:
        return jax.named_scope(f"hvd.{name}")
    except Exception:  # pragma: no cover — annotation is best-effort
        return contextlib.nullcontext()


class trace:
    """Scoped capture: ``with hvd.profiler.trace('/tmp/prof'): step()``."""

    def __init__(self, logdir: str):
        self.logdir = logdir

    def __enter__(self):
        start(self.logdir)
        return self

    def __exit__(self, *exc):
        stop()
        return False
