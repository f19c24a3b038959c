"""Autotuning surface for the compiled (JAX) path.

Parity: the reference's autotuner (``horovod/common/parameter_manager.cc`` +
``optim/bayesian_optimization.cc``) tunes runtime knobs online. The native
runtime embeds that machinery directly (``HOROVOD_AUTOTUNE=1`` tunes the
background loop's fusion threshold + cycle time; see ``cpp/autotune.cc``).
This module exposes the SAME native Bayesian optimizer to Python for the
JAX path, where the tunable is the trace-time gradient-bucketing threshold:
each candidate re-compiles the step, so the tuner times steady-state steps
per candidate and converges on the best bucket size.

Usage::

    best = hvd.autotune.tune_fusion_threshold(
        build_step,   # (threshold_bytes) -> step callable
        run_steps,    # (step) -> seconds per step (user-timed window)
        rounds=12,
    )
"""

from __future__ import annotations

import ctypes
from typing import Any, Callable, Sequence

from .utils.logging import get_logger


class BayesianTuner:
    """ctypes wrapper over the native GP/EI optimizer (maximizes score)."""

    def __init__(self, lows: Sequence[float], highs: Sequence[float],
                 seed: int = 42):
        from .runtime import load_library

        self._lib = load_library()
        self._lib.hvdrt_bo_new.restype = ctypes.c_int
        self._lib.hvdrt_bo_new.argtypes = [
            ctypes.c_int, ctypes.POINTER(ctypes.c_double),
            ctypes.POINTER(ctypes.c_double), ctypes.c_longlong,
        ]
        self._lib.hvdrt_bo_add.argtypes = [
            ctypes.c_int, ctypes.POINTER(ctypes.c_double), ctypes.c_int,
            ctypes.c_double,
        ]
        self._lib.hvdrt_bo_suggest.argtypes = [
            ctypes.c_int, ctypes.POINTER(ctypes.c_double), ctypes.c_int,
        ]
        self._lib.hvdrt_bo_best.restype = ctypes.c_double
        self._lib.hvdrt_bo_best.argtypes = [
            ctypes.c_int, ctypes.POINTER(ctypes.c_double), ctypes.c_int,
        ]
        self._dims = len(lows)
        arr = ctypes.c_double * self._dims
        self._id = self._lib.hvdrt_bo_new(
            self._dims, arr(*lows), arr(*highs), seed
        )

    def add_sample(self, params: Sequence[float], score: float) -> None:
        arr = (ctypes.c_double * self._dims)(*params)
        self._lib.hvdrt_bo_add(self._id, arr, self._dims, score)

    def suggest(self) -> list[float]:
        out = (ctypes.c_double * self._dims)()
        rc = self._lib.hvdrt_bo_suggest(self._id, out, self._dims)
        if rc != 0:
            raise RuntimeError("BO suggest failed")
        return list(out)

    def best(self) -> tuple[list[float], float]:
        out = (ctypes.c_double * self._dims)()
        score = self._lib.hvdrt_bo_best(self._id, out, self._dims)
        return list(out), score

    def close(self) -> None:
        self._lib.hvdrt_bo_free(self._id)


# -- compiled-path production tuning (VERDICT r3 #6) -------------------------
# The reference autotunes its actual hot path (parameter_manager.cc tunes
# the fusion buffer feeding NCCL); here the actual hot path is trace-time
# bucketing inside the user's jitted step, so the tuner re-traces the SAME
# step per candidate threshold, times a few steps, and pins the winner.

_tuned: dict = {"threshold": None, "segments": None, "sync_mode": None,
                "algorithm": None, "mesh_shape": None, "aborted": False,
                "history": [], "pruned": []}


def model_guided_enabled() -> bool:
    """Model-guided autotune mode (``HOROVOD_AUTOTUNE_MODEL_GUIDED=1``):
    the warmup tuner prices every grid candidate with the communication
    observatory's fitted α–β model (``comms_model.predict_flush_cost``)
    and prunes dominated grid points before sweeping them — the joint
    grid goes from exhaustive to guided. Off by default (the exhaustive
    sweep is the reference contract), and inert even when armed until
    the model has fitted samples AND a traced flush has noted its leaf
    layout — a cold process sweeps the full grid exactly as before."""
    from .utils.env import get_bool

    return get_bool("HOROVOD_AUTOTUNE_MODEL_GUIDED", False)


def _record_trial(tunable: str, seconds: float) -> None:
    """Metrics-plane record of one completed sampling window (the
    observability counterpart of HOROVOD_AUTOTUNE_LOG). Best-effort."""
    try:
        from . import metrics

        metrics.AUTOTUNE_TRIALS.inc(tunable=tunable)
        metrics.AUTOTUNE_TRIAL_SECONDS.observe(seconds)
    except Exception:  # noqa: BLE001
        pass


def warmup_aborted() -> bool:
    """True after a mid-warmup abort in THIS process (see
    ``AutotuneStep._abort``): peers may have pinned a different
    (broadcast) decision, so every factory-built step here refuses to
    run — not just the tuner's own wrapper. Co-built steps and steps
    built after the abort pass through ``maybe_autotune_step`` bare, so
    the gate lives in the factory wrapper (``_StallWatchedStep``)."""
    return _tuned["aborted"]


def _poison_error():
    from .exceptions import HorovodInternalError

    return HorovodInternalError(
        "autotune warmup aborted on this rank; peers may have pinned a "
        "different (broadcast) decision, so this process's traced "
        "collective sequences can no longer be trusted to match theirs "
        "— treat the original mid-warmup exception as fatal and restart "
        "the job")


def tuned_threshold() -> int | None:
    """The pinned autotune decision (None = untuned; env/config rule)."""
    return _tuned["threshold"]


def set_tuned_threshold(threshold_bytes: int | None) -> None:
    """Pin (or clear, with None) the trace-time fusion threshold. Wins
    over env/config in ``ops.fusion.fusion_threshold_bytes``."""
    _tuned["threshold"] = (
        None if threshold_bytes is None else int(threshold_bytes))


def tuned_segments() -> int | None:
    """The pinned overlap-scheduler segment count (None = untuned)."""
    return _tuned["segments"]


def set_tuned_segments(num_segments: int | None) -> None:
    """Pin (or clear, with None) the overlap scheduler's segment count K.
    Wins over ``HOROVOD_OVERLAP_SEGMENTS`` in
    ``ops.fusion.overlap_segments``."""
    _tuned["segments"] = (
        None if num_segments is None else int(num_segments))


def tuned_algorithm() -> str | None:
    """The pinned comms-planner collective algorithm (None = untuned —
    the planner prices per bucket; see ``ops/comms_planner.py``). The
    fourth joint-grid axis: a concrete pin overrides the per-bucket
    pricing for EVERY planned bucket, which is what lets one sampling
    window measure one schedule; the ``"auto"`` pin records that the
    sweep measured the un-pinned per-bucket mode and chose it (the
    planner treats it exactly like no pin)."""
    return _tuned["algorithm"]


def set_tuned_algorithm(algorithm: str | None) -> None:
    """Pin (or clear, with None) the planner's collective algorithm.
    Wins over per-bucket pricing in ``comms_planner.plan_bucket``;
    ``"auto"`` is a valid decision meaning per-bucket pricing won the
    sweep."""
    if algorithm is not None and algorithm != "auto":
        from .ops.comms_planner import PLANNER_ALGORITHMS

        if algorithm not in PLANNER_ALGORITHMS:
            raise ValueError(
                f"unknown algorithm {algorithm!r}; expected one of "
                f"{PLANNER_ALGORITHMS + ('auto',)}")
    _tuned["algorithm"] = algorithm


def tuned_sync_mode() -> str | None:
    """The pinned gradient sync mode (None = untuned; env/default rule).

    Consulted by ``optimizer.resolve_sync_mode`` at DistributedOptimizer
    CONSTRUCTION — the mode fixes the optimizer-state layout, so (unlike
    the threshold/segments axes, which re-trace in place) a pin only
    affects optimizers built after it lands."""
    return _tuned["sync_mode"]


def set_tuned_sync_mode(sync_mode: str | None) -> None:
    """Pin (or clear, with None) the gradient sync mode. Wins over
    ``HOROVOD_SYNC_MODE`` in ``optimizer.resolve_sync_mode``."""
    if sync_mode is not None:
        from .optimizer import _VALID_SYNC_MODES

        if sync_mode not in _VALID_SYNC_MODES:
            raise ValueError(
                f"unknown sync_mode {sync_mode!r}; expected one of "
                f"{_VALID_SYNC_MODES}")
    _tuned["sync_mode"] = sync_mode


def tuned_mesh_shape() -> tuple[int, int] | None:
    """The pinned 2-D ``(batch, model)`` mesh shape (None = untuned;
    the ``HOROVOD_MESH_SHAPE`` env and explicit ``mesh=`` factory
    arguments rule). Consulted by
    ``parallel.mesh.resolve_mesh_shape`` at step-factory CONSTRUCTION —
    like the sync_mode axis, the shape fixes the state layout's device
    placement, so a pin only affects steps built after it lands."""
    return _tuned["mesh_shape"]


def set_tuned_mesh_shape(mesh_shape: tuple[int, int] | None) -> None:
    """Pin (or clear, with None) the 2-D training-mesh shape. Loses to
    ``HOROVOD_MESH_SHAPE`` and explicit ``mesh=`` factory arguments in
    ``parallel.mesh.resolve_mesh_shape``."""
    if mesh_shape is None:
        _tuned["mesh_shape"] = None
        return
    try:
        b, m = (int(v) for v in mesh_shape)
    except (TypeError, ValueError):
        raise ValueError(
            f"mesh_shape must be a (batch, model) pair of ints, got "
            f"{mesh_shape!r}") from None
    if m < 1 or (b < 1 and b != -1):
        raise ValueError(
            f"mesh_shape axes must be positive (batch may be -1 to "
            f"infer), got {mesh_shape!r}")
    _tuned["mesh_shape"] = (b, m)


def autotune_state() -> dict:
    """Introspection (parity: the native ``hvdrt_autotune_state``): the
    live threshold, whether a tuned decision is pinned, and the measured
    (threshold, seconds/step) samples."""
    from .ops.fusion import fusion_threshold_bytes

    return {
        "active": _tuned["threshold"] is not None,
        "fusion_threshold": fusion_threshold_bytes(),
        "overlap_segments": _tuned["segments"],
        "sync_mode": _tuned["sync_mode"],
        "algorithm": _tuned["algorithm"],
        "mesh_shape": _tuned["mesh_shape"],
        "samples": len(_tuned["history"]),
        "history": list(_tuned["history"]),
        "pruned": list(_tuned["pruned"]),
    }


DEFAULT_THRESHOLDS = (256 * 1024, 4 * 1024 * 1024, 64 * 1024 * 1024)

# Candidate segment counts K for the overlap scheduler's warmup grid.
# Tuned JOINTLY with the fusion threshold (the per-segment bucket size and
# the segment count trade against each other: more segments -> smaller
# per-segment payloads -> a large threshold degenerates to one bucket per
# segment anyway).
DEFAULT_SEGMENT_CANDIDATES = (2, 4, 8)


class AutotuneStep:
    """Transparent warmup autotuning of a factory-built train step — the
    compiled-path consumer of ``HOROVOD_AUTOTUNE=1``.

    The reference's contract is the env flag and NOTHING else: tuning
    happens inside the first training steps, invisibly
    (``parameter_manager.cc`` warmup windows). Here the tunable is the
    trace-time fusion threshold, so the wrapper spends the first
    ``len(thresholds) * (1 + iters)`` REAL training calls as sampling
    windows: each candidate pins the threshold, re-traces the step
    (``clear_cache`` — the wrapper owns the jit object, the user calls
    nothing), and times ``iters`` live steps. Training progresses
    normally throughout (every call returns its real result, exactly as
    the reference tunes during real training). After the last window the
    fastest candidate is pinned process-wide, the decision is logged
    (and appended to ``HOROVOD_AUTOTUNE_LOG`` as a JSON line), and the
    wrapper becomes a passthrough. With ``segment_candidates`` (the
    overlap scheduler's factory supplies them) the warmup grid is the
    joint (fusion threshold, segment count K) product — the two knobs
    trade against each other, so they are sampled and pinned together.

    **Model-guided pruning** (``HOROVOD_AUTOTUNE_MODEL_GUIDED=1``, off
    by default): after the first sampling window — whose trace notes the flush's
    leaf layout on the communication observatory — every remaining grid
    candidate is priced with the fitted α–β cost model
    (``comms_model.predict_flush_cost``: segment, bucket, and price each
    collective half per the candidate's threshold/segments/sync_mode),
    and candidates whose predicted cost exceeds the best prediction by
    more than ``HOROVOD_AUTOTUNE_PRUNE_MARGIN`` are dropped before they
    cost a sampling window each. The kept list is rank 0's, broadcast
    through the same exchange the winner rides, so the per-window traced
    collective sequence stays rank-identical by construction; a cold
    model (no samples, no noted layout) leaves the grid untouched.

    Window timing ends in ``jax.block_until_ready`` on the window's last
    outputs. In multi-process worlds every rank
    samples on the same call schedule (lockstep training) and rank 0's
    winner is broadcast before pinning: the threshold changes the traced
    program, so ranks MUST agree or their collective sequences diverge.
    """

    def __init__(self, jitted, thresholds=None, iters: int = 3,
                 clock=None, segment_candidates=None,
                 sync_mode_candidates=None, algorithm_candidates=None):
        import time as _time

        self._fn = jitted
        self._tune_segments = segment_candidates is not None
        self._tune_sync = sync_mode_candidates is not None
        self._tune_algorithm = algorithm_candidates is not None
        if self._tune_segments or self._tune_sync or self._tune_algorithm:
            # Joint grid over the axes present — (threshold[, segments]
            # [, sync_mode][, algorithm]). Every axis changes the traced
            # program, so they pin together per window and broadcast
            # together at finish. The sync_mode axis carries the caveat
            # in :func:`tuned_sync_mode`: the mode fixes the
            # optimizer-state LAYOUT, so only a step whose callable
            # re-reads the pin per trace (a factory rebuilt per window,
            # or a mode-agnostic harness like tune_step_sync_mode's) can
            # ride this axis — the stock factories tune
            # threshold/segments (and, when the comms planner is live,
            # the algorithm axis: a re-trace re-plans, so the pin takes
            # effect in place).
            self._cands = [
                (int(t),)
                + ((int(s),) if self._tune_segments else ())
                + ((str(m),) if self._tune_sync else ())
                + ((str(a),) if self._tune_algorithm else ())
                for a in (algorithm_candidates or (None,))
                for m in (sync_mode_candidates or (None,))
                for s in (segment_candidates or (None,))
                for t in (thresholds or DEFAULT_THRESHOLDS)
            ]
        else:
            self._cands = list(thresholds or DEFAULT_THRESHOLDS)
        self._poisoned = False
        self._prune_checked = False
        self._iters = max(1, int(iters))
        self._win = 1 + self._iters  # 1 compile/settle call + timed calls
        self._calls = 0
        self._samples: list[tuple[int, float]] = []
        self._t0 = 0.0
        self._clock = clock or _time.perf_counter  # tests inject cost models
        self._co_steps: list = []  # steps built mid-warmup: re-trace at pin
        self._hvd_tuning = True  # stall watch skips while tuning

    def _axes_name(self) -> str:
        axes = ["fusion_threshold_bytes"]
        if self._tune_segments:
            axes.append("overlap_segments")
        if self._tune_sync:
            axes.append("sync_mode")
        if self._tune_algorithm:
            axes.append("algorithm")
        return "+".join(axes)

    def _broadcast_decision(self, decision):
        """Rank 0's value, everywhere (the same exchange :meth:`_finish`
        pins the winner with — single-process worlds pass through)."""
        from .process_world import size as _psize

        if _psize() > 1:
            from .process_world import broadcast_object_host

            return broadcast_object_host(
                decision, name="autotune/model-guided-prune")
        import jax

        if jax.process_count() > 1:
            from .functions import broadcast_object

            return broadcast_object(
                decision, name="autotune/model-guided-prune")
        return decision

    def _maybe_prune(self) -> None:
        """Model-guided grid pruning, run ONCE after the first window.

        The first window's trace noted the flush's leaf layout on the
        communication observatory (``ops/fusion``), so from here every
        remaining candidate's wire can be priced with the fitted α–β
        model and dominated grid points dropped before they cost a
        sampling window each. Rank-identical by construction: every
        rank computes its verdict from its local model, then adopts
        RANK 0's kept list through the same broadcast the final winner
        rides — so the candidate schedule (which fixes the traced
        collective sequence per window) can never diverge across ranks.
        The already-sampled first candidate is always kept; any failure
        leaves the full grid intact."""
        if self._prune_checked:
            return
        self._prune_checked = True
        from . import memory as _memory

        if not model_guided_enabled() and not _memory.memory_guard_enabled():
            return
        # LOCAL pricing may fail safe (kept_idx=None = no pruning): rank
        # 0's verdict is what everyone adopts, so a rank-local pricing
        # failure cannot diverge the schedule. The BROADCAST must NOT be
        # swallowed here: an asymmetric broadcast failure would leave
        # ranks on different grids, so it propagates to __call__'s
        # handler, which aborts rank-identically (_abort).
        kept_idx = None
        try:
            from . import comms_model

            model = comms_model.get_model()
            leaf_sizes = model.leaf_sizes()
            kept_list = list(self._cands[1:])
            did_filter = False
            if (model_guided_enabled() and model.ready() and leaf_sizes
                    and len(self._cands) > 1):
                from .ops.collective_ops import _link_class_of
                from .process_sets import global_process_set

                link_class = _link_class_of(global_process_set)
                verdict = comms_model.prune_candidates(
                    kept_list, leaf_sizes, link_class)
                kept_list = verdict["kept"]
                did_filter = True
            if _memory.memory_guard_enabled() and len(self._cands) > 1:
                # Second stage: the memory guard drops candidates whose
                # predicted per-rank peak exceeds device capacity —
                # pure pricing from the noted layout + env, so every
                # rank agrees, but rank 0's list is still what is
                # adopted (same broadcast discipline as the cost stage).
                mem_verdict = _memory.filter_candidates(kept_list)
                if mem_verdict["pruned"]:
                    get_logger().info(
                        "autotune: memory guard rejected %d candidate(s) "
                        "over HBM capacity: %s",
                        len(mem_verdict["pruned"]), mem_verdict["pruned"])
                    kept_list = mem_verdict["kept"]
                    did_filter = True
            if did_filter:
                # kept is an order-preserving subsequence of the tail:
                # recover indices with a two-pointer walk (id()/set
                # matching would misbehave on duplicate grid values).
                kept_idx = []
                ki = 0
                for i, c in enumerate(self._cands[1:]):
                    if ki < len(kept_list) and kept_list[ki] == c:
                        kept_idx.append(i)
                        ki += 1
        except Exception as e:  # noqa: BLE001 — pricing is an optimization
            get_logger().debug("autotune: model-guided pricing skipped: %s",
                               e)
            kept_idx = None
        kept_idx = self._broadcast_decision(kept_idx)
        if kept_idx is None:
            return
        tail = list(self._cands[1:])
        pruned = [c for i, c in enumerate(tail) if i not in kept_idx]
        if not pruned:
            return
        self._cands = [self._cands[0]] + [
            tail[i] for i in kept_idx if 0 <= i < len(tail)]
        _tuned["pruned"].extend(pruned)
        get_logger().info(
            "autotune: model-guided pruning dropped %d dominated "
            "candidate(s) %s; sweeping %d of the original grid",
            len(pruned), pruned, len(self._cands))

    def _pin(self, cand) -> None:
        """Pin one candidate process-wide: the threshold, plus jointly
        the segments, sync_mode, and/or algorithm axes when tuned."""
        if not (self._tune_segments or self._tune_sync
                or self._tune_algorithm):
            set_tuned_threshold(cand)
            return
        cand = tuple(cand)
        set_tuned_threshold(cand[0])
        i = 1
        if self._tune_segments:
            set_tuned_segments(cand[i])
            i += 1
        if self._tune_sync:
            set_tuned_sync_mode(cand[i])
            i += 1
        if self._tune_algorithm:
            set_tuned_algorithm(cand[i])

    def _finish(self) -> None:
        import json
        import os

        best = min(self._samples, key=lambda s: s[1])
        decision = best[0]
        if isinstance(decision, tuple):
            decision = tuple(
                x if isinstance(x, str) else int(x) for x in decision)
        else:
            decision = int(decision)
        from .process_world import rank as _prank
        from .process_world import size as _psize

        if _psize() > 1:
            from .process_world import broadcast_object_host

            decision = broadcast_object_host(
                decision, name="autotune/step-decision")
        else:
            import jax

            if jax.process_count() > 1:
                from .functions import broadcast_object

                decision = broadcast_object(
                    decision, name="autotune/step-decision")
        self._pin(decision)
        _tuned["history"].extend(self._samples)
        if decision != self._cands[-1]:
            # The cache holds the LAST candidate's trace; only a
            # different winner needs the re-trace.
            self._fn.clear_cache()
        for co in self._co_steps:
            # Steps built mid-warmup traced under a candidate threshold;
            # clear them so their next call re-traces with the winner.
            try:
                co.clear_cache()
            except AttributeError:  # pragma: no cover — non-jit callable
                pass
        self._co_steps.clear()
        self._hvd_tuning = False
        log = get_logger()
        log.info(
            "autotune: pinned %s=%s after %d warmup windows %s",
            self._axes_name(), decision, len(self._samples),
            [(t, round(s, 5)) for t, s in self._samples])
        path = os.environ.get("HOROVOD_AUTOTUNE_LOG", "")
        # One writer only: the env propagates to every worker and the
        # broadcast decision is rank 0's anyway — N appenders would tear
        # lines on shared filesystems. In the jax-multicontroller regime
        # (no hvdrun env contract) process_world.rank() is 0 everywhere,
        # so gate on jax.process_index there.
        import jax as _jax

        writer = (_prank() == 0 if _psize() > 1
                  else _jax.process_index() == 0)
        if path and writer:
            try:
                with open(path, "a") as f:
                    f.write(json.dumps({
                        "tunable": self._axes_name(),
                        "decision": decision,
                        "samples": self._samples,
                    }) + "\n")
            except OSError:  # pragma: no cover — logging is best-effort
                log.warning("autotune: cannot write HOROVOD_AUTOTUNE_LOG=%s",
                            path)

    def _abort(self) -> None:
        """A window (or the finish exchange) raised: pin the FIRST
        candidate, stop tuning, and POISON the wrapper. Not best-so-far:
        an abort may hit a single rank (a local exception), so any
        sample-derived choice could differ across ranks — and the
        threshold changes the traced program, so divergent pins deadlock
        the next collective. The first candidate is rank-identical by
        construction and needs no agreement exchange (which could itself
        hang mid-exception). The poison is PROCESS-WIDE
        (:func:`warmup_aborted`): calls through this wrapper, through
        co-built steps, and through factory steps built after the abort
        all raise ``HorovodInternalError`` instead of training on —
        surviving ranks keep sampling and later pin the broadcast
        winner, so a rank that caught the exception and kept calling ANY
        step would trace a DIFFERENT collective sequence and deadlock
        the job silently (ADVICE r5). The original exception still
        propagates to the caller."""
        decision = self._cands[0]
        self._pin(decision)
        self._poisoned = True
        _tuned["aborted"] = True
        self._fn.clear_cache()
        for co in self._co_steps:
            try:
                co.clear_cache()
            except AttributeError:  # pragma: no cover
                pass
        self._co_steps.clear()
        self._hvd_tuning = False
        get_logger().warning(
            "autotune: aborted mid-warmup after %d sample(s); pinned the "
            "rank-identical first candidate %s and poisoned the tuned "
            "step (further calls raise)", len(self._samples), decision)

    def __call__(self, *args, **kwargs):
        if self._poisoned or warmup_aborted():
            raise _poison_error()
        if not self._hvd_tuning:
            return self._fn(*args, **kwargs)
        import jax

        idx, pos = divmod(self._calls, self._win)
        self._calls += 1
        try:
            if pos == 0:
                # Window start: pin the candidate and force a re-trace.
                # The call compiles + settles; timing starts once its
                # outputs are ready.
                self._pin(self._cands[idx])
                self._fn.clear_cache()
                out = self._fn(*args, **kwargs)
                jax.block_until_ready(out)
                self._t0 = self._clock()
                return out
            out = self._fn(*args, **kwargs)
            if pos == self._win - 1:
                jax.block_until_ready(out)
                dt = (self._clock() - self._t0) / self._iters
                self._samples.append((self._cands[idx], dt))
                _record_trial(self._axes_name(), dt)
                if idx == 0:
                    # The first window's trace has noted the flush's
                    # leaf layout: prune dominated grid points before
                    # they each cost a sampling window (model-guided
                    # mode; no-op when the comms model is cold).
                    self._maybe_prune()
                if idx + 1 == len(self._cands):
                    self._finish()
            return out
        except Exception:
            self._abort()
            raise

    def __getattr__(self, item):
        if item == "_fn":  # guard: lookup before __init__ must not recurse
            raise AttributeError(item)
        return getattr(self._fn, item)


_active_tuner: list = []  # at most one in-flight warmup tuner per process


def maybe_autotune_step(jitted, segment_candidates=None,
                        sync_mode_candidates=None,
                        algorithm_candidates=None):
    """Wrap ``jitted`` in transparent warmup tuning when
    ``HOROVOD_AUTOTUNE=1`` (env or config) — the factory entry point.

    ``segment_candidates`` (the overlap scheduler's factory passes
    :data:`DEFAULT_SEGMENT_CANDIDATES`) switches the tuner to the joint
    (threshold, segments) grid; ``sync_mode_candidates`` adds the
    sync_mode axis (see :func:`tuned_sync_mode` for its layout caveat —
    the stock factories do not pass it; :func:`tune_step_sync_mode` is
    the mode-agnostic harness); ``algorithm_candidates`` adds the comms
    planner's collective-algorithm axis (the step factories pass
    ``comms_planner.autotune_candidates()`` — non-None only when
    ``HOROVOD_COMMS_PLANNER=auto`` and >1 algorithm is eligible). When
    the communication observatory has a fitted α–β model, the grid is
    swept model-guided: dominated candidates are pruned after the first
    window (rank-identically — see :meth:`AutotuneStep._maybe_prune`
    and docs/observability.md's "Communication cost model" section).

    At most ONE tuner is live per process: the threshold is
    process-global, so a second factory call before the first tuner
    finishes (a train step + an eval step built at startup) must not
    race it — later steps pass through and inherit the first tuner's
    decision, exactly as every step shares the native runtime's single
    parameter_manager in the reference."""
    from .utils.env import get_bool

    if not get_bool("HOROVOD_AUTOTUNE") or tuned_threshold() is not None:
        return jitted
    if _active_tuner and _active_tuner[0]._hvd_tuning:
        # A step built mid-warmup would trace under whatever CANDIDATE
        # is pinned at its first call — register it so the tuner clears
        # its cache when the winner lands and it re-traces tuned.
        _active_tuner[0]._co_steps.append(jitted)
        return jitted
    tuner = AutotuneStep(jitted, segment_candidates=segment_candidates,
                         sync_mode_candidates=sync_mode_candidates,
                         algorithm_candidates=algorithm_candidates)
    _active_tuner[:] = [tuner]
    return tuner


def tune_step_sync_mode(
    build_step: Callable[..., Callable[[], Any]],
    sync_modes: Sequence[str] = ("allreduce", "sharded", "fsdp"),
    iters: int = 3,
    mesh_shapes: Sequence[tuple[int, int] | None] | None = None,
) -> str:
    """Explicit warmup tuning of the gradient sync mode.

    The sync_mode axis cannot ride the transparent per-step tuner for a
    stock factory step: the mode fixes the optimizer-state LAYOUT
    (monolithic pytree vs sharded stacked rows vs resident fsdp param
    rows), so one jitted step cannot re-trace between modes against the
    same state arguments. This harness sidesteps that by letting the
    caller rebuild the whole (optimizer, state, step) world per mode::

        def build(mode):
            opt = hvd.DistributedOptimizer(optax.adam(1e-3),
                                           sync_mode=mode)
            step = hvd.data_parallel.make_train_step(loss_fn, opt)
            state = make_state_for(opt)          # replicate / shard_state
            return lambda: step(*state.feed())   # one timed step

    An INELIGIBLE mode — ``build_step(mode)`` (or its compile/settle
    call) raising :class:`~horovod_tpu.exceptions.SyncModeIneligibleError`,
    the guard tables' dedicated class (e.g. fsdp with num_groups>1,
    sharded on a hierarchical mesh, replicated params fed to the fsdp
    factory) — is SKIPPED with a warning, not treated as an abort:
    guard rejections are deterministic functions of the job's static
    configuration, so every rank skips identically and the sweep stays
    rank-aligned. Any OTHER exception (including a bare ``ValueError``
    from user code, which could be rank-local) keeps the abort
    semantics: the rank-identical first ELIGIBLE mode is pinned before
    re-raising, so a partially-sampled decision can never diverge
    across ranks. All modes ineligible raises ``ValueError``.

    The fastest eligible mode is pinned via :func:`set_tuned_sync_mode`
    (so optimizers built afterwards with ``sync_mode=None`` inherit it)
    and returned.

    ``mesh_shapes`` joins the 2-D training-mesh shape into the grid: the
    sweep then measures the cross product ``sync_modes × mesh_shapes``
    (a ``None`` shape = the flat 1-D wire) and ``build_step`` is called
    with TWO arguments, ``build_step(mode, shape)``. The winning pair is
    pinned via :func:`set_tuned_sync_mode` AND
    :func:`set_tuned_mesh_shape`; abort semantics pin the rank-identical
    first eligible (mode, shape) pair on both axes. Without
    ``mesh_shapes`` the signature and pins are exactly the historical
    single-axis ones.
    """
    import time as _time

    import jax

    from .exceptions import SyncModeIneligibleError

    log = get_logger()
    joint = mesh_shapes is not None
    shapes: Sequence[tuple[int, int] | None] = (
        tuple(mesh_shapes) if joint else (None,))
    grid = [(mode, shape) for mode in sync_modes for shape in shapes]
    results: list[tuple[tuple[str, tuple[int, int] | None], float]] = []
    skipped: set[tuple[str, tuple[int, int] | None]] = set()

    def _label(mode, shape):
        if not joint:
            return repr(mode)
        return f"{mode!r} x {shape[0]}x{shape[1]}" if shape else f"{mode!r} x flat"

    try:
        for mode, shape in grid:
            try:
                # The memory guard prices the candidate BEFORE building
                # it: a mode predicted to blow HBM raises
                # MemoryBudgetExceededError (a SyncModeIneligibleError,
                # so it rides the same rank-identical skip as the guard
                # tables — pricing is a pure function of the noted
                # layout + env). Inert with the knob unset.
                from . import memory as _memory

                _memory.check_candidate(mode, mesh_shape=shape)
                run = build_step(mode, shape) if joint else build_step(mode)
                out = run()  # compile + settle
            except SyncModeIneligibleError as e:
                log.warning(
                    "autotune sync_mode: %s ineligible for this job "
                    "(%s); skipped", _label(mode, shape), e)
                skipped.add((mode, shape))
                continue
            jax.block_until_ready(out)
            t0 = _time.perf_counter()
            for _ in range(max(1, iters)):
                out = run()
            jax.block_until_ready(out)
            seconds = (_time.perf_counter() - t0) / max(1, iters)
            results.append(((mode, shape), seconds))
            _record_trial("sync_mode", seconds)
            log.info("autotune sync_mode: %s -> %.6fs/step",
                     _label(mode, shape), seconds)
    except Exception:
        # Pin the first candidate NOT already proven ineligible — a
        # skipped mode would crash every later sync_mode=None
        # construction on its own guard. Skipping is a deterministic
        # function of the job's static config, so this choice stays
        # rank-identical.
        fb_mode, fb_shape = next(
            (c for c in grid if c not in skipped), grid[0])
        set_tuned_sync_mode(fb_mode)
        if joint:
            set_tuned_mesh_shape(fb_shape)
        log.warning(
            "autotune sync_mode: aborted mid-sweep; pinned the "
            "rank-identical first eligible candidate %s",
            _label(fb_mode, fb_shape))
        raise
    if not results:
        raise ValueError(
            f"autotune sync_mode: every candidate in {tuple(grid)} "
            "was ineligible for this job (see the skip warnings above)")
    (best, best_shape) = min(results, key=lambda p: p[1])[0]
    set_tuned_sync_mode(best)
    if joint:
        set_tuned_mesh_shape(best_shape)
    log.info("autotune sync_mode: pinned %s", _label(best, best_shape))
    return best


def tune_step_fusion(
    step,
    args: tuple,
    thresholds: Sequence[int] = DEFAULT_THRESHOLDS,
    iters: int = 3,
    measure: Callable[[int], float] | None = None,
) -> int:
    """Warmup-time tuning of the trace-time fusion threshold for a
    compiled training step.

    ``step`` is the user's ``jax.jit``-wrapped train step whose
    DistributedOptimizer was built WITHOUT an explicit
    ``fusion_threshold_bytes`` (so the bucketing pass reads the tunable).
    For each candidate the step cache is cleared, the step re-traced (the
    compiled analog of the reference's parameter_manager warmup windows),
    and ``iters`` steps timed on copies of ``args`` (copies because
    donated buffers cannot be re-fed). The fastest candidate is pinned via
    :func:`set_tuned_threshold` and returned; inspect the decision with
    :func:`autotune_state`.

    ``measure(threshold) -> seconds`` overrides the timing loop (tests
    inject deterministic cost models; production uses the default).
    """
    import time as _time

    import jax
    import jax.numpy as jnp

    if measure is None:
        if not hasattr(step, "clear_cache"):
            raise TypeError(
                "step must be a jax.jit-wrapped callable (needs "
                ".clear_cache() so each candidate re-traces); got "
                f"{type(step).__name__}"
            )

        def fresh_args():
            return jax.tree.map(
                lambda x: jnp.copy(x) if isinstance(x, jax.Array) else x,
                args)

        # Time the BARE callable: a factory step's default-on stall
        # watch (every Kth call drains the pipeline + a host-plane
        # round trip) landing inside one candidate's window would bias
        # the threshold choice.
        timed = getattr(step, "_hvd_unwatched", step)
        if hasattr(timed, "_hvd_tuning"):
            # A live transparent tuner (HOROVOD_AUTOTUNE=1) wraps the
            # jit: left armed, its window starts would re-pin its own
            # candidates OVER each measure() threshold (every sample
            # meaningless) and it would later override the explicit
            # decision. The user's explicit call wins — disarm it and
            # time the bare jit.
            timed._hvd_tuning = False
            timed = timed._fn

        def measure(threshold: int) -> float:  # noqa: F811
            set_tuned_threshold(threshold)
            timed.clear_cache()
            out = timed(*fresh_args())  # compile + warm
            jax.block_until_ready(out)
            t0 = _time.perf_counter()
            for _ in range(iters):
                out = timed(*fresh_args())
            jax.block_until_ready(out)
            return (_time.perf_counter() - t0) / max(1, iters)

    log = get_logger()
    results: list[tuple[int, float]] = []
    try:
        for threshold in thresholds:
            seconds = measure(int(threshold))
            results.append((int(threshold), seconds))
            _tuned["history"].append((int(threshold), seconds))
            _record_trial("fusion_threshold_bytes", seconds)
            log.info("autotune fusion: threshold=%d -> %.6fs/step",
                     int(threshold), seconds)
        best = min(results, key=lambda p: p[1])[0]
    finally:
        # Even on failure mid-sweep, leave the best-so-far (or None) pinned
        # rather than a half-measured candidate.
        best_sofar = (min(results, key=lambda p: p[1])[0]
                      if results else None)
        set_tuned_threshold(best_sofar)
        if hasattr(step, "clear_cache"):
            step.clear_cache()
    log.info("autotune fusion: pinned threshold=%d", best)
    return best


def tune_fusion_threshold(
    build_step: Callable[[int], Callable],
    time_step: Callable[[Callable], float],
    rounds: int = 12,
    low_bytes: int = 64 * 1024,
    high_bytes: int = 128 * 1024 * 1024,
    log_path: str | None = None,
) -> int:
    """Search the gradient-bucketing threshold for the fastest step.

    ``build_step(threshold)`` returns a (re)compiled step; ``time_step``
    measures steady-state seconds/step (caller warms up + times). Returns
    the best threshold in bytes. Throughput = 1/seconds is the score.
    """
    log = get_logger()
    tuner = BayesianTuner([float(low_bytes)], [float(high_bytes)])
    try:
        for r in range(rounds):
            (candidate,) = tuner.suggest()
            threshold = max(low_bytes, int(candidate))
            step = build_step(threshold)
            seconds = time_step(step)
            score = 1.0 / max(seconds, 1e-9)
            tuner.add_sample([float(threshold)], score)
            log.info(
                "autotune round %d: threshold=%d -> %.4fs/step", r,
                threshold, seconds,
            )
            if log_path:
                with open(log_path, "a") as f:
                    f.write(f"{threshold},{seconds:.6f},{score:.3f}\n")
        (best_params, best_score) = tuner.best()
        log.info(
            "autotune best: threshold=%d (score %.1f)", int(best_params[0]),
            best_score,
        )
        return int(best_params[0])
    finally:
        tuner.close()
