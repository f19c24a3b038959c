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
- :func:`annotate_collective` names in-trace regions of the compiled step
  — the phase scopes ``hvd.wire`` / ``hvd.optimizer`` / ``hvd.attn.*``
  and, inside the wire, segment allreduces, fusion buckets, hierarchical
  legs — so each is identifiable against the TPU op stream in the
  captured trace; :func:`instruction_scopes` reads them back from a
  compiled step's text, instruction by instruction.
- The models open block scopes the same way (``hvd.block.*``,
  ``attribution.BLOCK_SCOPE_NAMES``). :func:`owner_of` names the scope a
  name stack's time belongs to (the innermost phase, else the innermost
  block), and :func:`instruction_owners` reads an owner for every
  instruction of a compiled step's text, looking inside fusions and loops
  whose own instruction carries no name stack.
- :func:`compile_account` is JAX's own account of tracing, lowering and
  compiling (``jax.monitoring``), heard by the program once per process
  and served as ``hvd.cache_stats()["compile"]``.
"""

from __future__ import annotations

import dataclasses
import os
import re
import threading
import weakref

from .attribution import (SPAN_SETUP_BACKEND_COMPILE, SPAN_SETUP_CACHE_READ,
                          SPAN_SETUP_LOWER, SPAN_SETUP_TRACE)
from .tracing import get_tracer

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


class CompileAccount:
    """What ``jax.monitoring`` reports of every program this process
    traced, lowered and compiled, summed: seconds of jaxpr tracing (a
    jit nested in another counts in both), of lowering to MLIR, and of
    backend compile (on a persistent-cache hit, of loading the
    executable); persistent-cache hits and misses; programs compiled.
    ``steps`` holds, per factory step, the share of its first call and
    its recompiles (``data_parallel._StallWatchedStep`` books them).

    While the tracer's set-up account is open (``tracing.SetupAccount``)
    each duration is also kept there as a span that ended when it was
    reported (``SPANS``), with JAX's ``fun_name`` as ``program`` and, on a
    backend compile, whether the persistent cache was hit or missed."""

    DURATIONS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "backend_compile_s",
    }
    EVENTS = {
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }
    FIELDS = (*DURATIONS.values(), *EVENTS.values(), "programs")
    #: Event -> its span in the set-up account.
    SPANS = {
        "/jax/core/compile/jaxpr_trace_duration": SPAN_SETUP_TRACE,
        "/jax/core/compile/jaxpr_to_mlir_module_duration": SPAN_SETUP_LOWER,
        "/jax/core/compile/backend_compile_duration":
            SPAN_SETUP_BACKEND_COMPILE,
        "/jax/compilation_cache/cache_retrieval_time_sec":
            SPAN_SETUP_CACHE_READ,
    }

    def __init__(self):
        self.trace_s = self.lower_s = self.backend_compile_s = 0.0
        self.cache_hits = self.cache_misses = self.programs = 0
        self.steps: dict[str, dict] = {}
        self.listening = False
        self._cache = None  # "hit" / "miss" heard since the last compile

    def on_duration(self, event: str, seconds: float, **kwargs) -> None:
        field = self.DURATIONS.get(event)
        cache = None
        if field is not None:
            setattr(self, field, getattr(self, field) + seconds)
            if field == "backend_compile_s":
                self.programs += 1
                cache, self._cache = self._cache, None
        name = self.SPANS.get(event)
        tracer = get_tracer()
        if name is not None and tracer.setup_open:
            args = {}
            if "fun_name" in kwargs:
                args["program"] = str(kwargs["fun_name"])
            if cache:
                args["cache"] = cache
            tracer.setup_event(name, seconds, args)

    def on_event(self, event: str, **_) -> None:
        field = self.EVENTS.get(event)
        if field is not None:
            setattr(self, field, getattr(self, field) + 1)
            self._cache = "hit" if field == "cache_hits" else "miss"

    def listen(self) -> None:
        """Register with ``jax.monitoring``, once per process (its
        listeners cannot be taken back)."""
        import jax.monitoring

        with _lock:
            if self.listening:
                return
            jax.monitoring.register_event_listener(self.on_event)
            jax.monitoring.register_event_duration_secs_listener(
                self.on_duration)
            self.listening = True

    def totals(self) -> dict:
        return {field: getattr(self, field) for field in self.FIELDS}

    def since(self, before: dict) -> dict:
        """The account's growth since ``before`` (a :meth:`totals`)."""
        return {field: round(value - before[field], 6)
                if isinstance(value, float) else value - before[field]
                for field, value in self.totals().items()}

    def summary(self) -> dict:
        return {"listening": self.listening, **self.totals(),
                "steps": {name: dict(step)
                          for name, step in self.steps.items()}}


_compile_account = CompileAccount()


def compile_account() -> CompileAccount:
    """The process's compile account. ``hvd.init()`` and
    ``hvd.enable_compile_cache()`` make it listen, whichever runs first;
    until then every figure is 0 and ``listening`` False."""
    return _compile_account


#: ``%name = ... metadata={op_name="jit(step)/.../hvd.wire/psum"`` of one
#: instruction of a compiled program's text.
_INSTRUCTION_OP_NAME = re.compile(
    r'^\s*(?:ROOT )?%?([\w.-]+) = [^\n]*?metadata=\{op_name="([^"]*)"',
    re.M)


def instruction_scopes(hlo_text: str) -> dict[str, str]:
    """Instruction name → the JAX name stack it was compiled from
    (``jit(spmd_step)/transpose(jvp(Bert))/hvd.attn.bwd/pallas_call``),
    from a compiled program's text. A device trace names an operation by
    its instruction; which phase scope it ran under is only here. A
    fusion carries its root instruction's name stack. Raises where the
    text holds none of the phase scopes: that is no step of this
    program's, or an executable served by a persistent cache whose key
    leaves metadata out (``jax_compilation_cache_include_metadata_in_key``)
    and that was compiled before the scopes existed."""
    from .attribution import PHASE_SCOPE_NAMES

    scopes = dict(_INSTRUCTION_OP_NAME.findall(hlo_text))
    if not any(phase_of(scope) for scope in scopes.values()):
        raise ValueError(
            f"no phase scope ({', '.join(PHASE_SCOPE_NAMES)}) in the "
            f"program's text ({len(scopes)} instructions with an "
            "op_name): not a factory step, or an executable loaded from a "
            "persistent compilation cache that a tree with other scopes "
            "wrote (the cache's key leaves metadata out): compile it "
            "afresh, in a cache directory of its own or with "
            "jax_compilation_cache_include_metadata_in_key set")
    return scopes


def phase_of(scope: str) -> str | None:
    """The innermost phase scope (``hvd.wire``, ``hvd.optimizer``,
    ``hvd.attn.fwd``, ``hvd.attn.bwd``, ``hvd.moe.*``, ``hvd.linattn.*``,
    ``hvd.ssm.*``) among the components of a name stack, wherever it sits (the overlapped
    step's wire is under ``transpose``), or None. A transformation wraps the
    outermost name of what it transforms (``vmap(hvd.moe.route)``,
    ``transpose(jvp(hvd.moe.experts))``): the name inside is the scope."""
    from .attribution import PHASE_SCOPE_NAMES

    for part in reversed(scope.split("/")):
        part = part.rsplit("(", 1)[-1].rstrip(")")
        if part in PHASE_SCOPE_NAMES:
            return part
    return None


def owner_of(scope: str) -> str | None:
    """The scope a name stack's device time belongs to: its innermost
    phase scope (:func:`phase_of`) if it has one, else its innermost block
    scope (``hvd.block.*``), else None. A block opened inside a phase
    stays the phase's, and a phase inside a block (the flash kernels under
    ``hvd.block.attn_proj``) keeps what it had before the blocks were
    named. Transformations' wrappers are read through as ``phase_of``
    reads them."""
    from .attribution import BLOCK_SCOPE_NAMES, PHASE_SCOPE_NAMES

    parts = [part.rsplit("(", 1)[-1].rstrip(")")
             for part in reversed(scope.split("/"))]
    for names in (PHASE_SCOPE_NAMES, BLOCK_SCOPE_NAMES):
        for part in parts:
            if part in names:
                return part
    return None


#: What :func:`booked_to` books an instruction to when no one scope owns
#: it: several owners inside it and none of its own, or none at all.
OWNER_SHARED = "shared"
OWNER_UNOWNED = "unowned"


@dataclasses.dataclass(frozen=True)
class Owner:
    """What a compiled step's text says of who owns one instruction."""

    own: str | None  # owner_of its own op_name
    inside: frozenset  # owners of what the computations it calls compute
    neighbours: tuple  # (of its first operand's producer, of its first user)
    opcode: str
    shape: str


def booked_to(owner: Owner) -> str:
    """The one booking rule: an instruction's time goes to ``own``; where
    that is None, to the only member of ``inside``; where ``inside`` has
    several, to :data:`OWNER_SHARED`; else to :data:`OWNER_UNOWNED`."""
    if owner.own:
        return owner.own
    if len(owner.inside) == 1:
        return next(iter(owner.inside))
    return OWNER_SHARED if owner.inside else OWNER_UNOWNED


#: ``[ROOT ]%name = shape opcode(operands), attributes``: the opcode is
#: the first lower-case word before a parenthesis (a layout's ``T(8,128)``
#: and ``S(1)`` are upper-case).
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%?([\w.-]+) = (.*?) ?\b([a-z][a-z0-9-]*)\((.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.-]+) \(.*\{$")
_OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')
_CALLED = re.compile(
    r"\b(?:calls|body|condition|to_apply|true_computation|"
    r"false_computation)=%?([\w.-]+)|branch_computations=\{([^}]*)\}")
#: Instructions whose called computations are read for ``inside``.
_CALLERS = ("fusion", "while", "call", "conditional")
#: Instructions that compute nothing: no owner inside a computation.
_NOT_COMPUTING = ("parameter", "constant", "tuple", "get-tuple-element",
                  "bitcast")
_NEIGHBOUR_REACH = 8  # instructions without an owner looked through


def _operands(rest: str) -> list[str]:
    """The operands' names in ``%a, /*index=1*/%b), attributes``: what
    follows an instruction's opening parenthesis, up to the one that
    closes it. A literal (``constant(0)``) is returned as it stands and
    names no instruction."""
    depth, start, found = 0, 0, []
    for i, c in enumerate(rest):
        if c in "([{":
            depth += 1
        elif c in ")]}" and depth:
            depth -= 1
        elif c in ")," and not depth:
            words = rest[start:i].split()
            if words:
                found.append(words[-1].rsplit("*/", 1)[-1].lstrip("%"))
            if c == ")":
                break
            start = i + 1
    return found


def instruction_owners(hlo_text: str) -> dict[str, Owner]:
    """Instruction name → :class:`Owner`, for every instruction of a
    compiled program's text, the ones inside fused computations and loop
    bodies too. :func:`instruction_scopes` gives a fusion its root's name
    stack and nothing where the root has none; here a ``fusion``,
    ``while``, ``call`` or ``conditional`` also holds ``inside``, the
    owners of the instructions that compute in the computations it calls
    (all the way down), and ``neighbours``, the booking of the nearest
    instruction that has one up the chain of first operands and down the
    chain of first users (at most eight instructions away; a computation's
    parameters end the chain). Book its time with :func:`booked_to`. A
    fusion whose ``inside`` has several members is a *shared fusion*,
    whoever it is booked to. Raises where the text holds no block scope:
    no step of one of this package's models, or, as with
    :func:`instruction_scopes`, an executable out of a persistent cache
    that a tree from before the block scopes wrote."""
    from .attribution import BLOCK_SCOPE_NAMES

    parsed = {}  # name -> (opcode, shape, own, first operand, called)
    members = {}  # computation -> [instruction names]
    users = {}  # name -> its first user
    computation = None
    for line in hlo_text.splitlines():
        found = _INSTRUCTION.match(line)
        if found is None:
            header = _COMPUTATION.match(line)
            if header:
                computation = members.setdefault(header.group(1), [])
            continue
        if computation is None:
            continue
        name, shape, opcode, rest = found.groups()
        scope = _OP_NAME.search(rest)
        called = []
        if opcode in _CALLERS:
            for one, several in _CALLED.findall(rest):
                called.extend(
                    [one] if one else
                    [c.strip().lstrip("%") for c in several.split(",")])
        operands = _operands(rest)
        parsed[name] = (opcode, shape,
                        owner_of(scope.group(1)) if scope else None,
                        operands[0] if operands else None, called)
        computation.append(name)
        for operand in operands:
            users.setdefault(operand, name)
    if not any(own in BLOCK_SCOPE_NAMES for _, _, own, _, _ in
               parsed.values()):
        raise ValueError(
            f"no block scope ({', '.join(BLOCK_SCOPE_NAMES)}) in the "
            f"program's text ({len(parsed)} instructions): not a step of "
            "one of this package's models (a model of your own opens them "
            "with profiler.annotate_collective), or an executable loaded "
            "from a persistent compilation cache that a tree from before "
            "the block scopes wrote (the cache's key leaves metadata out): "
            "compile it afresh, in a cache directory of its own or with "
            "jax_compilation_cache_include_metadata_in_key set")

    held = {}  # computation -> owners of what it computes, all the way down

    def holds(name: str) -> frozenset:
        if name not in held:
            held[name] = frozenset()  # a computation that calls itself
            found = set()
            for member in members.get(name, ()):
                opcode, _, own, _, called = parsed[member]
                if own and opcode not in _NOT_COMPUTING:
                    found.add(own)
                for below in called:
                    found |= holds(below)
            held[name] = frozenset(found)
        return held[name]

    owners = {name: Owner(own, frozenset().union(*map(holds, called)),
                          (None, None), opcode, shape)
              for name, (opcode, shape, own, _, called) in parsed.items()}
    booked = {name: booked_to(owner) for name, owner in owners.items()
              if owner.own or owner.inside}

    def nearest(name, step) -> str | None:
        """The booking of the first instruction with one along ``step``."""
        for _ in range(_NEIGHBOUR_REACH):
            name = step(name)
            if name not in parsed:
                return None
            if name in booked:
                return booked[name]
        return None

    return {name: dataclasses.replace(owner, neighbours=(
        nearest(name, lambda n: parsed[n][3]), nearest(name, users.get)))
        for name, owner in owners.items()}


_factory_steps: "weakref.WeakSet" = weakref.WeakSet()


def register_step(step) -> None:
    """A factory step, at its first call (``_StallWatchedStep``)."""
    _factory_steps.add(step)


def step_texts() -> list[str]:
    """The compiled text of every live factory step that has been called:
    each is lowered with the shapes and shardings of its first call and
    compiled, which jit memoises, so that nothing compiles and the text
    is that of the executable that runs. About a second for a 24-layer
    step; never on a step's path."""
    texts = []
    for step in list(_factory_steps):
        if not hasattr(step, "lower"):
            continue  # a Python step of several programs (elastic)
        args, kwargs = step._abstract_args
        texts.append(step.lower(*args, **kwargs).compile().as_text())
    if not texts:
        raise ValueError("no factory step has been called in this process")
    return texts


def step_scopes() -> dict[str, str]:
    """:func:`instruction_scopes` of the live factory steps' texts."""
    return instruction_scopes("\n".join(step_texts()))


def annotate_collective(name: str):
    """Name the ops traced inside the scope (``jax.named_scope``) so each
    region is identifiable in xprof traces and HLO dumps: ``hvd.<name>``.

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
