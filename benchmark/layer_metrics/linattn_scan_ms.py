"""Device time of the gated delta rule, a step.

``scope_ms`` is the three linear-attention readers' way to the trace: the
operations of the most idle device whose innermost phase scope is one of
``scopes``, as ``program_spans.device`` attributes them (instruction ->
name stack from the program's own text), summed as the *union* of their
intervals. The rule's scan over chunks is a loop; where a trace holds an
event for the loop and events for the operations inside it, a plain sum
counts that time twice, and the union counts it once either way."""

import program_spans
import trace_reduce


def instruction_scopes(run) -> dict | None:
    """Instruction -> name stack of the step that ran, parsed once a run;
    ``None`` wherever ``program_spans.device`` has nothing to read."""
    if program_spans.device(run) is None:
        return None

    def make():
        import horovod_tpu as hvd

        return hvd.profiler.instruction_scopes(
            "\n".join(hvd.profiler.step_texts()))

    return program_spans.once(run, "instruction_scopes", make)


def scope_ms(run, scopes) -> float | None:
    table = instruction_scopes(run)
    if table is None:
        return None
    busy = trace_reduce.busy_seconds(run.trace)
    ops = [op for op in run.trace.devices[min(busy, key=busy.get)]
           if program_spans.phase_of(table.get(op.name)) in scopes]
    if not ops:
        return None
    return trace_reduce.total(trace_reduce.spans(ops)) / run.steps * 1e3


def read(run, params):
    ms = scope_ms(run, params["scopes"])
    if ms is not None:
        summed = sum(program_spans.device(run).phases.get(scope, 0.0)
                     for scope in params["scopes"])
        print(f"linattn_scan_ms: {ms:.3f} ms a step as the union of the "
              f"operations' intervals; their plain sum is {summed:.3f}",
              flush=True)
    return ms
