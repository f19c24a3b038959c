"""The set-up account: the spans a process records from ``import
horovod_tpu`` to its first warm step, kept past the flight recorder's ring
(``tracing.SetupAccount``, ``hvd.cache_stats()["setup"]``). Everything
compiled here is a toy MLP on the CPU mesh."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu import attribution, metrics, profiler, tracing

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EVENT_SPANS = attribution.SETUP_EVENT_SPAN_NAMES


class Clock(tracing.ClockSync):
    """A clock the test moves by hand."""

    def __init__(self):
        super().__init__()
        self.t = 100.0

    def now(self):
        return self.t


@pytest.fixture()
def tracer(monkeypatch):
    """A fresh tracer with an account just opened, every tracing and
    lowering kept however brief (a toy's take a millisecond)."""
    monkeypatch.setattr(tracing.SetupAccount, "SHORT_S", 0.0)
    fresh = tracing.StepTracer()
    tracing.reset_for_testing(fresh)
    fresh.open_setup()
    yield fresh
    tracing.reset_for_testing()


def toy_step():
    """A factory step over a two-layer MLP whose loss calls a jitted
    function of its own: a tracing nested in the step's."""

    @jax.jit
    def squash(x):
        return jnp.tanh(x) * 2.0

    def loss_fn(params, batch):
        hidden = squash(batch["x"] @ params["w"])
        return jnp.mean((hidden @ params["v"] - batch["y"]) ** 2)

    optimizer = hvd.DistributedOptimizer(optax.sgd(0.1))
    step = hvd.data_parallel.make_train_step(loss_fn, optimizer)
    params = {"w": jnp.ones((4, 8)), "v": jnp.ones((8, 2))}
    opt_state = hvd.data_parallel.replicate(optimizer.init(params))
    params = hvd.data_parallel.replicate(params)
    batch = hvd.data_parallel.shard_batch(
        {"x": jnp.ones((8, 4)), "y": jnp.zeros((8, 2))})
    return step, params, opt_state, batch


def run(step, params, opt_state, batch, calls):
    for _ in range(calls):
        params, opt_state, loss = step(params, opt_state, batch)
    return jax.block_until_ready((params, opt_state, loss))


def by_id(spans):
    return {span["id"]: span for span in spans if "id" in span}


def test_a_fresh_process_has_the_account_open_with_the_import_in_it():
    """Only a process of its own shows the account as the import left
    it: this one's closed with the first warm step of an earlier test."""
    script = (
        "import json, horovod_tpu as hvd\n"
        "print(json.dumps(hvd.cache_stats()['setup']))\n")
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=120, cwd=REPO_ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    account = json.loads(out.stdout.splitlines()[-1])
    assert account["open"] is True and account["dropped"] == 0
    first, = account["spans"]
    assert first["name"] == attribution.SPAN_SETUP_IMPORT
    assert first["dur"] > 0 and first["args"]["modules"] > 20
    assert account["by_name"][attribution.SPAN_SETUP_IMPORT]["count"] == 1


class TestAFactoryStep:
    def test_the_first_call_survives_with_its_children(self, tracer):
        step, *state = toy_step()
        run(step, *state, calls=3)
        spans = tracer.setup.spans
        found = by_id(spans)
        first, = [s for s in spans if s["name"] == attribution.SPAN_STEP
                  and s["args"]["call"] == 1]
        assert first["args"]["kind"] == "train_step"
        assert first["args"]["compile"]["programs"] >= 1
        dispatch, = [s for s in spans if s.get("parent") == first["id"]]
        assert dispatch["name"] == attribution.SPAN_STEP_DISPATCH

        def top(span):  # the span's ancestor right under the dispatch
            while span.get("parent") != dispatch["id"]:
                span = found.get(span.get("parent"))
                if span is None:
                    return None
            return span

        below = [s for s in spans if s["name"] in EVENT_SPANS and top(s)]
        names = {s["name"] for s in below}
        assert {attribution.SPAN_SETUP_TRACE, attribution.SPAN_SETUP_LOWER,
                attribution.SPAN_SETUP_BACKEND_COMPILE} <= names
        # The step's own tracing, lowering and compile are the dispatch's
        # children, one after the other.
        own = [s for s in below if s["parent"] == dispatch["id"]
               and "spmd_step" in s["args"]["program"]]
        assert [s["name"] for s in own] == [
            attribution.SPAN_SETUP_TRACE, attribution.SPAN_SETUP_LOWER,
            attribution.SPAN_SETUP_BACKEND_COMPILE]
        # The nested jit's tracing is a child of the step's tracing, and
        # a union counts it once where the sum counts it twice.
        outer = own[0]
        nested = [s for s in below if s["name"] == attribution.SPAN_SETUP_TRACE
                  and s["args"]["program"] == "squash"]
        assert nested and all(
            top(s) is outer and s is not outer for s in nested)
        traces = [s for s in below
                  if s["name"] == attribution.SPAN_SETUP_TRACE]
        union = attribution._length(attribution._merge(
            [(s["t"], s["t"] + s["dur"]) for s in traces]))
        assert union < sum(s["dur"] for s in traces)
        assert union == pytest.approx(outer["dur"], abs=1e-5)

    def test_it_closes_on_the_first_warm_call_and_records_nothing_after(
            self, tracer):
        step, params, opt_state, batch = toy_step()
        params, opt_state, _ = step(params, opt_state, batch)
        assert tracer.setup_open  # call 1 compiled the step
        params, opt_state, _ = step(params, opt_state, batch)
        assert not tracer.setup_open  # call 2 compiled nothing
        account = tracer.setup
        last = account.spans[-1]
        assert last["name"] == attribution.SPAN_STEP
        assert last["args"]["call"] == 2
        kept, ring_steps = len(account.spans), tracer.steps_recorded()
        for _ in range(3):
            params, opt_state, _ = step(params, opt_state, batch)
        assert len(account.spans) == kept and account.dropped == 0
        assert tracer.steps_recorded() == ring_steps + 3  # the ring goes on
        assert hvd.cache_stats()["setup"]["open"] is False
        # What a step opens after the close is what it opened before.
        newest = tracer.ring_snapshot()[-1]["spans"]
        assert [s["name"] for s in newest] == [
            attribution.SPAN_STEP, attribution.SPAN_STEP_DISPATCH]
        # The setup spans cost one attribute test from here on.
        assert tracing.setup_span(
            attribution.SPAN_SETUP_PLACE) is tracing._NO_SPAN
        hvd.data_parallel.shard_batch({"x": jnp.ones((8, 4))})
        assert len(account.spans) == kept

    def test_the_place_and_build_spans_say_what_they_placed(self, tracer):
        toy_step()
        spans = tracer.setup.spans
        build, = [s for s in spans
                  if s["name"] == attribution.SPAN_SETUP_BUILD]
        assert build["args"] == {"kind": "train_step"}
        init, = [s for s in spans
                 if s["name"] == attribution.SPAN_SETUP_OPTIMIZER_INIT]
        assert init["args"]["sync_mode"] == "allreduce"
        placed = {s["args"]["what"]: s["args"] for s in spans
                  if s["name"] == attribution.SPAN_SETUP_PLACE}
        assert set(placed) == {"replicate", "shard_batch"}
        assert placed["shard_batch"]["leaves"] == 2
        assert placed["shard_batch"]["bytes"] == (8 * 4 + 8 * 2) * 4
        assert placed["replicate"]["bytes"] == (4 * 8 + 8 * 2) * 4

    def test_setup_finished_is_journaled_once(self, tracer, tmp_path,
                                              monkeypatch):
        log = tmp_path / "events.jsonl"
        monkeypatch.setenv("HOROVOD_EVENT_LOG", str(log))
        try:
            step, *state = toy_step()
            run(step, *state, calls=4)
        finally:
            monkeypatch.delenv("HOROVOD_EVENT_LOG")
            metrics.journal()  # closes the file
        events = [json.loads(line) for line in log.read_text().splitlines()]
        finished, = [e for e in events if e["event"] == "setup_finished"]
        assert finished["dropped"] == 0
        assert finished["spans"] == len(tracer.setup.spans)
        assert finished["seconds"] == pytest.approx(
            tracer.setup.closed_at - tracer.setup.t0, abs=1e-5)
        assert finished["by_name"][attribution.SPAN_STEP]["count"] == 2


class TestTheAccountItself:
    def test_events_come_innermost_first_and_are_nested_by_their_starts(
            self):
        clock = Clock()
        tracer = tracing.StepTracer(clock)
        tracer.open_setup()
        with tracer.host_span(attribution.SPAN_STEP_DISPATCH):
            clock.t = 101.0  # a sibling that ended before the outer began
            tracer.setup_event(attribution.SPAN_SETUP_TRACE, 0.5,
                               {"program": "before"})
            clock.t = 103.0  # inner: [102.8, 103.0)
            tracer.setup_event(attribution.SPAN_SETUP_TRACE, 0.2,
                               {"program": "inner"})
            clock.t = 103.5  # a compile while tracing: [103.1, 103.5)
            tracer.setup_event(attribution.SPAN_SETUP_BACKEND_COMPILE, 0.4)
            clock.t = 104.0  # outer: [102.0, 104.0)
            tracer.setup_event(attribution.SPAN_SETUP_TRACE, 2.0,
                               {"program": "outer"})
            clock.t = 105.0  # lowering follows: [104.0, 105.0)
            tracer.setup_event(attribution.SPAN_SETUP_LOWER, 1.0)
        before, inner, compiled, outer, lower, dispatch = tracer.setup.spans
        assert dispatch["name"] == attribution.SPAN_STEP_DISPATCH
        assert inner["parent"] == compiled["parent"] == outer["id"]
        assert before["parent"] == outer["parent"] == lower["parent"] == (
            dispatch["id"])
        own = dict(zip(("before", "inner", "compiled", "outer", "lower",
                        "dispatch"), tracing.self_times(tracer.setup.spans)))
        assert own["outer"] == pytest.approx(2.0 - 0.2 - 0.4)
        assert own["dispatch"] == pytest.approx(5.0 - 0.5 - 2.0 - 1.0)
        rows = tracer.setup_summary()["by_name"]
        trace = rows[attribution.SPAN_SETUP_TRACE]
        assert trace["count"] == 3
        assert trace["total_s"] == pytest.approx(2.5)  # not 2.7
        assert trace["self_s"] == pytest.approx(0.5 + 0.2 + 1.4)

    def test_brief_tracings_are_counted_and_compiles_always_kept(self):
        tracer = tracing.StepTracer(Clock())
        tracer.open_setup()
        for _ in range(3):
            tracer.setup_event(attribution.SPAN_SETUP_TRACE, 0.001)
        tracer.setup_event(attribution.SPAN_SETUP_LOWER, 0.004)
        tracer.setup_event(attribution.SPAN_SETUP_BACKEND_COMPILE, 0.001)
        tracer.setup_event(attribution.SPAN_SETUP_CACHE_READ, 0.001)
        tracer.setup_event(attribution.SPAN_SETUP_TRACE, 0.006)
        assert [s["name"] for s in tracer.setup.spans] == [
            attribution.SPAN_SETUP_BACKEND_COMPILE,
            attribution.SPAN_SETUP_CACHE_READ, attribution.SPAN_SETUP_TRACE]
        rows = tracer.setup_summary()["by_name"]
        assert rows[attribution.SPAN_SETUP_TRACE]["short"] == {
            "count": 3, "seconds": pytest.approx(0.003)}
        assert rows[attribution.SPAN_SETUP_TRACE]["count"] == 1
        assert rows[attribution.SPAN_SETUP_LOWER] == {
            "count": 0, "total_s": 0.0, "self_s": 0.0,
            "short": {"count": 1, "seconds": pytest.approx(0.004)}}

    def test_overflow_is_counted_and_not_kept(self, monkeypatch):
        monkeypatch.setattr(tracing.SetupAccount, "CAP", 4)
        tracer = tracing.StepTracer(Clock(), max_spans=2)
        tracer.open_setup()
        with tracer.step_scope(attribution.SPAN_STEP):
            for i in range(4):  # the ring's step has room for two
                tracer.record(f"span{i}", "phase", 100.0 + i, 0.5)
        tracer.setup_event(attribution.SPAN_SETUP_BACKEND_COMPILE, 0.1)
        summary = tracer.setup_summary()
        assert [s["name"] for s in summary["spans"]] == [
            "span0", "span1", "span2", "span3"]  # whole, past the ring's cap
        assert summary["dropped"] == 2 and summary["open"] is True
        ring, = tracer.ring_snapshot()
        assert ring["dropped_spans"] == 2

    def test_closed_it_keeps_nothing_more_and_a_new_one_starts_empty(self):
        tracer = tracing.StepTracer(Clock())
        assert tracer.setup_summary() == {
            "open": False, "dropped": 0, "spans": [], "by_name": {}}
        tracer.open_setup()
        tracer.record("kept", "phase", 100.0, 1.0)
        tracer.close_setup()
        tracer.close_setup()  # for good: a second close is nothing
        tracer.record("after", "phase", 101.0, 1.0)
        tracer.setup_event(attribution.SPAN_SETUP_BACKEND_COMPILE, 0.1)
        with tracer.step_scope(attribution.SPAN_STEP, {"call": 9}):
            pass
        first = tracer.setup
        assert [s["name"] for s in first.spans] == ["kept"]
        assert tracer.setup_summary()["open"] is False
        tracer.reopen_setup()
        assert tracer.setup is not first and tracer.setup.spans == []
        tracer.reopen_setup()  # one is open: it stays
        assert tracer.setup_open and tracer.setup.t0 == 100.0

    def test_a_world_formed_anew_opens_the_next_account(self, tracer):
        tracer.close_setup()
        closed = tracer.setup
        try:
            hvd.shutdown()
            assert tracer.setup is not closed and tracer.setup_open
        finally:
            hvd.init()
        assert tracer.setup_open
        init, = [s for s in tracer.setup.spans
                 if s["name"] == attribution.SPAN_SETUP_INIT]
        assert init["args"] == {"ranks": 8, "backend": "cpu"}
        hvd.init()  # idempotent: no second span
        assert len([s for s in tracer.setup.spans
                    if s["name"] == attribution.SPAN_SETUP_INIT]) == 1


class TestTheCompileAccountBesideIt:
    def test_its_summary_has_the_keys_it_had(self):
        compile_ = hvd.cache_stats()["compile"]
        assert set(compile_) == {
            "listening", "trace_s", "lower_s", "backend_compile_s",
            "cache_hits", "cache_misses", "programs", "steps"}
        assert set(hvd.cache_stats()) == {
            "executable_cache", "eager_dispatch", "compile", "setup"}

    def test_a_duration_is_summed_as_before_and_kept_only_while_open(
            self, tracer):
        account = profiler.CompileAccount()
        event = "/jax/core/compile/backend_compile_duration"
        account.on_event("/jax/compilation_cache/cache_hits")
        account.on_duration(event, 0.25, fun_name="jit(f)")
        account.on_duration(event, 0.25, fun_name="jit(g)")
        account.on_duration("/jax/somebody/elses_duration", 9.0)
        assert account.programs == 2 and account.cache_hits == 1
        assert account.backend_compile_s == 0.5
        first, second = tracer.setup.spans
        assert first["args"] == {"program": "jit(f)", "cache": "hit"}
        assert second["args"] == {"program": "jit(g)"}
        tracer.close_setup()
        account.on_duration(event, 0.25, fun_name="jit(h)")
        assert account.programs == 3 and len(tracer.setup.spans) == 2

    def test_one_registration_a_process(self, monkeypatch):
        import jax.monitoring

        calls = []
        monkeypatch.setattr(jax.monitoring,
                            "register_event_duration_secs_listener",
                            calls.append)
        monkeypatch.setattr(jax.monitoring, "register_event_listener",
                            calls.append)
        account = profiler.CompileAccount()
        account.listen()
        account.listen()
        assert calls == [account.on_event, account.on_duration]
