"""The seven per-layer metrics of ``setup_s`` that read the program's
set-up account (``setup_account.py``, ``per_layer_pr50.json``): their
entries' form, and each reader on hand-made accounts with known answers.
No step is compiled here."""

import math
import os

import pytest

import cells
import setup_account
from conftest import BENCHMARK_DIR
from horovod_tpu import attribution, tracing

SEVEN = ("setup_import_s", "setup_place_s", "first_call_s",
         "first_call_trace_lower_s", "first_call_load_s",
         "first_call_other_s", "setup_other_programs_s")
EIGHT = ["bert-large_s512_dp1", "bert-large_s128_dp1", "bert-large_s512_dp4",
         "bert-large_s512_fsdp4", "resnet50_b128_dp1", "resnet50_b128_dp4",
         "olmoe-1b-7b_s4096_e16_dp1", "nemotron-3-nano-30b-a3b_s8192_e8_dp1"]
PINNED = ("granite-4.0-h-micro_s4096_dp1", "kimi-linear-48b-a3b_s8192_e8_dp1",
          "olmo-hybrid-7b_s4096_dp1", "sdar-30b-a3b_s8192_b4_e16_dp1",
          "smallthinker-21b-a3b_s16384_e16_dp1")


def entries() -> dict:
    staged = cells.load_json(BENCHMARK_DIR, "per_layer_pr50.json")
    return {entry["name"]: entry for entry in staged["per_layer"]}


def reader(name: str):
    return (cells.load_code(BENCHMARK_DIR, "layer_metrics", name + ".py"),
            cells.load_json(BENCHMARK_DIR, "layer_metrics", name + ".json"))


def read(name: str):
    code, params = reader(name)
    return code.read(None, params)


@pytest.mark.parametrize("name", SEVEN)
def test_an_entry_is_ready_for_benchmark_json(name):
    bench = cells.benchmark()
    entry = entries()[name]
    assert list(entries()) == list(SEVEN)
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert (entry["unit"], entry["better"], entry["source"],
            entry["moves"]) == ("s", "lower", "program_span", "setup_s")
    assert entry["layer"] in {"step_factory", "compile"}
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"]}
    assert entry["workloads"] == EIGHT
    assert set(EIGHT) <= {w["name"] for w in bench["workloads"]}
    for ending in (".json", ".py"):
        assert os.path.exists(os.path.join(
            BENCHMARK_DIR, "layer_metrics", name + ending))
    assert reader(name)[1]["definition"]
    # Listed only once the parent of a PR keeps the account: until then a
    # traced run of the parent has no value for it and run.py ends it.
    already = {m["name"] for m in bench["per_layer"]}
    assert name not in already or set(SEVEN) <= already


@pytest.mark.parametrize("cell", PINNED)
def test_a_cell_whose_test_pins_its_metrics_reads_none_of_them(cell):
    assert not set(SEVEN) & {
        entry["name"] for entry, _, _ in cells.layer_metrics(cell)}


class Clock(tracing.ClockSync):
    def __init__(self):
        super().__init__()
        self.t = 0.0

    def now(self):
        return self.t


@pytest.fixture()
def account():
    """A set-up made by hand on a clock of the test's own, through the
    program's recorder: import 2 s; init 0.5 s; the reference's program
    traced, compiled and loaded outside any step (3 s, its tracing nested);
    placing 1 s with a small program compiled inside; a first call of 10 s
    (tracing 6 s with a nested tracing and a compile inside it, lowering
    1 s, a cache read inside a 2 s backend compile); a warm call."""
    clock = Clock()
    tracer = tracing.StepTracer(clock)
    tracing.reset_for_testing(tracer)
    tracer.open_setup(0.0)

    def event(name, end, seconds, **args):
        clock.t = end
        tracer.setup_event(name, seconds, args)

    tracer.record(attribution.SPAN_SETUP_IMPORT, attribution.CAT_HOST,
                  0.0, 2.0, {"modules": 40})
    clock.t = 2.0
    with tracer.host_span(attribution.SPAN_SETUP_INIT):
        clock.t = 2.5
    event(attribution.SPAN_SETUP_TRACE, 3.5, 0.5, program="inner")
    event(attribution.SPAN_SETUP_TRACE, 4.0, 1.5, program="reference")
    event(attribution.SPAN_SETUP_LOWER, 4.5, 0.5, program="reference")
    event(attribution.SPAN_SETUP_BACKEND_COMPILE, 5.5, 1.0,
          program="reference", cache="miss")
    clock.t = 6.0
    with tracer.host_span(attribution.SPAN_SETUP_PLACE, {"what": "replicate"}):
        event(attribution.SPAN_SETUP_BACKEND_COMPILE, 6.5, 0.25,
              program="jit(copy)")
        clock.t = 7.0
    with tracer.step_scope(attribution.SPAN_STEP,
                           {"kind": "train_step", "call": 1}):
        with tracer.host_span(attribution.SPAN_STEP_DISPATCH):
            event(attribution.SPAN_SETUP_TRACE, 9.0, 1.0, program="nested")
            event(attribution.SPAN_SETUP_BACKEND_COMPILE, 12.0, 0.5,
                  program="a constant")
            event(attribution.SPAN_SETUP_TRACE, 13.5, 6.0, program="step")
            event(attribution.SPAN_SETUP_LOWER, 14.5, 1.0, program="step")
            event(attribution.SPAN_SETUP_CACHE_READ, 16.0, 1.0)
            event(attribution.SPAN_SETUP_BACKEND_COMPILE, 16.5, 2.0,
                  program="step", cache="hit")
            clock.t = 16.9
        clock.t = 17.0
    with tracer.step_scope(attribution.SPAN_STEP,
                           {"kind": "train_step", "call": 2}) as rec:
        rec.closes_setup = True
        clock.t = 17.5
    assert not tracer.setup_open and tracer.setup.dropped == 0
    yield tracer
    tracing.reset_for_testing()


EXPECTED = {
    "setup_import_s": 2.0,
    "setup_place_s": 0.5 + 1.0,
    "first_call_s": 10.0,
    "first_call_trace_lower_s": 6.0 + 1.0,  # the nested second counted once
    "first_call_load_s": 0.5 + 2.0,  # the cache read inside the compile
    "first_call_other_s": 10.0 - 9.0,  # 7.5 to 16.5 is covered
    "setup_other_programs_s": 1.5 + 0.5 + 1.0 + 0.25,
}


@pytest.mark.parametrize("name", SEVEN)
def test_a_reader_on_an_account_made_by_hand(account, name):
    value = read(name)
    assert isinstance(value, float) and math.isfinite(value)
    assert value == pytest.approx(EXPECTED[name])


def test_the_three_parts_of_the_first_call_cover_it(account):
    parts = sum(read(name) for name in (
        "first_call_trace_lower_s", "first_call_load_s",
        "first_call_other_s"))
    # More than the call by the compile that ran while the step was traced.
    assert parts == pytest.approx(read("first_call_s") + 0.5)
    spans = account.setup.spans
    call = setup_account.first_call(spans, "train_step")
    below = setup_account.under(spans, call)
    assert read("first_call_other_s") == call["dur"] - setup_account.seconds(
        s for s in below if s["name"].startswith("hvd.setup."))
    assert {s["name"] for s in below} == {
        attribution.SPAN_STEP_DISPATCH, *attribution.SETUP_EVENT_SPAN_NAMES}


@pytest.mark.parametrize("name", SEVEN)
def test_a_reader_finds_no_span_of_its_names_and_says_zero(name):
    tracer = tracing.StepTracer(Clock())
    tracing.reset_for_testing(tracer)
    try:
        assert read(name) == 0.0  # no account was ever opened here
        tracer.open_setup()
        tracer.record("somebody.elses", "phase", 0.0, 1.0)
        with tracer.step_scope(attribution.SPAN_STEP,
                               {"kind": "eval_step", "call": 1}):
            pass
        value = read(name)
        assert value == 0.0 and isinstance(value, float)
    finally:
        tracing.reset_for_testing()


@pytest.mark.parametrize("name", SEVEN)
def test_a_program_without_the_account_gives_no_metric(monkeypatch, name):
    import horovod_tpu as hvd

    older = {key: value for key, value in hvd.cache_stats().items()
             if key != "setup"}
    monkeypatch.setattr(hvd, "cache_stats", lambda: older)
    assert read(name) is None


def test_a_parent_the_account_dropped_ends_the_chain():
    spans = [
        {"name": "hvd.step", "t": 0.0, "dur": 4.0, "id": 1,
         "args": {"kind": "train_step", "call": 1}},
        {"name": "hvd.setup.trace", "t": 1.0, "dur": 1.0, "id": 3,
         "parent": 2},  # span 2 was not kept
        {"name": "hvd.setup.lower", "t": 2.0, "dur": 1.0, "id": 4,
         "parent": 1}]
    assert [s["id"] for s in setup_account.under(spans, spans[0])] == [4]
    assert [s["id"] for s in setup_account.outside_steps(spans)] == [1, 3]
