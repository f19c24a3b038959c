"""``program_spans`` on hand-made spans and operations with known answers,
the ten readers PR 23 added on a toy cell traced on the CPU (in a
temporary copy of the benchmark whose ``BENCHMARK.json`` has gained the ten
entries of ``per_layer_pr23.json`` with the cell in their ``workloads``,
nothing that was there edited), and what a reader says of a step whose
text holds no phase scope."""

import json
import os
import shutil
import types

import pytest

import cells
import program_spans
import trace_reduce
from conftest import BENCHMARK_DIR, REPO_ROOT
from program_spans import Span
from test_benchmark_rehearsal import result_of, run_cell
from trace_reduce import Op, Trace

NEW_METRICS = {
    "step_dispatch_ms", "step_hooks_ms", "idle_in_step_ms", "wire_pack_ms",
    "wire_mb_per_step", "optimizer_ms", "attn_fwd_kernel_ms",
    "attn_bwd_kernel_ms", "step_trace_lower_s", "hbm_temporaries_gib"}


def spans(*rows, line="/host:CPU/python3"):
    return [Span(name, start, end, line) for name, start, end in rows]


class TestHostSide:
    # Two calls into the step on one thread; the second drains.
    SPANS = spans(
        ("hvd.step", 1.0, 2.0),
        ("hvd.step.dispatch", 1.1, 1.8),
        ("hvd.step", 3.0, 5.0),
        ("hvd.step.dispatch", 3.2, 3.9),
        ("hvd.step.drain", 4.0, 4.9))
    # One device: busy but for [0, 1.5), [2.5, 3.5) and [4.5, 6).
    OPS = [Op("fusion.1", "fusion", 1.5, 2.5),
           Op("fusion.2", "fusion", 3.5, 4.5)]
    TRACE = Trace({0: OPS}, {0: []}, [("bench.step_call", 0.0, 6.0)],
                  (0.0, 6.0))

    def test_self_time_is_duration_less_what_children_cover(self):
        first, _, second, _, _ = self.SPANS
        assert program_spans.self_seconds(first, self.SPANS) == (
            pytest.approx(0.3))
        assert program_spans.self_seconds(second, self.SPANS) == (
            pytest.approx(2.0 - 0.7 - 0.9))

    def test_children_are_of_the_same_thread_and_counted_once(self):
        step, = spans(("hvd.step", 0.0, 1.0))
        others = spans(("a", 0.1, 0.5), ("b", 0.4, 0.6)) + spans(
            ("elsewhere", 0.2, 0.9), line="/host:CPU/worker")
        assert program_spans.self_seconds(step, [step] + others) == (
            pytest.approx(0.5))

    def test_a_gap_is_labelled_by_the_innermost_span_open_when_it_began(
            self):
        assert program_spans.label_at(self.SPANS, 0.5) == (
            program_spans.OUTSIDE)
        assert program_spans.label_at(self.SPANS, 1.05) == "hvd.step"
        assert program_spans.label_at(self.SPANS, 1.5) == "hvd.step.dispatch"
        assert program_spans.label_at(self.SPANS, 4.5) == "hvd.step.drain"
        assert program_spans.label_at(self.SPANS, 2.0) == (
            program_spans.OUTSIDE)  # a span holds its start, not its end

    def test_the_gap_table_holds_every_idle_second(self):
        gaps = program_spans.idle_gaps(self.TRACE)
        assert gaps == [(0.0, 1.5), (2.5, 3.5), (4.5, 6.0)]
        table = program_spans.gap_table(self.SPANS, gaps)
        assert table == {program_spans.OUTSIDE: pytest.approx(2.5),
                         "hvd.step.drain": pytest.approx(1.5)}
        assert sum(table.values()) == pytest.approx(
            trace_reduce.total(gaps))

    def test_host_side(self):
        found = program_spans.host_side(self.SPANS, self.TRACE, steps=2)
        assert found.dispatch_ms == pytest.approx(700.0)
        assert found.hooks_ms == pytest.approx((300.0 + 400.0) / 2)
        assert found.idle_in_step_ms == pytest.approx(750.0)
        assert found.gaps[program_spans.OUTSIDE] == pytest.approx(1250.0)

    def test_spans_are_clipped_to_the_window(self):
        trace = Trace({0: self.OPS}, {0: []}, [], (1.5, 4.0))
        clipped = program_spans.clip(self.SPANS, trace.window)
        assert [(s.name, s.start, s.end) for s in clipped] == [
            ("hvd.step", 1.5, 2.0), ("hvd.step.dispatch", 1.5, 1.8),
            ("hvd.step", 3.0, 4.0), ("hvd.step.dispatch", 3.2, 3.9)]
        found = program_spans.host_side(self.SPANS, trace, steps=2)
        assert found.dispatch_ms == pytest.approx((300.0 + 700.0) / 2)

    def test_no_device_plane_no_idle_figure(self):
        trace = Trace({}, {}, [], (0.0, 6.0))
        found = program_spans.host_side(self.SPANS, trace, steps=2)
        assert found.idle_in_step_ms is None and found.gaps == {}
        assert found.dispatch_ms == pytest.approx(700.0)

    def test_a_trace_from_before_the_spans_is_nothing_to_read(self):
        assert program_spans.host_side([], self.TRACE, steps=2) is None

    def test_what_the_benchmarks_timer_holds_around_the_step(self):
        before, after = program_spans.around_the_step(
            self.SPANS, [("bench.step_call", 0.9, 2.3),
                         ("bench.step_call", 2.9, 5.2)])
        assert before == pytest.approx(100.0)
        assert after == pytest.approx(250.0)


class TestDeviceSide:
    HLO = """
  %fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f.1, metadata={op_name="jit(spmd_step)/shard_map/jvp(Bert)/layer_0/mlp_in/dot_general"}
  %flash_attention.3 = bf16[8]{0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(spmd_step)/shard_map/jvp(Bert)/attention/jit(flash_attention)/hvd.attn.fwd/flash_attention/pallas_call"}
  %flash_attention.4 = bf16[8]{0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(spmd_step)/shard_map/transpose(jvp(Bert))/attention/jit(flash_attention)/hvd.attn.bwd/flash_attention/pallas_call"}
  %fusion.2 = bf16[8]{0} fusion(%q), kind=kLoop, calls=%f.2, metadata={op_name="jit(spmd_step)/shard_map/transpose(jvp(Bert))/layer_0/mlp_in/dot_general"}
  %fusion.5 = bf16[64]{0} fusion(%g), kind=kLoop, calls=%f.5, metadata={op_name="jit(spmd_step)/shard_map/hvd.wire/hvd.allreduce.bucket0.128B/concatenate"}
  %psum.9 = bf16[64]{0} all-reduce(%fusion.5), replica_groups={{0,1,2,3}}, metadata={op_name="jit(spmd_step)/shard_map/hvd.wire/hvd.allreduce.bucket0.128B/psum"}
  %all-reduce.1 = (bf16[32]{0:T(1024)(128)(2,1)}, bf16[2,16]{1,0}) all-reduce(%a, %b), metadata={op_name="jit(spmd_step)/shard_map/hvd.wire/hvd.allreduce.bucket1.128B/psum"}
  %slice.6 = bf16[8]{0} slice(%psum.9), metadata={op_name="jit(spmd_step)/shard_map/hvd.wire/hvd.wire.unpack/slice"}
  %fusion.7 = f32[8]{0} fusion(%slice.6), kind=kLoop, calls=%f.7, metadata={op_name="jit(spmd_step)/shard_map/hvd.optimizer/add"}
  %psum.10 = f32[]{:T(128)} all-reduce(%loss), metadata={op_name="jit(spmd_step)/shard_map/psum"}
"""
    DEVICE = [
        Op("fusion.1", "fusion", 0.0, 2.0),
        Op("flash_attention.3", "custom-call", 2.0, 3.0),
        Op("flash_attention.4", "custom-call", 3.0, 5.0),
        Op("fusion.2", "fusion", 5.0, 6.0),
        Op("fusion.5", "fusion", 6.0, 6.5),
        Op("psum.9", "all-reduce", 6.5, 7.5),
        Op("slice.6", "slice", 7.5, 7.75),
        Op("fusion.7", "fusion", 7.75, 8.75),
        Op("psum.10", "all-reduce", 8.75, 9.0),
        Op("copy.11", "copy", 9.0, 9.5),  # the step's text does not name it
    ]
    # A second device, less idle, whose kernels took longer.
    OTHER = [Op("fusion.1", "fusion", 0.0, 2.0),
             Op("flash_attention.3", "custom-call", 2.0, 3.5),
             Op("flash_attention.4", "custom-call", 3.5, 6.0),
             Op("fusion.2", "fusion", 6.0, 10.0)]
    TRACE = Trace({0: DEVICE, 1: OTHER}, {0: [], 1: []}, [], (0.0, 10.0))
    KERNELS = r"^flash_attention(\.\d+)?$"

    @pytest.fixture()
    def scopes(self):
        return trace_reduce.scopes_of(self.HLO)

    def test_phases_add_up_to_the_busy_time(self, scopes):
        found = program_spans.device_side(self.TRACE, 2, scopes,
                                          self.KERNELS)
        assert found.phases == {
            "forward": pytest.approx(1000.0),
            "hvd.attn.fwd": pytest.approx(500.0),
            "hvd.attn.bwd": pytest.approx(1000.0),
            "backward": pytest.approx(500.0),
            "hvd.wire": pytest.approx(875.0),
            "hvd.optimizer": pytest.approx(500.0),
            "unscoped": pytest.approx(375.0)}
        assert found.busy_ms == pytest.approx(sum(found.phases.values()))
        assert found.busy_ms == pytest.approx(
            trace_reduce.busy_seconds(self.TRACE)[0] / 2 * 1e3)

    def test_the_wire_outside_its_collectives(self, scopes):
        found = program_spans.device_side(self.TRACE, 2, scopes,
                                          self.KERNELS)
        # the pack (a fusion) and the unpacking slice, not the all-reduce
        assert found.wire_pack_ms == pytest.approx((0.5 + 0.25) / 2 * 1e3)

    def test_kernels_split_where_attn_kernel_ms_reads_them(self, scopes):
        found = program_spans.device_side(self.TRACE, 2, scopes,
                                          self.KERNELS)
        whole = trace_reduce.kernel_seconds(self.TRACE, self.KERNELS)
        assert whole == pytest.approx(4.0)  # device 1, where they took longest
        assert found.attn_fwd_ms == pytest.approx(750.0)
        assert found.attn_bwd_ms == pytest.approx(1250.0)
        assert found.attn_fwd_ms + found.attn_bwd_ms == pytest.approx(
            whole / 2 * 1e3)

    def test_a_kernel_under_neither_scope_is_refused(self, scopes):
        scopes = dict(scopes, **{
            "flash_attention.4": "jit(spmd_step)/transpose(jvp(Bert))/"
                                 "attention/pallas_call"})
        with pytest.raises(ValueError, match="neither hvd.attn.fwd"):
            program_spans.device_side(self.TRACE, 2, scopes, self.KERNELS)

    def test_no_kernel_no_kernel_time(self, scopes):
        trace = Trace({0: self.DEVICE[4:]}, {0: []}, [], (6.0, 10.0))
        found = program_spans.device_side(trace, 2, scopes, self.KERNELS)
        assert found.attn_fwd_ms is None and found.attn_bwd_ms is None
        assert found.phases["hvd.optimizer"] == pytest.approx(500.0)

    @pytest.mark.parametrize("scope, phase", [
        (None, "unscoped"),
        ("jit(spmd_step)/shard_map/psum", "unscoped"),
        ("jit(s)/jvp(Bert)/layer_0/add", "forward"),
        ("jit(s)/transpose(jvp(Bert))/layer_0/add", "backward"),
        ("jit(s)/transpose(jvp(f))/hvd.overlap.segment1/hvd.wire/"
         "hvd.allreduce.bucket0.8B/psum", "hvd.wire"),
        ("jit(s)/hvd.fsdp.param_gather.seg0/hvd.wire/hvd.param_allgather/"
         "all_gather", "hvd.wire"),
        ("jit(s)/hvd.optimizer/hvd.wire/mul", "hvd.wire"),
    ])
    def test_phase_of(self, scope, phase):
        assert program_spans.phase_of(scope) == phase

    def test_all_reduce_bytes_from_the_steps_text(self):
        assert program_spans.all_reduce_bytes(self.HLO) == {
            "bf16": 64 * 2 + 32 * 2 + 2 * 16 * 2, "f32": 4}


class FakeRun:
    """What ``run.py::per_layer`` hands a reader, of a traced TPU step."""

    def __init__(self, trace):
        self.trace, self.steps = trace, 2
        self.cell = types.SimpleNamespace(name="no-such-cell", job={})
        self.call_s = [0.001]


class TestReadersFailures:
    def test_a_step_whose_text_holds_no_scope_fails_saying_so(
            self, monkeypatch):
        import horovod_tpu as hvd

        stale = TestDeviceSide.HLO.replace("hvd.", "xyz.")
        monkeypatch.setattr(hvd.profiler, "step_texts", lambda: [stale])
        optimizer_ms = cells.load_code(
            BENCHMARK_DIR, "layer_metrics", "optimizer_ms.py")
        with pytest.raises(ValueError) as refused:
            optimizer_ms.read(FakeRun(TestDeviceSide.TRACE), {})
        said = str(refused.value)
        assert "no phase scope" in said and "hvd.optimizer" in said
        assert "persistent compilation cache" in said

    def test_a_program_from_before_the_scopes_is_nothing_to_read(
            self, monkeypatch):
        import horovod_tpu as hvd

        monkeypatch.delattr(hvd.profiler, "step_texts")
        assert program_spans.device(FakeRun(TestDeviceSide.TRACE)) is None

    def test_with_the_scopes_the_device_readers_read(self, monkeypatch):
        import horovod_tpu as hvd

        monkeypatch.setattr(hvd.profiler, "step_texts",
                            lambda: [TestDeviceSide.HLO])
        run = FakeRun(TestDeviceSide.TRACE)
        values = {name: cells.load_code(
            BENCHMARK_DIR, "layer_metrics", name + ".py").read(run, {})
            for name in ("optimizer_ms", "wire_pack_ms",
                         "attn_fwd_kernel_ms", "attn_bwd_kernel_ms")}
        assert values == {
            "optimizer_ms": pytest.approx(500.0),
            "wire_pack_ms": pytest.approx(375.0),
            "attn_fwd_kernel_ms": pytest.approx(750.0),
            "attn_bwd_kernel_ms": pytest.approx(1250.0)}


def benchmark_json() -> dict:
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def entries() -> list:
    """The ten entries as they are to be appended to ``per_layer``."""
    with open(os.path.join(BENCHMARK_DIR, "per_layer_pr23.json")) as f:
        return json.load(f)["per_layer"]


def test_the_entries_are_ready_for_benchmark_json():
    bench = benchmark_json()
    listed = {entry["name"]: entry for entry in entries()}
    assert set(listed) == NEW_METRICS
    measured = {w["name"] for w in bench["workloads"]}
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    already = {m["name"] for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]} | {"optimizer"}
    for name, entry in listed.items():
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        assert entry["workloads"] and set(entry["workloads"]) <= measured
        assert entry["better"] == "lower" and entry["moves"] in end_to_end
        assert entry["layer"] in layers
        assert entry["source"] in {"device_trace", "program_span",
                                   "program_counter"}
        # Listed only once the parent of a PR has the spans: until then a
        # traced run of the parent would have no value for it and fail.
        assert name not in already or all(
            other in already for other in NEW_METRICS)
        for ending in (".json", ".py"):
            assert os.path.exists(os.path.join(
                BENCHMARK_DIR, "layer_metrics", name + ending))
    assert listed["wire_pack_ms"]["workloads"] == ["bert-large_s512_dp4"]
    assert "resnet50_b128_dp1" not in listed["attn_fwd_kernel_ms"][
        "workloads"]


def test_the_ten_readers_on_a_toy_cell_traced_on_the_cpu(tmp_path,
                                                          tmp_path_factory):
    shutil.copytree(BENCHMARK_DIR, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    listed = benchmark_json()
    listed["per_layer"] = [
        entry for entry in listed["per_layer"]
        if entry["name"] not in NEW_METRICS] + [
        dict(entry, workloads=entry["workloads"] + ["rehearsal-bert_dp4"])
        for entry in entries()]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(listed))
    proc = run_cell("rehearsal-bert_dp4", trace=1,
                    cache=tmp_path_factory.mktemp("compile-cache"),
                    root=str(tmp_path), seed=3)
    result = result_of(proc)
    assert result["correct"] is True and result["attempted"] == 10
    metrics = {name: entry["value"]
               for name, entry in result["metrics"].items()}
    # No device plane and no memory counter on a CPU: what reads them is
    # left out, and no number stands under a device metric's name. The
    # program's own host spans and counters are there on any platform.
    assert set(metrics) == {
        "host_call_ms", "compile_s", "step_dispatch_ms", "step_hooks_ms",
        "wire_mb_per_step", "step_trace_lower_s"}
    assert 0 < metrics["step_hooks_ms"] < metrics["step_dispatch_ms"]
    assert (metrics["step_dispatch_ms"] + metrics["step_hooks_ms"]
            <= metrics["host_call_ms"])
    # The toy's gradients, float32 on the wire: 73,120 parameters.
    assert metrics["wire_mb_per_step"] == pytest.approx(73120 * 4 / 1e6)
    assert 0 < metrics["step_trace_lower_s"] < metrics["compile_s"] + 60
    assert "program_spans: hvd.step.dispatch" in proc.stdout
