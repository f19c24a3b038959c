"""``trace_reduce`` on hand-made intervals with known answers, and on a
trace recorded on the chip and trimmed to a fixture."""

import gzip
import os

import pytest

import trace_reduce
from trace_reduce import Op, Trace


def ops(*rows):
    return [Op(name, opcode, start, end) for name, opcode, start, end in rows]


class FakeEvent:
    def __init__(self, name, start_ns=1000.0, duration_ns=500.0):
        self.name, self.start_ns, self.duration_ns = (
            name, start_ns, duration_ns)


class TestEventNames:
    """An event is named by its instruction's text, as the chip's trace
    has it (PR 22's runs)."""

    @pytest.mark.parametrize("text, name, opcode", [
        ("%psum.92 = bf16[32536576]{0:T(1024)(128)(2,1)} all-reduce("
         "bf16[32536576]{0:T(1024)(128)(2,1)} %fusion.103), channel_id=1",
         "psum.92", "all-reduce"),
        ("%all-reduce.1 = (bf16[32536576]{0:T(1024)(128)(2,1)}, bf16[236]"
         "{0:T(1024)(128)(2,1)}) all-reduce(bf16[32536576]{0} %a, bf16[2"
         "36]{0} %b), channel_id=1", "all-reduce.1", "all-reduce"),
        ("%convert_reduce_fusion.8 = (f32[256]{0:T(256)S(1)}, bf16[128,56,"
         "56,256]{3,0,2,1:T(8,128)(2,1)}) fusion(f32[256]{0:T(256)S(1)} "
         "%copy-done.330), kind=kOutput, calls=%fused_computation",
         "convert_reduce_fusion.8", "fusion"),
        ("%flash_attention.24 = (bf16[384,512,64]{2,1,0:T(8,128)(2,1)S(1)}"
         ", f32[384,1,512]{2,1,0:T(1,128)}) custom-call(bf16[384,512,64]"
         "{2,1,0} %bitcast.5111), custom_call_target=\"tpu_custom_call\"",
         "flash_attention.24", "custom-call"),
        ("%copy-start.915 = (f32[64]{0:T(128)S(1)}, f32[64]{0:T(128)}, "
         "u32[]{:S(2)}) copy-start(f32[64]{0:T(128)} %params.1)",
         "copy-start.915", "copy-start"),
        ("%select_and_scatter.9 = bf16[128,112,112,64]{0,3,2,1:T(8,128)"
         "(2,1)} select-and-scatter(bf16[128,112,112,64]{0,3,2,1} %f), "
         "window={size=1x3x3x1}", "select_and_scatter.9",
         "select-and-scatter"),
        ("something else entirely", "something else entirely", ""),
    ])
    def test_name_and_opcode(self, text, name, opcode):
        op = trace_reduce.op_of(FakeEvent(text))
        assert (op.name, op.opcode) == (name, opcode)
        assert (op.start, op.end) == (pytest.approx(1e-6),
                                      pytest.approx(1.5e-6))


class TestIntervals:
    @pytest.mark.parametrize("intervals, merged", [
        ([], []),
        ([(0, 1), (2, 3)], [(0, 1), (2, 3)]),
        ([(2, 3), (0, 1)], [(0, 1), (2, 3)]),
        ([(0, 2), (1, 3)], [(0, 3)]),
        ([(0, 5), (1, 2), (3, 4)], [(0, 5)]),
        ([(0, 1), (1, 2)], [(0, 2)]),
        ([(1, 1)], []),
    ])
    def test_union(self, intervals, merged):
        assert trace_reduce.union(intervals) == merged

    def test_total_counts_an_instant_once(self):
        assert trace_reduce.total([(0, 2), (1, 3), (10, 11)]) == 4

    @pytest.mark.parametrize("intervals, holes, left", [
        ([(0, 10)], [], [(0, 10)]),
        ([(0, 10)], [(2, 3), (5, 7)], [(0, 2), (3, 5), (7, 10)]),
        ([(0, 10)], [(-1, 1), (9, 12)], [(1, 9)]),
        ([(0, 10)], [(-1, 11)], []),
        ([(0, 2), (4, 6)], [(1, 5)], [(0, 1), (5, 6)]),
        ([(0, 2)], [(2, 4)], [(0, 2)]),
    ])
    def test_subtract(self, intervals, holes, left):
        assert trace_reduce.subtract(intervals, holes) == left


class TestReduction:
    # One device, a window of 10 s holding two "steps": compute, an
    # asynchronous all-gather that compute hides in part, a synchronous
    # all-reduce (JAX calls it psum) that nothing hides, two flash calls.
    DEVICE = ops(
        ("fusion.1", "fusion", 0.0, 2.0),
        ("all-gather-start.7", "all-gather-start", 2.0, 2.1),
        ("flash_attention.3", "custom-call", 2.1, 3.0),
        ("fusion.2", "fusion", 2.5, 3.5),  # overlaps the kernel
        ("all-gather-done.7", "all-gather-done", 4.0, 5.0),
        ("psum.9", "all-reduce", 6.0, 7.0),
        ("flash_attention.4", "custom-call", 7.0, 9.0),
        ("copy-done.12", "copy-done", 9.0, 9.0),
    )
    FLIGHTS = ops(
        ("all-gather-start.7", "all-gather-start", 2.0, 5.0),
        ("copy-start.12", "copy-start", 8.0, 9.0),
    )
    # what the compiled step's text says of these instructions
    HLO = """
  %fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f.1, metadata={op_name="jit(spmd_step)/jvp(Bert)/layer_0/mlp_in/dot_general" stack_frame_id=1}
  ROOT %fusion.2 = bf16[8]{0} fusion(%q), kind=kLoop, calls=%f.2, metadata={op_name="jit(spmd_step)/transpose(jvp(Bert))/layer_0/mlp_in/dot_general"}
  %flash_attention.3 = bf16[8]{0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(spmd_step)/jvp(Bert)/layer_0/attention/pallas_call"}
  %flash_attention.4 = bf16[8]{0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(spmd_step)/transpose(jvp(Bert))/layer_0/attention/pallas_call"}
  %add.5 = f32[8]{0} add(%a, %b), metadata={op_name="jit(spmd_step)/add"}
"""
    HOST = [("bench.step_call", 0.0, 0.5), ("bench.step_call", 0.5, 1.0),
            ("bench.sync", 1.0, 10.0)]
    TRACE = Trace({0: DEVICE}, {0: FLIGHTS}, HOST, (0.0, 10.0))

    def test_busy_is_the_union_of_the_operations(self):
        # [0, 3.5] + [4, 5] + [6, 9]
        assert trace_reduce.busy_seconds(self.TRACE) == {
            0: pytest.approx(7.5)}
        assert trace_reduce.idle_share(self.TRACE) == pytest.approx(0.25)

    def test_idle_share_is_the_worst_device(self):
        two = Trace({0: self.DEVICE,
                     1: ops(("fusion.1", "fusion", 0.0, 5.0))}, {},
                    self.HOST, (0.0, 10.0))
        assert trace_reduce.idle_share(two) == pytest.approx(0.5)

    def test_collective_time_and_its_exposed_part(self):
        # in flight: all-gather [2, 5] and all-reduce [6, 7] = 4 s; other
        # operations cover [2.1, 3.5] of it, so 2.6 s are exposed
        in_flight, exposed = trace_reduce.collective_seconds(self.TRACE)
        assert in_flight == pytest.approx(4.0)
        assert exposed == pytest.approx(2.6)

    def test_no_collective_is_nothing_to_read(self):
        alone = Trace({0: ops(("fusion.1", "fusion", 0.0, 1.0))},
                      {0: self.FLIGHTS[1:]}, self.HOST, (0.0, 10.0))
        assert trace_reduce.collective_seconds(alone) is None

    def test_kernel_seconds_sums_the_matching_operations(self):
        assert trace_reduce.kernel_seconds(
            self.TRACE, r"^flash_attention(\.\d+)?$") == pytest.approx(2.9)
        assert trace_reduce.kernel_seconds(
            self.TRACE, r"^flash_attention\.4$") == pytest.approx(2.0)
        assert trace_reduce.kernel_seconds(self.TRACE, "absent") is None

    def test_scopes_come_from_the_compiled_steps_text(self):
        scopes = trace_reduce.scopes_of(self.HLO)
        assert scopes["fusion.2"] == (
            "jit(spmd_step)/transpose(jvp(Bert))/layer_0/mlp_in/dot_general")
        assert set(scopes) == {"fusion.1", "fusion.2", "flash_attention.3",
                               "flash_attention.4", "add.5"}

    @pytest.mark.parametrize("op, group", [
        (("fusion.1", "fusion"), "forward dot_general"),
        (("fusion.2", "fusion"), "backward dot_general"),
        (("flash_attention.3", "custom-call"), "forward pallas_call"),
        (("flash_attention.4", "custom-call"), "backward pallas_call"),
        (("add.5", "add"), "update add"),
        (("psum.9", "all-reduce"), "all-reduce"),
        (("all-gather-done.7", "all-gather-done"), "all-gather-done"),
        (("copy-done.12", "copy-done"), "copy-done"),  # no scope in the text
    ])
    def test_group_of(self, op, group):
        scopes = trace_reduce.scopes_of(self.HLO)
        assert trace_reduce.group_of(Op(*op, 0.0, 1.0), scopes) == group

    def test_breakdown_groups_operations_and_labels_gaps(self):
        found = trace_reduce.breakdown(
            self.TRACE, 2, trace_reduce.scopes_of(self.HLO))
        groups = dict(found["device_ops"])
        assert groups["forward dot_general"] == pytest.approx(1.0)
        assert groups["backward dot_general"] == pytest.approx(0.5)
        assert groups["all-gather-done"] == pytest.approx(0.5)
        assert groups["all-reduce"] == pytest.approx(0.5)
        assert groups["backward pallas_call"] == pytest.approx(1.0)
        assert list(groups)[0] == "forward dot_general"  # most time first
        # idle: [3.5, 4], [5, 6], [9, 10], all while the host waited
        assert found["idle_gaps"] == [["bench.sync", pytest.approx(1.25)]]

    def test_operations_are_clipped_to_the_window(self):
        clipped = trace_reduce.clip_ops(
            ops(("b", "", 9.0, 12.0), ("a", "", -1.0, 1.0),
                ("c", "", 20.0, 21.0)), (0.0, 10.0))
        assert [(op.name, op.start, op.end) for op in clipped] == [
            ("a", 0.0, 1.0), ("b", 9.0, 10.0)]


class TestChipTrace:
    """A trace recorded on the chip in PR 22 (``bert-large_s512_dp4``, four
    ``TPU v5 lite``), trimmed to a fixture: device 0's "XLA Ops" and "Async
    XLA Ops" lines and the benchmark's host annotations, cut to the first
    232 ms after the first step call (one step and the head of the next),
    each event's name cut after its opcode's parenthesis, statistics
    dropped. The answers are what the reduction made of it then."""

    @pytest.fixture(scope="class")
    def trace(self, tmp_path_factory):
        fixture = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "fixtures",
            "bert-large_s512_dp4.device0.step1.xplane.pb.gz")
        path = tmp_path_factory.mktemp("trace") / "chip.xplane.pb"
        with gzip.open(fixture, "rb") as f:
            path.write_bytes(f.read())
        return trace_reduce.read(str(path))

    def test_planes_lines_and_window(self, trace):
        assert list(trace.devices) == [0] and list(trace.flights) == [0]
        assert len(trace.devices[0]) == 13416
        assert len(trace.flights[0]) == 5105
        assert [name for name, _, _ in trace.host] == (
            ["bench.step_call"] * 5 + ["bench.sync"])
        low, high = trace.window
        assert high - low == pytest.approx(0.232)
        assert all(low <= op.start <= op.end <= high
                   for op in trace.devices[0] + trace.flights[0])

    def test_every_event_parsed_to_a_name_and_an_opcode(self, trace):
        assert all(op.opcode and " " not in op.name
                   for op in trace.devices[0] + trace.flights[0])
        opcodes = {op.opcode for op in trace.devices[0]}
        assert {"fusion", "custom-call", "all-reduce", "copy-done"} <= opcodes
        assert {op.opcode for op in trace.flights[0]} == {
            "copy-start", "async-start"}

    def test_the_wire_is_eight_synchronous_all_reduces(self, trace):
        wire = [op for op in trace.devices[0] if op.opcode == "all-reduce"]
        # JAX names them after psum; only the opcode tells a collective
        assert sorted(op.name.split(".")[0] for op in wire) == (
            ["all-reduce"] * 4 + ["psum"] * 4)
        in_flight, exposed = trace_reduce.collective_seconds(trace)
        assert in_flight == pytest.approx(0.011676634)
        assert exposed == in_flight  # the core runs nothing beside them

    def test_busy_idle_and_kernels(self, trace):
        assert trace_reduce.busy_seconds(trace)[0] == pytest.approx(
            0.229635819)
        assert trace_reduce.idle_share(trace) == pytest.approx(0.0101904353)
        flash = trace_reduce.matching(
            trace.devices[0], r"^flash_attention(\.\d+)?$")
        assert len(flash) == 51  # 48 of the step and 3 of the next
        assert trace_reduce.kernel_seconds(
            trace, r"^flash_attention(\.\d+)?$") == pytest.approx(0.0352452)

    def test_breakdown_without_the_steps_text_is_by_opcode(self, trace):
        found = trace_reduce.breakdown(trace, 1, {})
        assert [name for name, _ in found["device_ops"][:4]] == [
            "fusion", "custom-call", "copy", "all-reduce"]
        assert dict(found["device_ops"])["all-reduce"] == pytest.approx(
            0.011676634)
        assert found["idle_gaps"][0] == [
            "bench.step_call", pytest.approx(0.00227267)]
