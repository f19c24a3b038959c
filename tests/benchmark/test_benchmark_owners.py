"""``owners`` on hand-made intervals with known answers (a nested loop,
operations back to back, a gap, two that overlap without nesting), the seven
readers PR 34 added on a made-up trace and on the chip trace of the
fixtures (with a step's text made up from its events' names: the fixture
keeps no text), what they say without a device plane, of a program from
before the block scopes and of a stale executable, and the entries of
``per_layer_pr34.json`` against ``BENCHMARK.json``."""

import gzip
import json
import math
import os
import shutil
import types

import pytest

import cells
import owners
import trace_reduce
from conftest import BENCHMARK_DIR, REPO_ROOT
from test_benchmark_rehearsal import result_of, run_cell
from trace_reduce import Op, Trace

NEW_METRICS = ("unowned_ms", "shared_fusion_ms", "embed_ms", "attn_proj_ms",
               "norm_ms", "ffn_ms", "head_ms")
BLOCKS = {"embed_ms": "hvd.block.embed", "attn_proj_ms": "hvd.block.attn_proj",
          "norm_ms": "hvd.block.norm", "ffn_ms": "hvd.block.ffn",
          "head_ms": "hvd.block.head"}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def reader(name):
    return cells.load_code(BENCHMARK_DIR, "layer_metrics", name + ".py")


def parameters(name):
    return cells.load_json(BENCHMARK_DIR, "layer_metrics", name + ".json")


def ops(*rows):
    return [Op(name, name.split(".")[0], start, end)
            for name, start, end in rows]


class TestByInstant:
    def test_back_to_back_and_a_gap(self):
        found = owners.self_seconds(ops(
            ("fusion.1", 0.0, 1.0), ("fusion.2", 1.0, 3.0),
            ("copy.3", 5.0, 5.5)))
        assert found == [1.0, 2.0, 0.5]

    def test_a_loops_event_keeps_what_its_body_does_not_cover(self):
        nested = ops(
            ("while.1", 0.0, 10.0),        # the loop's own event
            ("fusion.2", 1.0, 3.0),        # inside it
            ("while.3", 3.0, 8.0),         # a loop inside the loop
            ("fusion.4", 4.0, 5.0), ("fusion.5", 5.0, 7.5),
            ("fusion.6", 8.5, 9.0),
            ("fusion.7", 10.0, 11.0))      # after it, back to back
        found = owners.self_seconds(nested)
        assert found == pytest.approx([
            1.0 + 0.5 + 1.0,  # 0-1, 8-8.5, 9-10
            2.0, 1.0 + 0.5,   # while.3: 3-4 and 7.5-8
            1.0, 2.5, 0.5, 1.0])
        assert sum(found) == pytest.approx(
            trace_reduce.total(trace_reduce.spans(nested)))
        # a plain sum counts what runs inside a loop again: 7.5 s inside
        # the outer one, 3.5 of them once more inside the inner one
        assert sum(op.end - op.start for op in nested) == pytest.approx(
            sum(found) + 7.5 + 3.5)

    def test_the_order_given_does_not_matter(self):
        nested = ops(("fusion.2", 1.0, 3.0), ("while.1", 0.0, 4.0),
                     ("copy.3", 4.0, 4.5))
        assert owners.self_seconds(nested) == [2.0, 2.0, 0.5]

    def test_two_that_overlap_without_nesting_still_add_up_to_the_union(
            self):
        crossed = ops(("fusion.1", 0.0, 2.0), ("fusion.2", 1.0, 3.0),
                      ("fusion.3", 1.0, 1.0))
        found = owners.self_seconds(crossed)
        assert sum(found) == pytest.approx(3.0)
        assert found == [1.0, 2.0, 0.0]

    def test_nothing_ran(self):
        assert owners.self_seconds([]) == []


STACK = "jit(spmd_step)/shard_map/"
FWD, BWD = STACK + "jvp(Bert)/", STACK + "transpose(jvp(Bert))/"


def line(name, opcode, scope=None, calls=None, operands="%p"):
    text = f"  %{name} = f32[8]{{0}} {opcode}({operands})"
    if calls:
        text += f", kind=kLoop, calls=%{calls}"
    if scope:
        text += f', metadata={{op_name="{scope}"}}'
    return text


def computation(name, *lines):
    return "\n".join([f"%{name} (p: f32[8]) -> f32[8] {{",
                      "  %p." + name + " = f32[8]{0} parameter(0)",
                      *lines, "}", ""])


HLO = "\n".join([
    "HloModule jit_spmd_step, is_scheduled=true", "",
    computation("f.embed", line(
        "gather.0", "gather", FWD + "hvd.block.embed/token_embeddings/take")),
    computation("f.mixed",
                line("dot.0", "dot", BWD + "layer_0/hvd.block.ffn/mlp_in/"
                     "dot_general"),
                line("add.0", "add", STACK + "hvd.optimizer/add")),
    computation("f.nobody", line("copy.0", "copy")),
    computation("body", line(
        "fusion.8", "fusion", FWD + "hvd.block.norm/ln_attn/mul")),
    "ENTRY %main (a: f32[8]) -> f32[8] {",
    "  %a = f32[8]{0} parameter(0)",
    line("fusion.1", "fusion", calls="f.embed", operands="%a"),
    line("fusion.2", "fusion", FWD + "layer_0/hvd.block.attn_proj/attention/"
         "query/dot_general", operands="%fusion.1"),
    line("flash_attention.3", "custom-call", FWD + "layer_0/"
         "hvd.block.attn_proj/attention/jit(flash_attention)/hvd.attn.fwd/"
         "flash_attention/pallas_call", operands="%fusion.2"),
    line("fusion.4", "fusion", FWD + "layer_0/hvd.block.norm/ln_attn/add",
         operands="%flash_attention.3"),
    line("fusion.5", "fusion", BWD + "layer_0/hvd.block.ffn/mlp_in/"
         "dot_general", calls="f.mixed", operands="%fusion.4"),
    line("fusion.6", "fusion", calls="f.mixed", operands="%fusion.5"),
    line("copy.7", "copy", operands="%fusion.6"),
    "  %while.9 = f32[8]{0} while(%copy.7), condition=%body, body=%body",
    line("fusion.10", "fusion", FWD + "hvd.block.head/mlm_transform/"
         "dot_general", operands="%while.9"),
    line("fusion.11", "fusion", calls="f.nobody", operands="%fusion.10"),
    "}", ""])
OPS = [
    Op("fusion.1", "fusion", 0.0, 1.0),            # embed, from inside
    Op("fusion.2", "fusion", 1.0, 2.0),            # attn_proj
    Op("flash_attention.3", "custom-call", 2.0, 3.0),  # a phase in a block
    Op("fusion.4", "fusion", 3.0, 3.5),            # norm
    Op("fusion.5", "fusion", 3.5, 5.5),            # ffn's, shared
    Op("fusion.6", "fusion", 5.5, 6.0),            # nobody's own, shared
    Op("copy.7", "copy", 6.0, 6.25),               # unowned
    Op("while.9", "while", 6.25, 8.25),            # norm inside: 0.5 its own
    Op("fusion.8", "fusion", 6.5, 8.0),            # the loop's body, norm
    Op("fusion.10", "fusion", 8.25, 9.0),          # head
    Op("fusion.11", "fusion", 9.0, 9.5),           # unowned
    Op("not-in-the-text", "", 9.5, 9.75),          # unowned
]
TRACE = Trace({0: OPS}, {0: []}, [], (0.0, 10.0))


@pytest.fixture()
def run(monkeypatch):
    import horovod_tpu as hvd

    monkeypatch.setattr(hvd.profiler, "step_texts", lambda: [HLO])
    return types.SimpleNamespace(
        trace=TRACE, steps=2, peak=PEAK, call_s=[0.001],
        cell=cells.resolve("bert-large_s512_dp1"))


def read(name, run):
    return reader(name).read(run, parameters(name))


class TestReaders:
    def test_the_rows_add_up_to_the_union(self, run):
        found = owners.of(run)
        half = 1e3 / 2  # seconds of two steps -> ms a step
        assert found.booked == pytest.approx({
            "hvd.block.ffn": 2.0 * half, "hvd.block.norm": 2.5 * half,
            "hvd.block.embed": 1.0 * half, "hvd.block.attn_proj": 1.0 * half,
            "hvd.attn.fwd": 1.0 * half, "unowned": 1.0 * half,
            "hvd.block.head": 0.75 * half, "shared": 0.5 * half})
        assert found.busy_ms == pytest.approx(9.75 * half)
        assert sum(found.booked.values()) == pytest.approx(found.busy_ms)
        # largest first
        assert list(found.booked)[:2] == ["hvd.block.norm", "hvd.block.ffn"]

    def test_shared_fusions_whoever_they_are_booked_to(self, run):
        found = owners.of(run)
        assert found.shared_sets == pytest.approx(
            {("hvd.block.ffn", "hvd.optimizer"): 2.5 * 500})
        assert found.in_shared == pytest.approx(
            {"hvd.block.ffn": 2.0 * 500, "shared": 0.5 * 500})
        assert read("shared_fusion_ms", run) == pytest.approx(1250.0)

    def test_the_unowned_are_named_with_their_neighbours(self, run):
        found = owners.of(run)
        assert [(name, opcode, neighbours)
                for _, name, opcode, _, neighbours in found.unowned] == [
            ("fusion.11", "fusion", ("hvd.block.head", None)),
            ("copy.7", "copy", ("shared", "hvd.block.norm")),
            ("not-in-the-text", "", (None, None))]
        assert found.unowned_kinds[
            "copy", ("shared", "hvd.block.norm")] == pytest.approx(125.0)
        assert read("unowned_ms", run) == pytest.approx(500.0)

    @pytest.mark.parametrize("name, ms", [
        ("embed_ms", 500.0), ("attn_proj_ms", 500.0), ("norm_ms", 1250.0),
        ("ffn_ms", 1000.0), ("head_ms", 375.0)])
    def test_a_block_reader_reads_its_row(self, run, name, ms):
        assert parameters(name)["owner"] == BLOCKS[name]
        assert read(name, run) == pytest.approx(ms)

    def test_the_table_is_printed_once_a_run(self, run, capsys):
        for name in NEW_METRICS:
            read(name, run)
        said = capsys.readouterr().out
        assert said.count("ms a step busy, the union of the intervals") == 1
        assert "owners: shared  1250.000  hvd.block.ffn + hvd.optimizer" \
            in said
        assert "copy.7 = f32[8]{0} copy; operand from shared, first user " \
            "hvd.block.norm" in said
        assert "head_ms: the head's" in said and "% of the bf16 peak" in said

    def test_a_block_nothing_ran_under_has_no_number(self, run,
                                                     monkeypatch):
        import horovod_tpu as hvd

        monkeypatch.setattr(hvd.profiler, "step_texts", lambda: [
            HLO.replace("hvd.block.head", "hvd.block.embed")])
        assert read("head_ms", run) is None
        assert read("embed_ms", run) == pytest.approx(875.0)

    @pytest.mark.parametrize("name", NEW_METRICS)
    def test_no_device_plane_no_number(self, run, name):
        run.trace = Trace({}, {}, [], (0.0, 10.0))
        assert read(name, run) is None

    @pytest.mark.parametrize("name", NEW_METRICS)
    def test_a_program_from_before_the_owners_is_nothing_to_read(
            self, run, name, monkeypatch):
        import horovod_tpu as hvd

        monkeypatch.delattr(hvd.profiler, "instruction_owners")
        assert read(name, run) is None

    def test_a_stale_executable_fails_saying_so(self, run, monkeypatch):
        import horovod_tpu as hvd

        monkeypatch.setattr(hvd.profiler, "step_texts", lambda: [
            HLO.replace("hvd.block.", "")])
        with pytest.raises(ValueError, match="no block scope") as refused:
            read("unowned_ms", run)
        assert "persistent compilation cache" in str(refused.value)

    def test_the_heads_flops_are_the_configurations(self):
        head = reader("head_ms")
        bert = cells.resolve("bert-large_s512_dp1")
        assert head.head_flops_per_step(bert) == pytest.approx(
            6.0 * 1024 * (1024 + 30522) * 76 * 24)
        olmoe = cells.resolve("olmoe-1b-7b_s4096_e16_dp1")
        assert head.head_flops_per_step(olmoe) == pytest.approx(
            6.0 * 2048 * 50304 * 4096)
        # "a third of the FLOPs", as the configuration's `why` says (of
        # the four layers kept: 40%)
        share = head.head_flops_per_step(olmoe) / olmoe.code.flops_per_step(
            olmoe.config, olmoe.job, olmoe.rows)
        assert 0.30 < share < 0.45


class TestChipTrace:
    """The readers on the trace recorded on the chip in PR 22 (see
    ``test_benchmark_trace_reduce.py``). The fixture holds no step's text,
    so one is made up from its events' names: every fusion whose number is
    a multiple of three under ``hvd.block.ffn``, of five under
    ``hvd.block.norm``, the kernels under ``hvd.attn.fwd``, one fusion each
    for the other blocks, the rest nobody's."""

    @pytest.fixture(scope="class")
    def trace(self, tmp_path_factory):
        fixture = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "fixtures",
            "bert-large_s512_dp4.device0.step1.xplane.pb.gz")
        path = tmp_path_factory.mktemp("trace") / "chip.xplane.pb"
        with gzip.open(fixture, "rb") as f:
            path.write_bytes(f.read())
        return trace_reduce.read(str(path))

    @staticmethod
    def made_up_text(trace) -> str:
        names = sorted({(op.name, op.opcode) for op in trace.devices[0]})
        fusions = [name for name, opcode in names if opcode == "fusion"]
        special = dict(zip(fusions[:3], (
            "hvd.block.embed", "hvd.block.attn_proj", "hvd.block.head")))
        lines = ["ENTRY %main (a: f32[8]) -> f32[8] {"]
        for name, opcode in names:
            number = int(name.rsplit(".", 1)[-1]) if name[-1].isdigit() else 1
            scope = special.get(name)
            if name.startswith("flash_attention"):
                scope = "hvd.attn.fwd/flash_attention/pallas_call"
            elif opcode == "fusion" and scope is None:
                scope = ("hvd.block.ffn/mul" if number % 3 == 0 else
                         "hvd.block.norm/add" if number % 5 == 0 else None)
            lines.append(line(name, opcode or "custom-call",
                              scope and STACK + scope))
        return "\n".join(lines + ["}", ""])

    def test_the_readers_read_finite_numbers_that_add_up(self, trace,
                                                         monkeypatch):
        import horovod_tpu as hvd

        text = self.made_up_text(trace)
        monkeypatch.setattr(hvd.profiler, "step_texts", lambda: [text])
        run = types.SimpleNamespace(
            trace=trace, steps=1, peak=PEAK, call_s=[0.001],
            cell=cells.resolve("bert-large_s512_dp4"))
        values = {name: read(name, run) for name in NEW_METRICS}
        assert all(value is not None and math.isfinite(value) and value >= 0
                   for value in values.values()), values
        found = owners.of(run)
        # busy = the union, as device_idle_share takes it
        assert found.busy_ms == pytest.approx(229.635819)
        assert sum(found.booked.values()) == pytest.approx(found.busy_ms,
                                                           abs=0.01)
        assert found.booked["hvd.attn.fwd"] == pytest.approx(35.2452)
        assert values["shared_fusion_ms"] == 0.0
        assert 0 < values["unowned_ms"] < found.busy_ms
        assert values["ffn_ms"] > values["embed_ms"] > 0


def benchmark_json() -> dict:
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def entries() -> list:
    """The seven entries as they are to be appended to ``per_layer``."""
    return cells.load_json(BENCHMARK_DIR, "per_layer_pr34.json")["per_layer"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_an_entry_is_ready_for_benchmark_json(name):
    bench = benchmark_json()
    entry, = (entry for entry in entries() if entry["name"] == name)
    cells_by_name = {w["name"]: w for w in bench["workloads"]}
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert (entry["unit"], entry["better"], entry["source"],
            entry["moves"]) == ("ms", "lower", "device_trace", "step_ms")
    assert entry["layer"] == (
        "model_blocks" if name in BLOCKS else "device")
    assert entry["workloads"] and set(entry["workloads"]) <= set(
        cells_by_name)
    # in BENCHMARK.json's own order of the cells
    assert entry["workloads"] == [w for w in cells_by_name
                                  if w in entry["workloads"]]
    configs = {cells_by_name[w]["config"] for w in entry["workloads"]}
    if name in ("unowned_ms", "shared_fusion_ms"):
        assert len(entry["workloads"]) == len(cells_by_name)
    elif name == "ffn_ms":  # the models with a dense feed-forward
        assert configs == {"bert-large", "olmo-hybrid-7b"}
    else:  # ResNet's blocks are in its table and have no metric
        assert configs == set(c["name"] for c in bench["configs"]) - {
            "resnet50"}
    for ending in (".json", ".py"):
        assert os.path.exists(os.path.join(
            BENCHMARK_DIR, "layer_metrics", name + ending))
    assert len(parameters(name)["definition"]) > 100


def test_the_entries_are_listed_together_or_not_at_all():
    """Listed only once the parent of a PR has the block scopes: until
    then a traced run of the parent would have no value for them and
    ``run.py`` would refuse it (``per_layer_pr34.json``)."""
    already = {m["name"] for m in benchmark_json()["per_layer"]}
    assert [entry["name"] for entry in entries()] == list(NEW_METRICS)
    assert not already & set(NEW_METRICS) or set(NEW_METRICS) <= already


def test_the_seven_readers_through_run_py_on_a_toy_cell(tmp_path,
                                                        tmp_path_factory):
    """In a temporary copy of the benchmark whose ``BENCHMARK.json`` has
    gained the seven entries with the toy cell in their ``workloads``,
    nothing that was there edited: ``run.py`` finds the readers and their
    shared reduction by name. A CPU's trace has no device plane, so no
    number stands under a device metric's name."""
    shutil.copytree(BENCHMARK_DIR, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    listed = benchmark_json()
    listed["per_layer"] = [
        entry for entry in listed["per_layer"]
        if entry["name"] not in NEW_METRICS] + [
        dict(entry, workloads=entry["workloads"] + ["rehearsal-bert_dp1"])
        for entry in entries()]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(listed))
    proc = run_cell("rehearsal-bert_dp1", trace=1,
                    cache=tmp_path_factory.mktemp("compile-cache"),
                    root=str(tmp_path), seed=5)
    result = result_of(proc)
    assert result["correct"] is True
    assert not set(result["metrics"]) & set(NEW_METRICS)
    assert "host_call_ms" in result["metrics"]
