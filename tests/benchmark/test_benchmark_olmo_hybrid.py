"""The Olmo Hybrid configuration and its cell: the files say what
``BENCHMARK.json`` says, the FLOPs are hand arithmetic at the published
sizes, the toy cell goes through ``run.py`` on the CPU (in a temporary
copy of the benchmark whose ``rehearsal.json`` has gained the cell, nothing
that was there edited), and the three readers read a made-up trace."""

import json
import os
import shutil
import types

import pytest

import cells
from conftest import BENCHMARK_DIR, REPO_ROOT
from test_benchmark_rehearsal import result_of, run_cell
from trace_reduce import Op, Trace

CELL = "olmo-hybrid-7b_s4096_dp1"
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
CATALOG = {  # architectures.jsonl's `config` for Olmo-Hybrid-7B
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": PERIOD * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
READERS = ("linattn_scan_ms", "linattn_scan_roofline", "linattn_mix_ms")


def reader(name):
    return cells.load_code(BENCHMARK_DIR, "layer_metrics", name + ".py")


def parameters(name):
    return cells.load_json(BENCHMARK_DIR, "layer_metrics", name + ".json")


class TestConfiguration:
    def test_every_catalog_key_is_as_published_or_listed_as_reduced(self):
        cell = cells.resolve(CELL)
        entry, = [c for c in cells.benchmark()["configs"]
                  if c["name"] == "olmo-hybrid-7b"]
        differs = {key for key, value in CATALOG.items()
                   if cell.config.get(key, "left out") != value}
        assert differs == {"num_hidden_layers", "layer_types", "vocab_size"}
        assert differs <= set(entry["reduced"])
        assert set(cell.config["reduced"]) == set(entry["reduced"]) == {
            "num_hidden_layers", "layer_types", "heads_here", "vocab_size"}
        assert entry["source"] in cell.config["source"]
        # the cuts keep to the guide's floors: a whole period, an eighth
        assert cell.config["layer_types"] == PERIOD
        assert cell.config["vocab_size"] * 8 == CATALOG["vocab_size"]
        # no width is among them
        assert not [key for key in entry["reduced"]
                    if key.endswith(("_dim", "_rank", "_size"))
                    and key != "vocab_size"]
        assert (cell.config["heads_here"], cell.config["first_head"]) == (
            15, 0)
        assert "two chips that share each layer's heads" in (
            cell.config["deployment"])
        assert {"rope_theta", "residuals_and_norms", "initialisation",
                "linear_attention_layer"} <= set(cell.config["assumed"])

    def test_the_cell_is_the_issues(self):
        cell = cells.resolve(CELL)
        assert (cell.chips, cell.measured, cell.rows) == (1, True, 1)
        assert cell.job["seq_len"] == 4096
        assert (cell.job["sync_mode"], cell.job["compression"],
                cell.job["reference_block_rows"]) == ("allreduce", "bf16", 1)
        assert cell.config["training"]["attention"] == "flash"
        assert cell.config["training"]["scan_chunk"] == 64
        assert cell.code.min_pallas_calls(cell.config) == 3
        assert cell.code.units_per_step(cell.job, 1) == (4096, "tokens")
        assert {e["name"] for e, _, _ in cells.layer_metrics(CELL)} == {
            *READERS, "device_idle_share", "host_call_ms", "compile_s",
            "hbm_buffers_gib", "hbm_setup_peak_gib"}
        entry, = [w for w in cells.benchmark()["workloads"]
                  if w["name"] == CELL]
        assert "feed-forward is whole" in entry["why"]
        for name in READERS:
            metric, = [m for m in cells.benchmark()["per_layer"]
                       if m["name"] == name]
            assert (metric["workloads"], metric["moves"]) == (
                [CELL], "step_ms")
        # seeds 0-9 recorded by the chip
        assert sorted(cell.job["loss_after_warmup"], key=int) == [
            str(seed) for seed in range(10)]

    def test_the_model_is_built_at_the_published_widths(self):
        from horovod_tpu.models import olmo_hybrid

        cell = cells.resolve(CELL)
        built = cell.code.model_config(cell.config)
        assert built == olmo_hybrid.OlmoHybridConfig(
            num_layers=4, layer_types=tuple(PERIOD), heads_here=15,
            vocab_size=12544)
        assert (built.window, built.head_dim, built.chunk) == (15, 128, 64)

    def test_flops_are_hand_arithmetic_at_the_published_sizes(self):
        cell = cells.resolve(CELL)
        macs = cell.code.macs_per_token(cell.config, 4096)
        assert macs == {
            # q, k (96), v, gate (192), out (192) and two gates, 15 heads
            "linear_projections": 3840 * 15 * (96 + 96 + 192 + 192 + 192 + 2),
            "short_conv": 4 * 15 * (96 + 96 + 192),
            "recurrence": 3 * 15 * 96 * 192,       # 0.83 M
            "full_projections": 4 * 3840 * 15 * 128,  # 29.5 M
            "causal_scores": 2 * 2048 * 15 * 128,  # 7.9 M: S / 2 keys
            "feed_forward": 3 * 3840 * 11008,      # 126.8 M, whole
            "head": 3840 * 12544}                  # 48.2 M
        linear = 44_352_000 + 23_040 + 829_440 + 126_812_160
        full = 29_491_200 + 7_864_320 + 126_812_160
        flops = cell.code.flops_per_step(cell.config, cell.job, 1)
        assert flops == 6 * (3 * linear + full + 48_168_960) * 4096
        assert flops == pytest.approx(17.90e12, rel=1e-3)
        # twice the rows, twice the FLOPs
        assert cell.code.flops_per_step(cell.config, cell.job, 2) == (
            2 * flops)


class TestReaders:
    """A made-up trace of one device and two steps, as
    ``test_benchmark_olmoe.py`` makes them; the scan's loop has an event of
    its own around the events of what runs inside it."""

    HLO = """
  %fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f.1, metadata={op_name="jit(spmd_step)/shard_map/jvp(OlmoHybrid)/layer_0/linear_attention/query/dot_general"}
  %fusion.2 = bf16[8]{0} fusion(%q), kind=kLoop, calls=%f.2, metadata={op_name="jit(spmd_step)/shard_map/jvp(OlmoHybrid)/layer_0/linear_attention/hvd.linattn.conv/mul"}
  %fusion.3 = f32[8]{0} fusion(%q), kind=kOutput, calls=%f.3, metadata={op_name="jit(spmd_step)/shard_map/jvp(OlmoHybrid)/layer_0/linear_attention/hvd.linattn.scan/bhnic,bhnjc->bhnij/dot_general"}
  %while.4 = (f32[8]{0}) while(%t), condition=%c.4, body=%b.4, metadata={op_name="jit(spmd_step)/shard_map/jvp(OlmoHybrid)/layer_0/linear_attention/hvd.linattn.scan/while"}
  %fusion.5 = f32[8]{0} fusion(%s), kind=kOutput, calls=%f.5, metadata={op_name="jit(spmd_step)/shard_map/jvp(OlmoHybrid)/layer_0/linear_attention/hvd.linattn.scan/while/body/bhck,bhkv->bhcv/dot_general"}
  %fusion.6 = f32[8]{0} fusion(%o), kind=kLoop, calls=%f.6, metadata={op_name="jit(spmd_step)/shard_map/jvp(OlmoHybrid)/layer_0/linear_attention/hvd.linattn.gate/o_norm/mul"}
  %flash_attention.7 = bf16[8]{0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(spmd_step)/shard_map/jvp(OlmoHybrid)/layer_3/attention/jit(flash_attention)/hvd.attn.fwd/flash_attention/pallas_call"}
  %fusion.8 = f32[8]{0} fusion(%s), kind=kOutput, calls=%f.8, metadata={op_name="jit(spmd_step)/shard_map/transpose(jvp(OlmoHybrid))/layer_0/linear_attention/hvd.linattn.scan/while/body/bhck,bhcv->bhkv/dot_general"}
  %fusion.9 = f32[8]{0} fusion(%s), kind=kLoop, calls=%f.9, metadata={op_name="jit(spmd_step)/shard_map/transpose(jvp(OlmoHybrid))/layer_0/linear_attention/hvd.linattn.conv/mul"}
  %fusion.10 = f32[8]{0} fusion(%s), kind=kLoop, calls=%f.10, metadata={op_name="jit(spmd_step)/shard_map/hvd.optimizer/add"}
"""
    OPS = [
        Op("fusion.1", "fusion", 0.0, 1.0),
        Op("fusion.2", "fusion", 1.0, 1.5),       # conv
        Op("fusion.3", "fusion", 1.5, 2.0),       # scan, outside the loop
        Op("while.4", "while", 2.0, 4.0),         # the loop's own event
        Op("fusion.5", "fusion", 2.25, 3.75),     # inside it
        Op("fusion.6", "fusion", 4.0, 4.25),      # gate
        Op("flash_attention.7", "custom-call", 4.25, 5.0),
        Op("fusion.8", "fusion", 5.0, 8.0),       # scan, backward
        Op("fusion.9", "fusion", 8.0, 9.25),      # conv, backward
        Op("fusion.10", "fusion", 9.25, 10.0),
    ]
    TRACE = Trace({0: OPS}, {0: []}, [], (0.0, 10.0))

    def a_run(self, monkeypatch, hlo):
        import horovod_tpu as hvd

        monkeypatch.setattr(hvd.profiler, "step_texts", lambda: [hlo])
        return types.SimpleNamespace(
            trace=self.TRACE, steps=2, peak=PEAK, call_s=[0.001],
            cell=cells.resolve(CELL))

    def test_the_scan_and_the_mix_read_the_programs_scopes(
            self, monkeypatch, capsys):
        run = self.a_run(monkeypatch, self.HLO)
        # 0.5 + the loop's 2.0 (what runs inside it counted once) + 3.0
        assert reader("linattn_scan_ms").read(
            run, parameters("linattn_scan_ms")) == pytest.approx(2750.0)
        assert ("2750.000 ms a step as the union of the operations' "
                "intervals; their plain sum is 3500.000"
                ) in capsys.readouterr().out
        assert reader("linattn_mix_ms").read(
            run, parameters("linattn_mix_ms")) == pytest.approx(
                (0.5 + 1.25 + 0.25) / 2 * 1e3)
        assert ("hvd.linattn.conv 875.000 ms, hvd.linattn.gate 125.000 ms"
                ) in capsys.readouterr().out

    def test_the_roofline_is_least_time_over_the_scans(self, monkeypatch):
        run = self.a_run(monkeypatch, self.HLO)
        share = reader("linattn_scan_roofline").read(
            run, parameters("linattn_scan_roofline"))
        # both passes are memory-bound: 71.3 and 118.9 MB a layer
        forward = 15 * 4096 * (2 * (96 + 96 + 192 + 192) + 8) / 819e9
        backward = 15 * 4096 * (2 * (4 * 96 + 3 * 192) + 16) / 819e9
        assert share == pytest.approx(
            100 * 3 * (forward + backward) / 2.75)

    def test_the_roofline_costs_are_hand_arithmetic(self):
        roofline = reader("linattn_scan_roofline")
        shape = (15, 4096, 64, 96, 192, 2)
        flops, nbytes = roofline.forward_cost(*shape)
        # a chunk: half of 64 x 64 against 3 x 96 + 2 x 192 columns, and
        # three 64 x 96 x 192 products with the state; 64 chunks, 15 heads
        assert flops == 15 * 64 * 2 * (2048 * 672 + 3 * 64 * 96 * 192)
        assert flops == pytest.approx(9.437e9, rel=1e-3)
        assert nbytes == 15 * 4096 * (576 * 2 + 8)
        back_flops, back_bytes = roofline.backward_cost(*shape)
        assert back_flops == 2 * flops
        assert back_bytes == 15 * 4096 * ((4 * 96 + 3 * 192) * 2 + 16)
        seconds, bound = roofline.least_seconds((flops, nbytes), PEAK)
        assert bound == "memory"
        assert seconds == pytest.approx(0.0870e-3, rel=1e-3)
        # never under what the model's FLOPs count for the recurrence
        cell = cells.resolve(CELL)
        recurrence = 2 * 4096 * cell.code.macs_per_token(
            cell.config, 4096)["recurrence"]
        assert flops > recurrence

    def test_a_program_without_the_scopes_is_nothing_to_read(
            self, monkeypatch):
        run = self.a_run(monkeypatch, self.HLO.replace("hvd.linattn.",
                                                       "linattn."))
        for name in READERS:
            assert reader(name).read(run, parameters(name)) is None

    def test_no_device_plane_no_number(self):
        run = types.SimpleNamespace(
            trace=Trace({}, {}, [], (0.0, 1.0)), steps=2, peak=None,
            call_s=[0.001], cell=cells.resolve(CELL))
        for name in READERS:
            assert reader(name).read(run, parameters(name)) is None


def test_the_toy_cell_through_run_py_on_the_cpu(tmp_path, tmp_path_factory):
    copy = tmp_path / "benchmark"
    shutil.copytree(BENCHMARK_DIR, copy,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO_ROOT, "BENCHMARK.json"), tmp_path)
    listed = json.loads((copy / "rehearsal.json").read_text())
    listed["workloads"].append({
        "name": "rehearsal-olmo-hybrid_dp1", "config": "rehearsal-olmo-hybrid",
        "traffic": "rehearsal-olmo-hybrid_dp1", "chips": 1})
    (copy / "rehearsal.json").write_text(json.dumps(listed))
    cache = tmp_path_factory.mktemp("compile-cache")
    proc = run_cell("rehearsal-olmo-hybrid_dp1", trace=0, cache=cache,
                    root=str(tmp_path), seed=2147483650)
    result = result_of(proc)
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert set(result["metrics"]) == {"step_ms", "setup_s"}
    assert "68 leaves, 2 rows a step" in proc.stdout
    for check in ("loss_vs_reference", "gradient_norms_vs_reference",
                  "loss_after_warmup", "kernels_in_step", "losses_finite"):
        assert f"check {check}: ok" in proc.stdout, proc.stdout[-3000:]
