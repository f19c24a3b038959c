"""The OLMoE configuration and its cell: the files say what
``BENCHMARK.json`` says, the FLOPs are hand arithmetic at the published
sizes, the toy cell goes through ``run.py`` on the CPU (in a temporary
copy of the benchmark whose ``rehearsal.json`` has gained the cell, nothing
that was there edited), and the four readers read a made-up trace."""

import json
import os
import shutil
import types

import pytest

import cells
from conftest import BENCHMARK_DIR, REPO_ROOT
from test_benchmark_rehearsal import result_of, run_cell
from trace_reduce import Op, Trace

CELL = "olmoe-1b-7b_s4096_e16_dp1"
CATALOG = {  # architectures.jsonl's `config` for OLMoE-1B-7B-0125-Instruct
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def reader(name):
    return cells.load_code(BENCHMARK_DIR, "layer_metrics", name + ".py")


def parameters(name):
    return cells.load_json(BENCHMARK_DIR, "layer_metrics", name + ".json")


class TestConfiguration:
    def test_every_catalog_key_is_as_published_or_listed_as_reduced(self):
        cell = cells.resolve(CELL)
        entry, = [c for c in cells.benchmark()["configs"]
                  if c["name"] == "olmoe-1b-7b"]
        differs = {key for key, value in CATALOG.items()
                   if cell.config.get(key, "left out") != value}
        assert differs == {"num_hidden_layers"}
        assert differs <= set(entry["reduced"])
        assert set(cell.config["reduced"]) == set(entry["reduced"]) == {
            "num_hidden_layers", "experts_here"}
        assert entry["source"] in cell.config["source"]
        # no width is among them
        assert not [key for key in entry["reduced"]
                    if key.endswith(("_dim", "_rank", "_size"))]
        assert "four-chip host" in cell.config["deployment"]

    def test_the_cell_is_the_issues(self):
        cell = cells.resolve(CELL)
        assert (cell.chips, cell.measured, cell.rows) == (1, True, 1)
        assert cell.job["seq_len"] == cell.config["max_position_embeddings"]
        assert (cell.job["sync_mode"], cell.job["compression"]) == (
            "allreduce", "bf16")
        assert cell.config["training"]["attention"] == "flash"
        assert cell.code.min_pallas_calls(cell.config) == 12
        assert cell.code.units_per_step(cell.job, 1) == (4096, "tokens")
        assert {e["name"] for e, _, _ in cells.layer_metrics(CELL)} >= {
            "moe_experts_ms", "moe_dispatch_ms", "causal_attn_kernel_ms",
            "causal_attn_roofline", "device_idle_share", "host_call_ms",
            "compile_s", "hbm_buffers_gib", "hbm_setup_peak_gib"}

    def test_the_model_is_built_at_the_published_widths(self):
        from horovod_tpu.models import olmoe

        cell = cells.resolve(CELL)
        built = cell.code.model_config(cell.config)
        assert built == olmoe.OlmoeConfig(num_layers=4, experts_here=16)
        assert built.capacity(cell.job["seq_len"]) == 640

    def test_flops_are_hand_arithmetic_at_the_published_sizes(self):
        cell = cells.resolve(CELL)
        macs = cell.code.macs_per_token(cell.config, 4096)
        assert macs == {
            "projections": 4 * 2048 * 2048,        # 16.8 M
            "causal_scores": 2 * 2048 * 2048,      # 8.4 M: S / 2 keys
            "router": 2048 * 64,
            "experts": 2 * 3 * 2048 * 1024,        # 8 x 16 / 64 = 2 pairs
            "head": 2048 * 50304}                  # 103 M
        per_token = 4 * (16_777_216 + 8_388_608 + 131_072 + 12_582_912) \
            + 103_022_592
        flops = cell.code.flops_per_step(cell.config, cell.job, 1)
        assert flops == 6 * per_token * 4096
        assert flops == pytest.approx(6.255e12, rel=1e-3)
        # twice the rows, twice the FLOPs
        assert cell.code.flops_per_step(cell.config, cell.job, 2) == (
            2 * flops)


class TestReaders:
    """A made-up trace of one device and two steps, as
    ``test_benchmark_program_spans.py`` makes them."""

    HLO = """
  %fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f.1, metadata={op_name="jit(spmd_step)/shard_map/jvp(Olmoe)/layer_0/attention/query/dot_general"}
  %flash_attention.3 = bf16[8]{0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(spmd_step)/shard_map/jvp(Olmoe)/layer_0/attention/jit(flash_attention)/hvd.attn.fwd/flash_attention/pallas_call"}
  %fusion.2 = f32[8]{0} fusion(%q), kind=kLoop, calls=%f.2, metadata={op_name="jit(spmd_step)/shard_map/jvp(Olmoe)/layer_0/moe/vmap(hvd.moe.route)/dot_general"}
  %gather.4 = bf16[8]{0} gather(%q), metadata={op_name="jit(spmd_step)/shard_map/jvp(Olmoe)/layer_0/moe/vmap(hvd.moe.dispatch)/gather"}
  %fusion.5 = bf16[8]{0} fusion(%g), kind=kOutput, calls=%f.5, metadata={op_name="jit(spmd_step)/shard_map/jvp(Olmoe)/layer_0/moe/vmap(hvd.moe.experts)/ecd,edh->ech/dot_general"}
  %scatter.6 = f32[8]{0} scatter(%g), metadata={op_name="jit(spmd_step)/shard_map/jvp(Olmoe)/layer_0/moe/vmap(hvd.moe.combine)/scatter-add"}
  %fusion.7 = bf16[8]{0} fusion(%g), kind=kOutput, calls=%f.7, metadata={op_name="jit(spmd_step)/shard_map/transpose(jvp(Olmoe))/layer_0/moe/vmap(hvd.moe.experts)/ech,ehd->ecd/dot_general"}
  %flash_attention.8 = bf16[8]{0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(spmd_step)/shard_map/transpose(jvp(Olmoe))/layer_0/attention/jit(flash_attention)/hvd.attn.bwd/flash_attention/pallas_call"}
  %flash_attention.9 = bf16[8]{0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(spmd_step)/shard_map/transpose(jvp(Olmoe))/layer_0/attention/jit(flash_attention)/hvd.attn.bwd/flash_attention/pallas_call"}
  %fusion.10 = f32[8]{0} fusion(%s), kind=kLoop, calls=%f.10, metadata={op_name="jit(spmd_step)/shard_map/hvd.optimizer/add"}
"""
    OPS = [
        Op("fusion.1", "fusion", 0.0, 1.0),
        Op("flash_attention.3", "custom-call", 1.0, 1.5),
        Op("fusion.2", "fusion", 1.5, 1.75),
        Op("gather.4", "gather", 1.75, 2.0),
        Op("fusion.5", "fusion", 2.0, 4.0),
        Op("scatter.6", "scatter", 4.0, 4.5),
        Op("fusion.7", "fusion", 4.5, 7.5),
        Op("flash_attention.8", "custom-call", 7.5, 8.5),
        Op("flash_attention.9", "custom-call", 8.5, 9.5),
        Op("fusion.10", "fusion", 9.5, 10.0),
    ]
    TRACE = Trace({0: OPS}, {0: []}, [], (0.0, 10.0))

    @pytest.fixture()
    def run(self, monkeypatch):
        import horovod_tpu as hvd

        monkeypatch.setattr(hvd.profiler, "step_texts", lambda: [self.HLO])
        return types.SimpleNamespace(
            trace=self.TRACE, steps=2, peak=PEAK, call_s=[0.001],
            cell=cells.resolve(CELL))

    def test_experts_and_dispatch_read_the_programs_scopes(self, run,
                                                           capsys):
        assert reader("moe_experts_ms").read(
            run, parameters("moe_experts_ms")) == pytest.approx(2500.0)
        said = capsys.readouterr().out
        # 4 layers x 16 x 640 slots x 3 projections x 3 passes x 2 x 2048 x
        # 1024 = 1.546 TFLOP a step, empty slots and all
        assert "the slots' 1.546 TFLOP a step in 2500.000 ms" in said
        assert reader("moe_dispatch_ms").read(
            run, parameters("moe_dispatch_ms")) == pytest.approx(
                (0.25 + 0.25 + 0.5) / 2 * 1e3)
        assert ("hvd.moe.route 125.000 ms, hvd.moe.dispatch 125.000 ms, "
                "hvd.moe.combine 250.000 ms") in capsys.readouterr().out

    def test_slot_flops_count_every_slot(self):
        cell = cells.resolve(CELL)
        assert reader("moe_experts_ms").slot_flops_per_step(
            cell.config, cell.job) == (
                4 * 16 * 640 * 3 * 3 * 2 * 2048 * 1024)

    def test_the_kernels_time_and_their_causal_roofline(self, run):
        kernel_ms = reader("causal_attn_kernel_ms").read(
            run, parameters("causal_attn_kernel_ms"))
        assert kernel_ms == pytest.approx(2.5 / 2 * 1e3)
        roofline = reader("causal_attn_roofline")
        share = roofline.read(run, parameters("causal_attn_roofline"))
        # 16 slices of 4096 x 128: half of 2 and of 5 products of 2 S^2 D
        forward = 16 * 2 * 4096 * 4096 * 128 / 197e12
        backward = 16 * 5 * 4096 * 4096 * 128 / 197e12
        assert share == pytest.approx(
            100 * 4 * (forward + backward) * 2 / 2.5)

    def test_causal_cost_is_half_the_products_and_all_the_bytes(self):
        causal = reader("causal_attn_roofline")
        full = reader("flash_attn_roofline")
        shape = (16, 4096, 128, 2)
        for name in ("forward_cost", "backward_cost"):
            flops, nbytes = getattr(causal, name)(*shape)
            full_flops, full_bytes = getattr(full, name)(*shape)
            assert (flops, nbytes) == (full_flops / 2, full_bytes)
        seconds, bound = causal.least_seconds(
            causal.forward_cost(*shape), PEAK)
        assert bound == "compute"
        assert seconds == pytest.approx(0.3488e-3, rel=1e-3)

    def test_a_program_without_the_scopes_is_nothing_to_read(
            self, monkeypatch):
        import horovod_tpu as hvd

        before = self.HLO.replace("hvd.moe.", "moe.")
        monkeypatch.setattr(hvd.profiler, "step_texts", lambda: [before])
        run = types.SimpleNamespace(
            trace=self.TRACE, steps=2, peak=PEAK, call_s=[0.001],
            cell=cells.resolve(CELL))
        assert reader("moe_experts_ms").read(
            run, parameters("moe_experts_ms")) is None
        assert reader("moe_dispatch_ms").read(
            run, parameters("moe_dispatch_ms")) is None

    def test_no_device_plane_no_number(self):
        run = types.SimpleNamespace(
            trace=Trace({}, {}, [], (0.0, 1.0)), steps=2, peak=None,
            call_s=[0.001], cell=cells.resolve(CELL))
        for name in ("moe_experts_ms", "moe_dispatch_ms",
                     "causal_attn_kernel_ms", "causal_attn_roofline"):
            assert reader(name).read(run, parameters(name)) is None


def test_the_toy_cell_through_run_py_on_the_cpu(tmp_path, tmp_path_factory):
    copy = tmp_path / "benchmark"
    shutil.copytree(BENCHMARK_DIR, copy,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO_ROOT, "BENCHMARK.json"), tmp_path)
    listed = json.loads((copy / "rehearsal.json").read_text())
    listed["workloads"].append({
        "name": "rehearsal-olmoe_dp1", "config": "rehearsal-olmoe",
        "traffic": "rehearsal-olmoe_dp1", "chips": 1})
    (copy / "rehearsal.json").write_text(json.dumps(listed))
    cache = tmp_path_factory.mktemp("compile-cache")
    proc = run_cell("rehearsal-olmoe_dp1", trace=0, cache=cache,
                    root=str(tmp_path), seed=2147483650)
    result = result_of(proc)
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert set(result["metrics"]) == {"step_ms", "setup_s"}
    assert "27 leaves, 2 rows a step" in proc.stdout
    for check in ("loss_vs_reference", "gradient_norms_vs_reference",
                  "loss_after_warmup", "kernels_in_step", "losses_finite"):
        assert f"check {check}: ok" in proc.stdout, proc.stdout[-3000:]
