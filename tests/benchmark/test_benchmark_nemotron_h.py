"""The Nemotron-H configuration and its cell as ``BENCHMARK.json`` lists them
(PR 47 appended one configuration, its one-chip cell, seven per-layer metrics
and the cell's name to the ``workloads`` of the accepted metrics whose
readers find something to read in it): the entries are in the file's form
and listed once, every catalog key is as published or listed as reduced and
no width is among them, the inferences and the tolerances have their
reasons, the FLOPs are hand arithmetic at the published sizes, the toy cell
goes through ``run.py`` on the CPU (in a temporary copy of the benchmark
whose ``rehearsal.json`` has gained the cell), the seven readers read a
made-up trace, and the rooflines' counts are the layers' and know nothing of
a chunk or a tile. Nothing here holds a cell to a place in its list or the
lists to a length."""

import json
import os
import shutil
import types

import pytest

import cells
from conftest import BENCHMARK_DIR, REPO_ROOT
from test_benchmark_rehearsal import result_of, run_cell
from trace_reduce import Op, Trace

CONFIG = "nemotron-3-nano-30b-a3b"
CELL = "nemotron-3-nano-30b-a3b_s8192_e8_dp1"
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
CATALOG = {  # architectures.jsonl's `config`, NVIDIA-Nemotron-3-Nano-30B-A3B
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern": PATTERN, "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = ("ssd_grouped_scan_roofline", "moe_plain_experts_ms",
               "moe_plain_shared_ms", "gqa16_attn_kernel_ms",
               "gqa16_attn_roofline", "ssd_grouped_scan_ms",
               "ssd_grouped_mix_ms")
S = 8192
REPORTS_TOO = (  # accepted metrics whose readers find something here
    "step_trace_lower_s", "hbm_temporaries_gib", "unowned_ms",
    "shared_fusion_ms", "embed_ms", "attn_proj_ms", "norm_ms", "ffn_ms",
    "head_ms", "moe_dispatch_ms")
EVERY_CELLS = ("device_idle_share", "host_call_ms", "compile_s",
               "hbm_buffers_gib", "hbm_setup_peak_gib")


def reader(name):
    return cells.load_code(BENCHMARK_DIR, "layer_metrics", name + ".py")


def parameters(name):
    return cells.load_json(BENCHMARK_DIR, "layer_metrics", name + ".json")


def listed(key, name):
    entry, = [e for e in cells.benchmark()[key] if e["name"] == name]
    return entry


class TestConfiguration:
    def test_every_catalog_key_is_as_published_or_listed_as_reduced(self):
        cell = cells.resolve(CELL)
        entry = listed("configs", CONFIG)
        differs = {key for key, value in CATALOG.items()
                   if cell.config.get(key, "left out") != value}
        assert differs == {"num_hidden_layers", "hybrid_override_pattern",
                           "vocab_size"}
        # experts_here is this repo's key: the catalog's n_routed_experts
        # stays, the router's width
        assert set(cell.config["reduced"]) == set(entry["reduced"]) == (
            differs | {"experts_here"})
        assert entry["source"] in cell.config["source"]
        assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
        # no width is among them
        assert not [key for key in entry["reduced"]
                    if key.endswith(("_dim", "_rank", "_size", "_head",
                                     "_state", "_expand"))
                    and key != "vocab_size"]
        # the source's first nine layers: four M, four E, one *
        assert cell.config["hybrid_override_pattern"] == PATTERN[:9]
        assert cell.config["num_hidden_layers"] == 9
        assert [PATTERN[:9].count(kind) for kind in "ME*"] == [4, 4, 1]
        assert PATTERN[9] == "M"
        assert cell.config["vocab_size"] * 8 == 131072
        assert (cell.config["experts_here"], cell.config["first_expert"],
                cell.config["n_routed_experts"]) == (8, 0, 128)
        assert cell.config["published"]["num_hidden_layers"] == 52
        assert cell.config["published"]["vocab_size"] == 131072
        assert PATTERN in cell.config["published"]["hybrid_override_pattern"]
        for said in ("16 that share each layer", "experts 8 a chip",
                     "split eight ways", "pipeline stages", "sixteenth",
                     "idle share"):
            assert said in cell.config["deployment"], said

    @pytest.mark.parametrize("item", [
        "layers", "attention_has_no_positions", "e_score_correction_bias",
        "auxiliary_loss", "capacity_factor", "chunked_scan", "recomputation",
        "initialisation", "inputs", "optimizer", "parameters"])
    def test_every_inference_is_written_down(self, item):
        said = cells.resolve(CELL).config["assumed"][item]
        assert len(said) > 20 and "TO BE SET" not in said

    def test_every_tolerance_has_its_reason(self):
        correct = cells.resolve(CELL).config["correct"]
        for key in ("loss_rel", "gradient_norm_rel_median",
                    "gradient_norm_rel_worst", "loss_record_rel"):
            assert 0 < correct[key] < 1
        for why in ("loss_rel_why", "gradient_norm_rel_why",
                    "loss_record_rel_why"):
            assert len(correct[why]) > 40
            assert "TO BE SET" not in correct[why]
        # the nine hand-made faults' readings are written down
        for said in ("group 0", "all 4,096 channels", "relu for relu",
                     "a gate added", "2.5 left out", "not renormalised",
                     "shared expert left out", "3 mantissa bits", "RoPE"):
            assert said in correct["gradient_norm_rel_why"], said

    def test_the_cell_is_the_issues(self):
        cell = cells.resolve(CELL)
        assert (cell.chips, cell.measured, cell.rows) == (1, True, 1)
        assert cell.job["seq_len"] == S
        assert S <= cell.config["max_position_embeddings"]
        assert S % cell.config["chunk_size"] == 0
        assert (cell.job["sync_mode"], cell.job["compression"],
                cell.job["reference_block_rows"]) == ("allreduce", "bf16", 1)
        assert len(cell.job["loss_after_warmup"]) >= 10
        assert cell.config["training"]["attention"] == "flash"
        assert cell.config["training"]["compute_dtype"] == "bfloat16"
        assert cell.config["training"]["remat"] is True
        assert cell.code.min_pallas_calls(cell.config) == 3
        assert cell.code.units_per_step(cell.job, 1) == (S, "tokens")
        assert cell.config["capacity_factor"] == 1.25
        built = cell.code.model_config(cell.config)
        assert built.capacity(S) == 480  # ceil(1.25 x 8,192 x 6 / 128)
        assert {e["name"] for e, _, _ in cells.layer_metrics(CELL)} >= {
            *NEW_METRICS, *REPORTS_TOO, *EVERY_CELLS}

    def test_what_is_listed_is_in_the_files_form(self):
        config, cell = listed("configs", CONFIG), listed("workloads", CELL)
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert cell["config"] == CONFIG and cell["traffic"] == CELL
        assert cell["chips"] == 1
        assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
        assert config["source"] == (
            "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"
            "/blob/main/config.json")
        for name in NEW_METRICS:
            assert set(listed("per_layer", name)) == {
                "name", "unit", "better", "source", "layer", "moves",
                "workloads"}

    def test_it_is_listed_once_and_the_quota_holds(self):
        bench = cells.benchmark()
        for key in ("configs", "workloads", "per_layer"):
            names = [entry["name"] for entry in bench[key]]
            assert len(names) == len(set(names))
        configs = [c["name"] for c in bench["configs"]]
        assert configs.index(CONFIG) > configs.index("kimi-linear-48b-a3b")
        workloads = [w["name"] for w in bench["workloads"]]
        assert workloads.index(CELL) > workloads.index("resnet50_b128_dp4")
        metrics = [e["name"] for e in bench["per_layer"]]
        assert metrics.index("moe_shared_ms") < min(
            metrics.index(name) for name in NEW_METRICS)
        # the quota itself: a quarter of the cells may take four chips
        four = [w for w in bench["workloads"] if w["chips"] == 4]
        assert len(four) <= len(bench["workloads"]) // 4
        with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
            assert len(f.read()) < 64 * 1024

    def test_the_accepted_metrics_it_reports_too_list_their_cells(self):
        """Each is an accepted metric with a ``workloads`` list that names
        the cell once, after the cells it named before, and moves an
        end-to-end metric the cell reports. Which further metrics name the
        cell is a later PR's to say."""
        entries = {e["name"]: e for e in cells.benchmark()["per_layer"]}
        for name in REPORTS_TOO:
            cells_of = entries[name]["workloads"]
            assert cells_of.count(CELL) == 1
            assert cells_of.index(CELL) > 0  # after those it named before
            assert entries[name]["moves"] in ("step_ms", "hbm_gib", "setup_s")

    def test_the_new_metrics_are_reported_in_this_cell(self):
        for name in NEW_METRICS:
            entry = listed("per_layer", name)
            assert entry["workloads"].count(CELL) == 1
            assert entry["moves"] == "step_ms"
            assert entry["source"] == "device_trace"
        for name in ("ssd_grouped_scan_roofline", "gqa16_attn_roofline"):
            assert listed("per_layer", name)["unit"] == "%"
            assert listed("per_layer", name)["better"] == "higher"
        layers = {name: listed("per_layer", name)["layer"]
                  for name in NEW_METRICS}
        assert layers == {
            "ssd_grouped_scan_roofline": "kernels",
            "gqa16_attn_kernel_ms": "kernels",
            "gqa16_attn_roofline": "kernels",
            "ssd_grouped_scan_ms": "kernels",
            "ssd_grouped_mix_ms": "state_space",
            "moe_plain_experts_ms": "moe", "moe_plain_shared_ms": "moe"}

    def test_the_model_is_built_at_the_published_widths(self):
        from horovod_tpu.models import nemotron_h

        cell = cells.resolve(CELL)
        built = cell.code.model_config(cell.config)
        assert built == nemotron_h.NemotronHConfig(
            vocab_size=16384, num_layers=9,
            hybrid_override_pattern="MEMEM*EME", experts_here=8)
        assert built.kinds == tuple("MEMEM*EME")

    def test_parameters_are_what_the_file_says(self):
        import jax
        import numpy as np

        cell = cells.resolve(CELL)
        shapes = jax.eval_shape(
            lambda key: cell.code.init_params(cell.config, cell.job, key),
            jax.random.PRNGKey(0))
        leaves = jax.tree.leaves(shapes)
        assert len(leaves) == 68
        assert sum(int(np.prod(leaf.shape)) for leaf in leaves) == 666962944
        assert "666,962,944 in 68 leaves" in cell.config["assumed"][
            "parameters"]
        assert shapes["layer_0"]["mamba"]["in_proj"]["kernel"].shape == (
            2688, 10304)
        assert shapes["layer_5"]["attention"]["key"]["kernel"].shape == (
            2688, 256)
        assert shapes["layer_8"]["moe"]["experts_down"].shape == (
            8, 1856, 2688)
        assert "experts_gate" not in shapes["layer_8"]["moe"]
        assert shapes["lm_head"].shape == (2688, 16384)

    def test_the_batch_is_rows_of_ids_from_the_slice(self):
        import jax

        cell = cells.resolve(CELL)
        batch = cell.code.make_batch(cell.config, dict(cell.job, seq_len=512),
                                     jax.random.PRNGKey(2147483650), 3)
        assert batch.shape == (3, 513)
        assert 0 <= int(batch.min()) and int(batch.max()) < 16384

    def test_flops_are_hand_arithmetic_at_the_published_sizes(self):
        cell = cells.resolve(CELL)
        macs = cell.code.macs_per_token(cell.config, S)
        assert macs == {
            "mamba_projections": 2688 * 10304 + 4096 * 2688,     # 38.71 M
            "short_conv": 4 * 6144,
            "recurrence": 3 * 64 * 64 * 128,                     # 1.57 M
            "attention_projections": 2 * 2688 * (4096 + 256),    # 23.40 M
            "causal_scores": 2 * (S / 2) * 4096,                 # 33.55 M
            "router": 2688 * 128,
            "shared_expert": 2 * 2688 * 3712,                    # 19.96 M
            "routed_experts": 0.375 * 2 * 2688 * 1856,  # 6 x 8 / 128 pairs
            "head": 2688 * 16384}
        mamba = macs["mamba_projections"] + macs["short_conv"] + macs[
            "recurrence"]
        experts = macs["router"] + macs["shared_expert"] + macs[
            "routed_experts"]
        attention = macs["attention_projections"] + macs["causal_scores"]
        per_token = 4 * mamba + 4 * experts + attention + macs["head"]
        flops = cell.code.flops_per_step(cell.config, cell.job, 1)
        assert flops == 6 * per_token * S
        # the issue's count: 358 M multiply-adds a token, 17.6 TFLOP a
        # step, the routed experts 4% of it, eight of nine layers the new
        # kinds
        assert per_token == pytest.approx(358e6, rel=0.01)
        assert flops == pytest.approx(1.76e13, rel=0.01)
        assert 6 * 4 * macs["routed_experts"] * S == pytest.approx(
            0.04 * flops, rel=0.1)
        assert 4 * (mamba + experts) / per_token == pytest.approx(
            0.72, abs=0.01)
        assert cell.code.flops_per_step(cell.config, cell.job, 2) == (
            2 * flops)


class TestReaders:
    """A made-up trace of one device and two steps: an ``M`` layer's
    projection, convolution, scan (a fusion and the loop that carries the
    states, whose own event covers the event inside it) and gate forward,
    the ``*`` layer's forward kernel, an ``E`` layer's experts and shared
    expert; then the backward pass with the recomputed forward, the ``*``
    layer's two backward kernels among it."""

    STACK = "jit(spmd_step)/shard_map/"
    FWD = STACK + "jvp(NemotronH)/layer_0/hvd.block.attn_proj/mamba/"
    AGAIN = (STACK + "transpose(jvp(NemotronH))/rematted_computation/"
             "layer_0/hvd.block.attn_proj/mamba/")
    BWD = (STACK + "transpose(jvp(NemotronH))/layer_0/hvd.block.attn_proj/"
           "mamba/")
    ATTN = "layer_5/hvd.block.attn_proj/attention/"
    FFN = "layer_1/hvd.block.ffn/"
    HLO = f"""
  %fusion.1 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f.1, metadata={{op_name="{FWD}in_proj/dot_general"}}
  %fusion.2 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f.2, metadata={{op_name="{FWD}hvd.ssm.conv/mul"}}
  %fusion.3 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f.3, metadata={{op_name="{FWD}hvd.ssm.scan/exp"}}
  %while.4 = f32[8]{{0}} while(%p), condition=%c.4, body=%b.4, metadata={{op_name="{FWD}hvd.ssm.scan/while"}}
  %fusion.5 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f.5, metadata={{op_name="{FWD}hvd.ssm.scan/while/body/mul"}}
  %fusion.6 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f.6, metadata={{op_name="{FWD}hvd.ssm.gate/mul"}}
  %flash_attention.7 = bf16[8]{{0}} custom-call(%p), custom_call_target="tpu_custom_call", metadata={{op_name="{STACK}jvp(NemotronH)/{ATTN}hvd.attn.fwd/flash_attention"}}
  %fusion.8 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f.8, metadata={{op_name="{STACK}jvp(NemotronH)/{FFN}moe/vmap(hvd.moe.experts)/ecd,edh->ech/dot_general"}}
  %fusion.9 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f.9, metadata={{op_name="{STACK}jvp(NemotronH)/{FFN}hvd.moe.shared/shared/up/dot_general"}}
  %fusion.10 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f.10, metadata={{op_name="{AGAIN}hvd.ssm.scan/exp"}}
  %fusion.11 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f.11, metadata={{op_name="{BWD}transpose(jvp(hvd.ssm.scan))/dot_general"}}
  %flash_attention.12 = bf16[8]{{0}} custom-call(%p), custom_call_target="tpu_custom_call", metadata={{op_name="{STACK}transpose(jvp(NemotronH))/{ATTN}hvd.attn.bwd/flash_attention"}}
  %flash_attention.13 = bf16[8]{{0}} custom-call(%p), custom_call_target="tpu_custom_call", metadata={{op_name="{STACK}transpose(jvp(NemotronH))/{ATTN}hvd.attn.bwd/flash_attention"}}
  %fusion.14 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f.14, metadata={{op_name="{STACK}transpose(jvp(NemotronH))/{FFN}moe/transpose(jvp(vmap(hvd.moe.experts)))/ech,ehd->ecd/dot_general"}}
  %fusion.15 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f.15, metadata={{op_name="{STACK}transpose(jvp(NemotronH))/{FFN}transpose(jvp(hvd.moe.shared))/shared/up/dot_general"}}
  %fusion.16 = f32[8]{{0}} fusion(%s), kind=kLoop, calls=%f.16, metadata={{op_name="{STACK}hvd.optimizer/add"}}
"""
    OPS = [
        Op("fusion.1", "fusion", 0.0, 1.0),
        Op("fusion.2", "fusion", 1.0, 1.25),            # conv
        Op("fusion.3", "fusion", 1.25, 2.0),            # scan
        Op("while.4", "while", 2.0, 3.0),               # scan: the loop
        Op("fusion.5", "fusion", 2.25, 2.75),           # scan: inside it
        Op("fusion.6", "fusion", 3.0, 3.5),             # gate
        Op("flash_attention.7", "custom-call", 3.5, 4.5),   # forward
        Op("fusion.8", "fusion", 4.5, 5.25),            # experts
        Op("fusion.9", "fusion", 5.25, 5.75),           # shared expert
        Op("fusion.10", "fusion", 5.75, 6.5),           # scan, recomputed
        Op("fusion.11", "fusion", 6.5, 8.5),            # scan, backward
        Op("flash_attention.12", "custom-call", 8.5, 9.5),    # dq
        Op("flash_attention.13", "custom-call", 9.5, 11.0),   # dkv
        Op("fusion.14", "fusion", 11.0, 12.5),          # experts, backward
        Op("fusion.15", "fusion", 12.5, 13.5),          # shared, backward
        Op("fusion.16", "fusion", 13.5, 14.0),
    ]
    TRACE = Trace({0: OPS}, {0: []}, [], (0.0, 14.0))
    SCAN_S = 0.75 + 1.0 + 0.75 + 2.0    # the loop's inside counted once
    ATTN_S = 1.0 + 1.0 + 1.5
    EXPERTS_S = 0.75 + 1.5
    SHARED_S = 0.5 + 1.0

    @pytest.fixture()
    def run(self, monkeypatch):
        import horovod_tpu as hvd

        monkeypatch.setattr(hvd.profiler, "step_texts", lambda: [self.HLO])
        return types.SimpleNamespace(
            trace=self.TRACE, steps=2, peak=PEAK, call_s=[0.001],
            cell=cells.resolve(CELL))

    def test_scan_and_mix_are_granites_readers_under_this_cells_names(
            self, run, capsys):
        assert reader("ssd_grouped_scan_ms").read(
            run, parameters("ssd_grouped_scan_ms")) == pytest.approx(
                self.SCAN_S / 2 * 1e3)
        # the plain sum counts the loop's inside twice, and says so
        assert f"{(self.SCAN_S + 0.5) / 2 * 1e3:.3f}" in (
            capsys.readouterr().out)
        assert reader("ssd_grouped_mix_ms").read(
            run, parameters("ssd_grouped_mix_ms")) == pytest.approx(
                (0.25 + 0.5) / 2 * 1e3)
        said = capsys.readouterr().out
        assert "hvd.ssm.conv 125.000 ms" in said
        assert "hvd.ssm.gate 250.000 ms" in said
        for name in ("scan", "mix"):  # the same scopes as Granite's
            assert parameters(f"ssd_grouped_{name}_ms")["scopes"] == (
                parameters(f"ssd_{name}_ms")["scopes"])

    def test_the_grouped_roofline_counts_the_recurrence_at_eight_groups(
            self, run, capsys):
        # a layer and pass: forward 3 x 64 x 64 x 128 multiply-adds a token
        # against x and y (4,096 each) and eight B and C (1,024 each) in
        # bfloat16 and the 64 steps in float32
        forward_flops = 2 * 3 * 64 * 64 * 128 * S
        forward_bytes = S * ((2 * 4096 + 2 * 1024) * 2 + 64 * 4)
        backward_bytes = S * ((3 * 4096 + 4 * 1024) * 2 + 2 * 64 * 4)
        granite = reader("ssd_scan_roofline")
        assert granite.forward_cost(1, S, 64, 64, 128, 8, 2) == (
            forward_flops, forward_bytes)
        assert granite.backward_cost(1, S, 64, 64, 128, 8, 2) == (
            2 * forward_flops, backward_bytes)
        least = 4 * (max(forward_flops / 197e12, forward_bytes / 819e9)
                     + max(2 * forward_flops / 197e12,
                           backward_bytes / 819e9))
        assert least == pytest.approx(2.16e-3, rel=0.01)
        assert reader("ssd_grouped_scan_roofline").read(
            run, parameters("ssd_grouped_scan_roofline")) == pytest.approx(
                100 * least / (self.SCAN_S / 2))
        said = capsys.readouterr().out
        assert "8 groups of B and C" in said and "4 layers" in said
        assert "memory-bound) + " in said

    def test_the_attention_kernels_are_told_by_name(self, run):
        assert reader("gqa16_attn_kernel_ms").read(
            run, parameters("gqa16_attn_kernel_ms")) == pytest.approx(
                self.ATTN_S / 2 * 1e3)

    def test_the_attention_roofline_counts_32_query_and_2_key_slices(
            self, run, capsys):
        pairs = S * (S + 1) // 2
        window = reader("window_attn_roofline")
        forward = window.forward_cost(32, 2, S, 128, 2, pairs)
        backward = window.backward_cost(32, 2, S, 128, 2, pairs)
        assert forward == (
            32 * 2 * 2 * pairs * 128,
            32 * (2 * S * 128 * 2 + 4 * S) + 2 * 2 * S * 128 * 2)
        assert backward == (
            32 * 5 * 2 * pairs * 128,
            32 * (3 * S * 128 * 2 + 12 * S) + 2 * 4 * S * 128 * 2)
        least = forward[0] / 197e12 + backward[0] / 197e12  # compute-bound
        assert least == pytest.approx(9.77e-3, rel=0.01)
        assert reader("gqa16_attn_roofline").read(
            run, parameters("gqa16_attn_roofline")) == pytest.approx(
                100 * least * 2 / self.ATTN_S)
        said = capsys.readouterr().out
        assert "32 query heads on 2" in said and "1 layer(s)" in said
        assert "compute-bound) + " in said

    def test_the_experts_are_their_scope_at_two_products_a_slot(
            self, run, capsys):
        assert reader("moe_plain_experts_ms").read(
            run, parameters("moe_plain_experts_ms")) == pytest.approx(
                self.EXPERTS_S / 2 * 1e3)
        # 4 E layers x 8 experts x 480 slots x 2 products, thrice
        flops = 4 * 8 * 480 * 2 * 3 * 2 * 2688 * 1856
        assert f"{flops / 1e12:.3f} TFLOP" in capsys.readouterr().out

    def test_the_shared_expert_is_its_scope(self, run, capsys):
        assert reader("moe_plain_shared_ms").read(
            run, parameters("moe_plain_shared_ms")) == pytest.approx(
                self.SHARED_S / 2 * 1e3)
        # 4 E layers x 2 x 2,688 x 3,712 multiply-adds a token, thrice
        flops = 6 * 4 * 2 * 2688 * 3712 * S
        assert f"{flops / 1e12:.3f} TFLOP" in capsys.readouterr().out

    def test_the_counts_know_nothing_of_the_chunk(self, run):
        """Another chunk in the configuration: the same share. The count is
        the layer's, not an implementation's."""
        roofline, params = reader("ssd_grouped_scan_roofline"), parameters(
            "ssd_grouped_scan_roofline")
        want = roofline.read(run, params)
        for chunk in (64, 256):
            other = types.SimpleNamespace(**{
                **vars(run), "cell": types.SimpleNamespace(
                    config=dict(run.cell.config, chunk_size=chunk),
                    job=run.cell.job)})
            assert roofline.read(other, params) == want

    def test_the_least_times_cannot_be_undercut(self):
        """The scan's passes are memory-bound on the v5e (reading the
        operands and writing the result once, which no implementation goes
        below), and the attention kernels' least time counts exactly the
        causal pairs: neither share can pass 100%."""
        granite = reader("ssd_scan_roofline")
        for cost in (granite.forward_cost, granite.backward_cost):
            assert granite.least_seconds(
                cost(1, S, 64, 64, 128, 8, 2), PEAK)[1] == "memory"
        window = reader("window_attn_roofline")
        assert window.visible_pairs(S, None) == S * (S + 1) // 2
        computed_tiles = 16 * 17 // 2 * 512 * 512  # what the kernels mask
        assert window.visible_pairs(S, None) < computed_tiles

    def test_the_names_are_the_programs(self):
        from horovod_tpu import attribution
        from horovod_tpu.ops import attention

        prefix = attribution.SCOPE_PREFIX
        for name in ("ssd_grouped_scan_roofline", "ssd_grouped_scan_ms"):
            assert parameters(name)["scopes"] == [
                prefix + attribution.SCOPE_SSM_SCAN]
        assert parameters("ssd_grouped_mix_ms")["scopes"] == [
            prefix + attribution.SCOPE_SSM_CONV,
            prefix + attribution.SCOPE_SSM_GATE]
        assert parameters("moe_plain_experts_ms")["scopes"] == [
            prefix + attribution.SCOPE_MOE_EXPERTS]
        assert parameters("moe_plain_shared_ms")["scopes"] == [
            prefix + attribution.SCOPE_MOE_SHARED]
        for name in ("gqa16_attn_kernel_ms", "gqa16_attn_roofline"):
            assert attention.KERNEL_NAME in parameters(name)["kernel_names"]

    def test_a_program_without_the_scopes_reads_nothing(self, monkeypatch):
        """What a program without the scopes and the kernels would give: the
        metrics are left out and nothing raises."""
        import horovod_tpu as hvd

        before = self.HLO.replace("hvd.ssm.", "ssm_").replace(
            "hvd.moe.", "moe_")
        monkeypatch.setattr(hvd.profiler, "step_texts", lambda: [before])
        ops = [op for op in self.OPS if op.opcode != "custom-call"]
        run = types.SimpleNamespace(
            trace=Trace({0: ops}, {0: []}, [], (0.0, 14.0)), steps=2,
            peak=PEAK, call_s=[0.001], cell=cells.resolve(CELL))
        for name in NEW_METRICS:
            assert reader(name).read(run, parameters(name)) is None

    def test_no_device_plane_no_number(self):
        run = types.SimpleNamespace(
            trace=Trace({}, {}, [], (0.0, 1.0)), steps=2, peak=None,
            call_s=[0.001], cell=cells.resolve(CELL))
        for name in NEW_METRICS:
            assert reader(name).read(run, parameters(name)) is None


def test_the_toy_cell_through_run_py_on_the_cpu(tmp_path, tmp_path_factory):
    copy = tmp_path / "benchmark"
    shutil.copytree(BENCHMARK_DIR, copy,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO_ROOT, "BENCHMARK.json"), tmp_path)
    rehearsed = json.loads((copy / "rehearsal.json").read_text())
    rehearsed["workloads"].append({
        "name": "rehearsal-nemotron-h_dp1",
        "config": "rehearsal-nemotron-h",
        "traffic": "rehearsal-nemotron-h_dp1", "chips": 1})
    (copy / "rehearsal.json").write_text(json.dumps(rehearsed))
    cache = tmp_path_factory.mktemp("compile-cache")
    proc = run_cell("rehearsal-nemotron-h_dp1", trace=0, cache=cache,
                    root=str(tmp_path), seed=2147483650)
    result = result_of(proc)
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert set(result["metrics"]) == {"step_ms", "setup_s"}
    assert "38 leaves, 2 rows a step" in proc.stdout
    for check in ("loss_vs_reference", "gradient_norms_vs_reference",
                  "loss_after_warmup", "kernels_in_step", "losses_finite"):
        assert f"check {check}: ok" in proc.stdout, proc.stdout[-3000:]


def test_a_checkout_that_lacks_the_cell_stops_at_once(monkeypatch):
    """Where ``BENCHMARK.json`` does not list the cell, as the parent's
    does not, ``run.py`` says so and runs nothing."""
    bench = cells.benchmark()
    without = dict(bench, workloads=[
        w for w in bench["workloads"] if w["name"] != CELL])
    monkeypatch.setattr(cells, "benchmark", lambda: without)
    with pytest.raises(SystemExit, match="no cell named"):
        cells.resolve(CELL)


def test_a_program_that_lacks_the_model_stops_before_any_device_work(
        monkeypatch):
    """The driver lays this PR's benchmark files over the parent's
    checkout, whose ``horovod_tpu.models`` has no ``nemotron_h``: the first
    thing the harness asks of the configuration's code raises
    ``ImportError``, in ``set_up`` before any weight is made, so the run
    ends at once with a non-zero exit code."""
    import sys

    import horovod_tpu.models as models

    cell = cells.resolve(CELL)
    monkeypatch.delattr(models, "nemotron_h")
    monkeypatch.setitem(sys.modules, "horovod_tpu.models.nemotron_h", None)
    with pytest.raises(ImportError):
        cell.code.init_params(cell.config, cell.job, None)
    with pytest.raises(ImportError):
        cell.code.loss_fn(cell.config, cell.job)
