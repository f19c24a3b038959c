"""The JoyAI-LLM Flash configuration and its cell as ``BENCHMARK.json`` lists
them (PR 52 appended one configuration, its one-chip cell, five per-layer
metrics and the cell's name to the ``workloads`` of the accepted metrics
whose readers find something to read in it): the entries are in the file's
form and listed once, every catalog key is as published or listed as reduced
and no width is among them, the inferences and the tolerances have their
reasons, the cut's parameters and the FLOPs are hand arithmetic at the
published sizes, the toy cell goes through ``run.py`` on the CPU (in a
temporary copy of the benchmark whose ``rehearsal.json`` has gained the
cell), the five readers read a made-up trace, and the roofline's count is the
layers' and knows nothing of a tile. Everything here is by membership:
nothing holds a cell or a metric to a place in its list or a list to a
length, so the next cell fails no case of it."""

import json
import os
import shutil
import types

import pytest

import cells
from conftest import BENCHMARK_DIR, REPO_ROOT
from test_benchmark_rehearsal import result_of, run_cell
from trace_reduce import Op, Trace

CONFIG = "joyai-llm-flash"
CELL = "joyai-llm-flash_s8192_e16_dp1"
CATALOG = {  # architectures.jsonl's `config`, JoyAI-LLM-Flash
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = {  # name -> layer
    "mla_rope_attn_kernel_ms": "kernels", "mla_rope_attn_roofline": "kernels",
    "mla_rope_ms": "model_blocks", "mtp_ms": "model_blocks",
    "moe_e768_experts_ms": "moe"}
S = 8192
REPORTS_TOO = (  # accepted metrics whose readers find something here
    "moe_dispatch_ms", "step_trace_lower_s", "hbm_temporaries_gib",
    "unowned_ms", "shared_fusion_ms", "embed_ms", "attn_proj_ms", "norm_ms",
    "ffn_ms", "head_ms")
NOT_THIS_CELLS = (  # held to other cells by their tests, or another width
    "mla_attn_kernel_ms", "mla_attn_roofline", "moe_shared_ms", "kda_scan_ms",
    "kda_scan_roofline", "kda_mix_ms", "moe_experts_ms")


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
        assert differs == {"num_hidden_layers", "vocab_size"}
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
        # the dense layer and the four that follow it; an eighth of the rows
        assert cell.config["num_hidden_layers"] == 5 == (
            cell.config["first_k_dense_replace"] + 4)
        assert cell.config["vocab_size"] * 8 == 129280
        assert (cell.config["experts_here"], cell.config["first_expert"],
                cell.config["n_routed_experts"]) == (16, 0, 256)
        assert cell.config["published"]["num_hidden_layers"] == 40
        assert cell.config["published"]["vocab_size"] == 129280
        for said in ("16 that share each layer", "experts 16 a chip",
                     "split eight ways", "pipeline stages", "sixteenth",
                     "one layer of six", "idle share"):
            assert said in cell.config["deployment"], said

    @pytest.mark.parametrize("item", [
        "layers", "mtp_loss_weight", "mtp_input",
        "rotary_split_in_the_program", "latent_attention_in_training",
        "capacity_factor", "e_score_correction_bias", "auxiliary_loss",
        "recomputation", "initialisation", "inputs", "optimizer",
        "parameters"])
    def test_every_inference_is_written_down(self, item):
        said = cells.resolve(CELL).config["assumed"][item]
        assert len(said) > 20 and "TO BE SET" not in said

    def test_the_assumed_sizes_are_the_issues(self):
        config = cells.resolve(CELL).config
        assert config["mtp_loss_weight"] == 0.3
        assert config["capacity_factor"] == 1.25
        assert "before the final norm" in config["assumed"]["mtp_input"]
        assert "embedding's first" in config["assumed"]["mtp_input"]
        assert "held at its initial zero" in config["assumed"][
            "e_score_correction_bias"]

    def test_every_tolerance_has_its_reason(self):
        correct = cells.resolve(CELL).config["correct"]
        for key in ("loss_rel", "gradient_norm_rel_median",
                    "gradient_norm_rel_worst", "loss_record_rel"):
            assert 0 < correct[key] < 1
        for why in ("loss_rel_why", "gradient_norm_rel_why",
                    "loss_record_rel_why"):
            assert len(correct[why]) > 40
            assert "TO BE SET" not in correct[why]
        # the control and the twelve hand-made faults' readings are there
        for said in ("3 mantissa bits", "turn left out", "all 192 lanes",
                     "half-split pairs", "q's norm left out", "128^-1/2",
                     "2.5 left out", "not renormalised",
                     "shared expert left out", "t_i+1 for t_i+2",
                     "Emb(t_i) for Emb(t_i+1)", "lambda left out",
                     "a head leaf of its own"):
            assert said in correct["gradient_norm_rel_why"], said

    def test_the_cell_is_the_issues(self):
        cell = cells.resolve(CELL)
        assert (cell.chips, cell.measured, cell.rows) == (1, True, 1)
        assert cell.job["seq_len"] == S
        assert S <= cell.config["max_position_embeddings"]
        assert (cell.job["sync_mode"], cell.job["compression"],
                cell.job["reference_block_rows"]) == ("allreduce", "bf16", 1)
        assert len(cell.job["loss_after_warmup"]) >= 10
        assert cell.config["training"] == {
            "learning_rate": 0.0001, "compute_dtype": "bfloat16",
            "attention": "flash", "remat": True}
        assert cell.code.min_pallas_calls(cell.config) == 18
        assert cell.code.units_per_step(cell.job, 1) == (S, "tokens")
        built = cell.code.model_config(cell.config)
        assert built.capacity(S) == 320  # ceil(1.25 x 8,192 x 8 / 256)
        reported = {e["name"] for e, _, _ in cells.layer_metrics(CELL)}
        assert reported >= {*NEW_METRICS, *REPORTS_TOO}
        assert not reported & set(NOT_THIS_CELLS)

    def test_what_is_listed_is_in_the_files_form(self):
        config, cell = listed("configs", CONFIG), listed("workloads", CELL)
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert cell["config"] == CONFIG and cell["traffic"] == CELL
        assert cell["chips"] == 1
        assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
        for said in ("sixteenth", "2% of FLOPs", "attention weighs more",
                     "1 of 6, not 1 of 41", "5 of 40 layers", "idle share"):
            assert said in cell["why"], said
        assert config["source"] == (
            "https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/"
            "config.json")
        for name in NEW_METRICS:
            assert set(listed("per_layer", name)) == {
                "name", "unit", "better", "source", "layer", "moves",
                "workloads"}

    def test_it_is_listed_once_and_the_quota_holds(self):
        bench = cells.benchmark()
        for key in ("configs", "workloads", "per_layer"):
            names = [entry["name"] for entry in bench[key]]
            assert len(names) == len(set(names))
        pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
        assert len(pairs) == len(set(pairs))
        # the quota itself: a quarter of the cells may take four chips
        four = [w for w in bench["workloads"] if w["chips"] == 4]
        assert len(four) <= max(1, len(bench["workloads"]) // 4)
        with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
            assert len(f.read()) < 64 * 1024

    def test_the_metrics_it_reports_list_the_cell(self):
        """Each accepted metric the cell joins names it once and moves an
        end-to-end metric the cell reports; each new metric is this cell's,
        from the device trace, and moves ``step_ms``."""
        entries = {e["name"]: e for e in cells.benchmark()["per_layer"]}
        for name in REPORTS_TOO:
            assert entries[name]["workloads"].count(CELL) == 1
            assert entries[name]["moves"] in ("step_ms", "hbm_gib", "setup_s")
        for name, layer in NEW_METRICS.items():
            entry = entries[name]
            assert CELL in entry["workloads"]
            assert (entry["moves"], entry["source"], entry["layer"]) == (
                "step_ms", "device_trace", layer)
        roofline = entries["mla_rope_attn_roofline"]
        assert (roofline["unit"], roofline["better"]) == ("%", "higher")

    def test_the_model_is_built_at_the_published_widths(self):
        from horovod_tpu.models import joyai_flash

        cell = cells.resolve(CELL)
        assert cell.code.model_config(cell.config) == (
            joyai_flash.JoyAIFlashConfig(vocab_size=16160, num_layers=5,
                                         experts_here=16))
        assert cell.code.kinds(cell.config) == [("mla", "dense")] + [
            ("mla", "experts")] * 5   # the module's layer last

    def test_parameters_are_what_the_file_says(self):
        import jax
        import numpy as np

        cell = cells.resolve(CELL)
        shapes = jax.eval_shape(
            lambda key: cell.code.init_params(cell.config, cell.job, key),
            jax.random.PRNGKey(0))
        leaves = jax.tree.leaves(shapes)
        assert len(leaves) == 99
        assert sum(int(np.prod(leaf.shape)) for leaf in leaves) == 680439808
        assert "680,439,808 in 99 leaves" in cell.config["assumed"][
            "parameters"]
        # the cut's arithmetic, by hand
        attention = (2048 * 1536 + 1536 + 1536 * 32 * 192 + 2048 * 576 + 512
                     + 512 * 32 * 256 + 32 * 128 * 2048)
        expert = 3 * 2048 * 768
        expert_layer = (attention + 2048 * 256 + expert + 16 * expert
                        + 2 * 2048)
        dense_layer = attention + 3 * 2048 * 7168 + 2 * 2048
        module = 4096 * 2048 + 3 * 2048 + expert_layer
        assert (attention, dense_layer, expert_layer, module) == (
            26347520, 70391808, 107091968, 115486720)
        assert (dense_layer + 4 * expert_layer + module
                + 2 * 16160 * 2048 + 2048) == 680439808
        # at place_state's 20 bytes a parameter, and a layer more
        assert 680439808 * 20 / 2 ** 30 == pytest.approx(12.67, abs=0.01)
        assert (680439808 + expert_layer) * 20 / 2 ** 30 == pytest.approx(
            14.67, abs=0.01)
        assert shapes["layer_4"]["attention"]["q_b"]["kernel"].shape == (
            1536, 6144)
        assert shapes["mtp_layer"]["moe"]["experts_down"].shape == (
            16, 768, 2048)
        assert shapes["lm_head"].shape == (2048, 16160)

    def test_the_batch_is_rows_of_s_plus_2_ids_from_the_slice(self):
        import jax

        cell = cells.resolve(CELL)
        batch = cell.code.make_batch(cell.config, dict(cell.job, seq_len=512),
                                     jax.random.PRNGKey(2147483650), 3)
        assert batch.shape == (3, 514)
        assert 0 <= int(batch.min()) and int(batch.max()) < 16160

    def test_flops_are_hand_arithmetic_at_the_published_sizes(self):
        cell = cells.resolve(CELL)
        macs = cell.code.macs_per_token(cell.config, S)
        assert macs == {
            "mla_projections": (2048 * 1536 + 1536 * 6144 + 2048 * 576
                                + 512 * 8192 + 4096 * 2048),      # 26.35 M
            "causal_scores": (S / 2) * 32 * (192 + 128),          # 41.94 M
            "dense_feed_forward": 3 * 2048 * 7168,
            "router": 2048 * 256,
            "shared_expert": 3 * 2048 * 768,
            "routed_experts": 0.5 * 3 * 2048 * 768,  # 8 x 16 / 256 pairs
            "mtp_projection": 4096 * 2048,
            "head": 2048 * 16160}
        attention = macs["mla_projections"] + macs["causal_scores"]
        experts = macs["router"] + macs["shared_expert"] + macs[
            "routed_experts"]
        module = (macs["mtp_projection"] + attention + experts
                  + macs["head"])
        per_token = (5 * attention + macs["dense_feed_forward"]
                     + 4 * experts + macs["head"] + module)
        flops = cell.code.flops_per_step(cell.config, cell.job, 1)
        assert flops == 6 * per_token * S
        assert cell.code.mtp_flops_per_step(
            cell.config, cell.job, 1) == 6 * module * S
        # the issue's count: 27.9 TFLOP a step, six calls 12.4 (44%), their
        # projections 7.8, the module 5.7 (20%), two head passes 3.3, the
        # dense layer 2.2, shared experts 1.2, routed 0.6
        assert flops == pytest.approx(27.9e12, rel=0.005)
        assert 6 * 6 * macs["causal_scores"] * S == pytest.approx(
            0.44 * flops, rel=0.02)
        assert 6 * 6 * macs["mla_projections"] * S == pytest.approx(
            7.8e12, rel=0.01)
        assert 6 * module * S == pytest.approx(0.20 * flops, abs=0.01 * flops)
        assert 2 * 6 * macs["head"] * S == pytest.approx(3.3e12, rel=0.02)
        assert 5 * 6 * macs["routed_experts"] * S == pytest.approx(
            0.02 * flops, rel=0.05)
        assert cell.code.flops_per_step(cell.config, cell.job, 2) == (
            2 * flops)


class TestReaders:
    """A made-up trace of one device and two steps: a stack layer's rotary
    turn, its forward kernel and its experts; the module's joining
    projection, rotary turn, forward kernel, experts and head; then the
    backward pass with the recomputed forward, the two backward kernels of
    each of the two layers among it, and the optimizer."""

    STACK = "jit(spmd_step)/shard_map/"
    FWD = STACK + "jvp(JoyAIFlash)/"
    BWD = STACK + "transpose(jvp(JoyAIFlash))/"
    ATTN = "hvd.block.attn_proj/attention/"
    KERNEL = "hvd.attn.mla/hvd.attn.{}/flash_attention"
    HLO = f"""
ENTRY %main (p: f32[8]) -> f32[8] {{
  %fusion.1 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f.1, metadata={{op_name="{FWD}layer_1/{ATTN}hvd.mla.rope/mul"}}
  %flash_attention.2 = bf16[8]{{0}} custom-call(%p), custom_call_target="tpu_custom_call", metadata={{op_name="{FWD}layer_1/{ATTN}{KERNEL.format('fwd')}"}}
  %fusion.3 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f.3, metadata={{op_name="{FWD}layer_1/moe/vmap(hvd.moe.experts)/ecd,edh->ech/dot_general"}}
  %fusion.4 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f.4, metadata={{op_name="{FWD}hvd.mtp/hvd.block.embed/mtp_proj/dot_general"}}
  %fusion.5 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f.5, metadata={{op_name="{FWD}hvd.mtp/mtp_layer/{ATTN}hvd.mla.rope/mul"}}
  %flash_attention.6 = bf16[8]{{0}} custom-call(%p), custom_call_target="tpu_custom_call", metadata={{op_name="{FWD}hvd.mtp/mtp_layer/{ATTN}{KERNEL.format('fwd')}"}}
  %fusion.7 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f.7, metadata={{op_name="{FWD}hvd.mtp/mtp_layer/moe/vmap(hvd.moe.experts)/ecd,edh->ech/dot_general"}}
  %fusion.8 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f.8, metadata={{op_name="{FWD}hvd.mtp/hvd.block.head/dot_general"}}
  %fusion.9 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f.9, metadata={{op_name="{BWD}transpose(jvp(hvd.mtp))/hvd.block.head/mul"}}
  %fusion.10 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f.10, metadata={{op_name="{BWD}hvd.mtp/rematted_computation/mtp_layer/{ATTN}hvd.mla.rope/mul"}}
  %flash_attention.11 = bf16[8]{{0}} custom-call(%p), custom_call_target="tpu_custom_call", metadata={{op_name="{BWD}hvd.mtp/mtp_layer/{ATTN}{KERNEL.format('bwd')}"}}
  %flash_attention.12 = bf16[8]{{0}} custom-call(%p), custom_call_target="tpu_custom_call", metadata={{op_name="{BWD}hvd.mtp/mtp_layer/{ATTN}{KERNEL.format('bwd')}"}}
  %fusion.13 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f.13, metadata={{op_name="{BWD}hvd.mtp/mtp_layer/moe/transpose(jvp(vmap(hvd.moe.experts)))/ech,ehd->ecd/dot_general"}}
  %flash_attention.14 = bf16[8]{{0}} custom-call(%p), custom_call_target="tpu_custom_call", metadata={{op_name="{BWD}layer_1/{ATTN}{KERNEL.format('bwd')}"}}
  %flash_attention.15 = bf16[8]{{0}} custom-call(%p), custom_call_target="tpu_custom_call", metadata={{op_name="{BWD}layer_1/{ATTN}{KERNEL.format('bwd')}"}}
  %fusion.16 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f.16, metadata={{op_name="{BWD}layer_1/{ATTN}transpose(jvp(hvd.mla.rope))/mul"}}
  %fusion.17 = f32[8]{{0}} fusion(%s), kind=kLoop, calls=%f.17, metadata={{op_name="{STACK}hvd.optimizer/add"}}
}}
"""
    OPS = [
        Op("fusion.1", "fusion", 0.0, 0.25),             # turn
        Op("flash_attention.2", "custom-call", 0.25, 1.25),   # forward
        Op("fusion.3", "fusion", 1.25, 2.0),             # experts
        Op("fusion.4", "fusion", 2.0, 2.5),              # module: eh_proj
        Op("fusion.5", "fusion", 2.5, 2.75),             # module: turn
        Op("flash_attention.6", "custom-call", 2.75, 3.75),   # its forward
        Op("fusion.7", "fusion", 3.75, 4.5),             # its experts
        Op("fusion.8", "fusion", 4.5, 5.0),              # its head pass
        Op("fusion.9", "fusion", 5.0, 5.5),              # its loss, backward
        Op("fusion.10", "fusion", 5.5, 5.75),            # its turn again
        Op("flash_attention.11", "custom-call", 5.75, 6.75),  # its dq
        Op("flash_attention.12", "custom-call", 6.75, 8.25),  # its dkv
        Op("fusion.13", "fusion", 8.25, 9.75),           # its experts, bwd
        Op("flash_attention.14", "custom-call", 9.75, 10.75),   # dq
        Op("flash_attention.15", "custom-call", 10.75, 12.25),  # dkv
        Op("fusion.16", "fusion", 12.25, 12.75),         # turn, backward
        Op("fusion.17", "fusion", 12.75, 13.25),
    ]
    TRACE = Trace({0: OPS}, {0: []}, [], (0.0, 13.25))
    KERNELS_S = 2 * (1.0 + 1.0 + 1.5)
    ROPE_S = 0.25 + 0.25 + 0.25 + 0.5
    MODULE_S = 9.75 - 2.0
    EXPERTS_S = 0.75 + 0.75 + 1.5

    @pytest.fixture()
    def run(self, monkeypatch):
        import horovod_tpu as hvd

        monkeypatch.setattr(hvd.profiler, "step_texts", lambda: [self.HLO])
        return types.SimpleNamespace(
            trace=self.TRACE, steps=2, peak=PEAK, call_s=[0.001],
            cell=cells.resolve(CELL))

    def test_the_kernels_are_kimi_linears_reader_under_this_cells_name(
            self, run):
        assert reader("mla_rope_attn_kernel_ms").read(
            run, parameters("mla_rope_attn_kernel_ms")) == pytest.approx(
                self.KERNELS_S / 2 * 1e3)
        for key in ("kernel_names", "mla_scope"):
            assert parameters("mla_rope_attn_kernel_ms")[key] == parameters(
                "mla_attn_kernel_ms")[key] == parameters(
                    "mla_rope_attn_roofline")[key]

    def test_the_roofline_counts_six_layers_at_kimi_linears_costs(
            self, run, capsys):
        pairs = S * (S + 1) // 2
        kimi = reader("mla_attn_roofline")
        forward = kimi.forward_cost(32, S, 192, 128, 2)
        backward = kimi.backward_cost(32, S, 192, 128, 2)
        assert forward[0] == 32 * 2 * pairs * (192 + 128)
        assert backward[0] == 32 * 2 * pairs * (3 * 192 + 2 * 128)
        least = 6 * (forward[0] + backward[0]) / 197e12   # compute-bound
        assert least == pytest.approx(75.5e-3, rel=0.01)
        assert reader("mla_rope_attn_roofline").read(
            run, parameters("mla_rope_attn_roofline")) == pytest.approx(
                100 * least * 2 / self.KERNELS_S)
        said = capsys.readouterr().out
        assert "6 layers" in said and "compute-bound) + " in said

    def test_the_least_time_cannot_be_undercut(self):
        """The count is of exactly the pairs the causal mask leaves, fewer
        than any tiling computes, and each kernel is in the time once (the
        forward kernel's results are kept): the share cannot pass 100%."""
        kimi = reader("mla_attn_roofline")
        for cost in (kimi.forward_cost, kimi.backward_cost):
            assert kimi.least_seconds(
                cost(32, S, 192, 128, 2), PEAK)[1] == "compute"
        computed_tiles = 16 * 17 // 2 * 512 * 512  # what the kernels mask
        assert S * (S + 1) // 2 < computed_tiles

    def test_the_rotary_split_is_its_owner(self, run):
        assert reader("mla_rope_ms").read(
            run, parameters("mla_rope_ms")) == pytest.approx(
                self.ROPE_S / 2 * 1e3)

    def test_the_module_is_what_holds_its_scope(self, run, capsys):
        assert reader("mtp_ms").read(
            run, parameters("mtp_ms")) == pytest.approx(
                self.MODULE_S / 2 * 1e3)
        said = capsys.readouterr().out
        assert "5.769 of the step's 27.838 model TFLOP, 20.7%" in said

    def test_the_experts_are_their_scope_at_an_experts_own_width(
            self, run, capsys):
        assert reader("moe_e768_experts_ms").read(
            run, parameters("moe_e768_experts_ms")) == pytest.approx(
                self.EXPERTS_S / 2 * 1e3)
        # 5 expert layers x 16 experts x 320 slots x 3 products, thrice
        flops = 5 * 16 * 320 * 3 * 3 * 2 * 2048 * 768
        assert f"{flops / 1e12:.3f} TFLOP" in capsys.readouterr().out

    def test_the_names_are_the_programs(self):
        from horovod_tpu import attribution
        from horovod_tpu.ops import attention

        prefix = attribution.SCOPE_PREFIX
        assert parameters("mla_rope_ms")["owner"] == (
            prefix + attribution.SCOPE_MLA_ROPE)
        assert prefix + attribution.SCOPE_MLA_ROPE in (
            attribution.PHASE_SCOPE_NAMES)
        assert parameters("mtp_ms")["scope"] == (
            prefix + attribution.SCOPE_MTP)
        assert prefix + attribution.SCOPE_MTP not in (
            attribution.PHASE_SCOPE_NAMES + attribution.BLOCK_SCOPE_NAMES)
        assert parameters("moe_e768_experts_ms")["scopes"] == [
            prefix + attribution.SCOPE_MOE_EXPERTS]
        for name in ("mla_rope_attn_kernel_ms", "mla_rope_attn_roofline"):
            assert attention.KERNEL_NAME in parameters(name)["kernel_names"]
            assert parameters(name)["mla_scope"] == (
                prefix + attribution.SCOPE_ATTN_MLA)

    def test_a_program_without_the_scopes_reads_nothing(self, monkeypatch):
        """What a program without the scopes and the kernels would give: the
        metrics are left out and nothing raises."""
        import horovod_tpu as hvd

        before = self.HLO.replace("hvd.mla.rope", "rope").replace(
            "hvd.mtp", "mtp").replace("hvd.moe.", "moe_").replace(
                "hvd.attn.mla/", "")
        monkeypatch.setattr(hvd.profiler, "step_texts", lambda: [before])
        ops = [op for op in self.OPS if op.opcode != "custom-call"]
        run = types.SimpleNamespace(
            trace=Trace({0: ops}, {0: []}, [], (0.0, 13.25)), steps=2,
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
        "name": "rehearsal-joyai-flash_dp1",
        "config": "rehearsal-joyai-flash",
        "traffic": "rehearsal-joyai-flash_dp1", "chips": 1})
    (copy / "rehearsal.json").write_text(json.dumps(rehearsed))
    cache = tmp_path_factory.mktemp("compile-cache")
    proc = run_cell("rehearsal-joyai-flash_dp1", trace=0, cache=cache,
                    root=str(tmp_path), seed=2147483650)
    result = result_of(proc)
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert set(result["metrics"]) == {"step_ms", "setup_s"}
    assert "67 leaves, 2 rows a step" in proc.stdout
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
    checkout, whose ``horovod_tpu.models`` has no ``joyai_flash``: the first
    thing the harness asks of the configuration's code raises
    ``ImportError``, in ``set_up`` before any weight is made, so the run
    ends at once with a non-zero exit code."""
    import sys

    import horovod_tpu.models as models

    cell = cells.resolve(CELL)
    monkeypatch.delattr(models, "joyai_flash")
    monkeypatch.setitem(sys.modules, "horovod_tpu.models.joyai_flash", None)
    with pytest.raises(ImportError):
        cell.code.init_params(cell.config, cell.job, None)
    with pytest.raises(ImportError):
        cell.code.loss_fn(cell.config, cell.job)
