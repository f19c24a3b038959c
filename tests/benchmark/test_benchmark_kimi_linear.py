"""The Kimi Linear configuration and its cell as ``BENCHMARK.json`` lists
them (PR 43 appended one configuration, its one-chip cell, six per-layer
metrics, the cell's name to the ``workloads`` of the accepted metrics whose
readers find something to read in it, and the queue's four-chip ResNet cell):
the entries are in the file's form and listed once, every catalog key is as
published or listed as reduced and no width is among them, the inferences
and the tolerances have their reasons, the FLOPs are hand arithmetic at the
published sizes, the toy cell goes through ``run.py`` on the CPU (in a
temporary copy of the benchmark whose ``rehearsal.json`` has gained the
cell), the six readers read a made-up trace, and the rooflines' counts are
the layers' and know nothing of a chunk or a tile. Nothing here holds a cell
to a place in its list or the lists to a length."""

import json
import os
import shutil
import types

import numpy as np
import pytest

import cells
from conftest import BENCHMARK_DIR, REPO_ROOT
from test_benchmark_rehearsal import result_of, run_cell
from trace_reduce import Op, Trace

CONFIG = "kimi-linear-48b-a3b"
CELL = "kimi-linear-48b-a3b_s8192_e8_dp1"
QUEUED = "resnet50_b128_dp4"
CATALOG = {  # architectures.jsonl's `config`, Kimi-Linear-48B-A3B-Instruct
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = ("kda_scan_ms", "kda_scan_roofline", "kda_mix_ms",
               "mla_attn_kernel_ms", "mla_attn_roofline", "moe_shared_ms")
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
        assert differs == {"num_hidden_layers", "linear_attn_config",
                           "vocab_size"}
        # experts_here is this repo's key: the catalog's num_experts stays
        assert set(cell.config["reduced"]) == set(entry["reduced"]) == (
            differs | {"experts_here"})
        assert entry["source"] in cell.config["source"]
        assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
        # no width is among them, nor changed inside the nested group
        assert not [key for key in entry["reduced"]
                    if key.endswith(("_dim", "_rank", "_size", "_head",
                                     "_state", "_expand"))
                    and key != "vocab_size"]
        linear, published = (cell.config["linear_attn_config"],
                             CATALOG["linear_attn_config"])
        for width in ("head_dim", "num_heads", "short_conv_kernel_size"):
            assert linear[width] == published[width]
        # the source's first five layers: one dense, one whole period
        assert linear["kda_layers"] == [1, 2, 3, 5] == [
            i for i in published["kda_layers"] if i <= 5]
        assert linear["full_attn_layers"] == [4] == [
            i for i in published["full_attn_layers"] if i <= 5]
        assert cell.config["num_hidden_layers"] == 5
        assert cell.config["vocab_size"] * 8 == 163840
        assert (cell.config["experts_here"], cell.config["first_expert"],
                cell.config["num_experts"]) == (8, 0, 256)
        assert cell.config["published"]["num_hidden_layers"] == 27
        assert cell.config["published"]["vocab_size"] == 163840
        for said in ("32 that share each layer", "experts 8 a chip",
                     "split eight ways", "pipeline stages",
                     "thirty-second", "idle share"):
            assert said in cell.config["deployment"], said

    @pytest.mark.parametrize("item", [
        "layers", "chunked_rule", "latent_attention_in_training",
        "capacity_factor", "e_score_correction_bias", "auxiliary_loss",
        "l2_norm_epsilon", "low_rank_projections", "recomputation",
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
            assert "provisional" not in correct[why]
        # the eight hand-made faults' readings are written down
        for said in ("mean over its channels", "3 mantissa bits",
                     "shared expert left out", "2.446 left out",
                     "not renormalised", "softmax scores",
                     "128^-1/2", "RoPE"):
            assert said in correct["gradient_norm_rel_why"], said

    def test_the_cell_is_the_issues(self):
        cell = cells.resolve(CELL)
        assert (cell.chips, cell.measured, cell.rows) == (1, True, 1)
        assert cell.job["seq_len"] == S
        assert S <= cell.config["model_max_length"]
        assert S % cell.config["training"]["chunk"] == 0
        assert (cell.job["sync_mode"], cell.job["compression"],
                cell.job["reference_block_rows"]) == ("allreduce", "bf16", 1)
        assert len(cell.job["loss_after_warmup"]) >= 10
        assert cell.config["training"]["attention"] == "flash"
        assert cell.config["training"]["compute_dtype"] == "bfloat16"
        assert cell.config["training"]["chunk"] % cell.config["training"][
            "sub_chunk"] == 0
        assert cell.code.min_pallas_calls(cell.config) == 3
        assert cell.code.units_per_step(cell.job, 1) == (S, "tokens")
        assert cell.config["capacity_factor"] == 1.25
        built = cell.code.model_config(cell.config)
        assert built.capacity(S) == 320  # ceil(1.25 x 8,192 x 8 / 256)
        assert {e["name"] for e, _, _ in cells.layer_metrics(CELL)} == {
            *NEW_METRICS, *REPORTS_TOO, *EVERY_CELLS}

    def test_what_is_listed_is_in_the_files_form(self):
        config, cell = listed("configs", CONFIG), listed("workloads", CELL)
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert cell["config"] == CONFIG and cell["traffic"] == CELL
        assert cell["chips"] == 1
        assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
        assert config["source"] == (
            "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct"
            "/blob/main/config.json")
        for name in NEW_METRICS:
            assert set(listed("per_layer", name)) == {
                "name", "unit", "better", "source", "layer", "moves",
                "workloads"}

    def test_it_is_listed_once_and_what_stood_before_it_stands_in_its_order(
            self):
        bench = cells.benchmark()
        for key in ("configs", "workloads", "per_layer"):
            names = [entry["name"] for entry in bench[key]]
            assert len(names) == len(set(names))
        configs = [c["name"] for c in bench["configs"]]
        before = ["bert-large", "resnet50", "olmoe-1b-7b", "olmo-hybrid-7b",
                  "smallthinker-21b-a3b", "sdar-30b-a3b",
                  "granite-4.0-h-micro"]
        assert configs[:len(before)] == before
        assert configs.index(CONFIG) >= len(before)
        workloads = [w["name"] for w in bench["workloads"]]
        before = ["bert-large_s512_dp1", "bert-large_s128_dp1",
                  "bert-large_s512_dp4", "resnet50_b128_dp1",
                  "olmoe-1b-7b_s4096_e16_dp1", "olmo-hybrid-7b_s4096_dp1",
                  "smallthinker-21b-a3b_s16384_e16_dp1",
                  "bert-large_s512_fsdp4", "sdar-30b-a3b_s8192_b4_e16_dp1",
                  "granite-4.0-h-micro_s4096_dp1"]
        assert workloads[:len(before)] == before
        assert workloads.index(CELL) >= len(before)
        metrics = [e["name"] for e in bench["per_layer"]]
        assert metrics.index("ssd_mix_ms") < min(
            metrics.index(name) for name in NEW_METRICS)
        # the quota itself: a quarter of the cells may take four chips
        four = [w for w in bench["workloads"] if w["chips"] == 4]
        assert len(four) <= len(bench["workloads"]) // 4
        with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
            assert len(f.read()) < 64 * 1024

    def test_the_accepted_metrics_it_reports_too_list_their_cells(self):
        """Each is an accepted metric with a ``workloads`` list that names
        the cell once, after the cells it named before, and moves an
        end-to-end metric the cell reports; no other accepted metric names
        the cell."""
        entries = {e["name"]: e for e in cells.benchmark()["per_layer"]}
        for name in REPORTS_TOO:
            cells_of = entries[name]["workloads"]
            assert cells_of.count(CELL) == 1
            assert cells_of.index(CELL) > 0  # after those it named before
            assert entries[name]["moves"] in ("step_ms", "hbm_gib", "setup_s")
        assert {name for name, entry in entries.items()
                if CELL in entry.get("workloads", ())} == {
            *REPORTS_TOO, *NEW_METRICS}
        # lists that other cells' tests hold to their own cell alone, and
        # the reader that would misread this configuration (its printed
        # share takes intermediate_size, here the dense layer's 9,216, for
        # an expert's width)
        assert not {"recompute_ms", "linattn_scan_ms", "linattn_mix_ms",
                    "linattn_scan_roofline", "moe_experts_ms"} & set(
                        REPORTS_TOO)

    def test_the_new_metrics_belong_to_this_cell_alone(self):
        for name in NEW_METRICS:
            entry = listed("per_layer", name)
            assert entry["workloads"] == [CELL]
            assert entry["moves"] == "step_ms"
            assert entry["source"] == "device_trace"
        for name in ("kda_scan_roofline", "mla_attn_roofline"):
            assert listed("per_layer", name)["unit"] == "%"
            assert listed("per_layer", name)["better"] == "higher"
        layers = {name: listed("per_layer", name)["layer"]
                  for name in NEW_METRICS}
        assert layers == {
            "kda_scan_ms": "kernels", "kda_scan_roofline": "kernels",
            "mla_attn_kernel_ms": "kernels", "mla_attn_roofline": "kernels",
            "kda_mix_ms": "linear_attention", "moe_shared_ms": "moe"}

    def test_the_model_is_built_at_the_published_widths(self):
        from horovod_tpu.models import kimi_linear

        cell = cells.resolve(CELL)
        built = cell.code.model_config(cell.config)
        assert built == kimi_linear.KimiLinearConfig(
            vocab_size=20480, num_layers=5, kda_layers=(1, 2, 3, 5),
            full_attn_layers=(4,), experts_here=8,
            remat=cell.config["training"]["remat"],
            chunk=cell.config["training"]["chunk"],
            sub_chunk=cell.config["training"]["sub_chunk"])
        assert built.kinds == ("kda", "kda", "kda", "mla", "kda")
        assert cell.code.kinds(cell.config) == [
            ("kda", "dense"), ("kda", "experts"), ("kda", "experts"),
            ("mla", "experts"), ("kda", "experts")]

    def test_parameters_are_what_the_file_says(self):
        import jax

        cell = cells.resolve(CELL)
        shapes = jax.eval_shape(
            lambda key: cell.code.init_params(cell.config, cell.job, key),
            jax.random.PRNGKey(0))
        leaves = jax.tree.leaves(shapes)
        assert len(leaves) == 109
        assert sum(int(np.prod(leaf.shape)) for leaf in leaves) == 602433408
        assert "602,433,408 in 109 leaves" in cell.config["assumed"][
            "parameters"]
        assert shapes["layer_0"]["kda"]["decay_b"]["kernel"].shape == (
            128, 4096)
        assert shapes["layer_3"]["attention"]["kv_a"]["kernel"].shape == (
            2304, 576)
        assert shapes["layer_4"]["moe"]["experts_down"].shape == (
            8, 1024, 2304)
        assert shapes["lm_head"].shape == (2304, 20480)

    def test_the_batch_is_rows_of_ids_from_the_slice(self):
        import jax

        cell = cells.resolve(CELL)
        batch = cell.code.make_batch(cell.config, dict(cell.job, seq_len=512),
                                     jax.random.PRNGKey(2147483650), 3)
        assert batch.shape == (3, 513)
        assert 0 <= int(batch.min()) and int(batch.max()) < 20480

    def test_flops_are_hand_arithmetic_at_the_published_sizes(self):
        cell = cells.resolve(CELL)
        macs = cell.code.macs_per_token(cell.config, S)
        assert macs == {
            "kda_projections": 4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096)
            + 2304 * 32,                                         # 39.46 M
            "short_conv": 4 * 3 * 4096,
            "recurrence": 4 * 32 * 128 * 128,                    # 2.10 M
            "mla_projections": 2304 * 6144 + 2304 * 576 + 512 * 8192
            + 4096 * 2304,                                       # 29.11 M
            "causal_scores": (S / 2) * 32 * (192 + 128),         # 41.94 M
            "dense_feed_forward": 3 * 2304 * 9216,
            "router": 2304 * 256,
            "shared_expert": 3 * 2304 * 1024,
            "routed_experts": 0.25 * 3 * 2304 * 1024,  # 8 x 8 / 256 pairs
            "head": 2304 * 20480}
        kda = macs["kda_projections"] + macs["short_conv"] + macs[
            "recurrence"]
        experts = macs["router"] + macs["shared_expert"] + macs[
            "routed_experts"]
        per_token = (kda + macs["dense_feed_forward"] + 3 * (kda + experts)
                     + macs["mla_projections"] + macs["causal_scores"]
                     + experts + macs["head"])
        flops = cell.code.flops_per_step(cell.config, cell.job, 1)
        assert flops == 6 * per_token * S
        # the issue's count: about 19 TFLOP a step, 2 of them the latent
        # layer's scores, the routed experts under 2%
        assert flops == pytest.approx(1.93e13, rel=2e-2)
        assert 6 * macs["causal_scores"] * S == pytest.approx(
            2.06e12, rel=0.01)
        assert 6 * 4 * macs["routed_experts"] * S < 0.02 * flops
        assert 6 * 4 * macs["recurrence"] * S == pytest.approx(
            0.41e12, rel=0.02)
        assert cell.code.flops_per_step(cell.config, cell.job, 2) == (
            2 * flops)


class TestTheQueuedCell:
    """``resnet50_b128_dp4``: the first of the four-chip cells the queue has
    held since PR 22, data files only."""

    def test_it_is_b128_dp1_on_four_chips(self):
        cell, one = cells.resolve(QUEUED), cells.resolve("resnet50_b128_dp1")
        assert (cell.chips, cell.measured) == (4, True)
        assert cell.rows == 4 * one.rows == 512
        assert cell.config == one.config
        same = {k: v for k, v in cell.job.items() if k != "loss_after_warmup"}
        assert same == {k: v for k, v in one.job.items()
                        if k != "loss_after_warmup"}
        assert set(cell.job["loss_after_warmup"]) == {
            str(seed) for seed in range(12)}
        entry = listed("workloads", QUEUED)
        assert set(entry) == {"name", "config", "traffic", "chips", "why"}
        assert len(entry["why"]) <= 200 and "four chips" in entry["why"]

    def test_it_reports_the_wire_and_what_b128_dp1_reports(self):
        entries = {e["name"]: e for e in cells.benchmark()["per_layer"]}
        mine = {name for name, e in entries.items()
                if QUEUED in e.get("workloads", ())}
        ones = {name for name, e in entries.items()
                if "resnet50_b128_dp1" in e.get("workloads", ())}
        wire = {"collective_ms", "collective_exposed_ms", "wire_pack_ms",
                "wire_mb_per_step"}
        assert mine == ones | wire
        for name in mine:
            assert entries[name]["workloads"].count(QUEUED) == 1


class TestReaders:
    """A made-up trace of one device and two steps: a KDA layer's
    convolutions, rule (a fusion and the loop that carries the states, whose
    own event covers the event inside it) and gate forward, the latent
    layer's forward kernel, the shared expert; then the backward pass with
    the recomputed forward, the latent layer's two backward kernels among
    it."""

    STACK = "jit(spmd_step)/shard_map/"
    FWD = STACK + "jvp(KimiLinear)/layer_1/hvd.block.attn_proj/kda/"
    AGAIN = (STACK + "transpose(jvp(KimiLinear))/rematted_computation/"
             "layer_1/hvd.block.attn_proj/kda/")
    BWD = STACK + "transpose(jvp(KimiLinear))/layer_1/hvd.block.attn_proj/kda/"
    MLA = "layer_3/hvd.block.attn_proj/attention/hvd.attn.mla/"
    HLO = f"""
  %fusion.1 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f.1, metadata={{op_name="{FWD}query/dot_general"}}
  %fusion.2 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f.2, metadata={{op_name="{FWD}hvd.linattn.conv/mul"}}
  %fusion.3 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f.3, metadata={{op_name="{FWD}hvd.linattn.scan/exp"}}
  %while.4 = f32[8]{{0}} while(%p), condition=%c.4, body=%b.4, metadata={{op_name="{FWD}hvd.linattn.scan/while"}}
  %fusion.5 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f.5, metadata={{op_name="{FWD}hvd.linattn.scan/while/body/dot_general"}}
  %fusion.6 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f.6, metadata={{op_name="{FWD}hvd.linattn.gate/mul"}}
  %flash_attention.7 = bf16[8]{{0}} custom-call(%p), custom_call_target="tpu_custom_call", metadata={{op_name="{STACK}jvp(KimiLinear)/{MLA}hvd.attn.fwd/flash_attention"}}
  %fusion.8 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f.8, metadata={{op_name="{STACK}jvp(KimiLinear)/layer_1/hvd.moe.shared/shared/up/dot_general"}}
  %fusion.9 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f.9, metadata={{op_name="{AGAIN}hvd.linattn.scan/exp"}}
  %fusion.10 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f.10, metadata={{op_name="{BWD}transpose(jvp(hvd.linattn.scan))/dot_general"}}
  %fusion.11 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f.11, metadata={{op_name="{BWD}transpose(jvp(hvd.linattn.conv))/mul"}}
  %flash_attention.12 = bf16[8]{{0}} custom-call(%p), custom_call_target="tpu_custom_call", metadata={{op_name="{STACK}transpose(jvp(KimiLinear))/{MLA}hvd.attn.bwd/flash_attention"}}
  %flash_attention.13 = bf16[8]{{0}} custom-call(%p), custom_call_target="tpu_custom_call", metadata={{op_name="{STACK}transpose(jvp(KimiLinear))/{MLA}hvd.attn.bwd/flash_attention"}}
  %flash_attention.14 = bf16[8]{{0}} custom-call(%p), custom_call_target="tpu_custom_call", metadata={{op_name="{STACK}jvp(KimiLinear)/layer_9/hvd.attn.fwd/flash_attention"}}
  %fusion.15 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f.15, metadata={{op_name="{STACK}transpose(jvp(KimiLinear))/layer_1/transpose(jvp(hvd.moe.shared))/shared/up/dot_general"}}
  %fusion.16 = f32[8]{{0}} fusion(%s), kind=kLoop, calls=%f.16, metadata={{op_name="{STACK}hvd.optimizer/add"}}
"""
    OPS = [
        Op("fusion.1", "fusion", 0.0, 1.0),
        Op("fusion.2", "fusion", 1.0, 1.25),            # conv
        Op("fusion.3", "fusion", 1.25, 2.0),            # rule
        Op("while.4", "while", 2.0, 3.0),               # rule: the loop
        Op("fusion.5", "fusion", 2.25, 2.75),           # rule: inside it
        Op("fusion.6", "fusion", 3.0, 3.5),             # gate
        Op("flash_attention.7", "custom-call", 3.5, 4.5),   # latent, forward
        Op("fusion.8", "fusion", 4.5, 5.0),             # shared expert
        Op("fusion.9", "fusion", 5.0, 5.75),            # rule, recomputed
        Op("fusion.10", "fusion", 5.75, 7.75),          # rule, backward
        Op("fusion.11", "fusion", 7.75, 8.25),          # conv, backward
        Op("flash_attention.12", "custom-call", 8.25, 9.25),   # dq
        Op("flash_attention.13", "custom-call", 9.25, 10.75),  # dkv
        Op("flash_attention.14", "custom-call", 10.75, 11.0),  # no latent
        Op("fusion.15", "fusion", 11.0, 12.0),          # shared, backward
        Op("fusion.16", "fusion", 12.0, 12.5),
    ]
    TRACE = Trace({0: OPS}, {0: []}, [], (0.0, 13.0))
    SCAN_S = 0.75 + 1.0 + 0.75 + 2.0    # the loop's inside counted once
    MIX_S = 0.25 + 0.5 + 0.5
    MLA_S = 1.0 + 1.0 + 1.5
    SHARED_S = 0.5 + 1.0

    @pytest.fixture()
    def run(self, monkeypatch):
        import horovod_tpu as hvd

        monkeypatch.setattr(hvd.profiler, "step_texts", lambda: [self.HLO])
        return types.SimpleNamespace(
            trace=self.TRACE, steps=2, peak=PEAK, call_s=[0.001],
            cell=cells.resolve(CELL))

    def test_rule_and_mix_are_told_apart_and_a_loop_counts_once(
            self, run, capsys):
        assert reader("kda_scan_ms").read(
            run, parameters("kda_scan_ms")) == pytest.approx(
                self.SCAN_S / 2 * 1e3)
        # the plain sum counts the loop's inside twice, and says so
        assert f"{(self.SCAN_S + 0.5) / 2 * 1e3:.3f}" in (
            capsys.readouterr().out)
        assert reader("kda_mix_ms").read(
            run, parameters("kda_mix_ms")) == pytest.approx(
                self.MIX_S / 2 * 1e3)
        said = capsys.readouterr().out
        assert "hvd.linattn.conv 375.000 ms" in said
        assert "hvd.linattn.gate 250.000 ms" in said

    def test_the_latent_layers_kernels_are_told_by_their_scope(self, run):
        assert reader("mla_attn_kernel_ms").read(
            run, parameters("mla_attn_kernel_ms")) == pytest.approx(
                self.MLA_S / 2 * 1e3)

    def test_the_shared_expert_is_its_scope(self, run, capsys):
        assert reader("moe_shared_ms").read(
            run, parameters("moe_shared_ms")) == pytest.approx(
                self.SHARED_S / 2 * 1e3)
        # 4 expert layers x 3 x 2,304 x 1,024 multiply-adds a token, thrice
        flops = 6 * 4 * 3 * 2304 * 1024 * S
        assert f"{flops / 1e12:.3f} TFLOP" in capsys.readouterr().out

    def test_the_rules_roofline_counts_the_recurrence(self, run, capsys):
        # a layer and pass: forward 4 x 32 x 128 x 128 multiply-adds a
        # token against q, k, v, o in bfloat16, g (a key channel) and beta
        # in float32
        forward_flops = 2 * 4 * 32 * 128 * 128 * S
        forward_bytes = S * (4 * 4096 * 2 + 4096 * 4 + 32 * 4)
        backward_bytes = S * (7 * 4096 * 2 + 2 * (4096 + 32) * 4)
        roofline = reader("kda_scan_roofline")
        assert roofline.forward_cost(1, S, 32, 128, 2) == (
            forward_flops, forward_bytes)
        assert roofline.backward_cost(1, S, 32, 128, 2) == (
            2 * forward_flops, backward_bytes)
        least = 4 * (max(forward_flops / 197e12, forward_bytes / 819e9)
                     + max(2 * forward_flops / 197e12,
                           backward_bytes / 819e9))
        assert roofline.read(
            run, parameters("kda_scan_roofline")) == pytest.approx(
                100 * least / (self.SCAN_S / 2))
        said = capsys.readouterr().out
        assert "memory-bound) + " in said and "4 layers" in said

    def test_the_latent_roofline_counts_each_product_at_its_lanes(
            self, run, capsys):
        pairs = S * (S + 1) / 2
        roofline = reader("mla_attn_roofline")
        assert roofline.forward_cost(32, S, 192, 128, 2) == (
            32 * 2 * pairs * (192 + 128),
            32 * (2 * S * (192 + 128) * 2 + 4 * S))
        assert roofline.backward_cost(32, S, 192, 128, 2) == (
            32 * 2 * pairs * (3 * 192 + 2 * 128),
            32 * (S * (4 * 192 + 3 * 128) * 2 + 12 * S))
        forward = 32 * 2 * pairs * 320 / 197e12     # compute-bound
        backward = 32 * 2 * pairs * 832 / 197e12
        assert forward + backward == pytest.approx(12.56e-3, rel=0.01)
        assert roofline.read(
            run, parameters("mla_attn_roofline")) == pytest.approx(
                100 * (forward + backward) * 2 / self.MLA_S)
        said = capsys.readouterr().out
        assert "compute-bound) + " in said and "1 layer(s)" in said

    def test_the_counts_know_nothing_of_the_chunk(self, run):
        """Another chunk or sub-block in the configuration: the same share.
        The count is the layer's, not an implementation's."""
        roofline, params = reader("kda_scan_roofline"), parameters(
            "kda_scan_roofline")
        want = roofline.read(run, params)
        for chunk, sub in ((32, 8), (128, 32)):
            config = dict(run.cell.config, training=dict(
                run.cell.config["training"], chunk=chunk, sub_chunk=sub))
            other = types.SimpleNamespace(**{
                **vars(run), "cell": types.SimpleNamespace(
                    config=config, job=run.cell.job)})
            assert roofline.read(other, params) == want
        import inspect
        for name in ("kda_scan_roofline", "mla_attn_roofline"):
            assert not {"chunk", "sub", "block", "tile"} & set(
                inspect.signature(reader(name).forward_cost).parameters)

    def test_the_least_times_cannot_be_undercut(self):
        """The rule's forward pass is memory-bound on the v5e (its least
        time is reading the operands and writing the result once, which no
        implementation goes below) and the latent kernels' least time counts
        exactly the causal pairs at the lanes the mathematics has (192 and
        128, not the 256 a padded block computes): neither share can pass
        100%."""
        roofline = reader("kda_scan_roofline")
        assert roofline.least_seconds(
            roofline.forward_cost(1, S, 32, 128, 2), PEAK)[1] == "memory"
        latent = reader("mla_attn_roofline")
        flops, _ = latent.forward_cost(32, S, 192, 128, 2)
        padded = 32 * 2 * (S * S / 2) * (256 + 128)
        assert flops < padded

    def test_the_names_are_the_programs(self):
        from horovod_tpu import attribution
        from horovod_tpu.ops import attention

        prefix = attribution.SCOPE_PREFIX
        scan = prefix + attribution.SCOPE_LINATTN_SCAN
        assert parameters("kda_scan_ms")["scopes"] == [scan]
        assert parameters("kda_scan_roofline")["scopes"] == [scan]
        assert parameters("kda_mix_ms")["scopes"] == [
            prefix + attribution.SCOPE_LINATTN_CONV,
            prefix + attribution.SCOPE_LINATTN_GATE]
        assert parameters("moe_shared_ms")["scopes"] == [
            prefix + attribution.SCOPE_MOE_SHARED]
        assert prefix + attribution.SCOPE_MOE_SHARED in (
            attribution.PHASE_SCOPE_NAMES)
        for name in ("mla_attn_kernel_ms", "mla_attn_roofline"):
            assert parameters(name)["mla_scope"] == (
                prefix + attribution.SCOPE_ATTN_MLA)
            assert attention.KERNEL_NAME in parameters(name)["kernel_names"]
        assert prefix + attribution.SCOPE_ATTN_MLA not in (
            attribution.PHASE_SCOPE_NAMES)

    def test_a_program_without_the_scopes_reads_nothing(self, monkeypatch):
        """What a program older than the scopes would give (the parent's
        has no ``hvd.attn.mla`` and no ``hvd.moe.shared``): the metrics are
        left out and nothing raises."""
        import horovod_tpu as hvd

        before = self.HLO.replace("hvd.linattn.", "linattn_").replace(
            "hvd.attn.mla/", "").replace("hvd.moe.shared", "shared_")
        monkeypatch.setattr(hvd.profiler, "step_texts", lambda: [before])
        run = types.SimpleNamespace(
            trace=self.TRACE, steps=2, peak=PEAK, call_s=[0.001],
            cell=cells.resolve(CELL))
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
        "name": "rehearsal-kimi-linear_dp1",
        "config": "rehearsal-kimi-linear",
        "traffic": "rehearsal-kimi-linear_dp1", "chips": 1})
    (copy / "rehearsal.json").write_text(json.dumps(rehearsed))
    cache = tmp_path_factory.mktemp("compile-cache")
    proc = run_cell("rehearsal-kimi-linear_dp1", trace=0, cache=cache,
                    root=str(tmp_path), seed=2147483650)
    result = result_of(proc)
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert set(result["metrics"]) == {"step_ms", "setup_s"}
    assert "leaves, 2 rows a step" in proc.stdout
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
    checkout, whose ``horovod_tpu.models`` has no ``kimi_linear``: the
    first thing the harness asks of the configuration's code raises
    ``ImportError``, in ``set_up`` before any weight is made, so the run
    ends at once with a non-zero exit code."""
    import sys

    import horovod_tpu.models as models

    cell = cells.resolve(CELL)
    monkeypatch.delattr(models, "kimi_linear")
    monkeypatch.setitem(sys.modules, "horovod_tpu.models.kimi_linear", None)
    with pytest.raises(ImportError):
        cell.code.init_params(cell.config, cell.job, None)
    with pytest.raises(ImportError):
        cell.code.loss_fn(cell.config, cell.job)
