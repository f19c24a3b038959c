"""The LFM2 configuration and its cell as ``BENCHMARK.json`` lists them (PR 54
appended one configuration, its one-chip cell, five per-layer metrics and the
cell's name to the ``workloads`` of the accepted metrics whose readers find
something to read in it): the entries are in the file's form and listed once,
every catalog key is as published or listed as reduced and no width is among
them, the inferences and the tolerances have their reasons, the cut's
parameters and the FLOPs are hand arithmetic at the published sizes, the toy
cell goes through ``run.py`` on the CPU (in a temporary copy of the benchmark
whose ``rehearsal.json`` has gained the cell), the five readers read a
made-up trace, and the rooflines' counts are the layers' and know nothing of
an implementation. Everything here is by membership: nothing holds a cell or
a metric to a place in its list or a list to a length, so the next cell fails
no case of it."""

import json
import os
import shutil
import types

import pytest

import cells
from conftest import BENCHMARK_DIR, REPO_ROOT
from test_benchmark_rehearsal import result_of, run_cell
from trace_reduce import Op, Trace

CONFIG = "lfm2-24b-a2b"
CELL = "lfm2-24b-a2b_s8192_e8_dp1"
PERIOD = ["conv", "conv", "full_attention", "conv"]
CATALOG = {  # architectures.jsonl's `config`, LFM2-24B-A2B
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": PERIOD * 10, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = {  # name -> layer
    "shortconv_mix_ms": "short_conv", "shortconv_mix_roofline": "short_conv",
    "gqa_d64_attn_kernel_ms": "kernels", "gqa_d64_attn_roofline": "kernels",
    "moe_e1536_experts_ms": "moe"}
S, ROWS = 8192, 2
REPORTS_TOO = (  # accepted metrics whose readers find something here
    "moe_dispatch_ms", "step_trace_lower_s", "hbm_temporaries_gib",
    "unowned_ms", "shared_fusion_ms", "embed_ms", "attn_proj_ms", "norm_ms",
    "ffn_ms", "head_ms")
NOT_THIS_CELLS = (  # held to other cells by their tests, or another family
    "moe_experts_ms", "moe_e768_experts_ms", "gqa16_attn_kernel_ms",
    "gqa16_attn_roofline", "gqa_full_attn_kernel_ms",
    "gqa_full_attn_roofline", "ssd_scan_ms", "ssd_scan_roofline",
    "ssd_mix_ms", "recompute_ms")


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
        assert differs == {"num_hidden_layers", "layer_types",
                           "num_dense_layers", "vocab_size"}
        # experts_here is this repo's key: the catalog's num_experts stays,
        # the router's width
        assert set(cell.config["reduced"]) == set(entry["reduced"]) == (
            differs | {"experts_here"})
        assert entry["source"] in cell.config["source"]
        assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
        # no width is among them
        assert not [key for key in entry["reduced"]
                    if key.endswith(("_dim", "_rank", "_size", "_head",
                                     "_state", "_expand", "_cache"))
                    and key != "vocab_size"]
        # the source's layers 1 to 5: one dense layer and a whole period
        assert cell.config["layer_types"] == CATALOG["layer_types"][1:6]
        assert cell.config["num_hidden_layers"] == 5 == (
            cell.config["num_dense_layers"] + 4)
        assert sorted(cell.config["layer_types"][1:]) == sorted(PERIOD)
        assert cell.config["vocab_size"] * 8 == 65536
        assert (cell.config["experts_here"], cell.config["first_expert"],
                cell.config["num_experts"]) == (8, 0, 64)
        published = cell.config["published"]
        assert (published["num_hidden_layers"], published["vocab_size"],
                published["num_dense_layers"]) == (40, 65536, 2)
        for said in ("8 that share each layer", "experts 8 a chip",
                     "split eight ways", "pipeline stages", "an eighth",
                     "two of forty", "36%", "three to one", "idle share"):
            assert said in cell.config["deployment"], said
        assert "two of six would make it half" in cell.config["reduced"][
            "num_dense_layers"]

    @pytest.mark.parametrize("item", [
        "layers", "tied_embedding", "head_dim", "rotary", "norm_placement",
        "projection_order", "gate_epsilon", "expert_bias", "auxiliary_loss",
        "capacity_factor", "recomputation", "initialisation", "inputs",
        "optimizer", "parameters"])
    def test_every_inference_is_written_down(self, item):
        said = cells.resolve(CELL).config["assumed"][item]
        assert len(said) > 20 and "TO BE SET" not in said

    def test_the_assumed_sizes_are_the_issues(self):
        config = cells.resolve(CELL).config
        assert config["capacity_factor"] == 1.25
        assert "head_dim" not in config
        assert config["hidden_size"] // config["num_attention_heads"] == 64
        assert "1e-6" in config["assumed"]["gate_epsilon"]
        assert "held at its initial zero" in config["assumed"]["expert_bias"]
        assert "B | C | x" in config["assumed"]["projection_order"]

    def test_every_tolerance_has_its_reason(self):
        correct = cells.resolve(CELL).config["correct"]
        for key in ("loss_rel", "gradient_norm_rel_median",
                    "gradient_norm_rel_worst", "loss_record_rel"):
            assert 0 < correct[key] < 1
        for why in ("loss_rel_why", "gradient_norm_rel_why",
                    "loss_record_rel_why"):
            assert len(correct[why]) > 40
            assert "TO BE SET" not in correct[why]
        # the control and the fourteen hand-made faults' readings are there
        for said in ("3 mantissa bits", "taps in reverse order", "t + 1",
                     "C and B exchanged", "SiLU put after",
                     "QK-norm left out", "one scale a head",
                     "interleaved pairs", "RoPE left out", "128^-1/2",
                     "j % 8 for j // 4", "softmax scores for sigmoid",
                     "not renormalised", "1e-20 for 1e-6", "an untied head"):
            assert said in correct["gradient_norm_rel_why"], said

    def test_the_cell_is_the_issues(self):
        cell = cells.resolve(CELL)
        assert (cell.chips, cell.measured, cell.rows) == (1, True, ROWS)
        assert cell.job["seq_len"] == S
        assert S <= cell.config["max_position_embeddings"]
        assert (cell.job["sync_mode"], cell.job["compression"],
                cell.job["reference_block_rows"]) == ("allreduce", "bf16", 1)
        assert len(cell.job["loss_after_warmup"]) >= 10
        assert cell.config["training"] == {
            "learning_rate": 0.0001, "compute_dtype": "bfloat16",
            "attention": "flash", "remat": True}
        assert cell.code.min_pallas_calls(cell.config) == 3
        assert cell.code.units_per_step(cell.job, ROWS) == (
            ROWS * S, "tokens")
        built = cell.code.model_config(cell.config)
        assert built.capacity(S) == 640  # ceil(1.25 x 8,192 x 4 / 64)
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
        for said in ("Two rows of 8,192", "4 of 5 layers are conv mixers",
                     "an eighth of their load", "9% of FLOPs",
                     "1 dense layer of 5", "2 of 40", "36%"):
            assert said in cell["why"], said
        assert config["source"] == (
            "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/"
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
        for name in ("shortconv_mix_roofline", "gqa_d64_attn_roofline"):
            assert (entries[name]["unit"], entries[name]["better"]) == (
                "%", "higher")

    def test_the_model_is_built_at_the_published_widths(self):
        from horovod_tpu.models import lfm2

        cell = cells.resolve(CELL)
        assert cell.code.model_config(cell.config) == lfm2.Lfm2Config(
            vocab_size=8192, num_layers=5, num_dense_layers=1,
            layer_types=("conv", "full_attention", "conv", "conv", "conv"),
            experts_here=8)
        assert cell.code.kinds(cell.config) == [
            ("conv", "dense"), ("full_attention", "experts")] + [
                ("conv", "experts")] * 3

    def test_parameters_are_what_the_file_says(self):
        import jax
        import numpy as np

        cell = cells.resolve(CELL)
        shapes = jax.eval_shape(
            lambda key: cell.code.init_params(cell.config, cell.job, key),
            jax.random.PRNGKey(0))
        leaves = jax.tree.leaves(shapes)
        assert len(leaves) == 49
        assert sum(int(np.prod(leaf.shape)) for leaf in leaves) == 469284992
        assert "469,284,992 in 49 leaves" in cell.config["assumed"][
            "parameters"]
        # the cut's arithmetic, by hand
        conv = 2048 * 6144 + 2048 * 3 + 2048 * 2048
        attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
        expert, router, norms = 3 * 2048 * 1536, 2048 * 64, 2 * 2048
        dense_layer = conv + 3 * 2048 * 11776 + norms
        attention_layer = attention + router + 8 * expert + norms
        conv_layer = conv + router + 8 * expert + norms
        assert (conv, attention, expert, 3 * 2048 * 11776) == (
            16783360, 10485888, 9437184, 72351744)
        assert (dense_layer + attention_layer + 3 * conv_layer
                + 8192 * 2048 + 2048) == 469284992
        # at place_state's 20 bytes a parameter; sixteen experts a layer
        assert 469284992 * 20 / 2 ** 30 == pytest.approx(8.74, abs=0.01)
        assert 469284992 + 4 * 8 * expert == pytest.approx(771e6, rel=2e-3)
        assert shapes["layer_0"]["conv"]["in_proj"]["kernel"].shape == (
            2048, 6144)
        assert shapes["layer_1"]["attention"]["q_norm"]["scale"].shape == (
            64,)
        assert shapes["layer_4"]["moe"]["experts_down"].shape == (
            8, 1536, 2048)
        assert shapes["embedding"].shape == (8192, 2048)

    def test_the_batch_is_rows_of_s_plus_1_ids_from_the_slice(self):
        import jax

        cell = cells.resolve(CELL)
        batch = cell.code.make_batch(cell.config, dict(cell.job, seq_len=512),
                                     jax.random.PRNGKey(2147483650), 2)
        assert batch.shape == (2, 513)
        assert 0 <= int(batch.min()) and int(batch.max()) < 8192

    def test_flops_are_hand_arithmetic_at_the_published_sizes(self):
        cell = cells.resolve(CELL)
        macs = cell.code.macs_per_token(cell.config, S)
        assert macs == {
            "conv_projections": 2048 * 6144 + 2048 * 2048,        # 16.78 M
            "conv_taps_and_gates": 5 * 2048,
            "attention_projections": 2 * 2048 * (2048 + 512),     # 10.49 M
            "causal_scores": 2 * (S / 2) * 2048,                  # 16.78 M
            "dense_feed_forward": 3 * 2048 * 11776,               # 72.35 M
            "router": 2048 * 64,
            "routed_experts": 0.5 * 3 * 2048 * 1536,  # 4 x 8 / 64 pairs
            "head": 2048 * 8192}
        conv = macs["conv_projections"] + macs["conv_taps_and_gates"]
        attention = macs["attention_projections"] + macs["causal_scores"]
        experts = macs["router"] + macs["routed_experts"]
        per_token = (4 * conv + attention + macs["dense_feed_forward"]
                     + 4 * experts + macs["head"])
        flops = cell.code.flops_per_step(cell.config, cell.job, ROWS)
        assert flops == 6 * per_token * ROWS * S
        # the issue's count: 406 MFLOP a token forward, 20.0 TFLOP a step;
        # the conv mixers 33%, the dense feed-forward 36%, attention 13%,
        # the experts 9%, the head 8%
        assert 2 * per_token == pytest.approx(406e6, rel=0.005)
        assert flops == pytest.approx(20.0e12, rel=0.005)
        for part, share in ((4 * conv, 0.33),
                            (macs["dense_feed_forward"], 0.36),
                            (attention, 0.13), (4 * experts, 0.09),
                            (macs["head"], 0.08)):
            assert part / per_token == pytest.approx(share, abs=0.007)
        assert cell.code.flops_per_step(cell.config, cell.job, 1) == (
            flops / 2)


class TestReaders:
    """A made-up trace of one device and two steps: a conv layer's mix, the
    attention layer's forward kernel and its experts; then the backward pass
    with the recomputed mix, the two backward kernels, the experts' and the
    mix's backward, and the optimizer."""

    STACK = "jit(spmd_step)/shard_map/"
    FWD = STACK + "jvp(Lfm2)/"
    BWD = STACK + "transpose(jvp(Lfm2))/"
    MIX = "hvd.block.attn_proj/conv/hvd.shortconv.mix/"
    KERNEL = "hvd.block.attn_proj/attention/hvd.attn.{}/flash_attention"
    HLO = f"""
ENTRY %main (p: f32[8]) -> f32[8] {{
  %fusion.1 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f.1, metadata={{op_name="{FWD}layer_0/{MIX}mul"}}
  %flash_attention.2 = bf16[8]{{0}} custom-call(%p), custom_call_target="tpu_custom_call", metadata={{op_name="{FWD}layer_1/{KERNEL.format('fwd')}"}}
  %fusion.3 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f.3, metadata={{op_name="{FWD}layer_1/moe/vmap(hvd.moe.experts)/ecd,edh->ech/dot_general"}}
  %fusion.4 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f.4, metadata={{op_name="{BWD}layer_1/moe/transpose(jvp(vmap(hvd.moe.experts)))/ech,ehd->ecd/dot_general"}}
  %flash_attention.5 = bf16[8]{{0}} custom-call(%p), custom_call_target="tpu_custom_call", metadata={{op_name="{BWD}layer_1/{KERNEL.format('bwd')}"}}
  %flash_attention.6 = bf16[8]{{0}} custom-call(%p), custom_call_target="tpu_custom_call", metadata={{op_name="{BWD}layer_1/{KERNEL.format('bwd')}"}}
  %fusion.7 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f.7, metadata={{op_name="{BWD}rematted_computation/layer_0/{MIX}mul"}}
  %fusion.8 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f.8, metadata={{op_name="{BWD}layer_0/hvd.block.attn_proj/conv/transpose(jvp(hvd.shortconv.mix))/mul"}}
  %fusion.9 = f32[8]{{0}} fusion(%s), kind=kLoop, calls=%f.9, metadata={{op_name="{STACK}hvd.optimizer/add"}}
}}
"""
    OPS = [
        Op("fusion.1", "fusion", 0.0, 0.5),                  # mix
        Op("flash_attention.2", "custom-call", 0.5, 1.5),    # forward
        Op("fusion.3", "fusion", 1.5, 2.25),                 # experts
        Op("fusion.4", "fusion", 2.25, 3.75),                # experts, bwd
        Op("flash_attention.5", "custom-call", 3.75, 4.75),  # dq
        Op("flash_attention.6", "custom-call", 4.75, 6.25),  # dkv
        Op("fusion.7", "fusion", 6.25, 6.75),                # mix again
        Op("fusion.8", "fusion", 6.75, 7.75),                # mix, backward
        Op("fusion.9", "fusion", 7.75, 8.25),
    ]
    TRACE = Trace({0: OPS}, {0: []}, [], (0.0, 8.25))
    MIX_S, KERNELS_S, EXPERTS_S = 0.5 + 0.5 + 1.0, 1.0 + 1.0 + 1.5, 2.25

    @pytest.fixture()
    def run(self, monkeypatch):
        import horovod_tpu as hvd

        monkeypatch.setattr(hvd.profiler, "step_texts", lambda: [self.HLO])
        return types.SimpleNamespace(
            trace=self.TRACE, steps=2, peak=PEAK, call_s=[0.001],
            cell=cells.resolve(CELL))

    def test_the_mix_is_its_scope_forward_recomputed_and_backward(self, run):
        assert reader("shortconv_mix_ms").read(
            run, parameters("shortconv_mix_ms")) == pytest.approx(
                self.MIX_S / 2 * 1e3)

    def test_the_mixs_roofline_is_the_layers_shapes_alone(self, run, capsys):
        mix = reader("shortconv_mix_roofline")
        forward = mix.forward_cost(ROWS, S, 2048, 3, 2)
        backward = mix.backward_cost(ROWS, S, 2048, 3, 2)
        positions = ROWS * S * 2048
        # B, C, x read and y written; those three and dy read, three written
        assert forward == (positions * 8.0, positions * 4 * 2.0)
        assert backward == (positions * 16.0, positions * 7 * 2.0)
        for cost in (forward, backward):
            assert mix.least_seconds(cost, PEAK)[1] == "memory"
        # the issue's count: 0.33 ms forward, 0.57 backward, 3.6 a step
        assert mix.least_seconds(forward, PEAK)[0] == pytest.approx(
            0.33e-3, abs=0.005e-3)
        assert mix.least_seconds(backward, PEAK)[0] == pytest.approx(
            0.57e-3, abs=0.005e-3)
        least = 4 * (forward[1] + backward[1]) / 819e9
        assert least == pytest.approx(3.6e-3, abs=0.02e-3)
        assert mix.read(run, parameters("shortconv_mix_roofline")) == (
            pytest.approx(100 * least * 2 / self.MIX_S))
        assert "4 layers" in capsys.readouterr().out

    def test_the_mixs_least_time_cannot_be_undercut(self):
        """Every operand and result once and no intermediate, the backward
        pass without the recomputed forward: whatever implements the layer
        moves at least these bytes, so the share cannot pass 100%."""
        mix = reader("shortconv_mix_roofline")
        _, forward = mix.forward_cost(1, S, 2048, 3, 2)
        _, backward = mix.backward_cost(1, S, 2048, 3, 2)
        array = S * 2048 * 2
        assert (forward, backward) == (4 * array, 7 * array)

    def test_the_kernels_are_told_by_name(self, run):
        assert reader("gqa_d64_attn_kernel_ms").read(
            run, parameters("gqa_d64_attn_kernel_ms")) == pytest.approx(
                self.KERNELS_S / 2 * 1e3)
        assert parameters("gqa_d64_attn_kernel_ms")["kernel_names"] == (
            parameters("gqa_d64_attn_roofline")["kernel_names"])

    def test_the_kernels_roofline_counts_the_triangle_at_64_lanes(
            self, run, capsys):
        pairs = S * (S + 1) // 2
        window = reader("window_attn_roofline")
        shape = (ROWS * 32, ROWS * 8, S, 64, 2, pairs)
        forward, backward = (window.forward_cost(*shape),
                             window.backward_cost(*shape))
        assert forward[0] == 64 * 2 * 2 * pairs * 64
        assert backward[0] == 64 * 5 * 2 * pairs * 64
        # q and o a query head, k and v a key/value head, float32 rows
        assert forward[1] == 64 * (2 * S * 64 * 2 + 4 * S) + 16 * (
            2 * S * 64 * 2)
        for cost in (forward, backward):
            assert window.least_seconds(cost, PEAK)[1] == "compute"
        least = (forward[0] + backward[0]) / 197e12
        assert least == pytest.approx(9.77e-3, rel=0.01)
        assert reader("gqa_d64_attn_roofline").read(
            run, parameters("gqa_d64_attn_roofline")) == pytest.approx(
                100 * least * 2 / self.KERNELS_S)
        said = capsys.readouterr().out
        assert "1 layer(s), 32 query heads on 8 of 64 lanes, 2 rows" in said

    def test_the_experts_are_their_scope_at_an_experts_own_width(
            self, run, capsys):
        assert reader("moe_e1536_experts_ms").read(
            run, parameters("moe_e1536_experts_ms")) == pytest.approx(
                self.EXPERTS_S / 2 * 1e3)
        # 2 rows x 4 expert layers x 8 experts x 640 slots x 3 products,
        # thrice
        flops = 2 * 4 * 8 * 640 * 3 * 3 * 2 * 2048 * 1536
        assert f"{flops / 1e12:.3f} TFLOP" in capsys.readouterr().out

    def test_the_names_are_the_programs(self):
        from horovod_tpu import attribution
        from horovod_tpu.ops import attention

        prefix = attribution.SCOPE_PREFIX
        mix = prefix + attribution.SCOPE_SHORTCONV_MIX
        assert mix == "hvd.shortconv.mix"
        assert mix in attribution.PHASE_SCOPE_NAMES
        for name in ("shortconv_mix_ms", "shortconv_mix_roofline"):
            assert parameters(name)["scopes"] == [mix]
        assert parameters("moe_e1536_experts_ms")["scopes"] == [
            prefix + attribution.SCOPE_MOE_EXPERTS]
        for name in ("gqa_d64_attn_kernel_ms", "gqa_d64_attn_roofline"):
            assert attention.KERNEL_NAME in parameters(name)["kernel_names"]

    def test_a_program_without_the_scopes_reads_nothing(self, monkeypatch):
        """What a program without the scope and the kernels would give: the
        metrics are left out and nothing raises."""
        import horovod_tpu as hvd

        before = self.HLO.replace("hvd.shortconv.mix", "mix").replace(
            "hvd.moe.", "moe_")
        monkeypatch.setattr(hvd.profiler, "step_texts", lambda: [before])
        ops = [op for op in self.OPS if op.opcode != "custom-call"]
        run = types.SimpleNamespace(
            trace=Trace({0: ops}, {0: []}, [], (0.0, 8.25)), steps=2,
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
        "name": "rehearsal-lfm2_dp1", "config": "rehearsal-lfm2",
        "traffic": "rehearsal-lfm2_dp1", "chips": 1})
    (copy / "rehearsal.json").write_text(json.dumps(rehearsed))
    cache = tmp_path_factory.mktemp("compile-cache")
    proc = run_cell("rehearsal-lfm2_dp1", trace=0, cache=cache,
                    root=str(tmp_path), seed=2147483650)
    result = result_of(proc)
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert set(result["metrics"]) == {"step_ms", "setup_s"}
    assert "49 leaves, 2 rows a step" in proc.stdout
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
    checkout, whose ``horovod_tpu.models`` has no ``lfm2``: the first thing
    the harness asks of the configuration's code raises ``ImportError``, in
    ``set_up`` before any weight is made, so the run ends at once with a
    non-zero exit code."""
    import sys

    import horovod_tpu.models as models

    cell = cells.resolve(CELL)
    monkeypatch.delattr(models, "lfm2")
    monkeypatch.setitem(sys.modules, "horovod_tpu.models.lfm2", None)
    with pytest.raises(ImportError):
        cell.code.init_params(cell.config, cell.job, None)
    with pytest.raises(ImportError):
        cell.code.loss_fn(cell.config, cell.job)
