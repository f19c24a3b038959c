"""The Granite 4.0-H configuration and its cell as ``BENCHMARK.json`` lists
them (PR 38 appended one configuration, one one-chip cell, three per-layer
metrics, and the cell's name to the ``workloads`` of the accepted metrics
whose readers find something to read in it): the entries are in the file's
form and listed once, what stood before them stands in its order, the files
say what they say, every catalog key is as published or listed as reduced,
the FLOPs are hand arithmetic at the published sizes, the toy cell goes
through ``run.py`` on the CPU (in a temporary copy of the benchmark whose
``rehearsal.json`` has gained the cell, nothing that was there edited), the
three readers read a made-up trace, and the roofline's count is the
recurrence's and knows nothing of the chunk. Nothing here holds the cell to
a place in its list or the lists to a length."""

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

CONFIG = "granite-4.0-h-micro"
CELL = "granite-4.0-h-micro_s4096_dp1"
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
CATALOG = {  # architectures.jsonl's `config`, granite-4.0-h-micro
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": PERIOD * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = ("ssd_scan_ms", "ssd_scan_roofline", "ssd_mix_ms")
S = 4096
REPORTS_TOO = (  # accepted metrics whose readers find something here
    "step_trace_lower_s", "hbm_temporaries_gib", "unowned_ms",
    "shared_fusion_ms", "embed_ms", "attn_proj_ms", "norm_ms", "ffn_ms",
    "head_ms")
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
        assert differs == {"num_hidden_layers", "layer_types", "vocab_size"}
        assert set(cell.config["reduced"]) == set(entry["reduced"]) == differs
        assert entry["source"] in cell.config["source"]
        assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
        # no width is among them
        assert not [key for key in entry["reduced"]
                    if key.endswith(("_dim", "_rank", "_size", "_head",
                                     "_state", "_expand"))
                    and key != "vocab_size"]
        # one whole period, the source's first ten; an eighth of the rows
        assert cell.config["layer_types"] == PERIOD == CATALOG[
            "layer_types"][:10]
        assert cell.config["num_hidden_layers"] == 10
        assert cell.config["vocab_size"] * 8 == 100352
        assert cell.config["published"]["num_hidden_layers"] == 40
        assert cell.config["published"]["vocab_size"] == 100352
        for said in ("split eight ways", "every layer whole",
                     "pipeline stages", "idle share"):
            assert said in cell.config["deployment"]

    @pytest.mark.parametrize("item", [
        "layers", "attention_scale_in_the_kernels", "chunked_scan",
        "recomputation", "initialisation", "inputs", "optimizer",
        "parameters"])
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
            assert len(correct[why]) > 40 and "TO BE SET" not in correct[why]
        # what a hand-made lowered precision and a stopped leaf read
        for said in ("bfloat16", "stopped"):
            assert said in correct["gradient_norm_rel_why"]

    def test_the_cell_is_the_issues(self):
        cell = cells.resolve(CELL)
        assert (cell.chips, cell.measured, cell.rows) == (1, True, 1)
        assert cell.job["seq_len"] == S
        assert S <= cell.config["max_position_embeddings"]
        assert S % cell.config["mamba_chunk_size"] == 0
        assert (cell.job["sync_mode"], cell.job["compression"],
                cell.job["reference_block_rows"]) == ("allreduce", "bf16", 1)
        assert len(cell.job["loss_after_warmup"]) >= 10
        assert cell.config["training"]["attention"] == "flash"
        assert cell.config["training"]["remat"] is True
        assert cell.config["training"]["compute_dtype"] == "bfloat16"
        assert cell.code.min_pallas_calls(cell.config) == 3
        assert cell.code.units_per_step(cell.job, 1) == (S, "tokens")
        assert {e["name"] for e, _, _ in cells.layer_metrics(CELL)} == {
            *NEW_METRICS, *REPORTS_TOO, *EVERY_CELLS}

    def test_what_is_listed_is_in_the_files_form(self):
        config, cell = listed("configs", CONFIG), listed("workloads", CELL)
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert cell["config"] == CONFIG and cell["traffic"] == CELL
        assert cell["chips"] == 1
        assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
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
                  "smallthinker-21b-a3b", "sdar-30b-a3b"]
        assert configs[:len(before)] == before
        assert configs.index(CONFIG) >= len(before)
        workloads = [w["name"] for w in bench["workloads"]]
        before = ["bert-large_s512_dp1", "bert-large_s128_dp1",
                  "bert-large_s512_dp4", "resnet50_b128_dp1",
                  "olmoe-1b-7b_s4096_e16_dp1", "olmo-hybrid-7b_s4096_dp1",
                  "smallthinker-21b-a3b_s16384_e16_dp1",
                  "bert-large_s512_fsdp4", "sdar-30b-a3b_s8192_b4_e16_dp1"]
        assert workloads[:len(before)] == before
        assert workloads.index(CELL) >= len(before)
        metrics = [e["name"] for e in bench["per_layer"]]
        assert metrics.index("blockdiff_attn_glue_ms") < min(
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
            assert cells_of.index(CELL) > cells_of.index(
                "bert-large_s512_dp1")
            assert entries[name]["moves"] in ("step_ms", "hbm_gib", "setup_s")
        assert {name for name, entry in entries.items()
                if CELL in entry.get("workloads", ())} == {
            *REPORTS_TOO, *NEW_METRICS}
        # lists that other cells' tests hold to their own cell alone
        assert not {"recompute_ms", "linattn_scan_ms", "linattn_mix_ms",
                    "gqa_full_attn_kernel_ms"} & set(REPORTS_TOO)

    def test_the_new_metrics_belong_to_this_cell_alone(self):
        for name in NEW_METRICS:
            entry = listed("per_layer", name)
            assert entry["workloads"] == [CELL]
            assert entry["moves"] == "step_ms"
            assert entry["source"] == "device_trace"
        assert listed("per_layer", "ssd_scan_roofline")["unit"] == "%"
        assert listed("per_layer", "ssd_scan_roofline")["better"] == "higher"
        assert listed("per_layer", "ssd_scan_ms")["layer"] == "kernels"
        assert listed("per_layer", "ssd_mix_ms")["layer"] == "state_space"

    def test_the_model_is_built_at_the_published_widths(self):
        from horovod_tpu.models import granite

        cell = cells.resolve(CELL)
        built = cell.code.model_config(cell.config)
        assert built == granite.GraniteConfig(
            vocab_size=12544, num_layers=10, layer_types=tuple(PERIOD))
        assert built.query_scale == 0.125 and built.remat

    def test_parameters_are_what_the_file_says(self):
        import jax

        cell = cells.resolve(CELL)
        shapes = jax.eval_shape(
            lambda key: cell.code.init_params(cell.config, cell.job, key),
            jax.random.PRNGKey(0))
        leaves = jax.tree.leaves(shapes)
        assert len(leaves) == 118
        assert sum(int(np.prod(leaf.shape)) for leaf in leaves) == 772160448
        assert "772,160,448 in 118 leaves" in cell.config["assumed"][
            "parameters"]
        assert shapes["layer_0"]["mamba"]["in_proj"]["kernel"].shape == (
            2048, 8512)
        assert shapes["layer_5"]["attention"]["key"]["kernel"].shape == (
            2048, 512)
        assert shapes["layer_9"]["mlp"]["input"]["kernel"].shape == (
            2048, 16384)
        assert shapes["embedding"].shape == (12544, 2048)
        assert "lm_head" not in shapes

    def test_the_batch_is_rows_of_ids_from_the_slice(self):
        import jax

        cell = cells.resolve(CELL)
        batch = cell.code.make_batch(cell.config, dict(cell.job, seq_len=512),
                                     jax.random.PRNGKey(2147483650), 3)
        assert batch.shape == (3, 513)
        assert 0 <= int(batch.min()) and int(batch.max()) < 12544

    def test_flops_are_hand_arithmetic_at_the_published_sizes(self):
        cell = cells.resolve(CELL)
        macs = cell.code.macs_per_token(cell.config, S)
        assert macs == {
            "mamba_projections": 2048 * 8512 + 4096 * 2048,     # 25.82 M
            "short_conv": 4 * 4352,
            "recurrence": 3 * 64 * 64 * 128,                    # 1.57 M
            "attention_projections": 2 * 2048 * 2048 + 2 * 2048 * 512,
            "causal_scores": 2 * (S / 2) * 2048,                # 8.39 M
            "feed_forward": 2048 * 16384 + 8192 * 2048,         # 50.33 M
            "head": 2048 * 12544}
        per_token = (
            9 * (macs["mamba_projections"] + macs["short_conv"]
                 + macs["recurrence"] + macs["feed_forward"])
            + macs["attention_projections"] + macs["causal_scores"]
            + macs["feed_forward"] + macs["head"])
        flops = cell.code.flops_per_step(cell.config, cell.job, 1)
        assert flops == 6 * per_token * S
        # the issue's count: 19.0 TFLOP of products by parameters, 0.2 of
        # attention's scores, 0.35 of the scan by the recurrence
        assert flops == pytest.approx(1.95e13, rel=1e-2)
        assert 6 * 772160448 * S == pytest.approx(1.90e13, rel=1e-2)
        assert 6 * macs["causal_scores"] * S == pytest.approx(
            0.2e12, rel=0.05)
        assert 6 * 9 * macs["recurrence"] * S == pytest.approx(
            0.35e12, rel=0.01)
        assert cell.code.flops_per_step(cell.config, cell.job, 2) == (
            2 * flops)


class TestReaders:
    """A made-up trace of one device and two steps: a Mamba-2 layer's
    convolution, scan (two fusions and the loop that carries the states,
    whose own event covers the event inside it) and gate forward, the same
    recomputed, and the backward pass."""

    STACK = "jit(spmd_step)/shard_map/"
    FWD = STACK + "jvp(Granite)/layer_0/mamba/"
    AGAIN = (STACK + "transpose(jvp(Granite))/rematted_computation/"
             "layer_0/mamba/")
    BWD = STACK + "transpose(jvp(Granite))/layer_0/mamba/"
    HLO = f"""
  %fusion.1 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f.1, metadata={{op_name="{FWD}in_proj/dot_general"}}
  %fusion.2 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f.2, metadata={{op_name="{FWD}hvd.ssm.conv/mul"}}
  %fusion.3 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f.3, metadata={{op_name="{FWD}hvd.ssm.scan/exp"}}
  %while.4 = f32[8]{{0}} while(%p), condition=%c.4, body=%b.4, metadata={{op_name="{FWD}hvd.ssm.scan/while"}}
  %fusion.5 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f.5, metadata={{op_name="{FWD}hvd.ssm.scan/while/body/mul"}}
  %fusion.6 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f.6, metadata={{op_name="{FWD}hvd.ssm.gate/mul"}}
  %fusion.7 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f.7, metadata={{op_name="{AGAIN}hvd.ssm.conv/mul"}}
  %fusion.8 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f.8, metadata={{op_name="{AGAIN}hvd.ssm.scan/exp"}}
  %fusion.9 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f.9, metadata={{op_name="{AGAIN}hvd.ssm.gate/mul"}}
  %fusion.10 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f.10, metadata={{op_name="{BWD}transpose(jvp(hvd.ssm.gate))/mul"}}
  %fusion.11 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f.11, metadata={{op_name="{BWD}transpose(jvp(hvd.ssm.scan))/dot_general"}}
  %fusion.12 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f.12, metadata={{op_name="{BWD}transpose(jvp(hvd.ssm.conv))/mul"}}
  %fusion.13 = f32[8]{{0}} fusion(%s), kind=kLoop, calls=%f.13, metadata={{op_name="{STACK}hvd.optimizer/add"}}
"""
    OPS = [
        Op("fusion.1", "fusion", 0.0, 1.0),
        Op("fusion.2", "fusion", 1.0, 1.25),        # conv
        Op("fusion.3", "fusion", 1.25, 2.0),        # scan
        Op("while.4", "while", 2.0, 3.0),           # scan: the loop
        Op("fusion.5", "fusion", 2.25, 2.75),       # scan: inside the loop
        Op("fusion.6", "fusion", 3.0, 3.5),         # gate
        Op("fusion.7", "fusion", 3.5, 3.75),        # conv, recomputed
        Op("fusion.8", "fusion", 3.75, 4.5),        # scan, recomputed
        Op("fusion.9", "fusion", 4.5, 5.0),         # gate, recomputed
        Op("fusion.10", "fusion", 5.0, 5.5),        # gate, backward
        Op("fusion.11", "fusion", 5.5, 7.5),        # scan, backward
        Op("fusion.12", "fusion", 7.5, 8.0),        # conv, backward
        Op("fusion.13", "fusion", 8.0, 8.5),
    ]
    TRACE = Trace({0: OPS}, {0: []}, [], (0.0, 10.0))
    SCAN_S = 0.75 + 1.0 + 0.75 + 2.0    # the loop's inside counted once
    MIX_S = 0.25 + 0.5 + 0.25 + 0.5 + 0.5 + 0.5

    @pytest.fixture()
    def run(self, monkeypatch):
        import horovod_tpu as hvd

        monkeypatch.setattr(hvd.profiler, "step_texts", lambda: [self.HLO])
        return types.SimpleNamespace(
            trace=self.TRACE, steps=2, peak=PEAK, call_s=[0.001],
            cell=cells.resolve(CELL))

    def test_scan_and_mix_are_told_apart_and_a_loop_counts_once(
            self, run, capsys):
        assert reader("ssd_scan_ms").read(
            run, parameters("ssd_scan_ms")) == pytest.approx(
                self.SCAN_S / 2 * 1e3)
        # the plain sum counts the loop's inside twice, and says so
        assert f"{(self.SCAN_S + 0.5) / 2 * 1e3:.3f}" in (
            capsys.readouterr().out)
        assert reader("ssd_mix_ms").read(
            run, parameters("ssd_mix_ms")) == pytest.approx(
                self.MIX_S / 2 * 1e3)
        said = capsys.readouterr().out
        assert "hvd.ssm.conv 500.000 ms" in said
        assert "hvd.ssm.gate 750.000 ms" in said

    def test_the_roofline_counts_the_recurrence(self, run, capsys):
        # a layer and pass: forward 3 x 64 x 64 x 128 multiply-adds a token
        # against x, B, C, y in bfloat16 and the step in float32
        forward_flops = 2 * 3 * 64 * 64 * 128 * S
        forward_bytes = S * ((2 * 4096 + 2 * 128) * 2 + 64 * 4)
        backward_bytes = S * ((3 * 4096 + 4 * 128) * 2 + 2 * 64 * 4)
        roofline = reader("ssd_scan_roofline")
        assert roofline.forward_cost(1, S, 64, 64, 128, 1, 2) == (
            forward_flops, forward_bytes)
        assert roofline.backward_cost(1, S, 64, 64, 128, 1, 2) == (
            2 * forward_flops, backward_bytes)
        least = 9 * (max(forward_flops / 197e12, forward_bytes / 819e9)
                     + max(2 * forward_flops / 197e12,
                           backward_bytes / 819e9))
        assert least == pytest.approx(1.95e-3, rel=0.02)
        assert roofline.read(
            run, parameters("ssd_scan_roofline")) == pytest.approx(
                100 * least / (self.SCAN_S / 2))
        said = capsys.readouterr().out
        assert "memory-bound) + " in said and "9 layers" in said

    def test_the_count_knows_nothing_of_the_chunk(self, run):
        """Another chunk, or none, in the configuration: the same share.
        The count is the layer's, not an implementation's."""
        roofline, params = reader("ssd_scan_roofline"), parameters(
            "ssd_scan_roofline")
        want = roofline.read(run, params)
        for chunk in (64, 1024, None):
            config = dict(run.cell.config, mamba_chunk_size=chunk)
            other = types.SimpleNamespace(**{
                **vars(run), "cell": types.SimpleNamespace(
                    config=config, job=run.cell.job)})
            assert roofline.read(other, params) == want
        import inspect
        assert "chunk" not in inspect.signature(
            roofline.forward_cost).parameters

    def test_the_least_time_is_the_operands_traffic_at_the_cells_sizes(self):
        """On the v5e at the published sizes the forward pass is
        memory-bound and the backward pass's two bounds meet within 0.2%:
        the least time is what reading the operands and writing the
        results once takes, which no implementation goes below, so the
        count of operations (the recurrence's three multiply-adds a state
        entry, more than a chunked form with a small chunk puts on the MXU)
        cannot carry the share past 100%."""
        roofline = reader("ssd_scan_roofline")
        flops, nbytes = roofline.forward_cost(1, S, 64, 64, 128, 1, 2)
        assert nbytes == pytest.approx(70.3e6, rel=0.01)
        assert roofline.least_seconds((flops, nbytes), PEAK)[1] == "memory"
        flops, nbytes = roofline.backward_cost(1, S, 64, 64, 128, 1, 2)
        assert flops / 197e12 == pytest.approx(nbytes / 819e9, rel=2e-3)

    def test_the_names_are_the_programs(self):
        from horovod_tpu import attribution

        scan = attribution.SCOPE_PREFIX + attribution.SCOPE_SSM_SCAN
        assert parameters("ssd_scan_ms")["scopes"] == [scan]
        assert parameters("ssd_scan_roofline")["scopes"] == [scan]
        assert parameters("ssd_mix_ms")["scopes"] == [
            attribution.SCOPE_PREFIX + attribution.SCOPE_SSM_CONV,
            attribution.SCOPE_PREFIX + attribution.SCOPE_SSM_GATE]
        assert set(parameters("ssd_mix_ms")["scopes"]) | {scan} <= set(
            attribution.PHASE_SCOPE_NAMES)

    def test_a_program_without_the_scopes_reads_nothing(self, monkeypatch):
        """What a program older than the scopes would give (the parent's
        ``phase_of`` knows no ``hvd.ssm.*``): the three metrics are left
        out and nothing raises."""
        import horovod_tpu as hvd

        before = self.HLO.replace("hvd.ssm.", "ssm_")
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
        "name": "rehearsal-granite_dp1", "config": "rehearsal-granite",
        "traffic": "rehearsal-granite_dp1", "chips": 1})
    (copy / "rehearsal.json").write_text(json.dumps(rehearsed))
    cache = tmp_path_factory.mktemp("compile-cache")
    proc = run_cell("rehearsal-granite_dp1", trace=0, cache=cache,
                    root=str(tmp_path), seed=2147483650)
    result = result_of(proc)
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert set(result["metrics"]) == {"step_ms", "setup_s"}
    assert "46 leaves, 2 rows a step" in proc.stdout
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
    checkout, whose ``horovod_tpu.models`` has no ``granite``: the first
    thing the harness asks of the configuration's code raises
    ``ImportError``, in ``set_up`` before any weight is made, so the run
    ends at once with a non-zero exit code."""
    import sys

    import horovod_tpu.models as models

    cell = cells.resolve(CELL)
    monkeypatch.delattr(models, "granite")
    monkeypatch.setitem(sys.modules, "horovod_tpu.models.granite", None)
    with pytest.raises(ImportError):
        cell.code.init_params(cell.config, cell.job, None)
    with pytest.raises(ImportError):
        cell.code.loss_fn(cell.config, cell.job)
