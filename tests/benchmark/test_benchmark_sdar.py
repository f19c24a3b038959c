"""The SDAR configuration and its cell as ``BENCHMARK.json`` lists them (PR
36 appended one configuration, one one-chip cell, three per-layer metrics, and
the cell's name to the ``workloads`` of the ten accepted metrics whose readers
find something to read in it): the entries are in the file's form and at the
end of their lists, the files say what they say, every catalog key is as
published or listed as reduced, the FLOPs are hand arithmetic at the published
sizes, the benchmark's own noise is the program's bit for bit, the toy cell
goes through ``run.py`` on the CPU (in a temporary copy of the benchmark whose
``rehearsal.json`` has gained the cell, nothing that was there edited), the
three readers read a made-up trace, and their cost function counts what a
brute-force count counts."""

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

CELL = "sdar-30b-a3b_s8192_b4_e16_dp1"
CATALOG = {  # architectures.jsonl's `config`, SDAR-30B-A3B-Chat
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = ("blockdiff_attn_kernel_ms", "blockdiff_attn_roofline",
               "blockdiff_attn_glue_ms")
S, B = 8192, 4
REPORTS_TOO = (  # accepted metrics whose readers find something here
    "step_trace_lower_s", "hbm_temporaries_gib", "moe_experts_ms",
    "moe_dispatch_ms", "unowned_ms", "shared_fusion_ms", "embed_ms",
    "attn_proj_ms", "norm_ms", "head_ms")
LISTS_BEFORE = {  # the length of each list before PR 36
    "configs": 5, "workloads": 8, "per_layer": 38}


def reader(name):
    return cells.load_code(BENCHMARK_DIR, "layer_metrics", name + ".py")


def parameters(name):
    return cells.load_json(BENCHMARK_DIR, "layer_metrics", name + ".json")


class TestConfiguration:
    def test_every_catalog_key_is_as_published_or_listed_as_reduced(self):
        cell = cells.resolve(CELL)
        entry, = [c for c in cells.benchmark()["configs"]
                  if c["name"] == "sdar-30b-a3b"]
        differs = {key for key, value in CATALOG.items()
                   if cell.config.get(key, "left out") != value}
        assert differs == {"num_hidden_layers", "vocab_size"}
        assert differs <= set(entry["reduced"])
        assert set(cell.config["reduced"]) == set(entry["reduced"]) == {
            "num_hidden_layers", "experts_here", "vocab_size"}
        assert entry["source"] in cell.config["source"]
        assert entry["file"] == "benchmark/configs/sdar-30b-a3b.json"
        # no width is among them
        assert not [key for key in entry["reduced"]
                    if key.endswith(("_dim", "_rank", "_size"))
                    and key != "vocab_size"]
        assert "eight chips" in cell.config["deployment"]
        assert cell.config["published"]["num_hidden_layers"] == 48
        assert cell.config["published"]["vocab_size"] == 151936
        assert cell.config["vocab_size"] * 8 == 151936
        # the floors: four layers, at least 8 routed experts, an eighth
        assert cell.config["num_hidden_layers"] >= 4
        assert cell.config["experts_here"] >= 8

    @pytest.mark.parametrize("item", [
        "layer", "mask", "block_length", "noise_schedule", "mask_id", "loss",
        "capacity_factor", "recomputation", "fixed_noise", "initialisation",
        "inputs", "optimizer", "parameters"])
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

    def test_the_cell_is_the_issues(self):
        cell = cells.resolve(CELL)
        assert (cell.chips, cell.measured, cell.rows) == (1, True, 1)
        assert cell.job["seq_len"] == S
        assert 2 * S <= cell.config["max_position_embeddings"]
        assert cell.config["block_length"] == B
        assert (cell.job["sync_mode"], cell.job["compression"],
                cell.job["reference_block_rows"],
                cell.job["trace_groups"]) == ("allreduce", "bf16", 1, 1)
        assert len(cell.job["loss_after_warmup"]) >= 10
        assert cell.config["training"]["attention"] == "flash"
        assert cell.code.min_pallas_calls(cell.config) == 24
        assert cell.code.units_per_step(cell.job, 1) == (S, "tokens")
        assert {e["name"] for e, _, _ in cells.layer_metrics(CELL)} == {
            *NEW_METRICS, *REPORTS_TOO, "device_idle_share",
            "host_call_ms", "compile_s", "hbm_buffers_gib",
            "hbm_setup_peak_gib"}

    def test_what_is_listed_is_one_of_each_and_three_metrics(self):
        bench = cells.benchmark()
        new = {key: bench[key][before:]
               for key, before in LISTS_BEFORE.items()}
        config, = new["configs"]
        cell, = new["workloads"]
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert (config["name"], cell["config"]) == ("sdar-30b-a3b",) * 2
        assert cell["name"] == cell["traffic"] == CELL
        assert cell["chips"] == 1
        assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
        assert [e["name"] for e in new["per_layer"]] == list(NEW_METRICS)
        for entry in new["per_layer"]:
            assert set(entry) == {"name", "unit", "better", "source",
                                  "layer", "moves", "workloads"}

    def test_it_fits_where_it_went(self):
        """The file repeats no name, the cell is the last of nine, what
        was there stands in front of it in the order it had, and a
        quarter of nine cells may take four chips."""
        bench = cells.benchmark()
        for key in LISTS_BEFORE:
            names = [entry["name"] for entry in bench[key]]
            assert len(names) == len(set(names))
        assert [c["name"] for c in bench["configs"]][:-1] == [
            "bert-large", "resnet50", "olmoe-1b-7b", "olmo-hybrid-7b",
            "smallthinker-21b-a3b"]
        assert [w["name"] for w in bench["workloads"]][-3:] == [
            "smallthinker-21b-a3b_s16384_e16_dp1", "bert-large_s512_fsdp4",
            CELL]
        four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
        assert len(four) == 2 <= len(bench["workloads"]) // 4
        with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
            assert len(f.read()) < 64 * 1024

    def test_the_accepted_metrics_it_reports_too_list_their_cells(self):
        """Each is an accepted metric with a ``workloads`` list, which
        has the cell at its end, once, and moves an end-to-end metric the
        cell reports; no other accepted metric names the cell."""
        entries = {e["name"]: e for e in cells.benchmark()["per_layer"]}
        assert len(set(REPORTS_TOO)) == 10
        for name in REPORTS_TOO:
            assert entries[name]["workloads"][-1] == CELL
            assert entries[name]["workloads"].count(CELL) == 1
            assert entries[name]["moves"] in ("step_ms", "hbm_gib", "setup_s")
        assert {name for name, entry in entries.items()
                if CELL in entry.get("workloads", ())} == {
            *REPORTS_TOO, *NEW_METRICS}
        assert not {"ffn_ms", "recompute_ms"} & set(REPORTS_TOO)

    def test_the_new_metrics_belong_to_this_cell_alone(self):
        entries = {e["name"]: e for e in cells.benchmark()["per_layer"]}
        for name in NEW_METRICS:
            assert entries[name]["workloads"] == [CELL]
            assert entries[name]["moves"] == "step_ms"
            assert entries[name]["source"] == "device_trace"
            assert entries[name]["layer"] == "kernels"
        assert entries["blockdiff_attn_roofline"]["unit"] == "%"

    def test_the_model_is_built_at_the_published_widths(self):
        from horovod_tpu.models import sdar

        cell = cells.resolve(CELL)
        built = cell.code.model_config(cell.config)
        assert built == sdar.SdarConfig(
            vocab_size=18992, num_layers=4, experts_here=16,
            remat=cell.config["training"]["remat"])
        assert built.capacity(2 * cell.job["seq_len"]) == 1280
        assert built.mask_id == 18991

    def test_parameters_are_what_the_file_says(self):
        import jax

        cell = cells.resolve(CELL)
        shapes = jax.eval_shape(
            lambda key: cell.code.init_params(cell.config, cell.job, key),
            jax.random.PRNGKey(0))
        leaves = jax.tree.leaves(shapes)
        assert len(leaves) == 51
        assert sum(int(np.prod(leaf.shape)) for leaf in leaves) == (
            pytest.approx(456.3e6, rel=1e-3))
        layer = shapes["layer_1"]
        assert layer["attention"]["key"]["kernel"].shape == (2048, 512)
        assert layer["attention"]["query"]["kernel"].shape == (2048, 4096)
        assert layer["attention"]["q_norm"]["scale"].shape == (128,)
        assert layer["moe"]["router"].shape == (2048, 128)
        assert layer["moe"]["experts_down"].shape == (16, 768, 2048)
        assert shapes["lm_head"].shape == (2048, 18992)

    def test_the_benchmarks_own_noise_is_the_programs(self):
        """``make_batch`` draws levels, masks and weights itself;
        ``models.sdar.noisy_batch`` follows the same recipe from the same
        key, so a wrong ``1 / t`` or masking probability on either side
        shows here, not on both sides of ``correct`` alike."""
        import jax

        from horovod_tpu.models import sdar

        cell = cells.resolve(CELL)
        job = dict(cell.job, seq_len=512)
        key = jax.random.PRNGKey(2147483650)
        made = cell.code.make_batch(cell.config, job, key, 3)
        _, noise_key = jax.random.split(key)
        want = sdar.noisy_batch(noise_key, made["clean"], B,
                                cell.config["vocab_size"] - 1)
        for name in ("clean", "noisy", "weight"):
            np.testing.assert_array_equal(made[name], want[name])
        masked = np.asarray(made["noisy"] != made["clean"])
        assert masked.mean() == pytest.approx(0.625, abs=0.03)
        assert (np.asarray(made["clean"]) < 18991).all()
        assert (np.asarray(made["noisy"])[masked] == 18991).all()
        weight = np.asarray(made["weight"])
        assert (weight[~masked] == 0).all()
        assert (weight[masked] >= 1).all() and (weight[masked] <= B).all()

    def test_the_batch_is_a_tree_of_rows(self):
        import jax

        cell = cells.resolve(CELL)
        batch = jax.eval_shape(
            lambda key: cell.code.make_batch(cell.config, cell.job, key, 2),
            jax.random.PRNGKey(0))
        assert sorted(batch) == ["clean", "noisy", "weight"]
        assert {leaf.shape for leaf in batch.values()} == {(2, S)}
        assert batch["weight"].dtype == np.float32

    def test_flops_are_hand_arithmetic_at_the_published_sizes(self):
        cell = cells.resolve(CELL)
        macs = cell.code.macs_per_token(cell.config, S)
        # per stream position and layer: the issue's hand count
        projections = 2048 * 4096 * 2 + 2 * 2048 * 512          # 18.87 M
        attention = 2 * 4096 * (S + B) / 2                      # 33.6 M
        experts = 1 * 3 * 2048 * 768                            # 8 x 16 / 128
        router = 2048 * 128
        assert projections == pytest.approx(18.87e6, rel=1e-3)
        assert attention == pytest.approx(33.6e6, rel=1e-3)
        assert experts == pytest.approx(4.72e6, rel=1e-3)
        assert macs == {
            "projections": 4 * 2 * projections,   # two stream positions
            "scores": 4 * 2 * attention,
            "router": 4 * 2 * router,
            "experts": 4 * 2 * experts,
            "head": 2048 * 18992}                 # a noisy position
        assert macs["head"] == pytest.approx(38.9e6, rel=1e-3)
        flops = cell.code.flops_per_step(cell.config, cell.job, 1)
        assert flops == 6 * sum(macs.values()) * S
        assert flops == pytest.approx(2.45e13, rel=5e-3)
        assert 6 * macs["scores"] * S / flops == pytest.approx(0.54, abs=0.005)
        assert cell.code.flops_per_step(cell.config, cell.job, 2) == (
            2 * flops)

    @pytest.mark.parametrize("seq, length", [(64, 4), (64, 8), (16, 2),
                                             (48, 16), (8, 8)])
    def test_visible_pairs_are_a_brute_force_count(self, seq, length):
        from horovod_tpu.models import sdar

        cell = cells.resolve(CELL)
        seen = np.asarray(sdar.visible(length, seq))
        pairs = cell.code.visible_pairs(seq, length)
        assert pairs == {"clean": seen[seq:, seq:].sum(),
                         "past": seen[:seq, seq:].sum(),
                         "own": seen[:seq, :seq].sum()}
        assert sum(pairs.values()) == seq * (seq + length)
        assert reader("blockdiff_attn_roofline").kernel_pairs(
            seq, length) == {"clean": pairs["clean"], "past": pairs["past"]}
        assert pairs["clean"] + pairs["past"] == seq * seq


class TestReaders:
    """A made-up trace of one device and two steps: one layer's two kernel
    calls forward, the glue around them, a recomputed layer and the four
    backward kernels."""

    STACK = "jit(spmd_step)/shard_map/"
    FWD = STACK + "jvp(Sdar)/layer_0/attention/hvd.attn.blockdiff/"
    BWD = (STACK + "transpose(jvp(Sdar))/jvp(Sdar)/checkpoint/layer_0/"
           "attention/transpose(jvp(hvd.attn.blockdiff))/")
    HLO = f"""
  %fusion.1 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f.1, metadata={{op_name="{STACK}jvp(Sdar)/layer_0/attention/query/dot_general"}}
  %copy.2 = bf16[8]{{0}} copy(%q), metadata={{op_name="{FWD}slice"}}
  %flash_attention.3 = bf16[8]{{0}} custom-call(%q), custom_call_target="tpu_custom_call", metadata={{op_name="{FWD}jit(flash_attention)/hvd.attn.fwd/flash_attention/pallas_call"}}
  %flash_attention.4 = bf16[8]{{0}} custom-call(%q), custom_call_target="tpu_custom_call", metadata={{op_name="{FWD}jit(flash_attention_lse)/hvd.attn.fwd/flash_attention/pallas_call"}}
  %fusion.5 = f32[8]{{0}} fusion(%q), kind=kLoop, calls=%f.5, metadata={{op_name="{FWD}reduce_sum"}}
  %fusion.6 = f32[8]{{0}} fusion(%q), kind=kLoop, calls=%f.6, metadata={{op_name="{FWD}exp"}}
  %fusion.7 = bf16[8]{{0}} fusion(%g), kind=kOutput, calls=%f.7, metadata={{op_name="{STACK}jvp(Sdar)/layer_0/moe/vmap(hvd.moe.experts)/ecd,edh->ech/dot_general"}}
  %fusion.8 = f32[8]{{0}} fusion(%g), kind=kLoop, calls=%f.8, metadata={{op_name="{BWD}mul"}}
  %flash_attention.9 = bf16[8]{{0}} custom-call(%q), custom_call_target="tpu_custom_call", metadata={{op_name="{BWD}jit(flash_attention_lse)/hvd.attn.bwd/flash_attention/pallas_call"}}
  %flash_attention.10 = bf16[8]{{0}} custom-call(%q), custom_call_target="tpu_custom_call", metadata={{op_name="{BWD}jit(flash_attention_lse)/hvd.attn.bwd/flash_attention/pallas_call"}}
  %flash_attention.11 = bf16[8]{{0}} custom-call(%q), custom_call_target="tpu_custom_call", metadata={{op_name="{BWD}jit(flash_attention)/hvd.attn.bwd/flash_attention/pallas_call"}}
  %flash_attention.12 = bf16[8]{{0}} custom-call(%q), custom_call_target="tpu_custom_call", metadata={{op_name="{BWD}jit(flash_attention)/hvd.attn.bwd/flash_attention/pallas_call"}}
  %fusion.13 = bf16[8]{{0}} fusion(%g), kind=kLoop, calls=%f.13, metadata={{op_name="{BWD}add_any"}}
  %fusion.14 = f32[8]{{0}} fusion(%s), kind=kLoop, calls=%f.14, metadata={{op_name="{STACK}hvd.optimizer/add"}}
"""
    OPS = [
        Op("fusion.1", "fusion", 0.0, 1.0),
        Op("copy.2", "copy", 1.0, 1.25),                      # glue
        Op("flash_attention.3", "custom-call", 1.25, 2.0),    # clean fwd
        Op("flash_attention.4", "custom-call", 2.0, 2.5),     # past fwd
        Op("fusion.5", "fusion", 2.5, 2.75),                  # glue
        Op("fusion.6", "fusion", 2.75, 3.0),                  # glue
        Op("fusion.7", "fusion", 3.0, 3.5),
        Op("fusion.8", "fusion", 3.5, 3.75),                  # glue, bwd
        Op("flash_attention.9", "custom-call", 3.75, 4.5),    # past dq
        Op("flash_attention.10", "custom-call", 4.5, 5.0),    # past dkv
        Op("flash_attention.11", "custom-call", 5.0, 6.0),    # clean dq
        Op("flash_attention.12", "custom-call", 6.0, 7.0),    # clean dkv
        Op("fusion.13", "fusion", 7.0, 7.5),                  # glue, bwd
        Op("fusion.14", "fusion", 7.5, 8.0),
    ]
    TRACE = Trace({0: OPS}, {0: []}, [], (0.0, 10.0))

    @pytest.fixture()
    def run(self, monkeypatch):
        import horovod_tpu as hvd

        monkeypatch.setattr(hvd.profiler, "step_texts", lambda: [self.HLO])
        return types.SimpleNamespace(
            trace=self.TRACE, steps=2, peak=PEAK, call_s=[0.001],
            cell=cells.resolve(CELL))

    def test_kernels_and_glue_are_told_apart(self, run):
        assert reader("blockdiff_attn_kernel_ms").read(
            run, parameters("blockdiff_attn_kernel_ms")) == pytest.approx(
                (0.75 + 0.5 + 0.75 + 0.5 + 1.0 + 1.0) / 2 * 1e3)
        assert reader("blockdiff_attn_glue_ms").read(
            run, parameters("blockdiff_attn_glue_ms")) == pytest.approx(
                (0.25 + 0.25 + 0.25 + 0.25 + 0.5) / 2 * 1e3)

    def test_the_roofline_counts_the_kernels_pairs(self, run, capsys):
        # compute-bound: 32 query heads x 2 (5) products x 2 x S^2 x 128,
        # four layers a step, two steps, over 4.5 s of kernels
        least = 4 * 32 * 7 * 2 * S * S * 128 / 197e12
        assert reader("blockdiff_attn_roofline").read(
            run, parameters("blockdiff_attn_roofline")) == pytest.approx(
                100 * least * 2 / 4.5)
        said = capsys.readouterr().out
        assert f"{S * S} pairs a head" in said
        assert "compute-bound" in said and "memory-bound" not in said

    def test_a_layers_cost_is_two_calls_of_the_window_readers(self):
        roofline = reader("blockdiff_attn_roofline")
        window = reader("window_attn_roofline")
        seq, dim, item = 1024, 128, 2
        cost = roofline.layer_cost(32, 4, seq, dim, item, 4)
        pairs = roofline.kernel_pairs(seq, 4)
        assert pairs == {"clean": seq * (seq + 4) // 2,
                         "past": seq * (seq - 4) // 2}
        for name, one in (("forward", window.forward_cost),
                          ("backward", window.backward_cost)):
            calls = [one(32, 4, seq, dim, item, p) for p in pairs.values()]
            assert cost[name] == (calls[0][0] + calls[1][0],
                                  calls[0][1] + calls[1][1])
        assert cost["forward"][0] == 32 * 2 * 2 * seq * seq * dim

    @pytest.mark.parametrize("seq, tile", [(256, 32), (128, 16), (64, 64)])
    def test_the_counted_pairs_never_pass_what_the_kernels_compute(
            self, seq, tile):
        """The numerator counts exactly the pairs the two calls' masks
        leave, and the kernels compute whole tiles: a share cannot pass
        100% by it."""
        from horovod_tpu.ops import attention

        pairs = reader("blockdiff_attn_roofline").kernel_pairs(seq, 4)
        blocks = seq // tile
        for name, before in (("clean", False), ("past", True)):
            computed = attention._tile_plan(
                True, blocks, blocks, tile, tile, 0, 0, None,
                attention._behind((4, before)))[0] * tile * tile
            assert pairs[name] <= computed

    def test_the_names_are_the_programs(self):
        from horovod_tpu import attribution

        for name in NEW_METRICS:
            assert parameters(name)["scope"] == (
                attribution.SCOPE_PREFIX + attribution.SCOPE_ATTN_BLOCKDIFF)
            assert parameters(name)["kernel_names"] == parameters(
                "causal_attn_kernel_ms")["kernel_names"]

    def test_a_program_without_the_scope_reads_nothing(self, monkeypatch):
        """What a program older than the scope would give: the three
        metrics are left out and nothing raises."""
        import horovod_tpu as hvd

        before = self.HLO.replace("hvd.attn.blockdiff", "attention")
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
    listed = json.loads((copy / "rehearsal.json").read_text())
    listed["workloads"].append({
        "name": "rehearsal-sdar_dp1", "config": "rehearsal-sdar",
        "traffic": "rehearsal-sdar_dp1", "chips": 1})
    (copy / "rehearsal.json").write_text(json.dumps(listed))
    cache = tmp_path_factory.mktemp("compile-cache")
    proc = run_cell("rehearsal-sdar_dp1", trace=0, cache=cache,
                    root=str(tmp_path), seed=2147483650)
    result = result_of(proc)
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert set(result["metrics"]) == {"step_ms", "setup_s"}
    assert "27 leaves, 2 rows a step" in proc.stdout
    for check in ("loss_vs_reference", "gradient_norms_vs_reference",
                  "loss_after_warmup", "kernels_in_step", "losses_finite"):
        assert f"check {check}: ok" in proc.stdout, proc.stdout[-3000:]


def test_a_checkout_that_lacks_the_cell_stops_at_once(monkeypatch):
    """Where ``BENCHMARK.json`` does not list the cell, as the parent's
    does not, ``run.py`` says so and runs nothing: what the driver's trial
    of a new cell on its parent sees."""
    listed = cells.benchmark()
    without = dict(listed, workloads=[
        w for w in listed["workloads"] if w["name"] != CELL])
    monkeypatch.setattr(cells, "benchmark", lambda: without)
    with pytest.raises(SystemExit, match="no cell named"):
        cells.resolve(CELL)
