"""The SmallThinker configuration and its cell, and the queued
``bert-large_s512_fsdp4``: the files say what ``BENCHMARK.json`` says, the
FLOPs are hand arithmetic at the published sizes, the toy cell goes through
``run.py`` on the CPU (in a temporary copy of the benchmark whose
``rehearsal.json`` has gained the cell, nothing that was there edited), the
five readers read a made-up trace, and their cost functions count what a
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

CELL = "smallthinker-21b-a3b_s16384_e16_dp1"
FSDP_CELL = "bert-large_s512_fsdp4"
LAYOUT = [0, 1, 1, 1] * 13
CATALOG = {  # architectures.jsonl's `config`, SmallThinker-21BA3B-Instruct
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_layout": LAYOUT,
    "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": LAYOUT, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = ("window_attn_kernel_ms", "window_attn_roofline",
               "gqa_full_attn_kernel_ms", "gqa_full_attn_roofline",
               "recompute_ms")


def reader(name):
    return cells.load_code(BENCHMARK_DIR, "layer_metrics", name + ".py")


def parameters(name):
    return cells.load_json(BENCHMARK_DIR, "layer_metrics", name + ".json")


class TestConfiguration:
    def test_every_catalog_key_is_as_published_or_listed_as_reduced(self):
        cell = cells.resolve(CELL)
        entry, = [c for c in cells.benchmark()["configs"]
                  if c["name"] == "smallthinker-21b-a3b"]
        differs = {key for key, value in CATALOG.items()
                   if cell.config.get(key, "left out") != value}
        assert differs == {"num_hidden_layers", "vocab_size"}
        assert differs <= set(entry["reduced"])
        assert set(cell.config["reduced"]) == set(entry["reduced"]) == {
            "num_hidden_layers", "experts_here", "vocab_size"}
        assert entry["source"] in cell.config["source"]
        assert entry["file"] == "benchmark/configs/smallthinker-21b-a3b.json"
        # no width is among them
        assert not [key for key in entry["reduced"]
                    if key.endswith(("_dim", "_rank", "_size"))
                    and key != "vocab_size"]
        assert "four-chip host" in cell.config["deployment"]
        assert cell.config["published"]["num_hidden_layers"] == 52
        assert cell.config["published"]["vocab_size"] == 151936
        assert cell.config["vocab_size"] * 8 == 151936

    @pytest.mark.parametrize("item", [
        "layer", "router_before_attention", "no_secondary_experts", "gates",
        "sparse_reglu", "window_semantics", "capacity_factor",
        "auxiliary_losses", "recomputation", "initialisation", "inputs",
        "optimizer"])
    def test_every_inference_is_written_down(self, item):
        assert len(cells.resolve(CELL).config["assumed"][item]) > 20

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
        assert cell.job["seq_len"] == cell.config[
            "max_position_embeddings"] == 16384
        assert (cell.job["sync_mode"], cell.job["compression"],
                cell.job["reference_block_rows"],
                cell.job["trace_groups"]) == ("allreduce", "bf16", 1, 1)
        assert len(cell.job["loss_after_warmup"]) >= 10
        assert cell.config["training"]["attention"] == "flash"
        assert cell.config["training"]["remat"] is True
        assert cell.code.min_pallas_calls(cell.config) == 12
        assert cell.code.units_per_step(cell.job, 1) == (16384, "tokens")
        assert {e["name"] for e, _, _ in cells.layer_metrics(CELL)} == {
            *NEW_METRICS, "device_idle_share", "host_call_ms", "compile_s",
            "hbm_buffers_gib", "hbm_setup_peak_gib"}

    def test_the_new_metrics_belong_to_this_cell_alone(self):
        entries = {e["name"]: e for e in cells.benchmark()["per_layer"]}
        for name in NEW_METRICS:
            assert entries[name]["workloads"] == [CELL]
            assert entries[name]["moves"] == "step_ms"
            assert entries[name]["source"] == "device_trace"
        assert entries["window_attn_roofline"]["unit"] == "%"
        assert entries["gqa_full_attn_roofline"]["unit"] == "%"

    def test_the_model_is_built_at_the_published_widths(self):
        from horovod_tpu.models import smallthinker

        cell = cells.resolve(CELL)
        built = cell.code.model_config(cell.config)
        assert built == smallthinker.SmallThinkerConfig(
            vocab_size=18992, num_layers=4, experts_here=16,
            sliding_window_layout=(0, 1, 1, 1), rope_layout=(0, 1, 1, 1))
        assert built.windowed == built.rotary == (False, True, True, True)
        assert built.capacity(cell.job["seq_len"]) == 1920
        assert built.remat is True

    def test_parameters_are_what_the_file_says(self):
        import jax

        cell = cells.resolve(CELL)
        shapes = jax.eval_shape(
            lambda key: cell.code.init_params(cell.config, cell.job, key),
            jax.random.PRNGKey(0))
        leaves = jax.tree.leaves(shapes)
        assert len(leaves) == 43
        assert sum(int(np.prod(leaf.shape)) for leaf in leaves) == pytest.approx(
            559.3e6, rel=1e-3)
        layer = shapes["layer_1"]
        assert layer["attention"]["key"]["kernel"].shape == (2560, 512)
        assert layer["attention"]["query"]["kernel"].shape == (2560, 3584)
        assert layer["router"].shape == (2560, 64)
        assert layer["moe"]["experts_down"].shape == (16, 768, 2560)

    def test_flops_are_hand_arithmetic_at_the_published_sizes(self):
        cell = cells.resolve(CELL)
        macs = cell.code.macs_per_token(cell.config, 16384)
        band = 16384 * 4096 - 4096 * 4095 // 2  # pairs of a window layer
        triangle = 16384 * 16385 // 2
        assert macs == {
            "projections": 4 * 2 * 2560 * (3584 + 512),   # q, o; k, v
            "scores": 2 * 3584 * (triangle + 3 * band) / 16384,
            "router": 4 * 2560 * 64,
            "experts": 4 * 1.5 * 3 * 2560 * 768,          # 6 x 16 / 64
            "head": 2560 * 18992}
        flops = cell.code.flops_per_step(cell.config, cell.job, 1)
        assert flops == 6 * sum(macs.values()) * 16384
        assert flops == pytest.approx(3.00e13, rel=5e-3)
        attention = 6 * macs["scores"] * 16384
        assert attention / flops == pytest.approx(0.444, abs=0.005)
        assert cell.code.flops_per_step(cell.config, cell.job, 2) == (
            2 * flops)

    @pytest.mark.parametrize("seq, window", [
        (64, 16), (64, 1), (64, 64), (64, 100), (48, 17), (64, None)])
    def test_visible_pairs_are_a_brute_force_count(self, seq, window):
        cell = cells.resolve(CELL)
        ahead = np.arange(seq)[:, None] - np.arange(seq)[None, :]
        seen = ahead >= 0
        if window is not None:
            seen &= ahead < window
        assert cell.code.visible_pairs(seq, window) == seen.sum()
        assert reader("window_attn_roofline").visible_pairs(
            seq, window) == seen.sum()


class TestTheQueuedFsdpCell:
    def test_the_job_is_dp4s_but_for_the_mode(self):
        cell, dp4 = cells.resolve(FSDP_CELL), cells.resolve(
            "bert-large_s512_dp4")
        assert (cell.chips, cell.measured, cell.rows) == (4, True, 96)
        assert cell.config == dp4.config
        differs = {key for key in dp4.job if key != "loss_after_warmup"
                   and cell.job[key] != dp4.job[key]}
        assert differs == {"sync_mode"}
        assert cell.job["sync_mode"] == "fsdp"
        assert len(cell.job["loss_after_warmup"]) >= 10

    def test_four_chip_cells_are_a_quarter(self):
        workloads = cells.benchmark()["workloads"]
        four = [w["name"] for w in workloads if w["chips"] == 4]
        assert four == ["bert-large_s512_dp4", FSDP_CELL]
        assert len(four) <= len(workloads) // 4
        assert [w["name"] for w in workloads][-2:] == [CELL, FSDP_CELL]

    def test_it_reports_the_collectives_and_the_kernels(self):
        names = {e["name"] for e, _, _ in cells.layer_metrics(FSDP_CELL)}
        assert names >= {"collective_ms", "collective_exposed_ms",
                         "attn_kernel_ms", "flash_attn_roofline",
                         "device_idle_share", "compile_s"}


class TestReaders:
    """A made-up trace of one device and two steps, as
    ``test_benchmark_olmoe.py`` makes them: one full layer and one window
    layer, each a forward kernel and two backward kernels, and a recomputed
    forward inside the backward pass."""

    STACK = "jit(spmd_step)/shard_map/"
    BWD = STACK + "transpose(jvp(SmallThinker))/jvp(SmallThinker)/checkpoint/"
    HLO = f"""
  %fusion.1 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f.1, metadata={{op_name="{STACK}jvp(SmallThinker)/layer_0/attention/query/dot_general"}}
  %flash_attention.2 = bf16[8]{{0}} custom-call(%q), custom_call_target="tpu_custom_call", metadata={{op_name="{STACK}jvp(SmallThinker)/layer_0/attention/jit(flash_attention)/hvd.attn.fwd/flash_attention/pallas_call"}}
  %flash_attention.3 = bf16[8]{{0}} custom-call(%q), custom_call_target="tpu_custom_call", metadata={{op_name="{STACK}jvp(SmallThinker)/layer_1/attention/jit(flash_attention)/hvd.attn.window/hvd.attn.fwd/flash_attention/pallas_call"}}
  %fusion.4 = bf16[8]{{0}} fusion(%g), kind=kOutput, calls=%f.4, metadata={{op_name="{STACK}jvp(SmallThinker)/layer_1/moe/vmap(hvd.moe.experts)/ecd,edh->ech/dot_general"}}
  %fusion.5 = bf16[8]{{0}} fusion(%g), kind=kOutput, calls=%f.5, metadata={{op_name="{BWD}rematted_computation/layer_1/moe/vmap(hvd.moe.experts)/ecd,edh->ech/dot_general"}}
  %fusion.6 = bf16[8]{{0}} fusion(%g), kind=kLoop, calls=%f.6, metadata={{op_name="{BWD}rematted_computation/layer_1/attention/query/dot_general"}}
  %flash_attention.7 = bf16[8]{{0}} custom-call(%q), custom_call_target="tpu_custom_call", metadata={{op_name="{BWD}layer_1/attention/jit(flash_attention)/hvd.attn.window/hvd.attn.bwd/flash_attention/pallas_call"}}
  %flash_attention.8 = bf16[8]{{0}} custom-call(%q), custom_call_target="tpu_custom_call", metadata={{op_name="{BWD}layer_1/attention/jit(flash_attention)/hvd.attn.window/hvd.attn.bwd/flash_attention/pallas_call"}}
  %fusion.9 = bf16[8]{{0}} fusion(%g), kind=kLoop, calls=%f.9, metadata={{op_name="{BWD}rematted_computation/layer_0/ln_attn/mul"}}
  %flash_attention.10 = bf16[8]{{0}} custom-call(%q), custom_call_target="tpu_custom_call", metadata={{op_name="{BWD}layer_0/attention/jit(flash_attention)/hvd.attn.bwd/flash_attention/pallas_call"}}
  %flash_attention.11 = bf16[8]{{0}} custom-call(%q), custom_call_target="tpu_custom_call", metadata={{op_name="{BWD}layer_0/attention/jit(flash_attention)/hvd.attn.bwd/flash_attention/pallas_call"}}
  %fusion.12 = f32[8]{{0}} fusion(%s), kind=kLoop, calls=%f.12, metadata={{op_name="{STACK}hvd.optimizer/add"}}
"""
    OPS = [
        Op("fusion.1", "fusion", 0.0, 1.0),
        Op("flash_attention.2", "custom-call", 1.0, 2.0),    # full fwd
        Op("flash_attention.3", "custom-call", 2.0, 2.5),    # window fwd
        Op("fusion.4", "fusion", 2.5, 3.0),
        Op("fusion.5", "fusion", 3.0, 3.5),                  # recomputed
        Op("fusion.6", "fusion", 3.5, 3.75),                 # recomputed
        Op("flash_attention.7", "custom-call", 3.75, 4.5),   # window dq
        Op("flash_attention.8", "custom-call", 4.5, 5.0),    # window dkv
        Op("fusion.9", "fusion", 5.0, 5.25),                 # recomputed
        Op("flash_attention.10", "custom-call", 5.25, 7.0),  # full dq
        Op("flash_attention.11", "custom-call", 7.0, 8.5),   # full dkv
        Op("fusion.12", "fusion", 8.5, 9.0),
    ]
    TRACE = Trace({0: OPS}, {0: []}, [], (0.0, 10.0))

    @pytest.fixture()
    def run(self, monkeypatch):
        import horovod_tpu as hvd

        monkeypatch.setattr(hvd.profiler, "step_texts", lambda: [self.HLO])
        return types.SimpleNamespace(
            trace=self.TRACE, steps=2, peak=PEAK, call_s=[0.001],
            cell=cells.resolve(CELL))

    def test_the_two_kinds_of_kernel_are_told_apart(self, run):
        assert reader("window_attn_kernel_ms").read(
            run, parameters("window_attn_kernel_ms")) == pytest.approx(
                (0.5 + 0.75 + 0.5) / 2 * 1e3)
        assert reader("gqa_full_attn_kernel_ms").read(
            run, parameters("gqa_full_attn_kernel_ms")) == pytest.approx(
                (1.0 + 1.75 + 1.5) / 2 * 1e3)

    def test_the_recomputed_forward_is_told_from_the_first(self, run):
        assert reader("recompute_ms").read(
            run, parameters("recompute_ms")) == pytest.approx(
                (0.5 + 0.25 + 0.25) / 2 * 1e3)

    def test_the_rooflines_count_pairs_and_key_value_heads(self, run,
                                                           capsys):
        window = reader("window_attn_roofline")
        band = 16384 * 4096 - 4096 * 4095 // 2
        triangle = 16384 * 16385 // 2
        # compute-bound both: 28 query heads x 2 (5) products x 2 x pairs
        # x 128; three window layers and one full layer a step, two steps
        least_window = 3 * 28 * 7 * 2 * band * 128 / 197e12
        least_full = 28 * 7 * 2 * triangle * 128 / 197e12
        assert window.read(run, parameters("window_attn_roofline")) == (
            pytest.approx(100 * least_window * 2 / 1.75))
        assert reader("gqa_full_attn_roofline").read(
            run, parameters("gqa_full_attn_roofline")) == pytest.approx(
                100 * least_full * 2 / 4.25)
        said = capsys.readouterr().out
        assert f"3 window layer(s), {band} pairs a head" in said
        assert f"1 full layer(s), {triangle} pairs a head" in said
        assert "compute-bound" in said and "memory-bound" not in said

    def test_bytes_are_once_a_query_head_or_once_a_key_value_head(self):
        window = reader("window_attn_roofline")
        seq, dim, item = 1024, 128, 2
        block = seq * dim * item
        flops, nbytes = window.forward_cost(28, 4, seq, dim, item, 1000)
        assert flops == 28 * 2 * 2 * 1000 * dim
        assert nbytes == 28 * (2 * block + 4 * seq) + 4 * 2 * block
        flops, nbytes = window.backward_cost(28, 4, seq, dim, item, 1000)
        assert flops == 28 * 5 * 2 * 1000 * dim
        assert nbytes == 28 * (3 * block + 12 * seq) + 4 * 4 * block
        # a head of keys and values a query head: flash_attn_roofline's
        full = reader("flash_attn_roofline")
        assert window.forward_cost(16, 16, seq, dim, item, seq * seq) == (
            full.forward_cost(16, seq, dim, item))
        assert window.backward_cost(16, 16, seq, dim, item, seq * seq) == (
            full.backward_cost(16, seq, dim, item))

    @pytest.mark.parametrize("seq, window, tile", [
        (256, 64, 32), (256, 100, 32), (128, 128, 16)])
    def test_the_counted_pairs_never_pass_what_the_kernels_compute(
            self, seq, window, tile):
        """The roofline's numerator counts exactly the visible pairs, and
        the kernels compute whole tiles: so the count is an undercount of
        nothing and at most what runs, and a share cannot pass 100% by
        it."""
        from horovod_tpu.ops import attention

        roofline = reader("window_attn_roofline")
        ahead = np.arange(seq)[:, None] - np.arange(seq)[None, :]
        brute = int(((ahead >= 0) & (ahead < window)).sum())
        assert roofline.visible_pairs(seq, window) == brute
        blocks = seq // tile
        computed = int(np.asarray(attention._tile_visible(
            np.arange(blocks)[:, None], np.arange(blocks)[None, :], tile,
            tile, 0, 0, window)).sum()) * tile * tile
        assert brute <= computed

    def test_layers_by_kind_come_from_the_layout(self):
        kernels = reader("window_attn_kernel_ms")
        assert kernels.layers(cells.resolve(CELL).config) == {
            "window": 3, "full": 1}
        assert kernels.components(
            "a/transpose(jvp(hvd.attn.window))/vmap(x)/y") == [
                "a", "hvd.attn.window", "x", "y"]

    def test_the_names_are_the_programs(self):
        from horovod_tpu import attribution

        assert parameters("window_attn_kernel_ms")["window_scope"] == (
            attribution.SCOPE_PREFIX + attribution.SCOPE_ATTN_WINDOW)
        assert parameters("recompute_ms")["scope"] == (
            attribution.SCOPE_RECOMPUTE)

    def test_a_program_without_the_window_scope_has_no_window_kernels(
            self, monkeypatch):
        """What the parent's program would give: every kernel is a full
        one, the window metrics are left out and nothing raises."""
        import horovod_tpu as hvd

        before = self.HLO.replace("hvd.attn.window/", "").replace(
            "rematted_computation/", "")
        monkeypatch.setattr(hvd.profiler, "step_texts", lambda: [before])
        run = types.SimpleNamespace(
            trace=self.TRACE, steps=2, peak=PEAK, call_s=[0.001],
            cell=cells.resolve(CELL))
        for name in ("window_attn_kernel_ms", "window_attn_roofline",
                     "recompute_ms"):
            assert reader(name).read(run, parameters(name)) is None
        assert reader("gqa_full_attn_kernel_ms").read(
            run, parameters("gqa_full_attn_kernel_ms")) == pytest.approx(
                6.0 / 2 * 1e3)

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
        "name": "rehearsal-smallthinker_dp1",
        "config": "rehearsal-smallthinker",
        "traffic": "rehearsal-smallthinker_dp1", "chips": 1})
    (copy / "rehearsal.json").write_text(json.dumps(listed))
    cache = tmp_path_factory.mktemp("compile-cache")
    proc = run_cell("rehearsal-smallthinker_dp1", trace=0, cache=cache,
                    root=str(tmp_path), seed=2147483650)
    result = result_of(proc)
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert set(result["metrics"]) == {"step_ms", "setup_s"}
    assert "43 leaves, 2 rows a step" in proc.stdout
    for check in ("loss_vs_reference", "gradient_norms_vs_reference",
                  "loss_after_warmup", "kernels_in_step", "losses_finite"):
        assert f"check {check}: ok" in proc.stdout, proc.stdout[-3000:]


# The two older decoders share the flash kernels and ``parallel/moe.py`` with
# this model. Their toy steps' lowered text on the CPU (interpreted kernels:
# no Mosaic bytecode, so no source lines in it) hashed on PR 32's parent
# (70b42bf), in a fresh process: what PR 32 added to the shared code left them
# character for character. A PR that means to change either step re-pins.
LOWERED = {
    ("rehearsal-olmoe", "rehearsal-olmoe_dp1"):
        "4677ddef45cd2c7b137fb2b2f0bbb07d98f6624b396a4a5ecc230eb2e045252c",
    ("rehearsal-olmo-hybrid", "rehearsal-olmo-hybrid_dp1"):
        "3b1793fc94246430229bffe5ad8e3a22588865666e537b230af75116419983c1",
}
LOWER_ONE = """
import hashlib, sys
from functools import partial
sys.path.insert(0, sys.argv[1])
import jax
import cells, run
import horovod_tpu as hvd
from jax.sharding import NamedSharding, PartitionSpec as P
config, job = sys.argv[2:4]
cfg = cells.load_json(cells.HERE, "configs", config + ".json")
cell = cells.Cell(
    name=job, chips=1, measured=False, config=cfg,
    job=cells.load_json(cells.HERE, "jobs", job + ".json"),
    code=cells.load_code(cells.HERE, "configs", cfg["code"]),
    reference=cells.load_code(cells.HERE, "reference", cfg["reference"]))
hvd.init(devices=jax.devices()[:1])
mesh, axis = hvd.global_mesh(), hvd.global_axis_name()
def placed(tree, spec):
    sharding = NamedSharding(mesh, spec)
    return jax.tree.map(lambda leaf: jax.ShapeDtypeStruct(
        leaf.shape, leaf.dtype, sharding=sharding), tree)
key = jax.random.PRNGKey(0)
params = jax.eval_shape(
    partial(cell.code.init_params, cell.config, cell.job), key)
batch = jax.eval_shape(partial(
    cell.code.make_batch, cell.config, cell.job, rows=cell.rows), key)
optimizer, step = run.build_step(cell)
text = step.lower(placed(params, P()),
                  placed(jax.eval_shape(optimizer.init, params), P()),
                  placed(batch, P(axis))).as_text()
print("sha256", hashlib.sha256(text.encode()).hexdigest())
"""


@pytest.mark.parametrize("toy", sorted(LOWERED), ids=lambda toy: toy[0])
def test_the_older_decoders_steps_lower_to_the_parents_text(toy, tmp_path):
    import subprocess
    import sys

    script = tmp_path / "lower_one.py"
    script.write_text(LOWER_ONE)
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("HOROVOD_")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO_ROOT)
    proc = subprocess.run(
        [sys.executable, str(script), BENCHMARK_DIR, *toy], env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.split()[-2:] == ["sha256", LOWERED[toy]]
