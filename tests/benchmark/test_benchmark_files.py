"""Every name in ``BENCHMARK.json`` resolves to its files, the file keeps
to its contract, the FLOPs functions agree with ``bench.py``'s, the plain
references compute what the product's models compute, and the comparisons
that decide ``correct`` decide as they say."""

import dataclasses
import json
import os
import re
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cells
import checks

REPO_ROOT = cells.ROOT
BENCH = cells.benchmark()
REHEARSAL = cells.load_json(cells.HERE, "rehearsal.json")
CELLS = [w["name"] for w in BENCH["workloads"] + REHEARSAL["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


class TestContract:
    def test_keys_are_exactly_the_contracts(self):
        assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
        for config in BENCH["configs"]:
            assert set(config) == {"name", "source", "file", "reduced", "why"}
        for cell in BENCH["workloads"]:
            assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        for metric in BENCH["end_to_end"]:
            assert set(metric) - {"workloads"} == {
                "name", "unit", "better", "bound", "source"}
        for metric in BENCH["per_layer"]:
            assert set(metric) - {"workloads"} == {
                "name", "unit", "better", "source", "layer", "moves"}

    def test_names_are_plain_unique_and_explained(self):
        names = [entry["name"] for key in (
            "configs", "workloads", "end_to_end", "per_layer")
            for entry in BENCH[key]]
        assert len(names) == len(set(names))
        assert all(NAME.match(name) for name in names)
        assert all(len(entry["why"]) <= 200
                   for entry in BENCH["configs"] + BENCH["workloads"])
        size = os.path.getsize(os.path.join(REPO_ROOT, "BENCHMARK.json"))
        assert size <= 64 * 1024

    def test_command_and_paths_stay_inside_the_benchmark(self):
        assert BENCH["command"][1:] == ["benchmark/run.py"]
        assert BENCH["paths"] == ["benchmark", "tests/benchmark"]
        plain = re.compile(r"^[A-Za-z0-9_./-]+$")
        for path in BENCH["paths"]:
            for root, dirs, files in os.walk(os.path.join(REPO_ROOT, path)):
                dirs[:] = [d for d in dirs if d != "__pycache__"]
                for name in files:
                    assert plain.match(os.path.relpath(
                        os.path.join(root, name), REPO_ROOT)), name

    def test_cells_configurations_and_chips(self):
        configs = {c["name"] for c in BENCH["configs"]}
        assert {w["config"] for w in BENCH["workloads"]} == configs
        pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
        assert len(pairs) == len(set(pairs))
        assert 2 <= len(pairs) <= 24
        four = [w for w in BENCH["workloads"] if w["chips"] == 4]
        assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
        assert len(four) <= max(1, len(pairs) // 4)

    def test_metrics_and_bounds(self):
        end_to_end = {m["name"] for m in BENCH["end_to_end"]}
        assert "setup_s" in end_to_end
        for metric in BENCH["end_to_end"]:
            assert metric["source"] in {"host_clock", "device_trace"}
            assert 0.01 <= metric["bound"] <= 0.1
        cell_names = {w["name"] for w in BENCH["workloads"]}
        for metric in BENCH["per_layer"]:
            assert metric["source"] in SOURCES
            assert metric["moves"] in end_to_end
            assert LAYER.match(metric["layer"]), metric["layer"]
            assert set(metric.get("workloads", [])) <= cell_names
            if metric["name"].endswith("_roofline"):
                assert metric["unit"] == "%"
        assert 1 <= BENCH["run_seconds"] <= 51

    def test_a_configurations_file_names_what_was_changed(self):
        for config in BENCH["configs"]:
            assert config["file"].startswith("benchmark/configs/")
            with open(os.path.join(REPO_ROOT, config["file"])) as f:
                as_run = json.load(f)
            assert sorted(as_run["reduced"]) == sorted(config["reduced"])


class TestResolution:
    @pytest.mark.parametrize("name", CELLS)
    def test_every_cell_resolves_to_its_files(self, name):
        cell = cells.resolve(name)
        assert cell.measured == (name in {w["name"]
                                          for w in BENCH["workloads"]})
        for function in ("init_params", "loss_fn", "inner_optimizer",
                         "first_gradient", "make_batch", "flops_per_step",
                         "units_per_step", "min_pallas_calls"):
            assert callable(getattr(cell.code, function)), function
        assert callable(cell.reference.loss)
        assert cell.job["rows_per_chip"] % cell.job[
            "reference_block_rows"] == 0
        assert cell.job["sync_mode"] in ("allreduce", "sharded", "fsdp")
        assert {"loss_rel", "gradient_norm_rel_median",
                "gradient_norm_rel_worst", "gradient_norm_floor_share",
                "loss_record_rel"} <= set(cell.config["correct"])
        assert cell.code.flops_per_step(
            cell.config, cell.job, cell.job["rows_per_chip"]) > 0

    def test_an_unknown_cell_is_refused_with_the_known_ones(self):
        with pytest.raises(SystemExit, match="bert-large_s512_dp1"):
            cells.resolve("no-such-cell")

    @pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
    def test_every_cell_has_its_per_layer_metrics(self, name):
        found = cells.layer_metrics(name)
        assert found, "every cell reports at least one per-layer metric"
        for entry, params, reader in found:
            assert callable(reader.read)
            assert params["definition"]

    def test_a_metric_listed_for_some_cells_stays_out_of_the_others(self):
        def names(cell):
            return {e["name"] for e, _, _ in cells.layer_metrics(cell)}

        assert "collective_exposed_ms" in names("bert-large_s512_dp4")
        assert "collective_exposed_ms" not in names("bert-large_s512_dp1")
        assert "flash_attn_roofline" not in names("resnet50_b128_dp1")
        assert "device_idle_share" in names("resnet50_b128_dp1")

    def test_the_peaks_table_names_its_source(self):
        peaks = cells.load_json(cells.HERE, "peaks.json")
        assert "Google Cloud" in peaks["source"]
        assert peaks["device_kinds"]["TPU v5 lite"] == {
            "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
            "ici_bits_per_s": 1600e9, "hbm_bytes": 16e9}


class TestFlops:
    """Against ``bench.py``'s arithmetic, which the benchmark copied."""

    @pytest.fixture(scope="class")
    def bench_py(self):
        sys.path.insert(0, REPO_ROOT)
        try:
            import bench
        finally:
            sys.path.remove(REPO_ROOT)
        return bench

    @pytest.mark.parametrize("cell_name", [
        "bert-large_s512_dp1", "bert-large_s128_dp1", "bert-large_s512_dp4"])
    def test_bert_flops_are_bench_pys(self, bench_py, cell_name):
        from horovod_tpu.models import bert

        cell = cells.resolve(cell_name)
        job, rows = cell.job, cell.job["rows_per_chip"] * cell.chips
        assert rows * job["seq_len"] == 12288 * cell.chips
        assert cell.code.flops_per_step(cell.config, job, rows) == (
            rows * job["seq_len"] * bench_py.bert_flops_per_token(
                bert.BERT_LARGE, job["seq_len"], job["masked_positions"]))

    def test_resnet50_flops_from_shapes_meet_the_constant(self, bench_py):
        cell = cells.resolve("resnet50_b128_dp1")
        per_image = cell.code.flops_per_step(cell.config, cell.job, 1)
        assert per_image == pytest.approx(
            bench_py.RESNET50_TRAIN_FLOPS_PER_IMAGE_224, rel=0.01)

    def test_bert_large_is_the_models_own(self):
        from horovod_tpu.models import bert

        cell = cells.resolve("bert-large_s512_dp1")
        assert cell.code.model_config(cell.config) == dataclasses.replace(
            bert.BERT_LARGE, dropout_rate=0.0)

    def test_flash_attention_cost_from_shapes(self):
        roofline = cells.load_code(
            cells.HERE, "layer_metrics", "flash_attn_roofline.py")
        peak = cells.load_json(
            cells.HERE, "peaks.json")["device_kinds"]["TPU v5 lite"]
        # 384 slices of 512 x 64 in bf16: forward 4 S^2 D FLOPs a slice
        flops, nbytes = roofline.forward_cost(384, 512, 64, 2)
        assert flops == 384 * 4 * 512 * 512 * 64
        assert nbytes == 384 * (4 * 512 * 64 * 2 + 4 * 512)
        seconds, bound = roofline.least_seconds((flops, nbytes), peak)
        assert bound == "compute"
        assert seconds == pytest.approx(flops / 197e12)
        flops, nbytes = roofline.backward_cost(384, 512, 64, 2)
        assert flops == 384 * 10 * 512 * 512 * 64
        assert nbytes == 384 * (7 * 512 * 64 * 2 + 12 * 512)


class TestReferences:
    """At toy size and in float32 the plain reference and the product's
    model are the same function, loss and gradients."""

    @pytest.mark.parametrize("name", ["rehearsal-bert_dp1",
                                      "rehearsal-resnet_dp1"])
    def test_reference_is_the_models_function(self, name):
        cell = cells.resolve(name)
        key = jax.random.PRNGKey(5)
        rows = cell.job["rows_per_chip"]
        params = jax.jit(partial(
            cell.code.init_params, cell.config, cell.job))(key)
        batch = jax.jit(partial(
            cell.code.make_batch, cell.config, cell.job, rows=rows))(
                jax.random.fold_in(key, 1))
        loss, grads = jax.jit(jax.value_and_grad(
            cell.code.loss_fn(cell.config, cell.job)))(params, batch)
        ref_loss, ref_norms = checks.reference_program(
            partial(cell.reference.loss, cell.config), jax.devices()[:1],
            cell.job["reference_block_rows"])(params, batch)
        assert float(ref_loss) == pytest.approx(float(loss), rel=1e-5)
        names = [jax.tree_util.keystr(path) for path, _ in
                 jax.tree_util.tree_leaves_with_path(params)]
        ok, seen = checks.norms_agree(
            checks.leaf_norms(grads), ref_norms, names,
            {"gradient_norm_rel_median": 1e-5,
             "gradient_norm_rel_worst": 1e-4,
             "gradient_norm_floor_share": 0.01})
        assert ok, seen

    def test_blocks_spread_over_devices_average_to_the_batch(self):
        def loss(params, batch):
            x, y = batch
            return jnp.mean((x @ params["w"] - y) ** 2)

        key = jax.random.PRNGKey(0)
        params = {"w": jax.random.normal(key, (6,))}
        x = jax.random.normal(jax.random.fold_in(key, 1), (16, 6))
        batch = (x, x.sum(1))
        value, grads = jax.value_and_grad(loss)(params, batch)
        for devices, block_rows in ((1, 16), (1, 4), (4, 2)):
            got_loss, got_norms = checks.reference_program(
                loss, jax.devices()[:devices], block_rows)(params, batch)
            assert float(got_loss) == pytest.approx(float(value), rel=1e-5)
            np.testing.assert_allclose(
                got_norms, checks.leaf_norms(grads), rtol=1e-5)


class TestChecks:
    NAMES = ["a", "b", "c", "d", "e"]
    REFERENCE = np.array([1.0, 2.0, 3.0, 4.0, 5.0])
    TOLERANCE = {"gradient_norm_rel_median": 0.01,
                 "gradient_norm_rel_worst": 0.5,
                 "gradient_norm_floor_share": 0.01}

    def agree(self, product, reference=None):
        return checks.norms_agree(
            np.asarray(product), self.REFERENCE if reference is None
            else np.asarray(reference), self.NAMES, self.TOLERANCE)

    def test_norms_within_the_bands_agree(self):
        assert self.agree(self.REFERENCE * 1.009)[0]
        # one leaf may be off by more than the median may
        assert self.agree(self.REFERENCE * [1, 1, 1.4, 1, 1])[0]

    def test_a_lowered_precision_moves_the_median(self):
        assert not self.agree(self.REFERENCE * 1.02)[0]

    def test_a_dropped_leaf_and_a_nan_disagree(self):
        ok, seen = self.agree([1.0, 0.0, 3.0, 4.0, 5.0])
        assert not ok and "b 0.0000e+00 against 2.0000e+00" in seen
        assert not self.agree([1.0, np.nan, 3.0, 4.0, 5.0])[0]

    def test_a_leaf_that_is_zero_by_the_mathematics(self):
        # both sides hold rounding there; it is held to a share of the
        # median leaf (0.01 x 3) and not to itself
        reference = [1.0, 2.0, 3.0, 4.0, 1e-9]
        assert self.agree([1.0, 2.0, 3.0, 4.0, 5e-3], reference)[0]
        assert not self.agree([1.0, 2.0, 3.0, 4.0, 5e-2], reference)[0]

    def test_a_recorded_seed_is_held_to_its_record(self):
        record = {"0": 10.0, "1": 10.2, "2": 10.1}
        assert checks.loss_in_record(10.03, 0, record, 2 ** -8)[0]
        assert not checks.loss_in_record(10.05, 0, record, 2 ** -8)[0]

    def test_an_unrecorded_seed_is_held_to_the_widened_band(self):
        record = {"0": 10.0, "1": 10.2, "2": 10.1}
        # the band: [9.8, 10.4], and one bf16 ulp beyond
        assert checks.loss_in_record(10.38, 7, record, 2 ** -8)[0]
        assert checks.loss_in_record(9.79, 7, record, 2 ** -8)[0]
        assert not checks.loss_in_record(10.5, 7, record, 2 ** -8)[0]
        assert not checks.loss_in_record(9.7, 7, record, 2 ** -8)[0]
        assert not checks.loss_in_record(float("nan"), 7, record, 2 ** -8)[0]

    def test_hlo_counters(self):
        hlo = "\n".join([
            '%a = bf16[1024]{0} all-reduce-start(bf16[1024]{0} %x)',
            '%b = bf16[1024]{0} all-reduce-done(bf16[1024]{0} %a)',
            '%c = f32[] all-reduce(f32[] %y)',
            '%d = f32[8]{0} all-gather(f32[2]{0} %z)',
            '%e = bf16[4] custom-call(%q), '
            'custom_call_target="tpu_custom_call"'])
        assert checks.collective_counts(hlo) == {
            "all-reduce": 2, "reduce-scatter": 0, "all-gather": 1, "bf16": 1}
        assert checks.pallas_call_count(hlo) == 1

    def test_replica_checksums_tell_a_replica_that_drifted(self):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()[:4]), ("hvd",))
        tree = {"w": jnp.arange(12.0).reshape(3, 4), "b": jnp.ones(3)}
        same = jax.device_put(tree, NamedSharding(mesh, P()))
        sums = np.asarray(checks.replica_checksums(same, mesh, "hvd"))
        assert sums.shape == (4, 2) and (sums == sums[0]).all()
        # one device's copy of one leaf off by one bit
        shards = [jnp.ones(3) if i != 2 else
                  jnp.ones(3).at[1].set(np.nextafter(np.float32(1), 2))
                  for i in range(4)]
        drifted = dict(same, b=jax.make_array_from_single_device_arrays(
            (3,), NamedSharding(mesh, P()),
            [jax.device_put(s, d) for s, d in zip(shards, mesh.devices)]))
        sums = np.asarray(checks.replica_checksums(drifted, mesh, "hvd"))
        assert (sums[[0, 1, 3]] == sums[0]).all()
        assert (sums[2] != sums[0]).any()
