"""``run.py`` rehearsed on the CPU: toy cells that are not in
``workloads``, on one and on four virtual devices, whose result names
``platform: cpu``; a measured cell refused off a TPU; a second run served
from the compile cache; and a cell, a configuration and a per-layer metric
added by new files alone."""

import hashlib
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from conftest import BENCHMARK_DIR, REPO_ROOT


def run_cell(cell, *, trace, cache, root=REPO_ROOT, seed=0):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("HOROVOD_")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO_ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", cell, "--seed", str(seed), "--seconds", "0.3",
         "--trace", str(trace)],
        env=env, cwd=root, capture_output=True, text=True, timeout=300)


def result_of(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.splitlines()[-1])


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("compile-cache")


def test_one_device_and_again_from_the_cache(cache):
    first = run_cell("rehearsal-bert_dp1", trace=0, cache=cache)
    result = result_of(first)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 5 and result["attempted"] % 5 == 0
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == 1
    # A CPU has no peak in the table and keeps no memory statistics: no
    # number appears under a device metric's name.
    assert set(result["metrics"]) == {"step_ms", "setup_s"}
    assert "this program sets none): []" in first.stdout
    second = run_cell("rehearsal-bert_dp1", trace=0, cache=cache)
    assert result_of(second)["correct"] is True
    setup_line, = [line for line in second.stdout.splitlines()
                   if line.startswith("set-up:")]
    assert setup_line.endswith("misses=0"), setup_line


def test_four_devices_traced(cache):
    proc = run_cell("rehearsal-bert_dp4", trace=1, cache=cache, seed=3)
    result = result_of(proc)
    assert result["correct"] is True
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == 4
    assert result["attempted"] == 10  # the job's two traced groups of five
    assert "check replicas_identical: ok" in proc.stdout
    # The readers that need a TPU's planes or its memory counters found
    # nothing and were left out; the host's clock and JAX's own compile
    # events are there on any platform.
    assert set(result["metrics"]) == {"host_call_ms", "compile_s"}
    assert "busy_s" not in result["device"]


@pytest.mark.parametrize("cell", ["rehearsal-bert_zero1x4",
                                  "rehearsal-bert_fsdp4"])
def test_the_sharded_sync_modes_place_their_state(cell, cache):
    proc = run_cell(cell, trace=0, cache=cache)
    assert result_of(proc)["correct"] is True
    assert "check gradient_norms_vs_reference: ok" in proc.stdout


def test_a_measured_cell_is_refused_off_a_tpu(cache):
    proc = run_cell("resnet50_b128_dp1", trace=0, cache=cache)
    assert proc.returncode != 0
    assert "needs 1 TPU chip(s)" in proc.stderr and "'cpu'" in proc.stderr
    assert proc.stdout == ""  # refused before anything was built


def test_it_does_not_run_without_the_program(tmp_path, cache):
    # A directory that holds only BENCHMARK.json and the benchmark's paths.
    shutil.copytree(BENCHMARK_DIR, tmp_path / "benchmark")
    shutil.copy(os.path.join(REPO_ROOT, "BENCHMARK.json"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "rehearsal-bert_dp1", "--seed", "0", "--seconds", "0.3",
         "--trace", "0"],
        env=env, cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "horovod_tpu" in proc.stderr
    assert not proc.stdout.strip().endswith("}")


TOY_CODE = '''
import jax, jax.numpy as jnp, optax

def init_params(config, job, key):
    return {"w": jax.random.normal(key, (config["features"],))}

def loss_fn(config, job):
    return lambda params, batch: jnp.mean(
        (batch[0] @ params["w"] - batch[1]) ** 2)

def inner_optimizer(config):
    return optax.sgd(0.01, momentum=0.9)

def first_gradient(opt_state):
    is_trace = lambda s: isinstance(s, optax.TraceState)
    trace, = filter(is_trace, jax.tree.leaves(opt_state, is_leaf=is_trace))
    return trace.trace

def make_batch(config, job, key, rows):
    x = jax.random.normal(key, (rows, config["features"]))
    return x, x.sum(1)

def flops_per_step(config, job, rows):
    return 6.0 * rows * config["features"]

def units_per_step(job, rows):
    return rows, "rows"

def min_pallas_calls(config):
    return 0
'''

TOY_REFERENCE = '''
import jax.numpy as jnp

def loss(config, params, batch):
    x, y = batch
    return jnp.mean((x @ params["w"] - y) ** 2)
'''

TOY_READER = '''
def read(run, params):
    return float(run.steps * params["scale"])
'''


def test_a_cell_a_configuration_and_a_metric_are_added_by_files(
        tmp_path, cache):
    copy = tmp_path / "benchmark"
    shutil.copytree(BENCHMARK_DIR, copy,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO_ROOT, "BENCHMARK.json"), tmp_path)

    def digests():
        return {os.path.relpath(os.path.join(root, name), tmp_path):
                hashlib.sha256(open(os.path.join(root, name), "rb").read())
                .hexdigest()
                for root, _, files in os.walk(tmp_path) for name in files}

    before = digests()

    def write(relative, text):
        (copy / relative).write_text(textwrap.dedent(text))

    write("configs/toy.json", json.dumps({
        "source": "none: a toy", "code": "toy.py", "reference": "toy.py",
        "features": 8, "correct": {"loss_rel": 1e-5,
                                   "gradient_norm_rel_median": 1e-4,
                                   "gradient_norm_rel_worst": 1e-4,
                                   "gradient_norm_floor_share": 0.01,
                                   "loss_record_rel": 2 ** -8}}))
    write("configs/toy.py", TOY_CODE)
    write("reference/toy.py", TOY_REFERENCE)
    write("jobs/toy_dp4.json", json.dumps({
        "rows_per_chip": 8, "sync_mode": "allreduce", "compression": "none",
        "reference_block_rows": 4, "trace_groups": 3,
        "loss_after_warmup": {}}))
    write("layer_metrics/toy_steps.json", json.dumps({
        "definition": "twice the steps traced", "scale": 2}))
    write("layer_metrics/toy_steps.py", TOY_READER)
    # ... and entries: a cell among the rehearsed, a metric among the
    # per-layer ones. Nothing that was there is edited.
    for path, key, entry in (
            (copy / "rehearsal.json", "workloads",
             {"name": "toy_dp4", "config": "toy", "traffic": "toy_dp4",
              "chips": 4}),
            (tmp_path / "BENCHMARK.json", "per_layer",
             {"name": "toy_steps", "unit": "steps", "better": "higher",
              "source": "program_counter", "layer": "toy",
              "moves": "step_ms", "workloads": ["toy_dp4"]})):
        listed = json.loads(path.read_text())
        listed[key].append(entry)
        path.write_text(json.dumps(listed))

    proc = run_cell("toy_dp4", trace=1, cache=cache, root=str(tmp_path))
    result = result_of(proc)
    assert result["correct"] is True and result["attempted"] == 15
    assert result["device"] == {"platform": "cpu", "kind": "cpu",
                                "count": 4, "memory_peak_bytes": 0}
    assert result["metrics"]["toy_steps"] == {"value": 30.0, "unit": "steps"}
    assert "host_call_ms" in result["metrics"]

    after = digests()
    edited = {path for path in before if after[path] != before[path]}
    assert edited == {"BENCHMARK.json", "benchmark/rehearsal.json"}
