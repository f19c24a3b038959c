"""What a CPU can check of the chip bring-up: the compile-cache helper
places the cache where it says, nothing else in the tree sets a cache
directory, ``chip_smoke.py`` refuses to run off a TPU, and the repository
has one benchmark entry point."""

import json
import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRINT_CACHE_DIR = (
    "import horovod_tpu as hvd, jax; "
    "print(hvd.enable_compile_cache()); "
    "print(jax.config.jax_compilation_cache_dir)"
)


def _cache_dirs(env_value):
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_value is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_value
    proc = subprocess.run(
        [sys.executable, "-c", _PRINT_CACHE_DIR], env=env, cwd="/",
        capture_output=True, text=True, timeout=120, check=True)
    return proc.stdout.split()


class TestCompileCachePlacement:
    def test_variable_set_wins_and_nothing_is_set_in_code(self, tmp_path):
        # Run from "/" with a path no code could derive: what JAX's config
        # shows can only have come from the variable.
        placed = str(tmp_path / "placed-from-outside")
        assert _cache_dirs(placed) == [placed, placed]

    def test_variable_unset_uses_the_fixed_checkout_path(self):
        fixed = os.path.join(REPO_ROOT, ".jax_cache")
        assert _cache_dirs(None) == [fixed, fixed]

    def test_no_other_cache_directory_write_in_the_tree(self):
        writers = []
        for root, dirs, files in os.walk(REPO_ROOT):
            # Hidden directories hold caches and unpacked copies of the
            # tree, chiprun_out what a chip run brought back.
            dirs[:] = [d for d in dirs if not d.startswith(".")
                       and d not in ("__pycache__", "chiprun_out")]
            for name in files:
                if name.endswith((".py", ".sh")):
                    path = os.path.join(root, name)
                    with open(path, errors="replace") as f:
                        if "jax_compilation_cache_dir" in f.read():
                            writers.append(os.path.relpath(path, REPO_ROOT))
        assert sorted(writers) == ["horovod_tpu/basics.py",
                                   "tests/test_chip_bringup.py"]


def test_chip_smoke_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py")],
        env=env, cwd=REPO_ROOT, capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr
    # Refused before anything was built, and no result line was printed.
    assert proc.stdout == ""


def test_the_repository_has_one_benchmark_entry_point():
    """``BENCHMARK.json``'s command is the benchmark. ``bench.py`` is what
    ``tests/benchmark``'s ``TestFlops`` imports and no more: two names of
    arithmetic, read without JAX, nothing to run."""
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    assert os.path.isfile(os.path.join(REPO_ROOT, command[1]))
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys, bench; print('jax' in sys.modules); "
         "print(sorted(n for n in vars(bench) if not n.startswith('_')))"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=60,
        check=True)
    assert proc.stdout.split("\n")[:2] == [
        "False",
        "['RESNET50_TRAIN_FLOPS_PER_IMAGE_224', 'bert_flops_per_token']"]
    with open(os.path.join(REPO_ROOT, "bench.py")) as f:
        source = f.read()
    assert "__main__" not in source and "def main" not in source
