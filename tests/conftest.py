"""Test harness: an 8-device virtual CPU mesh stands in for a TPU slice.

The reference tests every distributed behavior with N processes on one
machine (SURVEY.md §4 "localhost-as-cluster"); the single-controller analog
is N virtual CPU devices in one process. Must configure JAX before any
backend is initialized, so this runs at conftest import time.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# Inherited by the subprocesses tests start (examples, worker scripts).
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=8"
)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running end-to-end tests")


def _multiprocess_cpu_collectives_supported() -> bool:
    """Capability probe: can this image's jaxlib run a collective across
    TWO processes on the CPU backend?

    Some jaxlib builds abort with "Multiprocess computations aren't
    implemented on the CPU backend" (CHANGES.md PR 1) — an image fact, not
    a code regression, so tests needing real 2-process CPU collectives
    skip instead of failing tier-1. The probe launches the framework's own
    static runner on a minimal cross-process allreduce, once per
    jax/jaxlib version (result cached on disk).
    """
    import subprocess
    import sys
    import tempfile
    import textwrap

    try:
        import jaxlib

        jaxlib_ver = jaxlib.__version__
    except Exception:
        jaxlib_ver = "unknown"
    cache = os.path.join(
        tempfile.gettempdir(),
        f"hvd_mpcpu_probe_{jax.__version__}_{jaxlib_ver}.txt",
    )
    try:
        with open(cache) as f:
            return f.read().strip() == "1"
    except OSError:
        pass

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tmp = tempfile.mkdtemp(prefix="hvd_mpcpu_probe_")
    worker = os.path.join(tmp, "probe_worker.py")
    with open(worker, "w") as f:
        f.write(textwrap.dedent(f"""
            import os, sys
            sys.path.insert(0, {repo_root!r})
            os.environ["JAX_PLATFORMS"] = "cpu"
            import jax
            jax.config.update("jax_platforms", "cpu")
            from horovod_tpu._jax_compat import force_cpu_devices
            force_cpu_devices(1)
            import jax.numpy as jnp
            import horovod_tpu as hvd
            hvd.init()
            assert hvd.process_count() == 2, hvd.process_count()
            x = jnp.ones((2, 1), jnp.float32)
            out = hvd.to_local(hvd.allreduce(x, op=hvd.Sum))
            assert float(out[0, 0]) == 2.0, out
            print("MPCPU_PROBE_OK", flush=True)
        """))
    driver = os.path.join(tmp, "probe_driver.py")
    with open(driver, "w") as f:
        f.write(textwrap.dedent(f"""
            import sys
            sys.path.insert(0, {repo_root!r})
            from horovod_tpu.runner.launch import (
                parse_args, run_static, settings_from_args,
            )
            args = parse_args(["-np", "2", "--cpu-mode", {worker!r}])
            rc = run_static(settings_from_args(args), sink=print)
            sys.exit(rc)
        """))
    definitive = True
    try:
        proc = subprocess.run(
            [sys.executable, driver], capture_output=True, text=True,
            timeout=180,
        )
        ok = proc.returncode == 0 and "MPCPU_PROBE_OK" in proc.stdout
    except (subprocess.TimeoutExpired, OSError):
        # A timeout/OSError is a TRANSIENT verdict (machine under load),
        # not a capability fact: skip this session but don't cache it —
        # a cached false negative would silently shed coverage forever.
        ok = False
        definitive = False
    if definitive:
        try:
            with open(cache, "w") as f:
                f.write("1" if ok else "0")
        except OSError:
            pass  # uncacheable tmp: re-probe next session
    return ok


@pytest.fixture(scope="session")
def require_multiprocess_cpu_collectives():
    """Skip-guard for tests that need a REAL 2-process CPU collective."""
    if not _multiprocess_cpu_collectives_supported():
        pytest.skip(
            "this jaxlib cannot run multi-process CPU collectives "
            "(known image limitation, CHANGES.md PR 1)"
        )


@pytest.fixture(scope="session", autouse=True)
def _hvd_world():
    import horovod_tpu as hvd

    hvd.init()
    assert hvd.size() == 8, (
        f"expected the 8-device virtual CPU mesh, got {hvd.size()} devices "
        f"on backend {jax.default_backend()}"
    )
    yield


@pytest.fixture()
def hvd():
    import horovod_tpu as hvd

    return hvd
