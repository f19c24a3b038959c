"""Hierarchical (two-level ICI+DCN) allreduce.

Reference parity: ``NCCLHierarchicalAllreduce``
(``horovod/common/ops/nccl_operations.cc``) — reduce-scatter intra-node →
host allreduce across nodes → allgather intra-node, enabled by
``HOROVOD_HIERARCHICAL_ALLREDUCE``. Traced numerics are asserted against
the flat allreduce on the 8-device mesh reshaped 2x4; the host form's
cross leg is asserted to really run through the native C++ runtime
(cache/cycle counters move) in a 2-process subprocess test.
"""

import os
import socket
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from horovod_tpu.parallel.hierarchical import (
    HIERARCHICAL_AXES,
    hierarchical_allreduce,
    hierarchical_mesh,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _two_level(hvd, x, op, cross=2, local=4, **kw):
    mesh = hierarchical_mesh(cross, local)

    def body(v):
        return hierarchical_allreduce(v[0, 0], op, **kw)[None, None]

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=P(*HIERARCHICAL_AXES),
        out_specs=P(*HIERARCHICAL_AXES),
        check_vma=False,
    )
    return np.asarray(jax.jit(fn)(x))


class TestTracedHierarchical:
    @pytest.mark.parametrize("op", ["sum", "average", "min", "max"])
    def test_matches_flat_allreduce(self, hvd, op):
        # Per-rank tensors stacked (cross=2, local=4, *shape).
        rng = np.random.RandomState(0)
        x = rng.randn(2, 4, 6, 5).astype(np.float32)
        got = _two_level(hvd, x, op)
        flat = np.asarray(
            hvd.allreduce(x.reshape(8, 6, 5), op=op)
        ).reshape(2, 4, 6, 5)
        np.testing.assert_allclose(got, flat, rtol=1e-5, atol=1e-5)

    def test_padding_path_non_divisible(self, hvd):
        # 3 elements with local=4 forces the pad-to-multiple branch.
        x = np.arange(8 * 3, dtype=np.float32).reshape(2, 4, 3)
        got = _two_level(hvd, x, "sum")
        want = x.sum(axis=(0, 1))
        np.testing.assert_allclose(got, np.broadcast_to(want, (2, 4, 3)))

    def test_scale_factors(self, hvd):
        x = np.ones((2, 4, 4), np.float32)
        got = _two_level(
            hvd, x, "sum", prescale_factor=2.0, postscale_factor=0.5
        )
        np.testing.assert_allclose(got, 8.0 * np.ones((2, 4, 4)))

    def test_public_allreduce_detects_hierarchical_axes(self, hvd):
        # hvd.allreduce called inside a shard_map over the 2-D mesh must
        # dispatch to the two-level form, not the eager path.
        mesh = hierarchical_mesh(2, 4)

        def body(v):
            return hvd.allreduce(v[0, 0], op="average")[None, None]

        fn = jax.jit(
            jax.shard_map(
                body,
                mesh=mesh,
                in_specs=P(*HIERARCHICAL_AXES),
                out_specs=P(*HIERARCHICAL_AXES),
                check_vma=False,
            )
        )
        x = np.arange(8, dtype=np.float32).reshape(2, 4, 1)
        np.testing.assert_allclose(np.asarray(fn(x)), 3.5)

    def test_other_collectives_accept_hierarchical_axes(self, hvd):
        # allgather/broadcast/reducescatter/alltoall + rank() inside a
        # hierarchical shard_map must take the traced path (tuple axes),
        # not fall into eager dispatch with tracers.
        mesh = hierarchical_mesh(2, 4)

        def body(v):
            x = v[0, 0]
            g = hvd.allgather(x)
            b = hvd.broadcast(x, root_rank=0)
            rs = hvd.reducescatter(jnp.arange(8.0) + x[0], op="sum")
            r = hvd.rank()
            return g[None, None], b[None, None], rs[None, None], r[None, None]

        fn = jax.jit(
            jax.shard_map(
                body,
                mesh=mesh,
                in_specs=P(*HIERARCHICAL_AXES),
                out_specs=(P(*HIERARCHICAL_AXES),) * 4,
                check_vma=False,
            )
        )
        x = np.arange(8, dtype=np.float32).reshape(2, 4, 1)
        g, b, rs, r = fn(x)
        np.testing.assert_allclose(np.asarray(g)[0, 0], np.arange(8.0))
        np.testing.assert_allclose(np.asarray(b).ravel(), 0.0)
        # Each rank contributes arange(8)+rank; rank r keeps element r of
        # the sum: 8*r + sum(ranks) = 8*r + 28.
        np.testing.assert_allclose(
            np.asarray(rs).ravel(), 8 * np.arange(8) + 28.0
        )
        np.testing.assert_allclose(np.asarray(r).ravel(), np.arange(8))

    def test_mesh_conflicts_with_explicit_mesh(self, hvd):
        with pytest.raises(ValueError, match="not both"):
            hvd.parallel.make_train_step(
                lambda p, b: jnp.sum(p), None,
                mesh=hvd.global_mesh(), hierarchical=True,
            )

    def test_adasum_two_level_runs(self, hvd):
        # Adasum hierarchy: mean over local, adasum over cross. With equal
        # inputs the result equals the input (adasum of identical vectors).
        x = np.ones((2, 4, 8), np.float32) * 3.0
        got = _two_level(hvd, x, "adasum")
        np.testing.assert_allclose(got, 3.0 * np.ones((2, 4, 8)), rtol=1e-5)


class TestHierarchicalTrainStep:
    @pytest.mark.slow
    def test_train_step_matches_flat(self, hvd):
        from horovod_tpu.models.lenet import LeNet, cross_entropy_loss

        model = LeNet()
        params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1)))

        def loss_fn(p, batch):
            x, y = batch
            return cross_entropy_loss(model.apply(p, x), y)

        rng = np.random.RandomState(1)
        batch = (
            rng.rand(16, 28, 28, 1).astype(np.float32),
            rng.randint(0, 10, size=(16,)).astype(np.int32),
        )

        losses = {}
        for name, kw in (
            ("flat", dict(hierarchical=False)),
            ("hier", dict(hierarchical=(2, 4))),
        ):
            opt = hvd.DistributedOptimizer(optax.sgd(0.1))
            step = hvd.parallel.make_train_step(
                loss_fn, opt, donate=False, **kw
            )
            p = hvd.data_parallel.replicate(params)
            s = hvd.data_parallel.replicate(opt.init(params))
            trace = []
            b = hvd.data_parallel.shard_batch(batch)
            for _ in range(3):
                p, s, loss = step(p, s, b)
                trace.append(float(loss))
            losses[name] = trace
        np.testing.assert_allclose(
            losses["flat"], losses["hier"], rtol=1e-4, atol=1e-5
        )

    def test_env_flag_consumed(self, hvd, monkeypatch):
        # HOROVOD_HIERARCHICAL_ALLREDUCE=1 at init time must flow through
        # make_train_step's default. Single host → cross=1, still valid.
        cfg = hvd.config()
        monkeypatch.setattr(cfg, "hierarchical_allreduce", True)

        def loss_fn(p, batch):
            return jnp.sum(p["w"] * batch.sum())

        opt = hvd.DistributedOptimizer(optax.sgd(0.1))
        step = hvd.parallel.make_train_step(loss_fn, opt, donate=False)
        p = hvd.data_parallel.replicate({"w": jnp.ones((3,))})
        s = hvd.data_parallel.replicate(opt.init({"w": jnp.ones((3,))}))
        b = hvd.data_parallel.shard_batch(np.ones((8, 2), np.float32))
        p2, _, loss = step(p, s, b)
        assert np.isfinite(float(loss))


HOST_WORKER = textwrap.dedent(
    """
    import os, sys
    sys.path.insert(0, os.environ["REPO_ROOT"])
    import jax
    jax.config.update("jax_platforms", "cpu")
    from horovod_tpu._jax_compat import force_cpu_devices
    force_cpu_devices(4)
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.parallel.hierarchical import host_hierarchical_allreduce
    from horovod_tpu.runtime import NativeWorld

    proc = int(os.environ["TEST_RANK"]); nprocs = int(os.environ["TEST_SIZE"])
    port = int(os.environ["TEST_PORT"])
    hvd.init()
    assert hvd.size() == 4  # this process's local world
    w = NativeWorld(proc, nprocs, "127.0.0.1", port, timeout_s=30.0)
    # Logical world: nprocs x 4 local ranks. Local shard r of process p
    # holds value p*4 + r.
    local = np.stack(
        [np.full((5,), proc * 4 + r, np.float32) for r in range(4)])
    out = np.asarray(host_hierarchical_allreduce(
        local, "hhar.t", op="average", world=w))
    want = (nprocs * 4 - 1) / 2.0
    assert np.allclose(out, want), (out[:, 0], want)
    assert out.shape == local.shape
    # The cross leg must actually have run through libhvdrt.
    assert w.cycles > 0, "native runtime saw no cycles"
    for step in range(4):
        host_hierarchical_allreduce(local, "hhar.steady", op="sum", world=w)
    assert w.cache_hits >= 2, f"response cache never hit: {w.cache_hits}"
    print(f"proc{proc} host-hierarchical ok (cycles={w.cycles} "
          f"hits={w.cache_hits})", flush=True)
    w.shutdown()
    """
)


@pytest.mark.slow
def test_host_hierarchical_cross_leg_through_native_runtime(tmp_path):
    script = tmp_path / "host_worker.py"
    script.write_text(HOST_WORKER)
    with socket.socket() as s:
        s.bind(("", 0))
        port = s.getsockname()[1]
    procs = []
    for r in range(2):
        env = dict(
            os.environ,
            REPO_ROOT=REPO_ROOT,
            TEST_RANK=str(r),
            TEST_SIZE="2",
            TEST_PORT=str(port),
            JAX_PLATFORMS="cpu",
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, str(script)],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )
        )
    for r, p in enumerate(procs):
        try:
            out, err = p.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise AssertionError(f"proc {r} timed out")
        assert p.returncode == 0, f"proc {r}\nstdout:{out}\nstderr:{err}"
        assert f"proc{r} host-hierarchical ok" in out
