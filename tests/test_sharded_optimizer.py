"""Sharded-optimizer gradient sync (``sync_mode="sharded"``, ZeRO-1 style).

An allreduce is reduce-scatter + allgather; sharded mode splits them:
per-bucket reduce-scatter on the gradient path (still riding the overlap
scheduler's custom-vjp segment boundaries), inner update only on the
locally owned shard (state materialized sharded from init), and an
allgather of the *updated parameters* off the gradient critical path.
Asserted here:

- the per-leaf shard-ownership map is stable (shape-only, rank-identical)
  and the sharded step is stable across retraces;
- ``fused_reducescatter``/``fused_allgather_shards`` (and the eager
  ``reducescatter``/``grouped_reducescatter``) are parity with allreduce
  across ops, scale factors, uneven leaf sizes (padding path), and
  non-divisible world sizes;
- sharded-vs-monolithic equivalence after K steps — params AND optimizer
  state (unsharded) — including under the overlap scheduler and the int8
  wire (quantization tolerance: block boundaries differ by layout);
- elastic resize re-shard: world N→N-1 resumes with the same loss
  trajectory as a fresh N-1 run from the synced state, and
  ``TpuState(sharded_optimizer=...)`` re-shards in ``sync()``;
- checkpoint round-trip monolithic↔sharded (gather-on-save layout);
- the autotune sync_mode axis: joint grid, pinning, abort poisoning.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from horovod_tpu.ops.fusion import (
    fused_allgather_shards,
    fused_allreduce,
    fused_reducescatter,
    shard_ownership,
)


def _mlp_problem(n_layers=3, dim=8, batch=16, seed=0):
    rng = np.random.RandomState(seed)
    params = {
        f"layer{i}": {
            "w": jnp.asarray(rng.randn(dim, dim).astype(np.float32)),
            "b": jnp.asarray(rng.randn(dim).astype(np.float32)),
        }
        for i in range(n_layers)
    }

    def loss_fn(p, b):
        x, y = b
        h = x
        for i in range(n_layers):
            h = jnp.tanh(h @ p[f"layer{i}"]["w"] + p[f"layer{i}"]["b"])
        return jnp.mean((h.sum(axis=-1) - y) ** 2)

    x = rng.randn(batch, dim).astype(np.float32)
    y = rng.randn(batch).astype(np.float32)
    return params, (x, y), loss_fn


def _wide_problem(batch=16, seed=0):
    """An MLP whose first matrix, ``[512, 1024]`` float32, is 2 MiB on the
    wire: over ``ops.fusion.PACK_CUTOFF_BYTES``, so it is scattered and
    gathered as itself beside the packed small leaves."""
    rng = np.random.RandomState(seed)
    params = {
        "wide": {"w": jnp.asarray(
            (rng.randn(512, 1024) / 32).astype(np.float32)),
            "b": jnp.asarray(rng.randn(1024).astype(np.float32))},
        "out": {"w": jnp.asarray(
            (rng.randn(1024, 8) / 32).astype(np.float32)),
            "b": jnp.asarray(rng.randn(8).astype(np.float32))},
    }

    def loss_fn(p, b):
        x, y = b
        h = jnp.tanh(x @ p["wide"]["w"] + p["wide"]["b"])
        h = jnp.tanh(h @ p["out"]["w"] + p["out"]["b"])
        return jnp.mean((h.sum(axis=-1) - y) ** 2)

    x = rng.randn(batch, 512).astype(np.float32)
    y = rng.randn(batch).astype(np.float32)
    return params, (x, y), loss_fn


def _get_or_add_ps(hvd, ranks):
    """Process sets persist for the whole test session; re-adding the
    same ranks raises, so look it up first."""
    from horovod_tpu import process_sets as pss

    for ps in pss._table.values():
        if ps.ranks == sorted(ranks):
            return ps
    return hvd.add_process_set(ranks)


def _assert_tree_close(a, b, rtol=1e-5, atol=1e-6):
    jax.tree.map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=rtol, atol=atol),
        a, b)


class TestShardOwnership:
    def test_byte_balanced_ceil(self):
        leaves = [jnp.zeros((s,), jnp.float32) for s in (5, 13, 16, 3)]
        assert shard_ownership(leaves, 8) == [1, 2, 2, 1]
        assert shard_ownership(leaves, 3) == [2, 5, 6, 1]

    def test_stable_under_values_and_rank(self):
        # Shape-only: different values, identical map — the contract that
        # lets every rank and every retrace derive the same ownership.
        a = [jnp.zeros((5, 5)), jnp.ones((3,))]
        b = [jnp.full((5, 5), 7.0), jnp.zeros((3,)) - 4]
        assert shard_ownership(a, 8) == shard_ownership(b, 8)

    def test_sharded_step_stable_across_retraces(self, hvd):
        dp = hvd.data_parallel
        params, batch, loss_fn = _mlp_problem()
        opt = hvd.DistributedOptimizer(optax.adam(0.05),
                                       sync_mode="sharded")
        step = dp.make_train_step(loss_fn, opt, donate=False)
        p = dp.replicate(params)
        s = dp.shard_state(opt.init(params))
        b = dp.shard_batch(batch)
        p1, s1, l1 = step(p, s, b)
        step.clear_cache()  # force a retrace: the map must re-derive
        p2, s2, l2 = step(p, s, b)
        assert float(l1) == float(l2)
        _assert_tree_close(p1, p2, rtol=0, atol=0)
        _assert_tree_close(s1, s2, rtol=0, atol=0)


class TestReducescatterParity:
    """Satellite: reducescatter/grouped_reducescatter parity with
    allreduce across ops, scale factors, uneven leaf sizes (padding
    path), and non-divisible world sizes."""

    def _roundtrip(self, hvd, mesh, axis, n, leaves, op, pre=1.0, post=1.0):
        def rs_ag(ls):
            shards = fused_reducescatter(
                list(ls), op, axis, n, threshold_bytes=64,
                prescale_factor=pre, postscale_factor=post)
            return fused_allgather_shards(
                shards, list(ls), axis, n, threshold_bytes=64)

        def ar(ls):
            return fused_allreduce(list(ls), op, axis,
                                   prescale_factor=pre,
                                   postscale_factor=post)

        kw = dict(mesh=mesh, in_specs=(P(),), out_specs=P(),
                  check_vma=False)
        got = jax.jit(jax.shard_map(rs_ag, **kw))(leaves)
        want = jax.jit(jax.shard_map(ar, **kw))(leaves)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("op", ["sum", "average"])
    def test_fused_parity_uneven_leaves(self, hvd, op):
        # Leaf sizes 5/13/3 are all non-divisible by 8 (and 3 < 8): the
        # padding path runs for every leaf.
        rng = np.random.RandomState(1)
        leaves = [rng.randn(*s).astype(np.float32)
                  for s in [(5,), (13,), (4, 4), (3,)]]
        self._roundtrip(hvd, hvd.global_mesh(), "hvd", 8, leaves, op)

    def test_fused_parity_scale_factors(self, hvd):
        rng = np.random.RandomState(2)
        leaves = [rng.randn(9).astype(np.float32),
                  rng.randn(2, 3).astype(np.float32)]
        self._roundtrip(hvd, hvd.global_mesh(), "hvd", 8, leaves,
                        "sum", pre=0.5, post=3.0)
        self._roundtrip(hvd, hvd.global_mesh(), "hvd", 8, leaves,
                        "average", pre=2.0, post=0.25)

    def test_fused_parity_non_divisible_world(self, hvd):
        # World size 3: no leaf divides evenly, every shard is padded.
        ps = _get_or_add_ps(hvd, [0, 1, 2])
        rng = np.random.RandomState(3)
        leaves = [rng.randn(7).astype(np.float32),
                  rng.randn(4).astype(np.float32)]
        self._roundtrip(hvd, ps.mesh, ps.axis_name, 3, leaves, "average")

    def test_eager_reducescatter_parity_with_allreduce(self, hvd):
        n = hvd.size()
        x = np.random.RandomState(4).randn(n, n * 2, 3).astype(np.float32)
        reduced = np.asarray(hvd.allreduce(x, op=hvd.Sum))[0]
        out = np.asarray(hvd.reducescatter(x, op=hvd.Sum))
        for r in range(n):
            np.testing.assert_allclose(out[r], reduced[r * 2:(r + 1) * 2],
                                       rtol=1e-5)

    def test_eager_reducescatter_scale_factors(self, hvd):
        n = hvd.size()
        x = np.random.RandomState(5).randn(n, n, 2).astype(np.float32)
        want = np.asarray(
            hvd.allreduce(x, op=hvd.Sum, prescale_factor=0.5,
                          postscale_factor=2.0))[0]
        out = np.asarray(hvd.reducescatter(
            x, op=hvd.Sum, prescale_factor=0.5, postscale_factor=2.0))
        for r in range(n):
            np.testing.assert_allclose(out[r], want[r:r + 1], rtol=1e-5)

    def test_grouped_reducescatter_parity(self, hvd):
        n = hvd.size()
        rng = np.random.RandomState(6)
        xs = [rng.randn(n, n, 2).astype(np.float32) for _ in range(3)]
        outs = hvd.grouped_reducescatter(xs, op=hvd.Average)
        wants = hvd.grouped_allreduce(xs, op=hvd.Average)
        for out, want in zip(outs, wants):
            out, want = np.asarray(out), np.asarray(want)[0]
            for r in range(n):
                np.testing.assert_allclose(out[r], want[r:r + 1],
                                           rtol=1e-5)


class TestShardedEquivalence:
    """The numerical contract: sharded mode is bitwise-comparable to
    monolithic allreduce mode within reduction-order tolerance — params
    AND optimizer state — after K steps."""

    def _run(self, hvd, make_step, opt, params, batch, steps, sharded):
        dp = hvd.data_parallel
        p = dp.replicate(params)
        s = (dp.shard_state(opt.init(params)) if sharded
             else dp.replicate(opt.init(params)))
        b = dp.shard_batch(batch)
        losses = []
        for _ in range(steps):
            p, s, loss = make_step(p, s, b)
            losses.append(float(loss))
        return p, s, losses

    def test_matches_monolithic_params_and_state(self, hvd):
        dp = hvd.data_parallel
        params, batch, loss_fn = _mlp_problem()
        mono = hvd.DistributedOptimizer(optax.adam(0.05))
        shrd = hvd.DistributedOptimizer(optax.adam(0.05),
                                        sync_mode="sharded")
        step_m = dp.make_train_step(loss_fn, mono, donate=False)
        step_s = dp.make_train_step(loss_fn, shrd, donate=False)
        pm, sm, lm = self._run(hvd, step_m, mono, params, batch, 3, False)
        ps_, ss, ls = self._run(hvd, step_s, shrd, params, batch, 3, True)
        assert lm == pytest.approx(ls, rel=1e-6)
        _assert_tree_close(pm, ps_)
        full = hvd.unshard_opt_state(shrd, jax.device_get(ss), params)
        _assert_tree_close(jax.device_get(sm), full)

    def test_a_leaf_over_the_pack_cutoff_and_state_rows_in_the_old_layout(
            self, hvd):
        from horovod_tpu.ops import fusion

        dp = hvd.data_parallel
        params, batch, loss_fn = _wide_problem()
        assert params["wide"]["w"].nbytes >= fusion.PACK_CUTOFF_BYTES
        mono = hvd.DistributedOptimizer(optax.adam(0.01))
        shrd = hvd.DistributedOptimizer(optax.adam(0.01),
                                        sync_mode="sharded")
        step_m = dp.make_train_step(loss_fn, mono, donate=False)
        step_s = dp.make_train_step(loss_fn, shrd, donate=False)
        pm, sm, lm = self._run(hvd, step_m, mono, params, batch, 3, False)
        ps_, ss, ls = self._run(hvd, step_s, shrd, params, batch, 3, True)
        assert lm == pytest.approx(ls, rel=1e-6)
        _assert_tree_close(pm, ps_)
        _assert_tree_close(
            jax.device_get(sm),
            hvd.unshard_opt_state(shrd, jax.device_get(ss), params))

        # A checkpoint's state rows, laid out by hand as shard_ownership
        # always has (rank r owns flat[r*s:(r+1)*s]): the fourth step from
        # them is the monolithic run's fourth step.
        n = hvd.size()

        def rows(leaf):
            if not np.ndim(leaf):  # Adam's count: every rank keeps it
                return jnp.full((n,), leaf)
            flat = np.asarray(leaf).ravel()
            s = -(-flat.size // n)
            return jnp.asarray(
                np.pad(flat, (0, n * s - flat.size)).reshape(n, s))

        by_hand = jax.tree.map(rows, jax.device_get(sm))
        _assert_tree_close(jax.device_get(ss), by_hand)
        b = dp.shard_batch(batch)
        p4m, _, l4m = step_m(pm, sm, b)
        p4s, _, l4s = step_s(pm, dp.shard_state(by_hand), b)
        assert float(l4m) == pytest.approx(float(l4s), rel=1e-6)
        _assert_tree_close(p4m, p4s)

    def test_matches_monolithic_under_overlap_scheduler(self, hvd):
        dp = hvd.data_parallel
        params, batch, loss_fn = _mlp_problem()
        mono = hvd.DistributedOptimizer(optax.adam(0.05))
        shrd = hvd.DistributedOptimizer(optax.adam(0.05),
                                        sync_mode="sharded")
        step_m = dp.make_train_step(loss_fn, mono, donate=False)
        step_o = dp.make_overlapped_train_step(
            loss_fn, shrd, donate=False, num_segments=3)
        pm, _, _ = self._run(hvd, step_m, mono, params, batch, 3, False)
        po, so, _ = self._run(hvd, step_o, shrd, params, batch, 3, True)
        _assert_tree_close(pm, po)

    def test_int8_wire_matches_monolithic(self, hvd):
        # Sharded layout changes the quantization block boundaries, so
        # equality is to int8 tolerance (cf. test_overlap's int8 case).
        dp = hvd.data_parallel
        params, batch, loss_fn = _mlp_problem()
        m8 = hvd.DistributedOptimizer(
            optax.sgd(0.05), compression=hvd.Compression.int8)
        s8 = hvd.DistributedOptimizer(
            optax.sgd(0.05), compression=hvd.Compression.int8,
            sync_mode="sharded")
        step_m = dp.make_train_step(loss_fn, m8, donate=False)
        step_s = dp.make_train_step(loss_fn, s8, donate=False)
        pm, _, _ = self._run(hvd, step_m, m8, params, batch, 2, False)
        ps_, ss, _ = self._run(hvd, step_s, s8, params, batch, 2, True)
        _assert_tree_close(pm, ps_, rtol=0.05, atol=0.04)
        # The stochastic-rounding salt threads on the sharded path too:
        # the stacked counter advanced once per step on every rank.
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(ss).counter), np.full((8,), 2))

    def test_deferred_param_gather(self, hvd):
        dp = hvd.data_parallel
        params, batch, loss_fn = _mlp_problem()
        shrd = hvd.DistributedOptimizer(optax.adam(0.05),
                                        sync_mode="sharded")
        step = dp.make_train_step(loss_fn, shrd, donate=False)
        step_d = dp.make_train_step(loss_fn, shrd, donate=False,
                                    deferred_param_gather=True)
        p, s, _ = self._run(hvd, step, shrd, params, batch, 2, True)
        pd = dp.replicate(params)
        sd = dp.shard_state(shrd.init(params))
        b = dp.shard_batch(batch)
        for _ in range(2):
            pd, sd, _ = step_d(pd, sd, b)  # handle feeds straight back in
        assert isinstance(pd, hvd.DeferredParams)
        # Same math, different program split (the gather compiles
        # separately), so equality is to float-association noise.
        _assert_tree_close(p, pd.block_until_ready())
        _assert_tree_close(s, sd)

    def test_deferred_gather_int8_threads_salt(self, hvd):
        # The deferred gather compiles as its own program; with int8 it
        # must take the step counter so the requant salt matches the
        # non-deferred path (quantization tolerance: the programs split
        # differently, so borderline roundings may flip).
        dp = hvd.data_parallel
        params, batch, loss_fn = _mlp_problem()
        s8 = hvd.DistributedOptimizer(
            optax.sgd(0.05), compression=hvd.Compression.int8,
            sync_mode="sharded")
        step = dp.make_train_step(loss_fn, s8, donate=False)
        step_d = dp.make_train_step(loss_fn, s8, donate=False,
                                    deferred_param_gather=True)
        b = dp.shard_batch(batch)
        p1 = dp.replicate(params)
        s1 = dp.shard_state(s8.init(params))
        pd = dp.replicate(params)
        sd = dp.shard_state(s8.init(params))
        for _ in range(2):
            p1, s1, _ = step(p1, s1, b)
            pd, sd, _ = step_d(pd, sd, b)
        _assert_tree_close(p1, pd.block_until_ready(),
                           rtol=0.05, atol=0.04)
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(sd).counter), np.full((8,), 2))

    def test_standalone_update_keeps_optax_contract(self, hvd):
        """Users writing their own shard_map step call ``opt.update``
        directly: it reduce-scatters, shard-updates, and allgathers FULL
        updates (optax contract), taking this rank's state row."""
        dp = hvd.data_parallel
        params, batch, loss_fn = _mlp_problem(n_layers=2)
        mono = hvd.DistributedOptimizer(optax.adam(0.05))
        shrd = hvd.DistributedOptimizer(optax.adam(0.05),
                                        sync_mode="sharded")
        mesh = hvd.global_mesh()

        def spmd_s(p, st, b):
            g = jax.grad(loss_fn)(p, b)
            st_local = jax.tree.map(lambda a: a[0], st)
            upd, new_local = shrd.update(g, st_local, p)
            return (optax.apply_updates(p, upd),
                    jax.tree.map(lambda a: a[None], new_local))

        def spmd_m(p, st, b):
            g = jax.grad(loss_fn)(p, b)
            upd, new_st = mono.update(g, st, p)
            return optax.apply_updates(p, upd), new_st

        step_s = jax.jit(jax.shard_map(
            spmd_s, mesh=mesh, in_specs=(P(), P("hvd"), P("hvd")),
            out_specs=(P(), P("hvd")), check_vma=False))
        step_m = jax.jit(jax.shard_map(
            spmd_m, mesh=mesh, in_specs=(P(), P(), P("hvd")),
            out_specs=(P(), P()), check_vma=False))
        b = dp.shard_batch(batch)
        ps_, ss = step_s(dp.replicate(params),
                         dp.shard_state(shrd.init(params)), b)
        pm, _ = step_m(dp.replicate(params),
                       dp.replicate(mono.init(params)), b)
        _assert_tree_close(pm, ps_)

    def test_sharded_loss_decreases(self, hvd):
        dp = hvd.data_parallel
        params, batch, loss_fn = _mlp_problem()
        shrd = hvd.DistributedOptimizer(optax.adam(0.05),
                                        sync_mode="sharded")
        step = dp.make_train_step(loss_fn, shrd, donate=False)
        _, _, losses = self._run(hvd, step, shrd, params, batch, 4, True)
        assert losses[-1] < losses[0]


class TestShardedGuards:
    def test_rejects_adasum(self, hvd):
        with pytest.raises(ValueError, match="Average/Sum"):
            hvd.DistributedOptimizer(optax.sgd(0.1), op=hvd.Adasum,
                                     sync_mode="sharded")

    def test_rejects_gradient_accumulation(self, hvd):
        with pytest.raises(ValueError, match="backward_passes_per_step"):
            hvd.DistributedOptimizer(optax.sgd(0.1),
                                     backward_passes_per_step=2,
                                     sync_mode="sharded")

    def test_rejects_hierarchical_mesh(self, hvd):
        shrd = hvd.DistributedOptimizer(optax.sgd(0.1),
                                        sync_mode="sharded")
        with pytest.raises(ValueError, match="hierarchical"):
            hvd.data_parallel.make_train_step(
                lambda p, b: jnp.sum(p), shrd, hierarchical=(2, 4))

    def test_rejects_elastic_factory(self, hvd):
        shrd = hvd.DistributedOptimizer(optax.sgd(0.1),
                                        sync_mode="sharded")
        with pytest.raises(ValueError, match="sharded"):
            hvd.data_parallel.make_elastic_train_step(
                lambda p, b: jnp.sum(p), shrd)

    def test_deferred_gather_requires_sharded(self, hvd):
        mono = hvd.DistributedOptimizer(optax.sgd(0.1))
        with pytest.raises(ValueError, match="deferred_param_gather"):
            hvd.data_parallel.make_train_step(
                lambda p, b: jnp.sum(p), mono, deferred_param_gather=True)

    def test_env_resolution(self, hvd, monkeypatch):
        from horovod_tpu.optimizer import resolve_sync_mode

        assert resolve_sync_mode() == "allreduce"
        monkeypatch.setenv("HOROVOD_SYNC_MODE", "sharded")
        assert resolve_sync_mode() == "sharded"
        assert resolve_sync_mode("allreduce") == "allreduce"  # explicit wins
        monkeypatch.setenv("HOROVOD_SYNC_MODE", "zero3")
        with pytest.raises(ValueError, match="zero3"):
            resolve_sync_mode()


class TestElasticReshard:
    def test_unshard_reshard_roundtrip(self, hvd):
        dp = hvd.data_parallel
        params, batch, loss_fn = _mlp_problem()
        shrd = hvd.DistributedOptimizer(optax.adam(0.05),
                                        sync_mode="sharded")
        step = dp.make_train_step(loss_fn, shrd, donate=False)
        p = dp.replicate(params)
        s = dp.shard_state(shrd.init(params))
        b = dp.shard_batch(batch)
        p, s, _ = step(p, s, b)
        full = hvd.unshard_opt_state(shrd, jax.device_get(s), params)
        for n in (4, 3, 8):
            re = hvd.reshard_opt_state(shrd, full, params, n)
            assert all(np.shape(l)[0] == n
                       for l in jax.tree.leaves(re))
            back = hvd.unshard_opt_state(shrd, re, params)
            _assert_tree_close(full, back, rtol=0, atol=0)

    def test_resize_resumes_identical_trajectory(self, hvd):
        """World 8 -> 4 mid-run: the re-sharded continuation matches a
        fresh 4-rank run (monolithic, from the same synced full state)
        step for step."""
        dp = hvd.data_parallel
        params, batch, loss_fn = _mlp_problem()
        shrd = hvd.DistributedOptimizer(optax.adam(0.05),
                                        sync_mode="sharded")
        step8 = dp.make_train_step(loss_fn, shrd, donate=False)
        p = dp.replicate(params)
        s = dp.shard_state(shrd.init(params))
        b = dp.shard_batch(batch)
        for _ in range(2):
            p, s, _ = step8(p, s, b)
        synced_params = jax.device_get(p)
        synced_full = hvd.unshard_opt_state(shrd, jax.device_get(s),
                                            params)
        # Re-shard for the shrunk world; ownership is a pure function of
        # the new size, derived locally.
        ps4 = _get_or_add_ps(hvd, [0, 1, 2, 3])
        re4 = hvd.reshard_opt_state(shrd, synced_full, params, 4)
        shrd4 = hvd.DistributedOptimizer(optax.adam(0.05),
                                         sync_mode="sharded",
                                         process_set=ps4)
        mono4 = hvd.DistributedOptimizer(optax.adam(0.05),
                                         process_set=ps4)
        step_s4 = dp.make_train_step(loss_fn, shrd4, mesh=ps4.mesh,
                                     axis_name=ps4.axis_name, donate=False)
        step_m4 = dp.make_train_step(loss_fn, mono4, mesh=ps4.mesh,
                                     axis_name=ps4.axis_name, donate=False)
        x, y = batch
        b4 = dp.shard_batch((x[:8], y[:8]), mesh=ps4.mesh,
                            axis_name=ps4.axis_name)
        sp = dp.replicate(synced_params, mesh=ps4.mesh)
        sst = dp.shard_state(re4, mesh=ps4.mesh, axis_name=ps4.axis_name)
        mp = dp.replicate(synced_params, mesh=ps4.mesh)
        mst = dp.replicate(synced_full, mesh=ps4.mesh)
        for _ in range(3):
            sp, sst, l_s = step_s4(sp, sst, b4)
            mp, mst, l_m = step_m4(mp, mst, b4)
            assert float(l_s) == pytest.approx(float(l_m), rel=1e-6)
        _assert_tree_close(mp, sp)

    def test_tpu_state_sync_reshards_for_current_world(self, hvd):
        from horovod_tpu.elastic.state import TpuState

        params, batch, loss_fn = _mlp_problem()
        shrd = hvd.DistributedOptimizer(optax.adam(0.05),
                                        sync_mode="sharded")
        full = hvd.unshard_opt_state(shrd, shrd.init(params), params)
        stale = hvd.reshard_opt_state(shrd, full, params, 4)  # old world
        state = TpuState(params=params, opt_state=stale,
                         sharded_optimizer=shrd, epoch=7)
        assert state.needs_world_sync()  # 4-row state in an 8-rank world
        state.sync()
        assert not state.needs_world_sync()
        assert all(np.shape(l)[0] == hvd.size()
                   for l in jax.tree.leaves(state.opt_state))
        want = hvd.reshard_opt_state(shrd, full, params, hvd.size())
        _assert_tree_close(state.opt_state, want, rtol=0, atol=0)
        assert state.epoch == 7

    def test_tpu_state_sync_reshards_monolithic_install(self, hvd):
        # Rung-3 durable restore installs a monolithic-layout state (the
        # gather-on-save checkpoint); sync() must detect and re-shard it.
        from horovod_tpu.elastic.state import TpuState

        params, batch, loss_fn = _mlp_problem()
        shrd = hvd.DistributedOptimizer(optax.adam(0.05),
                                        sync_mode="sharded")
        full = hvd.unshard_opt_state(shrd, shrd.init(params), params)
        state = TpuState(params=params, opt_state=full,
                         sharded_optimizer=shrd)
        assert state.needs_world_sync()
        state.sync()
        want = hvd.reshard_opt_state(shrd, full, params, hvd.size())
        _assert_tree_close(state.opt_state, want, rtol=0, atol=0)

    def test_tpu_state_requires_sharded_optimizer(self, hvd):
        from horovod_tpu.elastic.state import TpuState

        mono = hvd.DistributedOptimizer(optax.sgd(0.1))
        with pytest.raises(ValueError, match="sync_mode='sharded'"):
            TpuState(params={}, opt_state=None, sharded_optimizer=mono)


class TestCheckpointRoundTrip:
    def _trained(self, hvd, steps=2):
        dp = hvd.data_parallel
        params, batch, loss_fn = _mlp_problem()
        shrd = hvd.DistributedOptimizer(optax.adam(0.05),
                                        sync_mode="sharded")
        step = dp.make_train_step(loss_fn, shrd, donate=False)
        p = dp.replicate(params)
        s = dp.shard_state(shrd.init(params))
        b = dp.shard_batch(batch)
        for _ in range(steps):
            p, s, _ = step(p, s, b)
        return params, batch, loss_fn, shrd, step, p, s, b

    def test_sharded_save_is_monolithic_layout(self, hvd, tmp_path):
        from horovod_tpu.checkpoint import (
            load_and_broadcast,
            save_state_on_rank_0,
        )

        params, _, _, shrd, _, p, s, _ = self._trained(hvd)
        path = str(tmp_path / "ckpt.pkl")
        save_state_on_rank_0(path, shrd, jax.device_get(p),
                             jax.device_get(s), step=2)
        obj = load_and_broadcast(path)
        # On disk: the exact monolithic layout (gather-on-save) — shapes
        # match spec.inner.init, not the stacked rows.
        template = hvd.reduce_spec_of(shrd).inner.init(params)
        assert ([np.shape(l) for l in jax.tree.leaves(obj["opt_state"])]
                == [np.shape(l) for l in jax.tree.leaves(template)])
        want = hvd.unshard_opt_state(shrd, jax.device_get(s),
                                     jax.device_get(p))
        _assert_tree_close(obj["opt_state"], want, rtol=0, atol=0)
        assert obj["step"] == 2

    def test_round_trip_resumes_sharded(self, hvd, tmp_path):
        from horovod_tpu.checkpoint import (
            load_state_and_broadcast,
            save_state_on_rank_0,
        )

        dp = hvd.data_parallel
        (params, batch, loss_fn, shrd, step, p, s, b) = self._trained(hvd)
        path = str(tmp_path / "ckpt.pkl")
        save_state_on_rank_0(path, shrd, jax.device_get(p),
                             jax.device_get(s))
        obj = load_state_and_broadcast(path, shrd)
        _assert_tree_close(obj["opt_state"], jax.device_get(s),
                           rtol=0, atol=0)
        # Resumed run continues identically to the uninterrupted one.
        rp = dp.replicate(obj["params"])
        rs = dp.shard_state(obj["opt_state"])
        p1, s1, l1 = step(p, s, b)
        p2, s2, l2 = step(rp, rs, b)
        assert float(l1) == pytest.approx(float(l2), rel=1e-6)
        _assert_tree_close(p1, p2)

    def test_monolithic_checkpoint_resumes_sharded(self, hvd, tmp_path):
        """Cross-mode: a checkpoint written by a MONOLITHIC job restores
        into a sharded one (load re-shards) and the trajectories match."""
        from horovod_tpu.checkpoint import (
            load_state_and_broadcast,
            save_state_on_rank_0,
        )

        dp = hvd.data_parallel
        params, batch, loss_fn = _mlp_problem()
        mono = hvd.DistributedOptimizer(optax.adam(0.05))
        step_m = dp.make_train_step(loss_fn, mono, donate=False)
        pm = dp.replicate(params)
        sm = dp.replicate(mono.init(params))
        b = dp.shard_batch(batch)
        for _ in range(2):
            pm, sm, _ = step_m(pm, sm, b)
        path = str(tmp_path / "mono.pkl")
        save_state_on_rank_0(path, mono, jax.device_get(pm),
                             jax.device_get(sm))
        shrd = hvd.DistributedOptimizer(optax.adam(0.05),
                                        sync_mode="sharded")
        obj = load_state_and_broadcast(path, shrd)
        step_s = dp.make_train_step(loss_fn, shrd, donate=False)
        sp = dp.replicate(obj["params"])
        ss = dp.shard_state(obj["opt_state"])
        pm, sm, lm = step_m(pm, sm, b)
        sp, ss, ls = step_s(sp, ss, b)
        assert float(lm) == pytest.approx(float(ls), rel=1e-6)
        _assert_tree_close(pm, sp)


class TestCrossModeResumeChain:
    """PR 8 satellite: the checkpoint layout is mode-INDEPENDENT across
    all three sync modes, proven as a resume CHAIN — fsdp → sharded →
    monolithic → fsdp, one file per hop — whose loss trajectory matches
    an uninterrupted monolithic run step for step."""

    def test_fsdp_sharded_monolithic_chain(self, hvd, tmp_path):
        from horovod_tpu.checkpoint import (
            load_state_and_broadcast,
            save_state_on_rank_0,
        )
        from horovod_tpu.parallel.param_sharding import ShardedParams

        dp = hvd.data_parallel
        params, batch, loss_fn = _mlp_problem()
        b = dp.shard_batch(batch)

        # The uninterrupted monolithic reference: 5 steps.
        mono_ref = hvd.DistributedOptimizer(optax.adam(0.05))
        step_ref = dp.make_train_step(loss_fn, mono_ref, donate=False)
        pr, sr = dp.replicate(params), dp.replicate(mono_ref.init(params))
        ref_losses = []
        for _ in range(5):
            pr, sr, loss = step_ref(pr, sr, b)
            ref_losses.append(float(loss))

        chain_losses = []

        # Hop 1: 2 steps under fsdp, save.
        fsdp = hvd.DistributedOptimizer(optax.adam(0.05), sync_mode="fsdp")
        step_f = dp.make_train_step(loss_fn, fsdp, donate=False)
        p = dp.shard_state(hvd.shard_params(params))
        s = dp.shard_state(fsdp.init(params))
        for _ in range(2):
            p, s, loss = step_f(p, s, b)
            chain_losses.append(float(loss))
        path1 = str(tmp_path / "hop1.pkl")
        save_state_on_rank_0(path1, fsdp, jax.device_get(p),
                             jax.device_get(s))

        # Hop 2: resume as sharded, 1 step, save.
        shrd = hvd.DistributedOptimizer(optax.adam(0.05),
                                        sync_mode="sharded")
        obj = load_state_and_broadcast(path1, shrd)
        assert not isinstance(obj["params"], ShardedParams)
        step_s = dp.make_train_step(loss_fn, shrd, donate=False)
        p = dp.replicate(obj["params"])
        s = dp.shard_state(obj["opt_state"])
        p, s, loss = step_s(p, s, b)
        chain_losses.append(float(loss))
        path2 = str(tmp_path / "hop2.pkl")
        save_state_on_rank_0(path2, shrd, jax.device_get(p),
                             jax.device_get(s))

        # Hop 3: resume as monolithic, 1 step, save.
        mono = hvd.DistributedOptimizer(optax.adam(0.05))
        obj = load_state_and_broadcast(path2, mono)
        step_m = dp.make_train_step(loss_fn, mono, donate=False)
        p = dp.replicate(obj["params"])
        s = dp.replicate(obj["opt_state"])
        p, s, loss = step_m(p, s, b)
        chain_losses.append(float(loss))
        path3 = str(tmp_path / "hop3.pkl")
        save_state_on_rank_0(path3, mono, jax.device_get(p),
                             jax.device_get(s))

        # Hop 4: back to fsdp (load re-shards params into resident rows).
        fsdp2 = hvd.DistributedOptimizer(optax.adam(0.05),
                                         sync_mode="fsdp")
        obj = load_state_and_broadcast(path3, fsdp2)
        assert isinstance(obj["params"], ShardedParams)
        step_f2 = dp.make_train_step(loss_fn, fsdp2, donate=False)
        p = dp.shard_state(obj["params"])
        s = dp.shard_state(obj["opt_state"])
        p, s, loss = step_f2(p, s, b)
        chain_losses.append(float(loss))

        assert chain_losses == pytest.approx(ref_losses, rel=1e-5)


class TestFsdpElasticResizeChain:
    def test_resize_8_4_6_keeps_trajectory(self, hvd):
        """Elastic resize chain 8 -> 4 -> 6 under fsdp (the PR 7 resize
        pattern, extended to resident params): each hop unshard-reshards
        params AND optimizer rows for the new world, and every segment
        of the chain matches a monolithic run from the same synced state
        on the same process set, step for step."""
        dp = hvd.data_parallel
        params, batch, loss_fn = _mlp_problem(batch=24)
        x, y = batch

        def world(ranks):
            if len(ranks) == 8:
                return None, hvd.global_mesh(), "hvd"
            ps = _get_or_add_ps(hvd, ranks)
            return ps, ps.mesh, ps.axis_name

        cur_params, cur_full_state = params, None
        for ranks, nbatch in (([*range(8)], 24), ([*range(4)], 16),
                              ([*range(6)], 24)):
            n = len(ranks)
            ps, mesh, axis = world(ranks)
            kw = dict(process_set=ps) if ps is not None else {}
            fsdp = hvd.DistributedOptimizer(optax.adam(0.05),
                                            sync_mode="fsdp", **kw)
            mono = hvd.DistributedOptimizer(optax.adam(0.05), **kw)
            step_f = dp.make_train_step(loss_fn, fsdp, mesh=mesh,
                                        axis_name=axis, donate=False)
            step_m = dp.make_train_step(loss_fn, mono, mesh=mesh,
                                        axis_name=axis, donate=False)
            bb = dp.shard_batch((x[:nbatch], y[:nbatch]), mesh=mesh,
                                axis_name=axis)
            # Re-shard the synced full state for THIS world (ownership
            # is a pure function of the new size — no coordination).
            sp = dp.shard_state(hvd.shard_params(cur_params, n), mesh=mesh,
                                axis_name=axis)
            if cur_full_state is None:
                sf = dp.shard_state(
                    hvd.init_sharded_state(fsdp, cur_params, world_size=n),
                    mesh=mesh, axis_name=axis)
                mono_state = mono.init(cur_params)
            else:
                sf = dp.shard_state(
                    hvd.reshard_opt_state(fsdp, cur_full_state,
                                          cur_params, n),
                    mesh=mesh, axis_name=axis)
                mono_state = cur_full_state
            pm = dp.replicate(cur_params, mesh=mesh)
            sm = dp.replicate(mono_state, mesh=mesh)
            for _ in range(2):
                sp, sf, l_f = step_f(sp, sf, bb)
                pm, sm, l_m = step_m(pm, sm, bb)
                assert float(l_f) == pytest.approx(float(l_m), rel=1e-6)
            # "Sync": gather to the mode-independent layout for the next
            # world (what TpuState.sync does across a real resize).
            cur_params = hvd.unshard_params(jax.device_get(sp))
            cur_full_state = hvd.unshard_opt_state(
                fsdp, jax.device_get(sf), cur_params)
            _assert_tree_close(jax.device_get(pm), cur_params)
            _assert_tree_close(jax.device_get(sm), cur_full_state)


class TestAutotuneSyncModeAxis:
    """The sync_mode axis in the joint warmup grid: candidates expand the
    product, _pin pins the mode process-wide, and an abort pins the
    rank-identical FIRST candidate with the usual poisoning."""

    class _Step:
        def __init__(self, fail_at=None):
            self.calls = 0
            self.fail_at = fail_at

        def __call__(self, x):
            self.calls += 1
            if self.fail_at is not None and self.calls >= self.fail_at:
                raise RuntimeError("window exploded")
            return jnp.zeros(())

        def clear_cache(self):
            pass

    def _cleanup(self):
        from horovod_tpu import autotune as at

        at.set_tuned_threshold(None)
        at.set_tuned_segments(None)
        at.set_tuned_sync_mode(None)
        at._tuned["aborted"] = False
        at._tuned["history"].clear()

    def test_joint_grid_and_pin(self, hvd):
        from horovod_tpu import autotune as at
        from horovod_tpu.optimizer import resolve_sync_mode

        tuner = at.AutotuneStep(
            self._Step(), thresholds=(1024, 4096), iters=1,
            segment_candidates=(2, 4),
            sync_mode_candidates=("allreduce", "sharded"))
        assert len(tuner._cands) == 2 * 2 * 2
        assert all(len(c) == 3 for c in tuner._cands)
        t = {"now": 0.0}

        def clock():  # sharded windows are cheaper, deterministically
            t["now"] += 1.0 if at.tuned_sync_mode() == "sharded" else 2.0
            return t["now"]

        tuner._clock = clock
        try:
            for _ in range(len(tuner._cands) * tuner._win):
                tuner(1.0)
            assert not tuner._hvd_tuning
            assert at.tuned_sync_mode() == "sharded"
            assert at.autotune_state()["sync_mode"] == "sharded"
            # Optimizers built after the pin inherit the decision.
            assert resolve_sync_mode() == "sharded"
        finally:
            self._cleanup()

    def test_abort_pins_first_candidate_and_poisons(self, hvd):
        from horovod_tpu import autotune as at
        from horovod_tpu.exceptions import HorovodInternalError

        tuner = at.AutotuneStep(
            self._Step(fail_at=2), thresholds=(1024, 4096), iters=1,
            sync_mode_candidates=("sharded", "allreduce"))
        try:
            tuner(1.0)  # window 0 settles fine
            with pytest.raises(RuntimeError, match="window exploded"):
                tuner(1.0)
            # Rank-identical first candidate pinned, both axes.
            assert at.tuned_threshold() == 1024
            assert at.tuned_sync_mode() == "sharded"
            assert at.warmup_aborted()
            with pytest.raises(HorovodInternalError):
                tuner(1.0)
        finally:
            self._cleanup()

    def test_tune_step_sync_mode_explicit(self, hvd):
        import time

        from horovod_tpu import autotune as at

        built = []

        def build(mode):
            built.append(mode)

            def run():
                if mode != "sharded":
                    time.sleep(0.03)
                return jnp.zeros(())

            return run

        try:
            best = at.tune_step_sync_mode(build, iters=1)
            # fsdp joined the default sweep axis (PR 8).
            assert built == ["allreduce", "sharded", "fsdp"]
            assert best == "sharded"
            assert at.tuned_sync_mode() == "sharded"
        finally:
            self._cleanup()

    def test_tune_step_sync_mode_abort_pins_first(self, hvd):
        from horovod_tpu import autotune as at

        def build(mode):
            if mode == "sharded":
                raise RuntimeError("boom")
            return lambda: jnp.zeros(())

        try:
            with pytest.raises(RuntimeError, match="boom"):
                at.tune_step_sync_mode(build, iters=1)
            assert at.tuned_sync_mode() == "allreduce"
        finally:
            self._cleanup()


class TestUnshardReshardEdgeCases:
    """The substrate the peer recovery rung stands on: re-materializing a
    departed rank's shard is ``stack rows -> unshard -> reshard``, so
    these two must be EXACT (bitwise) for every layout the replica plane
    can hand them — world size 1, uneven leaves, scalar leaves, resizes
    across non-divisible world sizes."""

    def _spec(self, inner=None):
        from horovod_tpu.optimizer import ReduceSpec

        return ReduceSpec(
            inner=inner if inner is not None else optax.sgd(
                0.1, momentum=0.9),
            op="average", compression=None, prescale_factor=1.0,
            postscale_factor=1.0, process_set=None, num_groups=0,
            fusion_threshold_bytes=None, backward_passes_per_step=1,
            sync_mode="sharded")

    def _filled_full(self, spec, params, seed=0):
        """The monolithic state with every leaf filled with distinct
        bit-patterns (zeros would hide transposition/padding bugs)."""
        rng = np.random.RandomState(seed)
        full = spec.inner.init(params)
        return jax.tree.map(
            lambda l: np.asarray(
                rng.standard_normal(np.shape(l)) if np.ndim(l) else
                rng.standard_normal(), dtype=np.asarray(l).dtype
            ).reshape(np.shape(l)),
            jax.device_get(full))

    def _assert_exact(self, a, b):
        la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            x, y = np.asarray(x), np.asarray(y)
            assert x.dtype == y.dtype, (x.dtype, y.dtype)
            np.testing.assert_array_equal(x, y)

    def test_world_size_one_roundtrip(self, hvd):
        params = {"w": np.arange(5, dtype=np.float32),
                  "b": np.float32(2.0)}
        spec = self._spec()
        full = self._filled_full(spec, params)
        sharded = hvd.reshard_opt_state(spec, full, params, 1)
        for leaf in jax.tree.leaves(sharded):
            assert np.shape(leaf)[0] == 1
        back = hvd.unshard_opt_state(spec, sharded, params)
        self._assert_exact(full, back)

    def test_uneven_leaves_roundtrip(self, hvd):
        # 7 and 5 elements over n=4: both leaves need padding, and the
        # padding must never leak back into the unsharded view.
        params = {"a": np.arange(7, dtype=np.float32).reshape(7),
                  "b": np.arange(5, dtype=np.float32)}
        spec = self._spec()
        full = self._filled_full(spec, params, seed=1)
        sharded = hvd.reshard_opt_state(spec, full, params, 4)
        back = hvd.unshard_opt_state(spec, sharded, params)
        self._assert_exact(full, back)

    def test_scalar_leaves_roundtrip(self, hvd):
        # adam carries a scalar step count: scalars stack to (n,) and
        # must come back as 0-d with the dtype intact.
        params = {"w": np.arange(6, dtype=np.float32)}
        spec = self._spec(inner=optax.adam(0.05))
        full = self._filled_full(spec, params, seed=2)
        sharded = hvd.reshard_opt_state(spec, full, params, 3)
        back = hvd.unshard_opt_state(spec, sharded, params)
        self._assert_exact(full, back)
        scalars = [l for l in jax.tree.leaves(back) if np.ndim(l) == 0]
        assert scalars, "adam state lost its scalar count leaf"

    def test_resize_across_non_divisible_world_sizes(self, hvd):
        # n=3 -> n=5 -> n=2 -> back to monolithic: ownership re-derives
        # from each world size alone; every hop must be lossless even
        # though no size divides the leaf sizes.
        params = {"w": np.arange(11, dtype=np.float32),
                  "v": np.arange(4, dtype=np.float32).reshape(2, 2)}
        spec = self._spec()
        full = self._filled_full(spec, params, seed=3)
        state = full
        for n in (3, 5, 2):
            state = hvd.reshard_opt_state(spec, state if n == 3 else
                                          hvd.unshard_opt_state(
                                              spec, state, params),
                                          params, n)
            for leaf in jax.tree.leaves(state):
                assert np.shape(leaf)[0] == n
        back = hvd.unshard_opt_state(spec, state, params)
        self._assert_exact(full, back)

    def test_row_stack_matches_reshard(self, hvd):
        # The peer rung's exact reconstruction path: per-rank rows pulled
        # from replicas, re-stacked, must equal the resharded layout the
        # live world held — byte for byte.
        params = {"w": np.arange(9, dtype=np.float32)}
        spec = self._spec()
        full = self._filled_full(spec, params, seed=4)
        n = 4
        sharded = hvd.reshard_opt_state(spec, full, params, n)
        rows = [jax.tree.map(lambda l: np.asarray(l)[r], sharded)
                for r in range(n)]
        restacked = jax.tree.map(
            lambda *xs: np.stack([np.asarray(x) for x in xs]), *rows)
        self._assert_exact(jax.device_get(sharded), restacked)
        self._assert_exact(full,
                           hvd.unshard_opt_state(spec, restacked, params))
