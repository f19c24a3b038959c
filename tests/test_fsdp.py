"""Full parameter sharding (``sync_mode="fsdp"``, ZeRO-3 / FSDP style).

Params live sharded at rest (each rank resident-holds ~1/n as stacked
``ShardedParams`` rows); full tensors exist only transiently per
segment: the forward allgathers each segment just in time, the backward
emits the gradient reduce-scatter inside backprop at the gather
boundaries (custom-vjp), and the shard-local update writes back to the
resident shard with no trailing allgather. Asserted here:

- shard/unshard/reshard round trips are bitwise (uneven leaves, scalar
  leaves, world 1, non-divisible resize chains) and the metadata
  (shapes/dtypes/structure) survives pickling — the peer replica plane
  stands on this;
- the fsdp step matches the monolithic allreduce step — loss trajectory,
  params, AND optimizer state — within reduction-order tolerance, on the
  8-dev mesh, including under the overlapped factory, explicit segment
  counts, the retain-after-forward knob, and the int8 wire;
- the traced program has the right wire shape: one all-gather per
  segment in the forward, one reduce-scatter per segment in the
  backward, and NO trailing post-update all-gather;
- per-rank resident param+opt bytes are < 40% of monolithic on the
  8-dev mesh (the acceptance memory bar);
- the guard table: num_groups>1, Adasum, accumulation, hierarchical
  meshes, deferred_param_gather, and the elastic factory are all
  rejected with actionable messages;
- elastic: ``TpuState(sharded_optimizer=<fsdp>)`` re-shards the resident
  rows across world changes, monolithic installs heal at sync();
- autotune: fsdp joins the sync_mode sweep, and ineligible modes are
  SKIPPED (not aborted) during the sweep.
"""

import pickle

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from horovod_tpu.parallel.param_sharding import (
    ShardedParams,
    gather_params,
    reshard_params,
    resident_param_bytes,
    shard_params,
    stack_param_rows,
    unshard_params,
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
    wire: over ``ops.fusion.PACK_CUTOFF_BYTES``, so it is gathered and
    scattered as itself beside the packed small leaves."""
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


def _rows_by_hand(leaf, world):
    """A leaf's resident ``(world, s)`` rows as ``shard_ownership`` lays
    them out, written down without the library: the layout every
    checkpoint and peer replica was written in."""
    flat = np.asarray(leaf).ravel()
    s = -(-flat.size // world)
    return np.pad(flat, (0, world * s - flat.size)).reshape(world, s)


def _assert_tree_close(a, b, rtol=1e-5, atol=1e-6):
    jax.tree.map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=rtol, atol=atol),
        a, b)


def _assert_tree_exact(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype, (x.dtype, y.dtype)
        np.testing.assert_array_equal(x, y)


class TestResidentLayout:
    def test_roundtrip_uneven_and_scalar_leaves(self, hvd):
        params = {"w": np.arange(11, dtype=np.float32),
                  "v": np.arange(6, dtype=np.float32).reshape(2, 3),
                  "s": np.float32(4.0),
                  "i": np.arange(3, dtype=np.int32)}
        for n in (1, 3, 8):
            sp = shard_params(params, n)
            assert sp.world_size == n
            for row in sp.rows:
                assert np.shape(row)[0] == n
            _assert_tree_exact(params, unshard_params(sp))

    def test_resident_bytes_are_one_nth(self, hvd):
        params = {"w": np.zeros(1000, np.float32)}
        sp = shard_params(params, 8)
        # ceil(1000/8)=125 f32 per rank.
        assert resident_param_bytes(sp) == 125 * 4

    def test_resize_chain_non_divisible(self, hvd):
        params = {"w": np.arange(13, dtype=np.float32),
                  "b": np.arange(4, dtype=np.float32).reshape(2, 2)}
        sp = shard_params(params, 3)
        for n in (5, 2, 7, 1):
            sp = reshard_params(sp, n)
            assert sp.world_size == n
        _assert_tree_exact(params, unshard_params(sp))

    def test_row_stack_reconstruction(self, hvd):
        # The peer replica path: per-rank row pytrees -> stacked resident
        # layout -> full params, byte for byte.
        params = {"a": np.arange(9, dtype=np.float32),
                  "b": np.arange(5, dtype=np.float32)}
        sp = shard_params(params, 4)
        rows = [sp.row(r) for r in range(4)]
        restacked = stack_param_rows(rows, sp.meta)
        _assert_tree_exact(unshard_params(sp), unshard_params(restacked))
        with pytest.raises(ValueError, match="4 rows"):
            stack_param_rows(rows[:2], sp.meta)

    def test_pickle_roundtrip(self, hvd):
        # Peer replica records and elastic commit snapshots pickle the
        # rows AND the metadata (treedef included).
        params = {"w": np.arange(7, dtype=np.float32),
                  "b": np.float32(2.0)}
        sp = shard_params(params, 3)
        sp2 = pickle.loads(pickle.dumps(jax.device_get(sp)))
        assert isinstance(sp2, ShardedParams)
        _assert_tree_exact(params, unshard_params(sp2))

    def test_is_a_pytree(self, hvd):
        params = {"w": np.arange(8, dtype=np.float32)}
        sp = shard_params(params, 4)
        doubled = jax.tree.map(lambda a: a * 2, sp)
        assert isinstance(doubled, ShardedParams)
        _assert_tree_exact(
            jax.tree.map(lambda a: a * 2, params), unshard_params(doubled))

    def test_unshard_rejects_plain_tree(self, hvd):
        with pytest.raises(TypeError, match="ShardedParams"):
            unshard_params({"w": np.zeros(4)})


class TestFsdpEquivalence:
    """The numerical contract: the fsdp step matches monolithic
    allreduce — loss trajectory, params, optimizer state — within
    reduction-order tolerance (f32 ulp on the 8-dev CPU mesh)."""

    def _run_mono(self, hvd, opt, params, batch, loss_fn, steps):
        dp = hvd.data_parallel
        step = dp.make_train_step(loss_fn, opt, donate=False)
        p = dp.replicate(params)
        s = dp.replicate(opt.init(params))
        b = dp.shard_batch(batch)
        losses = []
        for _ in range(steps):
            p, s, loss = step(p, s, b)
            losses.append(float(loss))
        return p, s, losses

    def _run_fsdp(self, hvd, opt, params, batch, loss_fn, steps,
                  factory=None, **factory_kwargs):
        dp = hvd.data_parallel
        factory = factory or dp.make_train_step
        step = factory(loss_fn, opt, donate=False, **factory_kwargs)
        p = dp.shard_state(hvd.shard_params(params))
        s = dp.shard_state(opt.init(params))
        b = dp.shard_batch(batch)
        losses = []
        for _ in range(steps):
            p, s, loss = step(p, s, b)
            losses.append(float(loss))
        return p, s, losses

    def test_matches_monolithic_params_state_and_loss(self, hvd):
        params, batch, loss_fn = _mlp_problem()
        mono = hvd.DistributedOptimizer(optax.adam(0.05))
        fsdp = hvd.DistributedOptimizer(optax.adam(0.05), sync_mode="fsdp")
        pm, sm, lm = self._run_mono(hvd, mono, params, batch, loss_fn, 3)
        pf, sf, lf = self._run_fsdp(hvd, fsdp, params, batch, loss_fn, 3)
        assert lm == pytest.approx(lf, rel=1e-6)
        assert isinstance(pf, ShardedParams)
        _assert_tree_close(pm, unshard_params(jax.device_get(pf)))
        full_p = unshard_params(jax.device_get(pf))
        full_s = hvd.unshard_opt_state(fsdp, jax.device_get(sf), full_p)
        _assert_tree_close(jax.device_get(sm), full_s)

    def test_a_leaf_over_the_pack_cutoff_from_rows_in_the_old_layout(
            self, hvd, monkeypatch):
        from horovod_tpu.ops import fusion

        params, batch, loss_fn = _wide_problem()
        assert params["wide"]["w"].nbytes >= fusion.PACK_CUTOFF_BYTES
        mono = hvd.DistributedOptimizer(optax.adam(0.01))
        fsdp = hvd.DistributedOptimizer(optax.adam(0.01), sync_mode="fsdp")
        pm, sm, lm = self._run_mono(hvd, mono, params, batch, loss_fn, 3)
        # A checkpoint's rows, laid out by hand: what shard_params makes,
        # to the bit, whatever the wire packs.
        resident = hvd.shard_params(params)
        by_hand = ShardedParams(
            [jnp.asarray(_rows_by_hand(leaf, hvd.size()))
             for leaf in jax.tree.leaves(params)], resident.meta)
        _assert_tree_exact(jax.device_get(resident), by_hand)
        monkeypatch.setattr(hvd, "shard_params", lambda _: by_hand)
        pf, sf, lf = self._run_fsdp(hvd, fsdp, params, batch, loss_fn, 3)
        assert lm == pytest.approx(lf, rel=1e-6)
        full_p = unshard_params(jax.device_get(pf))
        _assert_tree_close(pm, full_p)
        _assert_tree_close(
            jax.device_get(sm),
            hvd.unshard_opt_state(fsdp, jax.device_get(sf), full_p))

    def test_overlapped_factory_and_explicit_segments(self, hvd):
        params, batch, loss_fn = _mlp_problem()
        mono = hvd.DistributedOptimizer(optax.adam(0.05))
        fsdp = hvd.DistributedOptimizer(optax.adam(0.05), sync_mode="fsdp")
        pm, _, lm = self._run_mono(hvd, mono, params, batch, loss_fn, 3)
        dp = hvd.data_parallel
        po, _, lo = self._run_fsdp(
            hvd, fsdp, params, batch, loss_fn, 3,
            factory=dp.make_overlapped_train_step, num_segments=3)
        assert lm == pytest.approx(lo, rel=1e-6)
        _assert_tree_close(pm, unshard_params(jax.device_get(po)))

    def test_reshard_after_forward_knob(self, hvd, monkeypatch):
        # K segments (default) vs one retained up-front gather: the same
        # math, different gather granularity.
        params, batch, loss_fn = _mlp_problem()
        fsdp = hvd.DistributedOptimizer(optax.adam(0.05), sync_mode="fsdp")
        _, _, l_seg = self._run_fsdp(hvd, fsdp, params, batch, loss_fn, 3)
        monkeypatch.setenv("HOROVOD_FSDP_RESHARD_AFTER_FORWARD", "0")
        _, _, l_one = self._run_fsdp(hvd, fsdp, params, batch, loss_fn, 3)
        assert l_seg == pytest.approx(l_one, rel=1e-6)

    def test_int8_wire_matches_monolithic(self, hvd):
        params, batch, loss_fn = _mlp_problem()
        m8 = hvd.DistributedOptimizer(
            optax.sgd(0.05), compression=hvd.Compression.int8)
        f8 = hvd.DistributedOptimizer(
            optax.sgd(0.05), compression=hvd.Compression.int8,
            sync_mode="fsdp")
        pm, _, _ = self._run_mono(hvd, m8, params, batch, loss_fn, 2)
        pf, sf, _ = self._run_fsdp(hvd, f8, params, batch, loss_fn, 2)
        _assert_tree_close(pm, unshard_params(jax.device_get(pf)),
                           rtol=0.05, atol=0.04)
        # The stochastic-rounding salt advanced once per step, per rank.
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(sf).counter), np.full((8,), 2))

    def test_stable_across_retraces(self, hvd):
        dp = hvd.data_parallel
        params, batch, loss_fn = _mlp_problem()
        fsdp = hvd.DistributedOptimizer(optax.adam(0.05), sync_mode="fsdp")
        step = dp.make_train_step(loss_fn, fsdp, donate=False)
        p = dp.shard_state(hvd.shard_params(params))
        s = dp.shard_state(fsdp.init(params))
        b = dp.shard_batch(batch)
        p1, s1, l1 = step(p, s, b)
        step.clear_cache()
        p2, s2, l2 = step(p, s, b)
        assert float(l1) == float(l2)
        _assert_tree_exact(jax.device_get(p1), jax.device_get(p2))
        _assert_tree_exact(jax.device_get(s1), jax.device_get(s2))

    def test_flush_records_land_under_the_fsdp_label_only(self, hvd):
        # The gather boundary's backward reduce-scatter must record ONE
        # flush per segment, labeled sync_mode='fsdp' — not a phantom
        # 'sharded' series on top (the label rides down the shared wire).
        from horovod_tpu import metrics

        metrics.reset_for_testing()
        try:
            params, batch, loss_fn = _mlp_problem()
            fsdp = hvd.DistributedOptimizer(optax.adam(0.05),
                                            sync_mode="fsdp")
            self._run_fsdp(hvd, fsdp, params, batch, loss_fn, 1)
            samples = metrics.GRAD_SYNC_FLUSHES.dump()["samples"]
            by_mode = {s["labels"]["sync_mode"]: s["value"]
                       for s in samples if s["value"] > 0}
            assert set(by_mode) == {"fsdp"}, by_mode
        finally:
            metrics.reset_for_testing()

    def test_resident_bytes_under_40_percent(self, hvd):
        # The acceptance memory bar, on the real 8-dev layouts the step
        # consumes: per-rank resident param+opt bytes < 40% of
        # monolithic (here exactly ~1/8 plus padding).
        params, _, _ = _mlp_problem()
        fsdp = hvd.DistributedOptimizer(optax.adam(0.05), sync_mode="fsdp")
        mono = hvd.DistributedOptimizer(optax.adam(0.05))
        sp = hvd.shard_params(params)
        stacked = fsdp.init(params)

        def nbytes(tree):
            return sum(np.asarray(l).size * np.asarray(l).dtype.itemsize
                       for l in jax.tree.leaves(tree))

        resident = (resident_param_bytes(sp)
                    + nbytes(stacked) // hvd.size())
        monolithic = nbytes(params) + nbytes(mono.init(params))
        assert resident < 0.40 * monolithic, (resident, monolithic)


class TestWireShape:
    """The traced program's collective sequence: one all-gather per
    segment in the forward, one psum_scatter per segment in the
    backward, and NO trailing post-update all-gather (the no-trailing-
    allgather contract that distinguishes fsdp from sharded)."""

    def _jaxpr_ops(self, hvd, num_segments):
        dp = hvd.data_parallel
        params, batch, loss_fn = _mlp_problem()
        fsdp = hvd.DistributedOptimizer(optax.sgd(0.05), sync_mode="fsdp")
        spec = hvd.reduce_spec_of(fsdp)
        mesh = hvd.global_mesh()

        def spmd(rows, batch):
            shards = jax.tree.unflatten(
                rows.meta.treedef, [a[0] for a in rows.rows])

            def loss_of(sh):
                full = gather_params(sh, rows.meta, spec, "hvd", 8,
                                     num_segments=num_segments)
                return loss_fn(full, batch)

            loss, g = jax.value_and_grad(loss_of)(shards)
            # the "update": pure elementwise on shards — no collective
            new = jax.tree.map(lambda a, b: a - 0.05 * b, shards, g)
            return jax.tree.unflatten(
                jax.tree.structure(rows),
                [a[None] for a in jax.tree.leaves(new)]), loss

        sp = hvd.shard_params(params, 8)
        fn = jax.shard_map(
            spmd, mesh=mesh, in_specs=(P("hvd"), P("hvd")),
            out_specs=(P("hvd"), P()), check_vma=False)
        jaxpr = jax.make_jaxpr(fn)(
            jax.device_get(sp), (np.zeros((16, 8), np.float32),
                                 np.zeros((16,), np.float32)))
        import collections

        counts: collections.Counter = collections.Counter()

        def walk(jx):
            for eqn in jx.eqns:
                counts[eqn.primitive.name] += 1
                for v in eqn.params.values():
                    if hasattr(v, "jaxpr"):
                        walk(v.jaxpr)
                    elif hasattr(v, "eqns"):
                        walk(v)

        walk(jaxpr.jaxpr)
        return counts["all_gather"], counts["reduce_scatter"]

    def test_one_gather_and_one_rs_per_segment(self, hvd):
        gathers, scatters = self._jaxpr_ops(hvd, num_segments=3)
        assert gathers == 3, gathers   # forward only — no trailing AG
        assert scatters == 3, scatters  # one RS per segment, in backward

    def test_single_segment_degenerates(self, hvd):
        gathers, scatters = self._jaxpr_ops(hvd, num_segments=1)
        assert gathers == 1 and scatters == 1


class TestFsdpGuards:
    def test_rejects_adasum(self, hvd):
        with pytest.raises(ValueError, match="Average/Sum"):
            hvd.DistributedOptimizer(optax.sgd(0.1), op=hvd.Adasum,
                                     sync_mode="fsdp")

    def test_rejects_gradient_accumulation(self, hvd):
        with pytest.raises(ValueError, match="backward_passes_per_step"):
            hvd.DistributedOptimizer(optax.sgd(0.1),
                                     backward_passes_per_step=2,
                                     sync_mode="fsdp")

    def test_rejects_num_groups(self, hvd):
        with pytest.raises(ValueError,
                           match="fusion_threshold_bytes instead"):
            hvd.DistributedOptimizer(optax.sgd(0.1), num_groups=4,
                                     sync_mode="fsdp")

    def test_rejects_hierarchical_mesh(self, hvd):
        fsdp = hvd.DistributedOptimizer(optax.sgd(0.1), sync_mode="fsdp")
        with pytest.raises(ValueError, match="hierarchical"):
            hvd.data_parallel.make_train_step(
                lambda p, b: jnp.sum(p), fsdp, hierarchical=(2, 4))
        with pytest.raises(ValueError, match="hierarchical"):
            hvd.data_parallel.make_overlapped_train_step(
                lambda p, b: jnp.sum(p), fsdp, hierarchical=(2, 4))

    def test_rejects_deferred_param_gather(self, hvd):
        fsdp = hvd.DistributedOptimizer(optax.sgd(0.1), sync_mode="fsdp")
        with pytest.raises(ValueError, match="NO trailing"):
            hvd.data_parallel.make_train_step(
                lambda p, b: jnp.sum(p), fsdp, deferred_param_gather=True)

    def test_rejects_elastic_factory(self, hvd):
        fsdp = hvd.DistributedOptimizer(optax.sgd(0.1), sync_mode="fsdp")
        with pytest.raises(ValueError, match="PeerShardedState"):
            hvd.data_parallel.make_elastic_train_step(
                lambda p, b: jnp.sum(p), fsdp)

    def test_env_resolution(self, hvd, monkeypatch):
        from horovod_tpu.optimizer import resolve_sync_mode

        monkeypatch.setenv("HOROVOD_SYNC_MODE", "fsdp")
        assert resolve_sync_mode() == "fsdp"
        assert resolve_sync_mode("sharded") == "sharded"  # explicit wins

    def test_update_requires_params(self, hvd):
        fsdp = hvd.DistributedOptimizer(optax.sgd(0.1), sync_mode="fsdp")
        with pytest.raises(ValueError, match="params="):
            fsdp.update({"w": jnp.zeros(3)}, {"w": jnp.zeros(3)})

    def test_init_rejects_conflicting_world_size(self, hvd):
        from horovod_tpu.optimizer import init_sharded_state

        fsdp = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                                        sync_mode="fsdp")
        sp = shard_params({"w": np.arange(8, dtype=np.float32)}, 8)
        with pytest.raises(ValueError, match="reshard_params"):
            init_sharded_state(fsdp, sp, world_size=6)
        # Matching size (or omitted) is fine.
        st = init_sharded_state(fsdp, sp, world_size=8)
        assert np.shape(jax.tree.leaves(st)[0])[0] == 8


class TestFsdpElasticState:
    def test_tpu_state_reshards_stale_world(self, hvd):
        from horovod_tpu.elastic.state import TpuState

        params, batch, loss_fn = _mlp_problem()
        fsdp = hvd.DistributedOptimizer(optax.adam(0.05), sync_mode="fsdp")
        full_s = hvd.unshard_opt_state(fsdp, fsdp.init(params), params)
        stale_p = hvd.shard_params(params, 4)            # old world
        stale_s = hvd.reshard_opt_state(fsdp, full_s, params, 4)
        state = TpuState(params=stale_p, opt_state=stale_s,
                         sharded_optimizer=fsdp, epoch=5)
        assert state.needs_world_sync()
        state.sync()
        assert not state.needs_world_sync()
        assert state.params.world_size == hvd.size()
        _assert_tree_exact(params, unshard_params(state.params))
        assert state.epoch == 5

    def test_tpu_state_heals_monolithic_install(self, hvd):
        # A durable-rung restore installs FULL params (gather-on-save
        # layout); sync() must re-shard them into the resident rows.
        from horovod_tpu.elastic.state import TpuState

        params, _, _ = _mlp_problem()
        fsdp = hvd.DistributedOptimizer(optax.adam(0.05), sync_mode="fsdp")
        full_s = hvd.unshard_opt_state(fsdp, fsdp.init(params), params)
        state = TpuState(params=params, opt_state=full_s,
                         sharded_optimizer=fsdp)
        assert state.needs_world_sync()
        state.sync()
        assert isinstance(state.params, ShardedParams)
        assert not state.needs_world_sync()


class TestAutotuneFsdpAxis:
    def _cleanup(self):
        from horovod_tpu import autotune as at

        at.set_tuned_threshold(None)
        at.set_tuned_segments(None)
        at.set_tuned_sync_mode(None)
        at._tuned["aborted"] = False
        at._tuned["history"].clear()

    def test_fsdp_is_a_valid_pin(self, hvd):
        from horovod_tpu import autotune as at
        from horovod_tpu.optimizer import resolve_sync_mode

        try:
            at.set_tuned_sync_mode("fsdp")
            assert resolve_sync_mode() == "fsdp"
        finally:
            self._cleanup()

    def test_sweep_includes_fsdp_and_pins_fastest(self, hvd):
        import time

        from horovod_tpu import autotune as at

        built = []

        def build(mode):
            built.append(mode)

            def run():
                if mode != "fsdp":
                    time.sleep(0.03)
                return jnp.zeros(())

            return run

        try:
            best = at.tune_step_sync_mode(build, iters=1)
            assert built == ["allreduce", "sharded", "fsdp"]
            assert best == "fsdp"
            assert at.tuned_sync_mode() == "fsdp"
        finally:
            self._cleanup()

    def test_replicated_params_builder_skips_fsdp(self, hvd):
        # A pre-existing builder that feeds replicated params (valid for
        # allreduce/sharded) must SKIP the fsdp candidate — the factory
        # step's resident-layout guard is a ValueError eligibility fact,
        # not an abort.
        from horovod_tpu import autotune as at

        dp = hvd.data_parallel
        params, batch, loss_fn = _mlp_problem(n_layers=1)
        b = dp.shard_batch(batch)

        def build(mode):
            opt = hvd.DistributedOptimizer(optax.sgd(0.05),
                                           sync_mode=mode)
            step = dp.make_train_step(loss_fn, opt, donate=False)
            p = dp.replicate(params)  # WRONG layout for fsdp
            s = (dp.replicate(opt.init(params)) if mode == "allreduce"
                 else dp.shard_state(opt.init(params)))
            return lambda: step(p, s, b)[2]

        try:
            best = at.tune_step_sync_mode(build, iters=1)
            assert best in ("allreduce", "sharded")
        finally:
            self._cleanup()

    def test_ineligible_modes_are_skipped_not_aborted(self, hvd):
        from horovod_tpu import autotune as at
        from horovod_tpu.exceptions import SyncModeIneligibleError

        def build(mode):
            if mode in ("sharded", "fsdp"):
                # The guard tables reject with the DEDICATED class — a
                # deterministic function of the job config, so every
                # rank skips identically.
                raise SyncModeIneligibleError(
                    f"{mode} ineligible for this job")
            return lambda: jnp.zeros(())

        try:
            best = at.tune_step_sync_mode(build, iters=1)
            assert best == "allreduce"
            assert at.tuned_sync_mode() == "allreduce"
        finally:
            self._cleanup()

    def test_bare_valueerror_aborts_not_skips(self, hvd):
        # A plain ValueError could be a rank-LOCAL user error (bad batch
        # shard, data validation); silently skipping it could pin
        # divergent modes across ranks — it must keep abort semantics.
        from horovod_tpu import autotune as at

        def build(mode):
            if mode == "sharded":
                raise ValueError("rank-local user error")
            return lambda: jnp.zeros(())

        try:
            with pytest.raises(ValueError, match="rank-local"):
                at.tune_step_sync_mode(build, iters=1)
            assert at.tuned_sync_mode() == "allreduce"  # abort pin
        finally:
            self._cleanup()

    def test_all_ineligible_raises(self, hvd):
        from horovod_tpu import autotune as at
        from horovod_tpu.exceptions import SyncModeIneligibleError

        def build(mode):
            raise SyncModeIneligibleError("nope")

        try:
            with pytest.raises(ValueError, match="every candidate"):
                at.tune_step_sync_mode(build, iters=1)
            assert at.tuned_sync_mode() is None
        finally:
            self._cleanup()

    def test_real_error_still_aborts_and_pins_first(self, hvd):
        from horovod_tpu import autotune as at

        def build(mode):
            if mode == "sharded":
                raise RuntimeError("boom")  # NOT a guard rejection
            return lambda: jnp.zeros(())

        try:
            with pytest.raises(RuntimeError, match="boom"):
                at.tune_step_sync_mode(build, iters=1)
            assert at.tuned_sync_mode() == "allreduce"
        finally:
            self._cleanup()

    def test_abort_never_pins_a_skipped_mode(self, hvd):
        # First candidate proven ineligible, then a real error: the
        # abort pin must land on the first ELIGIBLE candidate — pinning
        # the skipped one would crash every later sync_mode=None
        # construction on its own guard.
        from horovod_tpu import autotune as at
        from horovod_tpu.exceptions import SyncModeIneligibleError

        def build(mode):
            if mode == "fsdp":
                raise SyncModeIneligibleError("fsdp ineligible here")
            if mode == "allreduce":
                raise RuntimeError("boom")
            return lambda: jnp.zeros(())

        try:
            with pytest.raises(RuntimeError, match="boom"):
                at.tune_step_sync_mode(
                    build, sync_modes=("fsdp", "allreduce", "sharded"),
                    iters=1)
            assert at.tuned_sync_mode() == "allreduce"
        finally:
            self._cleanup()
