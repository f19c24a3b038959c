"""Topology-aware per-bucket collective algorithm selection
(``ops/comms_planner.py``) — the ISSUE-14 acceptance proofs:

- plans are RANK-IDENTICAL under skewed per-rank fits (the decision is
  a pure function of the SYNCED snapshot, and the synced snapshot is
  rank 0's);
- flat / rhd / two_level produce ulp-identical reductions across ops,
  dtypes, uneven buckets, and non-power-of-two worlds — including the
  RS/AG halves the sharded/fsdp wires ride;
- int8 parity per leg (the two-level quantized exchange's error bound
  matches the flat EQuARX exchange's);
- plan stability across elastic resize: cached within a generation,
  replanned exactly at the generation fence;
- ``HOROVOD_COMMS_PLANNER`` unset is bit-for-bit inert (the planner is
  never consulted and the flat emission is byte-identical).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu import comms_model as cm
from horovod_tpu.ops import comms_planner as cp

N = 8
ISLANDS = ((0, 1, 2, 3), (4, 5, 6, 7))


@pytest.fixture(autouse=True)
def _fresh_planner(monkeypatch):
    """Every test starts with a cold planner and no env knobs armed."""
    monkeypatch.delenv("HOROVOD_COMMS_PLANNER", raising=False)
    monkeypatch.delenv("HOROVOD_LINK_CLASS_MAP", raising=False)
    cp.reset_for_testing()
    yield
    cp.reset_for_testing()


def _mesh(n=N):
    return Mesh(np.array(jax.devices()[:n]), ("w",))


def _run_sharded(fn, x, n=N):
    mesh = _mesh(n)
    wrapped = jax.shard_map(fn, mesh=mesh, in_specs=P("w"),
                            out_specs=P("w"), check_vma=False)
    return np.asarray(jax.jit(wrapped)(x))


# ---------------------------------------------------------------------------
# Decision layer: crossover, eligibility, pins, provenance
# ---------------------------------------------------------------------------


class TestDecision:
    def test_disabled_planner_returns_none(self):
        assert cp.plan_bucket("allreduce", 1 << 20, N) is None
        assert cp.planned_algorithm("allreduce", 1 << 20, N) == "flat"

    def test_static_crossover_on_emulated_split(self, monkeypatch):
        """Above-crossover buckets on a declared 2-slice fabric go
        two_level; tiny (latency-bound) buckets stay flat — both with
        explicit static_crossover provenance (cold model)."""
        monkeypatch.setenv("HOROVOD_COMMS_PLANNER", "auto")
        monkeypatch.setenv("HOROVOD_LINK_CLASS_MAP", "0-3;4-7")
        big = cp.plan_bucket("allreduce", 16 << 20, N)
        assert big.algorithm == "two_level"
        assert big.provenance == "static_crossover"
        # ...and it is chosen on price: the schedule's predicted cost is
        # under the flat one's on the split fabric.
        assert big.costs["two_level"] < big.costs["flat"]
        small = cp.plan_bucket("allreduce", 256, N)
        assert small.algorithm == "flat"
        assert small.provenance == "static_crossover"

    def test_uniform_fabric_stays_flat(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_COMMS_PLANNER", "auto")
        plan = cp.plan_bucket("allreduce", 16 << 20, N)
        assert plan.algorithm == "flat"

    def test_env_pin_and_ineligible_degrade(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_COMMS_PLANNER", "two_level")
        # No islands declared and the CPU mesh is one process — a
        # single island — so the pin is ineligible and degrades to
        # flat, loudly labeled.
        plan = cp.plan_bucket("allreduce", 1 << 20, N)
        assert plan.algorithm == "flat"
        assert plan.provenance == "env_pin:ineligible"
        monkeypatch.setenv("HOROVOD_LINK_CLASS_MAP", "0-3;4-7")
        cp.reset_for_testing()
        plan = cp.plan_bucket("allreduce", 1 << 20, N)
        assert plan.algorithm == "two_level"
        assert plan.provenance == "env_pin"

    def test_autotune_pin_wins_over_pricing(self, monkeypatch):
        from horovod_tpu import autotune

        monkeypatch.setenv("HOROVOD_COMMS_PLANNER", "auto")
        monkeypatch.setenv("HOROVOD_LINK_CLASS_MAP", "0-3;4-7")
        autotune.set_tuned_algorithm("rhd")
        try:
            plan = cp.plan_bucket("allreduce", 16 << 20, N)
            assert plan.algorithm == "rhd"
            assert plan.provenance == "autotune_pin"
        finally:
            autotune.set_tuned_algorithm(None)

    def test_eligibility_gates(self):
        # rhd on the RS/AG halves needs a power-of-two world; the
        # allreduce gets the fold-in step.
        assert "rhd" in cp.eligible_algorithms("allreduce", 6, None)
        assert "rhd" not in cp.eligible_algorithms("reducescatter", 6,
                                                   None)
        assert "rhd" in cp.eligible_algorithms("reducescatter", 8, None)
        # two_level needs a regular >=2 island layout.
        assert "two_level" not in cp.eligible_algorithms(
            "allreduce", 8, ((0, 1, 2, 3, 4, 5, 6, 7),))
        assert "two_level" not in cp.eligible_algorithms(
            "allreduce", 8, ((0, 1, 2), (3, 4, 5, 6, 7)))
        assert "two_level" in cp.eligible_algorithms("allreduce", 8,
                                                     ISLANDS)

    def test_model_priced_plan_uses_fitted_keys(self, monkeypatch):
        """A ready per-algorithm fit flips the decision to model
        provenance — the planner prices the measured schedule, not the
        seeds."""
        monkeypatch.setenv("HOROVOD_COMMS_PLANNER", "auto")
        monkeypatch.setenv("HOROVOD_LINK_CLASS_MAP", "0-3;4-7")
        cm.reset_for_testing()
        model = cm.get_model()
        # Fit flat as CHEAP and two_level as expensive on dcn — the
        # opposite of the seed table's large-bucket verdict.
        for nbytes in (4096, 1 << 20):
            for _ in range(4):
                model.observe("allreduce", "flat", "dcn", nbytes,
                              1e-6 + 1e-12 * nbytes)
                model.observe("allreduce", "two_level", "dcn", nbytes,
                              1e-3 + 1e-9 * nbytes)
        try:
            plan = cp.plan_bucket("allreduce", 16 << 20, N)
            assert plan.provenance == "model"
            assert plan.algorithm == "flat"
        finally:
            cm.reset_for_testing()


class TestRankIdentity:
    def test_decide_is_pure_in_the_snapshot(self):
        """Same (bucket, world, islands, snapshot) → same plan — the
        rank-identity contract reduces to feeding every rank the same
        snapshot, which the broadcast guarantees."""
        snap = {"allreduce|two_level|dcn": (1e-5, 1e-10),
                "allreduce|flat|dcn": (1e-5, 1e-9)}
        a = cp._decide("allreduce", 1 << 20, N, ISLANDS, snap, None)
        b = cp._decide("allreduce", 1 << 20, N, ISLANDS, snap, None)
        assert a == b
        assert a[0] == "two_level" and a[1] == "model"

    def test_skewed_local_fit_cannot_diverge_the_plan(self, monkeypatch):
        """Rank-1-style skewed LOCAL fits are irrelevant: the synced
        snapshot is rank 0's (exchanged through the autotune broadcast
        machinery), so the plan matches rank 0's everywhere."""
        monkeypatch.setenv("HOROVOD_COMMS_PLANNER", "auto")
        monkeypatch.setenv("HOROVOD_LINK_CLASS_MAP", "0-3;4-7")
        # Rank 0 measured BOTH schedules (two fitted keys → the model
        # regime ranks them) and found flat cheap, two_level slow.
        rank0_snapshot = {"allreduce|flat|dcn": (1e-6, 1e-12),
                          "allreduce|two_level|dcn": (1e-3, 1e-9)}

        def fake_broadcast(decision):
            # The wire: whatever THIS rank computed locally is replaced
            # by rank 0's broadcast value.
            return rank0_snapshot

        monkeypatch.setattr(cp, "_broadcast_decision", fake_broadcast)
        # Skew this rank's local model hard toward two_level.
        cm.reset_for_testing()
        model = cm.get_model()
        for nbytes in (4096, 1 << 20):
            for _ in range(4):
                model.observe("allreduce", "two_level", "dcn", nbytes,
                              1e-9)
                model.observe("allreduce", "flat", "dcn", nbytes, 1.0)
        try:
            plan = cp.plan_bucket("allreduce", 16 << 20, N)
            # Rank 0's snapshot only knows a cheap flat — the skewed
            # local two_level fit never entered the decision.
            assert plan.algorithm == "flat"
            assert plan.provenance == "model"
        finally:
            cm.reset_for_testing()

    def test_replan_only_at_generation_fence(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_COMMS_PLANNER", "auto")
        monkeypatch.setenv("HOROVOD_LINK_CLASS_MAP", "0-3;4-7")
        monkeypatch.setenv("HOROVOD_WORLD_VERSION", "7")
        p1 = cp.plan_bucket("allreduce", 16 << 20, N)
        assert cp.summary()["replans"] == 0
        # Same generation: the cached plan object is served verbatim.
        assert cp.plan_bucket("allreduce", 16 << 20, N) is p1
        # Generation fence: the table invalidates and replans.
        monkeypatch.setenv("HOROVOD_WORLD_VERSION", "8")
        p2 = cp.plan_bucket("allreduce", 16 << 20, N)
        assert p2 is not p1
        assert p2.algorithm == p1.algorithm  # same world facts
        assert cp.summary()["replans"] == 1


# ---------------------------------------------------------------------------
# Numerical equivalence: flat / rhd / two_level across ops, dtypes,
# uneven buckets, non-power-of-two worlds — allreduce AND the RS/AG
# halves
# ---------------------------------------------------------------------------


def _plan(op, algorithm, world, islands=None):
    return cp.BucketPlan(op, algorithm, 0, world, islands, "forced", {})


class TestNumericalEquivalence:
    @pytest.mark.parametrize("dtype", [np.float32, np.float64])
    @pytest.mark.parametrize("algorithm", ["rhd", "two_level"])
    def test_allreduce_sum_ulp_identical(self, algorithm, dtype):
        # Integer-valued payloads: every summation order is exact, so
        # the equivalence assertion is BITWISE, not a tolerance.
        rng = np.random.RandomState(0)
        x = rng.randint(-8, 9, size=(N, 999)).astype(dtype)
        plan = _plan("allreduce", algorithm, N, ISLANDS)

        def planned(v):
            return cp.apply_allreduce_sum(plan, v[0], "w")[None]

        def flat(v):
            return cp.apply_allreduce_sum(
                _plan("allreduce", "flat", N), v[0], "w")[None]

        got = _run_sharded(planned, x)
        ref = _run_sharded(flat, x)
        assert got.dtype == ref.dtype
        np.testing.assert_array_equal(got, ref)
        np.testing.assert_array_equal(ref[0], x.sum(0))

    @pytest.mark.parametrize("algorithm", ["rhd", "two_level"])
    def test_allreduce_random_floats_close(self, algorithm):
        rng = np.random.RandomState(1)
        x = rng.randn(N, 1237).astype(np.float32)
        plan = _plan("allreduce", algorithm, N, ISLANDS)

        def planned(v):
            return cp.apply_allreduce_sum(plan, v[0], "w")[None]

        got = _run_sharded(planned, x)
        np.testing.assert_allclose(got[0], x.sum(0), rtol=1e-5,
                                   atol=1e-5)

    def test_allreduce_nonpow2_fold_in(self):
        """The fold-in step: a 6-rank world's rhd allreduce is exact."""
        n = 6
        rng = np.random.RandomState(2)
        x = rng.randint(-8, 9, size=(n, 101)).astype(np.float32)
        plan = _plan("allreduce", "rhd", n)

        def planned(v):
            return cp.apply_allreduce_sum(plan, v[0], "w")[None]

        got = _run_sharded(planned, x, n=n)
        np.testing.assert_array_equal(got, np.tile(x.sum(0), (n, 1)))

    def test_two_level_uneven_island_payload(self):
        """Payload not divisible by the island size exercises the
        padding leg."""
        x = np.arange(N * 1001, dtype=np.float32).reshape(N, 1001)
        plan = _plan("allreduce", "two_level", N, ISLANDS)

        def planned(v):
            return cp.apply_allreduce_sum(plan, v[0], "w")[None]

        got = _run_sharded(planned, x)
        np.testing.assert_array_equal(got, np.tile(x.sum(0), (N, 1)))

    @pytest.mark.parametrize("algorithm", ["rhd", "two_level"])
    def test_reducescatter_half_matches_flat(self, algorithm):
        """The RS half: rank r's planned row is bitwise the flat tiled
        psum_scatter's — the sharded/fsdp ownership contract."""
        s = 37
        rng = np.random.RandomState(3)
        x = rng.randint(-8, 9, size=(N, N * s)).astype(np.float32)
        plan = _plan("reducescatter", algorithm, N, ISLANDS)

        def planned(v):
            return cp.apply_reducescatter_sum(plan, v[0], "w")[None]

        def flat(v):
            return cp.apply_reducescatter_sum(
                _plan("reducescatter", "flat", N), v[0], "w")[None]

        got = _run_sharded(planned, x)
        ref = _run_sharded(flat, x)
        np.testing.assert_array_equal(got, ref)
        # Stacked row r == row r of the full reduction (ownership map).
        np.testing.assert_array_equal(got, x.sum(0).reshape(N, s))

    @pytest.mark.parametrize("algorithm", ["rhd", "two_level"])
    def test_allgather_half_matches_flat(self, algorithm):
        s = 23
        rng = np.random.RandomState(4)
        rows = rng.randn(N, s).astype(np.float32)
        plan = _plan("allgather", algorithm, N, ISLANDS)

        def planned(v):
            return cp.apply_allgather_row(plan, v[0], "w")[None]

        got = _run_sharded(planned, rows)
        np.testing.assert_array_equal(
            got, np.tile(rows.reshape(-1), (N, 1)))


class TestInt8PerLeg:
    def test_int8_two_level_parity_per_leg(self):
        """The per-leg quantized two-level exchange stays within the
        flat EQuARX exchange's error envelope — compression never gets
        worse because the schedule changed."""
        from horovod_tpu.ops.quantization import (
            BLOCK,
            int8_allreduce_flat,
            int8_two_level_allreduce_flat,
        )

        rng = np.random.RandomState(5)
        x = rng.randn(N, 4 * BLOCK + 100).astype(np.float32)
        truth = x.mean(0)

        def flat(v):
            return int8_allreduce_flat(v[0], "w", N, op="average")[None]

        def two_level(v):
            return int8_two_level_allreduce_flat(
                v[0], "w", ISLANDS, op="average")[None]

        of = _run_sharded(flat, x)
        ot = _run_sharded(two_level, x)
        tol = 4.0 * np.abs(x).max() / 127.0
        assert np.abs(of[0] - truth).max() < tol
        assert np.abs(ot[0] - truth).max() < tol
        # Rank-identical outputs in both schedules.
        for i in range(N):
            np.testing.assert_array_equal(of[i], of[0])
            np.testing.assert_array_equal(ot[i], ot[0])


# ---------------------------------------------------------------------------
# Wiring: fused flushes, eager labels, inert A/B
# ---------------------------------------------------------------------------


class TestWiring:
    def _program(self, x_leaves, world=N):
        from horovod_tpu.ops.fusion import fused_allreduce

        def body(*vs):
            leaves = [v[0] for v in vs]
            out = fused_allreduce(leaves, op="sum", axis_name="w",
                                  threshold_bytes=1,
                                  world_size=world)
            return tuple(o[None] for o in out)

        mesh = _mesh(world)
        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=tuple(P("w") for _ in x_leaves),
                           out_specs=tuple(P("w") for _ in x_leaves),
                           check_vma=False)
        return jax.jit(fn)

    def _flush(self, x_leaves, world=N):
        return [np.asarray(o)
                for o in self._program(x_leaves, world)(*x_leaves)]

    def test_auto_on_a_uniform_fabric_lowers_to_the_flat_program(
            self, hvd, monkeypatch):
        """Where the planner prices and still picks flat, parity is by
        construction: the flush lowers to the text of the flush with the
        planner off. On a declared split the same buckets lower to
        another program."""
        leaves = [np.ones((N, 256 * 1024), np.float32) for _ in range(2)]
        flat = self._program(leaves).lower(*leaves).as_text()
        monkeypatch.setenv("HOROVOD_COMMS_PLANNER", "auto")
        cp.reset_for_testing()
        assert self._program(leaves).lower(*leaves).as_text() == flat
        monkeypatch.setenv("HOROVOD_LINK_CLASS_MAP", "0-3;4-7")
        cp.reset_for_testing()
        assert self._program(leaves).lower(*leaves).as_text() != flat

    def test_planned_flush_matches_flat_flush(self, hvd, monkeypatch):
        rng = np.random.RandomState(6)
        leaves = [rng.randint(-4, 5, size=(N, 300)).astype(np.float32),
                  rng.randint(-4, 5, size=(N, 41)).astype(np.float32)]
        ref = self._flush(leaves)
        monkeypatch.setenv("HOROVOD_COMMS_PLANNER", "two_level")
        monkeypatch.setenv("HOROVOD_LINK_CLASS_MAP", "0-3;4-7")
        cp.reset_for_testing()
        got = self._flush(leaves)
        for g, r in zip(got, ref):
            np.testing.assert_array_equal(g, r)

    def test_unset_knob_is_inert_and_never_consults_the_planner(
            self, hvd, monkeypatch):
        """The A/B: with HOROVOD_COMMS_PLANNER unset, plan_bucket is
        never reached past the enabled() gate (a poisoned _decide
        proves it) and the flush is bit-for-bit the flat one."""
        def poisoned(*a, **k):  # pragma: no cover — must not run
            raise AssertionError("planner consulted while disabled")

        monkeypatch.setattr(cp, "_decide", poisoned)
        monkeypatch.setattr(cp, "_synced_snapshot", poisoned)
        rng = np.random.RandomState(7)
        leaves = [rng.randint(-4, 5, size=(N, 97)).astype(np.float32)]
        got = self._flush(leaves)
        np.testing.assert_array_equal(
            got[0], np.tile(leaves[0].sum(0), (N, 1)))

    def test_eager_span_and_model_carry_the_algorithm(self, hvd,
                                                      monkeypatch):
        """The honest-labeling satellite: a planned eager dispatch's
        span args, per-algorithm dispatch counter, and comms-model
        sample all name the EXECUTED algorithm."""
        from horovod_tpu import metrics as hvd_metrics
        from horovod_tpu import tracing

        monkeypatch.setenv("HOROVOD_COMMS_PLANNER", "two_level")
        monkeypatch.setenv("HOROVOD_LINK_CLASS_MAP", "0-3;4-7")
        cp.reset_for_testing()
        cm.reset_for_testing()
        tracing.reset_for_testing()

        def count(algorithm):
            return sum(
                s["value"]
                for s in hvd_metrics.PLANNER_DISPATCH.dump()["samples"]
                if s["labels"] == {"op": "allreduce",
                                   "algorithm": algorithm})

        before = count("two_level")
        x = np.ones((N, 2048), np.float32)
        tracer = tracing.get_tracer()
        with tracer.step_scope("planned") as rec:
            rec.synced = True
            hvd.allreduce(x, op=hvd.Sum)
        assert count("two_level") == before + 1
        steps = tracer.payload()["steps"]
        spans = [sp for srec in steps for sp in srec["spans"]
                 if sp.get("name") == "allreduce"]
        assert spans and spans[-1]["args"]["algorithm"] == "two_level"
        fits = cm.get_model().payload()["fits"]
        assert any(k.startswith("allreduce|two_level|") for k in fits)
        cm.reset_for_testing()

    def test_payload_carries_plan_with_provenance(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_COMMS_PLANNER", "auto")
        monkeypatch.setenv("HOROVOD_LINK_CLASS_MAP", "0-3;4-7")
        cp.plan_bucket("allreduce", 16 << 20, N)
        payload = cm.get_model().payload()
        planner = payload["planner"]
        assert planner["enabled"] and planner["mode"] == "auto"
        plans = planner["plans"]
        assert plans and plans[0]["algorithm"] == "two_level"
        assert plans[0]["provenance"] == "static_crossover"
        assert plans[0]["costs_s"]  # the why: per-candidate prices
        # And the cluster merge passes it through, never a 500.
        merged = cm.merge_payloads({"h0": payload})
        (rank_entry,) = merged["ranks"].values()
        assert rank_entry["planner"]["enabled"]

    def test_topology_describe_renders_plans_cold(self, hvd,
                                                  monkeypatch):
        from horovod_tpu.basics import _state

        text = _state.topology.describe()
        assert "planner: off" in text
        monkeypatch.setenv("HOROVOD_COMMS_PLANNER", "auto")
        monkeypatch.setenv("HOROVOD_LINK_CLASS_MAP", "0-3;4-7")
        cp.reset_for_testing()
        text = _state.topology.describe()
        assert "planner: auto" in text
        assert "two_level(static_crossover)" in text
        assert "islands (HOROVOD_LINK_CLASS_MAP)" in text


# ---------------------------------------------------------------------------
# Topology map + autotune axis + predictor terms
# ---------------------------------------------------------------------------


class TestTopologyMap:
    def test_parse_grammar(self):
        from horovod_tpu.topology import parse_link_class_map

        assert parse_link_class_map("0-3;4-7") == [[0, 1, 2, 3],
                                                   [4, 5, 6, 7]]
        assert parse_link_class_map("0,2;1,3") == [[0, 2], [1, 3]]
        assert parse_link_class_map("0-1,4;2-3") == [[0, 1, 4], [2, 3]]
        assert parse_link_class_map("") is None
        assert parse_link_class_map("0-3;2-5") is None  # overlap
        assert parse_link_class_map("junk") is None

    def test_link_class_override(self, hvd, monkeypatch):
        from horovod_tpu.basics import _state

        topo = _state.topology
        assert topo.link_class(0, 7) == "ici"  # one CPU process
        monkeypatch.setenv("HOROVOD_LINK_CLASS_MAP", "0-3;4-7")
        assert topo.link_class(0, 3) == "ici"
        assert topo.link_class(0, 4) == "dcn"
        assert topo.set_link_class(list(range(8))) == "dcn"
        assert topo.set_link_class([0, 1, 2, 3]) == "ici"
        matrix = topo.link_class_matrix()
        assert matrix == {"ici": 12, "dcn": 16}
        assert topo.ici_islands() == [[0, 1, 2, 3], [4, 5, 6, 7]]


class TestAutotuneAxis:
    def test_candidate_axes_parses_algorithm(self):
        assert cm.candidate_axes((1024,)) == (1024, 1, "allreduce", None)
        assert cm.candidate_axes((1024, 2, "sharded", "rhd")) == (
            1024, 2, "sharded", "rhd")
        assert cm.candidate_axes((1024, "two_level")) == (
            1024, 1, "allreduce", "two_level")
        assert cm.candidate_axes((1024, "fsdp")) == (
            1024, 1, "fsdp", None)

    def test_autotune_step_pins_algorithm_axis(self):
        from horovod_tpu import autotune

        calls = []

        class FakeJit:
            def __call__(self, x):
                calls.append(autotune.tuned_algorithm())
                return x

            def clear_cache(self):
                pass

        clock = iter(float(i) for i in range(1000))
        tuner = autotune.AutotuneStep(
            FakeJit(), thresholds=(1024,), iters=1,
            clock=lambda: next(clock),
            algorithm_candidates=("flat", "two_level"))
        try:
            for _ in range(2 * (1 + 1)):  # two windows of (settle+timed)
                tuner(np.zeros(4))
            assert set(calls) == {"flat", "two_level"}
            assert autotune.tuned_algorithm() in ("flat", "two_level")
            assert autotune.autotune_state()["algorithm"] == \
                autotune.tuned_algorithm()
        finally:
            autotune.set_tuned_threshold(None)
            autotune.set_tuned_algorithm(None)

    def test_autotune_candidates_need_auto_mode(self, hvd, monkeypatch):
        assert cp.autotune_candidates(N) is None  # planner off
        monkeypatch.setenv("HOROVOD_COMMS_PLANNER", "two_level")
        assert cp.autotune_candidates(N) is None  # pinned, no axis
        monkeypatch.setenv("HOROVOD_COMMS_PLANNER", "auto")
        monkeypatch.setenv("HOROVOD_LINK_CLASS_MAP", "0-3;4-7")
        cp.reset_for_testing()
        cands = cp.autotune_candidates(N)
        assert cands is not None and "two_level" in cands
        # The un-pinned per-bucket mode leads the axis: a mixed plan
        # competes against every uniform pin.
        assert cands[0] == "auto"

    def test_autotune_candidates_respect_the_whole_wire(self,
                                                        monkeypatch):
        """Candidates intersect eligibility across ALL planner ops: on
        a non-power-of-two world rhd is allreduce-only (the RS/AG
        halves would degrade it to flat), so it must not cost warmup
        windows."""
        monkeypatch.setenv("HOROVOD_COMMS_PLANNER", "auto")
        assert "rhd" not in (cp.autotune_candidates(6) or ())
        monkeypatch.setenv("HOROVOD_LINK_CLASS_MAP", "0-3;4-7")
        cp.reset_for_testing()
        cands = cp.autotune_candidates(8) or ()
        assert "rhd" in cands and "two_level" in cands

    def test_auto_pin_means_per_bucket_pricing(self, monkeypatch):
        from horovod_tpu import autotune

        monkeypatch.setenv("HOROVOD_COMMS_PLANNER", "auto")
        monkeypatch.setenv("HOROVOD_LINK_CLASS_MAP", "0-3;4-7")
        autotune.set_tuned_algorithm("auto")
        try:
            plan = cp.plan_bucket("allreduce", 16 << 20, N)
            # Not an autotune_pin: the planner priced per bucket.
            assert plan.provenance == "static_crossover"
            assert plan.algorithm == "two_level"
        finally:
            autotune.set_tuned_algorithm(None)


class TestPredictorTerms:
    def test_predict_flush_cost_prices_the_algorithm_axis(self):
        """The satellite: per-algorithm fit keys price the candidate's
        schedule, not an assumed flat ring."""
        cm.reset_for_testing()
        model = cm.get_model()
        for nbytes in (4096, 1 << 20):
            for _ in range(4):
                model.observe("allreduce", "flat", "ici", nbytes,
                              1e-3 + 1e-9 * nbytes)
                model.observe("allreduce", "rhd", "ici", nbytes,
                              1e-5 + 1e-11 * nbytes)
        leaves = [(1 << 20, "float32")]
        try:
            flat_cost = cm.predict_flush_cost(
                leaves, 64 << 20, algorithm="flat", model=model)
            rhd_cost = cm.predict_flush_cost(
                leaves, 64 << 20, algorithm="rhd", model=model)
            assert flat_cost is not None and rhd_cost is not None
            assert rhd_cost < flat_cost / 10
        finally:
            cm.reset_for_testing()

    def test_bucket_name_regex_parses_algorithm_suffix(self):
        m = cm._BUCKET_NAME_RE.match("allreduce.bucket0.1048576B.rhd")
        assert m and m.group("algo") == "rhd"
        m = cm._BUCKET_NAME_RE.match("reducescatter.bucket2.4096B")
        assert m and m.group("algo") is None

    def test_ingest_attributes_suffixed_spans(self):
        cm.reset_for_testing()
        model = cm.get_model()
        folded = model.ingest_steps([{
            "spans": [{"cat": "collective", "dur": 0.5,
                       "name": "allreduce.bucket0.1048576B.two_level"}],
        }])
        assert folded == 1
        assert "allreduce|two_level|ici" in model.payload()["fits"]
        cm.reset_for_testing()
