"""HBM memory observatory tests (the PR-20 acceptance proof).

Layers, mirroring ``horovod_tpu/memory.py``'s model / measure / expose /
consume shape:

- **exactness**: ``predict_footprint`` / ``footprint_of`` priced against
  the MEASURED resident bytes of live state on the 8-device CPU mesh —
  monolithic / sharded / fsdp, 1-D and 2-D meshes, int8 on and off,
  uneven (non-divisible) and scalar leaves — byte-for-byte equality,
  not tolerance;
- **live accounting**: the call-site noting (shard_params, sharded
  optimizer init, executable cache), phase watermarks through real
  tracing spans, the top-leaves forensics table;
- **exposure**: the payload/merge contract (malformed-skip, rank
  collision, insufficient_samples) and the 2-worker ``GET /memory``
  HTTP merge e2e over the real heartbeat plumbing;
- **consumers**: the ``memory.pressure`` fault-injected OOM dumping a
  flight record that names the dominant leaf; the autotune memory
  guard's candidate pricing and SyncModeIneligibleError discipline;
  the scheduler's advisory admission check — each with an A/B arm
  proving the knob-unset path is bit-for-bit inert.
"""

import json
import urllib.request

import numpy as np
import pytest

from horovod_tpu import faults
from horovod_tpu import memory
from horovod_tpu import metrics as hvd_metrics
from horovod_tpu import tracing
from horovod_tpu.exceptions import (MemoryBudgetExceededError,
                                    SyncModeIneligibleError)


@pytest.fixture(autouse=True)
def _fresh_observatory():
    memory.reset_for_testing()
    faults.reset()
    yield
    memory.reset_for_testing()
    faults.reset()
    hvd_metrics.reset_for_testing()


def _init():
    import horovod_tpu as hvd

    hvd.init()
    return hvd


def _uneven_params():
    """Deliberately awkward leaves: a 10-element vector (ceil(10/8)=2,
    6 padding elements), a scalar, and a large divisible one."""
    import jax.numpy as jnp

    return {
        "w": jnp.arange(10, dtype=jnp.float32),
        "b": jnp.float32(0.5),
        "k": jnp.ones((1000,), jnp.float32),
    }


def _tree_bytes(tree) -> int:
    """Bytes of a pytree from its leaves' shapes and dtypes alone (so an
    ``eval_shape`` tree counts too). Kept here and not taken from
    ``memory.tree_nbytes``: it measures that module from outside."""
    import jax

    return int(sum(
        int(np.prod(np.shape(l)) if np.shape(l) else 1)
        * np.dtype(l.dtype).itemsize
        for l in jax.tree.leaves(tree)))


def _measured_resident(hvd, opt, params, mode, n):
    """The byte count the live layouts actually occupy per rank —
    measured from materialized state, independent of the model."""
    import jax

    from horovod_tpu.parallel import param_sharding

    if mode == "allreduce":
        return (_tree_bytes(params)
                + _tree_bytes(jax.eval_shape(opt.init, params)))
    if mode == "sharded":
        return _tree_bytes(params) + _tree_bytes(opt.init(params)) // n
    sp = hvd.shard_params(params, n)
    return (param_sharding.resident_param_bytes(sp)
            + _tree_bytes(opt.init(params)) // n)


# ---------------------------------------------------------------------------
# Exactness: predicted == measured
# ---------------------------------------------------------------------------


class TestExactness:
    @pytest.mark.parametrize("mode", ["allreduce", "sharded", "fsdp"])
    @pytest.mark.parametrize("int8", [False, True])
    def test_predicted_equals_measured(self, mode, int8):
        """footprint_of prices the live layouts byte-for-byte, uneven
        and scalar leaves included, with and without the int8 salt."""
        import optax

        hvd = _init()
        n = hvd.size()
        params = _uneven_params()
        opt = hvd.DistributedOptimizer(
            optax.sgd(0.1, momentum=0.9),
            compression=(hvd.Compression.int8 if int8
                         else hvd.Compression.none),
            sync_mode=mode)
        fp = memory.footprint_of(opt, params, world_size=n,
                                 sync_mode=mode)
        measured = _measured_resident(hvd, opt, params, mode, n)
        assert fp["resident_total"] == measured
        assert fp["opt_exact"] is True
        assert fp["int8"] is int8

    @pytest.mark.parametrize("int8", [False, True])
    def test_2d_mesh_resident_identical_to_1d(self, int8):
        """The ceil identity: fsdp resident bytes on any BxM
        factorization equal the flat rows exactly — and both equal the
        measured layout (resident rows keep the flat layout)."""
        import optax

        hvd = _init()
        n = hvd.size()
        params = _uneven_params()
        opt = hvd.DistributedOptimizer(
            optax.sgd(0.1, momentum=0.9),
            compression=(hvd.Compression.int8 if int8
                         else hvd.Compression.none),
            sync_mode="fsdp")
        flat = memory.footprint_of(opt, params, world_size=n,
                                   sync_mode="fsdp")
        two_d = memory.footprint_of(opt, params, world_size=n,
                                    sync_mode="fsdp",
                                    mesh_shape=(n // 2, 2))
        measured = _measured_resident(hvd, opt, params, "fsdp", n)
        assert flat["resident_total"] == two_d["resident_total"] == measured
        # What the model axis DOES change: the transient gather legs.
        assert two_d["transient"]["model_axis_gather"] > 0
        assert flat["transient"]["model_axis_gather"] == 0

    def test_adam_scalar_count_leaf(self):
        """Adam's () count leaf rides the max(1, ceil) floor — the
        classic off-by-padding case a bytes-level model gets wrong."""
        import optax

        hvd = _init()
        n = hvd.size()
        params = _uneven_params()
        for mode in ("sharded", "fsdp"):
            opt = hvd.DistributedOptimizer(optax.adam(1e-3),
                                           sync_mode=mode)
            fp = memory.footprint_of(opt, params, world_size=n,
                                     sync_mode=mode)
            measured = _measured_resident(hvd, opt, params, mode, n)
            assert fp["resident_total"] == measured

    def test_element_counts_not_bytes(self):
        """Sharding prices ELEMENT counts: a 10-elem float32 leaf on 8
        ranks costs ceil(10/8)*4 = 8 bytes/rank, not ceil(40/8) = 5."""
        fp = memory.predict_footprint([(10, 4, "float32")],
                                      sync_mode="fsdp", world_size=8,
                                      opt_templates=[])
        assert fp["resident"]["params"] == 8

    def test_predict_footprint_is_jax_free(self):
        """The template-level entry prices from plain tuples (the
        stdlib path the scheduler and driver-side tools use)."""
        fp = memory.predict_footprint(
            [(1000, 4, "float32"), (1, 4, "float32")],
            sync_mode="sharded", world_size=8, opt_slots=2)
        # full params + 2 param-sized slots sharded per-leaf.
        assert fp["resident"]["params"] == 4004
        assert fp["resident"]["opt_state"] == 2 * (125 * 4 + 4)
        assert fp["opt_exact"] is False

    def test_transient_terms(self):
        leaves = [(1 << 20, 4, "float32")]
        fp = memory.predict_footprint(
            leaves, sync_mode="fsdp", world_size=8,
            threshold_bytes=1 << 20, num_segments=1,
            expert_set={"bytes": 512}, serving_staging=True)
        t = fp["transient"]
        assert t["fsdp_gather"] == 4 << 20      # the full segment
        assert t["moe_alltoall"] == 1024        # 2x explicit bytes
        assert t["serve_staging"] == 4 << 20    # a full staged replica
        assert t["grad_buckets"] > 0
        assert fp["peak_total"] == fp["resident_total"] + max(t.values())

    @pytest.mark.parametrize("mode, int8, leaves, packed", [
        # the flat wire packs only the leaves under the cutoff
        ("allreduce", False, 3, 4000 + 400),
        # a bucket's one small leaf is reduced as itself: nothing packed
        ("allreduce", False, 2, 0),
        ("allreduce", True, 3, (2 << 20) + 1100),  # int8: whole, 1 B each
        ("sharded", False, 3, 4 * ((2 << 20) + 1100)),
    ])
    def test_grad_buckets_prices_what_is_still_packed(self, mode, int8,
                                                      leaves, packed):
        from horovod_tpu.ops.fusion import PACK_CUTOFF_BYTES

        layout = [(2 << 20, 4, "float32"), (1000, 4, "float32"),
                  (100, 4, "float32")][:leaves]
        assert layout[0][0] * 4 >= PACK_CUTOFF_BYTES > layout[1][0] * 4
        fp = memory.predict_footprint(
            layout, sync_mode=mode, world_size=4, opt_templates=[],
            int8=int8)
        assert fp["transient"]["grad_buckets"] == 2 * packed

    def test_capacity_headroom(self):
        base = memory.predict_footprint([(100, 4, "float32")],
                                        world_size=1, opt_templates=[])
        cap = 2 * base["peak_total"]
        fp = memory.predict_footprint([(100, 4, "float32")],
                                      world_size=1, opt_templates=[],
                                      capacity=cap)
        assert fp["capacity_bytes"] == cap
        assert fp["predicted_headroom_ratio"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# Live accounting
# ---------------------------------------------------------------------------


class TestLiveAccounting:
    def test_shard_params_notes_resident_and_leaves(self):
        hvd = _init()
        params = _uneven_params()
        hvd.shard_params(params, hvd.size())
        obs = memory.get_observatory()
        resident = obs.measured_resident()
        assert resident.get("params") == 512  # (2 + 1 + 125) * 4
        top = obs.top_leaves()
        assert top and top[0]["kind"] == "params"
        assert "k" in top[0]["leaf"]  # the 1000-elem leaf dominates

    def test_elastic_state_notes_sharded_opt_state(self):
        """TpuState registers the stacked sharded optimizer state at
        its exact per-rank bytes (total / world rows)."""
        import optax

        hvd = _init()
        params = _uneven_params()
        opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                                       sync_mode="sharded")
        hvd.elastic.TpuState(params=params, opt_state=opt.init(params),
                             sharded_optimizer=opt)
        assert memory.get_observatory().measured_resident().get(
            "opt_state") == 512

    def test_executable_cache_bytes_flow(self):
        hvd = _init()
        n = hvd.size()
        before = hvd.cache_stats()["executable_cache"]
        hvd.allreduce(np.ones((n, 4), np.float32), op=hvd.Sum)
        stats = hvd.cache_stats()["executable_cache"]
        assert "bytes" in before
        assert stats["bytes"] > 0
        assert memory.get_observatory().measured_resident().get(
            "executables") == stats["bytes"]
        from horovod_tpu.ops.executable_cache import global_cache

        global_cache().clear()
        assert hvd.cache_stats()["executable_cache"]["bytes"] == 0

    def test_phase_watermarks_through_spans(self):
        memory.note_resident("params", 1000)
        tracing.reset_for_testing()
        with tracing.span("forward_backward", "compute"):
            pass
        memory.note_resident("params", 4000)
        with tracing.span("optimizer_update", "compute"):
            pass
        marks = memory.get_observatory().watermarks()
        assert marks["forward_backward"] >= 1000
        assert marks["optimizer_update"] >= 4000
        assert memory.get_observatory().peak_bytes() >= 4000
        # Gauge side: the phase cell carries the watermark.
        assert hvd_metrics.HBM_WATERMARK.labels(
            phase="optimizer_update").get() >= 4000

    def test_summary_and_profiler_surface(self):
        memory.note_resident("params", 2048,
                             top_leaves=[("w", 2048)])
        s = memory.summary()
        assert s["status"] == "ok"
        assert s["resident"]["params"] == 2048
        assert s["top_leaves"][0]["leaf"] == "w"
        from horovod_tpu import profiler

        assert profiler.summary()["memory"]["resident"]["params"] == 2048

    def test_flight_summary_none_when_cold(self):
        assert memory.flight_summary() is None
        memory.note_resident("params", 1)
        assert memory.flight_summary()["resident"]["params"] == 1

    def test_device_stats_fold_over_every_local_device(self, monkeypatch):
        # Everything piled on the SECOND device must read as that
        # device, not as the first one's idle allocator.
        monkeypatch.setattr(memory, "_device_stats_dead", False)
        monkeypatch.setattr(memory, "local_device_memory_stats", lambda: [
            {"id": 0, "kind": "x", "bytes_in_use": 10,
             "peak_bytes_in_use": 20, "bytes_limit": 1000},
            {"id": 1, "kind": "x", "bytes_in_use": 700,
             "peak_bytes_in_use": 900, "peak_bytes_reserved": 5000,
             "bytes_limit": 990},
        ])
        assert memory.device_memory_stats() == {
            "bytes_in_use": 700, "peak_bytes_in_use": 900,
            "peak_bytes_reserved": 5000, "bytes_limit": 990}

    def test_the_latch_counts_what_the_chip_holds(self, monkeypatch):
        # A v5e's allocator counts buffers (bytes_in_use) and the loaded
        # programs' temporaries (bytes_reserved) apart; what a chip holds
        # is the two together, on the fullest device.
        stats = [
            {"id": 0, "kind": "x", "bytes_in_use": 4000,
             "peak_bytes_in_use": 6000, "bytes_reserved": 9000,
             "peak_bytes_reserved": 9500, "bytes_limit": 16000},
            {"id": 1, "kind": "x", "bytes_in_use": 4200,
             "peak_bytes_in_use": 4300, "bytes_reserved": 9000,
             "peak_bytes_reserved": 9000, "bytes_limit": 16000},
        ]
        monkeypatch.setattr(memory, "_device_stats_dead", False)
        monkeypatch.setattr(memory, "local_device_memory_stats",
                            lambda: stats)
        assert memory.device_taken_bytes() == (13200, 15500)
        memory.note_resident("params", 3000)
        resident = memory.get_observatory().measured_resident()
        assert resident == {"params": 3000, "program_temporaries": 9000}
        assert memory.summary()["resident"]["program_temporaries"] == 9000
        assert hvd_metrics.HBM_BYTES.labels(
            kind="program_temporaries").get() == 9000
        # The close of a synced step scope is the latch; an un-synced
        # one asks no device.
        tracing.reset_for_testing()
        tracer = tracing.get_tracer()
        with tracer.step_scope("hvd.step"):
            pass
        assert memory.get_observatory().watermarks() == {}
        with tracer.step_scope("hvd.step") as rec:
            rec.synced = True
        assert memory.get_observatory().watermarks() == {"step": 15500}
        assert memory.get_observatory().peak_bytes() == 15500
        assert hvd_metrics.HBM_WATERMARK.labels(phase="step").get() == 15500

    def test_a_backend_whose_stats_call_fails_reads_as_no_stats(
            self, monkeypatch):
        # The fold feeds heartbeat payloads and OOM flight records; it
        # may not be what fails them.
        def broken():
            raise RuntimeError("INTERNAL: allocator stats unavailable")

        monkeypatch.setattr(memory, "_device_stats_dead", False)
        monkeypatch.setattr(memory, "local_device_memory_stats", broken)
        assert memory.device_memory_stats() is None
        assert memory.capacity_bytes() is None
        assert memory.get_observatory().payload()["device"] is None

    def test_cpu_devices_report_no_allocator_stats(self):
        import jax

        per_device = memory.local_device_memory_stats()
        assert [d["id"] for d in per_device] == [
            d.id for d in jax.local_devices()]
        assert all(set(d) == {"id", "kind"} for d in per_device)


# ---------------------------------------------------------------------------
# Exposure: merge + GET /memory
# ---------------------------------------------------------------------------


def _payload(rank, host, **over):
    p = {"rank": rank, "host": host, "t": 1.0, "status": "ok",
         "resident": {"params": 100 * (rank + 1), "opt_state": 10},
         "resident_total": 100 * (rank + 1) + 10,
         "watermarks": {"step": 500 * (rank + 1)},
         "peak_bytes": 500 * (rank + 1),
         "headroom_ratio": 0.9 - rank * 0.5,
         "residual_bytes": (-3) ** rank,
         "capacity_bytes": 10000}
    p.update(over)
    return p


class TestMergePayloads:
    def test_cluster_aggregates(self):
        merged = memory.merge_payloads({
            "host-a": _payload(0, "host-a"),
            "host-b": _payload(1, "host-b"),
        })
        assert merged["status"] == "ok"
        assert len(merged["ranks"]) == 2
        c = merged["cluster"]
        assert c["resident_bytes"]["params"] == 300     # sums
        assert c["resident_total"] == 320
        assert c["watermark_bytes"]["step"] == 1000     # max
        assert c["headroom_ratio_min"] == pytest.approx(0.4)
        assert c["residual_bytes_worst"] == -3          # largest |.|

    def test_malformed_skipped_and_collision_keyed(self):
        merged = memory.merge_payloads({
            "host-a": _payload(0, "host-a"),
            "host-b": {"garbage": True},        # dict: kept, degraded
            "host-c": ["not", "a", "dict"],     # non-mapping: skipped
            "host-d": _payload(0, "host-d"),    # rank collision
        })
        assert merged["status"] == "ok"
        keys = set(merged["ranks"])
        assert keys == {"0", "0@host-d", "?"}
        # The degraded entry must not poison the cluster sums (both
        # surviving payloads are rank-0 shaped: 100 bytes each).
        assert merged["ranks"]["?"]["status"] == "insufficient_samples"
        assert merged["cluster"]["resident_bytes"]["params"] == 200

    def test_empty_is_insufficient_samples(self):
        assert memory.merge_payloads({})["status"] == "insufficient_samples"

    def test_nonfinite_rejected(self):
        merged = memory.merge_payloads({
            "host-a": _payload(0, "host-a",
                               resident={"params": float("nan")},
                               peak_bytes=float("inf"))})
        r = merged["ranks"]["0"]
        assert r["resident"].get("params", 0) == 0
        assert r["peak_bytes"] == 0
        json.dumps(merged)  # must stay JSON-serializable


class TestMemoryEndpoint:
    def _server(self):
        from horovod_tpu.runner.http.kv_server import RendezvousServer

        srv = RendezvousServer(host="127.0.0.1")
        srv.start()
        return srv

    @pytest.mark.parametrize("source", ["written_out", "observatory"])
    def test_get_memory_merges_two_ranks(self, source):
        """Two ranks' payloads on their heartbeats, merged over HTTP:
        written out by hand, and the live observatory's own wire format
        (what a worker piggybacks) relabelled as two ranks."""
        from horovod_tpu.runner.http.kv_server import KVClient

        if source == "observatory":
            memory.note_resident("params", 100)
            memory.note_resident("opt_state", 10)
            live = memory.get_observatory().payload()
            assert live["status"] == "ok"

            def payload(rank, host):
                scale = rank + 1
                return dict(live, rank=rank, host=host, resident=dict(
                    live["resident"], params=100 * scale))
        else:
            payload = _payload
        srv = self._server()
        try:
            client = KVClient("127.0.0.1", srv.port)
            for rank, host in ((0, "mem-r0"), (1, "mem-r1")):
                client.put("heartbeat", host, json.dumps(
                    {"rank": rank, "steps": 1, "commits": 0,
                     "memory": payload(rank, host)}).encode())
            url = f"http://127.0.0.1:{srv.port}/memory"
            with urllib.request.urlopen(url, timeout=10) as r:
                assert r.status == 200
                body = json.loads(r.read())
            assert body["status"] == "ok"
            assert len(body["ranks"]) == 2
            assert body["cluster"]["resident_bytes"]["params"] == 300
            assert body["generation"] == srv.version
        finally:
            srv.stop()

    def test_cold_server_insufficient_samples_not_500(self):
        srv = self._server()
        try:
            url = f"http://127.0.0.1:{srv.port}/memory"
            with urllib.request.urlopen(url, timeout=10) as r:
                assert r.status == 200
                body = json.loads(r.read())
            assert body["status"] == "insufficient_samples"
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# Consumer: OOM forensics
# ---------------------------------------------------------------------------


class TestOomForensics:
    def test_is_oom_error_markers(self):
        assert memory.is_oom_error(
            RuntimeError("RESOURCE_EXHAUSTED: out of memory allocating"))
        assert memory.is_oom_error(
            RuntimeError("Failed to allocate 2.5G for buffer"))
        assert not memory.is_oom_error(ValueError("plenty of room"))
        assert not memory.is_oom_error(ValueError("blooming gardens"))

    def test_injected_pressure_dumps_flight_record_naming_leaf(
            self, tmp_path, monkeypatch):
        """The acceptance e2e: arm memory.pressure, run a real watched
        factory step on the 8-dev mesh, and the dumped flight record
        names the dominant resident leaf."""
        import optax

        ev = tmp_path / "events.jsonl"
        monkeypatch.setenv("HOROVOD_EVENT_LOG", str(ev))
        hvd = _init()
        tracing.reset_for_testing()
        params = _uneven_params()
        hvd.shard_params(params, hvd.size())  # notes the leaf table

        def loss_fn(p, batch):
            import jax.numpy as jnp

            return jnp.mean((p["k"][:4] - batch) ** 2)

        opt = hvd.DistributedOptimizer(optax.sgd(0.1))
        step = hvd.data_parallel.make_train_step(loss_fn, opt,
                                                 donate=False)
        p = hvd.data_parallel.replicate(params)
        s = hvd.data_parallel.replicate(opt.init(params))
        batch = hvd.data_parallel.shard_batch(
            np.zeros((hvd.size() * 2, 4), np.float32))
        faults.inject(faults.MEMORY_PRESSURE, "drop", at=2)
        p, s, _ = step(p, s, batch)  # step 1: clean
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            step(p, s, batch)  # step 2: injected OOM at the boundary
        frs = [json.loads(l) for l in ev.read_text().splitlines()
               if json.loads(l)["event"] == "flight_record"]
        assert len(frs) == 1
        fr = frs[0]
        assert fr["reason"] == "oom"
        assert "memory.pressure" in fr["error"]
        top = fr["memory_top_leaves"]
        assert top and "k" in top[0]["leaf"]  # the dominant leaf, named
        assert fr["memory_resident"]["params"] == 512
        # Satellite: EVERY flight record carries the memory section.
        assert fr["memory"]["resident"]["params"] == 512
        monkeypatch.delenv("HOROVOD_EVENT_LOG")
        hvd_metrics.journal()

    def test_every_flight_record_attaches_memory(self, tmp_path,
                                                 monkeypatch):
        ev = tmp_path / "events.jsonl"
        monkeypatch.setenv("HOROVOD_EVENT_LOG", str(ev))
        memory.note_resident("params", 777)
        tracing.dump_flight_record("stall_shutdown")
        fr = [json.loads(l) for l in ev.read_text().splitlines()
              if json.loads(l)["event"] == "flight_record"][0]
        assert fr["memory"]["resident"]["params"] == 777
        monkeypatch.delenv("HOROVOD_EVENT_LOG")
        hvd_metrics.journal()


# ---------------------------------------------------------------------------
# Consumer: the autotune memory guard
# ---------------------------------------------------------------------------


class TestAutotuneGuard:
    # 256 MB of float32 params in 4 MB leaves: what allreduce and sharded
    # keep whole outweighs the whole buckets the fsdp wire packs (the flat
    # allreduce wire packs no leaf this large, so it has no such term).
    LAYOUT = [(1 << 20, 4, "float32")] * 64

    def _note_layout(self):
        memory.get_observatory().note_layout(self.LAYOUT)

    def _mid_capacity(self):
        """A budget strictly between the fsdp peak and the cheapest
        monolithic-params peak: fsdp fits, the other two do not."""
        peaks = {m: memory.predict_footprint(
            self.LAYOUT, sync_mode=m, world_size=8)["peak_total"]
            for m in ("allreduce", "sharded", "fsdp")}
        assert peaks["fsdp"] < min(peaks["allreduce"], peaks["sharded"])
        return (peaks["fsdp"]
                + min(peaks["allreduce"], peaks["sharded"])) // 2

    def test_inert_when_unset(self, monkeypatch):
        """A/B: with the knob unset the guard prices nothing and
        filters nothing, capacity or not."""
        monkeypatch.delenv("HOROVOD_AUTOTUNE_MEMORY_GUARD",
                           raising=False)
        monkeypatch.setenv("HOROVOD_HBM_BYTES_PER_DEVICE", "1")
        self._note_layout()
        assert memory.check_candidate("allreduce") is None
        cands = [(1 << 20, "allreduce"), (1 << 20, "fsdp")]
        verdict = memory.filter_candidates(cands, world_size=8)
        assert verdict["kept"] == cands
        assert verdict["pruned"] == []

    def test_check_candidate_raises_ineligible(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_AUTOTUNE_MEMORY_GUARD", "1")
        monkeypatch.setenv("HOROVOD_HBM_BYTES_PER_DEVICE",
                           str(self._mid_capacity()))
        monkeypatch.setenv("HOROVOD_SIZE", "8")
        self._note_layout()
        with pytest.raises(MemoryBudgetExceededError) as ei:
            memory.check_candidate("allreduce")
        assert isinstance(ei.value, SyncModeIneligibleError)
        assert memory.check_candidate("fsdp") is None  # fits

    def test_cold_or_capacityless_guard_is_inert(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_AUTOTUNE_MEMORY_GUARD", "1")
        monkeypatch.setenv("HOROVOD_SIZE", "8")
        # Armed but no layout noted: prunes nothing.
        monkeypatch.setenv("HOROVOD_HBM_BYTES_PER_DEVICE", "1")
        assert memory.check_candidate("allreduce") is None
        # Armed, layout noted, but no capacity source: prunes nothing.
        monkeypatch.delenv("HOROVOD_HBM_BYTES_PER_DEVICE")
        self._note_layout()
        assert memory.check_candidate("allreduce") is None

    def test_filter_candidates_never_prunes_whole_grid(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_AUTOTUNE_MEMORY_GUARD", "1")
        monkeypatch.setenv("HOROVOD_HBM_BYTES_PER_DEVICE", "1")
        monkeypatch.setenv("HOROVOD_SIZE", "8")
        self._note_layout()
        cands = [(1 << 20, "allreduce"), (1 << 20, "fsdp")]
        verdict = memory.filter_candidates(cands, world_size=8)
        assert verdict["kept"] == cands  # everything over: keep all
        monkeypatch.setenv("HOROVOD_HBM_BYTES_PER_DEVICE",
                           str(self._mid_capacity()))
        verdict = memory.filter_candidates(cands, world_size=8)
        assert verdict["kept"] == [(1 << 20, "fsdp")]
        assert verdict["pruned"] == [(1 << 20, "allreduce")]
        assert all(b is not None for b in verdict["bytes"])

    def test_tune_step_sync_mode_skips_over_budget(self, monkeypatch):
        """The sweep harness prices candidates before building them:
        over-budget modes skip rank-identically and the winner comes
        from the eligible ones."""
        from horovod_tpu import autotune

        monkeypatch.setenv("HOROVOD_AUTOTUNE_MEMORY_GUARD", "1")
        monkeypatch.setenv("HOROVOD_HBM_BYTES_PER_DEVICE",
                           str(self._mid_capacity()))
        monkeypatch.setenv("HOROVOD_SIZE", "8")
        _init()
        self._note_layout()
        built = []

        def build_step(mode):
            built.append(mode)
            import jax.numpy as jnp

            return lambda: jnp.zeros(())

        try:
            best = autotune.tune_step_sync_mode(
                build_step, sync_modes=("allreduce", "sharded", "fsdp"),
                iters=1)
            assert best == "fsdp"
            assert built == ["fsdp"]  # over-budget modes never built
        finally:
            autotune.set_tuned_sync_mode(None)


# ---------------------------------------------------------------------------
# Consumer: scheduler admission (advisory)
# ---------------------------------------------------------------------------


class TestAdmission:
    def test_admission_check_math(self):
        assert memory.admission_check(None, 100) is None
        assert memory.admission_check(100, None) is None
        assert memory.admission_check(80, 100) is None
        risk = memory.admission_check(150, 100)
        assert risk == {"predicted_bytes": 150, "capacity_bytes": 100,
                        "deficit_bytes": 50, "ratio": 1.5}

    def test_admission_check_garbage_is_none(self):
        assert memory.admission_check("junk", 100) is None
        assert memory.admission_check(-5, 100) is None

    def test_scheduler_grant_journals_risk_and_stays_advisory(
            self, tmp_path, monkeypatch):
        """A granted job with a declared over-capacity footprint
        journals admission_memory_risk — and is still granted. With
        the knobs unset, no event and the identical grant."""
        from horovod_tpu.runner.elastic.scheduler import (
            JobSpec, MultiJobScheduler)

        for arm, env in (("off", {}),
                         ("on", {"HOROVOD_HBM_PREDICTED_BYTES": "200"})):
            ev = tmp_path / f"events-{arm}.jsonl"
            monkeypatch.setenv("HOROVOD_EVENT_LOG", str(ev))
            if arm == "on":
                monkeypatch.setenv("HOROVOD_SCHED_HOST_HBM_BYTES", "100")
            else:
                monkeypatch.delenv("HOROVOD_SCHED_HOST_HBM_BYTES",
                                   raising=False)
            sched = MultiJobScheduler(
                [JobSpec(job_id=f"job-{arm}", command=["true"],
                         min_np=1, max_np=1, env=dict(env))],
                ["h1"], str(tmp_path / f"wd-{arm}"))
            monkeypatch.setattr(sched, "_launch_driver",
                                lambda job: None)
            sched._grant_pending()
            job = sched._jobs[f"job-{arm}"]
            assert job.lease == ["h1"]  # granted either way
            events = [json.loads(l) for l in ev.read_text().splitlines()
                      if l.strip()] if ev.exists() else []
            risks = [e for e in events
                     if e["event"] == "admission_memory_risk"]
            if arm == "on":
                assert len(risks) == 1
                assert risks[0]["deficit_bytes"] == 100
                assert risks[0]["job"] == "job-on"
            else:
                assert risks == []
            monkeypatch.delenv("HOROVOD_EVENT_LOG")
            hvd_metrics.journal()


# ---------------------------------------------------------------------------
# Gauges
# ---------------------------------------------------------------------------


class TestGauges:
    def test_zero_materialized_cells(self):
        text = hvd_metrics.render()
        for fam in ("hvd_hbm_bytes", "hvd_hbm_watermark_bytes",
                    "hvd_hbm_headroom_ratio",
                    "hvd_hbm_model_residual_bytes"):
            assert fam in text
        for kind in memory.KINDS:
            assert f'hvd_hbm_bytes{{kind="{kind}"}}' in text

    def test_note_resident_sets_kind_gauge(self):
        memory.note_resident("serving", 4096)
        assert hvd_metrics.HBM_BYTES.labels(kind="serving").get() == 4096

    def test_headroom_gauge_with_capacity(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_HBM_BYTES_PER_DEVICE", "1000")
        memory.note_resident("params", 250)
        assert memory.get_observatory().headroom_ratio() == \
            pytest.approx(0.75)
