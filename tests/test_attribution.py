"""Step-time attribution: phase decomposition, cluster critical path,
MFU, the regression sentinel, GET /criticalpath (+ the shared
?steps/?rank trace-route filters and 413 cap), journal rotation, the
metric-docs consistency lane, flight-recorder integration, and the
policy plane's step-regression evidence channel.
"""

from __future__ import annotations

import copy
import json
import os
import subprocess
import sys
import urllib.error
import urllib.request

import pytest

from horovod_tpu import abort, attribution, faults, metrics, tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_planes():
    metrics.reset_for_testing()
    tracing.reset_for_testing()
    attribution.reset_for_testing()
    faults.reset()
    abort.reset()
    yield
    faults.reset()
    abort.reset()
    attribution.reset_for_testing()
    tracing.reset_for_testing()


def _server():
    from horovod_tpu.runner.http.kv_server import RendezvousServer

    srv = RendezvousServer(host="127.0.0.1")
    srv.start()
    return srv


def _steprec(step=5, collective_t=0.8, collective_dur=0.7, synced=True):
    """compute [0,1]∪[1.6,1.8], collective [t, t+dur], step [0,2]."""
    return {
        "step": step, "kind": "train", "synced": synced, "t": 0.0,
        "dur": 2.0,
        "spans": [
            {"name": "train", "cat": "step", "t": 0.0, "dur": 2.0,
             "args": {"synced": synced}},
            {"name": attribution.SPAN_FORWARD_BACKWARD, "cat": "phase",
             "t": 0.0, "dur": 1.0},
            {"name": attribution.SPAN_COLLECTIVE, "cat": "collective",
             "t": collective_t, "dur": collective_dur},
            {"name": attribution.SPAN_OPTIMIZER_UPDATE, "cat": "phase",
             "t": 1.6, "dur": 0.2},
        ],
    }


def _payload(rank="0", host="h0", offset=0.0, steps=None, generation=1,
             **extra):
    return {"rank": rank, "host": host, "clock_offset_s": offset,
            "generation": generation,
            "steps": steps if steps is not None else [_steprec()],
            **extra}


# ---------------------------------------------------------------------------
# Per-rank decomposition
# ---------------------------------------------------------------------------


class TestDecomposition:
    def test_phases_sum_to_wall_exactly(self):
        d = attribution.decompose_step(_steprec())
        assert d["wall_s"] == pytest.approx(2.0)
        assert sum(d["phases"].values()) == pytest.approx(d["wall_s"])

    def test_exposed_vs_hidden_interval_math(self):
        # collective [0.8, 1.5]; compute covers [0,1]: 0.2s hidden,
        # 0.5s exposed; overhead = 2.0 - covered([0,1.5]∪[1.6,1.8]).
        d = attribution.decompose_step(_steprec())
        p = d["phases"]
        assert p[attribution.PHASE_COMPUTE] == pytest.approx(1.2)
        assert p[attribution.PHASE_EXPOSED_COMM] == pytest.approx(0.5)
        assert p[attribution.PHASE_OVERHEAD] == pytest.approx(0.3)
        assert d["overlap_hidden_s"] == pytest.approx(0.2)
        assert d["overlap_hidden_ratio"] == pytest.approx(0.2 / 0.7,
                                                          abs=1e-4)

    def test_fully_hidden_collective(self):
        d = attribution.decompose_step(
            _steprec(collective_t=0.1, collective_dur=0.5))
        assert d["phases"][attribution.PHASE_EXPOSED_COMM] == 0.0
        assert d["overlap_hidden_ratio"] == pytest.approx(1.0)

    def test_malformed_spans_tolerated(self):
        rec = _steprec()
        rec["spans"].append({"name": "bad"})          # no t/dur
        rec["spans"].append({"t": float("nan"), "dur": 1.0})
        d = attribution.decompose_step(rec)
        assert sum(d["phases"].values()) == pytest.approx(d["wall_s"])
        assert attribution.decompose_step({"spans": []}) is None
        assert attribution.decompose_step("not a mapping") is None


# ---------------------------------------------------------------------------
# Cluster merge + critical path
# ---------------------------------------------------------------------------


class TestClusterAnalysis:
    def _two_rank_payloads(self, late_by=0.5):
        p0 = _payload(rank="0", host="h0")
        rec1 = _steprec()
        for sp in rec1["spans"]:
            if sp["cat"] == "collective":
                sp["t"] += late_by
        p1 = _payload(rank="1", host="h1", steps=[rec1])
        return {"h0": p0, "h1": p1}

    def test_gating_rank_and_straggler_wait(self):
        out = attribution.analyze_cluster(self._two_rank_payloads())
        assert out["status"] == "ok"
        g = out["groups"][0]
        colls = [n for n in g["critical_path"]
                 if n["kind"] == "collective"]
        assert colls and colls[0]["gating_rank"] == "1"
        assert colls[0]["skew_s"] == pytest.approx(0.5)
        assert g["suspect_rank"] == "1" and g["suspect_host"] == "h1"
        # Rank 0 waited 0.5s for rank 1 inside its collective span:
        # carved out of its exposed comm, sum still = wall.
        r0 = g["ranks"]["0"]
        assert r0["phases"][attribution.PHASE_STRAGGLER_WAIT] == \
            pytest.approx(0.5)
        for d in g["ranks"].values():
            assert sum(d["phases"].values()) == pytest.approx(d["wall_s"])

    def test_offset_correction_zeroes_false_skew(self):
        # Rank 1's clock runs +5s ahead but ships the matching measured
        # offset: corrected arrivals coincide, no skew, no wait.
        p0 = _payload(rank="0", host="h0")
        p1 = copy.deepcopy(p0)
        p1.update(rank="1", host="h1", clock_offset_s=-5.0)
        for rec in p1["steps"]:
            for sp in rec["spans"]:
                sp["t"] += 5.0
        out = attribution.analyze_cluster({"h0": p0, "h1": p1})
        g = out["groups"][0]
        colls = [n for n in g["critical_path"]
                 if n["kind"] == "collective"]
        assert colls[0]["skew_s"] == pytest.approx(0.0, abs=1e-6)
        for d in g["ranks"].values():
            assert d["phases"][attribution.PHASE_STRAGGLER_WAIT] == 0.0

    def test_unsynced_and_ambient_steps_never_group(self):
        recs = [_steprec(synced=False), _steprec(step=-1)]
        out = attribution.analyze_cluster(
            {"h0": _payload(steps=recs)})
        assert out["status"] == "insufficient_samples"
        assert out["groups"] == []

    def test_cross_generation_steps_never_group(self):
        p0 = _payload(rank="0", host="h0", generation=1)
        p1 = _payload(rank="1", host="h1", generation=2)
        out = attribution.analyze_cluster({"h0": p0, "h1": p1})
        assert len(out["groups"]) == 2  # one single-rank group each
        for g in out["groups"]:
            assert len(g["ranks"]) == 1

    def test_mfu_from_shipped_flops(self):
        p = _payload(model_flops_per_step=1e9, peak_flops_per_rank=1e12)
        out = attribution.analyze_cluster({"h0": p})
        d = out["groups"][0]["ranks"]["0"]
        # 1e9 / (2.0s * 1e12) = 0.0005
        assert d["mfu"] == pytest.approx(0.0005)

    def test_steps_and_rank_filters(self):
        steps = [_steprec(step=s) for s in (1, 2, 3)]
        payloads = {"h0": _payload(steps=steps),
                    "h1": _payload(rank="1", host="h1",
                                   steps=copy.deepcopy(steps))}
        out = attribution.analyze_cluster(payloads, steps=2)
        assert [g["step"] for g in out["groups"]] == [2, 3]
        out = attribution.analyze_cluster(payloads, rank="1")
        assert all(list(g["ranks"]) == ["1"] for g in out["groups"])


# ---------------------------------------------------------------------------
# Regression sentinel
# ---------------------------------------------------------------------------


class TestRegressionSentinel:
    def test_warmup_then_alarm_latched_once(self):
        s = attribution.RegressionSentinel(alpha=0.3, sigma=4.0,
                                           min_steps=3)
        for _ in range(5):
            v = s.observe({"compute": 1.0, "exposed_comm": 0.1})
            assert v["alarms"] == []
        spike = {"compute": 1.0, "exposed_comm": 1.0}
        v = s.observe(spike)
        assert v["alarms"] == ["exposed_comm"]
        assert v["excess_s"]["exposed_comm"] == pytest.approx(0.9,
                                                              abs=0.05)
        # Latched: the same sustained regression does not re-alarm.
        v = s.observe(spike)
        assert v["alarms"] == []
        snap = s.snapshot()
        assert snap["alarms_total"] == 1
        assert "exposed_comm" in snap["alarmed"]

    def test_rearm_after_recovery(self):
        s = attribution.RegressionSentinel(alpha=0.5, sigma=4.0,
                                           min_steps=2)
        for _ in range(4):
            s.observe({"compute": 1.0})
        assert s.observe({"compute": 3.0})["alarms"] == ["compute"]
        for _ in range(8):  # recover: baseline re-converges, score < σ/2
            s.observe({"compute": 1.0})
        assert "compute" not in s.snapshot()["alarmed"]
        assert s.observe({"compute": 3.0})["alarms"] == ["compute"]
        assert s.snapshot()["alarms_total"] == 2

    def test_faster_steps_never_alarm(self):
        s = attribution.RegressionSentinel(alpha=0.3, sigma=4.0,
                                           min_steps=2)
        for _ in range(4):
            s.observe({"compute": 1.0})
        v = s.observe({"compute": 0.2})  # improvement: no positive excess
        assert v["alarms"] == [] and v["scores"]["compute"] == 0.0


# ---------------------------------------------------------------------------
# Worker-side plane: tracer hook, gauges, MFU, summary
# ---------------------------------------------------------------------------


def _run_synced_step():
    """One synced step on this process's tracer: compute [0, 1] and
    [1.6, 1.8], a collective over [0.8, 1.5]."""
    tr = tracing.get_tracer()
    with tr.step_scope("train_step") as rec:
        rec.synced = True
        t0 = tr.clock.now()
        tr.record(attribution.SPAN_FORWARD_BACKWARD,
                  attribution.CAT_PHASE, t0, 1.0)
        tr.record(attribution.SPAN_COLLECTIVE,
                  attribution.CAT_COLLECTIVE, t0 + 0.8, 0.7)
        tr.record(attribution.SPAN_OPTIMIZER_UPDATE,
                  attribution.CAT_PHASE, t0 + 1.6, 0.2)


class TestWorkerPlane:
    def test_synced_step_exports_gauges(self):
        attribution.set_model_flops_per_step(1e9, peak_flops=1e12)
        _run_synced_step()
        exposed = metrics.EXPOSED_COMM.labels().get()
        assert exposed == pytest.approx(0.5, abs=1e-3)
        hidden = metrics.OVERLAP_HIDDEN.labels().get()
        assert hidden == pytest.approx(0.2 / 0.7, abs=1e-3)
        compute = metrics.STEP_PHASE_SECONDS.labels(
            phase=attribution.PHASE_COMPUTE).get()
        assert compute == pytest.approx(1.2, abs=1e-3)
        assert metrics.MFU_RATIO.labels().get() > 0

    def test_unsynced_step_does_not_feed_plane(self):
        tr = tracing.get_tracer()
        with tr.step_scope("train_step"):
            tr.record(attribution.SPAN_COLLECTIVE,
                      attribution.CAT_COLLECTIVE, tr.clock.now(), 0.5)
        assert attribution.summary()["last_step"] is None
        assert metrics.EXPOSED_COMM.labels().get() == 0.0

    def test_payload_carries_declared_flops(self):
        attribution.set_model_flops_per_step(2e9, peak_flops=1e12)
        payload = tracing.get_tracer().payload()
        assert payload["model_flops_per_step"] == 2e9
        assert payload["peak_flops_per_rank"] == 1e12

    def test_profiler_summary_has_attribution(self):
        from horovod_tpu import profiler

        _run_synced_step()
        out = profiler.summary()["attribution"]
        assert out["last_step"]["phases"][attribution.PHASE_COMPUTE] \
            == pytest.approx(1.2, abs=1e-3)
        assert "sentinel" in out and "exposed_comm_residual_s" in out

    def test_unknown_device_kind_is_an_error_not_a_missing_peak(self):
        assert attribution.peak_flops_for_kind("TPU v5 lite") == 197e12
        with pytest.raises(ValueError, match="CHIP_PEAK_FLOPS"):
            attribution.peak_flops_for_kind("TPU v9 imaginary")
        # A CPU mesh has no chip peak to look up: None, and no error.
        assert attribution.detect_peak_flops() is None

    def test_unknown_chip_fails_the_declaration_not_the_step_hook(
            self, monkeypatch):
        # The per-step hook runs under tracing's "attribution is
        # advisory" guard, so a peak looked up there would fail quietly
        # and take the phase gauges and the sentinel with it. The peak is
        # resolved where the user declares the FLOPs, and only there.
        import types

        import jax

        def fake_devices(kind):
            return lambda: [types.SimpleNamespace(platform="tpu",
                                                  device_kind=kind)] * 4

        monkeypatch.setattr(jax, "local_devices", fake_devices("TPU v5"))
        with pytest.raises(ValueError, match="'TPU v5'"):
            attribution.set_model_flops_per_step(1e9)
        assert attribution.model_flops() == (None, None)
        _run_synced_step()  # the plane works on, without MFU
        assert attribution.summary()["last_step"]["phases"][
            attribution.PHASE_COMPUTE] == pytest.approx(1.2, abs=1e-3)
        assert "mfu" not in attribution.summary()["last_step"]

        monkeypatch.setattr(jax, "local_devices",
                            fake_devices("TPU v5 lite"))
        attribution.set_model_flops_per_step(1e9)
        assert attribution.model_flops() == (1e9, 4 * 197e12)
        # From here on no step looks at the devices again.
        monkeypatch.setattr(jax, "local_devices", fake_devices("TPU v5"))
        _run_synced_step()
        assert attribution.summary()["last_step"]["mfu"] > 0
        assert tracing.get_tracer().payload()[
            "peak_flops_per_rank"] == 4 * 197e12

    def test_phase_vocabulary_is_shared(self):
        # The elastic step and attribution must agree on one constant set.
        assert attribution.PHASE_SPAN_NAMES == (
            "forward_backward", "collective", "optimizer_update")
        assert attribution.STEP_PHASES == (
            "compute", "exposed_comm", "straggler_wait", "overhead")


# ---------------------------------------------------------------------------
# GET /criticalpath + trace-route filters over real HTTP
# ---------------------------------------------------------------------------


class TestCriticalpathEndpoint:
    def _publish(self, srv, late_by=0.5):
        from horovod_tpu.runner.http.kv_server import KVClient

        client = KVClient("127.0.0.1", srv.port)
        p0 = _payload(rank="0", host="h0")
        rec1 = _steprec()
        for sp in rec1["spans"]:
            if sp["cat"] == "collective":
                sp["t"] += late_by
        p1 = _payload(rank="1", host="h1", steps=[rec1])
        client.put("trace", "h0", json.dumps(p0).encode())
        client.put("trace", "h1", json.dumps(p1).encode())
        return client

    def _get(self, srv, path):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}{path}", timeout=10) as r:
            assert r.status == 200
            return json.loads(r.read())

    def test_criticalpath_over_http(self):
        srv = _server()
        try:
            self._publish(srv)
            body = self._get(srv, "/criticalpath")
            assert body["status"] == "ok"
            g = body["groups"][-1]
            colls = [n for n in g["critical_path"]
                     if n["kind"] == "collective"]
            assert colls and colls[0]["gating_rank"] == "1"
            for d in g["ranks"].values():
                assert sum(d["phases"].values()) == pytest.approx(
                    d["wall_s"], rel=0.05)
            assert "sentinel" in body["regression"]
        finally:
            srv.stop()

    def test_a_live_tracers_payload_analyses_as_two_ranks(self):
        """The producer's own wire format through the real routes: a
        synced step recorded on this process's tracer, published as
        rank 0 and, clocks shifted +5 s with the matching measured
        offset, as rank 1. Both land on one timebase, so every rank
        decomposes, the collective names its gating rank and the skew
        gauges read no lateness."""
        from horovod_tpu.runner.http.kv_server import KVClient

        _run_synced_step()
        live = tracing.get_tracer().payload()
        assert live["steps"] and live["steps"][-1]["synced"]
        shifted = copy.deepcopy(live)
        for rec in shifted["steps"]:
            rec["t"] += 5.0
            for sp in rec["spans"]:
                sp["t"] += 5.0
        srv = _server()
        try:
            srv.set_cluster_info(world_np=2)
            client = KVClient("127.0.0.1", srv.port)
            client.put("trace", "h0", json.dumps(dict(
                live, rank="0", host="h0", clock_offset_s=0.0)).encode())
            client.put("trace", "h1", json.dumps(dict(
                shifted, rank="1", host="h1",
                clock_offset_s=-5.0)).encode())
            body = self._get(srv, "/criticalpath")
            assert body["status"] == "ok"
            g = body["groups"][-1]
            assert sorted(g["ranks"]) == ["0", "1"]
            for d in g["ranks"].values():
                assert d["wall_s"] > 0
                assert sum(d["phases"].values()) == pytest.approx(
                    d["wall_s"], rel=0.05)
            colls = [n for n in g["critical_path"]
                     if n["kind"] == "collective"]
            assert colls and all(
                n["gating_rank"] in ("0", "1") for n in colls)
            parsed = metrics.validate_prometheus_text(srv.metrics_text())
            skews = [v for _, v in
                     parsed["hvd_collective_skew_seconds"]["samples"]]
            assert skews and max(skews) < 1e-6
        finally:
            srv.stop()

    def test_cold_start_insufficient_samples(self):
        srv = _server()
        try:
            body = self._get(srv, "/criticalpath")
            assert body["status"] == "insufficient_samples"
            assert body["groups"] == []
        finally:
            srv.stop()

    def test_query_filters_and_400(self):
        srv = _server()
        try:
            self._publish(srv)
            body = self._get(srv, "/criticalpath?rank=1")
            assert all(list(g["ranks"]) == ["1"]
                       for g in body["groups"])
            body = self._get(srv, "/criticalpath?steps=1")
            assert len(body["groups"]) == 1
            tl = self._get(srv, "/timeline?rank=0&steps=1")
            pids = {e["pid"] for e in tl["traceEvents"]
                    if e.get("ph") == "X"}
            assert pids == {0}
            for bad in ("?steps=0", "?steps=abc", "?bogus=1"):
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(
                        f"http://127.0.0.1:{srv.port}/timeline{bad}",
                        timeout=10)
                assert ei.value.code == 400
        finally:
            srv.stop()

    def test_413_cap_on_unfiltered_timeline(self, monkeypatch):
        srv = _server()
        try:
            self._publish(srv)
            monkeypatch.setenv("HOROVOD_TIMELINE_MAX_EVENTS", "2")
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/timeline", timeout=10)
            assert ei.value.code == 413
            # A bounded request always answers — and /criticalpath is
            # never capped: its body is the small per-group analysis,
            # not the raw spans.
            assert self._get(srv, "/timeline?steps=1")
            assert self._get(srv, "/criticalpath")["status"] == "ok"
        finally:
            srv.stop()

    def test_reset_invalidates_analysis(self):
        srv = _server()
        try:
            self._publish(srv)
            assert self._get(srv, "/criticalpath")["status"] == "ok"
            srv.reset()  # elastic re-formation clears the trace scope
            assert (self._get(srv, "/criticalpath")["status"]
                    == "insufficient_samples")
        finally:
            srv.stop()

    def test_step_regression_event_names_suspect(self, tmp_path,
                                                 monkeypatch):
        """Sustained baseline then a spiked group: the server journals
        ONE step_regression naming the critical path's gating rank."""
        from horovod_tpu.runner.http.kv_server import KVClient

        monkeypatch.setenv("HOROVOD_EVENT_LOG",
                           str(tmp_path / "events.jsonl"))
        monkeypatch.setenv("HOROVOD_STEP_REGRESSION_MIN_STEPS", "2")
        monkeypatch.setenv("HOROVOD_STEP_REGRESSION_SIGMA", "3.0")
        srv = _server()
        try:
            client = KVClient("127.0.0.1", srv.port)

            def ship(step, exposed_extra=0.0):
                recs = []
                for rank, host in (("0", "h0"), ("1", "h1")):
                    rec = _steprec(step=step)
                    if exposed_extra and rank == "1":
                        for sp in rec["spans"]:
                            if sp["cat"] == "collective":
                                sp["dur"] += exposed_extra
                                # rank 1 arrives late too: it gates.
                                sp["t"] += 0.01
                    recs.append((host, _payload(rank=rank, host=host,
                                                steps=[rec])))
                for host, p in recs:
                    client.put("trace", host, json.dumps(p).encode())
                srv.criticalpath_summary()  # tick the sentinel

            for step in range(1, 6):
                ship(step)
            ship(6, exposed_extra=2.0)  # the regression
            events = [json.loads(l) for l in
                      open(tmp_path / "events.jsonl")]
            regs = [e for e in events if e["event"] == "step_regression"]
            assert len(regs) == 1, regs
            assert regs[0]["suspect_rank"] == "1"
            assert regs[0]["suspect_host"] == "h1"
            assert "exposed_comm" in regs[0]["phases"]
            assert srv.regression_suspects().get("h1", 0.0) > 0.5
        finally:
            srv.stop()
            metrics.journal()


# ---------------------------------------------------------------------------
# Journal rotation (HOROVOD_EVENT_LOG_MAX_BYTES)
# ---------------------------------------------------------------------------


class TestJournalRotation:
    def test_size_gated_rotation_keeps_whole_lines(self, tmp_path,
                                                   monkeypatch):
        path = tmp_path / "events.jsonl"
        monkeypatch.setenv("HOROVOD_EVENT_LOG", str(path))
        monkeypatch.setenv("HOROVOD_EVENT_LOG_MAX_BYTES", "400")
        for i in range(40):
            metrics.event("rotation_probe", i=i, pad="x" * 40)
        metrics.journal()  # flush current handle state
        prev = tmp_path / "events.jsonl.prev"
        assert prev.exists()
        assert path.stat().st_size < 2 * 400
        # Line-atomic: every line in BOTH slots parses as a whole record.
        seen = []
        for p in (prev, path):
            for line in open(p).read().splitlines():
                seen.append(json.loads(line)["i"])
        # No record torn or lost across the rotation boundary: the tail
        # of .prev and the head of the current file are consecutive.
        assert seen == sorted(seen)
        assert seen[-1] == 39
        monkeypatch.delenv("HOROVOD_EVENT_LOG")
        metrics.journal()

    def test_unbounded_by_default(self, tmp_path, monkeypatch):
        path = tmp_path / "events.jsonl"
        monkeypatch.setenv("HOROVOD_EVENT_LOG", str(path))
        monkeypatch.delenv("HOROVOD_EVENT_LOG_MAX_BYTES", raising=False)
        for i in range(50):
            metrics.event("rotation_probe", i=i, pad="x" * 40)
        assert not (tmp_path / "events.jsonl.prev").exists()
        monkeypatch.delenv("HOROVOD_EVENT_LOG")
        metrics.journal()


# ---------------------------------------------------------------------------
# Flight-recorder integration
# ---------------------------------------------------------------------------


class TestFlightRecordAttribution:
    def test_dump_attaches_phase_decomposition(self, tmp_path,
                                               monkeypatch):
        monkeypatch.setenv("HOROVOD_EVENT_LOG",
                           str(tmp_path / "events.jsonl"))
        tr = tracing.get_tracer()
        with tr.step_scope("train_step") as rec:
            rec.synced = True
            t0 = tr.clock.now()
            tr.record(attribution.SPAN_FORWARD_BACKWARD,
                      attribution.CAT_PHASE, t0, 1.0)
            tr.record(attribution.SPAN_COLLECTIVE,
                      attribution.CAT_COLLECTIVE, t0 + 0.8, 0.7)
        snap = tracing.dump_flight_record("test_reason")
        att = snap["attribution"]
        phases = att["last_synced_step"]["phases"]
        assert phases[attribution.PHASE_COMPUTE] == pytest.approx(
            1.0, abs=1e-3)
        events = [json.loads(l)
                  for l in open(tmp_path / "events.jsonl")]
        fr = [e for e in events if e["event"] == "flight_record"][0]
        assert fr["attribution"]["last_synced_step"]["phases"]
        monkeypatch.delenv("HOROVOD_EVENT_LOG")
        metrics.journal()

    def test_wedged_collective_names_gating_rank(self, tmp_path,
                                                 monkeypatch):
        """Abort-consume with a collective span still OPEN: the dump's
        attribution section names the gating rank the cluster's partial
        critical path holds for that collective — fetched live from the
        rendezvous /criticalpath, like a real wedged worker would.
        Subprocess, alongside the existing abort/stall dump tests: the
        dump path runs in a worker whose env points at a REAL server."""
        from horovod_tpu.runner.http.kv_server import KVClient

        srv = _server()
        ev = tmp_path / "wedge_events.jsonl"
        try:
            client = KVClient("127.0.0.1", srv.port)
            p0 = _payload(rank="0", host="h0")
            rec1 = _steprec()
            for sp in rec1["spans"]:
                if sp["cat"] == "collective":
                    sp["t"] += 0.5
            p1 = _payload(rank="1", host="h1", steps=[rec1])
            client.put("trace", "h0", json.dumps(p0).encode())
            client.put("trace", "h1", json.dumps(p1).encode())

            script = f"""
import json, os
os.environ["HOROVOD_EVENT_LOG"] = {str(ev)!r}
os.environ["HOROVOD_RENDEZVOUS_ADDR"] = "127.0.0.1"
os.environ["HOROVOD_RENDEZVOUS_PORT"] = {str(srv.port)!r}
from horovod_tpu import abort, attribution, tracing
tr = tracing.get_tracer()
with tr.step_scope("train_step") as rec:
    rec.synced = True
    t0 = tr.clock.now()
    tr.record(attribution.SPAN_FORWARD_BACKWARD,
              attribution.CAT_PHASE, t0, 1.0)
# The wedge: the collective the cluster says rank 1 gates, still open.
tr.begin_span(attribution.SPAN_COLLECTIVE, attribution.CAT_COLLECTIVE)
abort.trigger_local("peer wedged")
abort.consume()
"""
            proc = subprocess.run(
                [sys.executable, "-c", script], timeout=120,
                capture_output=True, text=True,
                env={**os.environ, "JAX_PLATFORMS": "cpu"})
            assert proc.returncode == 0, proc.stderr[-2000:]
            events = [json.loads(l) for l in open(ev)]
            fr = [e for e in events if e["event"] == "flight_record"][0]
            wedged = fr["attribution"]["wedged_collectives"]
            assert wedged[0]["name"] == attribution.SPAN_COLLECTIVE
            assert wedged[0]["gating"]["rank"] == "1"
            assert wedged[0]["gating"]["host"] == "h1"
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# Policy plane: the step-regression evidence channel
# ---------------------------------------------------------------------------


class TestPolicyRegressionChannel:
    def _env(self, monkeypatch, **extra):
        monkeypatch.setenv("HOROVOD_TARGET_GOODPUT", "0.9")
        monkeypatch.setenv("HOROVOD_STRAGGLER_WINDOW", "1.0")
        monkeypatch.setenv("HOROVOD_POLICY_DRAIN_SKEW", "5.0")  # skew off
        monkeypatch.setenv("HOROVOD_POLICY_REALIZE_WINDOW", "2.0")
        monkeypatch.setenv("HOROVOD_POLICY_RESIZE_COST", "1.0")
        for k, v in extra.items():
            monkeypatch.setenv(k, v)

    def test_sustained_regression_drains_suspect(self, monkeypatch):
        from horovod_tpu.elastic.policy import PolicyController

        self._env(monkeypatch, HOROVOD_POLICY_STEP_REGRESSION="0.3")
        clock = [0.0]
        c = PolicyController(min_np=1, clock=lambda: clock[0])
        world = ["good", "bad"]
        blind = {"ranks": {}, "worst": None}
        for t in (0.0, 0.6, 1.2):
            clock[0] = t
            c.note_rate(2.0)
            c.observe(blind, {}, world,
                      regression_excess={"good": 0.0, "bad": 0.6})
        d = c.decide(world, spares_ready=1)
        assert d is not None and d.host == "bad"
        assert d.evidence["step_regression_ewma_s"]["bad"] > 0.3

    def test_channel_inert_without_knob(self, monkeypatch):
        """A/B: with HOROVOD_POLICY_STEP_REGRESSION unset, regression
        evidence changes NOTHING — decisions are bit-for-bit those of a
        sentinel-free build."""
        from horovod_tpu.elastic.policy import PolicyController

        self._env(monkeypatch)
        monkeypatch.delenv("HOROVOD_POLICY_STEP_REGRESSION",
                           raising=False)
        clock = [0.0]
        c = PolicyController(min_np=1, clock=lambda: clock[0])
        world = ["good", "bad"]
        blind = {"ranks": {}, "worst": None}
        for t in (0.0, 0.6, 1.2, 2.0):
            clock[0] = t
            c.note_rate(2.0)
            c.observe(blind, {}, world,
                      regression_excess={"good": 0.0, "bad": 9.9})
        assert c.decide(world, spares_ready=1) is None
        assert "bad" not in c._above_since

    def test_state_survives_export_restore(self, monkeypatch):
        from horovod_tpu.elastic.policy import PolicyController

        self._env(monkeypatch, HOROVOD_POLICY_STEP_REGRESSION="0.2")
        clock = [0.0]
        c = PolicyController(min_np=1, clock=lambda: clock[0])
        c.observe({"ranks": {}, "worst": None}, {}, ["h"],
                  regression_excess={"h": 0.7})
        state = c.export_state()
        assert state["regr_ewma"]["h"] > 0
        c2 = PolicyController(min_np=1, clock=lambda: clock[0])
        c2.restore_state(state)
        assert c2._regr_ewma["h"] == pytest.approx(
            state["regr_ewma"]["h"])


# ---------------------------------------------------------------------------
# Metric-docs consistency lane
# ---------------------------------------------------------------------------


class TestMetricDocsLane:
    def test_checker_passes_on_current_tree(self):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "check_metric_docs.py")],
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr or proc.stdout

    @pytest.fixture()
    def cmd(self):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import check_metric_docs
        finally:
            sys.path.pop(0)
        return check_metric_docs

    def test_checker_catches_drift(self, cmd, tmp_path):
        """An instrument registered in code but absent from the docs
        table fails the lane naming the metric."""
        pkg = tmp_path / "horovod_tpu"
        pkg.mkdir()
        (pkg / "m.py").write_text(
            'X = counter(\n    "hvd_totally_new_metric_total",\n'
            '    "help")\n')
        docs = tmp_path / "docs"
        docs.mkdir()
        (docs / "observability.md").write_text(
            "| `hvd_ghost_metric` | counter | — | documented only |\n")
        registered = cmd.code_metrics(str(tmp_path))
        documented = cmd.doc_metrics(str(docs / "observability.md"))
        assert "hvd_totally_new_metric_total" in registered
        assert "hvd_ghost_metric" in documented

    def test_every_instrument_has_a_writer_in_the_package(self, cmd):
        """The third lane on this tree: nothing is registered that only
        a script outside ``horovod_tpu/`` could ever have set."""
        assert cmd.unwritten_metrics() == {}

    @pytest.mark.parametrize("use, unwritten", [
        ("X.labels()\n", True),                       # a zero cell only
        ("y = X.labels(kind='a').get()\n", True),      # a read
        ("X.inc(2, kind='a')\n", False),
        ("X.labels(kind=str(k)).observe(\n    0.5)\n", False),
        ("from .m import X as _X\nm.X.set(1.0)\n", False),
    ])
    def test_writer_lane_tells_a_write_from_a_zero_cell(
            self, cmd, tmp_path, use, unwritten):
        pkg = tmp_path / "horovod_tpu"
        pkg.mkdir()
        (pkg / "m.py").write_text(
            'X = gauge(\n    "hvd_lonely_ratio", "help", ("kind",))\n'
            'self._c = counter("hvd_kept_total", "help")\n'
            'self._c.inc()\n')
        (pkg / "user.py").write_text(use)
        found = cmd.unwritten_metrics(str(tmp_path))
        assert "hvd_kept_total" not in found
        assert ("hvd_lonely_ratio" in found) is unwritten
        if unwritten:
            assert found["hvd_lonely_ratio"] == "X (horovod_tpu/m.py)"
