"""Cross-rank step tracing: clock alignment, timeline merge, skew
attribution, and the flight recorder.

Covers the tracing plane end to end over the REAL HTTP plumbing where it
matters: two simulated ranks with deliberately skewed clocks ship spans
through the real ``PUT /trace`` route, and the merged ``GET /timeline``
must restore their true ordering; a deliberately delayed rank (the
``worker.step`` faults point) must show up in the skew gauges with the
injected delay; every flight-recorder trigger must leave a journal
postmortem.
"""

from __future__ import annotations

import json
import os
import time
import urllib.request

import pytest

from horovod_tpu import abort, faults, metrics, tracing


@pytest.fixture(autouse=True)
def _fresh_planes(monkeypatch):
    metrics.reset_for_testing()
    tracing.reset_for_testing()
    faults.reset()
    abort.reset()
    yield
    faults.reset()
    abort.reset()
    tracing.reset_for_testing()


def _server():
    from horovod_tpu.runner.http.kv_server import RendezvousServer

    srv = RendezvousServer(host="127.0.0.1")
    srv.start()
    return srv


# ---------------------------------------------------------------------------
# Clock alignment
# ---------------------------------------------------------------------------


class TestClockSync:
    def test_offset_and_error_bound(self):
        cs = tracing.ClockSync()
        # Server is 100s ahead; 0.2s RTT symmetric.
        cs.observe(t_send=10.0, t_recv=10.2, t_server=110.1)
        assert cs.offset() == pytest.approx(100.0)
        assert cs.error() == pytest.approx(0.1)
        assert cs.synced()

    def test_minimum_rtt_sample_wins(self):
        cs = tracing.ClockSync()
        # Fat RTT with asymmetric delay gives a biased offset...
        cs.observe(10.0, 12.0, 111.9)  # offset estimate 100.9, err 1.0
        # ...the tight exchange afterwards corrects it.
        cs.observe(20.0, 20.02, 120.01)  # offset 100.0, err 0.01
        assert cs.offset() == pytest.approx(100.0)
        assert cs.error() == pytest.approx(0.01)

    def test_unsynced_defaults(self):
        cs = tracing.ClockSync()
        assert cs.offset() == 0.0
        assert cs.error() is None
        assert not cs.synced()

    def test_heartbeat_reply_carries_server_time_and_syncs(self, monkeypatch):
        """The worker's ordinary heartbeat PUT doubles as the NTP
        exchange: the server's reply stamps its wall clock and the
        worker's ClockSync converges to ~zero offset on loopback."""
        from horovod_tpu.runner.elastic import worker as elastic_worker

        srv = _server()
        try:
            monkeypatch.setenv("HOROVOD_RENDEZVOUS_ADDR", "127.0.0.1")
            monkeypatch.setenv("HOROVOD_RENDEZVOUS_PORT", str(srv.port))
            monkeypatch.setenv("HOROVOD_HOSTNAME", "sync-host")
            monkeypatch.setenv("HOROVOD_RANK", "0")
            ctx = elastic_worker.ElasticWorkerContext()
            assert ctx.send_heartbeat()
            cs = tracing.clock_sync()
            assert cs.synced()
            # Same machine, same clock: offset bounded by the RTT.
            assert abs(cs.offset()) < 1.0
            assert cs.error() is not None and cs.error() < 1.0
            # And the worker-side gauge mirrors it.
            assert metrics.CLOCK_OFFSET.labels().get() == pytest.approx(
                cs.offset())
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# Step tracer + spans
# ---------------------------------------------------------------------------


class TestStepTracer:
    def test_step_scope_records_spans_and_step(self):
        tr = tracing.get_tracer()
        with tr.step_scope("train_step") as rec:
            with tracing.span("forward", "phase"):
                pass
            with tracing.span("allreduce", "collective"):
                pass
        assert rec.step == 1
        steps = tr.ring_snapshot()
        assert len(steps) == 1
        names = [s["name"] for s in steps[0]["spans"]]
        assert names[0] == "train_step"  # the step span leads
        assert "forward" in names and "allreduce" in names

    def test_ring_keeps_last_k(self):
        tr = tracing.StepTracer(ring_steps=3)
        for _ in range(7):
            with tr.step_scope("train_step"):
                pass
        steps = [s["step"] for s in tr.ring_snapshot()]
        assert steps == [5, 6, 7]

    def test_span_cap_counts_drops(self):
        tr = tracing.StepTracer(max_spans=4)
        with tr.step_scope("train_step"):
            for i in range(10):
                tr.record(f"s{i}", "phase", 0.0, 0.001)
        (steprec,) = tr.ring_snapshot()
        assert len(steprec["spans"]) <= 5  # cap + the step span
        assert steprec["dropped_spans"] >= 6

    def test_ambient_spans_collect_outside_steps(self):
        tr = tracing.get_tracer()
        with tracing.span("allreduce", "collective"):
            pass
        snap = tr.ring_snapshot()
        assert snap and snap[-1]["kind"] == "eager"
        assert snap[-1]["spans"][0]["name"] == "allreduce"

    def test_open_spans_in_flight_snapshot(self):
        tr = tracing.get_tracer()
        token = tr.begin_span("wedged_allreduce", "collective")
        snap = tr.flight_snapshot()
        assert [o["name"] for o in snap["open_spans"]] == [
            "wedged_allreduce"]
        assert snap["open_spans"][0]["age_s"] >= 0.0
        tr.end_span(token)
        assert tr.flight_snapshot()["open_spans"] == []

    def test_payload_wire_format(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_RANK", "3")
        monkeypatch.setenv("HOROVOD_HOSTNAME", "payload-host")
        tr = tracing.get_tracer()
        with tr.step_scope("train_step"):
            pass
        p = tr.payload()
        assert p["rank"] == "3" and p["host"] == "payload-host"
        assert "clock_offset_s" in p and isinstance(p["steps"], list)
        json.dumps(p)  # must be wire-serializable


# ---------------------------------------------------------------------------
# Cross-rank merge e2e (real HTTP, injected clock skew)
# ---------------------------------------------------------------------------


class TestTimelineMerge:
    def _simulate_rank(self, srv, rank, host, clock_skew, start_delay,
                       monkeypatch):
        """One simulated worker: a skewed wall clock, a real heartbeat
        exchange measuring the offset, one traced step shipped through
        the real PUT /trace route."""
        from horovod_tpu.runner.http.kv_server import KVClient

        clock = lambda: time.time() + clock_skew  # noqa: E731
        cs = tracing.ClockSync(clock=clock)
        client = KVClient("127.0.0.1", srv.port)
        # Real NTP-style exchange over HTTP (timestamps on the SKEWED
        # clock, server time from the reply).
        for _ in range(3):
            t0 = clock()
            reply = client.put("heartbeat", host,
                               json.dumps({"rank": rank}).encode())
            t1 = clock()
            cs.observe(t0, t1, json.loads(reply)["t_server"])
        tracer = tracing.StepTracer(cs)
        if start_delay:
            time.sleep(start_delay)
        with tracer.step_scope("train_step"):
            with_span_clock = cs.now()
            tracer.record("allreduce", "collective", with_span_clock, 0.01)
        monkeypatch.setenv("HOROVOD_RANK", str(rank))
        monkeypatch.setenv("HOROVOD_HOSTNAME", host)
        payload = tracer.payload()
        client.put(tracing.TRACE_SCOPE, host, json.dumps(payload).encode())
        return payload

    def test_merged_timeline_corrects_injected_skew(self, monkeypatch):
        """Rank 1's clock runs 120s ahead of rank 0's, but it actually
        starts ~0.3s later. The merged /timeline must order the two
        ranks by TRUE time (0.3s apart), not raw clocks (120s apart)."""
        srv = _server()
        try:
            self._simulate_rank(srv, 0, "rank0-host", clock_skew=0.0,
                                start_delay=0.0, monkeypatch=monkeypatch)
            self._simulate_rank(srv, 1, "rank1-host", clock_skew=120.0,
                                start_delay=0.3, monkeypatch=monkeypatch)
            url = f"http://127.0.0.1:{srv.port}/timeline"
            with urllib.request.urlopen(url, timeout=10) as r:
                assert r.status == 200
                merged = json.loads(r.read())
            spans = [e for e in merged["traceEvents"] if e["ph"] == "X"]
            assert {e["pid"] for e in spans} == {0, 1}
            t0 = min(e["ts"] for e in spans if e["pid"] == 0
                     and e["name"] == "allreduce")
            t1 = min(e["ts"] for e in spans if e["pid"] == 1
                     and e["name"] == "allreduce")
            delta_s = (t1 - t0) / 1e6
            # True separation ~0.3s; raw clocks would say ~120.3s. Allow
            # generous slack for loopback RTT error + scheduling.
            assert 0.05 < delta_s < 2.0, (
                f"offset correction failed: corrected delta {delta_s}s")
            # Track metadata: one named process per rank.
            names = {e["args"]["name"] for e in merged["traceEvents"]
                     if e.get("name") == "process_name"}
            assert names == {"rank 0 (rank0-host)", "rank 1 (rank1-host)"}
        finally:
            srv.stop()

    def test_timeline_unauthenticated_even_with_secret(self, monkeypatch):
        """Trace viewers can't HMAC: /timeline and /stragglers share the
        /metrics auth exemption while the KV surface stays 403."""
        import urllib.error

        from horovod_tpu.runner import secret as _secret

        monkeypatch.setenv(_secret.ENV_KEY, _secret.make_secret_key())
        srv = _server()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            for route in ("/timeline", "/stragglers"):
                with urllib.request.urlopen(base + route, timeout=10) as r:
                    assert r.status == 200
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{base}/_version", timeout=10)
            assert ei.value.code == 403
        finally:
            srv.stop()

    def test_malformed_trace_payload_tolerated(self):
        from horovod_tpu.runner.http.kv_server import KVClient

        srv = _server()
        try:
            client = KVClient("127.0.0.1", srv.port)
            client.put(tracing.TRACE_SCOPE, "bad-host", b"not json")
            client.put(tracing.TRACE_SCOPE, "odd-host",
                       json.dumps({"rank": "0", "steps": [
                           {"spans": [{"cat": "collective"}]}]}).encode())
            merged = srv.timeline_json()
            assert merged["traceEvents"] is not None  # renders, no crash
            assert srv.straggler_summary()["matched"] == 0
        finally:
            srv.stop()

    def test_oversized_trace_payload_rejected(self):
        import urllib.error

        from horovod_tpu.runner.http.kv_server import KVClient

        srv = _server()
        try:
            client = KVClient("127.0.0.1", srv.port, retries=1)
            with pytest.raises(urllib.error.HTTPError) as ei:
                client.put(tracing.TRACE_SCOPE, "fat-host",
                           b"x" * (2 << 20))
            assert ei.value.code == 413
        finally:
            srv.stop()

    def test_clear_heartbeat_drops_trace_payload(self):
        from horovod_tpu.runner.http.kv_server import KVClient

        srv = _server()
        try:
            client = KVClient("127.0.0.1", srv.port)
            client.put(tracing.TRACE_SCOPE, "gone-host",
                       json.dumps({"rank": "0", "steps": []}).encode())
            assert srv.trace_payload("gone-host") is not None
            srv.clear_heartbeat("gone-host")
            assert srv.trace_payload("gone-host") is None
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# Skew attribution
# ---------------------------------------------------------------------------


class TestSkewAttribution:
    def test_compute_skew_math(self):
        payloads = {
            "hA": {"rank": "0", "clock_offset_s": 0.0, "steps": [
                {"step": 7, "spans": [
                    {"name": "allreduce", "cat": "collective",
                     "t": 100.0, "dur": 0.5}]}]},
            "hB": {"rank": "1", "clock_offset_s": -5.0, "steps": [
                {"step": 7, "spans": [
                    {"name": "allreduce", "cat": "collective",
                     "t": 105.3, "dur": 0.2}]}]},
        }
        skew = tracing.compute_skew(payloads)
        assert skew["matched"] == 1
        assert skew["worst"]["last_rank"] == "1"
        assert skew["worst"]["last_host"] == "hB"
        assert skew["worst"]["skew_s"] == pytest.approx(0.3)
        assert skew["ranks"]["1"]["max_lateness_s"] == pytest.approx(0.3)
        assert skew["ranks"]["0"]["max_lateness_s"] == 0.0

    def test_cross_generation_spans_never_match(self):
        """A zombie's pre-recovery spans (older generation) must not
        match — or skew — the re-formed world's."""
        span = {"name": "allreduce", "cat": "collective",
                "t": 100.0, "dur": 0.1}
        payloads = {
            "hA": {"rank": "0", "generation": 2, "steps": [
                {"step": 1, "spans": [dict(span)]}]},
            "hB": {"rank": "1", "generation": 3, "steps": [
                {"step": 1, "spans": [dict(span, t=150.0)]}]},
        }
        skew = tracing.compute_skew(payloads)
        assert skew["matched"] == 0 and skew["worst"] is None

    def test_rebase_zeroes_counter_keeps_ring(self):
        """World (re-)join rebases the step counter (so generation
        members count from one point) without dropping flight history."""
        tr = tracing.get_tracer()
        for _ in range(3):
            with tr.step_scope("train_step"):
                pass
        assert tr.steps_recorded() == 3
        tr.rebase()
        assert tr.steps_recorded() == 0
        assert len(tr.ring_snapshot()) == 3  # history survives
        with tr.step_scope("train_step") as rec:
            pass
        assert rec.step == 1

    def test_unmatched_spans_ignored(self):
        payloads = {
            "hA": {"rank": "0", "steps": [
                {"step": 1, "spans": [
                    {"name": "only_here", "cat": "collective",
                     "t": 1.0, "dur": 0.1}]}]},
        }
        skew = tracing.compute_skew(payloads)
        assert skew["matched"] == 0 and skew["worst"] is None

    def test_skew_gauges_exact_for_delayed_rank(self, monkeypatch):
        """A rank deliberately delayed via the faults plane
        (``worker.step=delay``) must show up in the /metrics skew gauges
        with approximately the injected delay, named as the last
        arriver."""
        from horovod_tpu.runner.http.kv_server import KVClient

        delay_s = 0.4
        # 2nd firing only: rank 0's step fires hit 1 (clean), rank 1's
        # fires hit 2 (delayed) — the deterministic per-hit window.
        faults.inject(faults.WORKER_STEP, "delay", arg=delay_s, at=2)
        srv = _server()
        try:
            client = KVClient("127.0.0.1", srv.port)
            for rank, host in ((0, "fast-host"), (1, "slow-host")):
                tracer = tracing.StepTracer(tracing.ClockSync())
                faults.fire(faults.WORKER_STEP)  # the step dispatch gate
                with tracer.step_scope("train_step"):
                    tracer.record("allreduce", "collective",
                                  tracer.clock.now(), 0.01)
                payload = dict(tracer.payload(), rank=str(rank), host=host)
                client.put(tracing.TRACE_SCOPE, host,
                           json.dumps(payload).encode())
            parsed = metrics.validate_prometheus_text(srv.metrics_text())
            skews = {l["rank"]: v for l, v in
                     parsed["hvd_collective_skew_seconds"]["samples"]}
            assert skews["0"] == pytest.approx(0.0, abs=0.15)
            assert skews["1"] == pytest.approx(delay_s, abs=0.25)
            scores = {l["host"]: v for l, v in
                      parsed["hvd_straggler_score"]["samples"]}
            assert scores["slow-host"] > scores.get("fast-host", 0.0)
            worst = srv.straggler_summary()["worst"]
            assert worst["last_rank"] == "1"
            assert worst["last_host"] == "slow-host"
        finally:
            srv.stop()

    def test_straggler_journal_event_throttled(self, tmp_path, monkeypatch):
        """Crossing HOROVOD_STRAGGLER_WARN_SKEW journals one
        straggler_detected per (generation, rank), not one per scrape."""
        from horovod_tpu.runner.http.kv_server import KVClient

        ev = tmp_path / "events.jsonl"
        monkeypatch.setenv("HOROVOD_EVENT_LOG", str(ev))
        monkeypatch.setenv("HOROVOD_STRAGGLER_WARN_SKEW", "0.1")
        srv = _server()
        try:
            client = KVClient("127.0.0.1", srv.port)
            for rank, host, t in (("0", "hA", 100.0), ("1", "hB", 100.5)):
                client.put(tracing.TRACE_SCOPE, host, json.dumps({
                    "rank": rank, "clock_offset_s": 0.0, "steps": [
                        {"step": 1, "spans": [
                            {"name": "allreduce", "cat": "collective",
                             "t": t, "dur": 0.1}]}]}).encode())
            srv.metrics_text()
            srv.metrics_text()  # second scrape: must not re-journal
            events = [json.loads(l) for l in ev.read_text().splitlines()]
            stragglers = [e for e in events
                          if e["event"] == "straggler_detected"]
            assert len(stragglers) == 1
            assert stragglers[0]["rank"] == "1"
            assert stragglers[0]["skew_s"] == pytest.approx(0.5)
        finally:
            srv.stop()
            monkeypatch.delenv("HOROVOD_EVENT_LOG")
            # Drop the journal handle so later tests get fresh files.
            metrics.journal()


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------


def _read_events(path) -> list[dict]:
    return [json.loads(l) for l in open(path).read().splitlines()]


class TestFlightRecorder:
    def _arm_ring(self, n=3):
        tr = tracing.get_tracer()
        for _ in range(n):
            with tr.step_scope("train_step"):
                with tracing.span("allreduce", "collective"):
                    pass
        return tr

    def test_abort_consume_dumps_flight_record(self, tmp_path, monkeypatch):
        ev = tmp_path / "events.jsonl"
        monkeypatch.setenv("HOROVOD_EVENT_LOG", str(ev))
        self._arm_ring()
        abort.trigger_local("peer died")
        abort.consume()
        frs = [e for e in _read_events(ev)
               if e["event"] == "flight_record"]
        assert len(frs) == 1
        fr = frs[0]
        assert fr["reason"] == "abort_consumed"
        assert fr["detail"] == "peer died"
        assert len(fr["steps"]) == 3
        assert fr["steps"][-1]["spans"][0]["name"] == "train_step"
        assert metrics.FLIGHT_DUMPS.labels(
            reason="abort_consumed").get() == 1
        monkeypatch.delenv("HOROVOD_EVENT_LOG")
        metrics.journal()

    def test_unarmed_consume_does_not_dump(self, tmp_path, monkeypatch):
        ev = tmp_path / "events.jsonl"
        monkeypatch.setenv("HOROVOD_EVENT_LOG", str(ev))
        self._arm_ring()
        abort.consume()  # hygiene call with nothing armed
        assert not [e for e in (_read_events(ev) if ev.exists() else [])
                    if e["event"] == "flight_record"]
        monkeypatch.delenv("HOROVOD_EVENT_LOG")
        metrics.journal()

    def test_stall_shutdown_dumps_flight_record(self, tmp_path, monkeypatch):
        """The inspector's shutdown path dumps the ring — with the wedged
        ticket's span still OPEN — before interrupting the main thread."""
        from horovod_tpu.stall import StallInspector

        ev = tmp_path / "events.jsonl"
        monkeypatch.setenv("HOROVOD_EVENT_LOG", str(ev))
        self._arm_ring()
        tr = tracing.get_tracer()
        token = tr.begin_span("wedged_step", "collective")
        inspector = StallInspector(warning_s=0.05, shutdown_s=0.15)
        ticket = inspector.begin("step[wedged]")
        try:
            try:
                time.sleep(8)  # the shutdown SIGINT breaks this sleep
            except KeyboardInterrupt:
                pass
            frs = [e for e in _read_events(ev)
                   if e["event"] == "flight_record"]
            assert frs and frs[0]["reason"] == "stall_shutdown"
            assert "wedged_step" in [o["name"]
                                     for o in frs[0]["open_spans"]]
            assert len(frs[0]["steps"]) == 3
        finally:
            inspector.end(ticket)
            tr.end_span(token)
            inspector.stop()
            abort.reset()
            monkeypatch.delenv("HOROVOD_EVENT_LOG")
            metrics.journal()

    def test_sigterm_drain_dumps_flight_record(self, tmp_path):
        """A real SIGTERM through the elastic drain handler leaves the
        postmortem (subprocess: the handler owns the main thread)."""
        import subprocess
        import sys

        ev = tmp_path / "drain_events.jsonl"
        script = f"""
import json, os, signal, time
os.environ["HOROVOD_EVENT_LOG"] = {str(ev)!r}
from horovod_tpu import tracing
from horovod_tpu.elastic import runner
runner._install_drain_handler()
tr = tracing.get_tracer()
with tr.step_scope("train_step"):
    pass
os.kill(os.getpid(), signal.SIGTERM)
time.sleep(0.5)
assert runner.drain_requested()
"""
        proc = subprocess.run(
            [sys.executable, "-c", script], timeout=120,
            capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert proc.returncode == 0, proc.stderr[-2000:]
        frs = [e for e in _read_events(ev)
               if e["event"] == "flight_record"]
        assert frs and frs[0]["reason"] == "drain_requested"
        assert frs[0]["steps"]

    def test_ring_depth_covers_last_k_steps(self, tmp_path, monkeypatch):
        """The dump carries exactly the last K steps (the acceptance
        contract: a postmortem of every rank's last K steps)."""
        tracing.reset_for_testing(tracing.StepTracer(ring_steps=4))
        ev = tmp_path / "events.jsonl"
        monkeypatch.setenv("HOROVOD_EVENT_LOG", str(ev))
        self._arm_ring(n=9)
        snap = tracing.dump_flight_record("test_dump")
        assert [s["step"] for s in snap["steps"]] == [6, 7, 8, 9]
        frs = [e for e in _read_events(ev)
               if e["event"] == "flight_record"]
        assert [s["step"] for s in frs[0]["steps"]] == [6, 7, 8, 9]
        monkeypatch.delenv("HOROVOD_EVENT_LOG")
        metrics.journal()


# ---------------------------------------------------------------------------
# Factory-step integration + profiler surface
# ---------------------------------------------------------------------------


class TestFactoryIntegration:
    def test_sampled_step_ships_to_server(self, monkeypatch):
        """A real make_train_step loop with HOROVOD_TRACE_SAMPLE ships
        the sampled (synced) step through the real PUT /trace route and
        shows up on the merged timeline."""
        import numpy as np
        import optax

        import horovod_tpu as hvd

        srv = _server()
        try:
            monkeypatch.setenv("HOROVOD_TRACE_SAMPLE", "2")
            monkeypatch.setenv("HOROVOD_STALL_CHECK_STEPS", "0")
            monkeypatch.setenv("HOROVOD_RENDEZVOUS_ADDR", "127.0.0.1")
            monkeypatch.setenv("HOROVOD_RENDEZVOUS_PORT", str(srv.port))
            monkeypatch.setenv("HOROVOD_HOSTNAME", "factory-host")
            monkeypatch.setenv("HOROVOD_RANK", "0")
            hvd.init()
            tracing.reset_for_testing()

            def loss_fn(params, batch):
                x, y = batch
                return (((x @ params["w"]) - y) ** 2).mean()

            opt = hvd.DistributedOptimizer(optax.sgd(0.1))
            step = hvd.data_parallel.make_train_step(loss_fn, opt)
            params = hvd.data_parallel.replicate(
                {"w": np.ones((4, 1), np.float32)})
            opt_state = hvd.data_parallel.replicate(opt.init(params))
            batch = hvd.data_parallel.shard_batch(
                (np.ones((8, 4), np.float32),
                 np.zeros((8, 1), np.float32)))
            for _ in range(4):
                params, opt_state, _ = step(params, opt_state, batch)
            deadline = time.time() + 15
            while (time.time() < deadline
                   and srv.trace_payload("factory-host") is None):
                time.sleep(0.1)
            payload = srv.trace_payload("factory-host")
            assert payload is not None, "sampled step never shipped"
            synced = [s["step"] for s in payload["steps"] if s["synced"]]
            assert synced and all(s % 2 == 0 for s in synced)
            spans = [e for e in srv.timeline_json()["traceEvents"]
                     if e.get("ph") == "X"]
            # The factory's step span, with the builder's name beside it.
            steps = [e for e in spans if e["name"] == "hvd.step"]
            assert steps and all(
                e["args"]["kind"] == "train_step" for e in steps)
        finally:
            srv.stop()

    def test_profiler_summary_has_stragglers(self):
        summ = __import__("horovod_tpu").profiler.summary()
        st = summ["stragglers"]
        assert "clock_offset_s" in st
        assert "steps_recorded" in st
        assert "trace_sample" in st

    def test_eager_dispatch_records_collective_span(self):
        import numpy as np

        import horovod_tpu as hvd

        hvd.init()
        tracing.reset_for_testing()
        n = hvd.size()
        hvd.allreduce(np.ones((n, 4), np.float32), op=hvd.Sum)
        snap = tracing.get_tracer().ring_snapshot()
        all_spans = [sp for s in snap for sp in s["spans"]]
        assert any(sp["name"] == "allreduce"
                   and sp["cat"] == "collective" for sp in all_spans)


# ---------------------------------------------------------------------------
# The factory step's own spans (hvd.step and its children)
# ---------------------------------------------------------------------------


def _toy_step(donate=True):
    import numpy as np
    import optax

    import horovod_tpu as hvd

    hvd.init()

    def loss_fn(params, batch):
        x, y = batch
        return (((x @ params["w"]) - y) ** 2).mean()

    opt = hvd.DistributedOptimizer(optax.sgd(0.1))
    step = hvd.data_parallel.make_train_step(loss_fn, opt, donate=donate)
    params = hvd.data_parallel.replicate({"w": np.ones((4, 1), np.float32)})
    opt_state = hvd.data_parallel.replicate(opt.init(params))

    def batch(rows=8):
        return hvd.data_parallel.shard_batch(
            (np.ones((rows, 4), np.float32), np.zeros((rows, 1), np.float32)))

    return step, params, opt_state, batch


class TestFactoryStepSpans:
    def test_step_and_children_land_in_the_ring(self, monkeypatch):
        from horovod_tpu import attribution

        monkeypatch.setenv("HOROVOD_STALL_CHECK_STEPS", "3")
        step, params, opt_state, batch = _toy_step()
        for _ in range(3):
            params, opt_state, _ = step(params, opt_state, batch())
        records = tracing.get_tracer().ring_snapshot()
        assert [r["kind"] for r in records] == [attribution.SPAN_STEP] * 3
        for call, record in enumerate(records, start=1):
            envelope, *children = record["spans"]
            assert envelope["name"] == "hvd.step"
            assert envelope["cat"] == "step"
            assert envelope["step"] == record["step"]
            assert "parent" not in envelope
            assert envelope["args"]["kind"] == "train_step"
            assert envelope["args"]["call"] == call
            assert envelope["args"]["synced"] == (call == 3)
            assert all(child["parent"] == envelope["id"]
                       and child["step"] == record["step"]
                       and child["cat"] == "host" for child in children)
            names = [child["name"] for child in children]
            assert names[0] == "hvd.step.dispatch"
            # The watched (third) call drains, and says why.
            assert names[1:] == (["hvd.step.drain"] if call == 3 else [])
        drain = records[-1]["spans"][2]
        assert drain["args"] == {"cause": "stall_watch"}
        # The first call compiled the step: its share rides on the span.
        share = records[0]["spans"][0]["args"]["compile"]
        assert share["programs"] >= 1 and share["trace_s"] > 0
        assert "compile" not in records[1]["spans"][0]["args"]

    def test_self_time_is_duration_less_children(self):
        spans = [
            {"name": "hvd.step", "t": 10.0, "dur": 1.0, "id": 1},
            {"name": "hvd.step.dispatch", "t": 10.1, "dur": 0.6, "id": 2,
             "parent": 1},
            # Overlaps its sibling by 0.1 s: counted once.
            {"name": "hvd.step.drain", "t": 10.6, "dur": 0.3, "id": 3,
             "parent": 1},
            {"name": "inner", "t": 10.2, "dur": 0.25, "id": 4, "parent": 2},
            {"name": "eager", "t": 10.15, "dur": 0.5},  # no id: no family
        ]
        got = tracing.self_times(spans)
        assert got == pytest.approx([0.2, 0.35, 0.3, 0.25, 0.5])

    def test_self_time_of_recorded_spans(self):
        ticks = iter([100.0, 100.1, 100.4, 100.5, 100.7, 101.0])
        tracer = tracing.StepTracer(tracing.ClockSync(lambda: next(ticks)))
        with tracer.step_scope("hvd.step"):       # 100.0 ... 101.0
            with tracer.host_span("a"):            # 100.1 ... 100.4
                pass
            with tracer.host_span("b"):            # 100.5 ... 100.7
                pass
        (record,) = tracer.ring_snapshot()
        assert [s["name"] for s in record["spans"]] == ["hvd.step", "a", "b"]
        assert tracing.self_times(record["spans"]) == pytest.approx(
            [0.5, 0.3, 0.2])

    def test_a_raising_step_still_closes_its_spans(self):
        from horovod_tpu.parallel.data_parallel import _StallWatchedStep

        def explode(x):
            raise RuntimeError("boom")

        wrapped = _StallWatchedStep(explode, "train_step")
        with pytest.raises(RuntimeError, match="boom"):
            wrapped(1)
        tracer = tracing.get_tracer()
        (record,) = tracer.ring_snapshot()
        names = [span["name"] for span in record["spans"]]
        assert names == ["hvd.step", "hvd.step.dispatch",
                         "error:RuntimeError"]
        assert tracer.flight_snapshot()["open_spans"] == []
        assert "current_step" not in tracer.flight_snapshot()
        # Nothing is left open on this thread: the next span has no parent.
        with tracer.host_span("after"):
            pass
        after = tracer.ring_snapshot()[-1]["spans"][-1]
        assert after["name"] == "after" and "parent" not in after

    def test_a_compile_on_a_later_call_is_one_recompile(self, tmp_path,
                                                        monkeypatch):
        import horovod_tpu as hvd

        events = tmp_path / "events.jsonl"
        monkeypatch.setenv("HOROVOD_EVENT_LOG", str(events))
        step, params, opt_state, batch = _toy_step(donate=False)
        for _ in range(3):
            step(params, opt_state, batch())
        assert metrics.STEP_RECOMPILES.labels(step="train_step").get() == 0
        # The account is the process's, by step name: whatever test file
        # this worker ran before may have left recompiles of its own there.
        before = hvd.cache_stats()["compile"]["steps"]["train_step"][
            "recompiles"]
        step(params, opt_state, batch(rows=16))  # a shape it has not seen
        step(params, opt_state, batch(rows=16))
        assert metrics.STEP_RECOMPILES.labels(step="train_step").get() == 1
        account = hvd.cache_stats()["compile"]
        assert account["listening"] and account["programs"] >= 2
        booked = account["steps"]["train_step"]
        assert booked["recompiles"] == before + 1
        assert booked["last_recompile"]["call"] == 4
        assert booked["first_call"]["programs"] >= 1
        (journaled,) = [e for e in _read_events(events)
                        if e["event"] == "step_recompiled"]
        assert journaled["step"] == "train_step" and journaled["call"] == 4
        assert journaled["programs"] >= 1
        fourth = tracing.get_tracer().ring_snapshot()[-2]["spans"][0]
        assert fourth["args"]["compile"]["programs"] >= 1
        monkeypatch.delenv("HOROVOD_EVENT_LOG")
        metrics.journal()

    def test_the_hot_path_reads_no_environment_variable(self, monkeypatch):
        step, params, opt_state, batch = _toy_step()
        one = batch()
        for _ in range(2):  # the first call compiles and reads its fill
            params, opt_state, _ = step(params, opt_state, one)
        read = []
        get = os.environ.get

        def counting(key, default=None):
            read.append(key)
            return get(key, default)

        monkeypatch.setattr(os.environ, "get", counting)
        for _ in range(20):
            params, opt_state, _ = step(params, opt_state, one)
        monkeypatch.undo()
        assert read == []
        assert len(tracing.get_tracer().ring_snapshot()) == 8

    def test_a_synced_step_latches_the_watermark_and_no_other(
            self, monkeypatch):
        from horovod_tpu import memory

        noted = []
        monkeypatch.setattr(memory, "note_phase",
                            lambda name, cat=None: noted.append((name, cat)))
        tracer = tracing.get_tracer()
        with tracer.step_scope("hvd.step"):
            with tracing.span("forward_backward", "phase"):
                pass
        assert noted == []  # un-synced, and a span inside a step
        with tracer.step_scope("hvd.step") as rec:
            rec.synced = True
        assert noted == [("hvd.step", "step")]
        with tracing.span("allreduce", "collective"):
            pass  # outside any step: its own close is the latch
        assert noted[-1] == ("allreduce", "collective")

    def test_spans_are_profiler_annotations_with_their_arguments(
            self, monkeypatch):
        opened = []

        class Recording:
            def __init__(self, name, **kwargs):
                opened.append((name, kwargs))

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(tracing, "_annotation_cls", Recording)
        tracer = tracing.get_tracer()
        with tracer.step_scope("hvd.step", {"kind": "train_step",
                                            "call": 7}):
            with tracer.host_span("hvd.step.drain", {"cause": "stall_watch"}):
                pass
        assert opened == [
            ("hvd.step", {"step": 1, "kind": "train_step", "call": 7}),
            ("hvd.step.drain", {"cause": "stall_watch"})]
