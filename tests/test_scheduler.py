"""Multi-tenant pod scheduler tests (ISSUE 17 acceptance proof).

Three layers, mirroring the subsystem's architecture:

- pool-tier and arbitration units under fake clocks: pool-wide
  condemnation evidence surviving a job handoff, cooldown expiry
  re-entering hosts as pool spares, priority-ordered victim selection
  with hysteresis (no A<->B thrash between two starving jobs), the
  three new fault points, and the multi-tenant observability surface
  (``/metrics`` zero-materialization, ``GET /pool``, the journal's
  ``job`` field, the job-aware log prefix);
- single-job inertness: with no scheduler and ``HOROVOD_JOB_ID`` unset,
  the log prefix, the endpoint record, and the journal schema are
  bit-for-bit those of HEAD;
- the chaos e2e with REAL processes — one scheduler, two elastic
  drivers, torch workers on a shared localhost pool: (a) SIGKILL a
  host's worker in job A and prove the pool spare heals A at its next
  generation fence with an exact loss trajectory while job B never sees
  an event; (b) SLO pressure on the high-priority job shrinks the
  low-priority job by one host through the drain -> final-commit ->
  reassign sequence, with exactly one ``sched_decision`` journal event
  per executed action carrying predicted + realized goodput.
"""

import json
import os
import signal
import sys
import textwrap
import threading
import time
import types
import urllib.request

import pytest

from horovod_tpu import faults
from horovod_tpu import metrics as hvd_metrics
from horovod_tpu.elastic.policy import JobArbiter
from horovod_tpu.runner.elastic.scheduler import (
    HostPool,
    JobSpec,
    MultiJobScheduler,
    SCHED_ACTIONS,
)
from horovod_tpu.utils.logging import rank_prefix

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(autouse=True)
def _no_job_env(monkeypatch):
    monkeypatch.delenv("HOROVOD_JOB_ID", raising=False)


# ---------------------------------------------------------------------------
# Pool tier
# ---------------------------------------------------------------------------


class TestHostPool:
    def _pool(self, monkeypatch, clock, cooldown="600"):
        monkeypatch.setenv("HOROVOD_SCHED_BLACKLIST_COOLDOWN", cooldown)
        return HostPool(["h1", "h2", "h3"], clock=lambda: clock[0])

    def test_condemnation_evidence_survives_job_handoff(self, monkeypatch):
        """A host condemned by job A carries A's evidence in the pool
        record and is never handed to job B inside the cooldown."""
        clock = [0.0]
        pool = self._pool(monkeypatch, clock)
        assert pool.assign("h1", "jobA")
        pool.condemn("h1", "jobA", "worker failed with rc=-9")
        # The evidence rides the pool record, attributed to the
        # condemning job.
        rec = pool.condemned_record("h1")
        assert rec["job"] == "jobA"
        assert rec["reason"] == "worker failed with rc=-9"
        # Inside the cooldown: invisible to spares, unassignable to B.
        clock[0] = 599.0
        assert pool.prune() == []
        assert "h1" not in pool.spares()
        assert not pool.assign("h1", "jobB")
        assert pool.counts()["blacklisted"] == 1

    def test_cooldown_expiry_reenters_as_pool_spare(self, monkeypatch):
        clock = [0.0]
        pool = self._pool(monkeypatch, clock)
        pool.condemn("h2", "jobA", "drain: straggler")
        clock[0] = 600.5
        assert pool.prune() == ["h2"]
        assert "h2" in pool.spares()
        assert pool.assign("h2", "jobB")          # any job may take it
        assert pool.condemned_record("h2") is None

    def test_zero_cooldown_is_permanent(self, monkeypatch):
        clock = [0.0]
        pool = self._pool(monkeypatch, clock, cooldown="0")
        pool.condemn("h1", "jobA", "bad")
        clock[0] = 1e9
        assert pool.prune() == []
        assert "h1" not in pool.spares()

    def test_release_is_immediate_spare_reentry(self, monkeypatch):
        """A surplus host from a shrunk job re-enters WITHOUT evidence:
        it is a spare any job can promote, with no cooldown."""
        clock = [0.0]
        pool = self._pool(monkeypatch, clock)
        assert pool.assign("h3", "jobA")
        assert "h3" not in pool.spares()
        pool.release("h3")
        assert "h3" in pool.spares()
        assert pool.assign("h3", "jobB")

    def test_assign_refuses_taken_and_unknown_hosts(self, monkeypatch):
        clock = [0.0]
        pool = self._pool(monkeypatch, clock)
        assert pool.assign("h1", "jobA")
        assert not pool.assign("h1", "jobB")      # disjointness
        assert not pool.assign("nope", "jobB")

    def test_pool_assign_fault_point(self, monkeypatch):
        """faults: pool.assign drop mode holds the host back (returns
        False); the pool record is untouched."""
        clock = [0.0]
        pool = self._pool(monkeypatch, clock)
        faults.inject(faults.POOL_ASSIGN, "drop", at=1, count=1)
        assert not pool.assign("h1", "jobA")
        assert faults.fired(faults.POOL_ASSIGN) == 1
        assert "h1" in pool.spares()              # held back, not burned
        assert pool.assign("h1", "jobA")          # next tick succeeds

    def test_export_carries_relative_evidence_ages(self, monkeypatch):
        clock = [0.0]
        pool = self._pool(monkeypatch, clock)
        pool.condemn("h2", "jobA", "bad link")
        clock[0] = 12.5
        by_name = {h["host"]: h for h in pool.export()}
        assert by_name["h2"]["condemned"]["age_s"] == pytest.approx(12.5)
        assert by_name["h2"]["condemned"]["job"] == "jobA"
        assert by_name["h1"]["condemned"] is None

    def test_host_slots_parse(self):
        pool = HostPool(["h1:4", "h2"])
        assert pool.slots_of("h1") == 4
        assert pool.slots_of("h2") == 1


# ---------------------------------------------------------------------------
# Cross-job arbitration
# ---------------------------------------------------------------------------


def _arbiter(monkeypatch, clock, hysteresis="10", cooldown="30",
             pin=None):
    monkeypatch.setenv("HOROVOD_SCHED_HYSTERESIS", hysteresis)
    monkeypatch.setenv("HOROVOD_SCHED_COOLDOWN", cooldown)
    if pin is not None:
        monkeypatch.setenv("HOROVOD_SCHED_PIN_COOLDOWN", pin)
    return JobArbiter(clock=lambda: clock[0])


class TestJobArbiter:
    def test_hysteresis_gates_sustained_starvation(self, monkeypatch):
        clock = [0.0]
        a = _arbiter(monkeypatch, clock)
        a.note_job("hi", 1, 2, 4, priority=10, target=0.9)
        a.note_job("lo", 3, 1, 4, priority=1, target=0.5)
        assert a.decide(0) is None                # not sustained yet
        clock[0] = 9.0
        a.note_job("hi", 1, 2, 4, priority=10, target=0.9)
        assert a.decide(0) is None
        clock[0] = 10.5
        a.note_job("hi", 1, 2, 4, priority=10, target=0.9)
        d = a.decide(0)
        assert d is not None and d.action == "shrink"
        assert d.victim == "lo" and d.recipient == "hi"
        assert d.predicted["recipient"]["goodput_after"] == 0.5

    def test_recovery_resets_the_hysteresis_clock(self, monkeypatch):
        clock = [0.0]
        a = _arbiter(monkeypatch, clock)
        a.note_job("hi", 1, 2, 4, priority=10, target=0.9)
        clock[0] = 8.0
        a.note_job("hi", 4, 2, 4, priority=10, target=0.9)  # healed...
        clock[0] = 9.0
        a.note_job("hi", 1, 2, 4, priority=10, target=0.9)  # ...starves
        a.note_job("lo", 3, 1, 4, priority=1, target=0.5)
        clock[0] = 12.0
        assert a.decide(0) is None        # fresh clock: 3s < 10s

    def test_pool_spare_preempts_arbitration(self, monkeypatch):
        """With a promotable spare the pool heals — no victim needed."""
        clock = [0.0]
        a = _arbiter(monkeypatch, clock)
        a.note_job("hi", 1, 2, 4, priority=10, target=0.9)
        a.note_job("lo", 3, 1, 4, priority=1, target=0.5)
        clock[0] = 20.0
        a.note_job("hi", 1, 2, 4, priority=10, target=0.9)
        assert a.decide(1) is None
        assert a.decide(0) is not None

    def test_victim_order_priority_then_surplus(self, monkeypatch):
        """Victims in priority order (lowest first), then furthest over
        SLO — the ISSUE's 'furthest OVER its SLO by priority order'."""
        clock = [0.0]
        a = _arbiter(monkeypatch, clock)
        a.note_job("hi", 1, 2, 6, priority=10, target=0.9)
        a.note_job("mid", 4, 1, 4, priority=5, target=0.5)   # over SLO
        a.note_job("lo", 4, 1, 4, priority=1, target=0.9)    # over SLO
        clock[0] = 20.0
        a.note_job("hi", 1, 2, 6, priority=10, target=0.9)
        d = a.decide(0)
        assert d.victim == "lo"           # lowest priority yields first

    def test_no_thrash_between_two_starving_equals(self, monkeypatch):
        """Two equal-priority starving jobs must never trade hosts: a
        job under its own SLO only yields to a strictly higher-priority
        recipient, so neither qualifies as the other's victim."""
        clock = [0.0]
        a = _arbiter(monkeypatch, clock)
        for t in (0.0, 15.0, 30.0, 60.0, 120.0):
            clock[0] = t
            a.note_job("a", 2, 1, 4, priority=5, target=0.9)
            a.note_job("b", 2, 1, 4, priority=5, target=0.9)
            assert a.decide(0) is None

    def test_shrink_respects_min_np_else_preempts_lower_priority(
            self, monkeypatch):
        clock = [0.0]
        a = _arbiter(monkeypatch, clock)
        a.note_job("hi", 1, 2, 4, priority=10, target=0.9)
        a.note_job("lo", 2, 2, 4, priority=1, target=0.5)
        clock[0] = 20.0
        a.note_job("hi", 1, 2, 4, priority=10, target=0.9)
        d = a.decide(0)
        assert d.action == "preempt"      # 2-1 < min_np=2: full preempt
        assert d.victim == "lo"
        assert d.predicted["victim"]["goodput_after"] == 0.0

    def test_priority_monotonicity_is_structural(self, monkeypatch):
        """Hosts only flow UP the priority gradient: a starving
        low-priority job never victimizes a higher-priority job, even
        one comfortably over its own SLO — transfer cycles are
        impossible by construction, not merely throttled."""
        clock = [0.0]
        a = _arbiter(monkeypatch, clock)
        a.note_job("hi", 4, 2, 6, priority=10, target=0.65)  # satisfied
        a.note_job("lo", 1, 1, 2, priority=1, target=0.9)    # starving
        clock[0] = 60.0
        a.note_job("lo", 1, 1, 2, priority=1, target=0.9)
        assert a.decide(0) is None        # sustained, but no victim

    def test_action_cooldown_and_recipient_pin(self, monkeypatch):
        """After an executed action: the cooldown throttles the next
        pass, and the healed recipient is pinned against being
        re-victimized by a still-higher-priority job for one pin
        window — the second layer of the anti-thrash contract."""
        clock = [0.0]
        a = _arbiter(monkeypatch, clock, hysteresis="10", cooldown="30",
                     pin="1000")
        a.note_job("mid", 1, 1, 4, priority=5, target=0.9)
        a.note_job("lo", 4, 1, 4, priority=1, target=0.5)
        clock[0] = 15.0
        a.note_job("mid", 1, 1, 4, priority=5, target=0.9)
        d = a.decide(0)
        assert d is not None and d.victim == "lo"
        assert d.recipient == "mid"
        a.record_action(d)                # pins 'mid', arms cooldown
        clock[0] = 20.0                   # inside the 30s cooldown
        assert a.decide(0) is None
        a.forget_job("lo")
        clock[0] = 50.0                   # cooldown over; 'top' starves
        a.note_job("top", 1, 2, 4, priority=10, target=0.9)
        a.note_job("mid", 2, 1, 4, priority=5, target=0.9)
        clock[0] = 61.0
        a.note_job("top", 1, 2, 4, priority=10, target=0.9)
        # 'mid' (priority 5 < 10) is the only candidate, but it just
        # received the transfer: pinned — no immediate claw-back.
        assert a.decide(0) is None
        clock[0] = 1020.0                 # pin window over
        a.note_job("top", 1, 2, 4, priority=10, target=0.9)
        d = a.decide(0)
        assert d is not None and d.victim == "mid"

    def test_sched_decide_fault_point(self, monkeypatch):
        """faults: sched.decide drop mode skips the arbitration pass."""
        clock = [0.0]
        a = _arbiter(monkeypatch, clock)
        a.note_job("hi", 1, 2, 4, priority=10, target=0.9)
        a.note_job("lo", 3, 1, 4, priority=1, target=0.5)
        clock[0] = 20.0
        a.note_job("hi", 1, 2, 4, priority=10, target=0.9)
        faults.inject(faults.SCHED_DECIDE, "drop", at=1, count=1)
        assert a.decide(0) is None
        assert faults.fired(faults.SCHED_DECIDE) == 1
        assert a.decide(0) is not None    # next pass decides

    def test_new_fault_points_parse_from_env_grammar(self):
        """The scheduler-plane injection points ride the standard
        HOROVOD_FAULTS grammar (point=mode[:arg]@N[xC])."""
        from horovod_tpu.faults import parse_spec

        specs = parse_spec(
            "sched.decide=drop@1; job.preempt=raise@2x3; "
            "pool.assign=delay:0.5@1")
        by = {s.point: s for s in specs}
        assert by[faults.SCHED_DECIDE].mode == "drop"
        assert by[faults.JOB_PREEMPT].mode == "raise"
        assert by[faults.JOB_PREEMPT].at == 2
        assert by[faults.JOB_PREEMPT].count == 3
        assert by[faults.POOL_ASSIGN].mode == "delay"


# ---------------------------------------------------------------------------
# Scheduler units (no subprocesses)
# ---------------------------------------------------------------------------


def _specs():
    return [
        JobSpec(job_id="alpha", command=["true"], min_np=2, max_np=4,
                priority=10, target_goodput=0.9),
        JobSpec(job_id="beta", command=["true"], min_np=1, max_np=2,
                priority=1),
    ]


class TestSchedulerUnits:
    def test_shrink_blacklist_is_drain_completion_not_evidence(
            self, tmp_path):
        """The victim driver blacklists the host the scheduler itself is
        draining (the preempt-notice path): that event advances the
        in-flight shrink — it must NOT condemn the healthy host."""
        sched = MultiJobScheduler(_specs(), ["h1", "h2", "h3"],
                                  str(tmp_path))
        beta = sched._jobs["beta"]
        beta.state = "running"
        beta.lease = ["h2"]
        sched._pool.assign("h2", "beta")
        sched._pending.append({
            "action": "shrink", "job": "alpha", "victim": "beta",
            "host": "h2", "stage": "drain", "reason": "r",
            "predicted": {}, "deadline": 1e18})
        sched._handle_job_event(beta, {
            "event": "blacklist", "host": "h2",
            "reason": "preempt: external preemption notice"})
        assert sched._pending[0]["stage"] == "reassign"
        assert sched._pool.condemned_record("h2") is None

    def test_worker_crash_blacklist_condemns_pool_wide(self, tmp_path):
        sched = MultiJobScheduler(_specs(), ["h1", "h2", "h3"],
                                  str(tmp_path))
        alpha = sched._jobs["alpha"]
        alpha.state = "running"
        alpha.lease = ["h1", "h2"]
        sched._pool.assign("h1", "alpha")
        sched._pool.assign("h2", "alpha")
        sched._handle_job_event(alpha, {
            "event": "blacklist", "host": "h2",
            "reason": "worker failed with rc=-9"})
        rec = sched._pool.condemned_record("h2")
        assert rec["job"] == "alpha"
        assert "rc=-9" in rec["reason"]
        assert alpha.lease == ["h1"]      # lease rewritten without it
        assert not sched._pool.assign("h2", "beta")

    def test_job_preempt_fault_point_holds_the_sigterm(self, tmp_path):
        from horovod_tpu.elastic.policy import ArbiterDecision

        sched = MultiJobScheduler(_specs(), ["h1", "h2"], str(tmp_path))
        beta = sched._jobs["beta"]
        beta.state = "running"
        signals = []
        beta.proc = types.SimpleNamespace(
            send_signal=signals.append, poll=lambda: None)
        d = ArbiterDecision(action="preempt", victim="beta",
                            recipient="alpha", reason="r", predicted={})
        faults.inject(faults.JOB_PREEMPT, "drop", at=1, count=1)
        sched._actuate_preempt(d)
        assert signals == [] and beta.state == "running"
        sched._actuate_preempt(d)         # injector exhausted: executes
        assert signals == [signal.SIGTERM]
        assert beta.state == "preempting"

    def test_metrics_and_pool_endpoints(self, tmp_path):
        """The observability surface, served over real HTTP: the pool
        and job gauges plus the decision counter zero-materialized on
        /metrics, and GET /pool carrying >= 2 job entries with
        world/goodput/SLO state."""
        sched = MultiJobScheduler(_specs(), ["h1", "h2", "h3"],
                                  str(tmp_path))
        sched._start_http()
        try:
            base = f"http://127.0.0.1:{sched.port}"
            text = urllib.request.urlopen(
                f"{base}/metrics", timeout=10).read().decode()
            parsed = hvd_metrics.validate_prometheus_text(text)
            assert parsed["hvd_pool_hosts"]["samples"] == [({}, 3.0)]
            assert parsed["hvd_pool_spares"]["samples"] == [({}, 3.0)]
            assert parsed["hvd_pool_blacklisted"]["samples"] == [
                ({}, 0.0)]
            assert parsed["hvd_jobs_running"]["samples"] == [({}, 0.0)]
            assert parsed["hvd_jobs_preempted_total"]["samples"] == [
                ({}, 0.0)]
            actions = {l["action"]: v for l, v in
                       parsed["hvd_sched_decisions_total"]["samples"]}
            assert actions == {a: 0.0 for a in SCHED_ACTIONS}
            pool = json.loads(urllib.request.urlopen(
                f"{base}/pool", timeout=10).read().decode())
            assert len(pool["jobs"]) == 2
            for entry in pool["jobs"].values():
                assert {"state", "priority", "min_np", "max_np",
                        "target_goodput", "lease"} <= set(entry)
            assert pool["jobs"]["alpha"]["target_goodput"] == 0.9
            assert pool["jobs"]["alpha"]["state"] == "pending"
            assert len(pool["hosts"]) == 3
            assert pool["spares"] == ["h1", "h2", "h3"]
        finally:
            sched._httpd.shutdown()
            sched._httpd.server_close()

    def test_duplicate_job_id_rejected(self, tmp_path):
        with pytest.raises(ValueError):
            MultiJobScheduler(
                [JobSpec(job_id="x", command=["true"], min_np=1,
                         max_np=1)] * 2, ["h1"], str(tmp_path))


# ---------------------------------------------------------------------------
# Single-job inertness + the job dimension (satellites 1 and 6)
# ---------------------------------------------------------------------------


class TestJobDimension:
    def test_log_prefix_unchanged_without_job(self, monkeypatch):
        """HEAD's exact prefix forms when HOROVOD_JOB_ID is unset."""
        for var in ("HOROVOD_JOB_ID", "HOROVOD_RANK", "HOROVOD_SIZE",
                    "HOROVOD_ELASTIC", "HOROVOD_WORLD_VERSION"):
            monkeypatch.delenv(var, raising=False)
        assert rank_prefix() == ""
        monkeypatch.setenv("HOROVOD_RANK", "1")
        monkeypatch.setenv("HOROVOD_SIZE", "4")
        assert rank_prefix() == "[1/4] "
        monkeypatch.setenv("HOROVOD_ELASTIC", "1")
        monkeypatch.setenv("HOROVOD_WORLD_VERSION", "3")
        assert rank_prefix() == "[1/4 g3] "

    def test_log_prefix_gains_job_dimension(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_JOB_ID", "trainA")
        monkeypatch.delenv("HOROVOD_RANK", raising=False)
        assert rank_prefix() == "[trainA] "          # driver-side form
        monkeypatch.setenv("HOROVOD_RANK", "0")
        monkeypatch.setenv("HOROVOD_SIZE", "2")
        monkeypatch.setenv("HOROVOD_ELASTIC", "1")
        monkeypatch.setenv("HOROVOD_WORLD_VERSION", "5")
        assert rank_prefix() == "[trainA/0/2 g5] "

    def test_journal_job_field_null_then_stamped(self, tmp_path,
                                                 monkeypatch):
        """Every journal record carries ``job``: null outside a
        scheduled job (the documented single-job schema), the env job id
        inside one — re-read per record, and an explicit ``job=`` field
        (the scheduler's own events) wins."""
        jpath = tmp_path / "events.jsonl"
        monkeypatch.setenv("HOROVOD_EVENT_LOG", str(jpath))
        hvd_metrics.event("unit_a")
        monkeypatch.setenv("HOROVOD_JOB_ID", "jobZ")
        hvd_metrics.event("unit_b")
        hvd_metrics.event("unit_c", job="explicit")
        recs = [json.loads(l) for l in
                jpath.read_text().splitlines()]
        by = {r["event"]: r for r in recs}
        assert by["unit_a"]["job"] is None
        assert by["unit_b"]["job"] == "jobZ"
        assert by["unit_c"]["job"] == "explicit"

    def test_endpoint_record_byte_identical_without_job(
            self, tmp_path, monkeypatch):
        from horovod_tpu.runner.elastic.driver_state import (
            DriverStateStore, read_endpoint)

        store = DriverStateStore(str(tmp_path), epoch=1)
        store.publish_endpoint("127.0.0.1", 1234, generation=2)
        rec = read_endpoint(str(tmp_path))
        assert set(rec) == {"addr", "port", "driver_epoch", "generation"}
        monkeypatch.setenv("HOROVOD_JOB_ID", "jobQ")
        store.publish_endpoint("127.0.0.1", 1234, generation=3)
        rec = read_endpoint(str(tmp_path))
        assert rec["job"] == "jobQ"


# ---------------------------------------------------------------------------
# Chaos e2e: real scheduler, real drivers, real workers, shared pool
# ---------------------------------------------------------------------------

POOL = ["127.0.0.2", "127.0.0.3", "127.0.0.4", "127.0.0.5", "127.0.0.6"]


def _elastic_worker(tmp_path) -> str:
    """Elastic torch SGD worker (the test_policy harness shape): exact
    per-(epoch, rank) seeded batches so a 2-rank trajectory has a closed
    -form oracle; writes a pidfile per (job, host) so the test can
    SIGKILL a specific host's worker; an allreduced stop-file check so
    open-ended jobs end on the SAME epoch on every rank."""
    path = tmp_path / "elastic_worker.py"
    path.write_text(textwrap.dedent(f"""
        import os, sys, time
        sys.path.insert(0, {REPO_ROOT!r})
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax
        jax.config.update("jax_platforms", "cpu")
        from horovod_tpu._jax_compat import force_cpu_devices
        force_cpu_devices(1)
        import numpy as np
        import torch
        import horovod_tpu.torch as hvd
        from horovod_tpu.elastic import run as elastic_run
        from horovod_tpu.torch.elastic import TorchState

        host = os.environ["HOROVOD_HOSTNAME"]
        job = os.environ["HOROVOD_JOB_ID"]
        piddir = os.environ["TEST_PID_DIR"]
        with open(os.path.join(piddir, f"pid.{{job}}.{{host}}"),
                  "w") as f:
            f.write(str(os.getpid()))
        EPOCHS = int(os.environ["TEST_EPOCHS"])
        STOP_FILE = os.environ.get("TEST_STOP_FILE", "")
        STEP_SLEEP = float(os.environ["TEST_STEP_SLEEP"])

        torch.manual_seed(0)
        model = torch.nn.Linear(4, 1, bias=False)
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.05),
            named_parameters=model.named_parameters())
        state = TorchState(model=model, optimizer=opt, epoch=0)

        @elastic_run
        def train(state):
            while state.epoch < EPOCHS:
                if STOP_FILE:
                    # Allreduced so every rank stops at the SAME epoch.
                    flag = torch.tensor(
                        [1.0 if os.path.exists(STOP_FILE) else 0.0])
                    if float(hvd.allreduce(flag, name="stop")) > 0:
                        break
                time.sleep(STEP_SLEEP)
                r = hvd.rank()
                x = torch.from_numpy(np.random.RandomState(
                    100 * state.epoch + r).randn(8, 4).astype(
                        np.float32))
                opt.zero_grad()
                loss = (model(x) ** 2).mean()
                loss.backward()
                opt.step()
                print("rank=%d host=%s epoch=%d np=%d loss=%.6f" % (
                    r, host, state.epoch, hvd.size(), float(loss)),
                    flush=True)
                state.epoch += 1
                state.commit()
            return state.epoch

        done = train(state)
        print("host=%s finished at epoch %d" % (host, done), flush=True)
    """))
    return str(path)


def _expected_losses(epochs: int) -> dict:
    """The exact 2-rank averaged-SGD loss schedule (host-independent)."""
    import numpy as np
    import torch

    torch.manual_seed(0)
    m = torch.nn.Linear(4, 1, bias=False)
    sgd = torch.optim.SGD(m.parameters(), lr=0.05)
    expected = {}
    for e in range(epochs):
        grads = []
        for r in range(2):
            x = torch.from_numpy(np.random.RandomState(
                100 * e + r).randn(8, 4).astype(np.float32))
            sgd.zero_grad()
            loss = (m(x) ** 2).mean()
            expected[(e, r)] = float(loss.detach())
            loss.backward()
            grads.append([p.grad.clone() for p in m.parameters()])
        with torch.no_grad():
            for p, g0, g1 in zip(m.parameters(), *grads):
                p.grad = (g0 + g1) / 2
        sgd.step()
    return expected


def _assert_loss_continuity(text: str, epochs: int):
    import re

    expected = _expected_losses(epochs)
    seen = set()
    # finditer over the whole text: the drivers' stdout relay can very
    # occasionally land two workers' lines on one physical line.
    for m in re.finditer(
            r"rank=(\d+) host=\S+ epoch=(\d+) np=2 loss=([0-9.]+)", text):
        r, e, got = int(m.group(1)), int(m.group(2)), float(m.group(3))
        assert abs(got - expected[(e, r)]) < 1e-4, (
            e, r, got, expected[(e, r)])
        seen.add((e, r))
    missing = {(e, r) for e in range(epochs) for r in (0, 1)} - seen
    assert not missing, sorted(missing)[:10]


def _job_records(path: str) -> list[dict]:
    records = []
    if os.path.exists(path):
        for line in open(path, encoding="utf-8"):
            try:
                records.append(json.loads(line))
            except ValueError:
                pass
    return records


def _sched_env(monkeypatch, tmp_path):
    monkeypatch.setenv("HOROVOD_EVENT_LOG",
                       str(tmp_path / "sched_events.jsonl"))
    monkeypatch.setenv("HOROVOD_SCHED_TICK", "0.25")
    monkeypatch.setenv("HOROVOD_ELASTIC_HEARTBEAT_INTERVAL", "0.25")
    monkeypatch.setenv("HOROVOD_ELASTIC_HEARTBEAT_TIMEOUT", "30")
    monkeypatch.setenv("HOROVOD_BLACKLIST_COOLDOWN", "600")
    # Wide enough that a cold-starting promoted worker's first native
    # attempt overlaps the surviving rank's accept window even when the
    # box is busy (a 6s window can phase-lock-miss under load).
    monkeypatch.setenv("HOROVOD_NATIVE_INIT_TIMEOUT", "15")
    monkeypatch.setenv("HOROVOD_SCHED_REALIZE_TIMEOUT", "90")


def _run_sched_in_thread(sched):
    result = {}

    def go():
        result["rc"] = sched.run()

    t = threading.Thread(target=go, name="sched-run", daemon=True)
    t.start()
    return t, result


def _wait(predicate, timeout, what):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.2)
    pytest.fail(f"timed out waiting for {what}")


@pytest.mark.slow
class TestMultiTenantPodE2E:
    def test_host_kill_heals_from_pool_spare_other_job_untouched(
            self, tmp_path, monkeypatch):
        """Scenario (a): two gangs on a shared pool, one spare. SIGKILL
        the worker on one of job A's hosts: A's driver blacklists it,
        the scheduler condemns it POOL-WIDE (evidence carried) and
        promotes the pool spare into A's lease; A republishes at g+1
        with the spare, its loss trajectory stays exact against the
        uninterrupted 2-rank oracle, and job B never observes an
        event."""
        pytest.importorskip("torch")
        epochs = 120
        _sched_env(monkeypatch, tmp_path)
        monkeypatch.setenv("TEST_PID_DIR", str(tmp_path))
        worker = _elastic_worker(tmp_path)
        common = dict(
            command=[sys.executable, worker], min_np=2, max_np=2,
            cpu_mode=True, elastic_timeout=90.0,
            env={"TEST_PID_DIR": str(tmp_path),
                 "TEST_EPOCHS": str(epochs),
                 "TEST_STEP_SLEEP": "0.05"})
        sched = MultiJobScheduler(
            [JobSpec(job_id="aaa", priority=5, **common),
             JobSpec(job_id="bbb", priority=5, **common)],
            POOL, str(tmp_path / "pod"))
        thread, result = _run_sched_in_thread(sched)

        # Both gangs formed: every leased host's worker wrote a pidfile.
        _wait(lambda: sched._jobs["aaa"].world is not None
              and sched._jobs["bbb"].world is not None,
              90, "both jobs to publish a world")
        lease_a = list(sched._jobs["aaa"].lease)
        doomed = lease_a[1]
        pidfile = tmp_path / f"pid.aaa.{doomed}"
        _wait(pidfile.exists, 60, "the doomed worker's pidfile")
        spare_before = sched._pool.spares()
        assert len(spare_before) == 1
        time.sleep(1.0)                    # let a few epochs land
        os.kill(int(pidfile.read_text()), signal.SIGKILL)

        thread.join(timeout=240)
        assert not thread.is_alive(), "scheduler never finished"
        assert result["rc"] == 0

        pod = tmp_path / "pod"
        sched_records = _job_records(str(tmp_path / "sched_events.jsonl"))
        a_records = _job_records(str(pod / "aaa" / "events.jsonl"))
        b_records = _job_records(str(pod / "bbb" / "events.jsonl"))
        a_log = (pod / "aaa" / "driver.log").read_text(errors="replace")
        b_log = (pod / "bbb" / "driver.log").read_text(errors="replace")

        # A's driver condemned the host; the evidence reached the pool.
        blk = [r for r in a_records if r["event"] == "blacklist"
               and r["host"] == doomed]
        assert blk, a_records
        # The coordinated abort fired in A (the survivors' recovery
        # trigger) — never in B.
        assert any(r["event"] == "abort_posted" for r in a_records)
        assert all(r["job"] == "aaa" for r in a_records), \
            [r for r in a_records if r["job"] != "aaa"][:3]
        cond = [r for r in sched_records if r["event"] == "sched_pool"
                and r.get("change") == "condemned"]
        assert len(cond) == 1 and cond[0]["host"] == doomed
        assert cond[0]["job"] == "aaa"
        rec = sched._pool.condemned_record(doomed)
        assert rec is not None and rec["job"] == "aaa", rec

        # The pool spare healed A at its next generation fence: exactly
        # one promote decision, realized in A's republished world.
        promotes = [r for r in sched_records
                    if r["event"] == "sched_decision"
                    and r["action"] == "promote"]
        assert len(promotes) == 1, sched_records
        assert promotes[0]["host"] == spare_before[0]
        assert promotes[0]["job"] == "aaa"
        assert promotes[0]["realized"] is not None, promotes
        worlds_a = [r for r in a_records
                    if r["event"] == "world_published"]
        assert len(worlds_a) >= 2
        assert spare_before[0] in worlds_a[-1]["hosts"]
        assert all(w["np"] == 2 for w in worlds_a), worlds_a

        # No arbitration was needed: the pool healed it.
        actions = {r["action"] for r in sched_records
                   if r["event"] == "sched_decision"}
        assert actions == {"grant", "promote"}, actions
        grants = [r for r in sched_records
                  if r["event"] == "sched_decision"
                  and r["action"] == "grant"]
        assert len(grants) == 2

        # Job B: one world, zero elastic events, untouched trajectory.
        worlds_b = [r for r in b_records
                    if r["event"] == "world_published"]
        assert len(worlds_b) == 1, worlds_b
        assert not any(r["event"] in ("blacklist", "abort_posted",
                                      "policy_drain", "recovery")
                       for r in b_records), b_records
        assert set(worlds_b[0]["hosts"]).isdisjoint(
            set(worlds_a[-1]["hosts"]))

        # Loss exactness for BOTH jobs against the uninterrupted oracle
        # (A replayed across the re-form; B never re-formed).
        _assert_loss_continuity(a_log, epochs)
        _assert_loss_continuity(b_log, epochs)

    def test_slo_pressure_shrinks_low_priority_job(self, tmp_path,
                                                   monkeypatch):
        """Scenario (b): both jobs under SLO pressure on a full pool.
        The arbiter shrinks the LOW-priority job by one host through
        the drain -> final-commit -> reassign sequence; the
        high-priority job heals at its next fence; exactly one
        ``sched_decision`` journal event per executed action, each with
        predicted + realized goodput; both jobs then run to a clean
        rc=0."""
        pytest.importorskip("torch")
        _sched_env(monkeypatch, tmp_path)
        monkeypatch.setenv("HOROVOD_SCHED_HYSTERESIS", "2")
        monkeypatch.setenv("HOROVOD_SCHED_COOLDOWN", "8")
        stop_file = tmp_path / "stop"
        worker = _elastic_worker(tmp_path)
        common = dict(
            command=[sys.executable, worker], cpu_mode=True,
            elastic_timeout=90.0,
            env={"TEST_PID_DIR": str(tmp_path),
                 "TEST_EPOCHS": "100000",
                 "TEST_STOP_FILE": str(stop_file),
                 "TEST_STEP_SLEEP": "0.1"})
        sched = MultiJobScheduler(
            [JobSpec(job_id="hi", priority=10, min_np=2, max_np=6,
                     target_goodput=0.65, **common),
             JobSpec(job_id="lo", priority=1, min_np=1, max_np=2,
                     target_goodput=0.9, **common)],
            POOL, str(tmp_path / "pod"))
        thread, result = _run_sched_in_thread(sched)

        # The shrink realizes: 'lo' yields one host, 'hi' adopts it.
        def shrink_realized():
            recs = _job_records(str(tmp_path / "sched_events.jsonl"))
            return any(r["event"] == "sched_decision"
                       and r["action"] == "shrink"
                       for r in recs)

        _wait(shrink_realized, 180, "the shrink decision to realize")
        stop_file.write_text("now")
        thread.join(timeout=240)
        assert not thread.is_alive(), "scheduler never finished"
        assert result["rc"] == 0

        pod = tmp_path / "pod"
        sched_records = _job_records(str(tmp_path / "sched_events.jsonl"))
        lo_records = _job_records(str(pod / "lo" / "events.jsonl"))
        hi_records = _job_records(str(pod / "hi" / "events.jsonl"))

        decisions = [r for r in sched_records
                     if r["event"] == "sched_decision"]
        by_action = {}
        for r in decisions:
            by_action.setdefault(r["action"], []).append(r)
        # Exactly one sched_decision per executed action: two gang
        # grants, two spare promotions (the initial fill), one shrink.
        assert len(by_action["grant"]) == 2
        assert len(by_action["shrink"]) == 1, decisions
        assert "preempt" not in by_action, decisions
        for r in decisions:
            assert r["predicted"] is not None, r
            assert r["realized"] is not None, r

        shrink = by_action["shrink"][0]
        assert shrink["victim"] == "lo" and shrink["job"] == "hi"
        pred = shrink["predicted"]
        assert pred["recipient"]["goodput_after"] > \
            pred["recipient"]["goodput_before"]
        assert shrink["realized"]["victim_goodput"] < \
            pred["victim"]["goodput_before"]
        moved = shrink["host"]

        # The victim drained the host through the final-commit preempt
        # path (the driver's policy_drain with action=preempt), then
        # republished at its own g+1 without it — never below min_np.
        drains = [r for r in lo_records if r["event"] == "policy_drain"]
        assert len(drains) == 1 and drains[0]["host"] == moved
        assert drains[0]["action"] == "preempt"
        lo_worlds = [r for r in lo_records
                     if r["event"] == "world_published"]
        assert lo_worlds[-1]["np"] >= 1
        assert moved not in lo_worlds[-1]["hosts"]

        # The recipient adopted the SAME host at its next fence.
        hi_worlds = [r for r in hi_records
                     if r["event"] == "world_published"]
        assert moved in hi_worlds[-1]["hosts"], hi_worlds
        assert hi_worlds[-1]["np"] > hi_worlds[0]["np"]

        # Journal job dimension: every job-journal record is stamped.
        assert all(r["job"] == "lo" for r in lo_records)
        assert all(r["job"] == "hi" for r in hi_records)

        # The scheduler's scrape reflects the executed decisions.
        text = sched.metrics_text()
        parsed = hvd_metrics.validate_prometheus_text(text)
        actions = {l["action"]: v for l, v in
                   parsed["hvd_sched_decisions_total"]["samples"]}
        assert actions["shrink"] == 1.0
        assert actions["grant"] == 2.0
        assert actions["preempt"] == 0.0
        assert parsed["hvd_jobs_preempted_total"]["samples"] == [
            ({}, 0.0)]
