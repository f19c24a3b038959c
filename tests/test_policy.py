"""Self-healing policy plane tests (ISSUE 9 acceptance proof).

Three layers, mirroring the plane's architecture:

- :class:`~horovod_tpu.elastic.policy.PolicyController` deliberation
  units under a fake clock — sustained-evidence windows, the SLO gate,
  cooldown/one-experiment throttling, realization accounting, and the
  inert-without-``HOROVOD_TARGET_GOODPUT`` contract;
- the rendezvous KV's spare-registration and preemption-notice scopes
  plus the zero-materialized ``hvd_policy_*`` scrape instruments;
- the chaos e2e with the REAL ``ElasticDriver``: one worker made
  persistently slow through the faults plane (the canonical
  ``worker.step`` delay injector), detected from shipped skew evidence,
  proactively drained through the SIGTERM→final-commit path, and
  replaced by a warm spare at the next generation fence — with loss
  continuity against the exact 2-rank averaged-SGD schedule, zero
  durable-storage reads, and exactly one ``policy_decision`` journal
  record whose realized goodput beats the no-action counterfactual.
  The A/B arm re-runs the same injected-fault script with the SLO knob
  unset and asserts the driver's decisions are those of a policy-free
  build (no drain, no blacklist, one world, straggler tolerated).
"""

import json
import os
import stat
import sys
import textwrap
import time

import pytest

from horovod_tpu import faults
from horovod_tpu import metrics as hvd_metrics
from horovod_tpu.elastic.policy import PolicyController, target_goodput
from horovod_tpu.runner.elastic.constants import EXIT_REMOVED
from horovod_tpu.runner.http.kv_server import (
    KVClient,
    PREEMPT_SCOPE,
    RendezvousServer,
    SPARE_SCOPE,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_faults():
    faults.reset()
    yield
    faults.reset()


def _skew(host: str, lateness: float, rank: str = "1") -> dict:
    """A compute_skew-shaped evidence snapshot naming one late host."""
    return {
        "matched": 4,
        "ranks": {rank: {"host": host, "mean_lateness_s": lateness,
                         "max_lateness_s": lateness, "samples": 4}},
        "worst": {"name": "allreduce.w#7", "step": -1, "skew_s": lateness,
                  "last_rank": rank, "last_host": host},
    }


class TestTargetGoodput:
    def test_unset_is_none(self, monkeypatch):
        monkeypatch.delenv("HOROVOD_TARGET_GOODPUT", raising=False)
        assert target_goodput() is None

    @pytest.mark.parametrize("raw", ["", "  ", "abc", "0", "-0.5", "1.5"])
    def test_invalid_is_none(self, monkeypatch, raw):
        monkeypatch.setenv("HOROVOD_TARGET_GOODPUT", raw)
        assert target_goodput() is None

    @pytest.mark.parametrize("raw,want", [("0.9", 0.9), ("1.0", 1.0),
                                          ("0.5", 0.5)])
    def test_ratio_parses(self, monkeypatch, raw, want):
        monkeypatch.setenv("HOROVOD_TARGET_GOODPUT", raw)
        assert target_goodput() == want


def _controller(monkeypatch, clock, target="0.9", window="1.0",
                skew_s="0.2", realize="2.0", resize_cost="1.0",
                min_np=1, **env):
    if target is None:
        monkeypatch.delenv("HOROVOD_TARGET_GOODPUT", raising=False)
    else:
        monkeypatch.setenv("HOROVOD_TARGET_GOODPUT", target)
    monkeypatch.setenv("HOROVOD_STRAGGLER_WINDOW", window)
    monkeypatch.setenv("HOROVOD_POLICY_DRAIN_SKEW", skew_s)
    monkeypatch.setenv("HOROVOD_POLICY_REALIZE_WINDOW", realize)
    monkeypatch.setenv("HOROVOD_POLICY_RESIZE_COST", resize_cost)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    return PolicyController(min_np=min_np, clock=lambda: clock[0])


WORLD = ["good", "bad"]


def _feed(c, clock, lateness=0.5, rate=2.0, host="bad", hb=None):
    c.note_rate(rate)
    c.observe(_skew(host, lateness), hb or {}, WORLD)


class TestPolicyController:
    def test_inert_without_target(self, monkeypatch):
        clock = [0.0]
        c = _controller(monkeypatch, clock, target=None)
        assert not c.enabled
        _feed(c, clock)
        clock[0] = 5.0
        _feed(c, clock)
        assert c.decide(WORLD, spares_ready=1) is None

    def test_single_spike_never_drains(self, monkeypatch):
        """The sustained-evidence clock: one spiky instance must not
        condemn — the threshold has to hold CONTINUOUSLY for window_s."""
        clock = [0.0]
        c = _controller(monkeypatch, clock)
        _feed(c, clock, lateness=5.0)          # spike
        assert c.decide(WORLD, 1) is None      # not sustained yet
        clock[0] = 1.0
        _feed(c, clock, lateness=0.0)          # back to healthy: resets
        clock[0] = 2.0
        _feed(c, clock, lateness=5.0)          # above again, clock restarts
        assert c.decide(WORLD, 1) is None

    def test_blind_tick_freezes_condemnation_clock(self, monkeypatch):
        """A snapshot with NO skew evidence at all (trace ships starved
        under load, scope just cleared) freezes the EWMAs and the
        sustained clock — blindness is not health, and must not reset a
        straggler's condemnation countdown."""
        clock = [0.0]
        c = _controller(monkeypatch, clock)
        _feed(c, clock)                        # condemned at t=0
        clock[0] = 0.8
        c.note_rate(2.0)
        c.observe({"ranks": {}, "worst": None}, {}, WORLD)   # blind tick
        clock[0] = 1.2
        _feed(c, clock)                        # evidence back, still late
        d = c.decide(WORLD, 1)                 # sustained SINCE t=0
        assert d is not None and d.host == "bad"

    def test_per_host_blindness_freezes_only_that_host(self, monkeypatch):
        """Blindness is per HOST: when the degrading host's own ships
        stall while healthy hosts keep reporting, its EWMA and clock
        freeze — its sensor outage must not read as recovery."""
        clock = [0.0]
        c = _controller(monkeypatch, clock)
        _feed(c, clock)                         # bad condemned at t=0
        clock[0] = 0.8
        c.note_rate(2.0)
        c.observe({"ranks": {"0": {"host": "good",
                                   "mean_lateness_s": 0.0}},
                   "worst": None}, {}, WORLD)   # bad absent, good fine
        clock[0] = 1.2
        _feed(c, clock)                         # bad's evidence returns
        d = c.decide(WORLD, 1)                  # sustained SINCE t=0
        assert d is not None and d.host == "bad"

    def test_dispatch_seq_bounded_for_auto_names(self):
        """Sensor-side regression: auto-named (one-per-call) dispatches
        are recorded unsuffixed and must not grow the tracer's per-name
        seq map — only the named vocabulary does."""
        from horovod_tpu import tracing

        tracing.reset_for_testing()
        t = tracing.get_tracer()
        for i in range(50):
            t.record_dispatch(f"op.{i}", unique=True)
            t.record_dispatch("grad.weight")
        assert list(t._dispatch_seq) == ["grad.weight"]
        assert t._dispatch_seq["grad.weight"] == 50
        spans = [s["name"] for rec in t.ring_snapshot()
                 for s in rec["spans"]]
        assert "op.0" in spans and "grad.weight#50" in spans
        tracing.reset_for_testing()

    def test_spanless_payload_cannot_steal_rank_identity(self):
        """Sensor-side regression (the flake that hid the straggler): a
        PARKED spare's payload carries its dummy launch-env rank label
        ("0") and no spans; depending on store order it used to
        overwrite the real rank 0's host in compute_skew — pinning the
        measured lateness on an out-of-world host the policy then
        dropped. A spanless payload must not claim a rank."""
        from horovod_tpu.tracing import compute_skew

        def payload(rank, t0, n=4, dt=1.0):
            return {"rank": rank, "generation": 1, "clock_offset_s": 0.0,
                    "steps": [{"step": -1, "spans": [
                        {"name": f"grad.w#{k}", "cat": "collective",
                         "t": t0 + k * dt, "dur": 0.0}
                        for k in range(n)]}]}

        strag = payload("0", 100.7)            # 0.7s late each instance
        surv = payload("1", 100.0)
        parked = {"rank": "0", "generation": 1, "clock_offset_s": 0.0,
                  "steps": []}                 # the spare: no spans
        out = compute_skew({"bad": strag, "good": surv, "spare": parked})
        assert out["ranks"]["0"]["host"] == "bad"
        assert out["ranks"]["0"]["mean_lateness_s"] == pytest.approx(0.7)
        assert out["worst"]["last_host"] == "bad"

    def test_healthy_evidence_still_resets(self, monkeypatch):
        """Positive evidence below the threshold (the host's ranks
        matched, and arrived on time) resets the clock — only blindness
        freezes."""
        clock = [0.0]
        c = _controller(monkeypatch, clock)
        _feed(c, clock)
        clock[0] = 1.0
        _feed(c, clock, lateness=0.0)          # measured healthy: resets
        clock[0] = 2.0
        _feed(c, clock)
        assert c.decide(WORLD, 1) is None

    def test_sustained_straggler_drains(self, monkeypatch):
        clock = [0.0]
        c = _controller(monkeypatch, clock)
        _feed(c, clock)
        clock[0] = 1.2                          # > window_s above threshold
        _feed(c, clock)
        d = c.decide(WORLD, spares_ready=1)
        assert d is not None and d.host == "bad" and d.action == "drain"
        assert d.evidence["straggler_ewma_s"]["bad"] >= 0.2
        assert d.evidence["worst_instance"]["last_host"] == "bad"
        assert d.predicted["predicted_gain_s"] > 0
        assert d.predicted["target_goodput"] == 0.9

    def test_slo_gate_tolerates_cheap_straggler(self, monkeypatch):
        """A straggler whose measured loss still clears the target is
        TOLERATED — voluntary resizes must pay for themselves."""
        clock = [0.0]
        c = _controller(monkeypatch, clock, target="0.5")
        # lateness 0.3s x rate 0.1 commits/s => lost fraction 3%:
        # projected goodput 0.97 >= 0.5 target.
        _feed(c, clock, lateness=0.3, rate=0.1)
        clock[0] = 1.2
        _feed(c, clock, lateness=0.3, rate=0.1)
        assert c.decide(WORLD, 1) is None

    def test_gain_must_beat_measured_resize_cost(self, monkeypatch):
        """The re-rendezvous price is weighed from the driver's MEASURED
        reconfiguration times: a cost above the horizon's predicted gain
        holds the drain."""
        clock = [0.0]
        c = _controller(monkeypatch, clock,
                        HOROVOD_POLICY_HORIZON="10.0")
        c.note_resize_cost(500.0)               # measured: very expensive
        _feed(c, clock)
        clock[0] = 1.2
        _feed(c, clock)
        assert c.decide(WORLD, 1) is None       # 0.95*10 - 500 < 0
        assert c.resize_cost_s() == 500.0

    def test_resize_cost_ewma_updates(self, monkeypatch):
        clock = [0.0]
        c = _controller(monkeypatch, clock)
        assert c.resize_cost_s() == 1.0         # seed until measured
        c.note_resize_cost(10.0)
        c.note_resize_cost(20.0)
        assert c.resize_cost_s() == 15.0        # 0.5/0.5 EWMA
        c.note_resize_cost(-1.0)                # nonsense ignored
        assert c.resize_cost_s() == 15.0

    def test_no_replacement_no_drain(self, monkeypatch):
        """Never drain the world below min_np without a warm spare to
        backfill."""
        clock = [0.0]
        c = _controller(monkeypatch, clock, min_np=2)
        _feed(c, clock)
        clock[0] = 1.2
        _feed(c, clock)
        assert c.decide(WORLD, spares_ready=0) is None
        assert c.decide(WORLD, spares_ready=1) is not None

    def test_no_rate_signal_no_drain(self, monkeypatch):
        """Without a throughput signal the gain model has no measured
        loss to project — hold rather than act on guesswork."""
        clock = [0.0]
        c = _controller(monkeypatch, clock)
        c.observe(_skew("bad", 0.5), {}, WORLD)
        clock[0] = 1.2
        c.observe(_skew("bad", 0.5), {}, WORLD)
        assert c.decide(WORLD, 1) is None

    def test_heartbeat_drift_channel(self, monkeypatch):
        """With HOROVOD_POLICY_HB_DRIFT armed, sustained heartbeat-age
        drift condemns a host even with zero collective skew (a degrading
        host beats late before it stops beating)."""
        clock = [0.0]
        c = _controller(monkeypatch, clock,
                        HOROVOD_POLICY_HB_DRIFT="2.0")
        _feed(c, clock, lateness=0.0, hb={"bad": 10.0})
        clock[0] = 1.2
        _feed(c, clock, lateness=0.0, hb={"bad": 10.0})
        d = c.decide(WORLD, 1)
        assert d is not None and d.host == "bad"
        assert d.evidence["hb_age_ewma_s"]["bad"] >= 2.0

    def test_one_experiment_at_a_time_and_cooldown(self, monkeypatch):
        clock = [0.0]
        c = _controller(monkeypatch, clock,
                        HOROVOD_POLICY_COOLDOWN="50.0")
        _feed(c, clock)
        clock[0] = 1.2
        _feed(c, clock)
        d = c.decide(WORLD, 1)
        assert d is not None
        c.record_drain(d, generation=2)
        clock[0] = 2.0
        _feed(c, clock)
        clock[0] = 3.1
        _feed(c, clock)
        assert c.decide(WORLD, 1) is None       # pending experiment
        assert c.realize_tick() is None         # window not elapsed
        clock[0] = 3.8
        assert c.realize_tick() is not None     # realized + journaled
        clock[0] = 10.0
        _feed(c, clock)
        clock[0] = 11.5
        _feed(c, clock)
        assert c.decide(WORLD, 1) is None       # cooldown still holds

    def test_realized_goodput_vs_counterfactual(self, monkeypatch,
                                                tmp_path):
        """The policy_decision record carries the predicted AND realized
        deltas: counterfactual = pre-drain rate, realized = post-drain
        rate over the realization window."""
        jpath = tmp_path / "journal.jsonl"
        monkeypatch.setenv("HOROVOD_EVENT_LOG", str(jpath))
        clock = [0.0]
        c = _controller(monkeypatch, clock)
        _feed(c, clock, rate=2.0)
        clock[0] = 1.2
        _feed(c, clock, rate=2.0)
        d = c.decide(WORLD, 1)
        c.record_drain(d, generation=3)
        assert d.pre_rate == 2.0
        clock[0] = 2.0
        c.note_rate(10.0)                       # the healed world
        clock[0] = 2.5
        c.note_rate(10.0)
        clock[0] = 3.5                          # realize window elapsed
        r = c.realize_tick()
        assert r is not None
        realized = r.predicted["realized"]
        assert realized["counterfactual_rate_commits_s"] == 2.0
        assert realized["realized_rate_commits_s"] == 10.0
        assert realized["realized_gain_commits_s"] == 8.0
        assert realized["partial"] is False
        recs = [json.loads(l) for l in jpath.read_text().splitlines()]
        decisions = [r for r in recs if r["event"] == "policy_decision"]
        assert len(decisions) == 1
        assert decisions[0]["generation"] == 3
        assert decisions[0]["host"] == "bad"
        assert decisions[0]["realized"]["realized_gain_commits_s"] == 8.0
        assert decisions[0]["evidence"]["straggler_ewma_s"]["bad"] > 0
        assert c.realize_tick() is None         # emitted exactly once

    def test_flush_emits_partial_record(self, monkeypatch, tmp_path):
        """A decision whose realization window the job outlives still
        gets its journal record, marked partial."""
        jpath = tmp_path / "journal.jsonl"
        monkeypatch.setenv("HOROVOD_EVENT_LOG", str(jpath))
        clock = [0.0]
        c = _controller(monkeypatch, clock)
        _feed(c, clock)
        clock[0] = 1.2
        _feed(c, clock)
        d = c.decide(WORLD, 1)
        c.record_drain(d, generation=2)
        clock[0] = 1.5                          # well inside the window
        r = c.flush()
        assert r is not None
        assert r.predicted["realized"]["partial"] is True
        recs = [json.loads(l) for l in jpath.read_text().splitlines()]
        assert sum(1 for x in recs
                   if x["event"] == "policy_decision") == 1
        assert c.flush() is None                # idempotent

    def test_observe_drops_departed_hosts(self, monkeypatch):
        """A drained host's EWMA state must not survive its departure —
        stale condemnation cannot follow a host back through the spare
        tier."""
        clock = [0.0]
        c = _controller(monkeypatch, clock)
        _feed(c, clock)
        clock[0] = 1.2
        _feed(c, clock)
        assert c.decide(WORLD, 1) is not None
        clock[0] = 2.0
        c.observe(_skew("bad", 0.0), {}, ["good"])   # bad left the world
        assert "bad" not in c._ewma and "bad" not in c._above_since

    def test_new_fault_points_parse_from_env_grammar(self):
        """The policy-plane injection points ride the standard
        HOROVOD_FAULTS grammar (point=mode[:arg]@N[xC])."""
        from horovod_tpu.faults import parse_spec

        specs = parse_spec(
            "policy.decide=drop@1; spare.promote=raise@2x3")
        by = {s.point: s for s in specs}
        assert by[faults.POLICY_DECIDE].mode == "drop"
        assert by[faults.SPARE_PROMOTE].mode == "raise"
        assert by[faults.SPARE_PROMOTE].at == 2
        assert by[faults.SPARE_PROMOTE].count == 3

    def test_policy_decide_fault_point(self, monkeypatch):
        """faults: policy.decide drop mode suppresses the evaluation
        (chaos proof that a skipped brain is a held hand, not a crash)."""
        clock = [0.0]
        c = _controller(monkeypatch, clock)
        _feed(c, clock)
        clock[0] = 1.2
        _feed(c, clock)
        faults.inject(faults.POLICY_DECIDE, "drop", at=1, count=1)
        assert c.decide(WORLD, 1) is None
        assert faults.fired(faults.POLICY_DECIDE) == 1
        assert c.decide(WORLD, 1) is not None   # window elapsed: fires


class TestSpareAndPreemptScopes:
    @pytest.fixture()
    def server(self):
        s = RendezvousServer(host="127.0.0.1")
        s.start()
        yield s
        s.stop()

    def test_spare_registration_roundtrip(self, server):
        client = KVClient("127.0.0.1", server.port)
        assert server.spare_records() == {}
        client.put(SPARE_SCOPE, "hostA",
                   json.dumps({"host": "hostA", "pid": 42}).encode())
        recs = server.spare_records()
        assert recs["hostA"]["pid"] == 42
        server.clear_spare("hostA")
        assert server.spare_records() == {}
        server.clear_spare("hostA")             # idempotent

    def test_malformed_spare_record_tolerated(self, server):
        client = KVClient("127.0.0.1", server.port)
        client.put(SPARE_SCOPE, "hostB", b"\xff not json")
        assert server.spare_records()["hostB"] == {}

    def test_preempt_notice_consumed_once(self, server):
        client = KVClient("127.0.0.1", server.port)
        client.put(PREEMPT_SCOPE, "hostA", b"{}")
        assert "hostA" in server.preempt_notices()
        server.consume_preempt("hostA")
        assert server.preempt_notices() == {}

    def test_scrape_zero_materializes_policy_instruments(self, server):
        """The hvd_policy_* instruments exist on the scrape BEFORE any
        decision fires, so dashboards can tell 'no drains yet' from 'not
        measuring'."""
        parsed = hvd_metrics.validate_prometheus_text(
            server.metrics_text())
        spares = parsed["hvd_policy_spare_hosts"]["samples"]
        assert spares == [({}, 0.0)]
        actions = {tuple(sorted(l.items())): v for l, v in
                   parsed["hvd_policy_decisions_total"]["samples"]}
        assert actions[(("action", "drain"),)] == 0.0
        assert actions[(("action", "promote"),)] == 0.0
        assert actions[(("action", "preempt"),)] == 0.0
        server.record_policy_action("drain")
        server.record_policy_action("drain")
        server.set_cluster_info(spares=2)
        parsed = hvd_metrics.validate_prometheus_text(
            server.metrics_text())
        assert parsed["hvd_policy_spare_hosts"]["samples"] == [({}, 2.0)]
        actions = {tuple(sorted(l.items())): v for l, v in
                   parsed["hvd_policy_decisions_total"]["samples"]}
        assert actions[(("action", "drain"),)] == 2.0


# ---------------------------------------------------------------------------
# Chaos e2e: straggler -> proactive drain -> warm-spare replacement
# ---------------------------------------------------------------------------

# Three names that all resolve to this machine (localhost-as-cluster):
# the two loopback aliases plus the machine's own hostname (is_local
# accepts all three; every connection goes to the rendezvous address,
# 127.0.0.1, so the hostname is only a label). pick_world orders
# sorted-lexicographically, so with max_np=2 the initial world is the
# first two names and the third starts as the warm spare. "127.0.0.1"
# sorts first always (digits < letters) — it is the straggler.
def _cluster_names() -> tuple[str, str, str]:
    import socket

    names = sorted({"127.0.0.1", "localhost", socket.gethostname()})
    if len(names) < 3:
        pytest.skip("machine hostname shadows a loopback alias; need "
                    "three distinct local names for the spare tier")
    straggler, survivor, spare = names[0], names[1], names[2]
    assert straggler == "127.0.0.1"
    return straggler, survivor, spare


def _write_discovery(tmp_path, hosts):
    hosts_file = tmp_path / "hosts.txt"
    hosts_file.write_text("\n".join(hosts) + "\n")
    script = tmp_path / "discover.sh"
    script.write_text(f"#!/bin/sh\ncat {hosts_file}\n")
    script.chmod(script.stat().st_mode | stat.S_IEXEC)
    return str(script)


def _straggler_worker(tmp_path) -> str:
    """Elastic torch worker; the behavior map makes ONE host arm the
    canonical straggler injector (faults-plane ``delay`` on
    ``worker.step``) so its every step enters the collectives late."""
    path = tmp_path / "straggler_worker.py"
    path.write_text(textwrap.dedent(f"""
        import json, os, sys, time
        sys.path.insert(0, {REPO_ROOT!r})
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax
        jax.config.update("jax_platforms", "cpu")
        from horovod_tpu._jax_compat import force_cpu_devices
        force_cpu_devices(1)
        import numpy as np
        import torch
        import horovod_tpu.torch as hvd
        from horovod_tpu import faults
        from horovod_tpu.elastic import run as elastic_run
        from horovod_tpu.torch.elastic import TorchState

        host = os.environ["HOROVOD_HOSTNAME"]
        behavior = json.load(open(os.environ["TEST_BEHAVIOR_FILE"])).get(
            host, "normal")
        EPOCHS = int(os.environ["TEST_EPOCHS"])
        STEP_SLEEP = float(os.environ["TEST_STEP_SLEEP"])
        if behavior.startswith("straggle:"):
            # The canonical straggler injector (docs/elastic.md): every
            # worker.step dispatch on this host is delayed — persistently
            # slow-but-alive, exactly what the skew gauges attribute.
            faults.inject(faults.WORKER_STEP, "delay",
                          arg=float(behavior.split(":")[1]),
                          at=1, count=10**9)

        torch.manual_seed(0)
        model = torch.nn.Linear(4, 1, bias=False)
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.05),
            named_parameters=model.named_parameters())
        state = TorchState(model=model, optimizer=opt, epoch=0)

        @elastic_run
        def train(state):
            while state.epoch < EPOCHS:
                faults.fire(faults.WORKER_STEP)  # the step dispatch gate
                time.sleep(STEP_SLEEP)
                r = hvd.rank()
                x = torch.from_numpy(np.random.RandomState(
                    100 * state.epoch + r).randn(8, 4).astype(np.float32))
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
    """The exact 2-rank averaged-SGD loss schedule (host-independent:
    the model update averages both ranks' grads whichever hosts carry
    them)."""
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


def _run_straggler_job(tmp_path, monkeypatch, epochs: int,
                       policy_on: bool):
    """One injected-fault run: 3 discovered hosts, world of 2, one made
    persistently slow. Returns (rc, stdout lines, journal records)."""
    pytest.importorskip("torch")
    from horovod_tpu.runner.elastic.driver import run_elastic
    from horovod_tpu.runner.launch import Settings

    jpath = tmp_path / "journal.jsonl"
    monkeypatch.setenv("HOROVOD_EVENT_LOG", str(jpath))
    monkeypatch.setenv("HOROVOD_ELASTIC_HEARTBEAT_INTERVAL", "0.25")
    # Liveness must stay WELL clear of the policy windows: under CPU
    # contention the single-threaded rendezvous server stamps heartbeat
    # receive times late, and a liveness kill of the slow-but-alive
    # straggler would preempt the proactive drain this test proves.
    monkeypatch.setenv("HOROVOD_ELASTIC_HEARTBEAT_TIMEOUT", "30")
    monkeypatch.setenv("HOROVOD_TRACE_SAMPLE", "1")
    monkeypatch.setenv("HOROVOD_TRACE_SHIP_SECONDS", "0.5")
    monkeypatch.setenv("HOROVOD_BLACKLIST_COOLDOWN", "600")
    # A recovering survivor can race the new epoch's publication and try
    # to re-join the dying one; a short native join timeout turns that
    # into a fast ladder retry instead of a 30s stall.
    monkeypatch.setenv("HOROVOD_NATIVE_INIT_TIMEOUT", "6")
    if policy_on:
        monkeypatch.setenv("HOROVOD_TARGET_GOODPUT", "0.9")
        monkeypatch.setenv("HOROVOD_WARM_SPARES", "1")
        monkeypatch.setenv("HOROVOD_STRAGGLER_WINDOW", "1.5")
        monkeypatch.setenv("HOROVOD_POLICY_DRAIN_SKEW", "0.15")
        monkeypatch.setenv("HOROVOD_POLICY_INTERVAL", "0.4")
        # The realization window must out-span the recovery hole (abort,
        # re-rendezvous, spare join — commits frozen) so the realized
        # rate reflects the HEALED world, not the surgery.
        monkeypatch.setenv("HOROVOD_POLICY_REALIZE_WINDOW", "15")
        monkeypatch.setenv("HOROVOD_POLICY_COOLDOWN", "120")
        monkeypatch.setenv("HOROVOD_POLICY_RESIZE_COST", "2.0")
    else:
        # The A/B arm: the SLO knob unset IS the policy-free build.
        monkeypatch.delenv("HOROVOD_TARGET_GOODPUT", raising=False)
        monkeypatch.delenv("HOROVOD_WARM_SPARES", raising=False)

    straggler, survivor, spare = _cluster_names()
    behavior_file = tmp_path / "behavior.json"
    behavior_file.write_text(json.dumps({straggler: "straggle:0.7"}))
    script = _write_discovery(tmp_path, [straggler, survivor, spare])
    settings = Settings(
        num_proc=2,
        hosts=[],
        command=[sys.executable, _straggler_worker(tmp_path)],
        cpu_mode=True,
        elastic=True,
        min_np=2,          # the world must NEVER drop below 2
        max_np=2,
        discovery_script=script,
        elastic_timeout=60.0,
        env={
            "TEST_BEHAVIOR_FILE": str(behavior_file),
            "TEST_EPOCHS": str(epochs),
            "TEST_STEP_SLEEP": "0.05",
        },
    )
    # Driver-side logs ride the sink too (policy/spare/drain WARNINGs
    # plus DEBUG evidence lines) so a detection flake is diagnosable
    # from the failure message alone.
    import logging

    from horovod_tpu.utils.logging import get_logger

    lines: list = []
    handler = logging.Handler()
    handler.emit = lambda rec: lines.append(f"[driver] {rec.getMessage()}")
    logger = get_logger()
    logger.addHandler(handler)
    try:
        rc = run_elastic(settings, sink=lines.append)
    finally:
        logger.removeHandler(handler)
    records = []
    if jpath.exists():
        for line in jpath.read_text().splitlines():
            try:
                records.append(json.loads(line))
            except ValueError:
                pass
    # The driver ran in THIS process: its policy gauges are readable
    # post-mortem — the straggler EWMAs are the first thing to check
    # when a detection assert fires.
    policy_gauges = [
        l for l in hvd_metrics.render().splitlines()
        if l.startswith("hvd_policy") and not l.startswith("#")]
    return rc, [str(x) for x in lines], records, (straggler, survivor,
                                                  spare), policy_gauges


def _assert_loss_continuity(text: str, epochs: int):
    import re

    expected = _expected_losses(epochs)
    seen = set()
    for line in text.splitlines():
        m = re.search(
            r"rank=(\d+) host=\S+ epoch=(\d+) np=2 loss=([0-9.]+)", line)
        if not m:
            continue
        r, e, got = int(m.group(1)), int(m.group(2)), float(m.group(3))
        assert abs(got - expected[(e, r)]) < 1e-4, (e, r, got,
                                                   expected[(e, r)])
        seen.add((e, r))
    # Every (epoch, rank) cell was trained on the exact schedule by
    # SOME world membership (replays across the drain only re-cover).
    missing = {(e, r) for e in range(epochs) for r in (0, 1)} - seen
    assert not missing, sorted(missing)[:10]


class TestStragglerSelfHealingE2E:
    @pytest.mark.slow
    def test_straggler_drained_spare_promoted(self, tmp_path,
                                              monkeypatch):
        """The tentpole, end to end: sustained skew evidence -> proactive
        SIGTERM drain (final commit lands: clean EXIT_REMOVED) -> warm
        spare joins at the next generation fence -> exactly one
        policy_decision whose realized goodput beats the no-action
        counterfactual. Zero durable-storage reads anywhere."""
        epochs = 240
        rc, lines, records, names, gauges = _run_straggler_job(
            tmp_path, monkeypatch, epochs, policy_on=True)
        straggler, survivor, spare = names
        text = "\n".join(lines)
        assert rc == 0, text

        events = {}
        for r in records:
            events.setdefault(r["event"], []).append(r)

        # The spare plane: launched at standby, promoted at g+1.
        assert any(r["host"] == spare
                   for r in events.get("spare_launched", [])), records
        promoted = [r for r in events.get("spare_promoted", [])
                    if r["host"] == spare]
        assert promoted, (sorted(events), gauges,
                          [l for l in lines if "[driver]" in l][-30:])
        assert promoted[0]["generation"] >= 2

        # The drain: policy-initiated, through the SIGTERM final-commit
        # path — the worker exits EXIT_REMOVED, never SIGKILL.
        drains = events.get("policy_drain", [])
        assert len(drains) == 1, drains
        assert drains[0]["host"] == straggler
        assert drains[0]["action"] == "drain"
        assert drains[0]["rc"] == EXIT_REMOVED, drains
        # Post-hoc evidence: the drain dumped a driver-side flight
        # record naming the condemned host.
        flights = [r for r in events.get("flight_record", [])
                   if r.get("reason") == "policy_drain"]
        assert flights and flights[0]["host"] == straggler, records
        assert flights[0]["evidence"]["straggler_ewma_s"][straggler] > 0

        # Exactly ONE policy decision, with an honest realized-vs-
        # counterfactual comparison: the healed world commits faster.
        decisions = events.get("policy_decision", [])
        assert len(decisions) == 1, decisions
        dec = decisions[0]
        assert dec["action"] == "drain" and dec["host"] == straggler
        assert dec["predicted"]["target_goodput"] == 0.9
        assert dec["predicted"]["predicted_gain_s"] > 0
        realized = dec["realized"]
        assert realized["counterfactual_rate_commits_s"] is not None
        assert realized["realized_rate_commits_s"] is not None
        assert (realized["realized_gain_commits_s"] is not None
                and realized["realized_gain_commits_s"] > 0), realized

        # The world never dropped below min_np=2 across every epoch.
        for r in events.get("world_published", []):
            assert r["np"] == 2, r

        # Zero durable-storage reads: recovery rode restore + live sync
        # (no Checkpointer was ever registered, nothing fell through).
        assert not any(r.get("rung") == "durable" for r in records)
        assert "checkpoint_fallback" not in events

        # Both final-world hosts finished the full run; the straggler
        # itself was drained out (blacklisted) and did NOT finish.
        assert f"host={survivor} finished at epoch {epochs}" in text, text
        assert f"host={spare} finished at epoch {epochs}" in text, text
        assert f"host={straggler} finished" not in text, text

        # Loss continuity: every np=2 loss line (any generation, either
        # membership) matches the exact uninterrupted 2-rank schedule.
        _assert_loss_continuity(text, epochs)

    @pytest.mark.slow
    def test_policy_plane_inert_without_target(self, tmp_path,
                                               monkeypatch):
        """The A/B arm: the SAME injected fault script with
        HOROVOD_TARGET_GOODPUT unset. The driver's decisions must be
        bit-for-bit those of a policy-free build: no drain, no
        blacklist, no spares, one world generation — the straggler is
        tolerated to the end (ring speed = worst member, as at HEAD)."""
        epochs = 16
        rc, lines, records, names, _gauges = _run_straggler_job(
            tmp_path, monkeypatch, epochs, policy_on=False)
        straggler, survivor, _spare = names
        text = "\n".join(lines)
        assert rc == 0, text

        names = {r["event"] for r in records}
        assert "policy_decision" not in names, records
        assert "policy_drain" not in names, records
        assert "driver_drain" not in names, records
        assert "blacklist" not in names, records
        assert not any(n.startswith("spare_") for n in names), names

        published = [r for r in records
                     if r["event"] == "world_published"]
        assert len(published) == 1, published   # one generation, ever

        # Every host finished — the straggler was tolerated, not drained.
        assert f"host={straggler} finished at epoch {epochs}" in text, text
        assert f"host={survivor} finished at epoch {epochs}" in text, text
        _assert_loss_continuity(text, epochs)
