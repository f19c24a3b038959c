"""Silent-data-corruption defense plane tests (ISSUE 12 acceptance proof).

Layered like the plane itself:

- fingerprint math: deterministic digests (shape/dtype headers), the
  per-bucket finite-count/L2 summaries, mode-dependent record coverage
  (allreduce / sharded / fsdp), and the interval-gated commit hook;
- the ``corrupt`` fault mode: seeded deterministic bit flips through
  ``faults.corrupt_payload``, the env-grammar ``corrupt[:nbits]`` spec,
  and the two canonical SDC injectors (``grad.corrupt`` mutates a
  committed snapshot — self-consistent digests, detectable only by
  cross-rank vote; ``peer.corrupt`` mutates the encoded replica blob —
  the KV's install gate rejects it with the previous good replica
  intact);
- cross-rank voting: n>=3 majority, the non-finite override, the
  two-voter drift tie-break, ambiguity, and newest-COMPLETE-group
  selection;
- the non-finite tripwire fused into the gradient flush: ``skip`` drops
  the update and keeps the optimizer state un-advanced rank-identically
  on the allreduce and sharded halves, ``warn`` only counts, and unset
  traces bit-for-bit as before (no ``is_finite`` in the jaxpr — the
  inertness contract at the HLO level);
- int8 quantization hardening: NaN/Inf/overflow payloads through the
  quantized allreduce and the RS/AG halves saturate instead of
  poisoning whole blocks' scales;
- checkpoint corruption edges: truncated sha footer, bit-rotted current
  + intact ``.prev`` through ``atomic_read``, both-slots-corrupt
  terminal error — the durable rung never installs a record that fails
  its own checksum;
- the KV plane: fingerprints ride heartbeats, ``GET /integrity`` serves
  the collected records + live vote, a quarantined rank's peer-replica
  PUTs are 409-fenced with the ``.prev`` slot retained and the fence
  lifts on a strictly-newer-generation write, and the worker-side
  assembly drops a condemned rank's records from its LOCAL pool too;
- rewind-on-spike: EWMA detector units, the storage-free rewind path in
  ``@hvd.elastic.run`` (no ladder climb, ``rewind`` journal event,
  skip-ahead staged), and the ``HOROVOD_REWIND_MAX`` storm breaker;
- the chaos e2e with the real ``ElasticDriver`` (2 workers + 1 warm
  spare): ``grad.corrupt``-injected rank detected by the voting plane,
  exactly one ``integrity_divergence`` journal event naming the corrupt
  host, the host drained and the spare promoted at g+1, recovery on the
  peer rung with ZERO durable reads, and final weights exact vs the
  uninterrupted clean run — plus the A/B arm proving the same script
  with every integrity knob unset is bit-for-bit HEAD.
"""

import hashlib
import json
import os
import stat
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from horovod_tpu import abort, checkpoint, faults, integrity, peercheck
from horovod_tpu import metrics as hvd_metrics
from horovod_tpu.exceptions import (
    CheckpointCorruptError,
    HorovodInternalError,
    LossSpikeError,
)
from horovod_tpu.runner.http.kv_server import (
    KVClient,
    PEERSTATE_SCOPE,
    RendezvousServer,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

HARD_TIMEOUT_S = float(os.environ.get("HOROVOD_TEST_HARD_TIMEOUT", "300"))


@pytest.fixture(autouse=True)
def _hard_timeout():
    import faulthandler

    faulthandler.dump_traceback_later(HARD_TIMEOUT_S, exit=True)
    yield
    faulthandler.cancel_dump_traceback_later()


@pytest.fixture(autouse=True)
def _clean_planes(monkeypatch):
    for knob in ("HOROVOD_INTEGRITY_INTERVAL", "HOROVOD_NONFINITE_ACTION",
                 "HOROVOD_LOSS_SPIKE_SIGMA", "HOROVOD_REWIND_MAX",
                 "HOROVOD_FAULTS"):
        monkeypatch.delenv(knob, raising=False)
    faults.reset()
    abort.reset()
    integrity.reset_for_testing()
    yield
    faults.reset()
    abort.reset()
    integrity.reset_for_testing()


# ---------------------------------------------------------------------------
# Fingerprints
# ---------------------------------------------------------------------------


class TestFingerprints:
    def test_digest_deterministic_and_key_order_free(self):
        a = {"b": np.arange(4, dtype=np.float32),
             "a": np.ones((2, 2), np.float32)}
        b = {"a": np.ones((2, 2), np.float32),
             "b": np.arange(4, dtype=np.float32)}
        assert integrity.digest_tree(a) == integrity.digest_tree(b)
        assert integrity.digest_tree(a) == integrity.digest_tree(a)

    def test_digest_guards_shape_and_dtype(self):
        flat = np.arange(4, dtype=np.float32)
        assert (integrity.digest_tree({"x": flat})
                != integrity.digest_tree({"x": flat.reshape(2, 2)}))
        assert (integrity.digest_tree({"x": flat})
                != integrity.digest_tree(
                    {"x": flat.view(np.int32)}))

    def test_digest_one_bit_apart(self):
        x = np.ones(8, np.float32)
        y = x.copy()
        y.view(np.uint8)[3] ^= 1
        assert integrity.digest_tree(x) != integrity.digest_tree(y)

    def test_summaries_count_nonfinite(self):
        tree = {"a": np.array([1.0, np.nan, np.inf, 2.0], np.float32),
                "b": np.ones(4, np.float32)}
        out = integrity.summarize_tree(tree, buckets=1)
        assert len(out) == 1
        assert out[0]["n"] == 8 and out[0]["finite"] == 6
        # L2 over the finite elements only: sqrt(1 + 4 + 4*1).
        assert out[0]["l2"] == pytest.approx(3.0)

    def test_summaries_bucket_count_bounded(self):
        leaves = {f"l{i}": np.ones(3, np.float32) for i in range(20)}
        out = integrity.summarize_tree(leaves)
        assert 1 <= len(out) <= integrity.SUMMARY_BUCKETS
        assert sum(b["n"] for b in out) == 60

    def test_record_modes(self):
        params = {"w": np.ones(4, np.float32)}
        opt = {"m": np.zeros(4, np.float32)}
        ar = integrity.make_record(params, opt, step=3, rank=0, host="h",
                                   generation=1)
        ar2 = integrity.make_record(params, {"m": np.ones(4, np.float32)},
                                    step=3, rank=0, host="h", generation=1)
        # allreduce: opt state is replicated — it is voted on.
        assert ar["digest"] != ar2["digest"]
        sh = integrity.make_record(params, opt, step=3,
                                   sync_mode="sharded",
                                   shard=np.ones(2, np.float32),
                                   rank=0, host="h", generation=1)
        sh2 = integrity.make_record(params, {"m": np.ones(4, np.float32)},
                                    step=3, sync_mode="sharded",
                                    shard=np.ones(2, np.float32),
                                    rank=0, host="h", generation=1)
        # sharded: the ZeRO-1 opt rows differ per rank by design — only
        # the params are cross-rank-comparable; the rank-local rows ride
        # the per-shard digest.
        assert sh["digest"] == sh2["digest"]
        assert sh["shard_digest"] is not None
        fs = integrity.make_record(params, None, step=3, sync_mode="fsdp",
                                   shard=np.ones(2, np.float32),
                                   rank=0, host="h", generation=1)
        assert fs["digest"] is None  # nothing replicated to vote on
        assert fs["shard_digest"] is not None
        assert fs["summaries"]  # the non-finite voting signal remains

    def test_bfloat16_leaves_summarized_and_corruptible(self,
                                                        monkeypatch):
        """ml_dtypes customs (bfloat16 — THE accelerator dtype) are not
        np.floating: the summaries and the grad.corrupt injector must
        not silently skip them."""
        ml_dtypes = pytest.importorskip("ml_dtypes")
        bf16 = ml_dtypes.bfloat16
        bad = np.ones(16, bf16)
        bad[3] = float("nan")
        s = integrity.summarize_tree({"w": bad})
        assert s and s[0]["n"] == 16 and s[0]["finite"] == 15
        monkeypatch.setenv("HOROVOD_FAULTS", "grad.corrupt=corrupt:64@1")
        faults.reset()
        saved = {"params": {"w": np.ones(64, bf16)}, "opt_state": None}
        out = integrity.maybe_corrupt_snapshot(saved)
        assert (out["params"]["w"].tobytes()
                != np.ones(64, bf16).tobytes())
        assert out["params"]["w"].dtype == bf16

    def test_maybe_fingerprint_unarmed_is_inert(self):
        assert integrity.maybe_fingerprint({"w": np.ones(2)}, None, 1) is None
        assert integrity.heartbeat_payload() is None
        assert integrity.summary()["checks"] == 0

    def test_maybe_fingerprint_interval_and_prev(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_INTEGRITY_INTERVAL", "2")
        p = {"w": np.ones(4, np.float32)}
        assert integrity.maybe_fingerprint(p, None, 1) is None
        r2 = integrity.maybe_fingerprint(p, None, 2)
        assert r2 is not None and r2["step"] == 2 and r2["prev"] is None
        assert integrity.maybe_fingerprint(p, None, 3) is None
        r4 = integrity.maybe_fingerprint(
            {"w": 2 * np.ones(4, np.float32)}, None, 4)
        assert r4 is not None
        # The previous interval's digest/L2 ride inline: the two-voter
        # tie-break needs each rank's own trend, serverless.
        assert r4["prev"]["digest"] == r2["digest"]
        assert r4["prev"]["step"] == 2
        assert r4["prev"]["l2"] == [b["l2"] for b in r2["summaries"]]
        assert integrity.heartbeat_payload() is r4


# ---------------------------------------------------------------------------
# The corrupt fault mode
# ---------------------------------------------------------------------------


class TestCorruptFaultMode:
    def test_flip_bits_deterministic(self):
        data = bytes(range(256)) * 4
        a = faults.flip_bits(data, nbits=16, seed="x#1")
        b = faults.flip_bits(data, nbits=16, seed="x#1")
        c = faults.flip_bits(data, nbits=16, seed="x#2")
        assert a == b != data
        assert c != a
        assert faults.flip_bits(b"", 8, "s") == b""
        assert faults.flip_bits(data, 0, "s") == data

    def test_corrupt_payload_unarmed_passthrough(self):
        data = b"payload-bytes" * 8
        assert faults.corrupt_payload("grad.corrupt", data) == data
        assert faults.hits("grad.corrupt") == 1  # hits count even unarmed

    def test_corrupt_payload_window_and_determinism(self):
        data = b"q" * 64
        faults.inject(faults.GRAD_CORRUPT, "corrupt", arg=8, at=2, count=1)
        first = faults.corrupt_payload(faults.GRAD_CORRUPT, data)
        second = faults.corrupt_payload(faults.GRAD_CORRUPT, data)
        third = faults.corrupt_payload(faults.GRAD_CORRUPT, data)
        assert first == data  # hit 1: before the window
        assert second != data  # hit 2: armed
        assert third == data  # hit 3: window closed
        # Same spec, same hit index -> same bits every run.
        faults.reset()
        faults.inject(faults.GRAD_CORRUPT, "corrupt", arg=8, at=2, count=1)
        faults.corrupt_payload(faults.GRAD_CORRUPT, data)
        assert faults.corrupt_payload(faults.GRAD_CORRUPT, data) == second

    def test_corrupt_payload_other_modes_keep_fire_semantics(self):
        faults.inject(faults.PEER_CORRUPT, "raise", at=1, count=1)
        with pytest.raises(faults.InjectedFault):
            faults.corrupt_payload(faults.PEER_CORRUPT, b"x")
        faults.reset()
        faults.inject(faults.PEER_CORRUPT, "drop", at=1, count=1)
        # Nothing to drop at a payload site: the caller keeps its bytes.
        assert faults.corrupt_payload(faults.PEER_CORRUPT, b"x") == b"x"

    def test_armed_check_does_not_count_hits(self):
        faults.inject(faults.GRAD_CORRUPT, "corrupt", at=1, count=1)
        assert faults.armed(faults.GRAD_CORRUPT)
        assert faults.armed(faults.GRAD_CORRUPT)
        assert faults.hits(faults.GRAD_CORRUPT) == 0
        assert not faults.armed("never.armed")

    def test_env_grammar_corrupt_mode(self):
        specs = {s.point: s
                 for s in faults.parse_spec(
                     "grad.corrupt=corrupt:16@2x3,peer.corrupt=corrupt")}
        assert specs["grad.corrupt"].mode == "corrupt"
        assert specs["grad.corrupt"].arg == 16
        assert specs["grad.corrupt"].at == 2
        assert specs["grad.corrupt"].count == 3
        assert specs["peer.corrupt"].mode == "corrupt"
        assert specs["peer.corrupt"].arg is None  # default bit budget

    def test_plain_fire_ignores_corrupt_mode(self):
        faults.inject(faults.GRAD_CORRUPT, "corrupt", at=1, count=10)
        assert faults.fire(faults.GRAD_CORRUPT) is False  # never a drop


class TestSnapshotCorruption:
    def test_unarmed_snapshot_untouched(self):
        saved = {"params": {"w": np.ones(4, np.float32)}, "epoch": 3}
        out = integrity.maybe_corrupt_snapshot(saved)
        assert out is saved
        np.testing.assert_array_equal(out["params"]["w"], 1.0)

    def test_armed_mutates_snapshot_not_inputs(self):
        live = np.ones(8, np.float32)
        saved = {"params": {"w": live.copy()},
                 "opt_state": [np.zeros(8, np.float32)], "epoch": 3}
        faults.inject(faults.GRAD_CORRUPT, "corrupt", arg=16, at=1,
                      count=1)
        out = integrity.maybe_corrupt_snapshot(saved)
        assert not np.array_equal(out["params"]["w"], live)
        assert not np.array_equal(out["opt_state"][0],
                                  np.zeros(8, np.float32))
        assert out["epoch"] == 3  # non-tree entries untouched
        # The corruption is deterministic: digests reproduce.
        d1 = integrity.digest_tree(out["params"])
        faults.reset()
        integrity.reset_for_testing()
        faults.inject(faults.GRAD_CORRUPT, "corrupt", arg=16, at=1,
                      count=1)
        saved2 = {"params": {"w": live.copy()},
                  "opt_state": [np.zeros(8, np.float32)], "epoch": 3}
        assert integrity.digest_tree(
            integrity.maybe_corrupt_snapshot(saved2)["params"]) == d1

    def test_tpu_state_commit_corrupts_saved_only(self, hvd, monkeypatch):
        from horovod_tpu.elastic import TpuState

        monkeypatch.setenv("HOROVOD_INTEGRITY_INTERVAL", "1")
        params = {"w": jnp.ones(4)}
        opt = optax.sgd(0.1)
        state = TpuState(params=params, opt_state=opt.init(params),
                         epoch=0)
        state.commit()
        clean = integrity.heartbeat_payload()
        faults.inject(faults.GRAD_CORRUPT, "corrupt", arg=16, at=1,
                      count=1)
        state.commit()
        rec = integrity.heartbeat_payload()
        # The fingerprint SEES the corruption (it covers the snapshot
        # the replica wire would ship)...
        assert rec["digest"] != clean["digest"]
        assert not np.array_equal(
            np.asarray(state._saved["params"]["w"]), np.ones(4))
        # ...while the live training state never did.
        np.testing.assert_array_equal(np.asarray(state.params["w"]), 1.0)

    def test_peer_corrupt_rejected_by_install_gate(self):
        server = RendezvousServer()
        server.start()
        try:
            client = KVClient("127.0.0.1", server.port)
            rep = peercheck.PeerReplicator(
                client=client, rank=0, world_size_fn=lambda: 1,
                generation_fn=lambda: 0)
            assert rep.replicate(b"good-shard" * 20, step=1)
            faults.inject(faults.PEER_CORRUPT, "corrupt", at=1, count=1)
            # The wire flip: encode (digest stamped), THEN mutate — the
            # server's install-time verification must 422 it and keep
            # the previous good replica authoritative.
            assert not rep.replicate(b"next-shard" * 20, step=2)
            blob = client.get(PEERSTATE_SCOPE, "0")
            rec = peercheck.decode_record(blob)  # verifies the checksum
            assert rec.step == 1 and rec.payload == b"good-shard" * 20
        finally:
            server.stop()


# ---------------------------------------------------------------------------
# Voting
# ---------------------------------------------------------------------------


def _rec(rank, digest, step=5, generation=1, summaries=None, prev=None,
         host=None):
    return {"v": 1, "rank": rank, "host": host or f"host{rank}",
            "generation": generation, "step": step, "digest": digest,
            "sync_mode": "allreduce",
            "summaries": summaries if summaries is not None
            else [{"n": 8, "finite": 8, "l2": 1.0}],
            "prev": prev, "t": float(rank)}


class TestVoting:
    def test_agreement_is_clean(self):
        v = integrity.vote({r: _rec(r, "aaa") for r in range(4)})
        assert not v["divergent"] and v["outlier_host"] is None

    def test_majority_names_minority(self):
        records = {r: _rec(r, "aaa") for r in range(3)}
        records[1] = _rec(1, "bbb")
        v = integrity.vote(records)
        assert v["divergent"] and not v["ambiguous"]
        assert v["method"] == "majority"
        assert v["outlier_rank"] == 1 and v["outlier_host"] == "host1"

    def test_three_way_split_is_ambiguous(self):
        v = integrity.vote({0: _rec(0, "aaa"), 1: _rec(1, "bbb"),
                            2: _rec(2, "ccc")})
        assert v["divergent"] and v["ambiguous"]
        assert v["outlier_host"] is None

    def test_nonfinite_summary_names_host_even_without_digest(self):
        # The fsdp path: no replicated digest, but a record whose state
        # carries NaN while every peer's is clean is damning alone.
        records = {r: _rec(r, None) for r in range(3)}
        records[2]["summaries"] = [{"n": 8, "finite": 5, "l2": 1.0}]
        v = integrity.vote(records)
        assert v["divergent"] and not v["ambiguous"]
        assert v["method"] == "nonfinite" and v["outlier_rank"] == 2

    def test_stuck_shard_named_without_digest(self):
        """The fsdp path's finite-state signal: a training step always
        changes a rank's shard, so a shard digest frozen across an
        interval while every peer's moved names a wedged/corrupt-stuck
        host."""
        records = {}
        for r in range(3):
            rec = _rec(r, None)
            rec["shard_digest"] = f"S{r}-new" if r != 2 else "S2-stuck"
            rec["prev"] = {"digest": None, "step": 4,
                           "shard_digest": (f"S{r}-old" if r != 2
                                            else "S2-stuck")}
            records[r] = rec
        v = integrity.vote(records)
        assert v["divergent"] and not v["ambiguous"]
        assert v["method"] == "stuck_shard" and v["outlier_rank"] == 2
        # Everyone moving is the steady state — clean verdict.
        for r in range(3):
            records[r]["prev"]["shard_digest"] = f"S{r}-old"
            records[r]["shard_digest"] = f"S{r}-new"
        assert not integrity.vote(records)["divergent"]
        # Missing prev shard evidence (first interval, replacement
        # rank): no verdict rather than a guess.
        records[1]["prev"] = None
        records[2]["shard_digest"] = "S2-stuck"
        assert not integrity.vote(records)["divergent"]

    def test_everyone_nonfinite_is_not_divergence(self):
        # A genuinely exploding model trips EVERY rank identically —
        # that is the tripwire's job, not the voting plane's.
        records = {r: _rec(r, "aaa",
                           summaries=[{"n": 8, "finite": 4, "l2": 1.0}])
                   for r in range(3)}
        v = integrity.vote(records)
        assert not v["divergent"]

    def test_two_voter_drift_tiebreak(self):
        prev = {"digest": "old", "step": 4, "l2": [1.0], "finite": [8]}
        records = {
            0: _rec(0, "aaa", prev=prev,
                    summaries=[{"n": 8, "finite": 8, "l2": 1.01}]),
            1: _rec(1, "bbb", prev=prev,
                    summaries=[{"n": 8, "finite": 8, "l2": 5.0e12}]),
        }
        v = integrity.vote(records)
        assert v["divergent"] and not v["ambiguous"]
        assert v["method"] == "drift" and v["outlier_rank"] == 1

    def test_two_voter_without_prev_is_ambiguous(self):
        v = integrity.vote({0: _rec(0, "aaa"), 1: _rec(1, "bbb")})
        assert v["divergent"] and v["ambiguous"]
        assert v["outlier_host"] is None

    def test_two_voter_comparable_drift_is_ambiguous(self):
        prev = {"digest": "old", "step": 4, "l2": [1.0], "finite": [8]}
        records = {
            0: _rec(0, "aaa", prev=prev,
                    summaries=[{"n": 8, "finite": 8, "l2": 1.5}]),
            1: _rec(1, "bbb", prev=prev,
                    summaries=[{"n": 8, "finite": 8, "l2": 2.0}]),
        }
        # Both drifted the same order of magnitude: one optimizer step
        # cannot be told from the other — nobody gets condemned.
        v = integrity.vote(records)
        assert v["divergent"] and v["ambiguous"]

    def test_two_voter_disagreeing_prev_is_ambiguous(self):
        # Disagreeing prev digests prove the corruption predates the
        # voted group: a stuck-at-corrupt state drifts ~zero vs its own
        # already-corrupt prev while the healthy rank's normal step
        # drift is nonzero — naming by drift would condemn the HEALTHY
        # rank. The verdict must stay ambiguous.
        records = {
            0: _rec(0, "aaa",  # healthy: normal optimizer-step drift
                    prev={"digest": "old0", "step": 4, "l2": [1.0],
                          "finite": [8]},
                    summaries=[{"n": 8, "finite": 8, "l2": 1.3}]),
            1: _rec(1, "bbb",  # stuck-at corrupt: ~zero drift
                    prev={"digest": "old1", "step": 4, "l2": [7.7],
                          "finite": [8]},
                    summaries=[{"n": 8, "finite": 8, "l2": 7.7}]),
        }
        v = integrity.vote(records)
        assert v["divergent"] and v["ambiguous"]
        assert v["outlier_rank"] is None and v["outlier_host"] is None

    def test_vote_latest_needs_a_complete_group(self):
        records = {0: _rec(0, "aaa", step=7), 1: _rec(1, "aaa", step=6)}
        assert integrity.vote_latest(records, world_size=2) is None

    def test_vote_latest_picks_newest_complete_group(self):
        records = {0: _rec(0, "aaa", step=6), 1: _rec(1, "bbb", step=6)}
        got = integrity.vote_latest(records, world_size=2)
        assert got is not None
        (gen, step), verdict = got
        assert (gen, step) == (1, 6)
        assert verdict["divergent"]

    def test_vote_latest_skips_malformed_records(self):
        records = {0: _rec(0, "aaa"), 1: _rec(1, "aaa"),
                   2: "not a record", 3: {"no": "step"}}
        got = integrity.vote_latest(records, world_size=2)
        assert got is not None and not got[1]["divergent"]


# ---------------------------------------------------------------------------
# The non-finite tripwire
# ---------------------------------------------------------------------------


def _traced_sgd_update(hvd, opt, grads_per_rank, params, momentum=False):
    """One opt.update inside shard_map; returns (updates, new_state)."""
    mesh = hvd.global_mesh()
    state0 = opt.init(params)

    def step(g):
        g = jax.tree.map(lambda a: a[0], g)
        updates, new_state = opt.update(g, state0, params)
        return updates, new_state

    f = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=P("hvd"),
                              out_specs=P(), check_vma=False))
    # Gradients must mirror the params pytree (optax state trees are
    # built from params); every caller uses a single-leaf params dict.
    return f(jax.tree.map(lambda _: grads_per_rank, params))


class TestFingerprintAlignment:
    """The voting plane survives membership changes: fingerprint gating
    and record steps must stay world-aligned across re-forms, or the
    first relaunch/spare promotion silently disarms detection (groups
    never complete again)."""

    def test_gate_follows_caller_step_not_process_count(self, monkeypatch):
        # A replacement rank's fresh process joins at the survivors'
        # commit counter: its FIRST maybe_fingerprint call must stage
        # when the world-aligned step is due, regardless of how many
        # times this process has been called before.
        monkeypatch.setenv("HOROVOD_INTEGRITY_INTERVAL", "2")
        integrity.reset_for_testing()
        p = {"w": np.ones(4, np.float32)}
        o = {"m": np.zeros(4, np.float32)}
        assert integrity.maybe_fingerprint(p, o, step=7) is None
        rec = integrity.maybe_fingerprint(p, o, step=8)
        assert rec is not None and rec["step"] == 8

    def test_tpustate_sync_realigns_commit_count(self, hvd, monkeypatch):
        from horovod_tpu.elastic import TpuState
        from horovod_tpu.elastic import state as state_mod

        params = {"w": jnp.ones(3)}
        st = TpuState(params=params,
                      opt_state=optax.sgd(0.1).init(params), epoch=0)
        st.commit()
        st.commit()
        assert st._commit_count == 3  # construction commit + 2
        # Simulate being the replacement in a re-formed world: rank 0
        # (a survivor) broadcasts its counter; ours must adopt it.
        monkeypatch.setattr(state_mod, "broadcast_parameters",
                            lambda t, root_rank=0: t)
        monkeypatch.setattr(
            state_mod, "broadcast_object",
            lambda obj: 41 if isinstance(obj, int) else obj)
        # Unarmed: no counter broadcast at all (sync()'s collective
        # schedule is part of the bit-for-bit-inert contract).
        monkeypatch.delenv("HOROVOD_INTEGRITY_INTERVAL", raising=False)
        st.sync()
        assert st._commit_count == 4  # local counter + sync's commit
        monkeypatch.setenv("HOROVOD_INTEGRITY_INTERVAL", "4")
        st.sync()
        # sync ends with a commit: the counter advanced FROM the
        # survivors' baseline, not from the local one.
        assert st._commit_count == 42


class TestNonfiniteTripwire:
    def test_unset_traces_without_isfinite(self, hvd, monkeypatch):
        from horovod_tpu.ops import fusion

        monkeypatch.delenv("HOROVOD_NONFINITE_ACTION", raising=False)
        assert fusion.nonfinite_action() is None
        opt = hvd.DistributedOptimizer(optax.sgd(0.1))
        params = {"w": jnp.ones(6)}
        mesh = hvd.global_mesh()
        state0 = opt.init(params)

        def step(g):
            g = jax.tree.map(lambda a: a[0], g)
            return opt.update(g, state0, params)

        jaxpr = str(jax.make_jaxpr(jax.shard_map(
            step, mesh=mesh, in_specs=P("hvd"), out_specs=P(),
            check_vma=False))(np.ones((8, 6), np.float32)))
        # The inertness contract at the HLO level: no guard anywhere.
        assert "is_finite" not in jaxpr

    def test_skip_traces_with_isfinite(self, hvd, monkeypatch):
        monkeypatch.setenv("HOROVOD_NONFINITE_ACTION", "skip")
        opt = hvd.DistributedOptimizer(optax.sgd(0.1))
        params = {"w": jnp.ones(7)}
        mesh = hvd.global_mesh()
        state0 = opt.init(params)

        def step(g):
            g = jax.tree.map(lambda a: a[0], g)
            return opt.update(g, state0, params)

        jaxpr = str(jax.make_jaxpr(jax.shard_map(
            step, mesh=mesh, in_specs=P("hvd"), out_specs=P(),
            check_vma=False))(np.ones((8, 7), np.float32)))
        assert "is_finite" in jaxpr

    def test_skip_zeroes_update_and_freezes_state(self, hvd, monkeypatch):
        monkeypatch.setenv("HOROVOD_NONFINITE_ACTION", "skip")
        opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9))
        params = {"w": jnp.ones(5)}
        bad = np.ones((8, 5), np.float32)
        bad[3, 2] = np.nan  # one rank's gradient poisons the allreduce
        updates, new_state = _traced_sgd_update(hvd, opt, bad, params)
        jax.block_until_ready(updates)
        np.testing.assert_array_equal(np.asarray(updates["w"]),
                                      np.zeros(5, np.float32))
        # The momentum trace did NOT advance: the step never happened.
        trace = jax.tree.leaves(new_state)[0]
        np.testing.assert_array_equal(np.asarray(trace), 0.0)
        time.sleep(0.2)  # callback flush
        assert integrity.summary()["nonfinite_detections"] >= 1

    def test_clean_step_unaffected_by_armed_tripwire(self, hvd,
                                                     monkeypatch):
        monkeypatch.setenv("HOROVOD_NONFINITE_ACTION", "skip")
        opt = hvd.DistributedOptimizer(optax.sgd(0.1))
        params = {"w": jnp.ones(5)}
        good = np.ones((8, 5), np.float32)
        updates, _ = _traced_sgd_update(hvd, opt, good, params)
        np.testing.assert_allclose(np.asarray(updates["w"]), -0.1,
                                   rtol=1e-6)

    def test_warn_counts_but_does_not_guard(self, hvd, monkeypatch):
        monkeypatch.setenv("HOROVOD_NONFINITE_ACTION", "warn")
        opt = hvd.DistributedOptimizer(optax.sgd(0.1))
        params = {"w": jnp.ones(9)}
        bad = np.ones((8, 9), np.float32)
        bad[0, 0] = np.inf
        updates, _ = _traced_sgd_update(hvd, opt, bad, params)
        jax.block_until_ready(updates)
        assert not np.isfinite(np.asarray(updates["w"])).all()
        time.sleep(0.2)
        assert integrity.summary()["nonfinite_detections"] >= 1

    def test_sharded_skip_is_rank_identical(self, hvd, monkeypatch):
        monkeypatch.setenv("HOROVOD_NONFINITE_ACTION", "skip")
        dp = hvd.data_parallel
        opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                                       sync_mode="sharded")

        def loss_fn(params, batch):
            return jnp.mean((batch * params["w"]).sum(-1))

        params = {"w": jnp.ones(6)}
        step = dp.make_train_step(loss_fn, opt, donate=False)
        p = dp.replicate(params)
        s = dp.shard_state(opt.init(params))
        bad = np.ones((8, 6), np.float32)
        bad[5, 1] = np.nan  # poisons ONE rank's reduce-scattered shard
        p1, s1, _ = step(p, s, jnp.asarray(bad))
        jax.block_until_ready(p1)
        # Every rank skipped identically: params unchanged everywhere.
        np.testing.assert_array_equal(np.asarray(jax.device_get(p1)["w"]),
                                      np.asarray(jax.device_get(p)["w"]))
        good = np.ones((8, 6), np.float32)
        p2, s2, _ = step(p1, s1, jnp.asarray(good))
        # ...and the next clean step advances from the unpoisoned state.
        assert not np.array_equal(np.asarray(jax.device_get(p2)["w"]),
                                  np.asarray(jax.device_get(p1)["w"]))
        assert np.isfinite(np.asarray(jax.device_get(p2)["w"])).all()

    def test_note_nonfinite_burst_dedup(self):
        # One step delivers every local shard's index once: only the
        # first callback of a burst counts the step.
        for idx in range(4):
            integrity.note_nonfinite("warn", False, idx)
        assert integrity.summary()["nonfinite_detections"] == 1
        for idx in range(4):  # the next step's burst
            integrity.note_nonfinite("warn", False, idx)
        assert integrity.summary()["nonfinite_detections"] == 2
        for idx in range(4):  # a clean step does not count
            integrity.note_nonfinite("warn", True, idx)
        assert integrity.summary()["nonfinite_detections"] == 2

    def test_abort_action_arms_coordinated_abort(self):
        integrity.note_nonfinite("abort", False, 0)
        try:
            with pytest.raises(HorovodInternalError):
                abort.raise_if_aborted()
        finally:
            abort.reset()

    def test_abort_action_posts_kv_record(self, kv_server, monkeypatch):
        """The abort action must POST the coordinated abort, not just arm
        locally: callback delivery is best-effort per rank, so a rank
        whose callback was dropped relies on the abort/<generation>
        record to unblock within one abort-poll interval."""
        monkeypatch.setenv("HOROVOD_RENDEZVOUS_ADDR", "127.0.0.1")
        monkeypatch.setenv("HOROVOD_RENDEZVOUS_PORT", str(kv_server.port))
        try:
            integrity.note_nonfinite("abort", False, 0)
            rec = kv_server.abort_record(0)
            assert rec is not None
            assert "non-finite" in json.loads(rec)["reason"]
        finally:
            abort.reset()


# ---------------------------------------------------------------------------
# Int8 quantization hardening
# ---------------------------------------------------------------------------


class TestQuantizationNonfiniteHardening:
    def _allreduce(self, hvd, x_per_rank):
        from horovod_tpu.ops.quantization import int8_allreduce_flat

        mesh = hvd.global_mesh()

        def f(x):
            return int8_allreduce_flat(x[0], "hvd", 8, op="average")

        return np.asarray(jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=P("hvd"), out_specs=P(),
            check_vma=False))(jnp.asarray(x_per_rank)))

    def _rs_ag(self, hvd, x_per_rank):
        from horovod_tpu.ops.quantization import (
            int8_fused_allgather_shards,
            int8_fused_reducescatter,
        )

        mesh = hvd.global_mesh()

        def f(x):
            t = x[0]
            shards = int8_fused_reducescatter([t], "hvd", 8, op="average")
            return int8_fused_allgather_shards(shards, [t], "hvd", 8)[0]

        return np.asarray(jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=P("hvd"), out_specs=P(),
            check_vma=False))(jnp.asarray(x_per_rank)))

    @pytest.mark.parametrize("poison", [np.nan, np.inf, -np.inf, 1e39])
    def test_allreduce_never_emits_garbage_blocks(self, hvd, poison):
        from horovod_tpu.ops.quantization import BLOCK

        m = 2 * BLOCK
        rng = np.random.RandomState(0)
        clean = rng.randn(8, m).astype(np.float32)
        poisoned = clean.copy()
        poisoned[2, 7] = poison  # one element of block 0 on one rank
        want = self._allreduce(hvd, clean)
        got = self._allreduce(hvd, poisoned)
        # The wire never amplifies: every output element is finite...
        assert np.isfinite(got).all()
        # ...and blocks the poison never touched are bit-identical to
        # the clean run (a NaN used to zero the whole block's scale).
        np.testing.assert_array_equal(got[BLOCK:], want[BLOCK:])

    @pytest.mark.parametrize("poison", [np.nan, np.inf, 1e39])
    def test_rs_ag_halves_never_emit_garbage_blocks(self, hvd, poison):
        from horovod_tpu.ops.quantization import BLOCK

        m = 8 * BLOCK  # one whole block per rank-owned shard
        rng = np.random.RandomState(1)
        clean = rng.randn(8, m).astype(np.float32)
        poisoned = clean.copy()
        poisoned[4, 3] = poison
        want = self._rs_ag(hvd, clean)
        got = self._rs_ag(hvd, poisoned)
        assert np.isfinite(got).all()
        # The poisoned element lives in rank 0's owned shard (element
        # 3); every OTHER rank's gathered shard matches the clean run.
        np.testing.assert_array_equal(got[BLOCK:], want[BLOCK:])

    def test_nan_contributes_zero_not_scale_poison(self, hvd):
        from horovod_tpu.ops.quantization import BLOCK

        x = np.ones((8, BLOCK), np.float32)
        x[0, 0] = np.nan
        got = self._allreduce(hvd, x)
        # The other 7 ranks' 1.0 average through: ~7/8, NOT NaN and NOT
        # zero (the old behavior dequantized the whole block to garbage).
        np.testing.assert_allclose(got[1:], 1.0, atol=0.02)
        np.testing.assert_allclose(got[0], 7.0 / 8.0, atol=0.02)

    @pytest.mark.parametrize("poison", [np.nan, np.inf, -np.inf])
    def test_armed_allreduce_propagates_poison(self, hvd, monkeypatch,
                                               poison):
        from horovod_tpu.ops.quantization import BLOCK

        # With the tripwire ARMED, saturation would silently disable the
        # detector (it inspects the REDUCED gradients, downstream of the
        # wire): the poisoned block must instead dequantize non-finite
        # on every rank, exactly as compression=none propagates it.
        monkeypatch.setenv("HOROVOD_NONFINITE_ACTION", "skip")
        m = 2 * BLOCK
        rng = np.random.RandomState(2)
        clean = rng.randn(8, m).astype(np.float32)
        poisoned = clean.copy()
        poisoned[2, 7] = poison  # one element of block 0 on one rank
        want = self._allreduce(hvd, clean)
        got = self._allreduce(hvd, poisoned)
        assert not np.isfinite(got[:BLOCK]).any()
        # Damage stays confined: untouched blocks match the clean run.
        np.testing.assert_array_equal(got[BLOCK:], want[BLOCK:])

    def test_armed_rs_ag_halves_propagate_poison(self, hvd, monkeypatch):
        from horovod_tpu.ops.quantization import BLOCK

        monkeypatch.setenv("HOROVOD_NONFINITE_ACTION", "warn")
        m = 8 * BLOCK  # one whole block per rank-owned shard
        rng = np.random.RandomState(3)
        clean = rng.randn(8, m).astype(np.float32)
        poisoned = clean.copy()
        poisoned[4, 3] = np.nan
        want = self._rs_ag(hvd, clean)
        got = self._rs_ag(hvd, poisoned)
        assert not np.isfinite(got[:BLOCK]).any()
        np.testing.assert_array_equal(got[BLOCK:], want[BLOCK:])

    def test_armed_skip_fires_through_int8_wire(self, hvd, monkeypatch):
        # End-to-end: int8 compression + skip — the tripwire must see
        # the poison through the quantized wire and drop the step.
        monkeypatch.setenv("HOROVOD_NONFINITE_ACTION", "skip")
        opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                                       compression=hvd.Compression.int8)
        params = {"w": jnp.ones(5)}
        bad = np.ones((8, 5), np.float32)
        bad[3, 2] = np.nan
        updates, new_state = _traced_sgd_update(hvd, opt, bad, params)
        jax.block_until_ready(updates)
        np.testing.assert_array_equal(np.asarray(updates["w"]),
                                      np.zeros(5, np.float32))
        trace = jax.tree.leaves(new_state)[0]
        np.testing.assert_array_equal(np.asarray(trace), 0.0)

    def test_armed_clean_int8_step_unaffected(self, hvd, monkeypatch):
        monkeypatch.setenv("HOROVOD_NONFINITE_ACTION", "skip")
        opt = hvd.DistributedOptimizer(optax.sgd(0.1),
                                       compression=hvd.Compression.int8)
        params = {"w": jnp.ones(5)}
        good = np.ones((8, 5), np.float32)
        updates, _ = _traced_sgd_update(hvd, opt, good, params)
        np.testing.assert_allclose(np.asarray(updates["w"]), -0.1,
                                   atol=0.02)


# ---------------------------------------------------------------------------
# Checkpoint corruption edges
# ---------------------------------------------------------------------------


class TestCheckpointCorruptionEdges:
    def _save_two(self, tmp_path, hvd):
        from horovod_tpu.checkpoint import save_on_rank_0

        path = str(tmp_path / "ckpt.pkl")
        save_on_rank_0(path, {"step": 1})
        save_on_rank_0(path, {"step": 2})
        return path

    def test_truncated_footer_is_corrupt_not_silent(self, tmp_path, hvd):
        from horovod_tpu.checkpoint import _CKPT_MAGIC, _read_verified

        path = self._save_two(tmp_path, hvd)
        blob = open(path, "rb").read()
        # Clip 4 digest bytes but keep the magic: the footer parses, the
        # sha cannot match — this must be a LOUD integrity failure, not
        # a silent partial load.
        assert blob.endswith(_CKPT_MAGIC)
        torn = blob[:-len(_CKPT_MAGIC) - 4] + _CKPT_MAGIC
        open(path, "wb").write(torn)
        with pytest.raises(CheckpointCorruptError):
            _read_verified(path)

    def test_atomic_read_yields_tagged_slots(self, tmp_path, hvd):
        path = self._save_two(tmp_path, hvd)
        slots = list(checkpoint.atomic_read(path))
        assert [which for _, which in slots] == ["current", "prev"]
        # The digest-verify consumer pattern every atomic_read caller
        # uses: rot the current slot, the first GOOD candidate is prev.
        good_digest = checkpoint.payload_digest(slots[1][0])
        blob = bytearray(slots[0][0])
        blob[5] ^= 0xFF
        open(path, "wb").write(bytes(blob))
        accepted = None
        for data, which in checkpoint.atomic_read(path):
            if checkpoint.payload_digest(data) == good_digest:
                accepted = which
                break
        assert accepted == "prev"

    def test_bitrot_current_falls_back_to_intact_prev(self, tmp_path,
                                                      hvd):
        from horovod_tpu.checkpoint import _read_verified

        path = self._save_two(tmp_path, hvd)
        blob = bytearray(open(path, "rb").read())
        blob[3] ^= 0x10
        open(path, "wb").write(bytes(blob))
        with pytest.raises(CheckpointCorruptError):
            _read_verified(path)
        assert _read_verified(path + ".prev") == {"step": 1}

    def test_both_slots_corrupt_is_terminal(self, tmp_path, hvd):
        from horovod_tpu.checkpoint import _read_verified, \
            load_and_broadcast

        path = self._save_two(tmp_path, hvd)
        for p in (path, path + ".prev"):
            blob = bytearray(open(p, "rb").read())
            blob[3] ^= 0x10
            open(p, "wb").write(bytes(blob))
        # Every slot fails its own checksum: each read raises — the
        # durable rung can never install either record...
        with pytest.raises(CheckpointCorruptError):
            _read_verified(path)
        with pytest.raises(CheckpointCorruptError):
            _read_verified(path + ".prev")
        # ...and resume degrades to missing-checkpoint semantics.
        assert load_and_broadcast(path) is None

    def test_missing_both_slots_reads_nothing(self, tmp_path):
        assert list(checkpoint.atomic_read(
            str(tmp_path / "never-written.pkl"))) == []


# ---------------------------------------------------------------------------
# The KV plane: /integrity, the heartbeat piggyback, and the quarantine
# ---------------------------------------------------------------------------


@pytest.fixture()
def kv_server():
    server = RendezvousServer()
    server.start()
    yield server
    server.stop()


def _put_heartbeat(client, host, rank, record):
    body = {"rank": str(rank), "step": 1, "commits": 1,
            "integrity": record}
    client.put("heartbeat", host, json.dumps(body).encode())


class TestIntegrityKvPlane:
    def test_get_integrity_cold_serves_no_records(self, kv_server):
        import urllib.request

        with urllib.request.urlopen(
                f"http://127.0.0.1:{kv_server.port}/integrity",
                timeout=5) as r:
            view = json.loads(r.read().decode())
        assert view["status"] == "no_records"
        assert view["records"] == {} and view["vote"] is None

    @pytest.mark.parametrize("divergent", [True, False])
    def test_records_ride_heartbeats_and_vote_renders(self, kv_server,
                                                      divergent):
        """Two ranks' fingerprints, collected and voted over HTTP: two
        digests that differ, and the steady state the plane certifies:
        ``make_record`` of one state on both ranks votes clean."""
        import urllib.request

        if divergent:
            records = [_rec(0, "aaa", step=6), _rec(1, "bbb", step=6)]
        else:
            state = ({"w": np.arange(8, dtype=np.float32)},
                     {"m": np.zeros(8, dtype=np.float32)})
            records = [integrity.make_record(
                *state, step=6, rank=r, host=f"host{r}", generation=1)
                for r in (0, 1)]
        client = KVClient("127.0.0.1", kv_server.port)
        _put_heartbeat(client, "hostA", 0, records[0])
        _put_heartbeat(client, "hostB", 1, records[1])
        kv_server.set_cluster_info(world_np=2)
        records = kv_server.integrity_records()
        assert sorted(records) == [0, 1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{kv_server.port}/integrity",
                timeout=5) as r:
            view = json.loads(r.read().decode())
        assert view["status"] == "ok"
        assert sorted(view["records"]) == ["0", "1"]
        assert all(rec["digest"] for rec in view["records"].values())
        assert view["vote"] is not None
        assert view["vote"]["divergent"] is divergent
        assert view["vote"]["voters"] == 2
        assert view["vote"]["group"][1] == 6

    def test_malformed_heartbeats_tolerated(self, kv_server):
        client = KVClient("127.0.0.1", kv_server.port)
        client.put("heartbeat", "hostA", b"not json")
        client.put("heartbeat", "hostB",
                   json.dumps({"rank": "1"}).encode())  # no integrity key
        client.put("heartbeat", "hostC", json.dumps(
            {"rank": "2", "integrity": {"rank": "NaN?"}}).encode())
        assert kv_server.integrity_records() == {}

    def test_stale_zombie_record_cannot_shadow_fresh_one(self, kv_server):
        client = KVClient("127.0.0.1", kv_server.port)
        fresh = _rec(0, "aaa", step=9)
        fresh["t"] = 100.0
        stale = _rec(0, "zzz", step=3)
        stale["t"] = 1.0
        _put_heartbeat(client, "hostA", 0, fresh)
        _put_heartbeat(client, "hostZombie", 0, stale)
        records = kv_server.integrity_records()
        assert records[0]["digest"] == "aaa"

    def test_quarantine_fences_puts_and_evicts_current_only(
            self, kv_server):
        from urllib.error import HTTPError

        client = KVClient("127.0.0.1", kv_server.port,
                          generation_fn=lambda: 0)
        rep = peercheck.PeerReplicator(
            client=client, rank=1, world_size_fn=lambda: 2,
            generation_fn=lambda: 0)
        assert rep.replicate(b"step-one" * 8, step=1)
        assert rep.replicate(b"step-two" * 8, step=2)
        kv_server.quarantine_rank(1, "hostB", generation=0, step=2)
        # The corrupt CURRENT record is evicted; .prev (the last commit
        # the vote did not condemn) survives for assembly fall-back.
        assert client.get(PEERSTATE_SCOPE, "1") is None
        prev = peercheck.decode_record(
            client.get(PEERSTATE_SCOPE, "1.prev"))
        assert prev.step == 1
        # Same-generation PUTs are fenced: a corrupt shard must never
        # displace a good replica.
        with pytest.raises(HTTPError) as e:
            client.put(PEERSTATE_SCOPE, "1",
                       peercheck.encode_record(peercheck.ReplicaRecord(
                           rank=1, step=3, generation=0, world_size=2,
                           payload=b"corrupt-replay" * 8)))
        assert e.value.code == 409
        # Headerless writes from the quarantined rank are fenced too.
        bare = KVClient("127.0.0.1", kv_server.port)
        with pytest.raises(HTTPError) as e2:
            bare.put(PEERSTATE_SCOPE, "1",
                     peercheck.encode_record(peercheck.ReplicaRecord(
                         rank=1, step=3, generation=0, world_size=2,
                         payload=b"unfenced-replay" * 8)))
        assert e2.value.code == 409

    def test_newer_generation_write_lifts_quarantine(self, kv_server,
                                                     monkeypatch):
        monkeypatch.setenv("HOROVOD_INTEGRITY_INTERVAL", "1")
        client0 = KVClient("127.0.0.1", kv_server.port,
                           generation_fn=lambda: 0)
        rep = peercheck.PeerReplicator(
            client=client0, rank=1, world_size_fn=lambda: 2,
            generation_fn=lambda: 0)
        assert rep.replicate(b"old-world" * 8, step=1)
        kv_server.quarantine_rank(1, "hostB", generation=0, step=1)
        kv_server.seed(generation=1)
        client1 = KVClient("127.0.0.1", kv_server.port,
                           generation_fn=lambda: 1)
        # The re-formed world reuses the rank id for a healthy worker:
        # a strictly-newer-generation write lifts the fence.
        client1.put(PEERSTATE_SCOPE, "1",
                    peercheck.encode_record(peercheck.ReplicaRecord(
                        rank=1, step=2, generation=1, world_size=2,
                        payload=b"new-world" * 8)))
        rec = peercheck.decode_record(client1.get(PEERSTATE_SCOPE, "1"))
        assert rec.generation == 1 and rec.step == 2
        # The lift is a TOMBSTONE, not a delete: the condemned range
        # still filters peer-rung assembly (a failure before the new
        # generation's replica group completes must not fall back to
        # the proven-corrupt old records), while the active-quarantine
        # gauge drops back to zero.
        q = rep.quarantined()
        assert q.get("1", {}).get("lifted") is True
        old = peercheck.ReplicaRecord(rank=1, step=1, generation=0,
                                      world_size=2, payload=b"x" * 8)
        assert peercheck._condemned(old, q["1"])
        assert not peercheck._condemned(rec, q["1"])  # new owner passes
        parsed = hvd_metrics.validate_prometheus_text(
            kv_server.metrics_text())
        assert (parsed["hvd_integrity_quarantined_ranks"]["samples"]
                == [({}, 0.0)])

    def test_lifted_tombstone_still_live_vote_fences(self, kv_server,
                                                     monkeypatch):
        """A rank id re-condemned in a later generation must not go
        unfenced during the vote-to-driver-tick window just because its
        earlier quarantine was tombstoned: the lifted entry falls
        through to the live-vote fence instead of short-circuiting."""
        from urllib.error import HTTPError

        monkeypatch.setenv("HOROVOD_INTEGRITY_INTERVAL", "1")
        client0 = KVClient("127.0.0.1", kv_server.port,
                           generation_fn=lambda: 0)
        rep = peercheck.PeerReplicator(
            client=client0, rank=1, world_size_fn=lambda: 3,
            generation_fn=lambda: 0)
        assert rep.replicate(b"old-world" * 8, step=1)
        kv_server.quarantine_rank(1, "hostB", generation=0, step=1)
        kv_server.seed(generation=1)
        kv_server.set_cluster_info(world_np=3)
        client1 = KVClient("127.0.0.1", kv_server.port,
                           generation_fn=lambda: 1)
        client1.put(PEERSTATE_SCOPE, "1",
                    peercheck.encode_record(peercheck.ReplicaRecord(
                        rank=1, step=2, generation=1, world_size=3,
                        payload=b"new-world" * 8)))  # lifts -> tombstone
        # Re-condemnation in the NEW generation: a complete unambiguous
        # divergent vote over the heartbeat fingerprints names rank 1.
        for r, d in ((0, "aaa"), (1, "bad"), (2, "aaa")):
            _put_heartbeat(client1, f"h{r}", r,
                           _rec(r, d, step=7, generation=1))
        with pytest.raises(HTTPError) as e:
            client1.put(PEERSTATE_SCOPE, "1",
                        peercheck.encode_record(peercheck.ReplicaRecord(
                            rank=1, step=3, generation=1, world_size=3,
                            payload=b"corrupt" * 8)))
        assert e.value.code == 409

    def test_assembly_drops_quarantined_local_pool_copies(
            self, kv_server, monkeypatch):
        """The inverse proof's worker half: copies of a condemned rank's
        records already pulled into a SURVIVOR's local pool (checksums
        self-consistent — the KV eviction cannot reach them) are dropped
        at assembly, falling back to the last uncondemned commit."""
        monkeypatch.setenv("HOROVOD_INTEGRITY_INTERVAL", "1")
        monkeypatch.setenv("HOROVOD_RENDEZVOUS_ADDR", "127.0.0.1")
        monkeypatch.setenv("HOROVOD_RENDEZVOUS_PORT",
                           str(kv_server.port))
        client = KVClient("127.0.0.1", kv_server.port)
        survivor = peercheck.PeerReplicator(
            client=client, rank=0, world_size_fn=lambda: 2,
            generation_fn=lambda: 0)
        corrupt = peercheck.PeerReplicator(
            client=client, rank=1, world_size_fn=lambda: 2,
            generation_fn=lambda: 0)
        for step, payload in ((1, b"good-1"), (2, b"good-2")):
            assert survivor.replicate(payload + b"-r0" * 8, step=step)
            assert corrupt.replicate(payload + b"-r1" * 8, step=step)
            survivor._pull_neighbors(client)
        # Step 3: rank 1's snapshot is corrupt (self-consistent record)
        # and the survivor already pulled it before any vote landed.
        assert survivor.replicate(b"good-3-r0" * 8, step=3)
        assert corrupt.replicate(b"CORRUPT-r1" * 8, step=3)
        survivor._pull_neighbors(client)
        got = survivor.assemble()
        assert [r.step for r in got] == [3, 3]  # corruption invisible
        kv_server.quarantine_rank(1, "hostB", generation=0, step=3)
        got = survivor.assemble()
        # The newest UNcondemned complete set: both ranks at step 2.
        assert [r.step for r in got] == [2, 2]
        assert got[1].payload == b"good-2" + b"-r1" * 8

    def test_condemned_range_spans_backdated_generation(self):
        """A vote that back-dates the corruption to a PRIOR world
        generation's fingerprint (a re-form landed between the two
        intervals) must condemn that generation's replica records too —
        otherwise the known-bad prior-generation record stays eligible
        for peer-rung assembly."""
        from types import SimpleNamespace as R

        entry = {"generation": 3, "step": 7,
                 "from_generation": 2, "from_step": 5}
        rec = lambda g, s: R(generation=g, step=s)  # noqa: E731
        assert peercheck._condemned(rec(2, 5), entry)  # back-dated start
        assert peercheck._condemned(rec(2, 9), entry)
        assert peercheck._condemned(rec(3, 7), entry)  # the vote's group
        assert not peercheck._condemned(rec(2, 4), entry)  # pre-corruption
        assert not peercheck._condemned(rec(4, 0), entry)  # new owner
        # No back-date fields (the common case): the old same-generation
        # semantics exactly.
        legacy = {"generation": 3, "step": 7}
        assert peercheck._condemned(rec(3, 7), legacy)
        assert peercheck._condemned(rec(3, 9), legacy)
        assert not peercheck._condemned(rec(3, 6), legacy)
        assert not peercheck._condemned(rec(2, 9), legacy)

    def test_assembly_filter_inert_when_plane_unarmed(self, kv_server,
                                                      monkeypatch):
        monkeypatch.delenv("HOROVOD_INTEGRITY_INTERVAL", raising=False)
        client = KVClient("127.0.0.1", kv_server.port)
        rep = peercheck.PeerReplicator(
            client=client, rank=0, world_size_fn=lambda: 1,
            generation_fn=lambda: 0)
        assert rep.quarantined() == {}  # no extra request, no filter
        assert rep.replicate(b"solo" * 8, step=1)
        assert [r.step for r in rep.assemble()] == [1]

    def test_scrape_zero_materializes_integrity_instruments(
            self, kv_server):
        parsed = hvd_metrics.validate_prometheus_text(
            kv_server.metrics_text())
        div = parsed["hvd_integrity_divergence_total"]["samples"]
        assert ({}, 0.0) in [(l, v) for l, v in div]
        quarantined = parsed["hvd_integrity_quarantined_ranks"]["samples"]
        assert quarantined == [({}, 0.0)]
        kv_server.record_integrity_divergence("hostB")
        kv_server.quarantine_rank(1, "hostB", generation=0, step=5)
        parsed = hvd_metrics.validate_prometheus_text(
            kv_server.metrics_text())
        div = {tuple(sorted(l.items())): v for l, v in
               parsed["hvd_integrity_divergence_total"]["samples"]}
        assert div[()] == 1.0
        assert div[(("host", "hostB"),)] == 1.0
        assert (parsed["hvd_integrity_quarantined_ranks"]["samples"]
                == [({}, 1.0)])

    def test_worker_heartbeat_carries_staged_record(self, kv_server,
                                                    monkeypatch):
        from horovod_tpu.runner.elastic import worker as elastic_worker

        monkeypatch.setenv("HOROVOD_INTEGRITY_INTERVAL", "1")
        monkeypatch.setenv("HOROVOD_RENDEZVOUS_ADDR", "127.0.0.1")
        monkeypatch.setenv("HOROVOD_RENDEZVOUS_PORT", str(kv_server.port))
        monkeypatch.setenv("HOROVOD_HOSTNAME", "sdc-host")
        monkeypatch.setenv("HOROVOD_RANK", "0")
        rec = integrity.maybe_fingerprint(
            {"w": np.ones(4, np.float32)}, None, 1)
        assert rec is not None
        ctx = elastic_worker.ElasticWorkerContext()
        assert ctx.send_heartbeat()
        records = kv_server.integrity_records()
        assert records[0]["digest"] == rec["digest"]
        # A PARKED spare has no world rank: it must ship nothing (its
        # launch-env rank label would collide with a live rank's).
        ctx.parked = True
        kv_server.clear_heartbeat("sdc-host")
        assert ctx.send_heartbeat()
        assert kv_server.integrity_records() == {}

    def test_heartbeat_unarmed_has_no_integrity_key(self, kv_server,
                                                    monkeypatch):
        from horovod_tpu.runner.elastic import worker as elastic_worker

        monkeypatch.delenv("HOROVOD_INTEGRITY_INTERVAL", raising=False)
        monkeypatch.setenv("HOROVOD_RENDEZVOUS_ADDR", "127.0.0.1")
        monkeypatch.setenv("HOROVOD_RENDEZVOUS_PORT", str(kv_server.port))
        monkeypatch.setenv("HOROVOD_HOSTNAME", "plain-host")
        ctx = elastic_worker.ElasticWorkerContext()
        assert ctx.send_heartbeat()
        payload = json.loads(kv_server.heartbeat_payload("plain-host"))
        assert "integrity" not in payload


# ---------------------------------------------------------------------------
# Policy integrity-strikes channel
# ---------------------------------------------------------------------------


class TestPolicyIntegrityStrikes:
    """The strikes channel is a CORRECTNESS channel: it must be able to
    drain a corrupting host without `HOROVOD_TARGET_GOODPUT` configured
    (corruption needs no throughput arithmetic to be worth acting on)."""

    def _controller(self, monkeypatch, target=None, strikes="2"):
        from horovod_tpu.elastic.policy import PolicyController

        if target is None:
            monkeypatch.delenv("HOROVOD_TARGET_GOODPUT", raising=False)
        else:
            monkeypatch.setenv("HOROVOD_TARGET_GOODPUT", target)
        monkeypatch.setenv("HOROVOD_POLICY_INTEGRITY_STRIKES", strikes)
        return PolicyController(min_np=1)

    def test_strikes_drain_without_goodput_slo(self, monkeypatch):
        ctl = self._controller(monkeypatch)
        assert not ctl.enabled and ctl.armed
        ctl.note_integrity("h1")
        assert ctl.decide(["h0", "h1"], spares_ready=1) is None  # 1 < 2
        ctl.note_integrity("h1")
        d = ctl.decide(["h0", "h1"], spares_ready=1)
        assert d is not None and d.action == "drain" and d.host == "h1"
        assert d.predicted.get("slo_bypassed") is True

    def test_strikes_respect_replacement_availability(self, monkeypatch):
        ctl = self._controller(monkeypatch)
        ctl.note_integrity("h1")
        ctl.note_integrity("h1")
        # Nobody to backfill below min_np: hold (the KV fences stay up).
        assert ctl.decide(["h1"], spares_ready=0) is None

    def test_strikes_only_never_runs_slo_channel(self, monkeypatch):
        ctl = self._controller(monkeypatch)
        # Straggler-looking evidence with no strikes: the SLO channel
        # must stay dark when only the strikes knob armed the controller.
        ctl.observe({"ranks": {"1": {"host": "h1",
                                     "mean_lateness_s": 9.9}}},
                    {}, ["h0", "h1"])
        assert ctl.decide(["h0", "h1"], spares_ready=1) is None

    def test_strikes_pruned_when_host_leaves_world(self, monkeypatch):
        """Strikes live for the host's MEMBERSHIP. In strikes-only
        arming observe() — the usual pruning site — never runs, so
        decide() must prune departed hosts itself: a drained host
        re-entering through the spare tier must not be instantly
        re-drained on strikes from its previous membership."""
        ctl = self._controller(monkeypatch)
        ctl.note_integrity("h1")
        ctl.note_integrity("h1")
        # h1 was drained out of the world: the next tick prunes it.
        assert ctl.decide(["h0", "h2"], spares_ready=1) is None
        assert ctl.integrity_strike_count("h1") == 0
        # Re-promotion starts with a clean record.
        assert ctl.decide(["h0", "h1"], spares_ready=1) is None

    def test_unarmed_without_either_knob(self, monkeypatch):
        from horovod_tpu.elastic.policy import PolicyController

        monkeypatch.delenv("HOROVOD_TARGET_GOODPUT", raising=False)
        monkeypatch.delenv("HOROVOD_POLICY_INTEGRITY_STRIKES",
                           raising=False)
        ctl = PolicyController(min_np=1)
        assert not ctl.armed
        ctl.note_integrity("h1")
        ctl.note_integrity("h1")
        assert ctl.decide(["h0", "h1"], spares_ready=1) is None


class TestDriverContinuityResolution:
    def _driver(self, monkeypatch):
        from horovod_tpu.runner.elastic.discovery import (
            FixedHostDiscovery,
        )
        from horovod_tpu.runner.elastic.driver import ElasticDriver
        from horovod_tpu.runner.hosts import HostInfo
        from horovod_tpu.runner.launch import Settings

        monkeypatch.delenv("HOROVOD_DRIVER_STATE_DIR", raising=False)
        settings = Settings(
            num_proc=2, hosts=[], command=["true"], elastic=True,
            min_np=1, max_np=2, discovery_script=None)
        drv = ElasticDriver(
            settings, discovery=FixedHostDiscovery(
                [HostInfo("hostA", 1), HostInfo("hostB", 1)]))
        drv._world_hosts = [HostInfo("hostA", 1), HostInfo("hostB", 1)]
        monkeypatch.setattr(drv._server, "quarantine_rank",
                            lambda *a, **k: None)
        monkeypatch.setattr(drv._server,
                            "record_integrity_divergence",
                            lambda h: None)
        monkeypatch.setattr(drv._server, "trace_payload", lambda h: None)
        return drv

    @staticmethod
    def _rec(rank, host, step, digest, prev=None, nonfinite=False):
        n = 4
        return {"rank": rank, "host": host, "generation": 0,
                "step": step, "sync_mode": "allreduce",
                "digest": digest, "prev": prev,
                "summaries": [{"n": n,
                               "finite": n - (1 if nonfinite else 0),
                               "l2": 1.0}],
                "t": 0.0}

    def test_two_voter_persistent_corruption_accumulates_strikes(
            self, monkeypatch):
        """With 2 voters a persistent corruption makes every vote after
        the first ambiguous (the outlier's prev — its own condemned
        record — disagrees with the peer's), which would pin strikes
        below HOROVOD_INTEGRITY_CONFIRMATIONS>=2 forever. The driver's
        continuity resolution attributes such a vote to the previously
        named rank when its prev IS the exact condemned digest."""
        monkeypatch.setenv("HOROVOD_INTEGRITY_INTERVAL", "1")
        monkeypatch.setenv("HOROVOD_INTEGRITY_ACTION", "warn")
        monkeypatch.setenv("HOROVOD_INTEGRITY_CONFIRMATIONS", "2")
        drv = self._driver(monkeypatch)
        hbv = [1]
        monkeypatch.setattr(drv._server, "heartbeat_version",
                            lambda: hbv[0])
        recs = {0: self._rec(0, "hostA", 1, "DA"),
                1: self._rec(1, "hostB", 1, "DX", nonfinite=True)}
        monkeypatch.setattr(
            drv._server, "integrity_vote_cached",
            lambda: (recs, integrity.vote_latest(recs, 2)))
        drv._last_integrity_tick = -1e9
        drv._integrity_tick()
        assert drv._integrity_strikes.get("hostB") == 1
        assert drv._last_outlier == (1, "DX")
        # Next interval: clean summaries, still-diverging digests,
        # DISAGREEING prevs — plain vote() is ambiguous, but rank 1's
        # prev is the condemned digest: continuity names it again.
        recs = {0: self._rec(0, "hostA", 2, "DB",
                             prev={"digest": "DA", "step": 1}),
                1: self._rec(1, "hostB", 2, "DY",
                             prev={"digest": "DX", "step": 1})}
        monkeypatch.setattr(
            drv._server, "integrity_vote_cached",
            lambda: (recs, integrity.vote_latest(recs, 2)))
        hbv[0] = 2
        drv._last_integrity_tick = -1e9
        drv._integrity_tick()
        assert drv._integrity_strikes.get("hostB") == 2

    def test_ambiguous_without_memory_stays_ambiguous(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_INTEGRITY_INTERVAL", "1")
        monkeypatch.setenv("HOROVOD_INTEGRITY_ACTION", "warn")
        drv = self._driver(monkeypatch)
        monkeypatch.setattr(drv._server, "heartbeat_version", lambda: 1)
        recs = {0: self._rec(0, "hostA", 2, "DB",
                             prev={"digest": "DA", "step": 1}),
                1: self._rec(1, "hostB", 2, "DY",
                             prev={"digest": "DX", "step": 1})}
        monkeypatch.setattr(
            drv._server, "integrity_vote_cached",
            lambda: (recs, integrity.vote_latest(recs, 2)))
        drv._last_integrity_tick = -1e9
        drv._integrity_tick()
        assert not drv._integrity_strikes  # no memory: nobody named


# ---------------------------------------------------------------------------
# Rewind-on-spike
# ---------------------------------------------------------------------------


class TestLossSpikeDetector:
    def test_spike_after_warmup(self):
        det = integrity.LossSpikeDetector(sigma=3.0, alpha=0.2, warmup=4)
        for loss in (1.0, 1.1, 0.9, 1.05, 0.95, 1.0):
            assert not det.observe(loss)
        assert not det.observe(1.1)  # within trend noise
        assert det.observe(100.0)  # 3 sigma above it

    def test_no_trip_inside_warmup(self):
        det = integrity.LossSpikeDetector(sigma=2.0, alpha=0.1, warmup=5)
        assert not det.observe(1000.0)  # first sample, whatever it is
        assert not det.observe(0.001)

    def test_spike_sample_not_folded_into_trend(self):
        det = integrity.LossSpikeDetector(sigma=3.0, alpha=0.5, warmup=2)
        for _ in range(4):
            det.observe(1.0)
        assert det.observe(50.0)
        # The replayed (clean) sample is still normal: the spike did not
        # desensitize the detector by inflating the trend.
        assert not det.observe(1.0)
        assert det.observe(50.0)  # and a repeat spike still trips

    def test_nonfinite_loss_trips_once_armed(self):
        det = integrity.LossSpikeDetector(sigma=3.0, warmup=8)
        assert not det.observe(float("nan"))  # nothing observed yet
        det.observe(1.0)
        assert det.observe(float("nan"))
        assert det.observe(float("inf"))

    def test_all_nonfinite_stream_trips_on_second_sample(self):
        """A loss stream non-finite from the very first step must not
        leave the armed detector disarmed forever: non-finite samples
        count as observed, so the second one trips."""
        det = integrity.LossSpikeDetector(sigma=3.0, warmup=8)
        assert not det.observe(float("nan"))
        assert det.observe(float("nan"))
        assert det.observe(float("inf"))

    def test_observe_loss_unarmed_is_inert(self, monkeypatch):
        monkeypatch.delenv("HOROVOD_LOSS_SPIKE_SIGMA", raising=False)
        for loss in (1.0, float("nan"), 1e30):
            integrity.observe_loss(loss)  # never raises
        assert integrity.consume_skip_ahead() == 0

    def test_observe_loss_raises_and_stages_skip_ahead(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_LOSS_SPIKE_SIGMA", "3")
        monkeypatch.setenv("HOROVOD_LOSS_SPIKE_WARMUP", "3")
        for _ in range(5):
            integrity.observe_loss(1.0)
        with pytest.raises(LossSpikeError):
            integrity.observe_loss(500.0)
        assert integrity.consume_skip_ahead() == 1
        assert integrity.consume_skip_ahead() == 0  # consumed once


class TestRewindInElasticRun:
    def _journal(self, jpath):
        if not os.path.exists(jpath):
            return []
        return [json.loads(l)
                for l in open(jpath).read().splitlines() if l.strip()]

    def test_spike_rewinds_without_climbing_the_ladder(
            self, hvd, monkeypatch, tmp_path):
        from horovod_tpu.elastic import ObjectState
        from horovod_tpu.elastic import run as elastic_run

        jpath = str(tmp_path / "events.jsonl")
        monkeypatch.setenv("HOROVOD_EVENT_LOG", jpath)
        monkeypatch.setenv("HOROVOD_LOSS_SPIKE_SIGMA", "3")
        monkeypatch.setenv("HOROVOD_LOSS_SPIKE_WARMUP", "3")
        monkeypatch.setenv("HOROVOD_RECOVERY_BACKOFF_MAX", "0.05")
        state = ObjectState(step=0)
        restores = []
        orig_restore = state.restore
        state.restore = lambda: (restores.append(state.step),
                                 orig_restore())
        losses = [1.0] * 5 + [400.0] + [1.0] * 3
        cursor = {"i": 0}

        @elastic_run
        def train(st):
            while cursor["i"] < len(losses):
                loss = losses[cursor["i"]]
                cursor["i"] += 1
                integrity.observe_loss(loss)
                st.step += 1
                st.commit()
            return "done"

        assert train(state) == "done"
        assert len(restores) == 1  # one rewind, one restore
        events = self._journal(jpath)
        rewinds = [e for e in events if e["event"] == "rewind"]
        assert len(rewinds) == 1
        assert rewinds[0]["reason"] == "loss_spike"
        assert rewinds[0]["consecutive"] == 1
        # The voluntary rewind never climbed the escalation ladder.
        assert not any(e["event"] == "recovery" for e in events)
        assert any(e["event"] == "flight_record"
                   and e.get("reason") == "rewind" for e in events)
        # The poison batch does not replay: one skip-ahead was staged
        # (the training loop's contract is to consume it after rewind).
        assert integrity.consume_skip_ahead() == 1
        assert integrity.summary()["rewinds"] == 1

    def test_rewind_storm_breaker_escalates_to_ladder(
            self, hvd, monkeypatch, tmp_path):
        from horovod_tpu.elastic import ObjectState
        from horovod_tpu.elastic import run as elastic_run

        jpath = str(tmp_path / "events.jsonl")
        monkeypatch.setenv("HOROVOD_EVENT_LOG", jpath)
        monkeypatch.setenv("HOROVOD_REWIND_MAX", "2")
        monkeypatch.setenv("HOROVOD_RECOVERY_BACKOFF_MAX", "0.05")
        state = ObjectState(step=0)
        failures = []

        @elastic_run
        def train(st):
            if len(failures) < 3:
                failures.append(1)
                raise LossSpikeError("synthetic spike, no commits land")
            return "recovered"

        assert train(state) == "recovered"
        events = self._journal(jpath)
        rewinds = [e for e in events if e["event"] == "rewind"]
        assert [e["consecutive"] for e in rewinds] == [1, 2]
        assert any(e["event"] == "rewind_storm" for e in events)
        # Past the cap the spike rides the normal ladder.
        rungs = [e["rung"] for e in events if e["event"] == "recovery"]
        assert rungs == ["restore"]

    def test_landed_commit_resets_the_storm_breaker(
            self, hvd, monkeypatch, tmp_path):
        from horovod_tpu.elastic import ObjectState
        from horovod_tpu.elastic import run as elastic_run

        jpath = str(tmp_path / "events.jsonl")
        monkeypatch.setenv("HOROVOD_EVENT_LOG", jpath)
        monkeypatch.setenv("HOROVOD_REWIND_MAX", "1")
        monkeypatch.setenv("HOROVOD_RECOVERY_BACKOFF_MAX", "0.05")
        state = ObjectState(step=0)
        spikes = []

        @elastic_run
        def train(st):
            # Commit, spike, commit, spike: progress between spikes
            # keeps each one inside the rewind budget of 1.
            while len(spikes) < 2:
                st.step += 1
                st.commit()
                spikes.append(1)
                raise LossSpikeError(f"spike #{len(spikes)}")
            return "done"

        assert train(state) == "done"
        events = self._journal(jpath)
        rewinds = [e for e in events if e["event"] == "rewind"]
        assert [e["consecutive"] for e in rewinds] == [1, 1]
        assert not any(e["event"] == "rewind_storm" for e in events)
        assert not any(e["event"] == "recovery" for e in events)

    def test_rewind_metric_counts(self, hvd, monkeypatch):
        before = integrity.summary()["rewinds"]
        integrity.record_rewind("loss_spike", generation=3, consecutive=1)
        assert integrity.summary()["rewinds"] == before + 1
        text = hvd_metrics.render()
        assert 'hvd_rewinds_total{reason="loss_spike"}' in text


# ---------------------------------------------------------------------------
# The integrity precommit gate
# ---------------------------------------------------------------------------


class TestIntegrityPrecommit:
    def test_armed_abort_blocks_commit_when_voting_live(
            self, hvd, monkeypatch):
        from horovod_tpu.elastic import TpuState

        monkeypatch.setenv("HOROVOD_INTEGRITY_INTERVAL", "1")
        params = {"w": jnp.ones(3)}
        state = TpuState(params=params,
                         opt_state=optax.sgd(0.1).init(params), epoch=0)
        state.commit()
        abort.trigger_local("integrity divergence on peer")
        # The world is condemned: committing would rotate the last-good
        # replica group away right when the peer rung needs it.
        with pytest.raises(HorovodInternalError):
            state.commit()

    def test_armed_abort_blocks_commit_under_nonfinite_only(
            self, hvd, monkeypatch):
        """The gate must fire for ANY abort-posting defense, not just
        the voting plane: with only HOROVOD_NONFINITE_ACTION=abort
        armed, a commit racing the posted abort would snapshot the
        poisoned state and destroy the last good commit the ladder is
        about to restore."""
        from horovod_tpu.elastic import TpuState

        monkeypatch.delenv("HOROVOD_INTEGRITY_INTERVAL", raising=False)
        monkeypatch.setenv("HOROVOD_NONFINITE_ACTION", "abort")
        params = {"w": jnp.ones(3)}
        state = TpuState(params=params,
                         opt_state=optax.sgd(0.1).init(params), epoch=0)
        state.commit()
        abort.trigger_local("non-finite gradients")
        with pytest.raises(HorovodInternalError):
            state.commit()

    def test_unarmed_plane_keeps_head_commit_behavior(self, hvd,
                                                      monkeypatch):
        from horovod_tpu.elastic import TpuState

        monkeypatch.delenv("HOROVOD_INTEGRITY_INTERVAL", raising=False)
        params = {"w": jnp.ones(3)}
        state = TpuState(params=params,
                         opt_state=optax.sgd(0.1).init(params), epoch=0)
        abort.trigger_local("some failure elsewhere")
        state.commit()  # HEAD behavior: the commit path never checked


# ---------------------------------------------------------------------------
# Flight-record / profiler surfaces
# ---------------------------------------------------------------------------


class TestObservabilitySurfaces:
    def test_flight_summary_none_until_engaged(self):
        assert integrity.flight_summary() is None

    def test_flight_summary_carries_latest_group(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_INTEGRITY_INTERVAL", "1")
        integrity.maybe_fingerprint({"w": np.ones(2, np.float32)}, None, 4)
        snap = integrity.flight_summary()
        assert snap["latest"]["step"] == 4
        assert snap["latest"]["digest"]
        assert snap["nonfinite_detections"] == 0

    def test_profiler_summary_has_integrity_ledger(self, hvd):
        from horovod_tpu import profiler

        ledger = profiler.summary()["integrity"]
        assert set(ledger) >= {"armed", "interval", "checks",
                               "nonfinite_detections", "rewinds"}

    def test_worker_metrics_zero_materialized(self):
        parsed = hvd_metrics.validate_prometheus_text(hvd_metrics.render())
        assert "hvd_integrity_checks_total" in parsed
        actions = {tuple(sorted(l.items()))
                   for l, _ in
                   parsed["hvd_nonfinite_steps_total"]["samples"]}
        assert (("action", "skip"),) in actions
        assert (("action", "warn"),) in actions
        assert (("action", "abort"),) in actions
        reasons = {tuple(sorted(l.items()))
                   for l, _ in parsed["hvd_rewinds_total"]["samples"]}
        assert (("reason", "loss_spike"),) in reasons


# ---------------------------------------------------------------------------
# Chaos e2e: grad.corrupt -> vote -> drain -> spare -> peer-rung recovery
# ---------------------------------------------------------------------------


_E2E_WORKER = '''
import json, os, sys
sys.path.insert(0, {repo_root!r})
os.environ["JAX_PLATFORMS"] = "cpu"
host = os.environ["HOROVOD_HOSTNAME"]
import jax
jax.config.update("jax_platforms", "cpu")
from horovod_tpu._jax_compat import force_cpu_devices
force_cpu_devices(1)
import pickle
import time
import numpy as np
import optax
import horovod_tpu as hvd
from horovod_tpu import checkpoint, faults, process_world
from horovod_tpu.elastic import PeerShardedState, run as elastic_run
from horovod_tpu.optimizer import ReduceSpec, init_sharded_state, \\
    unshard_opt_state

behavior = json.load(open(os.environ["TEST_BEHAVIOR_FILE"])).get(
    host, "normal")
if behavior == "corrupt":
    # The canonical SDC injector: from the 3rd commit on, every
    # committed snapshot on THIS host has seeded bits flipped — the
    # digests stay self-consistent, so only the cross-rank vote can
    # see it (docs/elastic.md fault table).
    faults.inject(faults.GRAD_CORRUPT, "corrupt", arg=48, at=3,
                  count=10**9)

LR, MU = 0.05, 0.9
EPOCHS = int(os.environ["TEST_EPOCHS"])
STEP_SLEEP = float(os.environ["TEST_STEP_SLEEP"])
W0 = np.linspace(0.5, -0.5, 8).astype(np.float32)


def local_grad(w, e, r):
    rng = np.random.RandomState(1000 + 10 * e + r)
    A = rng.randn(16, 8).astype(np.float32)
    return ((A.T @ (A @ w)) / 16.0).astype(np.float32)


spec = ReduceSpec(
    inner=optax.sgd(LR, momentum=MU), op="average", compression=None,
    prescale_factor=1.0, postscale_factor=1.0, process_set=None,
    num_groups=0, fusion_threshold_bytes=None, backward_passes_per_step=1,
    sync_mode="sharded")
n0 = process_world.size()
params = {{"w": W0.copy()}}
state = PeerShardedState(
    params=params, opt_state=init_sharded_state(spec, params, world_size=n0),
    sharded_optimizer=spec, epoch=0)


def durable_restore():
    print("DURABLE_RESTORE_USED", flush=True)
    raise RuntimeError("no durable checkpoint exists in this test")


state.register_durable_restore(durable_restore)


@elastic_run
def train(state):
    from horovod_tpu.parallel.hierarchical import _default_native_world

    while state.epoch < EPOCHS:
        e = state.epoch
        r, n = process_world.rank(), process_world.size()
        w = np.asarray(state.params["w"])
        g = local_grad(w, e, r)
        if n > 1:
            world = _default_native_world()
            g = np.asarray(world.allreduce(g, name="grad.%d" % e,
                                           op="average"),
                           dtype=np.float32)
        tdef = jax.tree.structure(state.opt_state)
        trace = np.asarray(jax.tree.leaves(state.opt_state)[0])
        n_axis, s = trace.shape
        g_rows = np.pad(g, (0, n_axis * s - g.size)).reshape(n_axis, s)
        trace = (MU * trace + g_rows).astype(np.float32)
        w = (w - LR * trace.reshape(-1)[: w.size]).astype(np.float32)
        state.opt_state = jax.tree.unflatten(tdef, [trace])
        state.params = {{"w": w}}
        print("rank=%d host=%s epoch=%d np=%d gen=%s w0=%.6f" % (
            r, host, e, n, os.environ.get("HOROVOD_WORLD_VERSION", "?"),
            float(w[0])), flush=True)
        state.epoch = e + 1
        state.commit()
        time.sleep(STEP_SLEEP)
    return state.epoch


done = train(state)
print("host=%s finished at epoch %d" % (host, done), flush=True)
'''


def _cluster_names():
    import socket

    names = sorted({"127.0.0.1", "localhost", socket.gethostname()})
    if len(names) < 3:
        pytest.skip("machine hostname shadows a loopback alias; need "
                    "three distinct local names for the spare tier")
    corrupt_host, survivor, spare = names[0], names[1], names[2]
    assert corrupt_host == "127.0.0.1"
    return corrupt_host, survivor, spare


def _expected_weights(epochs):
    """The uninterrupted 2-rank averaged momentum-SGD trajectory."""
    lr, mu = 0.05, 0.9

    def local_grad(w, e, r):
        rng = np.random.RandomState(1000 + 10 * e + r)
        A = rng.randn(16, 8).astype(np.float32)
        return ((A.T @ (A @ w)) / 16.0).astype(np.float32)

    w = np.linspace(0.5, -0.5, 8).astype(np.float32)
    m = np.zeros(8, np.float32)
    out = {}
    for e in range(epochs):
        g = ((local_grad(w, e, 0) + local_grad(w, e, 1)) / 2.0
             ).astype(np.float32)
        m = (mu * m + g).astype(np.float32)
        w = (w - lr * m).astype(np.float32)
        out[e] = w.copy()
    return out


def _assert_weight_continuity(text, epochs):
    import re

    expected = _expected_weights(epochs)
    seen = {}
    for line in text.splitlines():
        m = re.search(
            r"rank=(\d+) host=\S+ epoch=(\d+) np=(\d+) gen=\d+ "
            r"w0=(-?[0-9.]+)", line)
        if m:
            e, np_, w0 = (int(m.group(2)), int(m.group(3)),
                          float(m.group(4)))
            seen.setdefault(e, []).append((np_, w0))
    for e in range(epochs):
        assert e in seen, (e, sorted(seen))
        for np_, w0 in seen[e]:
            assert np_ == 2, (e, np_)  # the world never fell below 2
            assert abs(w0 - float(expected[e][0])) < 2e-4, (
                e, w0, float(expected[e][0]))


def _run_sdc_job(tmp_path, monkeypatch, epochs, integrity_on):
    from horovod_tpu.runner.elastic.driver import run_elastic
    from horovod_tpu.runner.launch import Settings

    jpath = tmp_path / "journal.jsonl"
    monkeypatch.setenv("HOROVOD_EVENT_LOG", str(jpath))
    monkeypatch.setenv("HOROVOD_ELASTIC_HEARTBEAT_INTERVAL", "0.25")
    # Liveness must stay clear of the voting/drain windows on this
    # contended box (the single-threaded server stamps receive times
    # late under load).
    monkeypatch.setenv("HOROVOD_ELASTIC_HEARTBEAT_TIMEOUT", "30")
    monkeypatch.setenv("HOROVOD_BLACKLIST_COOLDOWN", "600")
    monkeypatch.setenv("HOROVOD_NATIVE_INIT_TIMEOUT", "6")
    monkeypatch.setenv("HOROVOD_WARM_SPARES", "1")
    if integrity_on:
        monkeypatch.setenv("HOROVOD_INTEGRITY_INTERVAL", "1")
    else:
        # The A/B arm: every integrity knob unset IS the HEAD build.
        monkeypatch.delenv("HOROVOD_INTEGRITY_INTERVAL", raising=False)

    corrupt_host, survivor, spare = _cluster_names()
    behavior_file = tmp_path / "behavior.json"
    behavior_file.write_text(json.dumps({corrupt_host: "corrupt"}))
    hosts_file = tmp_path / "hosts.txt"
    hosts_file.write_text(
        "\n".join([corrupt_host, survivor, spare]) + "\n")
    discover = tmp_path / "discover.sh"
    discover.write_text(f"#!/bin/sh\ncat {hosts_file}\n")
    discover.chmod(discover.stat().st_mode | stat.S_IEXEC)
    worker = tmp_path / "sdc_worker.py"
    worker.write_text(_E2E_WORKER.format(repo_root=REPO_ROOT))
    settings = Settings(
        num_proc=2,
        hosts=[],
        command=[sys.executable, str(worker)],
        cpu_mode=True,
        elastic=True,
        min_np=2,
        max_np=2,
        discovery_script=str(discover),
        elastic_timeout=60.0,
        env={
            "TEST_BEHAVIOR_FILE": str(behavior_file),
            "TEST_EPOCHS": str(epochs),
            "TEST_STEP_SLEEP": "1.0",
            "HOROVOD_RECOVERY_BACKOFF_MAX": "0.2",
            "HOROVOD_ABORT_POLL_INTERVAL": "0.2",
        },
    )
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
    return rc, [str(x) for x in lines], records, (corrupt_host, survivor,
                                                  spare)


class TestSdcDefenseE2E:
    @pytest.mark.slow
    def test_corrupt_rank_detected_drained_and_replaced(
            self, tmp_path, monkeypatch):
        """The tentpole, end to end: a grad.corrupt-injected rank's
        fingerprints diverge, the voting plane names its host, exactly
        one ``integrity_divergence`` journal event lands, the host is
        drained and the warm spare promoted at g+1, the survivors
        recover storage-free on the peer rung (the quarantine keeps the
        corrupt replica out of assembly), and the final weights are
        exact vs the uninterrupted clean run."""
        epochs = 8
        rc, lines, records, names = _run_sdc_job(
            tmp_path, monkeypatch, epochs, integrity_on=True)
        corrupt_host, survivor, spare = names
        text = "\n".join(lines)
        assert rc == 0, text

        events = {}
        for r in records:
            events.setdefault(r["event"], []).append(r)

        # Exactly ONE divergence vote, unambiguous, naming the host.
        divergences = events.get("integrity_divergence", [])
        assert len(divergences) == 1, divergences
        div = divergences[0]
        assert div["host"] == corrupt_host, div
        assert div["ambiguous"] is False
        assert div["method"] in ("drift", "nonfinite"), div
        assert div["strikes"] == 1

        # The drain went through the existing actuators...
        drains = [r for r in events.get("policy_drain", [])
                  if r["host"] == corrupt_host]
        assert drains, sorted(events)
        assert any(r["host"] == corrupt_host
                   for r in events.get("blacklist", [])), sorted(events)
        # ...and the warm spare joined at the next generation fence.
        promoted = [r for r in events.get("spare_promoted", [])
                    if r["host"] == spare]
        assert promoted, (sorted(events),
                          [l for l in lines if "[driver]" in l][-25:])
        assert promoted[0]["generation"] >= 2

        # Post-hoc evidence: a driver-side flight record names the host.
        flights = [r for r in events.get("flight_record", [])
                   if r.get("reason") == "integrity_divergence"]
        assert flights and flights[0]["host"] == corrupt_host, records

        # Storage-free recovery: the peer rung, zero durable reads (the
        # registered durable restore loudly marks any use and would
        # crash the run).
        rungs = [r["rung"] for r in records if r["event"] == "recovery"]
        assert "peer" in rungs, rungs
        assert "durable" not in rungs, rungs
        assert "DURABLE_RESTORE_USED" not in text, text
        assert not any(r["event"] == "peer_fallback" for r in records)

        # The world never fell below min_np=2.
        for r in events.get("world_published", []):
            assert r["np"] == 2, r

        # The healed world finished the run; the corrupt host did not.
        assert f"host={survivor} finished at epoch {epochs}" in text, text
        assert f"host={spare} finished at epoch {epochs}" in text, text
        assert f"host={corrupt_host} finished" not in text, text

        # Loss continuity: every printed weight (any generation, either
        # membership) sits on the exact uninterrupted trajectory — the
        # corruption never reached anyone's live state, and the rewind
        # landed on the last UNcondemned commit.
        _assert_weight_continuity(text, epochs)

    @pytest.mark.slow
    def test_integrity_plane_inert_without_knobs(self, tmp_path,
                                                 monkeypatch):
        """The A/B arm: the SAME injected-corruption script with every
        integrity knob unset. The driver's decisions must be bit-for-bit
        those of a HEAD build: no votes, no quarantine, no drain, one
        world generation — the corruption rides silently into the
        replicas (nobody reads them) and the job completes on the exact
        clean trajectory (the injector only ever touched snapshots,
        never live state)."""
        epochs = 4
        rc, lines, records, names = _run_sdc_job(
            tmp_path, monkeypatch, epochs, integrity_on=False)
        corrupt_host, survivor, _spare = names
        text = "\n".join(lines)
        assert rc == 0, text

        names_seen = {r["event"] for r in records}
        assert "integrity_divergence" not in names_seen, records
        assert "policy_drain" not in names_seen, records
        assert "blacklist" not in names_seen, records
        assert "recovery" not in names_seen, records
        assert not any(r["event"] == "spare_promoted" for r in records)

        published = [r for r in records
                     if r["event"] == "world_published"]
        assert len(published) == 1, published  # one generation, ever

        # Both INITIAL world hosts finished — corruption tolerated
        # invisibly, exactly as at HEAD.
        assert f"host={corrupt_host} finished at epoch {epochs}" in text, \
            text
        assert f"host={survivor} finished at epoch {epochs}" in text, text
        _assert_weight_continuity(text, epochs)
