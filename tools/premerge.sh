#!/usr/bin/env bash
# Default pre-merge check, on the CPU: the metric-docs consistency lane,
# the tier-1 test suite as the driver runs it (six workers, a file to a
# worker), and the fault-injection lane with its slow tests (chaos
# coverage must not silently rot). Speed is not judged here: the chip
# cells of BENCHMARK.json are the speed record (benchmark/run.py,
# PERF.md). Run from anywhere; exits nonzero if any gate fails.
set -u -o pipefail
cd "$(dirname "$0")/.."

echo "== premerge gate 0/2: metric-docs consistency (static lane) =="
# Every hvd_* instrument registered in code must appear in
# docs/observability.md's metric tables and vice versa, and must be
# written somewhere inside horovod_tpu/ — the table drifted in every PR
# since the metrics plane landed; this makes the drift a named CI
# failure instead of a docs bug found at incident time.
if ! python tools/check_metric_docs.py; then
    echo "premerge: metric-docs consistency lane failed" >&2
    exit 1
fi

echo "== premerge gate 1/2: tier-1 tests =="
t1log="$(mktemp "${TMPDIR:-/tmp}/_t1.XXXXXX.log")"  # per-run: concurrent
trap 'rm -f "$t1log"' EXIT                          # premerges must not clobber
timeout -k 10 1470 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
    -p xdist -n 6 --dist loadfile -p no:randomly 2>&1 | tee "$t1log"
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$t1log" \
    | tr -cd . | wc -c)"
# Failures whose root cause is the image, not the code: this jaxlib build
# cannot run 2-process CPU collectives ("Multiprocess computations aren't
# implemented on the CPU backend"), so the multi-controller launch tests
# fail everywhere regardless of the diff. Anything NOT on this list fails
# the gate.
KNOWN_ENV_FAILURES='test_hvdrun_autotune_reaches_compiled_path|test_e2e_multiprocess_allreduce'
if [ "$rc" -ne 0 ]; then
    unexpected="$(grep -a '^FAILED' "$t1log" \
        | grep -avE "$KNOWN_ENV_FAILURES" || true)"
    if [ -n "$unexpected" ] || ! grep -qa '^FAILED' "$t1log"; then
        echo "premerge: tier-1 tests failed (rc=$rc)" >&2
        [ -n "$unexpected" ] && echo "$unexpected" >&2
        exit "$rc"
    fi
    echo "premerge: only known-environmental failures; continuing"
fi

echo "== premerge gate 2/2: fault-injection + recovery (chaos lane) =="
# The FULL chaos files, slow marks included: the e2e liveness/abort/
# recovery tests are the acceptance proof for the robustness layer and
# must not rot just because tier-1 deselects @slow. test_recovery.py
# additionally arms a HARD per-test wall-clock breaker (faulthandler
# dump+exit after HOROVOD_TEST_HARD_TIMEOUT, default 300s): a regression
# that re-introduces an unbounded hang fails THAT test fast with every
# thread's stack dumped, instead of silently eating the lane's budget.
# test_peercheck.py is the peer-replication plane's acceptance proof:
# SIGKILL-during-commit never half-writes the replica pool, and the
# SIGKILL-one-worker e2e recovers on the peer rung (rc=0, zero
# durable-storage reads) with corrupt replicas falling through to the
# durable rung instead of crashing. test_policy.py is the self-healing
# plane's: a faults-plane straggler (worker.step delay) is detected from
# shipped skew evidence, proactively SIGTERM-drained (final commit
# lands, rc=0), and a warm spare joins at the next generation — with
# loss continuity, exactly one policy_decision record whose realized
# goodput beats the no-action counterfactual, and an A/B arm proving
# the plane is inert with HOROVOD_TARGET_GOODPUT unset.
# test_driver_failover.py is the control-plane fault-tolerance proof:
# SIGKILL the driver mid-training -> supervisor relaunch takes over from
# the durable snapshot, both workers rejoin at generation g+1 WITHOUT a
# process restart, recovery lands on the peer rung (zero durable
# reads), loss continuity is exact; the SIGSTOP'd stale-driver variant
# stands down EXIT_DRIVER_SUPERSEDED with its writes 409-fenced; torn
# snapshot writes (SIGKILL mid-save) restore the previous epoch.
# test_integrity.py is the data-plane (SDC) defense proof: a
# grad.corrupt-injected rank is named by the cross-rank digest vote
# within one integrity interval, its host drained and the warm spare
# promoted at g+1 with recovery on the peer rung and final weights
# exact vs the clean run; the vote fences the corrupt replica's
# peerstate PUT so it never displaces a good shard; non-finite
# tripwires skip the poisoned step rank-identically; the loss-spike
# detector rewinds storage-free with skip-ahead + a storm breaker; and
# the A/B arm proves every knob unset is bit-for-bit inert.
# test_scheduler.py is the multi-tenant pod's acceptance proof: two
# real elastic drivers gang-scheduled on one shared host pool —
# SIGKILL a worker in job A and the pool-wide condemnation + spare
# promotion heal A at its next generation fence with an exact loss
# trajectory while job B never re-forms; under SLO pressure the
# arbiter shrinks the low-priority job one host through the signed
# preempt-notice drain -> final-commit -> reassign sequence with
# exactly one sched_decision journal event per executed action
# (predicted + realized goodput), both jobs rc=0.
if ! timeout -k 10 2400 env JAX_PLATFORMS=cpu HOROVOD_TEST_HARD_TIMEOUT=240 \
    python -m pytest \
    tests/test_faults.py tests/test_recovery.py tests/test_peercheck.py \
    tests/test_policy.py tests/test_driver_failover.py \
    tests/test_integrity.py tests/test_scheduler.py \
    tests/test_serving.py -q \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly; then
    echo "premerge: fault-injection/recovery chaos lane failed" >&2
    exit 1
fi
echo "premerge: all gates passed"
