"""Timeline + stall inspector, mirroring the reference's env-flag smoke
tests (SURVEY.md §4: timeline/stall have env-activation contracts)."""

import json
import time

import numpy as np
import pytest


def test_timeline_records_collectives(hvd, tmp_path, monkeypatch):
    import horovod_tpu.timeline as tl

    path = tmp_path / "timeline.json"
    monkeypatch.setenv("HOROVOD_TIMELINE", str(path))
    monkeypatch.setattr(tl, "_timeline", None)

    x = np.random.RandomState(0).randn(8, 3).astype(np.float32)
    hvd.allreduce(x, op=hvd.Sum)
    hvd.allreduce(x + 1, op=hvd.Sum)  # cache hit event

    timeline = tl.get_timeline()
    assert timeline is not None
    timeline.shutdown()
    events = json.loads(path.read_text())
    names = [e["name"] for e in events]
    assert "allreduce" in names
    caches = [e["args"]["cache"] for e in events if e["name"] == "allreduce"]
    assert "hit" in caches  # second identical call must hit the cache
    monkeypatch.setattr(tl, "_timeline", None)


def test_start_stop_timeline_api(hvd, tmp_path, monkeypatch):
    """Dynamic activation (parity: hvd.start_timeline/stop_timeline): no
    env at launch, capture starts mid-run, stop flushes a readable
    trace."""
    import horovod_tpu.timeline as tl

    monkeypatch.delenv("HOROVOD_TIMELINE", raising=False)
    monkeypatch.setattr(tl, "_timeline", None)
    assert tl.get_timeline() is None

    path = tmp_path / "dyn.json"
    hvd.start_timeline(str(path))
    x = np.random.RandomState(0).randn(hvd.size(), 2).astype(np.float32)
    hvd.allreduce(x, op=hvd.Sum)
    hvd.stop_timeline()
    events = json.loads(path.read_text())
    assert any(e["name"] == "allreduce" for e in events), events
    # stopped: no more capture
    assert tl.get_timeline() is None


def test_stall_inspector_reports_outstanding():
    from horovod_tpu.stall import StallInspector

    ins = StallInspector(warning_s=0.01, shutdown_s=0.0)
    ticket = ins.begin("allreduce.layer0")
    time.sleep(0.02)
    # Deterministic clock: the first warning's log emission can take
    # longer than warning_s under load, which would legitimately re-warn
    # on the second (re-warn-every-warning_s contract) — pin `now` so the
    # two passes observe the same instant.
    now = time.monotonic()
    stalled = ins.check_once(now=now)
    assert len(stalled) == 1
    assert "allreduce.layer0" in stalled[0]
    # within the warning window: not re-reported
    assert ins.check_once(now=now) == []
    # a full warning_s later: re-warned with escalating age
    assert len(ins.check_once(now=now + 1.0)) == 1
    ins.end(ticket)
    ins.stop()


def test_stall_inspector_clean_ops_not_reported():
    from horovod_tpu.stall import StallInspector

    ins = StallInspector(warning_s=10.0)
    t = ins.begin("fast_op")
    ins.end(t)
    assert ins.check_once() == []
    ins.stop()


def test_fetch_single_controller(hvd):
    """hvd.fetch materializes a compiled result under a local inspector
    ticket (no host plane in 1-process worlds) and returns the tree."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.stall import get_inspector

    f = jax.jit(lambda v: (v * 2.0, v + 1.0))
    a, b = hvd.fetch(f(jnp.ones(3)), name="unit.step")
    np.testing.assert_allclose(np.asarray(a), 2.0)
    np.testing.assert_allclose(np.asarray(b), 2.0)
    # The ticket must be closed (nothing outstanding afterwards).
    assert not get_inspector()._outstanding


@pytest.mark.slow
class TestCompiledStepStall:
    def test_diverged_rank_named_in_report(self, tmp_path):
        """VERDICT r3 #7: a rank that skips a compiled step must produce
        the reference-style report — tensor named, missing ranks listed —
        via hvd.fetch's stallwatch announcement on the host plane, while
        the job itself recovers once the straggler arrives."""
        import os
        import textwrap

        from horovod_tpu.runner.launch import (
            parse_args, run_static, settings_from_args,
        )

        repo_root = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))
        script = tmp_path / "stall_worker.py"
        script.write_text(
            "import os, sys\n"
            f"sys.path.insert(0, {repo_root!r})\n"
            + textwrap.dedent("""
            import os, time
            os.environ["HOROVOD_STALL_CHECK_TIME"] = "0.5"
            import jax
            jax.config.update("jax_platforms", "cpu")
            import numpy as np
            import horovod_tpu as hvd
            from horovod_tpu.process_world import rank

            r = rank()
            f = jax.jit(lambda x: x * 2.0)
            # Step 1: both ranks in lockstep.
            out = hvd.fetch(f(np.ones(4, np.float32)), name="step.1")
            assert float(np.asarray(out)[0]) == 2.0
            # Step 2: rank 1 diverges (sleeps past the stall threshold)
            # before reaching the step; rank 0's controller must name the
            # missing rank while waiting, then everything resolves.
            if r == 1:
                time.sleep(3.0)
            out = hvd.fetch(f(np.ones(4, np.float32)), name="step.2")
            assert float(np.asarray(out)[0]) == 2.0
            print(f"rank{r} stallfetch ok", flush=True)
            """))
        lines: list = []
        args = parse_args(["-np", "2", "--cpu-mode", str(script)])
        settings = settings_from_args(args)
        rc = run_static(settings, sink=lines.append)
        text = "\n".join(str(x) for x in lines)
        assert rc == 0, text
        assert "rank0 stallfetch ok" in text and "rank1 stallfetch ok" in text
        assert "stallwatch/step.2" in text, text  # the step is NAMED
        assert "missing from rank(s) [1]" in text, text  # the rank is NAMED

    def test_plain_train_step_loop_watched_by_default(
            self, tmp_path, require_multiprocess_cpu_collectives):
        """VERDICT r4 #3: a VANILLA make_train_step loop — no hvd.fetch
        in user code — still produces the reference-style diverged-rank
        report: every Kth step (HOROVOD_STALL_CHECK_STEPS) routes through
        the stallwatch, so the rank that dawdles gets NAMED.

        Deflaked (PR 8), twice over. (1) The factory step's compiled
        mesh spans both processes, so on jaxlib builds that cannot run
        multi-process CPU computations the test fails for image reasons
        — it now rides the PR 2 capability probe
        (``require_multiprocess_cpu_collectives``) like the rest of that
        class instead of red-flagging tier-1. (2) On capable machines,
        the old fixed-phase race — rank 1 sleeps 3s from its OWN step-4
        arrival and rank 0 must reach the watch within that window
        despite compile time and machine load — is replaced by a
        marker-file handshake: rank 1 diverges only after rank 0
        announces it is about to ENTER the watched step, so the
        compile/warmup phase is out of the race entirely and rank 0 has
        the whole divergence window to open the watch and fire its 0.5s
        stall check."""
        import os
        import textwrap

        from horovod_tpu.runner.launch import (
            parse_args, run_static, settings_from_args,
        )

        repo_root = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))
        marker = tmp_path / "rank0_entering_watched_step"
        script = tmp_path / "watched_step_worker.py"
        script.write_text(
            "import os, sys\n"
            f"sys.path.insert(0, {repo_root!r})\n"
            f"MARKER = {str(marker)!r}\n"
            + textwrap.dedent("""
            import os, time
            os.environ["HOROVOD_STALL_CHECK_TIME"] = "0.5"
            os.environ["HOROVOD_STALL_CHECK_STEPS"] = "2"
            import jax
            jax.config.update("jax_platforms", "cpu")
            import numpy as np
            import optax
            import horovod_tpu as hvd
            from horovod_tpu.process_world import rank

            hvd.init()
            r = rank()
            opt = hvd.DistributedOptimizer(optax.sgd(0.1))
            step = hvd.data_parallel.make_train_step(
                lambda p, b: ((p["w"] * b).sum() - 1.0) ** 2, opt,
                donate=False)
            params = hvd.data_parallel.replicate(
                {"w": np.ones(4, np.float32)})
            opt_state = hvd.data_parallel.replicate(opt.init(params))
            batch = hvd.data_parallel.shard_batch(
                np.ones((4, 4), np.float32) * 0.1)
            for i in range(4):
                if r == 0 and i == 3:
                    # Announce: about to enter the watched step. From
                    # here rank 0 proceeds straight into the watch.
                    with open(MARKER, "w") as f:
                        f.write("go")
                if r == 1 and i == 3:
                    # Diverge only once rank 0 is provably at the
                    # watched step's doorstep, then stay away long
                    # enough for its 0.5s stall check to fire and name
                    # this rank — the handshake removes compile time
                    # and machine load from the race.
                    deadline = time.monotonic() + 60.0
                    while (not os.path.exists(MARKER)
                           and time.monotonic() < deadline):
                        time.sleep(0.05)
                    assert os.path.exists(MARKER), "rank 0 never arrived"
                    time.sleep(4.0)
                params, opt_state, loss = step(params, opt_state, batch)
            print(f"rank{r} watchedstep ok", flush=True)
            """))
        lines: list = []
        args = parse_args(["-np", "2", "--cpu-mode", str(script)])
        settings = settings_from_args(args)
        rc = run_static(settings, sink=lines.append)
        text = "\n".join(str(x) for x in lines)
        assert rc == 0, text
        assert "rank0 watchedstep ok" in text, text
        assert "rank1 watchedstep ok" in text, text
        assert "stallwatch/train_step.4" in text, text
        assert "missing from rank(s) [1]" in text, text


class TestProfilerMerge:
    """VERDICT r2 item 9: timeline activities dual-emit jax.profiler
    TraceAnnotations; HOROVOD_TIMELINE_MARK_CYCLES marks dispatch cycles."""

    def test_mark_cycles_honored(self, hvd, tmp_path, monkeypatch):
        import json
        import numpy as np

        import horovod_tpu.timeline as tl

        path = tmp_path / "tl.json"
        monkeypatch.setenv("HOROVOD_TIMELINE", str(path))
        monkeypatch.setenv("HOROVOD_TIMELINE_MARK_CYCLES", "1")
        tl._timeline = None
        tl._mark_cycles = None
        try:
            n = hvd.size()
            hvd.allreduce(np.ones((n, 2), np.float32), op=hvd.Sum)
            hvd.allreduce(np.ones((n, 3), np.float32), op=hvd.Sum)
            timeline = tl.get_timeline()
            assert timeline is not None
            timeline.shutdown()
            events = json.loads(path.read_text())
            cycles = [e for e in events if e.get("cat") == "cycle"]
            assert len(cycles) >= 2, events
        finally:
            tl._timeline = None
            tl._mark_cycles = None

    def test_activity_emits_trace_annotation(self):
        # TraceAnnotation must wrap cleanly even with no trace running.
        from horovod_tpu.timeline import activity

        with activity("merge.probe", "collective"):
            pass

    def test_profiler_module_api(self, tmp_path):
        import horovod_tpu.profiler as prof

        assert not prof.active()
        with prof.trace(str(tmp_path / "prof")):
            assert prof.active()
        assert not prof.active()

    def test_a_requested_trace_that_cannot_start_is_an_error(
            self, monkeypatch):
        import horovod_tpu.profiler as prof

        def broken(logdir):
            raise RuntimeError(f"cannot trace into {logdir}")

        monkeypatch.setattr(prof, "start", broken)
        monkeypatch.setenv("HOROVOD_PROFILER_LOGDIR", "/nonexistent/prof")
        with pytest.raises(RuntimeError, match="cannot trace"):
            prof.maybe_start_from_env()


class TestExecutableCacheSingleFlight:
    """Concurrent misses on one key must produce ONE build (XLA compiles
    cost seconds) and ONE counted miss — the waiters ride the builder's
    event and land as hits."""

    def test_concurrent_misses_build_once(self):
        import threading

        from horovod_tpu.ops.executable_cache import ExecutableCache

        cache = ExecutableCache(capacity=8)
        builds = []
        release = threading.Event()
        started = threading.Event()

        def slow_build():
            builds.append(1)
            started.set()
            release.wait(5.0)  # hold every concurrent caller in-flight
            return "value"

        results = []

        def caller():
            results.append(cache.get_or_build("k", slow_build))

        threads = [threading.Thread(target=caller) for _ in range(5)]
        threads[0].start()
        assert started.wait(5.0)  # builder is inside build()
        for t in threads[1:]:
            t.start()
        import time

        time.sleep(0.05)  # let the waiters reach the event wait
        release.set()
        for t in threads:
            t.join(5.0)
        assert results == ["value"] * 5
        assert len(builds) == 1  # single-flight: one compile
        assert cache.misses == 1  # ...and one counted miss
        assert cache.hits == 4  # waiters landed as hits

    def test_failed_build_elects_next_builder(self):
        import threading

        from horovod_tpu.ops.executable_cache import ExecutableCache

        cache = ExecutableCache(capacity=8)
        attempts = []
        first_in = threading.Event()
        release = threading.Event()

        def build():
            attempts.append(1)
            if len(attempts) == 1:
                first_in.set()
                release.wait(5.0)
                raise RuntimeError("compile failed")
            return "second"

        out = {}

        def first():
            try:
                cache.get_or_build("k", build)
            except RuntimeError:
                pass

        def second():
            out["v"] = cache.get_or_build("k", build)

        t1 = threading.Thread(target=first)
        t1.start()
        assert first_in.wait(5.0)
        t2 = threading.Thread(target=second)
        t2.start()
        release.set()
        t1.join(5.0)
        t2.join(5.0)
        assert out["v"] == "second"  # waiter retried after the failure
        assert len(attempts) == 2
        assert cache.misses == 1  # only the successful build counts


def test_cache_stats_counts_dispatches_and_cache(hvd):
    stats0 = hvd.cache_stats()
    n = hvd.size()
    shape = (n, 7)  # unlikely to collide with other tests' signatures
    x = np.random.RandomState(0).randn(*shape).astype(np.float32)
    hvd.allreduce(x, op=hvd.Sum)
    hvd.allreduce(x + 1, op=hvd.Sum)  # same signature: cache hit
    stats = hvd.cache_stats()
    assert (stats["eager_dispatch"].get("allreduce", 0)
            - stats0["eager_dispatch"].get("allreduce", 0)) == 2
    assert stats["executable_cache"]["hits"] > \
        stats0["executable_cache"]["hits"]
    assert stats["executable_cache"]["size"] >= 1
    # profiler.summary surfaces the same counters.
    import horovod_tpu.profiler as prof

    summary = prof.summary()
    assert summary["executable_cache"] == stats["executable_cache"]
    assert "trace_active" in summary


# ---------------------------------------------------------------------------
# Cluster-wide metrics plane (PR 5): registry primitives, the eager-dispatch
# instruments, the /metrics scrape, the lifecycle journal, goodput, and the
# rank-prefixed logging satellite.
# ---------------------------------------------------------------------------


class TestMetricsPrimitives:
    def test_counter_gauge_histogram_basics(self):
        from horovod_tpu.metrics import Registry

        reg = Registry()
        c = reg.counter("t_requests_total", "help", ("kind",))
        c.inc(kind="a")
        c.inc(2, kind="a")
        c.inc(kind="b")
        g = reg.gauge("t_depth", "help")
        g.set(7)
        h = reg.histogram("t_lat_seconds", "help", (), (0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 100.0):
            h.observe(v)
        snap = {f["name"]: f for f in reg.snapshot()}
        counts = {tuple(s["labels"].items()): s["value"]
                  for s in snap["t_requests_total"]["samples"]}
        assert counts[(("kind", "a"),)] == 3
        assert counts[(("kind", "b"),)] == 1
        assert snap["t_depth"]["samples"][0]["value"] == 7
        hs = snap["t_lat_seconds"]["samples"][0]
        assert hs["counts"] == [1, 2, 0]  # 100.0 only lands in +Inf
        assert hs["count"] == 4
        assert hs["sum"] == pytest.approx(101.05)

    def test_label_schema_enforced(self):
        from horovod_tpu.metrics import Registry

        reg = Registry()
        c = reg.counter("t_labeled_total", "h", ("kind",))
        with pytest.raises(ValueError):
            c.inc(wrong="x")
        with pytest.raises(ValueError):
            c.inc()  # missing the declared label
        # Re-registration is idempotent with the same schema...
        assert reg.counter("t_labeled_total", "h", ("kind",)) is c
        # ...and refuses a conflicting one.
        with pytest.raises(ValueError):
            reg.gauge("t_labeled_total", "h")

    def test_histogram_requires_buckets(self):
        from horovod_tpu.metrics import Registry

        with pytest.raises(ValueError):
            Registry().histogram("t_h", "h", (), ())

    def test_render_round_trips_through_validator(self):
        from horovod_tpu.metrics import Registry, validate_prometheus_text

        reg = Registry()
        reg.counter("t_total", "with \"quotes\" and\nnewline",
                    ("k",)).inc(k='va"l\nue')
        reg.histogram("t_h_seconds", "h", ("k",), (0.5, 2.0)).observe(
            1.0, k="x")
        parsed = validate_prometheus_text(
            reg.render(extra_labels={"rank": "3"}))
        (labels, value), = parsed["t_total"]["samples"]
        assert labels == {"k": 'va"l\nue', "rank": "3"}
        assert value == 1

    def test_backslash_label_values_round_trip(self):
        """A literal backslash followed by 'n' (Windows path) must not
        unescape into a newline — left-to-right scan, not chained
        replaces."""
        from horovod_tpu.metrics import Registry, validate_prometheus_text

        reg = Registry()
        reg.counter("t_bs_total", "h", ("p",)).inc(p="C:\\new")
        (labels, _), = validate_prometheus_text(
            reg.render())["t_bs_total"]["samples"]
        assert labels == {"p": "C:\\new"}


class TestPrometheusValidator:
    def test_rejects_malformed_sample(self):
        from horovod_tpu.metrics import validate_prometheus_text

        with pytest.raises(ValueError, match="line 1"):
            validate_prometheus_text('foo{bad 1\n')

    def test_rejects_duplicate_series(self):
        from horovod_tpu.metrics import validate_prometheus_text

        with pytest.raises(ValueError, match="duplicate series"):
            validate_prometheus_text('foo{a="1"} 1\nfoo{a="1"} 2\n')

    def test_rejects_duplicate_type(self):
        from horovod_tpu.metrics import validate_prometheus_text

        with pytest.raises(ValueError, match="duplicate TYPE"):
            validate_prometheus_text(
                "# TYPE foo counter\n# TYPE foo gauge\n")

    def test_rejects_non_cumulative_histogram(self):
        from horovod_tpu.metrics import validate_prometheus_text

        text = (
            "# TYPE h histogram\n"
            'h_bucket{le="1"} 5\n'
            'h_bucket{le="2"} 3\n'
            'h_bucket{le="+Inf"} 5\n'
            "h_sum 1\n"
            "h_count 5\n"
        )
        with pytest.raises(ValueError, match="not cumulative"):
            validate_prometheus_text(text)

    def test_rejects_histogram_missing_inf_bucket(self):
        from horovod_tpu.metrics import validate_prometheus_text

        text = (
            "# TYPE h histogram\n"
            'h_bucket{le="1"} 5\n'
            "h_sum 1\n"
            "h_count 5\n"
        )
        with pytest.raises(ValueError, match=r"\+Inf"):
            validate_prometheus_text(text)

    def test_rejects_inf_bucket_count_mismatch(self):
        from horovod_tpu.metrics import validate_prometheus_text

        text = (
            "# TYPE h histogram\n"
            'h_bucket{le="+Inf"} 5\n'
            "h_sum 1\n"
            "h_count 7\n"
        )
        with pytest.raises(ValueError, match="_count"):
            validate_prometheus_text(text)


def test_eager_dispatch_populates_histograms(hvd):
    """The acceptance path: a REAL eager allreduce lands in the dispatch
    counter and the latency/byte histograms with exact counts/bytes."""
    from horovod_tpu import metrics

    metrics.reset_for_testing()
    n = hvd.size()
    x = np.random.RandomState(1).randn(n, 17).astype(np.float32)
    hvd.allreduce(x, op=hvd.Sum)
    hvd.allreduce(x + 1, op=hvd.Sum)
    snap = {f["name"]: f for f in metrics.snapshot()}

    def sample(fam, **labels):
        for s in snap[fam]["samples"]:
            if s["labels"] == labels:
                return s
        raise AssertionError(f"no {labels} sample in {snap[fam]}")

    assert sample("hvd_collective_dispatch_total",
                  kind="allreduce")["value"] == 2
    lat = sample("hvd_collective_latency_seconds", kind="allreduce")
    assert lat["count"] == 2 and lat["sum"] > 0
    by = sample("hvd_collective_payload_bytes", kind="allreduce")
    assert by["count"] == 2
    assert by["sum"] == 2 * n * 17 * 4  # float32 stacked payload, exact
    # One compile (miss) + one hit, mirrored in the cache-event counter.
    assert sample("hvd_executable_cache_events_total",
                  outcome="miss")["value"] >= 1
    assert sample("hvd_executable_cache_events_total",
                  outcome="hit")["value"] >= 1
    compile_h = sample("hvd_collective_compile_seconds", kind="allreduce")
    assert compile_h["count"] >= 1
    # The whole snapshot renders to valid Prometheus text.
    from horovod_tpu.metrics import validate_prometheus_text

    validate_prometheus_text(metrics.render())


def test_cache_stats_reset(hvd):
    n = hvd.size()
    hvd.allreduce(np.ones((n, 13), np.float32), op=hvd.Sum)
    stats = hvd.cache_stats(reset=True)
    assert stats["eager_dispatch"].get("allreduce", 0) >= 1
    after = hvd.cache_stats()
    assert after["eager_dispatch"] == {}
    assert after["executable_cache"]["hits"] == 0
    assert after["executable_cache"]["misses"] == 0
    # Entries survive the counter reset: the same signature is a hit.
    hvd.allreduce(np.ones((n, 13), np.float32), op=hvd.Sum)
    assert hvd.cache_stats()["executable_cache"]["hits"] == 1


def test_grad_sync_flush_instrumented(hvd):
    """A traced DistributedOptimizer flush records trace-time wire bytes
    and bucket counts under its sync_mode label (counts traces, not
    steps — the documented contract)."""
    import jax
    import optax
    from jax.sharding import PartitionSpec as P

    from horovod_tpu import metrics

    metrics.reset_for_testing()
    mesh = hvd.global_mesh()
    params = {"w": np.ones((64,), np.float32),
              "b": np.ones((32,), np.float32)}
    opt = hvd.DistributedOptimizer(optax.sgd(0.1))

    def step(g):
        g = jax.tree.map(lambda a: a[0], g)  # strip the stacking axis
        state = opt.init(params)
        updates, _ = opt.update(g, state, params)
        return updates

    f = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=P("hvd"), out_specs=P(),
        check_vma=False))
    out = f({"w": np.ones((8, 64), np.float32),
             "b": np.ones((8, 32), np.float32)})
    jax.block_until_ready(out)
    snap = {fam["name"]: fam for fam in metrics.snapshot()}
    (fl,) = [s for s in snap["hvd_grad_sync_flushes_total"]["samples"]
             if s["labels"] == {"sync_mode": "allreduce"}]
    assert fl["value"] >= 1
    (hb,) = [s for s in snap["hvd_grad_sync_bytes"]["samples"]
             if s["labels"] == {"sync_mode": "allreduce"}]
    # (64 + 32) float32 leaves per flush, exact per trace.
    assert hb["sum"] == (64 + 32) * 4 * fl["value"]
    (bk,) = [s for s in snap["hvd_grad_sync_buckets"]["samples"]
             if s["labels"] == {"sync_mode": "allreduce"}]
    assert bk["count"] == fl["value"]


class TestClusterScrape:
    """KV server /metrics: two fake worker snapshots ride heartbeat PUTs,
    the scrape aggregates them with per-rank labels plus driver gauges,
    and every line passes the strict validator."""

    def _fake_snapshot(self, dispatches):
        from horovod_tpu.metrics import Registry

        reg = Registry()
        c = reg.counter("hvd_collective_dispatch_total", "h", ("kind",))
        c.inc(dispatches, kind="allreduce")
        h = reg.histogram("hvd_collective_latency_seconds", "h", ("kind",),
                          (0.01, 0.1, 1.0))
        for _ in range(dispatches):
            h.observe(0.05, kind="allreduce")
        return reg.snapshot()

    def test_scrape_end_to_end(self):
        import json as _json
        import urllib.request

        from horovod_tpu.metrics import validate_prometheus_text
        from horovod_tpu.runner.http.kv_server import (
            KVClient, RendezvousServer,
        )

        server = RendezvousServer(host="127.0.0.1")
        server.start()
        try:
            server.set_cluster_info(world_np=2, blacklisted=1)
            client = KVClient("127.0.0.1", server.port)
            for rank, host, n in ((0, "hostA", 3), (1, "hostB", 5)):
                client.put("heartbeat", host, _json.dumps({
                    "rank": rank, "steps": 10 * (rank + 1), "commits": rank,
                    "metrics": self._fake_snapshot(n),
                }).encode())
            # A malformed heartbeat must not break the scrape.
            client.put("heartbeat", "hostC", b"not json at all")
            url = f"http://127.0.0.1:{server.port}/metrics"
            with urllib.request.urlopen(url, timeout=10) as r:
                assert r.status == 200
                assert "text/plain" in r.headers["Content-Type"]
                text = r.read().decode()
            parsed = validate_prometheus_text(text)  # EVERY line, strictly
            # Driver-plane gauges.
            assert parsed["hvd_world_generation"]["samples"][0][1] == 0
            assert parsed["hvd_world_size"]["samples"][0][1] == 2
            assert parsed["hvd_blacklisted_hosts"]["samples"][0][1] == 1
            assert parsed["hvd_fenced_writes_total"]["samples"][0][1] == 0
            hosts = {l["host"]
                     for l, _ in parsed["hvd_heartbeat_age_seconds"]["samples"]}
            assert hosts == {"hostA", "hostB", "hostC"}
            # Worker progress counters with host+rank labels.
            steps = {l["rank"]: v
                     for l, v in parsed["hvd_worker_steps_total"]["samples"]}
            assert steps == {"0": 10, "1": 20}
            # Per-rank collective series from the piggybacked snapshots.
            dispatch = {
                l["rank"]: v
                for l, v in parsed["hvd_collective_dispatch_total"]["samples"]
            }
            assert dispatch == {"0": 3, "1": 5}
            inf_counts = {
                l["rank"]: v
                for l, v in parsed["hvd_collective_latency_seconds"]["samples"]
                if l.get("le") == "+Inf"
            }
            assert inf_counts == {"0": 3, "1": 5}
        finally:
            server.stop()

    @pytest.fixture(scope="class")
    def cold_scrape(self):
        """``GET /metrics`` of a live server holding one heartbeat of a
        process that has measured nothing yet, parsed strictly."""
        import json as _json
        import urllib.request

        from horovod_tpu import metrics
        from horovod_tpu.runner.http.kv_server import (
            KVClient, RendezvousServer,
        )

        metrics.reset_for_testing()
        server = RendezvousServer(host="127.0.0.1")
        server.start()
        try:
            server.set_cluster_info(world_np=1)
            KVClient("127.0.0.1", server.port).put(
                "heartbeat", "cold-host", _json.dumps({
                    "rank": 0, "steps": 0, "commits": 0,
                    "metrics": metrics.snapshot()}).encode())
            url = f"http://127.0.0.1:{server.port}/metrics"
            with urllib.request.urlopen(url, timeout=10) as r:
                return metrics.validate_prometheus_text(r.read().decode())
        finally:
            server.stop()

    @pytest.mark.parametrize("plane, families", [
        ("driver", ("hvd_heartbeat_age_seconds", "hvd_world_generation",
                    "hvd_policy_decisions_total", "hvd_policy_spare_hosts",
                    "hvd_driver_epoch", "hvd_driver_lost_total",
                    "hvd_integrity_quarantined_ranks")),
        ("goodput", ("hvd_goodput_productive_seconds_total",
                     "hvd_goodput_lost_seconds_total")),
        ("checkpoint_and_sharding", (
            "hvd_checkpoint_seconds", "hvd_peer_replication_bytes",
            "hvd_param_gather_bytes", "hvd_resident_state_bytes",
            "hvd_mesh_axis_size")),
        ("comms", ("hvd_link_bandwidth_bytes_per_second",
                   "hvd_link_latency_seconds",
                   "hvd_collective_efficiency_ratio",
                   "hvd_comms_residual_seconds", "hvd_planner_plans_total",
                   "hvd_planner_replans_total",
                   "hvd_planner_dispatch_total")),
        ("integrity", ("hvd_integrity_checks_total",
                       "hvd_integrity_divergence_total",
                       "hvd_nonfinite_steps_total", "hvd_rewinds_total")),
        ("attribution", ("hvd_step_phase_seconds",
                         "hvd_exposed_comm_seconds",
                         "hvd_overlap_hidden_ratio", "hvd_mfu_ratio",
                         "hvd_step_regression_score")),
        ("moe", ("hvd_moe_dispatch_bytes", "hvd_moe_tokens_dropped_total",
                 "hvd_moe_expert_load", "hvd_alltoall_latency_seconds")),
        ("serving", ("hvd_serve_model_age_seconds", "hvd_serve_swaps_total",
                     "hvd_serve_rejected_publishes_total",
                     "hvd_serve_requests_total", "hvd_serve_swap_seconds")),
        ("memory", ("hvd_hbm_bytes", "hvd_hbm_watermark_bytes",
                    "hvd_hbm_headroom_ratio",
                    "hvd_hbm_model_residual_bytes")),
    ])
    def test_zero_cells_reach_the_scrape(self, cold_scrape, plane,
                                         families):
        """A plane that has measured nothing still shows its series at 0
        on the cluster scrape (0 = nothing happened, absence = not
        measuring), carried by an ordinary heartbeat."""
        missing = [name for name in families
                   if not cold_scrape.get(name, {}).get("samples")]
        assert not missing, (plane, missing)
        if plane == "checkpoint_and_sharding":
            # Both axes of the 2-D mesh, or "flat wire" and "not
            # measuring that axis" read the same.
            for name in ("hvd_mesh_axis_size", "hvd_param_gather_bytes"):
                axes = {labels.get("axis")
                        for labels, _ in cold_scrape[name]["samples"]}
                assert {"batch", "model"} <= axes, (name, axes)

    def test_scrape_unauthenticated_even_with_secret(self, monkeypatch):
        """A Prometheus scraper cannot HMAC-sign: /metrics must answer
        without auth while the KV surface stays 403-protected."""
        import urllib.error
        import urllib.request

        from horovod_tpu.runner import secret as _secret
        from horovod_tpu.runner.http.kv_server import RendezvousServer

        monkeypatch.setenv(_secret.ENV_KEY, _secret.make_secret_key())
        server = RendezvousServer(host="127.0.0.1")
        server.start()
        try:
            base = f"http://127.0.0.1:{server.port}"
            with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
                assert r.status == 200
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{base}/_version", timeout=10)
            assert ei.value.code == 403
        finally:
            server.stop()


def test_heartbeat_piggybacks_metrics_snapshot(monkeypatch):
    """The worker's ordinary heartbeat PUT carries the full instrument
    snapshot, and the server's scrape renders it under this host's
    labels — the cluster plane needs no extra connection."""
    import json as _json

    from horovod_tpu.metrics import validate_prometheus_text
    from horovod_tpu.runner.elastic import worker as elastic_worker
    from horovod_tpu.runner.http.kv_server import RendezvousServer

    server = RendezvousServer(host="127.0.0.1")
    server.start()
    try:
        monkeypatch.setenv("HOROVOD_RENDEZVOUS_ADDR", "127.0.0.1")
        monkeypatch.setenv("HOROVOD_RENDEZVOUS_PORT", str(server.port))
        monkeypatch.setenv("HOROVOD_HOSTNAME", "hb-host")
        monkeypatch.setenv("HOROVOD_RANK", "0")
        ctx = elastic_worker.ElasticWorkerContext()
        assert ctx.send_heartbeat()
        payload = _json.loads(server.heartbeat_payload("hb-host"))
        assert payload["rank"] == "0"
        assert isinstance(payload["metrics"], list) and payload["metrics"]
        names = {f["name"] for f in payload["metrics"]}
        assert "hvd_goodput_productive_seconds_total" in names
        parsed = validate_prometheus_text(server.metrics_text())
        assert any(
            l.get("host") == "hb-host"
            for l, _ in
            parsed["hvd_goodput_productive_seconds_total"]["samples"])
        # Opt-out strips the snapshot but keeps the liveness beat.
        monkeypatch.setenv("HOROVOD_METRICS_PIGGYBACK", "0")
        assert ctx.send_heartbeat()
        payload = _json.loads(server.heartbeat_payload("hb-host"))
        assert "metrics" not in payload
    finally:
        server.stop()


class TestLifecycleJournal:
    def test_journal_abort_recover_replay(self, hvd, tmp_path, monkeypatch):
        """A simulated abort→recover under @hvd.elastic.run leaves a
        well-formed JSONL journal that replays the lifecycle in
        generation order with both clocks stamped."""
        import json as _json

        from horovod_tpu import abort
        from horovod_tpu.elastic import ObjectState

        jpath = tmp_path / "events.jsonl"
        monkeypatch.setenv("HOROVOD_EVENT_LOG", str(jpath))
        monkeypatch.setenv("HOROVOD_RECOVERY_BACKOFF_MAX", "0.05")
        abort.reset()
        calls = []
        state = ObjectState(step=0)

        @hvd.elastic.run
        def train(st):
            calls.append(1)
            if len(calls) == 1:
                abort.trigger_local("simulated wedge")
                abort.raise_if_aborted()
            return "done"

        try:
            assert train(state) == "done"
        finally:
            abort.reset()
        records = [_json.loads(line)
                   for line in jpath.read_text().splitlines()]
        events = [r["event"] for r in records]
        assert "elastic_run_start" in events
        assert "abort_consumed" in events
        assert "recovery" in events
        assert events.count("world_synced") == 2  # initial + post-recovery
        for r in records:
            assert isinstance(r["generation"], int)
            assert isinstance(r["t_wall"], float)
            assert isinstance(r["t_mono"], float)
        # Replays in order: monotonic clock strictly ordered, generations
        # never regress.
        monos = [r["t_mono"] for r in records]
        assert monos == sorted(monos)
        gens = [r["generation"] for r in records]
        assert gens == sorted(gens)
        rec = [r for r in records if r["event"] == "recovery"][0]
        assert rec["rung"] == "restore" and rec["failures"] == 1
        # The abort flowed through the counters too.
        snap = {f["name"]: f for f in hvd.metrics.snapshot()}
        assert snap["hvd_abort_consumed_total"]["samples"][0]["value"] >= 1
        assert any(s["labels"] == {"rung": "restore"}
                   for s in snap["hvd_recoveries_total"]["samples"])

    def test_journal_disabled_without_env(self, monkeypatch):
        from horovod_tpu import metrics

        monkeypatch.delenv("HOROVOD_EVENT_LOG", raising=False)
        assert metrics.journal() is None
        metrics.event("should_be_dropped")  # must not raise

    def test_journal_unopenable_path_never_raises(self, monkeypatch):
        from horovod_tpu import metrics

        monkeypatch.setenv(
            "HOROVOD_EVENT_LOG", "/nonexistent-dir/nope/events.jsonl")
        metrics.event("dropped")  # warns once, never raises
        assert metrics.journal() is None


def test_goodput_tracker_accounting():
    from horovod_tpu.metrics import GoodputTracker

    gp = GoodputTracker()
    gp.add_productive(9.0)
    gp.add_lost("rendezvous", 0.5)
    gp.add_lost("restore", 0.25)
    gp.add_lost("backoff", 0.25)
    gp.add_productive(-1.0)  # ignored: clocks can't run backwards
    s = gp.summary()
    assert s["productive_s"] == 9.0
    assert s["lost_total_s"] == 1.0
    assert s["goodput_ratio"] == 0.9
    gp.reset()
    assert gp.summary()["goodput_ratio"] is None


def test_elastic_run_accrues_goodput(hvd, monkeypatch):
    """One failure+recovery cycle books rendezvous, restore, backoff,
    productive AND failed_attempt seconds — the accounting
    profiler.summary() surfaces. The failed attempt landed no commit, so
    its whole tail is lost{failed_attempt}, NOT productive (the PR 5
    caveat, fixed): only the successful attempt's time is productive."""
    import time as _time

    from horovod_tpu import metrics
    from horovod_tpu.elastic import ObjectState
    from horovod_tpu.exceptions import HorovodInternalError

    monkeypatch.setenv("HOROVOD_RECOVERY_BACKOFF_MAX", "0.05")
    gp = metrics.goodput()
    before = gp.summary()
    calls = []
    state = ObjectState(step=0)

    @hvd.elastic.run
    def train(st):
        calls.append(1)
        _time.sleep(0.02)
        if len(calls) == 1:
            raise HorovodInternalError("boom")
        return "ok"

    assert train(state) == "ok"
    after = gp.summary()
    assert after["productive_s"] >= before["productive_s"] + 0.015
    assert (after["lost_s"]["failed_attempt"]
            >= before["lost_s"].get("failed_attempt", 0.0) + 0.015)
    assert after["lost_s"]["backoff"] > before["lost_s"]["backoff"]
    assert after["lost_s"]["rendezvous"] >= before["lost_s"]["rendezvous"]
    import horovod_tpu.profiler as prof

    assert prof.summary()["goodput"] == gp.summary()


def test_failed_attempt_tail_splits_at_last_commit(hvd, monkeypatch):
    """An attempt that commits then fails books productive time only up
    to its last commit; the doomed tail after it is lost{failed_attempt}."""
    import time as _time

    from horovod_tpu import metrics
    from horovod_tpu.elastic import ObjectState
    from horovod_tpu.exceptions import HorovodInternalError

    monkeypatch.setenv("HOROVOD_RECOVERY_BACKOFF_MAX", "0.05")
    gp = metrics.goodput()
    before = gp.summary()
    calls = []
    state = ObjectState(step=0)

    @hvd.elastic.run
    def train(st):
        calls.append(1)
        if len(calls) == 1:
            _time.sleep(0.03)   # productive: committed below
            st.commit()
            _time.sleep(0.05)   # the doomed tail
            raise HorovodInternalError("boom")
        return "ok"

    assert train(state) == "ok"
    after = gp.summary()
    tail = (after["lost_s"]["failed_attempt"]
            - before["lost_s"].get("failed_attempt", 0.0))
    productive = after["productive_s"] - before["productive_s"]
    assert tail >= 0.04, after  # the post-commit sleep, not the whole run
    assert productive >= 0.02, after  # the pre-commit sleep survived


def test_log_records_carry_rank_generation_prefix(monkeypatch):
    """Satellite: every log record is prefixed [rank/size g<generation>]
    so interleaved multi-worker logs attribute without hostname greps."""
    import logging as pylog

    from horovod_tpu.utils.logging import RankPrefixFormatter, rank_prefix

    monkeypatch.setenv("HOROVOD_RANK", "2")
    monkeypatch.setenv("HOROVOD_SIZE", "8")
    monkeypatch.setenv("HOROVOD_ELASTIC", "1")
    monkeypatch.setenv("HOROVOD_WORLD_VERSION", "3")
    fmt = RankPrefixFormatter("[%(levelname)s] %(hvdctx)s%(message)s")
    rec = pylog.LogRecord("horovod_tpu", pylog.INFO, __file__, 1,
                          "hello", (), None)
    assert fmt.format(rec) == "[INFO] [2/8 g3] hello"
    # Elastic resize rewrites the env in place; the NEXT record must
    # carry the new identity (per-record recompute, not cached).
    monkeypatch.setenv("HOROVOD_WORLD_VERSION", "4")
    rec2 = pylog.LogRecord("horovod_tpu", pylog.INFO, __file__, 1,
                           "again", (), None)
    assert fmt.format(rec2) == "[INFO] [2/8 g4] again"
    # Non-elastic launched world: rank prefix without the generation.
    monkeypatch.delenv("HOROVOD_ELASTIC")
    monkeypatch.delenv("HOROVOD_WORLD_VERSION")
    assert rank_prefix() == "[2/8] "
    # Plain scripts keep clean logs.
    monkeypatch.delenv("HOROVOD_RANK")
    assert rank_prefix() == ""
    assert get_logger_formats_with_prefix()


def get_logger_formats_with_prefix():
    """The live get_logger() handler must be wired to the prefixed
    formatter (not just the class existing)."""
    import horovod_tpu.utils.logging as hl

    logger = hl.get_logger()
    return all(isinstance(h.formatter, hl.RankPrefixFormatter)
               for h in logger.handlers)


def test_stall_tickets_counted():
    from horovod_tpu import metrics
    from horovod_tpu.stall import StallInspector

    snap0 = {f["name"]: f for f in metrics.snapshot()}

    def val(snap, name):
        fam = snap.get(name, {"samples": []})
        return sum(s["value"] for s in fam["samples"])

    ins = StallInspector(warning_s=0.01, shutdown_s=0.0)
    t = ins.begin("metrics.probe")
    time.sleep(0.02)
    ins.check_once()
    ins.end(t)
    ins.stop()
    snap = {f["name"]: f for f in metrics.snapshot()}
    assert val(snap, "hvd_stall_tickets_total") == \
        val(snap0, "hvd_stall_tickets_total") + 1
    assert val(snap, "hvd_stall_warnings_total") >= \
        val(snap0, "hvd_stall_warnings_total") + 1
    (g,) = snap["hvd_stall_outstanding"]["samples"]
    assert g["value"] == 0  # ticket closed


def test_kv_retries_counted(monkeypatch):
    from horovod_tpu import metrics
    from horovod_tpu.utils.retry import call_with_retries

    def val():
        for f in metrics.snapshot():
            if f["name"] == "hvd_retries_total":
                return sum(s["value"] for s in f["samples"])
        return 0

    before = val()
    attempts = []

    def flaky():
        attempts.append(1)
        if len(attempts) < 3:
            raise OSError("blip")
        return "ok"

    assert call_with_retries(flaky, attempts=3, base_delay=0.001) == "ok"
    assert val() == before + 2  # two retries, the success is free
