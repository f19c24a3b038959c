"""Rendezvous key-value server over HTTP.

TPU-native analog of the reference's launcher-side KV store
(``horovod/runner/http/http_server.py — RendezvousServer, KVStoreHandler``),
which Gloo contexts rendezvoused against. Here the *data plane* needs no
rendezvous (XLA collectives bootstrap via ``jax.distributed``); the KV server
serves the **control plane**: worker registration, elastic host-update
notification, and generic scoped key/value exchange (used e.g. by
``broadcast_object`` fallbacks and the native runtime's coordinator
discovery).

Protocol: ``PUT /scope/key`` (body = value bytes), ``GET /scope/key``
(200 + bytes, or 404), ``DELETE /scope`` (drop a scope),
``GET /_scope/scope`` (list keys, newline separated). A monotonically
increasing ``version`` is bumped by ``reset()`` on elastic reconfiguration;
workers read it at ``GET /_version``.

World generation & coordinated abort: the epoch ``version`` doubles as the
monotonic **world generation**. Two mechanisms hang off it:

- **Abort records** (``abort/<generation>`` scope): the elastic driver
  posts one (``post_abort``) whenever it kills/blacklists a host or reaps
  an unclean worker exit, and a worker's stall inspector posts one when a
  stall crosses its shutdown deadline. Workers poll the record for *their*
  generation (``horovod_tpu.abort``) and convert a wedged collective into
  ``HorovodInternalError`` → elastic recovery.
- **Generation fencing**: a write (PUT/DELETE) carrying
  ``X-Hvd-Generation`` older than the current generation is rejected with
  409. A zombie worker from the pre-abort world (SIGSTOP'd through a
  recovery, then resumed) replays its buffered writes with its stale
  generation and corrupts nothing — the re-formed world's rendezvous and
  heartbeat records stay authoritative. Clients without the header (plain
  tooling, static launches) are not fenced.

Authentication (parity: ``horovod/runner/common/util/secret.py`` — the
reference HMAC-signs driver↔task traffic): when ``HOROVOD_SECRET_KEY`` is
set (the launcher generates one per job and ships it in the worker env
block), every request carries ``X-Hvd-Auth: HMAC-SHA256(method\\npath\\n
body)`` and the server rejects missing/invalid tags with 403 — a port
scanner on the cluster network cannot read or poison the rendezvous state.
No key set = open dev mode.

Metrics plane: ``GET /metrics`` serves a Prometheus-text aggregate of the
whole job — driver-side gauges (world generation/size, blacklisted hosts,
fenced writes, per-host heartbeat ages) plus every worker's instrument
snapshot, which workers piggyback on the heartbeat PUTs they already send
(``runner/elastic/worker.py``), labeled per rank/host. The endpoint is
exempt from HMAC auth by design: a standard Prometheus scraper cannot sign
requests, and the data is read-only operational telemetry (it carries no
rendezvous state a scraper could poison). See ``docs/observability.md``.

Tracing plane (``horovod_tpu.tracing``): heartbeat PUT replies carry the
server's wall clock (``{"t_server": ...}``) so workers can estimate their
clock offset NTP-style from timestamps they already have; workers post
sampled step spans to ``PUT /trace/<host>`` (bounded payloads, replaced
per host); ``GET /timeline`` serves the merged, offset-corrected
Chrome/Perfetto trace JSON with one track per rank; ``GET /stragglers``
serves the per-collective arrival-skew attribution as JSON, and the
``/metrics`` scrape gains ``hvd_collective_skew_seconds{rank}`` /
``hvd_straggler_score{host}`` gauges from the same computation. The
read-only ``/timeline`` and ``/stragglers`` routes share ``/metrics``'s
auth exemption (trace viewers can't HMAC either). See
``docs/timeline.md``.

Communication observatory (``horovod_tpu.comms_model``): each worker's
heartbeat also piggybacks its fitted α–β link cost model (``"comms"``
key); ``GET /comms`` (auth-exempt, read-only) serves the cluster-merged
view — per-rank fits, effective-sample-weighted cluster aggregates per
(op, algorithm, link_class), and the per-host predicted-vs-observed
residuals the self-healing policy consumes as a second
straggler-evidence channel. A cold cluster serves an explicit
``insufficient_samples`` body, never a 500. See
``docs/observability.md``.
"""

from __future__ import annotations

import json
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable
from urllib.error import HTTPError
from urllib.parse import parse_qs, urlsplit
from urllib.request import Request, urlopen

from ... import attribution as _attribution
from ... import comms_model as _comms_model
from ... import memory as _memory
from ... import faults
from ... import integrity as _integrity
from ... import metrics as _metrics
from ... import peercheck as _peercheck
from ... import tracing as _tracing
from ...checkpoint import rotate_slots
from ...utils.env import get_float, get_int
from ...utils.retry import call_with_retries
from .. import secret as _secret

AUTH_HEADER = "X-Hvd-Auth"
GENERATION_HEADER = "X-Hvd-Generation"

# Split-brain fence (control-plane fault tolerance): alongside the world
# generation, writes may carry the monotonic DRIVER EPOCH (bumped on
# every driver (re)start, persisted in runner/elastic/driver_state.py).
# A write stamped with an epoch LOWER than the serving driver's is a
# resurrected stale driver's (or a worker still loyal to one) and is
# rejected with 409 — a SIGSTOP'd-through-takeover driver can never
# reclaim or corrupt the re-formed world. Writes without the header are
# unfenced (plain tooling, static launches).
DRIVER_EPOCH_HEADER = "X-Hvd-Driver-Epoch"

# Liveness scope: workers PUT /heartbeat/<host>; the server records the
# RECEIVE time (server clock — worker clocks don't enter the liveness
# decision, so skew/NTP steps on preempted VMs can't fake death or life).
HEARTBEAT_SCOPE = "heartbeat"

# Coordinated-abort scope: one record per world generation, posted by the
# driver (host kill/blacklist/unclean exit) or a worker's stall inspector.
ABORT_SCOPE = "abort"

# Tracing scope: workers PUT /trace/<host> with sampled step spans + their
# measured clock offset; one payload per host (replaced on each ship).
TRACE_SCOPE = _tracing.TRACE_SCOPE

# Warm-spare registration scope: a spare worker (launched with
# HOROVOD_SPARE=1, waiting for an assignment) PUTs /spare/<host> once its
# framework imports are done — the driver's policy plane treats presence
# here (plus a fresh heartbeat) as "warm and promotable".
SPARE_SCOPE = "spare"

# Completion scope: an elastic worker whose training function RETURNED
# announces it here (``PUT /done/<host>``) before exiting 0. The driver
# normally learns completion from the exit code it reaps — but a worker
# ADOPTED across a driver restart is not the new driver's child, so its
# exit code is unreadable; the done record is how job completion
# survives a control-plane takeover.
DONE_SCOPE = "done"

# Preemption-notice scope: an external agent (cloud metadata watcher,
# maintenance tooling) PUTs /preempt/<host> to announce the host is about
# to be reclaimed. The elastic driver polls the scope and drains the host
# through the SIGTERM -> final-commit path — driver-side forwarding, so
# the notice works even when the cloud cannot signal the worker process
# directly. Notices are consumed once handled.
PREEMPT_SCOPE = "preempt"

#: The self-healing policy's action vocabulary (the `action` label values
#: of hvd_policy_decisions_total; zero-materialized on every scrape).
POLICY_ACTIONS = ("drain", "promote", "preempt")

# Peer-replication scope: each elastic rank PUTs its owned-shard replica
# record to /peerstate/<rank> on every commit (generation-fenced like all
# worker writes). Records are checksum-verified at install time — a torn
# body from a SIGKILL mid-PUT is rejected with 422 and the previous good
# record survives — and rotated (<rank> + <rank>.prev) through the same
# helper as the durable checkpoint's .prev file, so the replica pool is
# never left half-written. The scope deliberately SURVIVES epoch
# publication: the replica set of generation g is exactly what the peer
# recovery rung of generation g+1 assembles (horovod_tpu/peercheck.py).
PEERSTATE_SCOPE = _peercheck.PEERSTATE_SCOPE

# Training→serving bridge scope: trainers (HOROVOD_SERVE_PUBLISH=1)
# mirror each commit's replica record to ``PUT /modelstate/<rank>`` —
# same wire format, same install-time verification, same
# generation/epoch/quarantine fences as peerstate, but a scope of its
# own so serving-side consumption never contends with recovery. The
# read-only health/age view is the auth-exempt ``GET /model``.
MODELSTATE_SCOPE = _peercheck.MODELSTATE_SCOPE

# Payload bound for /trace PUTs: the worker caps spans/steps at the
# source; this is the server-side backstop against a misbehaving client.
_TRACE_MAX_BYTES = 1 << 20


def timeline_max_events() -> int:
    """Span-event cap for UNFILTERED ``GET /timeline`` bodies
    (``HOROVOD_TIMELINE_MAX_EVENTS``, default 200000; 0 disables): a
    large world's full merge can run to hundreds of MB, so past the cap
    the server answers **413** and the caller must bound the request
    with ``?steps=N`` / ``?rank=R``. Filtered requests are never capped
    (the caller already bounded them), and ``/criticalpath`` is never
    capped (its body is the small per-group analysis, not the raw
    spans). Documented in docs/timeline.md."""
    return get_int("HOROVOD_TIMELINE_MAX_EVENTS", 200000)


def _trace_query(query: str) -> tuple[int | None, str | None] | None:
    """Parse the shared ``?steps=N&rank=R`` trace-route filters.
    Returns (steps, rank), or None when a value is malformed (400)."""
    try:
        q = parse_qs(query, keep_blank_values=False)
    except ValueError:
        return None
    steps = None
    rank = None
    if "steps" in q:
        try:
            steps = int(q["steps"][-1])
        except (ValueError, IndexError):
            return None
        if steps <= 0:
            return None
    if "rank" in q:
        rank = q["rank"][-1]
    unknown = set(q) - {"steps", "rank"}
    if unknown:
        return None
    return steps, rank


def env_generation() -> int | None:
    """The launcher-written world generation, or None outside elastic
    worlds (static/manual launches are never fenced)."""
    import os

    raw = os.environ.get("HOROVOD_WORLD_VERSION", "")
    try:
        return int(raw)
    except ValueError:
        return None


def _auth_payload(method: str, path: str, body: bytes) -> bytes:
    return method.encode() + b"\n" + path.encode() + b"\n" + body


class _KVHandler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    # Silence per-request stderr logging (the launcher multiplexes worker
    # output; interleaved request logs would corrupt it).
    def log_message(self, fmt, *args):  # noqa: D102
        pass

    def _serve_fault(self) -> bool:
        """The ``kv.serve`` injection point: firing (drop semantics)
        closes the connection without answering — to the client that is
        a transport failure, indistinguishable from a driver dying
        mid-request; ``delay``/``hang`` stretch the request in place."""
        if faults.fire(faults.KV_SERVE):
            self.close_connection = True
            return True
        return False

    def _authenticate(self, body: bytes = b"") -> bool:
        tag = self.headers.get(AUTH_HEADER, "")
        key = self.server.secret  # type: ignore[attr-defined]
        if _secret.verify(_auth_payload(self.command, self.path, body), tag,
                          key=key):
            return True
        self._reply(403, b"bad auth tag")
        return False

    def _split(self):
        # Key = last path component; scope = everything before it (scopes may
        # contain slashes, e.g. "world/3").
        path = self.path.strip("/")
        if path.startswith("_scope/"):
            return "_scope", path[len("_scope/"):]
        if "/" not in path:
            return path, None
        scope, key = path.rsplit("/", 1)
        return scope, key

    def do_GET(self):  # noqa: N802
        if self._serve_fault():
            return
        route = urlsplit(self.path)
        if self.path == "/metrics":
            # Unauthenticated by design: Prometheus scrapers can't HMAC.
            return self._serve_metrics()
        if route.path in ("/timeline", "/criticalpath"):
            # Same exemption: Perfetto/curl can't sign; read-only. Both
            # routes take ?steps=N / ?rank=R so large-world scrapes stay
            # bounded; an unfiltered body past the event cap answers 413
            # (see timeline_max_events).
            return self._serve_trace_route(route.path, route.query)
        if self.path == "/stragglers":
            return self._serve_json(
                lambda httpd: _compute_cluster_skew(httpd)[0],
                "application/json")
        if self.path == "/comms":
            # Same exemption as /metrics: read-only operational
            # telemetry (the cluster-merged alpha-beta link cost model).
            return self._serve_json(_render_comms, "application/json")
        if self.path == "/memory":
            # Same exemption: the cluster-merged HBM breakdown (per-rank
            # resident bytes by kind, phase watermarks, headroom, model
            # drift) — read-only operational telemetry like /comms.
            return self._serve_json(_render_memory, "application/json")
        if self.path == "/integrity":
            # Same exemption: the collected integrity fingerprints (one
            # per rank, piggybacked on heartbeats) plus the live vote —
            # the SDC defense plane's observability window.
            return self._serve_json(_render_integrity, "application/json")
        if self.path == "/model":
            # Same exemption: the training→serving bridge's health/age
            # view (newest assemblable modelstate commit, publish
            # counters, staleness) — load balancers and serving probes
            # can't HMAC either.
            return self._serve_json(_render_model, "application/json")
        if not self._authenticate():
            return
        store = self.server.store  # type: ignore[attr-defined]
        scope, key = self._split()
        if scope == "_version":
            body = str(self.server.version).encode()  # type: ignore[attr-defined]
            return self._reply(200, body)
        if scope == "_epoch":
            body = str(self.server.driver_epoch).encode()  # type: ignore[attr-defined]
            return self._reply(200, body)
        if scope == "_scope":
            with self.server.lock:  # type: ignore[attr-defined]
                keys = sorted(store.get(key or "", {}).keys())
            return self._reply(200, ("\n".join(keys)).encode())
        with self.server.lock:  # type: ignore[attr-defined]
            val = store.get(scope, {}).get(key)
        if val is None:
            return self._reply(404, b"")
        self._reply(200, val)

    def _fence_check_locked(self) -> bytes | None:
        """Generation fence (call under the server lock): a write stamped
        with a generation older than the current world generation is a
        zombie from a pre-abort world — reject it so it cannot corrupt the
        re-formed world's records. Returns the 409 body, or None to
        proceed. Writes without the header are unfenced (plain clients)."""
        raw = self.headers.get(GENERATION_HEADER)
        if raw is None:
            # No generation stamp, but the driver-epoch fence must still
            # run: epoch-only clients (abort.post) fence on it alone.
            return self._epoch_fence_locked()
        try:
            gen = int(raw)
        except ValueError:
            return b"bad generation header"
        current = self.server.version  # type: ignore[attr-defined]
        if gen < current:
            self.server.fenced += 1  # type: ignore[attr-defined]
            return (f"stale generation {gen} rejected "
                    f"(world at generation {current})").encode()
        return self._epoch_fence_locked()

    def _epoch_fence_locked(self) -> bytes | None:
        """Driver-epoch fence (under the server lock): a write stamped
        with a driver epoch older than the serving driver's comes from a
        resurrected stale driver's world — 409 it so a
        SIGSTOP'd-through-takeover driver can never corrupt the state of
        the driver that superseded it. Headerless writes are unfenced."""
        raw = self.headers.get(DRIVER_EPOCH_HEADER)
        if raw is None:
            return None
        try:
            epoch = int(raw)
        except ValueError:
            return b"bad driver-epoch header"
        current = self.server.driver_epoch  # type: ignore[attr-defined]
        if epoch < current:
            self.server.fenced += 1  # type: ignore[attr-defined]
            return (f"stale driver epoch {epoch} rejected "
                    f"(world owned by driver epoch {current})").encode()
        return None

    def _integrity_quarantine_locked(self, key: str) -> bytes | None:
        """The integrity-vote fence on the ``peerstate`` scope (under
        the server lock): a rank named divergent by the voting plane has
        its replica PUTs rejected with 409 until a write arrives from a
        STRICTLY newer world generation (the re-formed world reuses the
        rank id for a healthy worker) — a corrupt shard must never
        displace a good replica. Headerless writes from a quarantined
        rank are rejected too: a corrupt host replaying unfenced is
        exactly who this fence exists for."""
        base = key
        while base.endswith(_peercheck.PREV_SUFFIX):
            base = base[:-len(_peercheck.PREV_SUFFIX)]
        raw = self.headers.get(GENERATION_HEADER)
        try:
            gen = int(raw) if raw is not None else None
        except ValueError:
            gen = None
        quarantine = getattr(self.server, "integrity_quarantine", None)
        entry = (quarantine or {}).get(base)
        if entry is not None:
            if entry.get("lifted"):
                # Tombstone: the formal fence is down (PUTs flow, the
                # condemned range still filters assembly) — but the
                # LIVE-vote fence must keep evaluating, or a rank id
                # re-condemned in a later generation would go unfenced
                # during the vote-to-driver-tick window.
                return self._live_vote_fence_locked(base, gen)
            if gen is not None and gen > int(entry.get("generation", 0)):
                # New world owns the rank id again: lift the PUT fence
                # but TOMBSTONE the entry instead of deleting it — the
                # condemned (possibly back-dated) range still filters
                # peer-rung assembly, or a failure before the new
                # generation's replica group completes could fall back
                # to and install the proven-corrupt old records.
                entry["lifted"] = True
                return None
            self.server.fenced += 1  # type: ignore[attr-defined]
            return (f"integrity quarantine: rank {base} was voted "
                    f"divergent at generation {entry.get('generation')} "
                    f"step {entry.get('step')} (host "
                    f"{entry.get('host')}); replica PUTs are fenced "
                    "until a newer generation").encode()
        return self._live_vote_fence_locked(base, gen)

    def _live_vote_fence_locked(self, base: str, gen: int | None
                                ) -> bytes | None:
        """The formal quarantine lands only on the driver's next monitor
        tick — latency a corrupt rank's NEXT commit can race, rotating
        the last good ``.prev`` away before ``quarantine_rank`` evicts
        anything. The server already holds every rank's fingerprint
        (heartbeat piggyback), so the fence votes inline: a replica PUT
        from the named outlier of a complete unambiguous divergent vote
        is rejected unless it proves a strictly newer world generation.
        Unarmed plane → no fingerprint has ever ridden a heartbeat → the
        ``integrity_seen`` latch short-circuits before any heartbeat
        body is parsed (inertness); armed, the parse+vote is cached per
        heartbeat mutation (``hb_version``), not re-run per PUT."""
        if not getattr(self.server, "integrity_seen", False):
            return None
        _records, voted = _cached_integrity_vote(self.server, locked=True)
        if voted is None:
            return None
        (vgen, vstep), verdict = voted
        if not verdict.get("divergent") or verdict.get("ambiguous"):
            return None
        try:
            outlier = int(verdict["outlier_rank"])
        except (KeyError, TypeError, ValueError):
            return None
        if str(outlier) != base or (gen is not None and gen > vgen):
            return None
        self.server.fenced += 1  # type: ignore[attr-defined]
        return (f"integrity live-vote fence: rank {base} is the outlier "
                f"of a divergent vote at generation {vgen} step {vstep}; "
                "replica PUT rejected pending driver quarantine").encode()

    def _drain_and_413(self, length: int, reason: bytes):
        """Reject an oversize body WITHOUT buffering it: the backstop
        must bound server memory, not just storage — the whole control
        plane rides this one process. The body is drained in small
        chunks and discarded (so the client reads a clean 413 instead of
        a connection reset mid-upload), never held whole."""
        remaining = length
        while remaining > 0:
            chunk = self.rfile.read(min(remaining, 1 << 16))
            if not chunk:
                break
            remaining -= len(chunk)
        return self._reply(413, reason)

    def do_PUT(self):  # noqa: N802
        if self._serve_fault():
            return
        scope, key = self._split()
        if key is None:
            return self._reply(400, b"missing key")
        length = int(self.headers.get("Content-Length", 0))
        if (scope in (PEERSTATE_SCOPE, MODELSTATE_SCOPE)
                and length > _peercheck.max_record_bytes()):
            return self._drain_and_413(length, b"replica record too large")
        if scope == TRACE_SCOPE and length > _TRACE_MAX_BYTES:
            return self._drain_and_413(length, b"trace payload too large")
        body = self.rfile.read(length)
        if not self._authenticate(body):
            return
        if scope in (PEERSTATE_SCOPE, MODELSTATE_SCOPE):
            # Install-time integrity gate: a half-received body (SIGKILL
            # mid-PUT, cut connection) or a corrupt record is rejected
            # BEFORE it can touch the pool — the previous good replica
            # (and its .prev) stay authoritative. The modelstate scope
            # rides the identical gate: a torn publish must never become
            # a servable record.
            why = _peercheck.verify_wire(body)
            if why is not None:
                if scope == MODELSTATE_SCOPE:
                    with self.server.lock:  # type: ignore[attr-defined]
                        self.server.model_rejected += 1  # type: ignore[attr-defined]
                return self._reply(422, why.encode())
        with self.server.lock:  # type: ignore[attr-defined]
            rejected = self._fence_check_locked()
            if rejected is None and scope in (PEERSTATE_SCOPE,
                                              MODELSTATE_SCOPE):
                rejected = self._integrity_quarantine_locked(key)
            if rejected is not None and scope == MODELSTATE_SCOPE:
                self.server.model_rejected += 1  # type: ignore[attr-defined]
            if rejected is None:
                if scope == MODELSTATE_SCOPE:
                    self.server.model_publishes += 1  # type: ignore[attr-defined]
                    self.server.model_last_t = time.time()  # type: ignore[attr-defined]
                if scope in (PEERSTATE_SCOPE, MODELSTATE_SCOPE):
                    # Rotate, don't overwrite: <rank> + <rank>.prev, via
                    # the same helper as the durable .prev file — the
                    # previous good commit survives until this one is
                    # verified and installed. An armed integrity plane
                    # keeps one slot more: its quarantine condemns up to
                    # a commit of detection latency, and assembly must
                    # still find an uncondemned group underneath.
                    rotate_slots(
                        self.server.store.setdefault(scope, {}),  # type: ignore[attr-defined]
                        key, body, prev_suffix=_peercheck.PREV_SUFFIX,
                        depth=_peercheck.retention_depth())
                else:
                    self.server.store.setdefault(scope, {})[key] = body  # type: ignore[attr-defined]
                if scope == TRACE_SCOPE:
                    # Attribution cache key: one bump per trace mutation
                    # so /criticalpath and the regression sentinel
                    # re-analyze exactly when new spans arrive.
                    self.server.trace_version = (  # type: ignore[attr-defined]
                        getattr(self.server, "trace_version", 0) + 1)
                if scope == HEARTBEAT_SCOPE:
                    # Liveness plane: stamp the receive time on the SERVER
                    # clock (driver-side monotonic; worker clocks
                    # irrelevant).
                    self.server.hb_times[key] = time.monotonic()  # type: ignore[attr-defined]
                    # Arm/refresh the live-vote fence: a cheap substring
                    # scan (no JSON parse) latches integrity_seen, and
                    # the mutation counter invalidates the vote cache.
                    self.server.hb_version = (  # type: ignore[attr-defined]
                        getattr(self.server, "hb_version", 0) + 1)
                    if (not getattr(self.server, "integrity_seen", False)
                            and b'"integrity"' in body):
                        self.server.integrity_seen = True  # type: ignore[attr-defined]
        if rejected is not None:
            return self._reply(409, rejected)
        if scope == HEARTBEAT_SCOPE:
            # Clock-alignment plane: the reply carries the SERVER's wall
            # clock so the worker can estimate its offset NTP-style from
            # its own send/receive stamps (horovod_tpu.tracing.ClockSync)
            # — no extra round trip, no extra route.
            return self._reply(
                200, json.dumps({"t_server": time.time()}).encode())
        self._reply(200, b"")

    def do_DELETE(self):  # noqa: N802
        if self._serve_fault():
            return
        if not self._authenticate():
            return
        scope = self.path.strip("/")
        with self.server.lock:  # type: ignore[attr-defined]
            rejected = self._fence_check_locked()
            if rejected is None:
                self.server.store.pop(scope, None)  # type: ignore[attr-defined]
        if rejected is not None:
            return self._reply(409, rejected)
        self._reply(200, b"")

    def _serve_trace_route(self, path: str, query: str):
        parsed = _trace_query(query)
        if parsed is None:
            return self._reply(
                400, b"bad query: use ?steps=N (positive int) "
                     b"and/or ?rank=R")
        steps, rank = parsed
        if path == "/timeline" and steps is None and rank is None:
            # The cap guards /timeline only: its body scales with the
            # raw span count. /criticalpath serves the small per-group
            # analysis (computed cached on every scrape regardless), so
            # capping it would deny the route while protecting nothing.
            cap = timeline_max_events()
            if cap > 0:
                count = _timeline_span_count(self.server)
                if count > cap:
                    return self._reply(
                        413,
                        (f"merged trace holds {count} span events > cap "
                         f"{cap} (HOROVOD_TIMELINE_MAX_EVENTS); bound "
                         f"the request with ?steps=N and/or ?rank=R"
                         ).encode())
        if path == "/criticalpath":
            render = (lambda httpd:
                      _render_criticalpath(httpd, steps=steps, rank=rank))
        else:
            render = (lambda httpd:
                      _render_timeline(httpd, steps=steps, rank=rank))
        return self._serve_json(render, "application/json")

    def _serve_metrics(self):
        try:
            body = _render_cluster_metrics(self.server).encode()
        except Exception as e:  # noqa: BLE001 — scrape must not kill the KV
            return self._reply(500, f"metrics render failed: {e}".encode())
        self.send_response(200)
        self.send_header("Content-Type",
                         "text/plain; version=0.0.4; charset=utf-8")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _serve_json(self, render, content_type: str):
        try:
            body = json.dumps(render(self.server)).encode()
        except Exception as e:  # noqa: BLE001 — must not kill the KV
            return self._reply(500, f"render failed: {e}".encode())
        self.send_response(200)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _reply(self, code: int, body: bytes):
        self.send_response(code)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


def _trace_payloads(httpd) -> dict[str, dict]:
    """Parsed ``PUT /trace`` payloads by host (malformed ones dropped —
    a broken worker must not break the merge for everyone else)."""
    with httpd.lock:
        raw = dict(httpd.store.get(TRACE_SCOPE, {}))
    out: dict[str, dict] = {}
    for host, body in raw.items():
        try:
            payload = json.loads(body)
        except (ValueError, UnicodeDecodeError):
            continue
        if isinstance(payload, dict):
            out[host] = payload
    return out


def _timeline_span_count(httpd) -> int:
    """Span events an unfiltered /timeline body would carry (the 413
    cap's cheap estimate — no JSON re-render)."""
    total = 0
    for payload in _trace_payloads(httpd).values():
        for steprec in payload.get("steps", ()) or ():
            if isinstance(steprec, dict):
                total += len(steprec.get("spans", ()) or ())
    return total


def _render_timeline(httpd, steps: int | None = None,
                     rank: str | None = None) -> dict:
    """The merged cross-rank trace: every shipped payload's spans on one
    server timebase (each rank's measured clock offset applied), one
    Chrome-trace process track per rank. Loadable directly in Perfetto /
    chrome://tracing. ``steps`` keeps only each rank's last N buffered
    steps; ``rank`` keeps one rank's track — the ``?steps=N`` /
    ``?rank=R`` query filters that keep large-world scrapes bounded."""
    payloads = _trace_payloads(httpd)
    events: list[dict] = []
    for host, payload in sorted(payloads.items()):
        if rank is not None and str(payload.get("rank", "?")) != str(rank):
            continue
        try:
            pid = int(payload.get("rank", 0))
        except (TypeError, ValueError):
            pid = 0
        try:
            offset = float(payload.get("clock_offset_s", 0.0) or 0.0)
        except (TypeError, ValueError):
            offset = 0.0
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "args": {"name": f"rank {pid} ({host})"}})
        events.append({"name": "process_sort_index", "ph": "M", "pid": pid,
                       "args": {"sort_index": pid}})
        steprecs = list(payload.get("steps", ()) or ())
        if steps is not None and steps > 0:
            steprecs = steprecs[-steps:]  # ring order: oldest first
        for steprec in steprecs:
            if not isinstance(steprec, dict):
                continue
            for sp in steprec.get("spans", ()) or ():
                if not isinstance(sp, dict):
                    continue
                try:
                    ts_us = (float(sp["t"]) + offset) * 1e6
                    dur_us = max(float(sp.get("dur", 0.0)), 0.0) * 1e6
                except (KeyError, TypeError, ValueError):
                    continue
                events.append({
                    "name": str(sp.get("name", "?")),
                    "cat": str(sp.get("cat", "phase")),
                    "ph": "X",
                    "ts": ts_us,
                    "dur": dur_us,
                    "pid": pid,
                    "tid": 0,
                    "args": {
                        "step": steprec.get("step"),
                        "host": host,
                        **(sp.get("args") or {}),
                    },
                })
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "metadata": {
            "timebase": "rendezvous-server wall clock (offsets applied)",
            "ranks": sorted(
                str(p.get("rank", "?")) for p in payloads.values()),
        },
    }


def _compute_cluster_skew(httpd) -> tuple[dict, dict[str, dict]]:
    """Arrival-skew attribution over the shipped payloads, plus the
    payloads themselves (so /metrics renders offsets without re-parsing).
    Journals a throttled ``straggler_detected`` event when the worst
    matched instance crosses ``HOROVOD_STRAGGLER_WARN_SKEW``."""
    payloads = _trace_payloads(httpd)
    skew = _tracing.compute_skew(payloads)
    worst = skew.get("worst")
    # Threshold on skew MINUS the combined clock-error bound: congested
    # heartbeats widen each rank's offset uncertainty (up to ~RTT/2), and
    # that uncertainty must never journal a healthy host as a straggler.
    if worst and (worst["skew_s"] - worst.get("err_s", 0.0)
                  >= _tracing.straggler_warn_skew()):
        with httpd.lock:
            version = httpd.version
            logged = getattr(httpd, "straggler_logged", None)
            if logged is None:
                logged = httpd.straggler_logged = set()
            key = (version, worst["last_rank"])
            fresh = key not in logged
            logged.add(key)
        if fresh:
            _metrics.event(
                "straggler_detected", generation=version,
                rank=worst["last_rank"], host=worst["last_host"],
                skew_s=worst["skew_s"], collective=worst["name"],
                step=worst["step"])
    return skew, payloads


def _cluster_attribution(httpd) -> dict:
    """The full-cluster step attribution (``attribution.analyze_cluster``
    over the shipped payloads), cached per trace-store mutation
    (``trace_version``) so repeated scrapes and replica polls cost one
    integer compare. A cache MISS additionally folds any new
    (generation, step) groups into the server's regression sentinel —
    the one place the sentinel ticks, so it advances exactly once per
    new sampled step no matter how many routes render it."""
    with httpd.lock:
        version = getattr(httpd, "trace_version", 0)
        cached = getattr(httpd, "attrib_cache", None)
    if cached is not None and cached[0] == version:
        return cached[1]
    analysis = _attribution.analyze_cluster(_trace_payloads(httpd))
    _sentinel_fold(httpd, analysis)
    with httpd.lock:
        httpd.attrib_cache = (version, analysis)
    return analysis


def _sentinel_fold(httpd, analysis: dict) -> None:
    """Feed NEW (generation, step) groups into the server's regression
    sentinel (EWMA baseline per phase over the cluster-mean
    decomposition), journal a ``step_regression`` event for each phase
    that newly crosses the drift threshold — naming the suspect rank the
    group's critical path gated on — and refresh the advisory
    ``regression_suspects`` map ({host: excess seconds}) the self-healing
    policy may consult (``HOROVOD_POLICY_STEP_REGRESSION``)."""
    sentinel = getattr(httpd, "attrib_sentinel", None)
    if sentinel is None:
        return
    with httpd.lock:
        folded = httpd.attrib_folded
        new = [g for g in analysis.get("groups", ())
               if (g["generation"], g["step"]) not in folded]
        folded.update((g["generation"], g["step"]) for g in new)
        if len(folded) > 4096:
            # Evict the OLDEST keys only: the per-rank ring advances
            # monotonically, so a low (generation, step) can never
            # reappear in the payloads — while an arbitrary set.pop()
            # could evict a still-buffered group and double-fold it
            # into the sentinel on the next mutation.
            for key in sorted(folded)[:len(folded) - 2048]:
                folded.discard(key)
    suspects: dict[str, float] = {}
    for g in new:
        ranks = g.get("ranks") or {}
        if not ranks:
            continue
        phases = {
            p: sum(d["phases"].get(p, 0.0) for d in ranks.values())
            / len(ranks)
            for p in _attribution.STEP_PHASES
        }
        verdict = sentinel.observe(phases, wall=g.get("wall_s"))
        alarmed = sorted(sentinel.snapshot()["alarmed"])
        if verdict["alarms"]:
            _metrics.event(
                "step_regression",
                generation=g["generation"], step=g["step"],
                phases=verdict["alarms"],
                scores={p: verdict["scores"].get(p)
                        for p in verdict["alarms"]},
                excess_s={p: verdict["excess_s"].get(p)
                          for p in verdict["alarms"]},
                suspect_rank=g.get("suspect_rank"),
                suspect_host=g.get("suspect_host"))
        # Advisory policy channel: while ANY phase is in alarm, the
        # latest group's critical-path suspect carries the worst
        # alarmed excess (seconds — directly comparable to the skew
        # and comms-residual lateness channels). No alarm = empty map.
        if alarmed and g.get("suspect_host"):
            suspects = {
                str(g["suspect_host"]): max(
                    (verdict["excess_s"].get(p, 0.0) for p in alarmed),
                    default=0.0)
            }
        elif not alarmed:
            suspects = {}
    if new:
        with httpd.lock:
            httpd.regression_suspects = suspects


def _render_criticalpath(httpd, steps: int | None = None,
                         rank: str | None = None) -> dict:
    """``GET /criticalpath``: the merged per-step attribution — per-rank
    phase decomposition (phases sum to each rank's step wall time), the
    cluster critical path with a named gating rank per collective
    barrier, per-rank MFU where the model declared its FLOPs, and the
    regression sentinel's state. A world with no synced samples yet
    (cold start, ``HOROVOD_TRACE_SAMPLE=0``) serves an explicit
    ``insufficient_samples`` body — never a 500. ``steps``/``rank`` are
    the bounding query filters (applied to the cached full analysis)."""
    analysis = _cluster_attribution(httpd)
    groups = list(analysis.get("groups", ()))
    if steps is not None and steps > 0:
        groups = groups[-steps:]
    if rank is not None:
        groups = [
            dict(g, ranks={r: d for r, d in g.get("ranks", {}).items()
                           if r == str(rank)})
            for g in groups
        ]
        groups = [g for g in groups if g["ranks"]]
    with httpd.lock:
        generation = httpd.version
        sentinel = getattr(httpd, "attrib_sentinel", None)
        suspects = dict(getattr(httpd, "regression_suspects", {}))
    return {
        "status": "ok" if groups else "insufficient_samples",
        "generation": generation,
        "groups": groups,
        "regression": {
            "sentinel": (sentinel.snapshot()
                         if sentinel is not None else None),
            "suspects": suspects,
        },
    }


def _comms_payloads(httpd) -> dict[str, dict]:
    """Per-rank comms-model payloads, as piggybacked on heartbeat PUTs
    (the ``"comms"`` key of each heartbeat body), keyed by host.
    Malformed heartbeats are skipped — same tolerance as the metrics
    piggyback."""
    with httpd.lock:
        raw = dict(httpd.store.get(HEARTBEAT_SCOPE, {}))
    out: dict[str, dict] = {}
    for host, body in raw.items():
        try:
            hb = json.loads(body)
        except (ValueError, UnicodeDecodeError):
            continue
        if not isinstance(hb, dict):
            continue
        comms = hb.get("comms")
        if isinstance(comms, dict):
            out[host] = comms
    return out


def _render_comms(httpd) -> dict:
    """``GET /comms``: the cluster-merged α–β link cost model. A world
    where nothing fitted yet (cold start, parked spares, single-device
    smoke) serves an explicit ``insufficient_samples`` body — never a
    500 (``comms_model.merge_payloads`` owns that contract)."""
    merged = _comms_model.merge_payloads(_comms_payloads(httpd))
    with httpd.lock:
        merged["generation"] = httpd.version
    return merged


def _memory_payloads(httpd) -> dict[str, dict]:
    """Per-rank memory-observatory payloads, as piggybacked on heartbeat
    PUTs (the ``"memory"`` key of each heartbeat body), keyed by host.
    Malformed heartbeats are skipped — same tolerance as the comms
    piggyback."""
    with httpd.lock:
        raw = dict(httpd.store.get(HEARTBEAT_SCOPE, {}))
    out: dict[str, dict] = {}
    for host, body in raw.items():
        try:
            hb = json.loads(body)
        except (ValueError, UnicodeDecodeError):
            continue
        if not isinstance(hb, dict):
            continue
        mem = hb.get("memory")
        if isinstance(mem, dict):
            out[host] = mem
    return out


def _render_memory(httpd) -> dict:
    """``GET /memory``: the cluster-merged HBM breakdown. A world where
    nothing measured yet (cold start, parked spares) serves an explicit
    ``insufficient_samples`` body — never a 500
    (``memory.merge_payloads`` owns that contract). Generation-fenced
    like ``/comms``: the body carries the world generation so readers
    can discard cross-generation merges."""
    merged = _memory.merge_payloads(_memory_payloads(httpd))
    with httpd.lock:
        merged["generation"] = httpd.version
    return merged


def _integrity_records(httpd, locked: bool = False) -> dict[int, dict]:
    """Per-rank integrity fingerprints, as piggybacked on heartbeat PUTs
    (the ``"integrity"`` key of each heartbeat body), keyed by the
    record's self-reported rank. Malformed heartbeats are skipped. Pass
    ``locked=True`` from a caller already holding ``httpd.lock`` (it is
    not reentrant)."""
    if locked:
        raw = dict(httpd.store.get(HEARTBEAT_SCOPE, {}))
    else:
        with httpd.lock:
            raw = dict(httpd.store.get(HEARTBEAT_SCOPE, {}))
    out: dict[int, dict] = {}
    for host, body in raw.items():
        try:
            hb = json.loads(body)
        except (ValueError, UnicodeDecodeError):
            continue
        if not isinstance(hb, dict):
            continue
        rec = hb.get("integrity")
        if not isinstance(rec, dict):
            continue
        try:
            rank = int(rec.get("rank", 0))
        except (TypeError, ValueError):
            continue
        # Colliding self-reported ranks: freshest record wins, so a
        # stale zombie's payload cannot shadow the live rank's.
        held = out.get(rank)
        if held is None or rec.get("t", 0) >= held.get("t", 0):
            out[rank] = rec
    return out


def _cached_integrity_vote(server, locked: bool = False):
    """(records, voted) for the current heartbeat store, cached per
    (``hb_version``, ``world_np``) mutation — repeated replica PUTs and
    idle ``GET /integrity`` polls (the scraper, every peer-rung
    assembly's quarantine fetch) cost one integer compare instead of a
    JSON parse of every fattened heartbeat body plus a re-vote."""
    world_np = getattr(server, "world_np", 0)
    key = (getattr(server, "hb_version", 0), world_np)
    cached = getattr(server, "integrity_vote_cache", None)
    if cached is not None and cached[0] == key:
        return cached[1], cached[2]
    records = _integrity_records(server, locked=locked)
    if not records:
        voted = None
    else:
        voted = _integrity.vote_latest(records, world_np or len(records))
    server.integrity_vote_cache = (key, records, voted)
    return records, voted


def _render_integrity(httpd) -> dict:
    """``GET /integrity``: the collected fingerprints plus the newest
    complete group's vote. A world where nothing fingerprinted yet
    (plane unarmed, cold start) serves an explicit ``no_records`` body —
    never a 500."""
    records, voted = _cached_integrity_vote(httpd)
    with httpd.lock:
        generation = httpd.version
        world_np = getattr(httpd, "world_np", 0)
        quarantined = dict(getattr(httpd, "integrity_quarantine", {}))
        divergence = dict(getattr(httpd, "integrity_divergence", {}))
    out = {
        "status": "ok" if records else "no_records",
        "generation": generation,
        "world_size": world_np,
        "records": {str(r): rec for r, rec in sorted(records.items())},
        "quarantined": quarantined,
        "divergence_counts": divergence,
        "vote": None,
    }
    if voted is not None:
        (gen, step), verdict = voted
        out["vote"] = {"group": [gen, step], **verdict}
    return out


def _render_model(httpd) -> dict:
    """``GET /model``: the training→serving bridge's health/age view —
    the newest complete, checksum-valid, unquarantined ``modelstate``
    commit the stored records can assemble right now, plus publish
    counters and the model age. A cold scope serves an explicit
    ``no_model`` body, an unassemblable one serves the reason — never a
    500: this is what load balancers and readiness probes poll."""
    with httpd.lock:
        generation = httpd.version
        publishes = getattr(httpd, "model_publishes", 0)
        rejected = getattr(httpd, "model_rejected", 0)
        last_t = getattr(httpd, "model_last_t", None)
        blobs = list(httpd.store.get(MODELSTATE_SCOPE, {}).values())
        quarantine = dict(getattr(httpd, "integrity_quarantine", {}))
    records = []
    for blob in blobs:
        try:
            records.append(_peercheck.decode_record(blob, verify=True))
        except Exception:  # noqa: BLE001 — judged at assembly, not here
            continue
    out = {
        "status": "no_model",
        "generation": generation,
        "publishes": publishes,
        "rejected": rejected,
        "age_seconds": (None if last_t is None
                        else max(0.0, time.time() - last_t)),
        "model": None,
    }
    if not records:
        return out
    try:
        members = _peercheck.assemble_records(
            records, generation, quarantine=quarantine)
    except _peercheck.ReplicaUnavailableError as e:
        out["status"] = "unassemblable"
        out["reason"] = str(e)
        return out
    out["status"] = "ok"
    out["model"] = {
        "generation": members[0].generation,
        "step": members[0].step,
        "world_size": members[0].world_size,
        "ranks": [r.rank for r in members],
        "bytes": sum(len(r.payload) for r in members),
        "digest": _peercheck.replica_set_digest(members),
    }
    return out


def _render_cluster_metrics(httpd) -> str:
    """The driver's cluster-wide scrape: driver-plane gauges built from
    live server state, then every worker snapshot found piggybacked on a
    heartbeat payload, rendered with per-rank/host labels."""
    with httpd.lock:
        version = httpd.version
        fenced = httpd.fenced
        world_np = getattr(httpd, "world_np", 0)
        blacklisted = getattr(httpd, "blacklisted", 0)
        spares = getattr(httpd, "spare_count", 0)
        policy_actions = dict(getattr(httpd, "policy_actions", {}))
        driver_epoch = getattr(httpd, "driver_epoch", 0)
        driver_lost = dict(getattr(httpd, "driver_lost", {}))
        integrity_div = dict(getattr(httpd, "integrity_divergence", {}))
        quarantined = sum(
            1 for e in getattr(httpd, "integrity_quarantine", {}).values()
            if not e.get("lifted"))  # tombstones only filter assembly
        now = time.monotonic()
        ages = {h: now - t for h, t in httpd.hb_times.items()}
        payloads = dict(httpd.store.get(HEARTBEAT_SCOPE, {}))
    driver_families = [
        _metrics.make_family(
            "hvd_world_generation", "gauge",
            "Monotonic world generation (the rendezvous epoch version).",
            [({}, version)]),
        _metrics.make_family(
            "hvd_world_size", "gauge",
            "Hosts in the current world epoch (0 before the first "
            "elastic publish).", [({}, world_np)]),
        _metrics.make_family(
            "hvd_blacklisted_hosts", "gauge",
            "Hosts currently blacklisted by the elastic driver.",
            [({}, blacklisted)]),
        _metrics.make_family(
            "hvd_fenced_writes_total", "counter",
            "Stale-generation writes rejected by the generation fence.",
            [({}, fenced)]),
        _metrics.make_family(
            "hvd_heartbeat_age_seconds", "gauge",
            "Seconds since each host's last heartbeat (server clock).",
            [({"host": h}, age) for h, age in sorted(ages.items())]),
        # Self-healing policy plane: zero-materialized so the scrape gate
        # can assert the instruments exist before any decision fires, and
        # dashboards can tell "no drains yet" from "not measuring".
        _metrics.make_family(
            "hvd_policy_spare_hosts", "gauge",
            "Warm spare hosts currently launched, heartbeating, and held "
            "out of the world by the elastic driver.",
            [({}, spares)]),
        _metrics.make_family(
            "hvd_policy_decisions_total", "counter",
            "Self-healing policy actions taken by the elastic driver "
            "(drain|promote|preempt).",
            [({"action": a}, policy_actions.get(a, 0))
             for a in POLICY_ACTIONS]),
        # Control-plane fault tolerance: the driver epoch (split-brain
        # fence identity; 0 = no driver-state plane) and per-host
        # EXIT_DRIVER_LOST reap counts. The unlabeled sample is the
        # job-wide total, zero-materialized so the scrape gate can
        # assert the instrument before any flap.
        _metrics.make_family(
            "hvd_driver_epoch", "gauge",
            "Monotonic driver epoch: bumped on every driver (re)start; "
            "stale-epoch writes are 409-fenced.",
            [({}, driver_epoch)]),
        _metrics.make_family(
            "hvd_driver_lost_total", "counter",
            "Workers reaped with EXIT_DRIVER_LOST (rendezvous KV "
            "unreachable past the deadline) — control-plane flaps, by "
            "host, plus the unlabeled job-wide total.",
            [({}, sum(driver_lost.values()))]
            + [({"host": h}, n) for h, n in sorted(driver_lost.items())]),
        # Integrity defense plane (driver-side vote outcomes): the
        # unlabeled sample is the job-wide total, zero-materialized so
        # the scrape gate can assert the instrument before any
        # corruption ever happens.
        _metrics.make_family(
            "hvd_integrity_divergence_total", "counter",
            "Cross-rank integrity votes that named a host's replica "
            "state divergent (silent data corruption evidence), by "
            "host, plus the unlabeled job-wide total.",
            [({}, sum(integrity_div.values()))]
            + [({"host": h}, n)
               for h, n in sorted(integrity_div.items())]),
        _metrics.make_family(
            "hvd_integrity_quarantined_ranks", "gauge",
            "Ranks whose peer-replica PUTs are currently fenced by an "
            "integrity-vote quarantine.", [({}, quarantined)]),
    ]
    # Multi-tenant pod: a driver serving one job of a shared pool
    # (HOROVOD_JOB_ID set per job process tree by the scheduler) stamps
    # every family on its scrape with the job dimension, so N per-job
    # scrape targets merge in PromQL without relabeling. Unset (every
    # single-job path) the scrape is bit-for-bit the HEAD body.
    job = os.environ.get("HOROVOD_JOB_ID") or ""
    job_labels = {"job": job} if job else {}
    groups: list = [(job_labels, driver_families)]
    steps_samples: list = []
    commit_samples: list = []
    for host, raw in sorted(payloads.items()):
        try:
            payload = json.loads(raw)
        except (ValueError, UnicodeDecodeError):
            continue
        if not isinstance(payload, dict):
            continue
        labels = {"host": host, **job_labels}
        rank = payload.get("rank")
        if rank is not None:
            labels["rank"] = str(rank)
        if isinstance(payload.get("steps"), (int, float)):
            steps_samples.append((labels, payload["steps"]))
        if isinstance(payload.get("commits"), (int, float)):
            commit_samples.append((labels, payload["commits"]))
        families = payload.get("metrics")
        if isinstance(families, list):
            families = [f for f in families
                        if isinstance(f, dict) and "name" in f]
            if families:
                groups.append((labels, families))
    driver_families.append(_metrics.make_family(
        "hvd_worker_steps_total", "counter",
        "Watched steps reported on each worker's last heartbeat.",
        steps_samples))
    driver_families.append(_metrics.make_family(
        "hvd_worker_commits_total", "counter",
        "State commits reported on each worker's last heartbeat.",
        commit_samples))
    # Tick the step-attribution plane on every scrape (cached per trace
    # mutation, so an idle poll costs one integer compare): the
    # regression sentinel must advance on the operator's regular
    # /metrics cadence even when nobody fetches /criticalpath.
    try:
        _cluster_attribution(httpd)
    except Exception:  # noqa: BLE001 — attribution must not kill the scrape
        pass
    # Straggler attribution from the tracing plane: per-rank arrival skew
    # against the earliest rank on matched collectives/steps (shipped
    # trace payloads, offset-corrected), and a per-host score the
    # autoscaler (ROADMAP item 3) can threshold on. Empty when no traces
    # have shipped (HOROVOD_TRACE_SAMPLE=0) — absent series, not zeros,
    # so dashboards can tell "no stragglers" from "not measuring".
    skew, payloads = _compute_cluster_skew(httpd)
    skew_samples = []
    host_lateness: dict[str, list[float]] = {}
    for rank, info in sorted(skew.get("ranks", {}).items()):
        labels = {"rank": rank, "host": info.get("host", "")}
        skew_samples.append((labels, info["max_lateness_s"]))
        host_lateness.setdefault(info.get("host", ""), []).append(
            info["mean_lateness_s"])
    if skew_samples:
        driver_families.append(_metrics.make_family(
            "hvd_collective_skew_seconds", "gauge",
            "Max arrival lateness of each rank behind the earliest rank "
            "on matched collectives (offset-corrected trace spans).",
            skew_samples))
        driver_families.append(_metrics.make_family(
            "hvd_straggler_score", "gauge",
            "Mean arrival lateness per host across its ranks' matched "
            "collectives — the straggler-replacement signal.",
            [({"host": h}, sum(ls) / len(ls))
             for h, ls in sorted(host_lateness.items())]))
    offset_samples = [
        ({"rank": str(p.get("rank", "?")), "host": h},
         float(p.get("clock_offset_s", 0.0) or 0.0))
        for h, p in sorted(payloads.items())
    ]
    if offset_samples:
        driver_families.append(_metrics.make_family(
            "hvd_trace_clock_offset_seconds", "gauge",
            "Each rank's measured wall-clock offset vs the rendezvous "
            "server (server - local), as shipped with its trace.",
            offset_samples))
    return _metrics.render_families(groups)


class RendezvousServer:
    """In-memory scoped KV over HTTP, owned by the launcher."""

    def __init__(self, host: str = "0.0.0.0"):
        self._httpd = ThreadingHTTPServer((host, 0), _KVHandler)
        self._httpd.store = {}  # type: ignore[attr-defined]
        self._httpd.lock = threading.Lock()  # type: ignore[attr-defined]
        self._httpd.version = 0  # type: ignore[attr-defined]
        self._httpd.fenced = 0  # type: ignore[attr-defined]
        self._httpd.hb_times = {}  # type: ignore[attr-defined]
        self._httpd.world_np = 0  # type: ignore[attr-defined]
        self._httpd.blacklisted = 0  # type: ignore[attr-defined]
        self._httpd.spare_count = 0  # type: ignore[attr-defined]
        self._httpd.policy_actions = {}  # type: ignore[attr-defined]
        self._httpd.driver_epoch = 0  # type: ignore[attr-defined]
        self._httpd.driver_lost = {}  # type: ignore[attr-defined]
        self._httpd.integrity_quarantine = {}  # type: ignore[attr-defined]
        self._httpd.integrity_divergence = {}  # type: ignore[attr-defined]
        # Training→serving bridge counters (the GET /model health view):
        # accepted / fence-or-verify-rejected modelstate publishes and
        # the wall time of the last accepted one (model age).
        self._httpd.model_publishes = 0  # type: ignore[attr-defined]
        self._httpd.model_rejected = 0  # type: ignore[attr-defined]
        self._httpd.model_last_t = None  # type: ignore[attr-defined]
        # Inertness latch + vote cache for the live-vote fence: until a
        # heartbeat actually carries an integrity fingerprint, peerstate
        # PUTs must not pay a JSON parse of every heartbeat body; once
        # armed, the parse+vote runs once per heartbeat mutation, not
        # once per replica PUT.
        self._httpd.integrity_seen = False  # type: ignore[attr-defined]
        self._httpd.hb_version = 0  # type: ignore[attr-defined]
        self._httpd.integrity_vote_cache = None  # type: ignore[attr-defined]
        self._httpd.straggler_logged = set()  # type: ignore[attr-defined]
        # Step-attribution plane: the analysis cache (keyed by the trace
        # mutation counter), the regression sentinel, the set of
        # (generation, step) groups already folded into it, and the
        # advisory {host: excess seconds} suspect map the policy may
        # consult (HOROVOD_POLICY_STEP_REGRESSION).
        self._httpd.trace_version = 0  # type: ignore[attr-defined]
        self._httpd.attrib_cache = None  # type: ignore[attr-defined]
        self._httpd.attrib_sentinel = (  # type: ignore[attr-defined]
            _attribution.RegressionSentinel())
        self._httpd.attrib_folded = set()  # type: ignore[attr-defined]
        self._httpd.regression_suspects = {}  # type: ignore[attr-defined]
        # Key snapshot at construction: the job's secret must not drift
        # under a live server (and env edits elsewhere must not rekey it).
        self._httpd.secret = _secret.current_key()  # type: ignore[attr-defined]
        self._thread: threading.Thread | None = None

    @property
    def port(self) -> int:
        return self._httpd.server_address[1]

    @property
    def version(self) -> int:
        return self._httpd.version  # type: ignore[attr-defined]

    @property
    def generation(self) -> int:
        """The monotonic world generation (alias of the epoch version:
        both bump together on every world re-formation)."""
        return self._httpd.version  # type: ignore[attr-defined]

    @property
    def fenced_writes(self) -> int:
        """How many stale-generation/stale-epoch writes the fences have
        rejected."""
        with self._httpd.lock:  # type: ignore[attr-defined]
            return self._httpd.fenced  # type: ignore[attr-defined]

    @property
    def driver_epoch(self) -> int:
        return self._httpd.driver_epoch  # type: ignore[attr-defined]

    def seed(self, generation: int | None = None,
             driver_epoch: int | None = None) -> None:
        """Takeover entry (``runner/elastic/driver_state.py``): a
        restarted driver seeds its fresh server with the snapshot's
        world generation — so the takeover epoch publishes at g+1 and
        the existing generation fence stays monotonic across the crash —
        and with its own (bumped) driver epoch, arming the split-brain
        fence. Call before :meth:`start`."""
        with self._httpd.lock:  # type: ignore[attr-defined]
            if generation is not None:
                self._httpd.version = int(generation)  # type: ignore[attr-defined]
            if driver_epoch is not None:
                self._httpd.driver_epoch = int(driver_epoch)  # type: ignore[attr-defined]

    def seed_driver_lost(self, counts: dict) -> None:
        """Takeover resume: carry the predecessor's per-host
        EXIT_DRIVER_LOST counts into the scrape, so
        ``hvd_driver_lost_total`` keeps telling the truth about flaps
        building toward the blacklist cap across the very control-plane
        event it exists to expose."""
        with self._httpd.lock:  # type: ignore[attr-defined]
            table = self._httpd.driver_lost  # type: ignore[attr-defined]
            for host, n in (counts or {}).items():
                try:
                    table[str(host)] = max(table.get(str(host), 0),
                                           int(n))
                except (TypeError, ValueError):
                    continue

    def record_driver_lost(self, host: str) -> None:
        """Count one EXIT_DRIVER_LOST reap into the scrape's
        ``hvd_driver_lost_total{host}`` counter (the control-plane flap
        signal operators watch before the 3-consecutive cap blacklists
        a healthy host)."""
        with self._httpd.lock:  # type: ignore[attr-defined]
            counts = self._httpd.driver_lost  # type: ignore[attr-defined]
            counts[host] = counts.get(host, 0) + 1

    def done_records(self) -> dict[str, dict]:
        """Hosts whose workers announced clean completion (parsed
        ``PUT /done/<host>`` records) — how an ADOPTED worker's rc=0
        survives the driver restart that orphaned it."""
        return self._scope_records(DONE_SCOPE)

    def set_cluster_info(self, world_np: int | None = None,
                         blacklisted: int | None = None,
                         spares: int | None = None) -> None:
        """Driver-side gauges for the ``/metrics`` scrape: the elastic
        driver refreshes these on every world publish / blacklist / spare
        change, since only it knows them (the server sees heartbeats, not
        topology)."""
        with self._httpd.lock:  # type: ignore[attr-defined]
            if world_np is not None:
                self._httpd.world_np = int(world_np)  # type: ignore[attr-defined]
            if blacklisted is not None:
                self._httpd.blacklisted = int(blacklisted)  # type: ignore[attr-defined]
            if spares is not None:
                self._httpd.spare_count = int(spares)  # type: ignore[attr-defined]

    def record_policy_action(self, action: str) -> None:
        """Count one self-healing policy action into the scrape's
        ``hvd_policy_decisions_total{action=...}`` counter."""
        with self._httpd.lock:  # type: ignore[attr-defined]
            counts = self._httpd.policy_actions  # type: ignore[attr-defined]
            counts[action] = counts.get(action, 0) + 1

    # -- warm-spare registration + preemption notices -------------------------

    def _scope_records(self, scope: str) -> dict[str, dict]:
        with self._httpd.lock:  # type: ignore[attr-defined]
            raw = dict(self._httpd.store.get(scope, {}))  # type: ignore[attr-defined]
        out: dict[str, dict] = {}
        for key, body in raw.items():
            try:
                rec = json.loads(body)
            except (ValueError, UnicodeDecodeError):
                rec = {}
            out[key] = rec if isinstance(rec, dict) else {}
        return out

    def spare_records(self) -> dict[str, dict]:
        """Hosts whose spare workers have registered as warm (parsed
        ``PUT /spare/<host>`` records)."""
        return self._scope_records(SPARE_SCOPE)

    def clear_spare(self, host: str) -> None:
        """Drop a host's spare registration (promotion into the world, or
        spare teardown)."""
        with self._httpd.lock:  # type: ignore[attr-defined]
            self._httpd.store.get(  # type: ignore[attr-defined]
                SPARE_SCOPE, {}).pop(host, None)

    def preempt_notices(self) -> dict[str, dict]:
        """Outstanding external preemption notices by host (parsed
        ``PUT /preempt/<host>`` records)."""
        return self._scope_records(PREEMPT_SCOPE)

    def consume_preempt(self, host: str) -> None:
        """Drop a handled preemption notice so the drain fires once."""
        with self._httpd.lock:  # type: ignore[attr-defined]
            self._httpd.store.get(  # type: ignore[attr-defined]
                PREEMPT_SCOPE, {}).pop(host, None)

    # -- integrity defense plane ----------------------------------------------

    def heartbeat_version(self) -> int:
        """Monotonic heartbeat-store mutation counter (bumped on every
        heartbeat PUT and ``clear_heartbeat``): lets pollers skip
        re-parsing every heartbeat body when nothing has changed."""
        return getattr(self._httpd, "hb_version", 0)

    def integrity_records(self) -> dict[int, dict]:
        """Per-rank integrity fingerprints from the heartbeat piggyback
        — what the driver's voting tick consumes."""
        return _integrity_records(self._httpd)

    def integrity_vote_cached(self):
        """(records, voted) via the ``(hb_version, world_np)``-keyed
        cache shared with the live-vote fence and ``GET /integrity`` —
        the driver's voting tick must not re-parse every heartbeat body
        when the in-process fence already did."""
        return _cached_integrity_vote(self._httpd)

    def integrity_summary(self) -> dict:
        """The collected records + live vote (what ``GET /integrity``
        serves over HTTP), rendered in-process."""
        return _render_integrity(self._httpd)

    def record_integrity_divergence(self, host: str) -> None:
        """Count one divergence vote against ``host`` into the scrape's
        ``hvd_integrity_divergence_total{host}``."""
        with self._httpd.lock:  # type: ignore[attr-defined]
            counts = self._httpd.integrity_divergence  # type: ignore[attr-defined]
            counts[host] = counts.get(host, 0) + 1

    def quarantine_rank(self, rank, host: str, generation: int,
                        step: int, from_generation: int | None = None,
                        from_step: int | None = None) -> None:
        """Fence a divergent rank's peer-replica PUTs and EVICT its
        current ``peerstate`` record (the corrupt shard): the retained
        ``.prev`` slot — the last commit the vote did not condemn —
        stays, so peer-rung assembly falls back one commit instead of
        installing corruption. The fence lifts when a write arrives from
        a strictly newer world generation (the re-formed world reuses
        the rank id for a healthy worker). ``generation``/``step`` are
        the VOTE's group (the fence-lift anchor);
        ``from_generation``/``from_step`` (default: the same group) are
        where the condemned range STARTS — a vote that back-dated the
        corruption to a prior generation's fingerprint condemns that
        generation's replica records too."""
        with self._httpd.lock:  # type: ignore[attr-defined]
            self._httpd.integrity_quarantine[str(rank)] = {  # type: ignore[attr-defined]
                "host": str(host),
                "generation": int(generation),
                "step": int(step),
                "from_generation": int(generation if from_generation is None
                                       else from_generation),
                "from_step": int(step if from_step is None else from_step),
                "t": time.time(),
            }
            self._httpd.store.get(  # type: ignore[attr-defined]
                PEERSTATE_SCOPE, {}).pop(str(rank), None)

    def quarantine_export(self) -> dict:
        """The integrity-quarantine map (incl. tombstones), JSON-able —
        persisted in the driver snapshot so a takeover driver's fresh
        server re-fences a condemned rank instead of re-admitting its
        proven-corrupt replicas to peer-rung assembly."""
        with self._httpd.lock:  # type: ignore[attr-defined]
            return {r: dict(e) for r, e in
                    self._httpd.integrity_quarantine.items()}  # type: ignore[attr-defined]

    def restore_quarantine(self, entries) -> None:
        if not isinstance(entries, dict):
            return
        with self._httpd.lock:  # type: ignore[attr-defined]
            for r, e in entries.items():
                if isinstance(e, dict):
                    self._httpd.integrity_quarantine[str(r)] = dict(e)  # type: ignore[attr-defined]

    def metrics_text(self) -> str:
        """The scrape body, rendered in-process (what ``GET /metrics``
        serves over HTTP)."""
        return _render_cluster_metrics(self._httpd)

    def timeline_json(self) -> dict:
        """The merged cross-rank Chrome trace (what ``GET /timeline``
        serves over HTTP), rendered in-process."""
        return _render_timeline(self._httpd)

    def criticalpath_summary(self, steps: int | None = None,
                             rank: str | None = None) -> dict:
        """The merged step attribution (what ``GET /criticalpath``
        serves over HTTP), rendered in-process."""
        return _render_criticalpath(self._httpd, steps=steps, rank=rank)

    def regression_suspects(self) -> dict[str, float]:
        """The regression sentinel's advisory {host: excess seconds}
        map — non-empty only while a phase baseline is in alarm, naming
        the critical path's gating host. The elastic driver feeds this
        to the policy controller when ``HOROVOD_POLICY_STEP_REGRESSION``
        arms that channel. Ticks the (cached) analysis first so the map
        reflects the latest shipped traces."""
        try:
            _cluster_attribution(self._httpd)
        except Exception:  # noqa: BLE001 — advisory channel
            pass
        with self._httpd.lock:  # type: ignore[attr-defined]
            return dict(getattr(self._httpd, "regression_suspects", {}))

    def straggler_summary(self) -> dict:
        """The arrival-skew attribution (what ``GET /stragglers``
        serves), rendered in-process."""
        return _compute_cluster_skew(self._httpd)[0]

    def comms_summary(self) -> dict:
        """The cluster-merged α–β link cost model (what ``GET /comms``
        serves), rendered in-process. Its ``"residuals"`` map (host →
        worst predicted-vs-observed residual seconds) is the second
        straggler-evidence channel the elastic driver feeds
        ``elastic/policy.py``."""
        return _render_comms(self._httpd)

    def memory_summary(self) -> dict:
        """The cluster-merged HBM breakdown (what ``GET /memory``
        serves), rendered in-process — per-rank resident bytes by kind,
        phase watermark maxes, the minimum headroom ratio, and the
        worst model drift."""
        return _render_memory(self._httpd)

    def trace_payload(self, host: str) -> dict | None:
        """The last trace payload a host shipped, parsed, or None."""
        return _trace_payloads(self._httpd).get(host)

    def start(self) -> int:
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="hvd-rendezvous", daemon=True
        )
        self._thread.start()
        return self.port

    def reset(self) -> int:
        """Elastic reconfiguration: clear state, bump the world version."""
        with self._httpd.lock:  # type: ignore[attr-defined]
            self._httpd.store.clear()  # type: ignore[attr-defined]
            self._httpd.version += 1  # type: ignore[attr-defined]
            # Trace scope went with the store: invalidate the cached
            # attribution analysis or /criticalpath would keep serving
            # the dead world's groups.
            self._httpd.trace_version = (  # type: ignore[attr-defined]
                getattr(self._httpd, "trace_version", 0) + 1)
            return self._httpd.version  # type: ignore[attr-defined]

    def publish_epoch(self, scope_prefix: str, data: dict[str, bytes],
                      keep_epochs: int = 2) -> int:
        """Atomically publish a new epoch: write ``<scope_prefix>/<v+1>``
        first, THEN bump the version — in-flight readers of the previous
        epoch keep seeing their scope (the last ``keep_epochs`` are kept)."""
        with self._httpd.lock:  # type: ignore[attr-defined]
            version = self._httpd.version + 1  # type: ignore[attr-defined]
            store = self._httpd.store  # type: ignore[attr-defined]
            store[f"{scope_prefix}/{version}"] = dict(data)
            stale = version - keep_epochs
            if stale > 0:
                store.pop(f"{scope_prefix}/{stale}", None)
            self._httpd.version = version  # type: ignore[attr-defined]
            return version

    # -- coordinated abort plane --------------------------------------------

    def post_abort(self, reason: str, generation: int | None = None) -> int:
        """Post the abort record for a world generation (default: the
        current one). Every worker of that generation polls it and
        converts its current wedge into ``HorovodInternalError``; posted
        BEFORE the driver bumps the generation so survivors still at the
        dying generation see it. Returns the generation posted for."""
        record = json.dumps({"reason": reason, "time": time.time()}).encode()
        with self._httpd.lock:  # type: ignore[attr-defined]
            gen = (self._httpd.version  # type: ignore[attr-defined]
                   if generation is None else generation)
            self._httpd.store.setdefault(  # type: ignore[attr-defined]
                ABORT_SCOPE, {})[str(gen)] = record
        return gen

    def abort_record(self, generation: int) -> bytes | None:
        with self._httpd.lock:  # type: ignore[attr-defined]
            return self._httpd.store.get(  # type: ignore[attr-defined]
                ABORT_SCOPE, {}).get(str(generation))

    # -- heartbeat liveness plane -------------------------------------------

    def heartbeat_ages(self) -> dict[str, float]:
        """Seconds since each host's last heartbeat (server clock)."""
        now = time.monotonic()
        with self._httpd.lock:  # type: ignore[attr-defined]
            return {h: now - t
                    for h, t in self._httpd.hb_times.items()}  # type: ignore[attr-defined]

    def heartbeat_age(self, host: str) -> float | None:
        """Seconds since `host`'s last heartbeat, or None if never seen."""
        with self._httpd.lock:  # type: ignore[attr-defined]
            t = self._httpd.hb_times.get(host)  # type: ignore[attr-defined]
        return None if t is None else time.monotonic() - t

    def heartbeat_payload(self, host: str) -> bytes | None:
        """The host's last heartbeat body (JSON: step/commit counters)."""
        with self._httpd.lock:  # type: ignore[attr-defined]
            return self._httpd.store.get(  # type: ignore[attr-defined]
                HEARTBEAT_SCOPE, {}).get(host)

    def clear_heartbeat(self, host: str) -> None:
        """Forget a host's liveness record (worker relaunch/removal): a
        stale timestamp must neither mask a hung relaunch nor instantly
        condemn a fresh one. The host's trace payload goes with it — a
        departed rank's spans must not keep skewing the merged timeline
        and straggler gauges against the re-formed world."""
        with self._httpd.lock:  # type: ignore[attr-defined]
            self._httpd.hb_times.pop(host, None)  # type: ignore[attr-defined]
            self._httpd.store.get(  # type: ignore[attr-defined]
                HEARTBEAT_SCOPE, {}).pop(host, None)
            self._httpd.store.get(  # type: ignore[attr-defined]
                TRACE_SCOPE, {}).pop(host, None)
            # The departed host's fingerprint left the record set: the
            # live-vote fence must not keep serving a vote over it.
            self._httpd.hb_version = (  # type: ignore[attr-defined]
                getattr(self._httpd, "hb_version", 0) + 1)
            # Its trace payload left too: the attribution cache must
            # re-analyze without the departed rank's spans.
            self._httpd.trace_version = (  # type: ignore[attr-defined]
                getattr(self._httpd, "trace_version", 0) + 1)

    def stop(self) -> None:
        self._httpd.shutdown()
        if self._thread:
            self._thread.join(timeout=5)
        self._httpd.server_close()


class KVClient:
    """Worker-side client for the rendezvous KV server. Signs every
    request with the job secret when HOROVOD_SECRET_KEY is set.

    Every request retries transient transport failures with bounded
    exponential backoff + jitter (``HOROVOD_KV_RETRIES`` attempts, base
    ``HOROVOD_KV_RETRY_BACKOFF`` seconds): a driver mid-restart or a
    network blip below the retry budget is fully absorbed, while a dead
    driver still surfaces as an exception the caller's escalation path
    (``worker.start_polling``) can act on — never an unbounded silent
    retry. HTTP status answers (404 = no value, 403 = bad auth, 409 =
    fenced stale-generation write) are answers, not blips, and propagate
    immediately.

    ``generation_fn`` (elastic workers pass their live world-generation
    view) stamps every write with ``X-Hvd-Generation`` so the server's
    fence can reject zombies from a pre-abort world; ``epoch_fn``
    likewise stamps ``X-Hvd-Driver-Epoch`` (the split-brain fence: a
    write still loyal to a superseded driver's epoch is 409'd). ``None``
    (or a fn returning ``None``) leaves writes unfenced.
    """

    def __init__(self, addr: str, port: int, timeout: float = 10.0,
                 retries: int | None = None, backoff: float | None = None,
                 generation_fn: Callable[[], int | None] | None = None,
                 epoch_fn: Callable[[], int | None] | None = None):
        self._base = f"http://{addr}:{port}"
        self._timeout = timeout
        self._retries = (get_int("HOROVOD_KV_RETRIES", 3)
                         if retries is None else retries)
        self._backoff = (get_float("HOROVOD_KV_RETRY_BACKOFF", 0.1)
                         if backoff is None else backoff)
        self._generation_fn = generation_fn
        self._epoch_fn = epoch_fn

    def _request(self, method: str, path: str, body: bytes | None = None):
        def attempt():
            if faults.fire(faults.KV_REQUEST):
                # drop: the request never happened — to the caller that is
                # a transport failure, so surface it as one (and retry).
                raise faults.InjectedFault(f"kv request dropped: {path}")
            req = Request(f"{self._base}{path}", data=body, method=method)
            tag = _secret.sign(_auth_payload(method, path, body or b""))
            if tag:
                req.add_header(AUTH_HEADER, tag)
            if self._generation_fn is not None and method in ("PUT",
                                                              "DELETE"):
                gen = self._generation_fn()
                if gen is not None:
                    if faults.fire(faults.KV_FENCE):
                        # Chaos: impersonate a zombie from the pre-abort
                        # world — the server must 409 this write.
                        gen -= 1
                    req.add_header(GENERATION_HEADER, str(gen))
            if self._epoch_fn is not None and method in ("PUT", "DELETE"):
                epoch = self._epoch_fn()
                if epoch is not None:
                    req.add_header(DRIVER_EPOCH_HEADER, str(epoch))
            return urlopen(req, timeout=self._timeout)

        return call_with_retries(
            attempt,
            attempts=max(1, self._retries),
            base_delay=self._backoff,
            give_up_on=(HTTPError,),
        )

    def put(self, scope: str, key: str, value: bytes) -> bytes:
        """Write one key; returns the reply body (heartbeat PUTs carry
        the server's wall clock there — see ``tracing.ClockSync``)."""
        with self._request("PUT", f"/{scope}/{key}", value) as r:
            return r.read()

    def get(self, scope: str, key: str) -> bytes | None:
        try:
            with self._request("GET", f"/{scope}/{key}") as r:
                return r.read()
        except HTTPError as e:
            if e.code == 404:
                return None
            raise

    def integrity_view(self) -> dict:
        """``GET /integrity`` (auth-exempt): the SDC defense plane's
        collected fingerprints, live vote, and quarantine map — what the
        peer-replica assembly consults so a condemned rank's records are
        dropped from its LOCAL pool too, not just evicted from the KV."""
        with self._request("GET", "/integrity") as r:
            return json.loads(r.read().decode())

    def model_view(self) -> dict:
        """``GET /model`` (auth-exempt): the training→serving bridge's
        health/age view — the newest assemblable ``modelstate`` commit,
        publish counters, and the model age (what serving readiness
        probes poll)."""
        with self._request("GET", "/model") as r:
            return json.loads(r.read().decode())

    def keys(self, scope: str) -> list[str]:
        with self._request("GET", f"/_scope/{scope}") as r:
            body = r.read().decode()
        return [k for k in body.split("\n") if k]

    def delete_scope(self, scope: str) -> None:
        with self._request("DELETE", f"/{scope}"):
            pass

    def world_version(self) -> int:
        with self._request("GET", "/_version") as r:
            return int(r.read())

    def driver_epoch(self) -> int:
        """The serving driver's epoch (``GET /_epoch``; 0 when the
        driver-state plane is off)."""
        with self._request("GET", "/_epoch") as r:
            return int(r.read())

    def abort_posted(self, generation: int) -> dict | None:
        """The abort record for a world generation, or None. Decoded JSON
        (``{"reason", "time", ...}``); raw text falls back to a dict."""
        raw = self.get(ABORT_SCOPE, str(generation))
        if raw is None:
            return None
        try:
            return json.loads(raw)
        except ValueError:
            return {"reason": raw.decode(errors="replace")}
