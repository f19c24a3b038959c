"""Compiled-executable cache for eager collectives.

The TPU-native descendant of the reference's response cache
(``horovod/common/response_cache.cc``): where Horovod caches *negotiated
responses* keyed by tensor signature so steady-state steps skip the
controller round-trip, an XLA system caches *compiled executables* keyed by
the same signature — op type, shape, dtype, process set, scale factors. A
cache hit dispatches a pre-compiled collective with zero negotiation or
compilation; a miss costs one XLA compile (the analog of Horovod's slow
negotiation path), so signatures are designed to repeat (static shapes,
bucket-size quantization in the fusion pass).

An LRU bound (``HOROVOD_CACHE_CAPACITY``) protects against signature churn
from dynamic shapes, just as the reference's capacity bound does.

The cache also keeps a per-entry serialized-cost ledger
(:meth:`ExecutableCache.note_bytes` / :meth:`ExecutableCache.nbytes`):
the dispatch path notes each compiled program's serialized size on the
miss, so ``hvd.cache_stats()`` can report the cache's memory cost in
bytes and the memory observatory can expose it as
``hvd_hbm_bytes{kind="executables"}`` — previously the cache's size was
visible only as an entry COUNT.
"""

from __future__ import annotations

import collections
import threading
from typing import Any, Callable, Hashable


class ExecutableCache:
    def __init__(self, capacity: int = 1024):
        self.capacity = capacity
        self._entries: "collections.OrderedDict[Hashable, Any]" = (
            collections.OrderedDict()
        )
        self._lock = threading.Lock()
        # In-flight builds, keyed like entries: concurrent misses on the
        # same key must not each pay a full XLA compile (seconds) nor
        # each count a miss — the first caller builds, the rest wait on
        # its event and read the landed entry (single-flight).
        self._building: dict[Hashable, threading.Event] = {}
        # Serialized executable cost per entry (noted best-effort by the
        # dispatch path on each miss); evicted/cleared entries drop
        # their ledger rows with them.
        self._bytes: dict[Hashable, int] = {}
        self.hits = 0
        self.misses = 0

    def get_or_build(self, key: Hashable, build: Callable[[], Any]) -> Any:
        while True:
            with self._lock:
                if key in self._entries:
                    self._entries.move_to_end(key)
                    self.hits += 1
                    return self._entries[key]
                pending = self._building.get(key)
                if pending is None:
                    done = self._building[key] = threading.Event()
                    break
            # Another thread is compiling this key: wait it out, then
            # re-check — its entry lands as our hit. If the builder
            # FAILED (event set, no entry), the loop elects us builder.
            pending.wait()
        # Build outside the lock: XLA compiles can take seconds and must not
        # serialize unrelated lookups.
        try:
            value = build()
        except BaseException:
            with self._lock:
                self._building.pop(key, None)
            done.set()  # wake waiters; one of them retries the build
            raise
        with self._lock:
            self.misses += 1
            self._entries[key] = value
            self._entries.move_to_end(key)
            while len(self._entries) > self.capacity:
                evicted, _ = self._entries.popitem(last=False)
                self._bytes.pop(evicted, None)
            self._building.pop(key, None)
        done.set()
        return value

    def note_bytes(self, key: Hashable, nbytes: int) -> None:
        """Record one entry's serialized executable cost (dispatch notes
        it on the miss). Unknown keys (already evicted) are ignored."""
        try:
            nbytes = int(nbytes)
        except (TypeError, ValueError):
            return
        if nbytes < 0:
            return
        with self._lock:
            if key in self._entries:
                self._bytes[key] = nbytes

    def nbytes(self) -> int:
        """Total noted serialized bytes of the resident entries — a
        lower bound on the cache's memory cost (entries whose dispatch
        could not serialize a cost report 0)."""
        with self._lock:
            return sum(self._bytes.values())

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._bytes.clear()
            self.hits = 0
            self.misses = 0

    def reset_stats(self) -> None:
        """Zero the hit/miss counters WITHOUT dropping entries — the
        ``cache_stats(reset=True)`` contract (a caller zeroes the
        counters while keeping its warm executables)."""
        with self._lock:
            self.hits = 0
            self.misses = 0

    def __len__(self) -> int:
        return len(self._entries)


_global_cache: ExecutableCache | None = None


def global_cache() -> ExecutableCache:
    global _global_cache
    if _global_cache is None:
        from ..basics import _state
        from ..utils.env import get_int

        if _state.initialized and _state.config is not None:
            capacity = _state.config.cache_capacity
        else:
            capacity = get_int("HOROVOD_CACHE_CAPACITY", 1024)
        _global_cache = ExecutableCache(capacity)
    try:
        # The memory observatory polls the cache's serialized cost
        # live (hvd_hbm_bytes{kind="executables"}) — entries land
        # from any dispatch path, outside local noting call sites.
        # Registered on every lookup (an idempotent dict write) so a
        # fresh observatory — reset_for_testing — re-acquires it.
        from .. import memory

        cache = _global_cache
        memory.get_observatory().register_supplier(
            "executables", cache.nbytes)
    except Exception:  # noqa: BLE001 — observability is best-effort
        pass
    return _global_cache
