"""Master-hosted KV store for inter-node barrier/address exchange, plus
the persistent compile-cache artifact store.

Reference analog: dlrover/python/master/elastic_training/kv_store_service.py
and the agent-side MasterKVStore (elastic_agent/torch/master_kv_store.py:1),
which replace torch's TCPStore. On TPU the heavy lifting is done by the JAX
coordination service; this store covers pre-init exchange (coordinator
address publication, barriers, checkpoint sync counts).

``CompileCacheService`` is the master half of the elastic compile cache
(DESIGN.md §17): trainers publish serialized AOT train-step executables
keyed on topology × model-shape × strategy fingerprint, and any later
incarnation — promoted standby, re-joined node after a membership
change, fresh gateway replica — fetches the executable instead of
re-paying the XLA compile. The master is the natural home because it is
the only process that survives every trainer incarnation and already
speaks to every node.
"""

from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict

from dlrover_tpu.common.log import get_logger
from dlrover_tpu.telemetry.metrics import registry

logger = get_logger(__name__)

# Shared by the master-side service (layer="master") and the trainer's
# node-local directory layer (layer="local", parallel/compile_cache.py):
# a single registration site keeps the exposition contract collision-free.
cache_hits_total = registry().counter(
    "dlrover_tpu_compile_cache_hits_total",
    "compile-cache lookups served from the cache, by layer",
    label_names=("layer",),
)
cache_misses_total = registry().counter(
    "dlrover_tpu_compile_cache_misses_total",
    "compile-cache lookups that found nothing, by layer",
    label_names=("layer",),
)
cache_puts_total = registry().counter(
    "dlrover_tpu_compile_cache_puts_total",
    "compile-cache artifacts published, by layer",
    label_names=("layer",),
)
_cache_bytes = registry().gauge(
    "dlrover_tpu_compile_cache_bytes",
    "bytes currently held by the master compile-cache store",
)


def topology_tag(total_devices: int, num_nodes: int) -> str:
    """The topology component of a compile-cache key. Keys are
    ``<tag>/<digest>`` so coverage queries ("is ANY executable
    pre-compiled for the N-1 world?") are a prefix scan — the agent can
    choose reshard-with-fallback before the trainer even starts. Node
    count leads so the agent can scan by world size alone
    (``node_topology_prefix``): the agent's chip count and the
    trainer's jax device count legitimately differ on virtual-device
    test meshes."""
    return f"n{int(num_nodes)}t{int(total_devices)}"


def node_topology_prefix(num_nodes: int) -> str:
    """Coverage-scan prefix for an N-node world of any device count."""
    return f"n{int(num_nodes)}t"


class KVStoreService:
    def __init__(self):
        self._store: dict[str, bytes] = {}
        self._counters: dict[str, int] = {}
        self._lock = threading.Lock()

    def set(self, key: str, value: bytes) -> None:
        with self._lock:
            self._store[key] = value

    def get(self, key: str) -> bytes | None:
        with self._lock:
            return self._store.get(key)

    def add(self, key: str, amount: int) -> int:
        """Atomic counter add; used for barrier arrivals."""
        with self._lock:
            self._counters[key] = self._counters.get(key, 0) + amount
            return self._counters[key]

    def wait(self, key: str, timeout: float = 30.0) -> bytes | None:
        deadline = time.time() + timeout
        while time.time() < deadline:
            v = self.get(key)
            if v is not None:
                return v
            time.sleep(0.05)
        return None

    def clear(self) -> None:
        with self._lock:
            self._store.clear()
            self._counters.clear()


class CompileCacheService:
    """Byte-bounded LRU store of serialized AOT executables.

    Keys are ``<topology_tag>/<fingerprint_digest>`` (see
    ``parallel/compile_cache.py::compile_fingerprint``); values are
    opaque artifact blobs plus a small meta dict the client uses to
    verify the fingerprint inputs actually match (a digest hit with
    mismatched inputs is served but rejected client-side as a miss).

    Eviction is LRU on get/put recency. One artifact larger than
    ``max_bytes`` is refused outright — a 7B-model executable must not
    flush every other topology out of the cache.
    """

    def __init__(self, max_bytes: int = 512 << 20,
                 max_entry_bytes: int = 128 << 20):
        from dlrover_tpu.master.saturation import TimedLock

        self.max_bytes = max_bytes
        self.max_entry_bytes = min(max_entry_bytes, max_bytes)
        # instrumented: the LRU is one of the named hot master
        # structures the saturation layer attributes wait time to
        self._lock = TimedLock("compile_cache_lru")
        # key -> (payload, meta); OrderedDict end = most recently used
        self._entries: OrderedDict[str, tuple[bytes, dict]] = OrderedDict()
        self._bytes = 0

    def put(self, key: str, payload: bytes, meta: dict | None = None
            ) -> bool:
        if not key or not payload or len(payload) > self.max_entry_bytes:
            return False
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= len(old[0])
            self._entries[key] = (payload, dict(meta or {}))
            self._bytes += len(payload)
            while self._bytes > self.max_bytes and len(self._entries) > 1:
                _, (evicted, _meta) = self._entries.popitem(last=False)
                self._bytes -= len(evicted)
            cache_puts_total.labels("master").inc()
            _cache_bytes.set(self._bytes)
            return True

    def get(self, key: str) -> tuple[bytes, dict] | None:
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                cache_misses_total.labels("master").inc()
                return None
            self._entries.move_to_end(key)
            cache_hits_total.labels("master").inc()
            return entry

    def evict(self, key: str) -> bool:
        with self._lock:
            entry = self._entries.pop(key, None)
            if entry is None:
                return False
            self._bytes -= len(entry[0])
            _cache_bytes.set(self._bytes)
            return True

    # -------------------------------------------- crash-failover state (§26)

    def export_state(self, spill_dir: str | None) -> list[dict]:
        """Entry metadata for the master snapshot, blobs spilled to
        ``spill_dir`` (same ``<key with / -> _>.aot`` naming as the
        node-local compile-cache layer (``compile_cache.cache_root``), so the dir
        is inspectable with the same tooling). ``spill_dir=None``
        exports metadata only — a restarted master then serves misses
        for the blobs, which is a degradation, not corruption.
        Already-spilled blobs are skipped by size (content is
        CRC-guarded at restore)."""
        import zlib

        with self._lock:
            entries = list(self._entries.items())
        exported: list[dict] = []
        for key, (payload, meta) in entries:
            record = {
                "key": key, "meta": dict(meta),
                "bytes": len(payload),
                "crc32": zlib.crc32(payload) & 0xFFFFFFFF,
            }
            if spill_dir:
                path = os.path.join(spill_dir,
                                    key.replace("/", "_") + ".aot")
                try:
                    if not os.path.exists(path) \
                            or os.path.getsize(path) != len(payload):
                        from dlrover_tpu.common.storage import (
                            atomic_write_file,
                        )

                        atomic_write_file(payload, path)
                    record["spilled"] = True
                except OSError:
                    logger.warning("compile-cache spill of %s failed",
                                   key, exc_info=True)
            exported.append(record)
        return exported

    def restore_state(self, exported: list[dict],
                      spill_dir: str | None) -> int:
        """Re-hydrate spilled entries in their original LRU order;
        returns how many blobs came back. A missing/corrupt spill file
        drops that entry (the client treats the miss as a cold
        compile — never a wrong program)."""
        import zlib

        restored = 0
        for record in exported:
            key = record.get("key", "")
            if not key or not spill_dir or not record.get("spilled"):
                continue
            path = os.path.join(spill_dir,
                                key.replace("/", "_") + ".aot")
            try:
                with open(path, "rb") as f:
                    payload = f.read()
            except OSError:
                continue
            if zlib.crc32(payload) & 0xFFFFFFFF \
                    != int(record.get("crc32", -1)):
                logger.warning(
                    "spilled compile-cache blob %s failed its CRC; "
                    "dropped (will recompile)", key,
                )
                continue
            if self.put(key, payload, record.get("meta")):
                restored += 1
        return restored

    def covers(self, topology: str) -> int:
        """Number of cached executables under a topology prefix (a full
        ``topology_tag`` or a ``node_topology_prefix``) — the agent's
        reshard-vs-restart decision input. Does not count as a
        hit/miss: coverage is a planning query, not an artifact fetch."""
        with self._lock:
            return sum(1 for k in self._entries if k.startswith(topology))

    def stats(self) -> dict:
        with self._lock:
            return {"entries": len(self._entries), "bytes": self._bytes}
