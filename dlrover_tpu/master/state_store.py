"""Master HA: control-plane state snapshot + restore.

Reference analog: dlrover/python/util/state/store_mananger.py +
memory_store.py (pluggable state backends for master recovery).

Snapshot v1 covered only the DATA-PLANE bookkeeping (dataset shard
progress). Since PRs 9-14 the master became the hub of the persist-ack
ledger, the compile-cache store, the autopilot controller and the
rendezvous epoch — a crash silently lost warm compiles, in-flight
checkpoint commits and retune budgets. Snapshot **v2** (DESIGN.md §26)
is the full recoverable control-plane state:

- ``master_epoch``: the monotonic incarnation counter the epoch fence
  is built on (bumped by the restarting master, stamped on every RPC
  response);
- ``persist_acks``: the §20 ack ledger, BOTH groups (``""`` dense and
  ``"embedding"``), plus the rid-dedup set that keeps redelivered
  reports idempotent;
- ``rendezvous``: per-manager round counter, previous world, departed
  and waiting sets — a restarted master continues the round sequence
  instead of reissuing round numbers;
- ``nodes``: the node census with incarnation/failure counters;
- ``autopilot``: armed plan, ranked alternatives and the retune budget
  already charged (a restart must not re-grant spent retunes);
- ``interval_tuner``: the Young-Daly MTBF window (failure ages) and
  blended costs;
- ``compile_cache``: entry metadata in the snapshot, blobs spilled to
  ``<state_dir>/compile_cache`` with the same ``<key>.aot`` naming as
  the node-local compile-cache layer — a restarted
  master answers ``CompileCacheGet`` warm.

Components that were in the snapshot are restored; everything else
rebuilds organically (heartbeats re-register nodes within one
interval). ``request_snapshot()`` lets the servicer mark the state
dirty after ledger/failure/retune mutations so durability is bounded
by milliseconds, not the periodic interval.
"""

from __future__ import annotations

import json
import os
import threading
import time
import zlib
from typing import Any

from dlrover_tpu.common.log import get_logger
from dlrover_tpu.telemetry.journal import get_journal
from dlrover_tpu.telemetry.metrics import registry

logger = get_logger(__name__)

_state_rollback_total = registry().counter(
    "dlrover_tpu_master_state_rollback_total",
    "master restarts recovered from the previous state snapshot",
)

SNAPSHOT_VERSION = 2


class StateBackend:
    def save(self, state: dict) -> None:
        raise NotImplementedError

    def load(self) -> dict | None:
        raise NotImplementedError


class MemoryStateBackend(StateBackend):
    def __init__(self):
        self._state: dict | None = None

    def save(self, state: dict) -> None:
        self._state = json.loads(json.dumps(state))

    def load(self) -> dict | None:
        return self._state


class FileStateBackend(StateBackend):
    """Atomic checksummed JSON file (k8s analog: a ConfigMap or PVC file).

    Snapshots are wrapped as ``{"crc32", "body"}`` so a restarted
    master can tell torn/corrupt bytes from valid state, and every save
    rotates the previous snapshot to ``<path>.prev`` — a corrupt (or
    mid-write-crashed) current snapshot recovers from the previous one
    instead of crashing the master or silently starting fresh.
    """

    def __init__(self, path: str):
        self._path = path

    @property
    def path(self) -> str:
        return self._path

    def save(self, state: dict) -> None:
        from dlrover_tpu.common.storage import atomic_write_file

        body = json.dumps(state)
        wrapped = json.dumps({
            "crc32": zlib.crc32(body.encode("utf-8")) & 0xFFFFFFFF,
            "body": body,
        })
        if os.path.exists(self._path):
            try:
                os.replace(self._path, self._path + ".prev")
            except OSError:
                pass
        atomic_write_file(wrapped, self._path)

    def load(self) -> dict | None:
        state = self._load_one(self._path)
        if state is not None:
            return state
        state = self._load_one(self._path + ".prev")
        if state is not None:
            _state_rollback_total.inc()
            get_journal().emit("state_rollback", path=self._path)
            logger.warning(
                "current state snapshot unusable; recovered from the "
                "previous snapshot %s.prev", self._path,
            )
            return state
        return None

    def _load_one(self, path: str) -> dict | None:
        if not os.path.exists(path):
            return None
        try:
            with open(path) as f:
                data = json.load(f)
        except (json.JSONDecodeError, OSError):
            logger.exception("state snapshot %s unreadable", path)
            return None
        if isinstance(data, dict) and "body" in data and "crc32" in data:
            body = data["body"]
            if zlib.crc32(body.encode("utf-8")) & 0xFFFFFFFF \
                    != int(data["crc32"]):
                logger.error("state snapshot %s failed its checksum", path)
                return None
            try:
                return json.loads(body)
            except json.JSONDecodeError:
                return None
        # pre-checksum snapshot: accepted, but the CRC guard was
        # bypassed — operators must know the bytes were taken on faith
        get_journal().emit("state_legacy_snapshot", path=path)
        logger.warning(
            "state snapshot %s predates the checksum wrapper; loaded "
            "without CRC verification", path,
        )
        return data


class MasterStateManager:
    """Periodic + on-demand snapshots of a JobMaster's recoverable state.

    ``spill_dir`` is where compile-cache blobs land (``None`` keeps the
    snapshot metadata-only — the fleet simulator's in-memory backend
    path). ``request_snapshot()`` wakes the loop early after a
    state-changing RPC (persist ack, failure report, autopilot arm or
    retune) so those survive a crash within milliseconds.
    """

    def __init__(self, master: Any, backend: StateBackend,
                 interval_s: float = 5.0, spill_dir: str | None = None,
                 min_gap_s: float = 0.2):
        self._master = master
        self._backend = backend
        self._interval_s = interval_s
        self._min_gap_s = min_gap_s
        self._spill_dir = spill_dir
        self._stopped = threading.Event()
        self._dirty = threading.Event()
        # capture+save must be one atomic unit: an explicit snapshot()
        # (shutdown, tests) racing the loop thread's periodic one could
        # otherwise persist OLDER state last — the loop captures before
        # a dispatch mutates, then its save lands after the newer write
        self._snap_lock = threading.Lock()
        self._thread: threading.Thread | None = None
        # what the last restore() recovered: the restarting master bumps
        # its epoch past this before serving
        self.restored_epoch = 0

    def request_snapshot(self) -> None:
        self._dirty.set()

    def snapshot(self) -> None:
        with self._snap_lock:
            self._snapshot_locked()

    def _snapshot_locked(self) -> None:
        master = self._master
        servicer = getattr(master, "servicer", None)
        state = {
            "version": SNAPSHOT_VERSION,
            "timestamp": time.time(),
            "job_name": master.job_name,
            "master_epoch": int(getattr(master, "master_epoch", 0)),
            "datasets": master.task_manager.export_state(),
        }
        if servicer is not None:
            state["persist_acks"] = servicer.export_persist_state()
            state["autopilot"] = servicer.export_autopilot_state()
            state["interval_tuner"] = servicer.export_tuner_state()
            state["compile_cache"] = \
                servicer.compile_cache.export_state(self._spill_dir)
            state["racks"] = servicer.export_rack_state()
        rdzv = getattr(master, "rdzv_managers", None)
        if rdzv:
            state["rendezvous"] = {
                name: mgr.export_state() for name, mgr in rdzv.items()
            }
        node_manager = getattr(master, "node_manager", None)
        if node_manager is not None:
            state["nodes"] = node_manager.export_state()
        self._backend.save(state)

    def restore(self) -> bool:
        state = self._backend.load()
        if not state:
            return False
        version = int(state.get("version", 1))
        master = self._master
        master.task_manager.restore_state(state.get("datasets", {}))
        self.restored_epoch = int(state.get("master_epoch", 0))
        restored = ["datasets"]
        servicer = getattr(master, "servicer", None)
        if version >= 2 and servicer is not None:
            if state.get("persist_acks") is not None:
                servicer.restore_persist_state(state["persist_acks"])
                restored.append("persist_acks")
            if state.get("autopilot"):
                servicer.restore_autopilot_state(state["autopilot"])
                restored.append("autopilot")
            if state.get("interval_tuner"):
                servicer.restore_tuner_state(state["interval_tuner"])
                restored.append("interval_tuner")
            if state.get("compile_cache"):
                n = servicer.compile_cache.restore_state(
                    state["compile_cache"], self._spill_dir
                )
                restored.append(f"compile_cache:{n}")
            if state.get("racks"):
                # per-rack sub-master epochs: the fence guarantee (§28)
                # is that a restarted root never re-mints an epoch a
                # rack's agents already observed
                servicer.restore_rack_state(state["racks"])
                restored.append("racks")
        if version >= 2 and state.get("rendezvous"):
            for name, mgr in getattr(master, "rdzv_managers",
                                     {}).items():
                exported = state["rendezvous"].get(name)
                if exported:
                    mgr.restore_state(exported)
            restored.append("rendezvous")
        if version >= 2 and state.get("nodes") is not None:
            master.node_manager.restore_state(state["nodes"])
            restored.append("nodes")
        age = time.time() - state.get("timestamp", time.time())
        get_journal().emit(
            "master_restore", epoch=self.restored_epoch,
            version=version, age=round(age, 3),
            components=",".join(restored),
        )
        logger.info(
            "restored master state v%d from %s (age %.1fs, epoch %d, "
            "components: %s)", version, type(self._backend).__name__,
            age, self.restored_epoch, ", ".join(restored),
        )
        return True

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._loop, name="master-state", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stopped.set()
        self._dirty.set()  # wake the loop so the join below is prompt
        if self._thread is not None:
            # join BEFORE the final snapshot: without it, a periodic
            # snapshot mid-write could interleave with (and clobber)
            # the final one during shutdown
            self._thread.join(timeout=10.0)
        try:
            self.snapshot()
        except Exception:  # noqa: BLE001 - shutdown must proceed
            logger.exception("final state snapshot failed")

    def _loop(self) -> None:
        while not self._stopped.is_set():
            # on-demand wake (request_snapshot) or the periodic tick —
            # either way at most one snapshot per loop turn
            self._dirty.wait(self._interval_s)
            self._dirty.clear()
            if self._stopped.is_set():
                return
            try:
                self.snapshot()
            except Exception:  # noqa: BLE001
                logger.exception("state snapshot failed")
            # throttle: a storm of request_snapshot nudges (fleet-scale
            # joins/acks) coalesces to <= 1/min_gap snapshots per
            # second, bounding the durability window without letting
            # the dirty loop spin back-to-back
            self._stopped.wait(self._min_gap_s)
