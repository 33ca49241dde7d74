"""Multi-host sharded embedding service: the elastic-PS analog.

Reference analog: DLRover's elastic parameter servers for sparse models —
tables sharded across PS processes with runtime scaling
(dlrover/python/master/elastic_training/elastic_ps.py:82 version-bumped
PS cluster, master/node/job_auto_scaler.py:98 PSTrainingAutoScaler) over
tfplus's hybrid embedding storage
(tfplus/kv_variable/kernels/hybrid_embedding/table_manager.h:1). That is
the one reference capability a single-process KvEmbeddingTable cannot
represent: a table bigger than one host's RAM, or a scale event that
re-partitions rows.

TPU-native shape: the dense tower trains under jit on the chips; the
unbounded sparse rows live in N *embedding shard servers* (each wrapping
the native C++ table, embedding/kv_table.py). The trainer's
``ShardedKvClient`` routes each batch's ids by a stable key hash,
gathers/updates over the repo's no-pickle length-prefixed TCP framing
(common/rpc.py), and presents the same lookup/apply surface as the local
table so the recsys training loop is unchanged.

Elasticity follows the reference's *versioned cluster* design
(elastic_ps.py: workers watch a version and rebuild): every request
carries the routing version; a scale event migrates rows server→server
(each old owner pushes the rows whose new owner differs), then bumps the
version. A client holding stale routing gets a structured version error,
refetches the route from the coordinator, and retries — training blocks
briefly instead of losing updates.

Wire protocol (hot path, so raw arrays rather than JSON floats): one
frame = JSON header (op, meta, array manifest) + concatenated raw array
bytes, inside the common/rpc length-prefixed frame.
"""

from __future__ import annotations

import argparse
import os
import socket
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Iterable

import numpy as np

from dlrover_tpu import chaos

# decode_msg is re-exported: tests and tools treat this module as the
# wire-protocol surface for the embedding tier
from dlrover_tpu.common.array_wire import decode_msg, encode_msg  # noqa: F401
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.common.msg_server import (
    ArrayMsgServer,
    MsgError,
    call_msg,
)
from dlrover_tpu.embedding.kv_table import (
    IncrementalCheckpointManager,
    KvEmbeddingTable,
)

logger = get_logger(__name__)

# rows per migration push: bounded so one frame stays well under
# rpc.MAX_FRAME even for wide tables with optimizer slots
_MIGRATE_CHUNK_BYTES = 8 << 20


def shard_owner(ids: np.ndarray, num_shards: int) -> np.ndarray:
    """Stable owner shard per id: splitmix64 finalizer then mod — raw
    ``id % n`` would put every hot contiguous id range on one server."""
    x = np.asarray(ids, dtype=np.uint64)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    x = x ^ (x >> np.uint64(31))
    return (x % np.uint64(num_shards)).astype(np.int64)


class ShardError(MsgError):
    pass


def _apply_msg_fault(fault, sock: socket.socket) -> None:
    """Injected embedding-transport faults (chaos plan ``embedding_msg``
    point). The embedding tier's raw-array TCP framing bypasses
    ``RpcClient``, so the PR-4 ``rpc_call`` rules never touch it —
    this point closes that blind spot at the one client-side choke
    point every lookup/apply/migration push goes through.

    ``delay`` sleeps before sending (a congested link), ``drop`` loses
    the request before it hits the wire (the server never sees it),
    ``reset`` kills the connection mid-exchange (server death /
    conntrack reset — the socket is poisoned and must be re-dialed),
    ``garble`` poisons the stream with a corrupt frame (the server
    closes it; protocol state is unrecoverable on this socket).
    """
    if fault.action == "delay":
        time.sleep(float(fault.args.get("s", 0.2)))
        return
    if fault.action == "drop":
        raise ConnectionError("chaos: dropped embedding message")
    if fault.action == "reset":
        try:
            sock.close()
        except OSError:
            pass
        raise ConnectionResetError("chaos: embedding connection reset")
    if fault.action == "garble":
        from dlrover_tpu.common.rpc import send_frame

        try:
            send_frame(sock, b"\x00garbled-embedding-frame")
        except OSError:
            pass
        raise ConnectionError("chaos: garbled embedding frame")


def _call(sock: socket.socket, op: str, meta: dict | None = None,
          arrays: dict | None = None) -> tuple[dict, dict]:
    if chaos.ENABLED:
        # the trainer->shard partition site (§30): like the rack tier,
        # the embedding framing bypasses RpcClient, so the link-level
        # net_partition rules need their own hook here
        from dlrover_tpu.chaos import partition as net_partition

        if net_partition.check("trainer", "shard", op=op) is not None:
            raise ConnectionError(
                "chaos: net partition open (trainer->shard)"
            )
        fault = chaos.fire("embedding_msg", op=op)
        if fault is not None:
            _apply_msg_fault(fault, sock)
    return call_msg(sock, op, meta, arrays, error_cls=ShardError)


class EmbeddingShardServer(ArrayMsgServer):
    """One embedding PS shard: a native KvEmbeddingTable behind TCP
    (accept/dispatch scaffolding in common/msg_server.py).

    Owns rows with ``shard_owner(id, num_shards) == index`` at the
    current routing version. ``migrate_to`` re-partitions under a new
    epoch, pushing rows to their new owners (the PS migration analog).
    """

    error_cls = ShardError

    def __init__(self, dim: int, num_slots: int = 2, *, seed: int = 0,
                 host: str = "0.0.0.0", port: int = 0,
                 version: int = 0, num_shards: int = 1, index: int = 0,
                 ckpt_dir: str = "", base_interval: int = 10):
        super().__init__(host=host, port=port,
                         name=f"emb-shard-{index}")
        self.table = KvEmbeddingTable(dim=dim, num_slots=num_slots,
                                      seed=seed + 7919 * index)
        self.dim = dim
        self.num_slots = num_slots
        self.version = version
        self.num_shards = num_shards
        self.index = index
        self._ckpt_dir = ckpt_dir
        self._base_interval = base_interval
        self._ckpt: IncrementalCheckpointManager | None = None
        # one lock serializes table mutations against migration: the
        # native table is internally thread-safe, but a migrate must see
        # a frozen row set while it repartitions
        self._lock = threading.Lock()
        self._migrating = False
        # liveness escape: a coordinator that dies between copy and
        # commit would otherwise leave the gate armed forever. After
        # the TTL the server self-aborts (safe: phase 1 deleted
        # nothing); a commit arriving later is rejected (gate no longer
        # armed) so the coordinator's retry re-runs the whole scale.
        self._migrating_since = 0.0
        self.migrate_ttl_s = 1800.0

    def start(self) -> "EmbeddingShardServer":
        super().start()
        logger.info(
            "embedding shard %d/%d v%d serving on port %d",
            self.index, self.num_shards, self.version, self.port,
        )
        return self

    # ------------------------------------------------------------- dispatch

    def _check_epoch(self, meta: dict) -> None:
        if self._migrating:
            if (self._migrating_since
                    and time.monotonic() - self._migrating_since
                    > self.migrate_ttl_s):
                logger.warning(
                    "migration armed > %.0fs with no commit/abort "
                    "(dead coordinator?); self-aborting to restore "
                    "service", self.migrate_ttl_s,
                )
                self.abort_migration()
            else:
                raise ShardError("migrating",
                                 "shard is re-partitioning",
                                 {"retry_ms": 100})
        v = meta.get("v")
        if v is not None and v != self.version:
            raise ShardError(
                "version",
                f"client routing v{v} != shard v{self.version}",
                {"current": self.version},
            )

    def _handle(self, op: str, meta: dict, arrays: dict) -> bytes:
        if op == "ping":
            return encode_msg("ok", {
                "version": self.version, "num_shards": self.num_shards,
                "index": self.index, "rows": len(self.table),
            })
        if op == "lookup":
            self._check_epoch(meta)
            with self._lock:
                values = self.table.lookup(
                    arrays["ids"], init_missing=meta.get("init", True)
                )
            return encode_msg("ok", arrays={"values": values})
        if op == "apply":
            self._check_epoch(meta)
            with self._lock:
                self.table.apply(
                    meta["optimizer"], arrays["ids"], arrays["grads"],
                    **meta.get("kwargs", {}),
                )
            return encode_msg("ok", {"rows": len(self.table)})
        if op == "import_rows":
            # migration push from a peer (or a bulk load): no epoch check
            # — the pusher is mid-migration ahead of the version bump
            with self._lock:
                self.table.import_(dict(arrays))
            return encode_msg("ok", {"rows": len(self.table)})
        if op == "export":
            with self._lock:
                snap = self.table.export(
                    min_freq=meta.get("min_freq", 0)
                )
            return encode_msg("ok", {"rows": int(snap["keys"].size)},
                              arrays=snap)
        if op == "rows":
            return encode_msg("ok", {"rows": len(self.table)})
        if op == "migrate":
            moved = self.migrate_to(
                meta["addrs"], meta["version"],
                self_index=meta.get("self_index", -1),
            )
            return encode_msg("ok", {
                "moved": moved, "rows": len(self.table),
            })
        if op == "commit_migration":
            pruned = self.commit_migration(
                meta["version"], meta["num_shards"],
                meta.get("index", -1),
            )
            return encode_msg("ok", {
                "pruned": pruned, "rows": len(self.table),
            })
        if op == "prune_unowned":
            # rollback path for DESTINATIONS of an aborted scale: drop
            # every row this server does not own under the GIVEN ring
            # (index < 0 = not in that ring at all -> drop everything
            # it received). No epoch or gate change.
            n_shards = int(meta["num_shards"])
            index = int(meta.get("index", -1))
            with self._lock:
                keys = self.table.export()["keys"]
                if index < 0:
                    prune = keys
                elif keys.size:
                    prune = keys[shard_owner(keys, n_shards) != index]
                else:
                    prune = keys
                if prune.size:
                    self.table.remove(prune)
            return encode_msg("ok", {"pruned": int(prune.size),
                                     "rows": len(self.table)})
        if op == "abort_migration":
            self.abort_migration()
            return encode_msg("ok", {"version": self.version})
        if op == "set_epoch":
            with self._lock:
                self.version = meta["version"]
                self.num_shards = meta["num_shards"]
                self.index = meta["index"]
            return encode_msg("ok", {"version": self.version})
        if op == "ckpt_save":
            return encode_msg("ok", {"path": self.ckpt_save()})
        if op == "ckpt_restore":
            return encode_msg("ok", {"version": self.ckpt_restore()})
        raise ShardError("bad_op", f"unknown op {op!r}")

    # ------------------------------------------------------------ migration

    def migrate_to(self, addrs: list[str], new_version: int,
                   self_index: int = -1) -> int:
        """Phase 1 of the two-phase scale: COPY every row whose new owner
        isn't this server to its destination. Nothing is removed and the
        epoch is not adopted here — this server stays the authoritative
        owner of all its rows until the coordinator's
        ``commit_migration`` lands, so a failed push leaves the ring
        fully intact and a retried scale simply re-pushes (``import_``
        is last-write-wins). That retires the r04 loss window where rows
        were deleted per-destination mid-migration and a later failure
        left them unreachable, with ``lookup(init_missing=True)``
        silently resurrecting fresh rows.

        ``self_index`` is this server's position in the NEW ring,
        computed by the coordinator from the address it knows this
        server by (a port-based self-guess would misfire when multiple
        hosts use the same port); -1 = scale-down, everything moves.
        Rows transfer WITH optimizer slots and frequency, chunked to
        bound frame sizes. The ``_migrating`` gate stays ARMED on
        success (mutations between copy and commit would be lost after
        the flip); ``commit_migration``/``abort_migration`` clears it.
        Returns rows copied."""
        self._migrating = True
        # TTL disarmed (0.0) while the copy is IN FLIGHT: the copy's
        # liveness is proven by its open RPC, and a TTL counted from
        # copy start would self-abort any legitimately long copy (and
        # the aborting request thread would block on _lock behind it).
        # The clock starts when the copy finishes — from then on only a
        # dead coordinator can leave the gate armed.
        self._migrating_since = 0.0
        try:
            with self._lock:
                new_n = len(addrs)
                my_index = self_index if 0 <= self_index < new_n else -1
                snap = self.table.export()
                keys = snap["keys"]
                owners = (shard_owner(keys, new_n) if keys.size
                          else np.zeros(0, np.int64))
                moved = 0
                for dest in range(new_n):
                    if dest == my_index:
                        continue
                    sel = owners == dest
                    if not np.any(sel):
                        continue
                    moved += int(sel.sum())
                    self._push_rows(addrs[dest], {
                        "keys": keys[sel],
                        "values": snap["values"][sel],
                        "slots": snap["slots"][sel]
                        if "slots" in snap else None,
                        "freq": snap["freq"][sel],
                    })
                self._migrating_since = time.monotonic()
                return moved
        except BaseException:
            # a failed copy aborts THIS server's phase; re-open for
            # traffic at the old epoch (the coordinator may retry)
            self._migrating = False
            self._migrating_since = 0.0
            raise

    def commit_migration(self, new_version: int, num_shards: int,
                         index: int) -> int:
        """Phase 2: adopt the new epoch and PRUNE every row this server
        does not own in the new ring. Pruning by ownership (rather than
        a remembered moved-key list) is idempotent and self-healing: it
        also clears dormant copies left by a previously aborted scale.
        ``index`` < 0 = departing server (drained; prunes everything).
        Rejected when the gate is no longer armed (the server
        self-aborted past its TTL): the copies may be stale by now, so
        the coordinator must re-run the whole scale."""
        with self._lock:
            if not self._migrating:
                raise ShardError(
                    "not_migrating",
                    "no armed migration (self-aborted past TTL?); "
                    "re-run the scale",
                )
            snap_keys = self.table.export()["keys"]
            if index < 0:
                prune = snap_keys
            elif snap_keys.size:
                prune = snap_keys[
                    shard_owner(snap_keys, num_shards) != index
                ]
            else:
                prune = snap_keys
            if prune.size:
                self.table.remove(prune)
            self.version = new_version
            self.num_shards = num_shards
            self.index = max(index, 0)
            self._migrating = False
            self._migrating_since = 0.0
            return int(prune.size)

    def abort_migration(self) -> int:
        """Roll back phase 1. Nothing was removed from the authoritative
        owners, so re-opening at the old epoch restores service — but
        rows already COPIED to surviving destinations are strays there
        (un-owned at the old epoch) and would double-count in
        export/checkpoint, where a later restore could replay the stale
        copy over the authoritative row. Prune them by ownership at the
        CURRENT epoch."""
        with self._lock:
            keys = self.table.export()["keys"]
            if keys.size and self.num_shards > 0:
                strays = keys[
                    shard_owner(keys, self.num_shards) != self.index
                ]
                if strays.size:
                    self.table.remove(strays)
            else:
                strays = keys[:0]
            self._migrating = False
            self._migrating_since = 0.0
            return int(strays.size)

    def _push_rows(self, addr: str, rows: dict) -> None:
        host, _, port = addr.rpartition(":")
        row_bytes = self.dim * 4 * (1 + self.num_slots) + 8 + 4
        chunk = max(1, _MIGRATE_CHUNK_BYTES // row_bytes)
        with socket.create_connection(
            (host or "127.0.0.1", int(port)), timeout=30.0
        ) as conn:
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            n = rows["keys"].size
            for i in range(0, n, chunk):
                sl = slice(i, i + chunk)
                payload = {
                    "keys": rows["keys"][sl],
                    "values": rows["values"][sl],
                    "freq": rows["freq"][sl],
                }
                if rows.get("slots") is not None:
                    payload["slots"] = rows["slots"][sl]
                _call(conn, "import_rows", arrays=payload)

    # ----------------------------------------------------------- checkpoint

    def ckpt_save(self) -> str:
        if not self._ckpt_dir:
            raise ShardError("no_ckpt_dir", "server started without one")
        with self._lock:
            mgr = self._ckpt_manager()
            return mgr.save()

    def ckpt_restore(self) -> int:
        if not self._ckpt_dir:
            raise ShardError("no_ckpt_dir", "server started without one")
        with self._lock:
            mgr = self._ckpt_manager()
            return mgr.restore()

    def _ckpt_manager(self) -> IncrementalCheckpointManager:
        # per-(shard-count, index) directory: after a reshard the row
        # ownership changed, so the old chain must not be appended to —
        # a fresh manager in a fresh dir starts with a base
        d = os.path.join(self._ckpt_dir,
                         f"n{self.num_shards}-s{self.index}")
        if self._ckpt is None or self._ckpt.directory != d:
            self._ckpt = IncrementalCheckpointManager(
                self.table, d, base_interval=self._base_interval
            )
        return self._ckpt


class EmbeddingCoordinator(ArrayMsgServer):
    """Routing authority: (version, shard addrs) + the scale operation.

    Reference analog: ElasticPsService's version-bumped PS cluster
    (elastic_ps.py:82) driven by the PS auto-scaler. ``scale()`` runs the
    migration: every CURRENT server re-partitions against the new address
    ring (pushing moved rows directly peer-to-peer), then every server in
    the new ring adopts the bumped epoch. Clients that raced the scale
    get a version error from a shard and re-fetch the route here."""

    error_cls = ShardError

    def __init__(self, addrs: Iterable[str], host: str = "0.0.0.0",
                 port: int = 0):
        super().__init__(host=host, port=port, name="emb-coord")
        self.version = 0
        self.addrs = list(addrs)
        # _lock guards the (version, addrs) route snapshot and is held
        # only for instants; _scale_lock serializes scale operations,
        # which legitimately run for minutes — holding _lock across a
        # scale (the r04 design) starved `route` requests past the
        # client timeout and crashed trainers mid-migration
        self._lock = threading.Lock()
        self._scale_lock = threading.Lock()

    def start(self) -> "EmbeddingCoordinator":
        self._push_epochs()
        super().start()
        logger.info("embedding coordinator on port %d (%d shards)",
                    self.port, len(self.addrs))
        return self

    def _handle(self, op: str, meta: dict, arrays: dict) -> bytes:
        if op == "route":
            with self._lock:
                return encode_msg("ok", {
                    "version": self.version, "addrs": self.addrs,
                })
        if op == "scale":
            try:
                self.scale(meta["addrs"])
            except Exception as e:  # noqa: BLE001 - report to caller
                raise ShardError(
                    "scale_failed", f"{type(e).__name__}: {e}"
                ) from e
            with self._lock:
                return encode_msg("ok", {
                    "version": self.version, "addrs": self.addrs,
                })
        raise ShardError("bad_op", f"unknown op {op!r}")

    def _shard_call(self, addr: str, op: str, meta: dict | None = None,
                    timeout: float = 60.0):
        host, _, port = addr.rpartition(":")
        with socket.create_connection(
            (host or "127.0.0.1", int(port)), timeout=timeout
        ) as conn:
            return _call(conn, op, meta)

    def _push_epochs(self) -> None:
        for i, addr in enumerate(self.addrs):
            self._shard_call(addr, "set_epoch", {
                "version": self.version, "num_shards": len(self.addrs),
                "index": i,
            })

    def scale(self, new_addrs: list[str], migrate_retries: int = 3,
              retry_backoff_s: float = 0.5) -> None:
        """Re-partition the table onto ``new_addrs`` (grow or shrink),
        failure-atomically.

        Two phases (reference analog: elastic_ps.py:82's versioned
        cluster, hardened per the r04 verdict):

        1. COPY — every old server pushes the rows whose new owner
           differs (retried per server: ``import_`` is last-write-wins,
           so a re-push after a destination hiccup is idempotent).
           Nothing is deleted; a failure here rolls back by simply
           re-opening every server at the old epoch. Zero loss.
        2. COMMIT — pure-new servers adopt the epoch, then every old
           server prunes the rows it no longer owns and adopts. A
           failure HERE is rolled *forward* (commits retried), because
           a committed server has already pruned — rolling back would
           recreate exactly the loss window phase 1 exists to close.
           If commits keep failing the scale raises and must be
           retried; rows are never lost, only unavailable until the
           retry converges (clients back off on version errors).

        The route flips only after full commit; ``route`` requests are
        served throughout from the short-hold snapshot lock."""
        with self._scale_lock:
            with self._lock:
                old_addrs = list(self.addrs)
                new_version = self.version + 1
            try:
                for addr in old_addrs:
                    # the coordinator knows each server by address, so
                    # IT computes the server's position in the new ring
                    # (a port-based self-guess would misfire when hosts
                    # share ports); no timeout cap — a migrate streams
                    # the shard's whole row set and may legitimately
                    # run for minutes on big tables
                    try:
                        self_index = new_addrs.index(addr)
                    except ValueError:
                        self_index = -1
                    meta = self._retry_shard_call(
                        addr, "migrate", {
                            "addrs": new_addrs, "version": new_version,
                            "self_index": self_index,
                        }, migrate_retries, retry_backoff_s,
                        timeout=None,
                    )
                    logger.info("shard %s copied %d rows", addr,
                                meta["moved"])
            except Exception:
                self._rollback(old_addrs, new_addrs)
                raise
            # phase 2a: epochs for pure-new members first (they only
            # gain rows). Retried, and STILL rollback-safe on failure —
            # no old server has pruned anything yet, so abort is the
            # same clean path as a phase-1 failure (review finding: an
            # unretried, unrolled-back set_epoch here left every old
            # server's migrating gate armed until the TTL).
            try:
                for i, addr in enumerate(new_addrs):
                    if addr not in old_addrs:
                        self._retry_shard_call(
                            addr, "set_epoch", {
                                "version": new_version,
                                "num_shards": len(new_addrs),
                                "index": i,
                            }, migrate_retries, retry_backoff_s,
                        )
            except Exception:
                self._rollback(old_addrs, new_addrs)
                raise
            # phase 2b: commit (prune+adopt) the old members — from
            # here failures roll FORWARD (see docstring)
            for addr in old_addrs:
                try:
                    idx = new_addrs.index(addr)
                except ValueError:
                    idx = -1
                self._retry_shard_call(
                    addr, "commit_migration", {
                        "version": new_version,
                        "num_shards": len(new_addrs), "index": idx,
                    }, migrate_retries, retry_backoff_s,
                )
            with self._lock:
                self.version = new_version
                self.addrs = list(new_addrs)

    def _rollback(self, old_addrs: list[str],
                  new_addrs: list[str]) -> None:
        """Undo an uncommitted scale: nothing was deleted from the
        authoritative owners, so re-opening them at the old epoch is
        the core rollback (abort prunes their own strays). PURE-NEW
        destinations additionally drop every row they received — they
        sit outside the old ring, so a stray copy there would otherwise
        survive until a later scale and could resurrect a row the
        trainer deleted in between (review finding r05)."""
        for addr in old_addrs:
            try:
                self._shard_call(addr, "abort_migration")
            except Exception:  # noqa: BLE001 - best effort
                logger.warning("abort_migration to %s failed", addr)
        for addr in new_addrs:
            if addr in old_addrs:
                continue
            try:
                self._shard_call(addr, "prune_unowned",
                                 {"num_shards": len(old_addrs),
                                  "index": -1})
            except Exception:  # noqa: BLE001 - best effort
                logger.warning("prune_unowned to %s failed", addr)

    def _retry_shard_call(self, addr: str, op: str, meta: dict,
                          retries: int, backoff_s: float,
                          timeout: float | None = 60.0) -> dict:
        last: Exception | None = None
        for attempt in range(max(1, retries)):
            try:
                rmeta, _ = self._shard_call(addr, op, meta,
                                            timeout=timeout)
                return rmeta
            except (ShardError, ConnectionError, OSError) as e:
                last = e
                logger.warning("%s to %s failed (attempt %d/%d): %s",
                               op, addr, attempt + 1, retries, e)
                time.sleep(backoff_s * (attempt + 1))
        raise RuntimeError(f"{op} to {addr} failed after "
                           f"{retries} attempts: {last}")

    def total_rows(self) -> int:
        with self._lock:
            addrs = list(self.addrs)
        return sum(
            self._shard_call(a, "rows")[0]["rows"] for a in addrs
        )


class ShardedKvClient:
    """Trainer-side sharded table: the KvEmbeddingTable surface over N
    shard servers. ``lookup``/``apply`` split each batch by owner shard,
    fan out in parallel, and reassemble — so the recsys training loop is
    identical whether the table is local or sharded."""

    def __init__(self, coordinator_addr: str | None = None,
                 addrs: list[str] | None = None, dim: int = 0,
                 timeout: float = 30.0, retry_window_s: float = 600.0):
        if not coordinator_addr and not addrs:
            raise ValueError("need coordinator_addr or addrs")
        self.dim = dim
        self._timeout = timeout
        self.retry_window_s = retry_window_s
        self._coord_addr = coordinator_addr
        self.version = 0
        self._addrs: list[str] = list(addrs or [])
        self._socks: dict[str, socket.socket] = {}
        self._lock = threading.Lock()
        self._pool = ThreadPoolExecutor(
            max_workers=8, thread_name_prefix="emb-client"
        )
        if coordinator_addr:
            self.refresh_route()
        self._step = 0

    # ------------------------------------------------------------- plumbing

    def refresh_route(self) -> None:
        host, _, port = self._coord_addr.rpartition(":")
        with socket.create_connection(
            (host or "127.0.0.1", int(port)), timeout=self._timeout
        ) as conn:
            meta, _ = _call(conn, "route")
        with self._lock:
            self.version = meta["version"]
            self._addrs = list(meta["addrs"])
            # stale sockets may point at drained servers
            for s in self._socks.values():
                try:
                    s.close()
                except OSError:
                    pass
            self._socks.clear()

    def _sock_for(self, addr: str) -> socket.socket:
        s = self._socks.get(addr)
        if s is None:
            host, _, port = addr.rpartition(":")
            s = socket.create_connection(
                (host or "127.0.0.1", int(port)), timeout=self._timeout
            )
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self._socks[addr] = s
        return s

    def _evict_sock(self, addr: str) -> None:
        """Close-and-forget a socket that failed: popping without
        closing (the r05 behavior) leaked one fd per dead server, and
        leaving it cached re-sent the NEXT call into the same dead
        connection — recovery then had to come from the slower
        version-error/route-refresh path instead of a fresh dial."""
        s = self._socks.pop(addr, None)
        if s is not None:
            try:
                s.close()
            except OSError:
                pass

    def _shard_call(self, idx: int, op: str, meta: dict,
                    arrays: dict) -> tuple[dict, dict]:
        addr = self._addrs[idx]
        try:
            return _call(self._sock_for(addr), op, meta, arrays)
        except (ConnectionError, OSError):
            # evict + one immediate re-dial: the server may have
            # restarted between ops (same addr, new process)
            self._evict_sock(addr)
            try:
                return _call(self._sock_for(addr), op, meta, arrays)
            except (ConnectionError, OSError):
                # still down: evict again so the retry loop's NEXT
                # attempt (after a route refresh) dials fresh instead
                # of reusing a half-dead connection
                self._evict_sock(addr)
                raise

    def _fanout(self, op: str, ids: np.ndarray,
                per_shard_arrays, meta_extra: dict | None = None,
                retry_window_s: float | None = None):
        """Split by owner, call each touched shard, return per-shard
        (selector, response-arrays) pairs.

        Retry semantics: completion is tracked PER ID — a retry after a
        route-level failure (version bump, migration in progress, or a
        dead/drained server) re-sends only the ids whose shard call
        failed, re-routed under the refreshed route. Shards that already
        answered are never re-sent, so a scale event racing an ``apply``
        cannot double-apply gradients to the shards that succeeded.
        (The residual at-least-once window — a shard that applied but
        whose *response* was lost — is inherent to retrying writes and
        matches the sharding-client's at-least-once contract.)

        The retry budget is TIME-based (default ``self.retry_window_s``,
        600 s): a big-table scale legitimately blocks shards behind
        their migrating gate for minutes, and the r04 count-based
        budget (60 x 0.25 s ~ 15 s) crashed training during exactly the
        event the retries exist to ride out. ``refresh_route`` failures
        are themselves retriable — the coordinator answers from a
        short-hold snapshot lock now, but a momentarily unreachable
        coordinator must not kill the trainer either."""
        flat = np.ascontiguousarray(ids, np.int64).reshape(-1)
        pending = np.ones(flat.size, dtype=bool)
        results: list[tuple[np.ndarray, dict]] = []
        last: Exception | None = None
        deadline = time.monotonic() + (
            retry_window_s if retry_window_s is not None
            else self.retry_window_s
        )
        backoff = 0.25
        while True:
            n = max(1, len(self._addrs))
            idxs = np.nonzero(pending)[0]
            owners = shard_owner(flat[idxs], n)
            futures = []
            for s in range(n):
                sel = idxs[owners == s]
                if sel.size == 0:
                    continue
                meta = {"v": self.version, **(meta_extra or {})}
                arrays = per_shard_arrays(flat[sel], sel)
                futures.append((sel, self._pool.submit(
                    self._shard_call, s, op, meta, arrays
                )))
            for sel, fut in futures:
                try:
                    _, rarrays = fut.result()
                    results.append((sel, rarrays))
                    pending[sel] = False
                except ShardError as e:
                    last = e
                    if e.code not in ("version", "migrating"):
                        raise
                except (ConnectionError, OSError) as e:
                    # a drained server may already be gone after a
                    # scale-down: re-route instead of crashing training
                    last = e
            # success is checked AFTER collecting: an iteration that
            # completes past the deadline keeps its own result instead
            # of discarding applied gradients as a spurious failure
            if not pending.any():
                return results, flat
            if time.monotonic() >= deadline:
                break
            time.sleep(backoff)
            backoff = min(backoff * 1.5, 2.0)
            if self._coord_addr:
                try:
                    self.refresh_route()
                except (ShardError, ConnectionError, OSError) as e:
                    last = e  # coordinator busy/unreachable: retry
        raise RuntimeError(
            f"embedding fanout kept failing after "
            f"{retry_window_s or self.retry_window_s:.0f}s: {last}"
        )

    # ------------------------------------------------------------- user ops

    def lookup(self, ids: np.ndarray, init_missing: bool = True
               ) -> np.ndarray:
        flat_shape = np.shape(ids)
        parts, flat = self._fanout(
            "lookup", ids,
            lambda shard_ids, sel: {"ids": shard_ids},
            meta_extra={"init": init_missing},
        )
        out = np.empty((flat.size, self.dim), np.float32)
        for sel, rarrays in parts:
            out[sel] = rarrays["values"]
        return out.reshape(*flat_shape, self.dim)

    def apply(self, optimizer: str, ids: np.ndarray, grads: np.ndarray,
              **kwargs) -> None:
        g = np.ascontiguousarray(grads, np.float32).reshape(-1, self.dim)
        self._step += 1
        if optimizer in ("adam", "group_adam", "radam"):
            kwargs.setdefault("step", self._step)
        self._fanout(
            "apply", ids,
            lambda shard_ids, sel: {"ids": shard_ids, "grads": g[sel]},
            meta_extra={"optimizer": optimizer, "kwargs": kwargs},
        )

    def apply_adam(self, ids: np.ndarray, grads: np.ndarray,
                   **kwargs) -> None:
        self.apply("adam", ids, grads, **kwargs)

    def row_count(self) -> int:
        total = 0
        for i in range(len(self._addrs)):
            meta, _ = self._shard_call(i, "rows", {}, {})
            total += meta["rows"]
        return total

    def __len__(self) -> int:
        return self.row_count()

    def export(self, min_freq: int = 0, with_slots: bool = True
               ) -> dict[str, np.ndarray]:
        """KvEmbeddingTable-compatible snapshot alias (full table)."""
        snap = self.export_all()
        if not with_slots:
            snap.pop("slots", None)
        return snap

    def export_all(self) -> dict[str, np.ndarray]:
        """Full-table snapshot across shards (tests/verification)."""
        snaps = []
        for i in range(len(self._addrs)):
            _, arrays = self._shard_call(i, "export", {}, {})
            snaps.append(arrays)
        out: dict[str, np.ndarray] = {}
        for k in ("keys", "values", "slots", "freq"):
            if all(k in s for s in snaps):
                out[k] = np.concatenate([s[k] for s in snaps])
        return out

    def ckpt_save(self) -> list[str]:
        return [self._shard_call(i, "ckpt_save", {}, {})[0]["path"]
                for i in range(len(self._addrs))]

    def ckpt_restore(self) -> list[int]:
        return [self._shard_call(i, "ckpt_restore", {}, {})[0]["version"]
                for i in range(len(self._addrs))]

    def close(self) -> None:
        self._pool.shutdown(wait=False)
        for s in self._socks.values():
            try:
                s.close()
            except OSError:
                pass
        self._socks.clear()


class EmbeddingServerScaler:
    """Scaler-contract adapter for the table tier: the
    PSTrainingAutoScaler analog (reference
    dlrover/python/master/node/job_auto_scaler.py:98 resizes parameter
    servers through the pod scaler + elastic-PS version bump).

    A ScalePlan whose ``replica_resources`` carries the
    ``"table_server"`` group is executed as: spawn/stop local shard
    server processes toward the target count, then
    ``EmbeddingCoordinator.scale`` migrates rows onto the new ring and
    bumps the routing version. Plugs directly into
    ``master.auto_scaler.JobAutoScaler`` as its scaler (or alongside a
    worker scaler via a dispatching wrapper). Pod-based deployments do
    the same with the operator spawning server pods and an addr-watch
    feeding ``coordinator.scale``.
    """

    GROUP = "table_server"

    def __init__(self, dim: int, *, coordinator: EmbeddingCoordinator,
                 spawn=None, num_slots: int = 2, seed: int = 0,
                 ckpt_dir: str = "", host: str = "127.0.0.1",
                 spawn_timeout_s: float = 60.0):
        self.dim = dim
        self.num_slots = num_slots
        self.seed = seed
        self.ckpt_dir = ckpt_dir
        self.host = host
        self.spawn_timeout_s = spawn_timeout_s
        self._coord = coordinator
        self._procs: dict[str, object] = {}  # addr -> Popen/server
        # _lock guards _procs ONLY (short holds, so stop_all can always
        # proceed); _scale_lock serializes scale operations, whose
        # migrate leg is legitimately unbounded on big tables
        self._lock = threading.Lock()
        self._scale_lock = threading.Lock()
        self._stopped = False
        self._spawn = spawn or self._default_spawn

    def _default_spawn(self, index: int) -> tuple[str, object]:
        """Spawn a shard-server subprocess carrying the TIER'S table
        configuration — a new server with a different num_slots/seed
        would reject migrated rows (import_ shape check) or break the
        deterministic-init contract mid-ring."""
        import subprocess
        import sys

        cmd = [sys.executable, "-m", "dlrover_tpu.embedding.service",
               "--dim", str(self.dim),
               "--num-slots", str(self.num_slots),
               "--seed", str(self.seed),
               "--host", self.host, "--index", str(index)]
        if self.ckpt_dir:
            cmd += ["--ckpt-dir", self.ckpt_dir]
        proc = subprocess.Popen(
            cmd, stdout=subprocess.PIPE, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        # bounded readiness wait: a wedged child must not park scale()
        # (and with it the auto-scaler tick + stop_all) on readline
        # forever
        line_box: list[str] = []

        def read():
            line_box.append(proc.stdout.readline().strip())

        t = threading.Thread(target=read, daemon=True)
        t.start()
        t.join(self.spawn_timeout_s)
        line = line_box[0] if line_box else ""
        if not line.startswith("PORT "):
            self._terminate(proc)
            raise RuntimeError(
                f"table server not ready within {self.spawn_timeout_s}s"
                f" (got {line!r})"
            )
        # the pipe has served its one purpose; keeping it open leaks an
        # fd per spawn and would wedge a child that ever filled it
        try:
            proc.stdout.close()
        except OSError:
            pass
        return f"{self.host}:{line.split()[1]}", proc

    def scale(self, plan) -> None:
        target = plan.replica_resources.get(self.GROUP)
        if target is None:
            return
        if target < 1:
            # an empty ring has nowhere to migrate rows TO — executing
            # it would strand every row and then kill their holders
            raise ValueError(
                f"table_server target {target}: the tier cannot scale "
                "below 1 (rows need an owner)"
            )
        with self._scale_lock:
            if self._stopped:
                raise RuntimeError("table tier is shut down")
            addrs = list(self._coord.addrs)
            spawned: list[str] = []
            try:
                while len(addrs) + len(spawned) < target:
                    # re-check per spawn: a stop_all() racing this scale
                    # must not have servers registered AFTER its clear
                    if self._stopped:
                        raise RuntimeError("table tier is shut down")
                    addr, proc = self._spawn(len(addrs) + len(spawned))
                    with self._lock:
                        self._procs[addr] = proc
                    spawned.append(addr)
                new_addrs = (addrs + spawned)[:target]
                retired = [a for a in addrs if a not in new_addrs]
                if spawned or retired:
                    logger.info(
                        "table tier %d -> %d servers (%s)", len(addrs),
                        target, plan.reason or "scale plan",
                    )
                    self._coord.scale(new_addrs)  # migrates, bumps ver
            except BaseException:
                # a failed spawn OR migration must not leak the servers
                # just spawned for this plan: they are not in the route,
                # and a retried plan would spawn a fresh set on top
                for addr in spawned:
                    with self._lock:
                        proc = self._procs.pop(addr, None)
                    self._terminate(proc)
                raise
            for addr in retired:  # drained by the migrate; now stop
                with self._lock:
                    proc = self._procs.pop(addr, None)
                self._terminate(proc)

    @staticmethod
    def _terminate(proc) -> None:
        """terminate -> wait -> kill for subprocesses (no zombies, no
        SIGTERM-ignoring stragglers); in-process servers (tests,
        co-located tiers) expose stop()."""
        import subprocess

        if proc is None:
            return
        if hasattr(proc, "terminate") and hasattr(proc, "wait"):
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=10)
        elif hasattr(proc, "stop"):
            proc.stop()

    def stop_all(self) -> None:
        # flag first so an in-flight/next scale() refuses to spawn more;
        # terminate OUTSIDE the lock (a straggler's wait must not block
        # the registrations scale() does under short lock holds)
        self._stopped = True
        with self._lock:
            procs = list(self._procs.values())
            self._procs.clear()
        for proc in procs:
            self._terminate(proc)


def main(argv=None) -> int:
    """CLI shard-server entry: prints ``PORT <n>`` once listening (the
    spawner's readiness/port-discovery contract, like data_worker.py)."""
    p = argparse.ArgumentParser("embedding shard server")
    p.add_argument("--dim", type=int, required=True)
    p.add_argument("--num-slots", type=int, default=2)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--index", type=int, default=0)
    p.add_argument("--num-shards", type=int, default=1)
    p.add_argument("--ckpt-dir", default="")
    p.add_argument("--spill-dir", default="",
                   help="hybrid tier: spill file for cold rows")
    args = p.parse_args(argv)
    server = EmbeddingShardServer(
        dim=args.dim, num_slots=args.num_slots, seed=args.seed,
        host=args.host, port=args.port, index=args.index,
        num_shards=args.num_shards, ckpt_dir=args.ckpt_dir,
    )
    if args.spill_dir:
        os.makedirs(args.spill_dir, exist_ok=True)
        server.table.enable_spill(os.path.join(
            args.spill_dir, f"shard-{args.index}.spill"
        ))
    server.start()
    print(f"PORT {server.port}", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        server.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
