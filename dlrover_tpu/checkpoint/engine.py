"""Trainer-side flash-checkpoint engine.

Reference analog: dlrover/trainer/torch/flash_checkpoint/engine.py (:134
CheckpointEngine, :287 save_state_dict_to_memory) + full_ckpt_engine.py.

Save path: snapshot the pytree into this node's shm arena (sub-second), then
— for DISK saves — enqueue an event so the *agent's* AsyncCheckpointSaver
persists shm -> storage off the training path. Load path: shm fast-path if a
snapshot exists (restart-in-place), else read the committed step from
storage.

Runs in two modes:
- agent mode: the agent owns the shm primitives; this engine connects as a
  client (detected by the agent's IPC sockets existing).
- solo mode (no agent — notebooks, bench scripts): the engine owns the
  primitives and runs an in-process AsyncCheckpointSaver thread, keeping the
  same async behavior.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from typing import Any, Callable

import numpy as np

from dlrover_tpu.common.constants import CheckpointStorageType, EnvKey
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.common.multi_process import SharedQueue, client_socket_ready
from dlrover_tpu.common.storage import CheckpointStorage, PosixDiskStorage
from dlrover_tpu.telemetry.journal import (
    adopt_remote_ctx,
    current_trace_id,
    format_ctx,
    get_journal,
    hot_span,
    spawn_ctx,
)
from dlrover_tpu.telemetry.metrics import registry
from dlrover_tpu.checkpoint.shm_handler import (
    SharedMemoryHandler,
    restore_pytree,
)

logger = get_logger(__name__)

# shared by CheckpointEngine.load and ShardedCheckpointEngine.load_sharded
_restore_seconds = registry().histogram(
    "dlrover_tpu_ckpt_restore_seconds",
    "checkpoint restore duration by engine",
    label_names=("engine",),
)
_snapshot_seconds = registry().histogram(
    "dlrover_tpu_ckpt_snapshot_seconds",
    "in-memory (shm) snapshot duration on the training path — the C "
    "the Young-Daly interval tuner prices",
)


def _record_restore(engine: str, start_monotonic: float, step: int) -> None:
    dur = time.monotonic() - start_monotonic
    _restore_seconds.labels(engine).observe(dur)
    # spawn_ctx (§27): a restore in a child respawned during a recovery
    # incident journals under that incident's node_restart root
    get_journal().emit("ckpt_restore", dur=dur, step=step, engine=engine,
                       remote_parent=spawn_ctx())


@dataclasses.dataclass
class PersistWait:
    """Typed outcome of a durable-persist wait.

    Truthiness preserves the old bool contract, but ``kind`` makes a
    timeout distinguishable from "no checkpoint was ever requested" at
    every call site — the silent-False bug class where a caller shut
    down believing the step was durable. Every timeout is journaled
    (``ckpt_persist_timeout``), so the trail shows exactly which steps
    the job gave up waiting for.
    """

    ok: bool
    kind: str            # "ok" | "timeout"
    step: int
    waited_s: float
    persisted_step: int  # newest step durably committed when we stopped

    def __bool__(self) -> bool:
        return self.ok


def _journal_persist_timeout(what: str, step: int, waited_s: float,
                             **fields) -> None:
    get_journal().emit("ckpt_persist_timeout", what=what, step=step,
                       waited_s=waited_s, **fields)


def _read_storage_arrays(storage: CheckpointStorage, ckpt_dir: str,
                         node_id: int, step: int | None = None
                         ) -> tuple[int, dict[str, np.ndarray]] | None:
    """CRC-verified storage read: resolve the newest VERIFIED step (or a
    pinned one) and materialize its arrays. Pure function of storage
    state so it can run on the restore-prefetch thread concurrently
    with rendezvous/compile as well as inline."""
    from dlrover_tpu.agent.ckpt_saver import step_dir
    from dlrover_tpu.checkpoint.integrity import resolve_restore_step

    if step is None:
        # newest VERIFIED step: crc-checked against the COMMIT
        # manifest, rolling back past corrupt/incomplete steps —
        # a flipped bit must cost a checkpoint interval, never a
        # silent restore of bad bytes. An explicitly pinned `step`
        # (best-model reload) bypasses this by caller contract.
        committed = resolve_restore_step(storage, ckpt_dir)
        if committed is None:
            return None
        step, _ = committed
    sdir = step_dir(ckpt_dir, step)
    # replicated ckpt: one node file holds everything; prefer our own,
    # else the smallest node id present.
    metas = [
        f for f in storage.listdir(sdir) if f.endswith(".meta.json")
    ]
    if not metas:
        return None
    own = f"node_{node_id}.meta.json"
    meta_file = own if own in metas else sorted(metas)[0]
    header = json.loads(
        storage.read_text(os.path.join(sdir, meta_file))
    )
    if meta_file != own and not header.get("replicated", True):
        # Sharded checkpoint: another node's file holds a different
        # shard — loading it would silently install wrong weights.
        raise FileNotFoundError(
            f"sharded checkpoint at {sdir} is missing this node's "
            f"shard {own}; refusing to load another node's shard"
        )
    bin_file = meta_file.replace(".meta.json", ".bin")
    blob = storage.read(os.path.join(sdir, bin_file))
    arrays: dict[str, np.ndarray] = {}
    for name, info in header["metas"].items():
        arr = np.frombuffer(
            blob, dtype=np.dtype(info["dtype"]),
            count=max(1, int(np.prod(info["shape"] or [1]))),
            offset=info["offset"],
        ).reshape(info["shape"])
        arrays[name] = arr
    logger.info("restored step %d from storage %s", step, sdir)
    return step, arrays


def _storage_fallback_leaf(storage: CheckpointStorage, ckpt_dir: str,
                           name: str, leaf, registry_box: list
                           ) -> np.ndarray | None:
    """Assemble a full leaf from the newest VERIFIED storage step's
    piece registry — the path a MULTI-host reshard takes for shards
    whose only live copy died with a host. ``registry_box`` caches the
    resolved plan across leaves of one reshard (lazy: resolved on the
    first miss)."""
    from dlrover_tpu.checkpoint import sharded as sharded_mod
    from dlrover_tpu.checkpoint.integrity import resolve_restore_plan

    if not registry_box:
        plan = resolve_restore_plan(storage, ckpt_dir)
        registry_box.append(
            None if plan is None else
            sharded_mod.storage_piece_registry(
                storage, ckpt_dir, plan.step, plan.num_shards,
                bad_pieces=plan.bad_pieces,
            )
        )
    registry = registry_box[0]
    pieces = (registry or {}).get(name)
    if not pieces:
        return None
    shape = tuple(pieces[0].global_shape)
    if shape != tuple(getattr(leaf, "shape", shape)):
        return None
    return sharded_mod.assemble(
        [[0, s] for s in shape], pieces[0].dtype, pieces
    )


class RestorePrefetch:
    """Background storage restore: the read + integrity verification run
    on a daemon thread while the process is busy with rendezvous,
    ``jax.distributed.initialize`` or the first compile; ``join`` hands
    the verified arrays over before the first step needs them.

    Failure ordering is safe by construction: the thread runs the same
    ``resolve_restore_step`` rollback logic as the inline path, a
    raised error or timeout makes ``join`` return None (callers fall
    back to the synchronous read), and a consumer that pins a different
    step than the prefetch resolved discards the prefetched result.
    """

    def __init__(self, ckpt_dir: str, node_id: int,
                 storage: CheckpointStorage | None = None):
        self.ckpt_dir = ckpt_dir
        self.node_id = node_id
        self.storage = storage or PosixDiskStorage()
        self._result: tuple[int, dict[str, np.ndarray]] | None = None
        self._error: BaseException | None = None
        self.outcome = "pending"  # "ok"|"empty"|"error"|"timeout"
        self._done = threading.Event()
        self._started = time.monotonic()
        threading.Thread(
            target=self._run, name="restore-prefetch", daemon=True
        ).start()

    def _run(self) -> None:
        try:
            self._result = _read_storage_arrays(
                self.storage, self.ckpt_dir, self.node_id
            )
        except BaseException as e:  # noqa: BLE001 - reported via join()
            logger.warning("restore prefetch failed: %s", e)
            self._error = e
        finally:
            dur = time.monotonic() - self._started
            self._done.set()
            get_journal().emit(
                "restore_prefetch", dur=dur,
                step=self._result[0] if self._result else -1,
                ok=self._error is None, remote_parent=spawn_ctx(),
            )

    def join(self, timeout: float = 120.0
             ) -> tuple[int, dict[str, np.ndarray]] | None:
        """The verified (step, arrays), or None on no-checkpoint /
        error / timeout — None always means 'do the synchronous read'.
        ``outcome`` ("ok" | "empty" | "error" | "timeout") types WHY,
        and a timeout is journaled (``ckpt_persist_timeout``) — a
        prefetch thread wedged on sick storage must be visible, not a
        silently slower restore."""
        if not self._done.wait(timeout):
            self.outcome = "timeout"
            _journal_persist_timeout("restore_prefetch", -1, timeout,
                                     ckpt_dir=self.ckpt_dir)
            logger.warning("restore prefetch still running after %.0fs; "
                           "falling back to the synchronous read", timeout)
            return None
        if self._error is not None:
            self.outcome = "error"
            return None
        self.outcome = "ok" if self._result is not None else "empty"
        return self._result


_prefetch_lock = threading.Lock()
_prefetches: dict[tuple[str, int], RestorePrefetch] = {}


def start_restore_prefetch(ckpt_dir: str, node_id: int | None = None,
                           storage: CheckpointStorage | None = None
                           ) -> RestorePrefetch:
    """Begin the storage restore read + verification NOW (idempotent per
    (ckpt_dir, node)); the next ``CheckpointEngine`` load for the same
    checkpoint consumes it. Called by a parked standby trainer when the
    agent signals an imminent promotion (overlap with the rendezvous
    round) and by trainer mains before distributed init / compile."""
    nid = (node_id if node_id is not None
           else int(os.environ.get(EnvKey.NODE_ID, "0")))
    key = (os.path.abspath(ckpt_dir), nid)
    with _prefetch_lock:
        pf = _prefetches.get(key)
        if pf is None:
            pf = _prefetches[key] = RestorePrefetch(ckpt_dir, nid, storage)
        return pf


def take_restore_prefetch(ckpt_dir: str, node_id: int
                          ) -> RestorePrefetch | None:
    with _prefetch_lock:
        return _prefetches.pop((os.path.abspath(ckpt_dir), node_id), None)


class CheckpointEngine:
    # async snapshots skip steps while the writer is busy, which is safe
    # only when one node's snapshot is the whole checkpoint; sharded
    # engines need cross-node step agreement and keep the sync path
    supports_async_snapshot = True

    def __init__(
        self,
        ckpt_dir: str,
        storage: CheckpointStorage | None = None,
        node_id: int | None = None,
        node_rank: int | None = None,
        world_size: int | None = None,
        replicated: bool = True,
        snapshot_mode: str = "direct",
    ):
        self.ckpt_dir = ckpt_dir
        self.storage = storage or PosixDiskStorage()
        self.node_id = (
            node_id if node_id is not None
            else int(os.environ.get(EnvKey.NODE_ID, "0"))
        )
        self.node_rank = (
            node_rank if node_rank is not None
            else int(os.environ.get(EnvKey.NODE_RANK, "0"))
        )
        self.world_size = (
            world_size if world_size is not None
            else int(os.environ.get(EnvKey.NODE_NUM, "1"))
        )
        # replicated: every node holds the full state (DP); only rank 0
        # persists to storage. Sharded engines set replicated=False and every
        # node persists its own shard.
        self.replicated = replicated
        # async-snapshot pipeline state (save_to_memory_async)
        self._pending_lock = threading.Lock()
        # (seq, step, device copy, the request's journal span id)
        self._pending: tuple[int, int, Any, str] | None = None
        self._async_seq = 0
        # sequence floor: a sync save lifts it so an older async snapshot
        # popped-but-unwritten can never overwrite the newer sync write
        self._async_floor = 0
        self._async_writing = False
        self._snap_wake = threading.Event()
        self._snap_stop = threading.Event()
        self._snap_thread: threading.Thread | None = None
        self._device_copy = None
        self._async_ok: bool | None = None
        # COW (fork) snapshot mode: save_to_memory returns after the fork
        # and a child process does the arena memcpy (shm_handler.
        # save_state_dict_fork). "direct" keeps the in-process copy.
        if snapshot_mode not in ("direct", "cow"):
            raise ValueError(f"snapshot_mode {snapshot_mode!r}")
        self.snapshot_mode = (
            snapshot_mode if hasattr(os, "fork") else "direct"
        )
        self._cow_done = threading.Event()
        self._cow_done.set()
        self._cow_info: dict = {}
        self._cow_ok: bool | None = None  # None = no COW save yet
        self._solo_saver = None
        agent_present = client_socket_ready(f"dict_ckpt_node{self.node_id}")
        if not agent_present:
            from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver

            self._solo_saver = AsyncCheckpointSaver.start(self.node_id)
            self.shm_handler = self._solo_saver.shm_handler
            self.event_queue = self._solo_saver.event_queue
        else:
            self.shm_handler = SharedMemoryHandler(self.node_id, owner=False)
            self.event_queue = SharedQueue(
                f"ckpt_event_{self.node_id}", create=False
            )

    # ------------------------------------------------------------------ save

    def _extra_meta(self) -> dict:
        return {
            "ckpt_dir": self.ckpt_dir,
            "storage": self.storage.class_meta().to_dict(),
            "node_rank": self.node_rank,
            "node_id": self.node_id,
            "world_size": self.world_size,
            "num_shards": 1 if self.replicated else self.world_size,
            "replicated": self.replicated,
        }

    def _prepare_state(self, state: Any) -> tuple[Any, dict]:
        """Hook: transform the pytree before snapshotting (sharded engines
        split leaves into addressable pieces here). Returns (tree, extra
        header metadata)."""
        return state, {}

    def save_to_memory(self, step: int, state: Any,
                       _async_seq: int | None = None) -> bool:
        """Sub-second snapshot into shm. Returns False if the saver is mid-
        persist (skip rather than block the training step).

        ``_async_seq`` is the snapshot-worker's ordering token: under the
        shm lock, an async write whose sequence a sync save has already
        superseded is dropped — otherwise a worker that popped step N and
        then got descheduled could overwrite a NEWER sync snapshot the
        persister is about to read.
        """
        # at most one COW child in flight: its arena write is guarded by
        # the shm lock the watcher releases, so a second save must wait
        # for that release rather than silently skip
        if self.snapshot_mode == "cow":
            self.wait_snapshot(timeout=300.0)
        if not self.shm_handler.lock.acquire(blocking=False):
            logger.warning(
                "skipping in-memory save at step %d: persister busy", step
            )
            return False
        release_lock = True
        try:
            with self._pending_lock:
                if _async_seq is not None:
                    if _async_seq <= self._async_floor:
                        return False  # superseded by a sync save
                else:
                    # sync write wins over anything async still in flight
                    self._async_floor = self._async_seq
                    self._pending = None
            start = time.monotonic()
            tree, extra = self._prepare_state(state)
            extra_meta = {**self._extra_meta(), **extra}
            if self.snapshot_mode == "cow":
                self._cow_done.clear()
                self._cow_ok = None

                def _on_done(ok: bool, info: dict) -> None:
                    # _cow_done MUST be set even if the lock release
                    # throws (dead lock-server socket): a missed set()
                    # wedges every later save/load behind 300s waits
                    try:
                        self._cow_info = info
                        self._cow_ok = ok
                        self.shm_handler.lock.release()
                    except Exception:  # noqa: BLE001 - see above
                        logger.exception(
                            "COW watcher completion cleanup failed")
                        self._cow_ok = False
                    finally:
                        self._cow_done.set()

                try:
                    info = self.shm_handler.save_state_dict_fork(
                        step, tree, extra_meta=extra_meta,
                        on_done=_on_done,
                    )
                except BaseException:
                    self._cow_done.set()
                    raise
                release_lock = False  # the watcher owns the release now
                logger.info(
                    "step %d COW-snapshot forked in %.3fs (child %d "
                    "copying %.2f GB)", step, info["fork_s"],
                    info["pid"], info["total_bytes"] / (1 << 30),
                )
                return True
            self.shm_handler.save_state_dict(
                step, tree, extra_meta=extra_meta
            )
            # a direct save supersedes any earlier failed COW verdict
            self._cow_ok = None
            snap_s = time.monotonic() - start
            # the training-path cost the Young-Daly tuner prices (C)
            _snapshot_seconds.observe(snap_s)
            logger.info(
                "step %d snapshotted to shm in %.3fs%s", step, snap_s,
                " (async writer)" if _async_seq is not None else "",
            )
            return True
        finally:
            if release_lock:
                self.shm_handler.lock.release()

    def wait_snapshot(self, timeout: float = 60.0) -> bool:
        """Block until any in-flight COW snapshot child has finished.
        Returns False if it timed out OR the child FAILED (its header
        was never published — the previous snapshot still stands).
        True immediately in direct mode."""
        if not self._cow_done.wait(timeout=timeout):
            return False
        return self._cow_ok is not False

    @property
    def last_snapshot_info(self) -> dict:
        """Timing of the last completed COW snapshot ({fork_s, copy_s,
        total_bytes}); empty in direct mode."""
        return dict(self._cow_info)

    def _async_eligible(self) -> bool:
        """The gate lives HERE, not at call sites: sharded engines need
        cross-node step agreement (supersede would break it), and on the
        CPU backend a second host thread touching arrays mid-collective
        wedges XLA:CPU's in-process rendezvous."""
        if not self.supports_async_snapshot:
            return False
        if self._async_ok is None:
            import jax

            self._async_ok = jax.devices()[0].platform != "cpu"
        return self._async_ok

    def save_to_memory_async(self, step: int, state: Any) -> None:
        """Zero-stall snapshot: returns before any device sync.
        Falls back to the synchronous path where async is unsafe
        (sharded engine, CPU backend) — callers never need their own
        gate. The synchronous path's cost is NOT the arena write — it is the
        host blocking on ``device_get`` until every queued step finishes,
        charged to the training loop (measured 0.15-0.35s per snapshot in
        the goodput bench, 5-8% of steady step time at tuned cadences).
        Here the state is first duplicated ON DEVICE (a jitted identity —
        async dispatch, fresh buffers immune to the train step's buffer
        donation; a post-donation host read of the original would raise
        "Array has been deleted"), then a worker thread blocks and writes
        the arena while the main thread keeps dispatching steps.

        Costs ONE transient state copy in HBM, never two: a request
        that arrives while the writer still holds the previous copy is
        skipped (the next cadence tick takes a fresh one), so a cadence
        faster than the device-to-host copy cannot stack copies — a
        gpt2-medium AdamW state is 4.5 GB, and two copies beside the
        state and the step's temporaries exceed a 16 GB chip. Callers
        with states near the HBM limit (the 1B ckpt bench) use the sync
        path.
        """
        with hot_span("snapshot_request", step=step) as request:
            if not self._async_eligible():
                self.save_to_memory(step, state)
                return
            import jax

            with self._pending_lock:
                if self._pending is not None or self._async_writing:
                    request.set(skipped=True)
                    return

            if self._device_copy is None:
                import jax.numpy as jnp

                self._device_copy = jax.jit(
                    lambda t: jax.tree.map(jnp.copy, t)
                )
            snap = self._device_copy(state)
            with self._pending_lock:
                self._async_seq += 1
                self._pending = (self._async_seq, step, snap, request.id)
            request.set(seq=self._async_seq, skipped=False)
            if self._snap_thread is None:
                self._snap_thread = threading.Thread(
                    target=self._snapshot_worker, name="snapshot-writer",
                    daemon=True,
                )
                self._snap_thread.start()
            self._snap_wake.set()

    def _snapshot_worker(self) -> None:
        while not self._snap_stop.is_set():
            self._snap_wake.wait()
            if self._snap_stop.is_set():
                return
            self._snap_wake.clear()
            with self._pending_lock:
                pending, self._pending = self._pending, None
                if pending is not None:
                    self._async_writing = True
            if pending is None:
                continue
            seq, step, snap, request_id = pending
            try:
                # the writer's spans are children of the request that
                # handed the copy over, across the thread boundary
                with adopt_remote_ctx(
                        format_ctx(current_trace_id(), request_id)):
                    self.save_to_memory(step, snap, _async_seq=seq)
            except Exception:  # noqa: BLE001 - snapshots are best-effort
                logger.exception("async snapshot at step %d failed", step)
            finally:
                # let the device copy go BEFORE reporting idle: this
                # thread's locals would otherwise hold it through the
                # wait above, beside the next request's copy
                pending = snap = None
                with self._pending_lock:
                    self._async_writing = False

    def flush_async(self, timeout: float = 60.0) -> bool:
        """Wait until no snapshot is pending or mid-write."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._pending_lock:
                # _async_writing covers the pop-to-write gap: pending is
                # None the moment the worker claims it, before the shm
                # lock is even requested
                idle = self._pending is None and not self._async_writing
            if idle and not self._snap_wake.is_set():
                return True
            time.sleep(0.02)
        return False

    def save_to_storage(self, step: int, state: Any) -> bool:
        # a pending/mid-write async snapshot holds the shm lock across
        # its device fetch; without this flush the non-blocking acquire
        # below loses the race and the DURABLE save silently degrades
        if self._snap_thread is not None:
            self.flush_async()
        if not self.save_to_memory(step, state):
            return False
        # a COW child may still be copying; the persist event must not
        # race it or the saver would read the previous header. A FAILED
        # child (OOM-killed mid-memcpy) must not enqueue either — the
        # header still describes the previous step and the persister
        # would durably commit the wrong one. Fall back to the direct
        # in-process copy: slower, but the durable save semantics hold.
        if not self.wait_snapshot(timeout=300.0):
            logger.warning(
                "COW snapshot for step %d failed; falling back to the "
                "direct copy for the durable save", step,
            )
            mode, self.snapshot_mode = self.snapshot_mode, "direct"
            try:
                if not self.save_to_memory(step, state):
                    return False
            finally:
                self.snapshot_mode = mode
        if self._should_write_storage():
            self.event_queue.put({"kind": "save", "step": step})
        return True

    def _should_write_storage(self) -> bool:
        return (not self.replicated) or self.node_rank == 0

    def save(self, step: int, state: Any,
             storage_type: CheckpointStorageType =
             CheckpointStorageType.MEMORY) -> bool:
        if storage_type == CheckpointStorageType.MEMORY:
            return self.save_to_memory(step, state)
        return self.save_to_storage(step, state)

    # ------------------------------------------------------------------ load

    def load(self, template: Any,
             put: Callable[[str, np.ndarray], Any] | None = None,
             zero_copy: bool = False,
             step: int | None = None,
             ) -> tuple[int, Any] | None:
        """Restore the newest checkpoint: shm first, then storage.

        ``zero_copy=True`` hands shm arena views straight to ``put``, which
        must consume them immediately (device transfer, file write) and
        return something that does NOT alias the input — retained views are
        overwritten by the next snapshot and block arena growth. Requires
        ``put``; explicit opt-in because safety depends on the callback.

        ``step`` pins the restore to a specific persisted step (best-model
        reload) instead of the newest; the shm fast path only applies when
        its snapshot is exactly that step.
        """
        if zero_copy and put is None:
            raise ValueError("zero_copy=True requires a consuming `put`")
        start = time.monotonic()
        # a COW child mid-copy is overwriting the arena under the OLD
        # header: reading now would return a torn mix of two steps. A
        # FAILED child is fine (header untouched, previous snapshot
        # stands), but an in-flight one must finish first.
        if not self._cow_done.wait(timeout=300.0):
            raise RuntimeError(
                "COW snapshot child still copying after 300s; refusing "
                "a torn arena read"
            )
        loaded = self._load_from_memory(copy=not zero_copy)
        if loaded is not None and step is not None and loaded[0] != step:
            loaded = None
        if loaded is None:
            loaded = self._load_from_storage(step=step)
        else:
            # shm fast path won: release any overlapped storage prefetch
            # so its arrays don't linger for the process lifetime
            take_restore_prefetch(self.ckpt_dir, self.node_id)
        if loaded is None:
            return None
        step, arrays = loaded
        state = restore_pytree(template, arrays, put=put)
        if put is not None:
            # a device `put` returns before its transfer has finished:
            # the restore is over when the state is on the chip
            import jax

            jax.block_until_ready(state)
        _record_restore("engine", start, step)
        return step, state

    def load_raw(self) -> tuple[int, dict] | None:
        """(step, {leaf_path: array}) without a shape template — for
        states with data-dependent shapes (embedding tables, whose row
        count is only known from the checkpoint itself)."""
        if not self._cow_done.wait(timeout=300.0):
            raise RuntimeError(
                "COW snapshot child still copying after 300s; refusing "
                "a torn arena read"
            )
        loaded = self._load_from_memory()
        if loaded is None:
            loaded = self._load_from_storage()
        return loaded

    def _load_from_memory(self, copy: bool = True
                          ) -> tuple[int, dict[str, np.ndarray]] | None:
        try:
            header = self.shm_handler.header()
            if header and header.get("ckpt_dir") not in (
                None, self.ckpt_dir
            ):
                # the shm segment is keyed by node id only: a snapshot
                # left by ANOTHER job on this host must not shadow the
                # requested checkpoint directory
                logger.info(
                    "shm snapshot belongs to %s, not %s; reading storage",
                    header.get("ckpt_dir"), self.ckpt_dir,
                )
                return None
            snap = self.shm_handler.load_arrays(copy=copy)
        except Exception:  # noqa: BLE001 - fall back to storage on any damage
            logger.exception("shm restore failed; falling back to storage")
            return None
        if snap is not None:
            logger.info("restoring step %d from shared memory", snap[0])
        return snap

    def _load_from_storage(self, step: int | None = None
                           ) -> tuple[int, dict[str, np.ndarray]] | None:
        prefetch = take_restore_prefetch(self.ckpt_dir, self.node_id)
        if prefetch is not None:
            got = prefetch.join()
            if got is not None and (step is None or got[0] == step):
                logger.info(
                    "restored step %d from the overlapped prefetch", got[0]
                )
                return got
            # the prefetch lost its race (errored, resolved a different
            # step than the pinned one, or a later failure changed the
            # storage state it read): fall through to a fresh
            # synchronous read, which re-runs the rollback logic
            logger.info("restore prefetch discarded; reading storage")
        return _read_storage_arrays(
            self.storage, self.ckpt_dir, self.node_id, step=step
        )

    # ------------------------------------------------------------- reshard

    def reshard_state(self, old_mesh, new_mesh, state,
                      step: int | None = None):
        """Membership change as a resharding event, not a restart
        (ElasWave; DESIGN.md §17): remap the live state's DP/TP/PP
        shards onto a reshaped mesh through this node's shm snapshot.

        The state is snapshotted into the shm arena first (sub-second;
        the training cadence usually already did it), then every leaf
        is scattered host-side onto ``new_mesh`` under its remapped
        PartitionSpec — the surviving incarnation resumes on the
        pre-compiled fallback program without a cold ``pjit`` compile,
        and the snapshot doubles as the rollback point if the reshape
        itself dies. Falls back to a direct device gather for leaves
        the snapshot cannot serve.
        """
        import jax

        from dlrover_tpu.checkpoint.shm_handler import _leaf_paths
        from dlrover_tpu.parallel import mesh as mesh_mod

        if step is None:
            step_leaf = getattr(state, "step", None)
            step = int(jax.device_get(step_leaf)) \
                if step_leaf is not None else 0
        arrays: dict[str, np.ndarray] | None = None
        if self.save_to_memory(step, state) and self.wait_snapshot():
            snap = self._load_from_memory(copy=False)
            if snap is not None and snap[0] == step:
                arrays = snap[1]
        names = iter(n for n, _ in _leaf_paths(state))
        registry_box: list = []  # lazy plan cache for _storage_fallback_leaf

        def _put(leaf, new_sharding):
            name = next(names)
            host = arrays.get(name) if arrays is not None else None
            if host is None:
                try:
                    host = np.asarray(jax.device_get(leaf))
                except (RuntimeError, ValueError) as e:
                    # a live shard is gone (its host died): fall back
                    # to the committed storage step instead of aborting
                    # the reshard (DESIGN.md §20)
                    host = _storage_fallback_leaf(
                        self.storage, self.ckpt_dir, name, leaf,
                        registry_box,
                    )
                    if host is None:
                        raise RuntimeError(
                            f"reshard cannot source leaf {name!r}: no "
                            "shm snapshot, no live device copy, and no "
                            "verified storage piece covers it"
                        ) from e
                    get_journal().emit("ckpt_restore_shard", step=step,
                                       writer="storage", leaf=name)
            return jax.device_put(host, new_sharding)

        out = mesh_mod.reshard_state(old_mesh, new_mesh, state, put=_put)
        # the resharded state's whole purpose is to feed the
        # pre-compiled (donating) fallback executable: re-stage the
        # device_put-built leaves into proper per-device buffers
        # (compile_cache.launder) or the donation corrupts them in
        # place on the CPU backend
        from dlrover_tpu.parallel.compile_cache import launder

        return launder(out)

    def latest_persisted_step(self) -> int:
        from dlrover_tpu.agent.ckpt_saver import read_tracker

        committed = read_tracker(self.storage, self.ckpt_dir)
        return -1 if committed is None else committed[0]

    def wait_for_persist(self, step: int, timeout: float = 120.0
                         ) -> PersistWait:
        """Block until ``step`` is durably committed (tracker moved past
        it). Returns a truthy ``PersistWait``; on timeout the result is
        falsy with ``kind="timeout"`` and the journal carries a
        ``ckpt_persist_timeout`` record — callers must not treat the
        step as durable (shutdown paths, checkpoint rotation)."""
        start = time.monotonic()
        deadline = time.time() + timeout
        newest = -1
        while time.time() < deadline:
            newest = self.latest_persisted_step()
            if newest >= step:
                return PersistWait(
                    ok=True, kind="ok", step=step,
                    waited_s=time.monotonic() - start,
                    persisted_step=newest,
                )
            time.sleep(0.1)
        waited = time.monotonic() - start
        _journal_persist_timeout("persist", step, waited,
                                 persisted_step=newest)
        logger.warning(
            "persist of step %d not durable after %.0fs (newest "
            "committed: %d)", step, waited, newest,
        )
        return PersistWait(ok=False, kind="timeout", step=step,
                           waited_s=waited, persisted_step=newest)

    def close(self) -> None:
        self.wait_snapshot(timeout=30.0)
        if self._snap_thread is not None:
            self.flush_async(timeout=10.0)
            self._snap_stop.set()
            self._snap_wake.set()
            self._snap_thread.join(timeout=5.0)
        if self._solo_saver is not None:
            from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver

            AsyncCheckpointSaver.reset(self.node_id)
        else:
            self.shm_handler.close()
            self.event_queue.close()
