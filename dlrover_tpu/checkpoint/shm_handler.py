"""Shared-memory snapshot arena for JAX pytrees.

Reference analog: SharedMemoryHandler in
dlrover/python/elastic_agent/torch/ckpt_saver.py (:209): tensor metas in a
SharedDict, tensor bytes packed into one named shm block at precomputed
offsets. The arena outlives the training process, so the agent can persist
the last snapshot even after a crash, and a restarted process restores from
memory without touching storage.

JAX specifics: all D2H transfers are kicked off with
``copy_to_host_async`` before the first blocking ``device_get`` so they
overlap, then each host buffer is copied into its arena view. Restore hands
back numpy arrays; the caller ``device_put``s them with target shardings
(which may differ from the saving mesh — reshard-on-load).
"""

from __future__ import annotations

import math
import os
import struct
import threading
import time
from typing import Any, Callable

import numpy as np

from dlrover_tpu.common.log import get_logger
from dlrover_tpu.common.multi_process import (
    SharedDict,
    SharedLock,
    SharedMemoryArena,
)
from dlrover_tpu.telemetry.journal import hot_span

logger = get_logger(__name__)

_HEADER_KEY = "__snapshot__"


def _leaf_paths(tree: Any) -> list[tuple[str, Any]]:
    """Flatten a pytree into sorted (path, leaf) pairs with stable names."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        name = "/".join(_path_elem_str(p) for p in path) or "."
        out.append((name, leaf))
    return out


def _path_elem_str(p: Any) -> str:
    import jax

    if isinstance(p, jax.tree_util.DictKey):
        return str(p.key)
    if isinstance(p, jax.tree_util.SequenceKey):
        return str(p.idx)
    if isinstance(p, jax.tree_util.GetAttrKey):
        return str(p.name)
    if isinstance(p, jax.tree_util.FlattenedIndexKey):
        return str(p.key)
    return str(p)


def compute_layout(named_leaves: list[tuple[str, Any]]) -> tuple[dict, int]:
    """Per-leaf shm offsets (64-byte aligned) and the total arena size."""
    metas: dict[str, dict] = {}
    offset = 0
    for name, leaf in named_leaves:
        arr = np.asarray(leaf) if np.isscalar(leaf) else leaf
        nbytes = int(np.dtype(arr.dtype).itemsize * math.prod(arr.shape or (1,)))
        metas[name] = {
            "offset": offset,
            "shape": list(arr.shape),
            "dtype": str(np.dtype(arr.dtype)),
            "nbytes": nbytes,
        }
        offset += (nbytes + 63) & ~63
    return metas, max(offset, 64)


class SharedMemoryHandler:
    """One node's snapshot arena + meta dict + writer lock.

    ``owner=True`` in the agent process (hosts the meta dict and lock
    servers); ``owner=False`` in the training process (clients).
    """

    def __init__(self, node_id: int, owner: bool = False):
        self.node_id = node_id
        self._owner = owner
        name = f"ckpt_node{node_id}"
        self.meta_dict = SharedDict(name, create=owner)
        self.lock = SharedLock(name, create=owner)
        self._arena: SharedMemoryArena | None = None
        self._arena_name = f"ckpt_arena_{node_id}"
        self._local_lock = threading.Lock()
        self._pack_fn = None  # jitted per-dtype concat (packed fetch)

    # ---------------------------------------------------------------- write

    # total device bytes above which packed fetch falls back to per-leaf
    # (the pack's concat output transiently duplicates the state in HBM)
    PACK_LIMIT_BYTES = 4 << 30

    def save_state_dict(self, step: int, tree: Any,
                        extra_meta: dict | None = None) -> None:
        """Snapshot a pytree of device/host arrays into shared memory.

        Device leaves are fetched PACKED: a jitted per-dtype concat turns
        N arrays into one, so the host pays one fixed transfer overhead
        per dtype instead of per leaf. Measured on the 8-virtual-device
        CPU mesh: per-array fetch costs ~4-12 ms regardless of size
        (~0.4 s per snapshot for a 38-leaf state), packed ~10-30 ms
        total. Falls back to per-leaf (with overlapped async D2H) for
        host leaves or states too big to duplicate on device.
        """
        named = _leaf_paths(tree)
        metas, total = compute_layout(named)
        fetched = self._fetch(named, step, total)
        with hot_span("snapshot_arena_write", step=step, bytes=total), \
                self._local_lock:
            arena = self._ensure_arena(total)
            buf = arena.buf
            for name, _ in named:
                info = metas[name]
                host = fetched[name]
                view = np.ndarray(
                    host.shape, dtype=host.dtype,
                    buffer=buf, offset=info["offset"],
                )
                np.copyto(view, host)
        header = {
            "step": step,
            "total_size": total,
            "metas": metas,
        }
        if extra_meta:
            header.update(extra_meta)
        self.meta_dict.set(_HEADER_KEY, header)

    def save_state_dict_fork(self, step: int, tree: Any,
                             extra_meta: dict | None = None,
                             on_done: Callable[[bool, dict], None]
                             | None = None) -> dict:
        """Copy-on-write snapshot: device leaves are fetched in the caller
        (D2H must happen here — a forked child must never touch the device
        runtime), then the process forks and the CHILD copies the host
        buffers into the shared arena while the parent returns immediately.

        Blocking cost is the ``fork`` itself (page-table duplication —
        milliseconds even for multi-GB states, THP-backed heaps fork at
        ~2MB/PTE granularity), not the memcpy: on a single-core host the
        direct path is memcpy-roofline-bound (~7 GB/s measured, 1.6 s for
        12 GB) and no threadpool can beat that, but COW moves the copy off
        the training path entirely. The tax shifts to subsequent steps as
        COW faults when training rewrites the state — the goodput bench's
        snapshot-overhead accounting is where that shows up, honestly.

        The header is published ONLY after the child exits cleanly, by a
        watcher thread in the parent (the SharedDict/SharedLock clients are
        mutex-guarded, so cross-thread use is safe; the child itself never
        touches the socket clients — it inherits forked copies of their
        fds and writing would interleave frames with the parent).

        Returns ``{"pid", "fork_s", "total_bytes"}``; completion is
        signalled via ``on_done(ok, info)`` from the watcher thread.

        Fork-safety: the copy loop in the child runs over (view, host)
        ndarray pairs constructed BEFORE the fork, so the child performs
        no allocations beyond loop temporaries — minimizing the window
        for the classic fork-while-malloc-locked deadlock.
        """
        named = _leaf_paths(tree)
        metas, total = compute_layout(named)
        fetched = self._fetch(named, step, total)
        with self._local_lock:
            arena = self._ensure_arena(total)
        buf = arena.buf
        pairs = []
        for name, _ in named:
            info = metas[name]
            host = fetched[name]
            view = np.ndarray(host.shape, dtype=host.dtype,
                              buffer=buf, offset=info["offset"])
            pairs.append((view, host))
        header = {"step": step, "total_size": total, "metas": metas}
        if extra_meta:
            header.update(extra_meta)

        r_fd, w_fd = os.pipe()
        t0 = time.monotonic()
        import warnings

        with warnings.catch_warnings():
            # the multithreaded-fork warning is acknowledged: the child
            # only runs the pre-built memcpy loop and _exit (see above)
            warnings.simplefilter("ignore", DeprecationWarning)
            pid = os.fork()
        if pid == 0:  # ---- child: memcpy + signal, nothing else
            try:
                os.close(r_fd)
                t_c = time.monotonic()
                for view, host in pairs:
                    np.copyto(view, host)
                os.write(w_fd, struct.pack("d", time.monotonic() - t_c))
                os._exit(0)
            except BaseException:  # noqa: BLE001 - no cleanup in the child
                os._exit(1)
        fork_s = time.monotonic() - t0
        os.close(w_fd)
        info = {"pid": pid, "fork_s": fork_s, "total_bytes": total}

        def _watch() -> None:
            # ok means "copied AND header published": a child that
            # copied but whose header publish failed must report
            # failure, or the engine would enqueue a persist against
            # the previous header believing this step landed
            ok = False
            try:
                payload = os.read(r_fd, 8)
                _, status = os.waitpid(pid, 0)
                child_ok = (os.waitstatus_to_exitcode(status) == 0
                            and len(payload) == 8)
                if child_ok:
                    info["copy_s"] = struct.unpack("d", payload)[0]
                    self.meta_dict.set(_HEADER_KEY, header)
                    ok = True
                else:
                    logger.error(
                        "COW snapshot child (pid %d) failed; header for "
                        "step %d not published", pid, step,
                    )
            except OSError:
                logger.exception("COW snapshot watcher failed")
            finally:
                os.close(r_fd)
                if on_done is not None:
                    on_done(ok, info)

        threading.Thread(target=_watch, name="cow-snapshot-watch",
                         daemon=True).start()
        return info

    def _fetch(self, named: list[tuple[str, Any]], step: int,
               total: int) -> dict[str, np.ndarray]:
        """Every leaf on the host: packed where that is possible, else
        leaf by leaf with the device-to-host copies overlapped."""
        import jax

        with hot_span("snapshot_fetch", step=step, bytes=total):
            fetched = self._fetch_packed(named)
            if fetched is None:
                # kick off all D2H copies before the first blocking read
                for _, leaf in named:
                    if isinstance(leaf, jax.Array) and hasattr(
                        leaf, "copy_to_host_async"
                    ):
                        try:
                            leaf.copy_to_host_async()
                        except RuntimeError:
                            pass
                fetched = {
                    name: np.asarray(jax.device_get(leaf))
                    for name, leaf in named
                }
        return fetched

    def _fetch_packed(self, named: list[tuple[str, Any]]
                      ) -> dict[str, np.ndarray] | None:
        """One device fetch per dtype instead of per leaf, or None to
        fall back (host leaves present / state too large to duplicate)."""
        import jax
        import jax.numpy as jnp

        total = 0
        groups: dict[tuple, list[tuple[str, Any]]] = {}
        for name, leaf in named:
            if not isinstance(leaf, jax.Array):
                return None
            total += leaf.nbytes
            # group by (dtype, device set): an MPMD state's stages live
            # on disjoint submeshes and one jitted concat cannot span
            # device sets — per-group packing keeps the fast path
            devs = tuple(sorted(
                d.id for d in getattr(leaf.sharding, "device_set", ())
            ))
            groups.setdefault((str(leaf.dtype), devs),
                              []).append((name, leaf))
        if total > self.PACK_LIMIT_BYTES:
            return None
        if self._pack_fn is None:
            self._pack_fn = jax.jit(
                lambda leaves: jnp.concatenate(
                    [jnp.ravel(x) for x in leaves]
                )
            )
        out: dict[str, np.ndarray] = {}
        try:
            flats = {
                key: self._pack_fn([leaf for _, leaf in items])
                for key, items in groups.items()
            }
            for f in flats.values():
                f.copy_to_host_async()
            for key, items in groups.items():
                host = np.asarray(jax.device_get(flats[key]))
                off = 0
                for name, leaf in items:
                    n = int(np.prod(leaf.shape or (1,)))
                    out[name] = host[off:off + n].reshape(leaf.shape)
                    off += n
        except (RuntimeError, ValueError) as e:
            logger.warning("packed snapshot fetch failed (%s); "
                           "falling back to per-leaf", e)
            return None
        return out

    def _ensure_arena(self, size: int) -> SharedMemoryArena:
        if self._arena is None or self._arena.size < size:
            if self._arena is not None:
                self._arena.close()
            self._arena = SharedMemoryArena.open_or_create(
                self._arena_name, size
            )
        return self._arena

    # ----------------------------------------------------------------- read

    def header(self) -> dict | None:
        return self.meta_dict.get().get(_HEADER_KEY)

    def load_arrays(self, copy: bool = True
                    ) -> tuple[int, dict[str, np.ndarray]] | None:
        """Read the snapshot: (step, {path: array}). None if empty.

        ``copy=False`` returns zero-copy views into the arena — valid only
        until the next snapshot overwrites it. Use when a consumer reads the
        arrays immediately (``jax.device_put`` on restore) and skip the
        host-memory materialization cost.
        """
        header = self.header()
        if not header:
            return None
        arena = self._open_arena(min_size=int(header["total_size"]))
        if arena is None:
            return None
        out: dict[str, np.ndarray] = {}
        for name, info in header["metas"].items():
            view = np.ndarray(
                tuple(info["shape"]),
                dtype=np.dtype(info["dtype"]),
                buffer=arena.buf,
                offset=info["offset"],
            )
            out[name] = np.array(view) if copy else view
        return int(header["step"]), out

    def write_raw(self, header: dict, payload: bytes) -> None:
        """Install a snapshot received as raw bytes (buddy restore path:
        checkpoint/buddy.py fetch_snapshot -> this node's arena). The
        header becomes visible only after the bytes are in place, same
        ordering as save_state_dict."""
        total = int(header["total_size"])
        if len(payload) < total:
            raise ValueError(
                f"payload {len(payload)} bytes < header total {total}"
            )
        with self._local_lock:
            arena = self._ensure_arena(total)
            arena.buf[:total] = payload[:total]
        self.meta_dict.set(_HEADER_KEY, header)

    def read_raw(self) -> tuple[dict, memoryview] | None:
        """Agent-side zero-copy access: (header, raw buffer)."""
        header = self.header()
        if not header:
            return None
        arena = self._open_arena(min_size=int(header["total_size"]))
        if arena is None:
            return None
        return header, arena.buf

    def _open_arena(self, min_size: int = 0) -> SharedMemoryArena | None:
        """Open (or re-open) the arena mapping.

        The trainer unlinks and recreates the segment under the same name
        when a snapshot grows, so a cached mapping smaller than the header's
        ``total_size`` is stale — close it and map the new segment.
        """
        with self._local_lock:
            if self._arena is not None and self._arena.size < min_size:
                self._arena.close()
                self._arena = None
            if self._arena is None:
                self._arena = SharedMemoryArena.open(self._arena_name)
            return self._arena

    def clear(self) -> None:
        self.meta_dict.pop(_HEADER_KEY)

    def close(self, unlink: bool = False) -> None:
        with self._local_lock:
            if self._arena is not None:
                if unlink:
                    self._arena.unlink()
                self._arena.close()
                self._arena = None
        self.meta_dict.close()
        self.lock.close()


def restore_pytree(template: Any, arrays: dict[str, np.ndarray],
                   put: Callable[[str, np.ndarray], Any] | None = None) -> Any:
    """Rebuild a pytree shaped like ``template`` from named arrays.

    ``put`` maps (path, host_array) -> leaf (e.g. ``jax.device_put`` with a
    target sharding for reshard-on-load); identity by default.
    """
    import jax

    named = _leaf_paths(template)
    leaves = []
    for name, leaf in named:
        if name not in arrays:
            raise KeyError(f"checkpoint missing leaf {name!r}")
        arr = arrays[name]
        tmpl = np.asarray(leaf) if np.isscalar(leaf) else leaf
        if tuple(arr.shape) != tuple(tmpl.shape):
            raise ValueError(
                f"leaf {name!r} shape {arr.shape} != template {tmpl.shape}"
            )
        leaves.append(put(name, arr) if put else arr)
    treedef = jax.tree_util.tree_structure(template)
    return jax.tree_util.tree_unflatten(treedef, leaves)
