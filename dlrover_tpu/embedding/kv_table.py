"""Python binding for the native KvVariable embedding runtime.

Reference analog: the KvVariable python layer
(tfplus/tfplus/kv_variable/python/ops/kv_variable_ops.py + embedding_ops.py)
over the C++ kernels (kv_variable/kernels/kv_variable.h:89,
kernels/training_ops.cc). TPU-native shape: the unbounded id->row table
lives host-side (XLA needs static shapes); ``lookup`` gathers the batch's
rows into a dense [n, dim] block that ships to the device, and
``apply_adam`` applies the sparse optimizer update host-side to exactly the
touched rows (GroupAdam family: Adam + optional L2 + group-lasso row
shrinkage, reference group_adam.py:272).

The binding is ctypes over ``native/libdlrover_tpu_native.so`` (built by
``make -C native``; auto-built on first import when the toolchain is
available).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

from dlrover_tpu.common.log import get_logger

logger = get_logger(__name__)

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
)
_LIB_PATH = os.path.join(_NATIVE_DIR, "libdlrover_tpu_native.so")
_lib = None
_lib_lock = threading.Lock()

_i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
_f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
_u32p = np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS")


def _load_lib():
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        # run make unconditionally: it's a no-op when the .so is current,
        # and an edited kv_variable.cc must never load stale. The .so is
        # git-ignored and built from the tracked sources at first use;
        # a failed build is an error, never a stale library.
        proc = subprocess.run(
            ["make", "-C", _NATIVE_DIR], capture_output=True, text=True
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"native build failed:\n{proc.stderr[-4000:]}"
            )
        lib = ctypes.CDLL(_LIB_PATH)
        lib.kv_create.restype = ctypes.c_void_p
        lib.kv_create.argtypes = [
            ctypes.c_int, ctypes.c_int, ctypes.c_uint64, ctypes.c_float,
        ]
        lib.kv_free.argtypes = [ctypes.c_void_p]
        lib.kv_size.restype = ctypes.c_int64
        lib.kv_size.argtypes = [ctypes.c_void_p]
        lib.kv_lookup.argtypes = [
            ctypes.c_void_p, _i64p, ctypes.c_int64, _f32p, ctypes.c_int,
        ]
        lib.kv_apply_adam.argtypes = [
            ctypes.c_void_p, _i64p, _f32p, ctypes.c_int64,
            ctypes.c_float, ctypes.c_float, ctypes.c_float, ctypes.c_float,
            ctypes.c_int64, ctypes.c_float, ctypes.c_float,
        ]
        lib.kv_apply_adagrad.restype = ctypes.c_int
        lib.kv_apply_adagrad.argtypes = [
            ctypes.c_void_p, _i64p, _f32p, ctypes.c_int64,
            ctypes.c_float, ctypes.c_float, ctypes.c_float, ctypes.c_float,
        ]
        lib.kv_apply_ftrl.restype = ctypes.c_int
        lib.kv_apply_ftrl.argtypes = [
            ctypes.c_void_p, _i64p, _f32p, ctypes.c_int64,
            ctypes.c_float, ctypes.c_float, ctypes.c_float, ctypes.c_float,
            ctypes.c_float,
        ]
        lib.kv_apply_radam.restype = ctypes.c_int
        lib.kv_apply_radam.argtypes = [
            ctypes.c_void_p, _i64p, _f32p, ctypes.c_int64,
            ctypes.c_float, ctypes.c_float, ctypes.c_float, ctypes.c_float,
            ctypes.c_int64, ctypes.c_float,
        ]
        lib.kv_export.restype = ctypes.c_int64
        lib.kv_export.argtypes = [
            ctypes.c_void_p, ctypes.c_uint32,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
        ]
        lib.kv_import.argtypes = [
            ctypes.c_void_p, _i64p, _f32p,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
        ]
        lib.kv_remove.restype = ctypes.c_int64
        lib.kv_remove.argtypes = [ctypes.c_void_p, _i64p, ctypes.c_int64]
        lib.kv_delta_export.restype = ctypes.c_int64
        lib.kv_delta_export.argtypes = [
            ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int64, _i64p, ctypes.c_int,
        ]
        lib.kv_delta_overflowed.restype = ctypes.c_int
        lib.kv_delta_overflowed.argtypes = [ctypes.c_void_p]
        lib.kv_overflow_gen.restype = ctypes.c_int64
        lib.kv_overflow_gen.argtypes = [ctypes.c_void_p]
        lib.kv_ack_overflow.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.kv_io_errors.restype = ctypes.c_int64
        lib.kv_io_errors.argtypes = [ctypes.c_void_p]
        lib.kv_clear_deltas.argtypes = [ctypes.c_void_p]
        lib.kv_mark_dirty.argtypes = [ctypes.c_void_p, _i64p, ctypes.c_int64]
        lib.kv_enable_spill.restype = ctypes.c_int
        lib.kv_enable_spill.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.kv_evict.restype = ctypes.c_int64
        lib.kv_evict.argtypes = [
            ctypes.c_void_p, ctypes.c_uint32, ctypes.c_int64,
        ]
        lib.kv_disk_rows.restype = ctypes.c_int64
        lib.kv_disk_rows.argtypes = [ctypes.c_void_p]
        _lib = lib
        return lib


class KvEmbeddingTable:
    """Unbounded sparse-id embedding table with a sparse Adam optimizer.

    ``num_slots=2`` reserves Adam's (m, v) per row; set 0 for a frozen /
    SGD-updated table.
    """

    def __init__(self, dim: int, num_slots: int = 2, seed: int = 0,
                 init_scale: float = 0.05):
        self._lib = _load_lib()
        self.dim = dim
        self.num_slots = num_slots
        self._handle = self._lib.kv_create(
            dim, num_slots, seed, init_scale
        )
        self._step = 0

    def __del__(self):
        handle = getattr(self, "_handle", None)
        if handle:
            self._lib.kv_free(handle)
            self._handle = None

    def __len__(self) -> int:
        return int(self._lib.kv_size(self._handle))

    # ------------------------------------------------------------------- ops

    def lookup(self, ids: np.ndarray, init_missing: bool = True
               ) -> np.ndarray:
        """Gather rows for ``ids`` (any shape) -> [*ids.shape, dim] f32."""
        flat = np.ascontiguousarray(ids, np.int64).reshape(-1)
        out = np.empty((flat.size, self.dim), np.float32)
        self._lib.kv_lookup(
            self._handle, flat, flat.size, out, int(init_missing)
        )
        return out.reshape(*np.shape(ids), self.dim)

    def apply_adam(self, ids: np.ndarray, grads: np.ndarray,
                   lr: float = 1e-3, beta1: float = 0.9,
                   beta2: float = 0.999, eps: float = 1e-8,
                   l2: float = 0.0, group_lasso: float = 0.0,
                   step: int | None = None) -> None:
        """Sparse (Group)Adam on the rows of ``ids`` with ``grads``.

        Duplicate ids apply sequentially. ``group_lasso`` adds the
        proximal row-shrinkage step of the reference's GroupAdam.
        """
        flat, g = self._check_grads(ids, grads, 2, "apply_adam")
        if step is None:
            self._step += 1
            step = self._step
        self._lib.kv_apply_adam(
            self._handle, flat, g, flat.size,
            lr, beta1, beta2, eps, step, l2, group_lasso,
        )

    def _check_grads(self, ids: np.ndarray, grads: np.ndarray,
                     need_slots: int, what: str
                     ) -> tuple[np.ndarray, np.ndarray]:
        flat = np.ascontiguousarray(ids, np.int64).reshape(-1)
        g = np.ascontiguousarray(grads, np.float32).reshape(-1, self.dim)
        if g.shape[0] != flat.size:
            raise ValueError(
                f"{flat.size} ids but {g.shape[0]} gradient rows"
            )
        if self.num_slots < need_slots:
            raise ValueError(
                f"{what} needs num_slots >= {need_slots}, "
                f"table has {self.num_slots}"
            )
        return flat, g

    def apply_adagrad(self, ids: np.ndarray, grads: np.ndarray,
                      lr: float = 0.1, eps: float = 1e-8,
                      l2: float = 0.0, group_lasso: float = 0.0) -> None:
        """Sparse (Group)Adagrad: slot 0 is the squared-grad accumulator;
        ``group_lasso`` adds the reference GroupAdagrad's proximal row
        shrinkage (tfplus training_ops.cc Adagrad family)."""
        flat, g = self._check_grads(ids, grads, 1, "apply_adagrad")
        rc = self._lib.kv_apply_adagrad(
            self._handle, flat, g, flat.size, lr, eps, l2, group_lasso,
        )
        if rc != 0:
            raise RuntimeError(f"kv_apply_adagrad failed ({rc})")

    def apply_ftrl(self, ids: np.ndarray, grads: np.ndarray,
                   lr: float = 0.1, l1: float = 0.0, l2: float = 0.0,
                   beta: float = 1.0, group_lasso: float = 0.0) -> None:
        """Sparse (Group)FTRL-proximal: slots are (z, n). L1 drives
        per-coordinate sparsity; ``group_lasso`` prunes whole rows
        (reference SparseGroupFtrl)."""
        flat, g = self._check_grads(ids, grads, 2, "apply_ftrl")
        rc = self._lib.kv_apply_ftrl(
            self._handle, flat, g, flat.size, lr, l1, l2, beta,
            group_lasso,
        )
        if rc != 0:
            raise RuntimeError(f"kv_apply_ftrl failed ({rc})")

    def apply_radam(self, ids: np.ndarray, grads: np.ndarray,
                    lr: float = 1e-3, beta1: float = 0.9,
                    beta2: float = 0.999, eps: float = 1e-8,
                    l2: float = 0.0, step: int | None = None) -> None:
        """Sparse Rectified Adam (variance-rectified warmup-free Adam;
        reference tfplus rectified_adam.py). Slots are (m, v)."""
        flat, g = self._check_grads(ids, grads, 2, "apply_radam")
        if step is None:
            self._step += 1
            step = self._step
        rc = self._lib.kv_apply_radam(
            self._handle, flat, g, flat.size, lr, beta1, beta2, eps,
            step, l2,
        )
        if rc != 0:
            raise RuntimeError(f"kv_apply_radam failed ({rc})")

    def apply(self, optimizer: str, ids: np.ndarray, grads: np.ndarray,
              **kwargs) -> None:
        """Name-dispatched sparse update — what config-driven trainers
        (the recsys example) call. Optimizers: adam, group_adam,
        adagrad, group_adagrad, ftrl, group_ftrl, radam."""
        known = {"adam", "group_adam", "adagrad", "group_adagrad",
                 "ftrl", "group_ftrl", "radam"}
        if optimizer not in known:
            raise ValueError(f"unknown sparse optimizer {optimizer!r}")
        base = optimizer.removeprefix("group_")
        if optimizer.startswith("group_") and "group_lasso" not in kwargs:
            kwargs["group_lasso"] = 1e-3
        fn = {
            "adam": self.apply_adam,
            "adagrad": self.apply_adagrad,
            "ftrl": self.apply_ftrl,
            "radam": self.apply_radam,
        }[base]
        fn(ids, grads, **kwargs)

    def remove(self, ids: np.ndarray) -> int:
        flat = np.ascontiguousarray(ids, np.int64).reshape(-1)
        return int(self._lib.kv_remove(self._handle, flat, flat.size))

    # ---------------------------------------------- hybrid (tiered) storage

    def enable_spill(self, path: str) -> None:
        """Attach a disk spill tier (reference: hybrid_embedding's
        mem + storage tables). Cold rows move there via ``evict`` and
        fault back in on access; export/checkpoint sees both tiers."""
        rc = int(self._lib.kv_enable_spill(
            self._handle, os.fsencode(path)
        ))
        if rc == -2:
            raise RuntimeError(
                "spill tier already enabled; re-pointing it would orphan "
                "the spilled rows"
            )
        if rc != 0:
            raise OSError(f"cannot open spill file {path!r}")

    def evict(self, max_freq: int = 1, max_rows: int = 0) -> int:
        """Spill rows with frequency <= ``max_freq`` to disk (at most
        ``max_rows``; 0 = unlimited), freeing their host memory. Returns
        the number spilled."""
        return int(self._lib.kv_evict(self._handle, max_freq, max_rows))

    @property
    def disk_rows(self) -> int:
        return int(self._lib.kv_disk_rows(self._handle))

    # ------------------------------------------------------------ checkpoint

    def export(self, min_freq: int = 0, with_slots: bool = True
               ) -> dict[str, np.ndarray]:
        """Snapshot rows with frequency >= ``min_freq`` (the reference's
        under-threshold feature filtering)."""
        n = int(self._lib.kv_export(self._handle, min_freq, None, None,
                                    None, None, 0, None))
        keys = np.empty(n, np.int64)
        values = np.empty((n, self.dim), np.float32)
        slots = np.empty((n, self.num_slots * self.dim), np.float32)
        freq = np.empty(n, np.uint32)
        errs = np.zeros(1, np.int64)
        written = 0
        if n:
            # the fill pass is capacity-bounded: the table may mutate
            # between the count and fill calls (shard-level locking only)
            written = int(self._lib.kv_export(
                self._handle, min_freq,
                keys.ctypes.data_as(ctypes.c_void_p),
                values.ctypes.data_as(ctypes.c_void_p),
                slots.ctypes.data_as(ctypes.c_void_p)
                if with_slots and self.num_slots else None,
                freq.ctypes.data_as(ctypes.c_void_p),
                n,
                errs.ctypes.data_as(ctypes.c_void_p),
            ))
        if written < n:
            keys, values = keys[:written], values[:written]
            slots, freq = slots[:written], freq[:written]
        if int(errs[0]):
            # scoped to THIS call (the global io_errors counter also
            # counts unrelated lookup-path failures)
            raise OSError(
                f"{int(errs[0])} spill-tier read failures during "
                "export: the snapshot would silently omit rows"
            )
        out = {
            "keys": keys, "values": values, "freq": freq,
            "step": np.asarray(self._step, np.int64),
        }
        if with_slots and self.num_slots:
            out["slots"] = slots
        return out

    def import_(self, snapshot: dict[str, np.ndarray]) -> None:
        keys = np.ascontiguousarray(snapshot["keys"], np.int64)
        values = np.ascontiguousarray(snapshot["values"], np.float32)
        slots = snapshot.get("slots")
        freq = snapshot.get("freq")
        if values.shape != (keys.size, self.dim):
            raise ValueError(
                f"snapshot values shape {values.shape} != "
                f"({keys.size}, {self.dim}) — saved with a different dim?"
            )
        if slots is not None and np.shape(slots) != (
            keys.size, self.num_slots * self.dim
        ):
            raise ValueError(
                f"snapshot slots shape {np.shape(slots)} != "
                f"({keys.size}, {self.num_slots * self.dim}) — saved with "
                "different num_slots?"
            )
        if freq is not None and np.shape(freq) != (keys.size,):
            raise ValueError(f"snapshot freq shape {np.shape(freq)}")
        self._lib.kv_import(
            self._handle, keys, values,
            np.ascontiguousarray(slots, np.float32).ctypes.data_as(
                ctypes.c_void_p
            ) if slots is not None else None,
            np.ascontiguousarray(freq, np.uint32).ctypes.data_as(
                ctypes.c_void_p
            ) if freq is not None else None,
            keys.size,
        )
        if "step" in snapshot:
            self._step = int(snapshot["step"])

    # ----------------------------------------------------- incremental ckpt

    def _delta_drain_once(self, with_slots: bool, clear: bool
                          ) -> tuple[dict[str, np.ndarray], bool]:
        """One native drain pass; returns (chunk, complete). The chunk's
        ``read_errors`` counts spilled rows whose disk read failed — they
        keep their dirty marks and surface in the next drain."""
        counts = np.zeros(3, np.int64)
        self._lib.kv_delta_export(
            self._handle, None, None, None, None, 0, None, 0, counts, 0
        )
        # slack: the table may grow between count and fill; an early-stop
        # just means the remainder drains on the next pass
        n = int(counts[0]) + 256
        m = int(counts[1]) + 256
        keys = np.empty(n, np.int64)
        values = np.empty((n, self.dim), np.float32)
        slots = np.empty((n, self.num_slots * self.dim), np.float32)
        freq = np.empty(n, np.uint32)
        removed = np.empty(m, np.int64)
        complete = int(self._lib.kv_delta_export(
            self._handle,
            keys.ctypes.data_as(ctypes.c_void_p),
            values.ctypes.data_as(ctypes.c_void_p),
            slots.ctypes.data_as(ctypes.c_void_p)
            if with_slots and self.num_slots else None,
            freq.ctypes.data_as(ctypes.c_void_p),
            n,
            removed.ctypes.data_as(ctypes.c_void_p),
            m, counts, int(clear),
        ))
        r, d = int(counts[0]), int(counts[1])
        chunk = {
            "keys": keys[:r], "values": values[:r], "freq": freq[:r],
            "removed": removed[:d],
            "step": np.asarray(self._step, np.int64),
            "read_errors": np.asarray(int(counts[2]), np.int64),
        }
        if with_slots and self.num_slots:
            chunk["slots"] = slots[:r]
        return chunk, bool(complete)

    def delta_export(self, with_slots: bool = True, clear: bool = True
                     ) -> dict[str, np.ndarray]:
        """Rows whose values changed since the last clearing delta export
        (the reference's delta export for incremental checkpoints /
        serving sync). Includes ``removed``: keys deleted since then —
        restore replays removals before upserts. ``clear=True`` resets the
        tracking so the next delta is relative to this one.

        Each native pass drains whole shards atomically (a key's value
        export and its removal never interleave within a pass); passes
        are folded with ``merge_deltas`` so later events win. Lookup-only
        frequency bumps do not mark rows dirty, so restored frequencies
        can lag the live table's — value data is exact.
        """
        if clear:
            out, complete = self._delta_drain_once(with_slots, True)
            tries = 0
            while not complete and tries < 8:
                chunk, complete = self._delta_drain_once(with_slots, True)
                out = merge_deltas(out, chunk)
                tries += 1
            # early stops and spill-read failures are both LOSSLESS here:
            # an undrained shard keeps its marks/logs, and a failed-read
            # row keeps its dirty mark — the change surfaces in the next
            # delta. ``read_errors`` in the result tells checkpointing
            # callers this delta is not yet a complete cut.
        else:
            # clear=False passes drain nothing, so chunks can't be
            # merged (they'd duplicate); retry whole passes with freshly
            # counted buffers until one completes
            for _ in range(8):
                out, complete = self._delta_drain_once(with_slots, False)
                if complete:
                    break
            else:
                raise RuntimeError(
                    "delta_export(clear=False) could not complete: the "
                    "table is mutating faster than the drain"
                )
            if int(out["read_errors"]):
                # nothing was drained/cleared, so raising loses nothing —
                # and a peek consumer must not mistake this for complete
                raise OSError(
                    f"{int(out['read_errors'])} spill-tier read failures "
                    "during delta export"
                )
        return out

    def delta_overflowed(self) -> bool:
        """True when removals were dropped (bounded removed-log overflow)
        and no covering base has been acked: the delta chain is broken
        and the next save must be a full export."""
        return bool(self._lib.kv_delta_overflowed(self._handle))

    def overflow_gen(self) -> int:
        """Monotonic overflow generation (see the manager's ack cycle)."""
        return int(self._lib.kv_overflow_gen(self._handle))

    def ack_overflow(self, gen: int) -> None:
        """Mark overflows up to ``gen`` as covered by a durable base."""
        self._lib.kv_ack_overflow(self._handle, gen)

    @property
    def io_errors(self) -> int:
        """Cumulative spill-tier read failures."""
        return int(self._lib.kv_io_errors(self._handle))

    def clear_deltas(self) -> None:
        """Reset delta tracking (call after a full/base export)."""
        self._lib.kv_clear_deltas(self._handle)

    def mark_dirty(self, ids: np.ndarray) -> None:
        """Re-mark rows dirty (failed-checkpoint recovery: the export
        cleared their marks but the file never became durable)."""
        flat = np.ascontiguousarray(ids, np.int64).reshape(-1)
        if flat.size:
            self._lib.kv_mark_dirty(self._handle, flat, flat.size)

    def apply_delta(self, delta: dict[str, np.ndarray]) -> None:
        """Replay one delta: removals first, then row upserts."""
        removed = delta.get("removed")
        if removed is not None and np.size(removed):
            self.remove(np.asarray(removed))
        if np.size(delta["keys"]):
            self.import_({
                k: v for k, v in delta.items()
                if k not in ("removed", "read_errors")
            })


def merge_deltas(older: dict | None, newer: dict) -> dict:
    """Fold an older delta under a newer one (one replayable delta out).

    Replay applies removals before upserts, so an older row whose key was
    since removed must be dropped — keeping it would resurrect the stale
    value. For duplicate keys the newer row wins (import applies rows
    sequentially; newer rows are concatenated after older ones).
    """
    if older is None:
        return newer
    keep = ~np.isin(older["keys"], newer["removed"])
    out = dict(newer)
    out["keys"] = np.concatenate([older["keys"][keep], newer["keys"]])
    out["values"] = np.concatenate(
        [older["values"][keep], newer["values"]]
    )
    out["freq"] = np.concatenate([older["freq"][keep], newer["freq"]])
    if "slots" in newer and "slots" in older:
        out["slots"] = np.concatenate(
            [older["slots"][keep], newer["slots"]]
        )
    out["removed"] = np.concatenate([older["removed"], newer["removed"]])
    out["read_errors"] = np.asarray(
        int(older.get("read_errors", 0)) + int(newer.get("read_errors", 0)),
        np.int64,
    )
    return out


class IncrementalCheckpointManager:
    """Base + delta checkpoints for a KvEmbeddingTable.

    Reference analog: the incremental checkpoint manager
    (tfplus/tfplus/kv_variable/python/training/checkpoint_manager.py) —
    periodic full saves with cheap deltas between them, so a 100M-row
    table checkpoints at the cost of the rows that actually changed.

    Layout under ``directory``: ``base-N.npz`` (full export at version N)
    and ``delta-N.npz`` (changes from version N-1 to N); ``restore()``
    loads the newest base then replays every later delta in order.
    """

    def __init__(self, table: KvEmbeddingTable, directory: str,
                 base_interval: int = 10):
        self.table = table
        self.directory = directory
        self.base_interval = base_interval
        self._version = 0
        # changes drained from the table's delta tracking but not yet
        # durably written (carried across a failed save so nothing is
        # ever lost from the chain)
        self._pending: dict[str, np.ndarray] | None = None
        os.makedirs(directory, exist_ok=True)

    def _write(self, path: str, snap: dict) -> None:
        # save()'s contract is "tracking only advances once the file is
        # durable" — so durable must mean fsynced, not just in page cache
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            np.savez(f, **snap)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        dfd = os.open(self.directory, os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)

    def save(self) -> str:
        """Write the next checkpoint (base every ``base_interval``-th
        save, delta otherwise); returns the path written.

        Tracking state only advances when the file is durable: a failed
        write parks the drained changes in ``_pending`` (folded into the
        next attempt) and does not consume the version, so the chain
        stays gapless and lossless. A removed-log overflow (bounded
        native log) forces a base — the delta chain is broken there.
        """
        v = self._version + 1
        # the overflow generation observed BEFORE draining is what a
        # durable base can ack; an overflow racing the save keeps the
        # flag up and forces the next save to be a base as well
        overflow_gen = self.table.overflow_gen()
        force_base = self.table.delta_overflowed()
        if force_base or (v - 1) % self.base_interval == 0:
            # drain tracking FIRST, then snapshot: the full export is a
            # superset of the drained delta, so a durable base supersedes
            # it (and any older pending) — rows dirtied between drain and
            # export keep their marks and land in the next delta
            pend = self.table.delta_export()
            path = os.path.join(self.directory, f"base-{v}.npz")
            try:
                self._write(path, self.table.export())
            except BaseException:
                self._pending = merge_deltas(self._pending, pend)
                raise
            self._pending = None
            self.table.ack_overflow(overflow_gen)
        else:
            path = os.path.join(self.directory, f"delta-{v}.npz")
            snap = merge_deltas(self._pending, self.table.delta_export())
            if int(snap.get("read_errors", 0)):
                # some spilled rows could not be read: a delta written now
                # would be a valid-but-stale cut (those rows revert on a
                # restore taken before the next delta). Park everything
                # drained and surface the failure; the next save retries.
                self._pending = snap
                raise OSError(
                    f"{int(snap['read_errors'])} spill-tier read "
                    "failures while draining the delta; checkpoint "
                    "postponed (no data lost)"
                )
            snap = {k: v_ for k, v_ in snap.items() if k != "read_errors"}
            try:
                self._write(path, snap)
            except BaseException:
                self._pending = snap
                raise
            self._pending = None
        self._version = v
        return path

    def restore(self) -> int:
        """Load newest base + later deltas; returns the version restored
        (0 when the directory holds no base). Raises when delta files
        exist beyond a gap in the chain (a replay would silently skip
        them — the directory is corrupt or from a foreign run)."""
        names = os.listdir(self.directory)
        bases = sorted(
            int(f[len("base-"):-len(".npz")])
            for f in names
            if f.startswith("base-") and f.endswith(".npz")
        )
        if not bases:
            return 0
        base_v = bases[-1]
        deltas = {
            int(f[len("delta-"):-len(".npz")])
            for f in names
            if f.startswith("delta-") and f.endswith(".npz")
        }
        # validate the chain BEFORE touching the table: raising after a
        # partial replay would leave the caller's table half-mutated
        v = base_v
        while (v + 1) in deltas:
            v += 1
        orphans = sorted(d for d in deltas if d > v)
        if orphans:
            raise ValueError(
                f"delta chain ends at version {v} but later files exist "
                f"(delta-{orphans}): refusing a restore that would drop "
                "them"
            )
        with np.load(os.path.join(self.directory, f"base-{base_v}.npz")) as z:
            self.table.import_(dict(z))
        for d in range(base_v + 1, v + 1):
            with np.load(os.path.join(self.directory, f"delta-{d}.npz")) as z:
                self.table.apply_delta(dict(z))
        # restore itself dirties every imported row; the next delta
        # should be relative to this restored state
        self.table.clear_deltas()
        self._version = v
        return v
