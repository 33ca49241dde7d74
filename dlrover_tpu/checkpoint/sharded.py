"""Sharded flash checkpoint with reshard-on-load.

Reference analog: the FSDP DCP engine
(dlrover/trainer/torch/flash_checkpoint/fsdp_engine.py:158,224
SharedMemoryWriter/Reader implementing torch DCP storage over shm) and
ATorch's flat-param reshard-on-load (atorch/atorch/utils/fsdp_save_util.py:523
ShardTensorUtil). TPU-native design: every node snapshots only the array
shards it *addresses* (``jax.Array.addressable_shards``), each tagged with
its global index; restore rebuilds global arrays on ANY target mesh with
``jax.make_array_from_callback``, assembling each device's slice from
whichever saved pieces cover it. A checkpoint written on mesh A restores
onto mesh B — the elastic-membership-change case XLA's static world makes
mandatory.

Commit protocol: every node's agent writes ``node_<id>.bin/.meta.json`` +
``done_<id>``; rank-0's agent waits for ``num_shards`` done markers before
moving the ``latest`` tracker (agent/ckpt_saver.py:_maybe_commit).
"""

from __future__ import annotations

import json
import os
from typing import Any, Callable, Sequence

import numpy as np

from dlrover_tpu.common import envspec
from dlrover_tpu.common.constants import EnvKey
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.telemetry.journal import get_journal
from dlrover_tpu.telemetry.metrics import registry
from dlrover_tpu.checkpoint.engine import CheckpointEngine
from dlrover_tpu.checkpoint.shm_handler import _leaf_paths

logger = get_logger(__name__)

PIECE_SEP = "::piece"

_restore_parallel_seconds = registry().histogram(
    "dlrover_tpu_ckpt_restore_parallel_seconds",
    "per-host sharded storage restore duration (parallel piece reads "
    "+ assembly) — flat in host count by design",
)


def persist_replicas() -> int:
    """How many DP replica copies of each shard are persisted to
    storage. 1 = exactly-one-writer dedup (smallest checkpoint);
    2 = primary + twin, the redundancy the per-shard rollback needs."""
    return max(1, envspec.get_int(EnvKey.CKPT_PERSIST_REPLICAS))


class CoverageError(RuntimeError):
    """The available pieces do not cover a requested slice."""


def _norm_index(index: Sequence[slice], shape: Sequence[int]
                ) -> list[list[int]]:
    """Normalize a tuple of slices to [[start, stop], ...] (step 1 only)."""
    out = []
    for sl, dim in zip(index, shape):
        start, stop, step = sl.indices(dim)
        if step != 1:
            raise ValueError(f"strided shard index {sl} unsupported")
        out.append([start, stop])
    return out


class PieceSource:
    """One saved shard of one leaf + how to read its bytes."""

    def __init__(self, path: str, global_shape: tuple[int, ...],
                 dtype: np.dtype, index: list[list[int]],
                 read: Callable[[], np.ndarray], replica: int = 0):
        self.path = path
        self.global_shape = global_shape
        self.dtype = dtype
        self.index = index  # [[start, stop], ...] in the global array
        self.replica = replica  # DP replica rank of the saved copy
        self._read = read

    def data(self) -> np.ndarray:
        return self._read()


def assemble(target_index: list[list[int]], dtype: np.dtype,
             pieces: list[PieceSource]) -> np.ndarray:
    """Fill the target slice from overlapping pieces; error on gaps."""
    shape = tuple(stop - start for start, stop in target_index)
    out = np.empty(shape, dtype)
    filled = 0
    for p in pieces:
        dst, src = [], []
        empty = False
        for (t0, t1), (p0, p1) in zip(target_index, p.index):
            lo, hi = max(t0, p0), min(t1, p1)
            if lo >= hi:
                empty = True
                break
            dst.append(slice(lo - t0, hi - t0))
            src.append(slice(lo - p0, hi - p0))
        if empty:
            continue
        block = p.data()[tuple(src)]
        out[tuple(dst)] = block
        filled += block.size
    if filled < int(np.prod(shape)):
        raise CoverageError(
            f"pieces cover {filled} of {int(np.prod(shape))} elements for "
            f"target {target_index}"
        )
    return out


def _registry_entries(metas: dict, index_map: dict,
                      view: Callable[[dict], np.ndarray]
                      ) -> dict[str, list[PieceSource]]:
    registry: dict[str, list[PieceSource]] = {}
    for key, entry in index_map.items():
        info = metas.get(key)
        if info is None:
            continue
        registry.setdefault(entry["path"], []).append(
            PieceSource(
                path=entry["path"],
                global_shape=tuple(entry["global_shape"]),
                dtype=np.dtype(entry["dtype"]),
                index=[list(p) for p in entry["index"]],
                read=lambda info=info: view(info),
                replica=int(entry.get("replica", 0)),
            )
        )
    return registry


def storage_piece_registry(
    storage, ckpt_dir: str, step: int, num_shards: int,
    bad_pieces: dict[str, set | None] | None = None,
) -> dict[str, list[PieceSource]] | None:
    """Piece registry over the COMMITTED world's files for ``step``.

    Only node files named by a ``done_<id>_w<num_shards>`` marker are
    read: a step directory may also hold stale files from a previous
    incarnation with a different world size (same step re-reached after
    an elastic reshape), and blending those would restore divergent
    weights. ``bad_pieces`` (from the integrity RestorePlan) excludes
    shard files — or individual pieces — that failed verification, so
    their replica twins serve those slices instead.

    The per-node metadata reads run CONCURRENTLY (each inside a
    ``ckpt_restore_shard`` span): against an object store these are
    round trips, and a restore's setup must stay flat as the writer
    count grows. Piece BYTES stay lazy — memmap windows locally,
    ``read_range`` slices remotely — so a topology-changing restore
    pulls only the byte ranges the local mesh actually needs.
    """
    from concurrent.futures import ThreadPoolExecutor

    from dlrover_tpu.agent.ckpt_saver import step_dir
    from dlrover_tpu.common.storage import PosixDiskStorage

    sdir = step_dir(ckpt_dir, step)
    if not storage.exists(sdir):
        return None
    suffix = f"_w{num_shards}"
    node_ids = [
        f[len("done_"):-len(suffix)]
        for f in storage.listdir(sdir)
        if f.startswith("done_") and f.endswith(suffix)
    ]
    bad_pieces = bad_pieces or {}
    local = isinstance(storage, PosixDiskStorage)

    def _node_part(nid: str) -> dict[str, list[PieceSource]]:
        bad = bad_pieces.get(nid, set())
        if bad is None:
            return {}  # whole shard file failed; twins cover it
        meta_path = os.path.join(sdir, f"node_{nid}.meta.json")
        if not storage.exists(meta_path):
            return {}
        with get_journal().span("ckpt_restore_shard", step=step,
                                writer=str(nid)):
            header = json.loads(storage.read_text(meta_path))
            index_map = {
                k: v
                for k, v in (header.get("sharded_index") or {}).items()
                if k not in bad
            }
            if not index_map:
                return {}
            bin_path = os.path.join(sdir, f"node_{nid}.bin")
            if local:
                # memmap keeps restore lazy: only bytes a target slice
                # needs are paged in
                blob = np.memmap(bin_path, dtype=np.uint8, mode="r")

                def view(info, blob=blob):
                    return np.ndarray(
                        tuple(info["shape"]),
                        dtype=np.dtype(info["dtype"]),
                        buffer=blob, offset=info["offset"],
                    )
            else:
                # ranged reads: one GET per needed piece, never a
                # whole-file download
                def view(info, bin_path=bin_path):
                    raw = storage.read_range(
                        bin_path, int(info["offset"]),
                        int(info["nbytes"]),
                    )
                    return np.frombuffer(
                        raw, dtype=np.dtype(info["dtype"])
                    ).reshape(tuple(info["shape"]))
            return _registry_entries(header["metas"], index_map, view)

    registry: dict[str, list[PieceSource]] = {}
    ordered = sorted(nid for nid in node_ids)
    if len(ordered) > 1:
        with ThreadPoolExecutor(max_workers=min(8, len(ordered))) as pool:
            parts = list(pool.map(_node_part, ordered))
    else:
        parts = [_node_part(nid) for nid in ordered]
    for part in parts:
        for path, lst in part.items():
            registry.setdefault(path, []).extend(lst)
    # primary replicas first: overlapping twin pieces hold the same
    # bytes, but deterministic order keeps assembly stable
    for lst in registry.values():
        lst.sort(key=lambda p: p.replica)
    return registry or None


class ShardedCheckpointEngine(CheckpointEngine):
    """Per-node shard snapshots + any-mesh restore.

    ``owned`` decides which addressable shards this node snapshots. The
    default keeps, for every distinct shard index, this NODE's
    lowest-replica copy — i.e. replicas are deduplicated within a node
    but every node retains full coverage of the data its own devices
    hold. A global replica_id==0 policy would be smaller (exactly-once
    across the job) but leaves rank>0 nodes unable to restore
    REPLICATED leaves (the step counter, norms — everything, under pure
    dp) from their local shm: their restore would always fall through
    to storage, defeating restart-in-place AND buddy replication. The
    reference's per-rank shm snapshots make the same size-for-locality
    trade (ckpt_saver.py: each rank snapshots its own state view).
    """

    # async supersede semantics would break cross-node step agreement
    supports_async_snapshot = False

    def __init__(self, *args,
                 owned: Callable[[Any], bool] | None = None, **kwargs):
        kwargs.setdefault("replicated", False)
        super().__init__(*args, **kwargs)
        self._owned = owned  # None -> per-node replica dedup (default)

    @staticmethod
    def _node_owned_shards(leaf) -> list:
        """This node's lowest-replica copy of each distinct shard index."""
        best: dict = {}
        for s in leaf.addressable_shards:
            key = tuple(
                tuple(pair) for pair in _norm_index(s.index, leaf.shape)
            )
            cur = best.get(key)
            if cur is None or s.replica_id < cur.replica_id:
                best[key] = s
        return list(best.values())

    # ------------------------------------------------------------------ save

    def _prepare_state(self, state: Any) -> tuple[Any, dict]:
        """Split the pytree into this node's addressable pieces.

        Every piece carries its global index, its REPLICA rank, and a
        ``persist`` flag: the shm snapshot keeps full local coverage
        (restart-in-place, buddy replication), but the agent persister
        writes only flagged pieces — ``replica_id <
        DLROVER_TPU_CKPT_PERSIST_REPLICAS`` — so exactly one DP replica
        (or one primary + one twin at replicas=2) writes each shard to
        storage, with zero cross-host coordination: the writer
        assignment is a pure function of the sharding.
        """
        import jax

        keep = persist_replicas()
        pieces: dict[str, Any] = {}
        index_map: dict[str, dict] = {}
        for name, leaf in _leaf_paths(state):
            if isinstance(leaf, jax.Array):
                if self._owned is not None:
                    shards = [
                        s for s in leaf.addressable_shards
                        if self._owned(s)
                    ]
                else:
                    shards = self._node_owned_shards(leaf)
                for i, s in enumerate(shards):
                    key = f"{name}{PIECE_SEP}{i}"
                    pieces[key] = s.data
                    index_map[key] = {
                        "path": name,
                        "global_shape": list(leaf.shape),
                        "dtype": str(np.dtype(leaf.dtype)),
                        "index": _norm_index(s.index, leaf.shape),
                        "replica": int(s.replica_id),
                        "persist": bool(s.replica_id < keep),
                    }
            else:
                # host leaves are replicated on every node: the node
                # RANK is the replica rank, so rank 0 (and rank 1 at
                # replicas=2) persists and the rest dedup away
                arr = np.asarray(leaf)
                pieces[name] = arr
                index_map[name] = {
                    "path": name,
                    "global_shape": list(arr.shape),
                    "dtype": str(arr.dtype),
                    "index": _norm_index(
                        tuple(slice(None) for _ in arr.shape), arr.shape
                    ),
                    "replica": int(self.node_rank),
                    "persist": bool(self.node_rank < keep),
                }
        return pieces, {"sharded_index": index_map}

    def snapshot_pieces(self, step: int, pieces: dict[str, np.ndarray],
                        index_map: dict[str, dict]) -> None:
        """Install an explicit piece set as this node's shm snapshot
        (bench / chaos-scenario hosts simulated in one process, remote
        producers). ``index_map`` entries need path/global_shape/dtype/
        index; replica defaults to 0 (persisted)."""
        for key, entry in index_map.items():
            entry.setdefault("replica", 0)
            entry.setdefault("persist",
                             entry["replica"] < persist_replicas())
            if key not in pieces:
                raise KeyError(f"index_map key {key!r} has no piece")
        self.shm_handler.save_state_dict(
            step, dict(pieces),
            extra_meta={**self._extra_meta(),
                        "sharded_index": dict(index_map)},
        )

    # ------------------------------------------------------------------ load

    def _shm_pieces(self) -> tuple[int, dict[str, list[PieceSource]]] | None:
        """Zero-copy piece registry from this node's shm snapshot."""
        raw = self.shm_handler.read_raw()
        if raw is None:
            return None
        header, buf = raw
        index_map = header.get("sharded_index")
        if not index_map:
            return None
        return int(header["step"]), self._registry_from(
            header["metas"], index_map,
            lambda info: np.ndarray(
                tuple(info["shape"]), dtype=np.dtype(info["dtype"]),
                buffer=buf, offset=info["offset"],
            ),
        )

    def _storage_pieces(self, step: int, num_shards: int,
                        bad_pieces: dict[str, set | None] | None = None,
                        ) -> dict[str, list[PieceSource]] | None:
        return storage_piece_registry(
            self.storage, self.ckpt_dir, step, num_shards,
            bad_pieces=bad_pieces,
        )

    @staticmethod
    def _registry_from(metas: dict, index_map: dict,
                       view: Callable[[dict], np.ndarray]
                       ) -> dict[str, list[PieceSource]]:
        return _registry_entries(metas, index_map, view)

    def load_sharded(self, template: Any, shardings: Any
                     ) -> tuple[int, Any] | None:
        import time as _time

        from dlrover_tpu.checkpoint.engine import _record_restore
        from dlrover_tpu.parallel.compile_cache import launder

        start = _time.monotonic()
        loaded = self._load_sharded_impl(template, shardings)
        if loaded is not None:
            # every branch below builds the tree host-side (arena views
            # / storage pieces through device_put or
            # make_array_from_callback): re-stage before ANY cached AOT
            # executable can see it, or donation corrupts it in place
            # on the CPU backend (DESIGN.md §17.4)
            loaded = (loaded[0], launder(loaded[1]))
            # over when the state is on the chip, not when it is queued
            import jax

            jax.block_until_ready(loaded[1])
            _record_restore("sharded", start, loaded[0])
        return loaded

    def _load_sharded_impl(self, template: Any, shardings: Any
                           ) -> tuple[int, Any] | None:
        """Restore onto ``shardings`` (any mesh): (step, state) or None.

        ``template`` supplies structure/shape/dtype (concrete arrays or
        ``jax.eval_shape`` structs); ``shardings`` is a matching tree of
        target ``Sharding``s. shm fast path first (restart-in-place, same
        mesh) — only when every process's snapshot is at the SAME step
        (nodes killed mid-step may be one snapshot apart; mixing steps
        would silently blend divergent shards) — else the committed
        storage step, which the tracker guarantees is shard-complete.
        """
        snap = self._shm_pieces()
        # every process joins the step-agreement collective (a process
        # with nothing local reports -1), or the others deadlock in it;
        # the gathered vector is kept so every later branch decision is
        # computed identically on all processes (collective-uniform)
        steps = self._allgather_steps(snap[0] if snap else -1)
        use_shm = bool((steps >= 0).all() and (steps == steps[0]).all())
        built = None
        if use_shm:
            step, registry = snap
            try:
                built = self._build(template, shardings, registry)
            except CoverageError:
                logger.info(
                    "local shm pieces don't cover the target shardings "
                    "(mesh changed); assembling from storage"
                )
            # the shm-vs-storage choice must be collective: if ANY
            # process's local pieces can't cover its new shards, all
            # processes fall back to the committed storage step together
            # — half restoring step N from shm and half step M from
            # storage is silent divergence
            if not self._all_processes_agree(built is not None):
                built = None
            if built is not None:
                return step, built
        elif (steps >= 0).any():
            rolled = self._consensus_rollback(
                template, shardings, snap, steps
            )
            if rolled is not None:
                return rolled
            logger.info(
                "shm snapshot steps disagree across nodes and the oldest "
                "holder can't serve the full state; restoring the "
                "committed storage step instead"
            )
        import time as _time

        from dlrover_tpu.checkpoint.integrity import resolve_restore_plan

        # newest VERIFIED restore plan (crc manifest + COMMIT marker +
        # quorum over replica twins): every process resolves
        # independently but deterministically — same storage, same walk
        # — so the choice stays collective-uniform
        plan = resolve_restore_plan(self.storage, self.ckpt_dir)
        if plan is None:
            return None
        registry = self._storage_pieces(
            plan.step, plan.num_shards, bad_pieces=plan.bad_pieces
        )
        if registry is None:
            return None
        t0 = _time.monotonic()
        built = self._build(template, shardings, registry)
        _restore_parallel_seconds.observe(_time.monotonic() - t0)
        return plan.step, built

    @staticmethod
    def _allgather_steps(step: int) -> np.ndarray:
        """Every process's snapshot step (-1 = none), identical on all."""
        import jax

        if jax.process_count() == 1:
            return np.asarray([step], np.int64)
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(
            np.asarray(step, np.int64)
        )).reshape(-1)

    def _consensus_rollback(self, template: Any, shardings: Any,
                            snap, steps: np.ndarray
                            ) -> tuple[int, Any] | None:
        """Steps diverge across processes: roll every node back to the
        OLDEST snapshot if its holder can serve the full state.

        This is the zero-storage-read preemption recovery: the node that
        died was restored from its buddy one or two snapshots behind the
        survivors (the buddy copy lags by the replication cadence), and
        the survivors cannot rewind their own shm. When the oldest
        holder's local pieces cover every leaf in full — always true for
        replicated/dp layouts, where each node snapshots complete
        arrays — it broadcasts that state and the whole job resumes from
        the common step; at-least-once data sharding re-runs the few
        rolled-back steps. Truly sharded layouts return None (storage is
        the only consistent source there).
        """
        import jax

        valid = steps[steps >= 0]
        if valid.size == 0 or jax.process_count() == 1:
            return None
        consensus = int(valid.min())
        src = int(np.nonzero(steps == consensus)[0][0])
        i_am_src = jax.process_index() == src
        full = None
        if i_am_src and snap is not None:
            try:
                full = self._full_host_state(template, snap[1])
            except (CoverageError, ValueError) as e:
                logger.info("consensus rollback unavailable: %s", e)
        from jax.experimental import multihost_utils

        flags = np.asarray(multihost_utils.process_allgather(
            np.asarray(1 if full is not None else 0, np.int64)
        )).reshape(-1)
        if not flags[src]:
            return None
        if full is None:
            full = jax.tree.map(
                lambda l: np.zeros(tuple(l.shape), l.dtype), template
            )
        logger.info(
            "rolling back to step %d from process %d (steps were %s)",
            consensus, src, steps.tolist(),
        )
        state = multihost_utils.broadcast_one_to_all(
            full, is_source=i_am_src
        )
        state = jax.tree.map(jax.device_put, state, shardings)
        return consensus, state

    def _full_host_state(self, template: Any,
                         registry: dict[str, list[PieceSource]]) -> Any:
        """Materialize the COMPLETE state host-side from local pieces;
        raises CoverageError when any leaf isn't fully covered."""
        named = _leaf_paths(template)
        leaves = []
        for name, leaf in named:
            pieces = registry.get(name)
            if not pieces:
                raise CoverageError(f"no local pieces for {name!r}")
            shape = tuple(pieces[0].global_shape)
            if tuple(getattr(leaf, "shape", shape)) != shape:
                raise ValueError(
                    f"leaf {name!r}: snapshot shape {shape} != template "
                    f"{tuple(leaf.shape)}"
                )
            want_dtype = getattr(leaf, "dtype", None)
            if (want_dtype is not None
                    and np.dtype(want_dtype) != pieces[0].dtype):
                # non-source processes broadcast zeros of the TEMPLATE
                # dtype; a mismatched source tree would wedge the
                # recovery collective instead of falling back to storage
                raise ValueError(
                    f"leaf {name!r}: snapshot dtype {pieces[0].dtype} "
                    f"!= template {np.dtype(want_dtype)}"
                )
            leaves.append(assemble(
                [[0, s] for s in shape], pieces[0].dtype, pieces
            ))
        import jax

        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(template), leaves
        )

    @staticmethod
    def _all_processes_agree(ok: bool) -> bool:
        import jax

        if jax.process_count() == 1:
            return ok
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(
            np.asarray(1 if ok else 0, np.int64)
        )
        return bool(flags.all())

    def _build(self, template: Any, shardings: Any,
               registry: dict[str, list[PieceSource]]) -> Any:
        import jax

        named = _leaf_paths(template)
        shard_of = dict(_leaf_paths(shardings))
        leaves = []
        for name, leaf in named:
            pieces = registry.get(name)
            if not pieces:
                raise CoverageError(f"checkpoint has no pieces for {name!r}")
            shape = tuple(pieces[0].global_shape)
            dtype = pieces[0].dtype
            if tuple(getattr(leaf, "shape", shape)) != shape:
                raise ValueError(
                    f"leaf {name!r}: checkpoint shape {shape} != template "
                    f"{tuple(leaf.shape)}"
                )
            sharding = shard_of[name]
            arr = jax.make_array_from_callback(
                shape, sharding,
                lambda idx, p=pieces, d=dtype, s=shape: assemble(
                    _norm_index(idx, s), d, p
                ),
            )
            leaves.append(arr)
        treedef = jax.tree_util.tree_structure(template)
        return jax.tree_util.tree_unflatten(treedef, leaves)
