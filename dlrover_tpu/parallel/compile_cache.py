"""Elastic compile cache: serialized AOT executables keyed on
topology × model-shape × strategy fingerprint (DESIGN.md §17).

The residual per-failure cost after the warm-recovery path (PR 5) is
XLA recompilation: respawn/rendezvous/restore are ~0, but every
incarnation re-traces and re-compiles the same program — ~7s on CPU,
tens of seconds per real XLA:TPU compile. ElasWave
(PAPERS.md 2510.00606) closes this gap by making a membership change a
resharding event instead of a restart; the enabling piece is that the
program for the post-change topology must already exist.

This module is the trainer half of that cache:

- ``compile_fingerprint``: canonical key over everything that changes
  the executable — device topology, mesh axes, model config, strategy,
  abstract arg shapes/shardings, jax version + backend.
- ``serialize_executable_blob`` / ``load_executable_blob``: the
  ``jax.experimental.serialize_executable`` round-trip, wrapped in a
  CRC-checked envelope (a torn cache file must read as a miss, never a
  misloaded program).
- ``CompileCacheClient``: two layers — a node-local directory (shared
  by every incarnation and the parked standby on the host; placed by
  ``JAX_COMPILATION_CACHE_DIR``, see ``cache_root``) in front of the
  master-served store (``master/kv_store.py::CompileCacheService``)
  that survives node relaunches and feeds freshly joined hosts.
- ``load_or_compile``: the one call sites use — returns the loaded
  executable on a key hit (~0.1s) or compiles, publishes, and returns.
- ``FallbackPrecompiler``: the AOT-fallback-topology daemon — after a
  successful rendezvous it lowers and compiles the N−1/N+1 meshes in
  the background (reusing the offline AOT machinery of
  ``parallel/dry_run.py``: compile is host-side and needs no exclusive
  chip access) and publishes them, so the fallback executable is
  already resident when a node dies.

Module top level is jax-free on purpose: the metrics live in
``master/kv_store.py`` (one registration site serves both the master
and this client), and jax is imported lazily so control-plane processes
can import the fingerprint helpers without initializing a backend.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import os
import pickle
import threading
import time
import zlib
from typing import Any, Callable, Sequence

from dlrover_tpu.common import envspec
from dlrover_tpu.common.constants import EnvKey
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.master.kv_store import (
    cache_hits_total,
    cache_misses_total,
    cache_puts_total,
    topology_tag,
)
from dlrover_tpu.telemetry.journal import get_journal, spawn_ctx

logger = get_logger(__name__)

_ENVELOPE_MAGIC = b"DLRTPU-AOT1"


def aot_cache_enabled() -> bool:
    """The executable cache rides ``serialize_executable`` (a pickled
    XLA executable + arg tree) — unlike the XLA persistent-cache-dir
    path it round-trips correctly on this CPU backend, so it defaults
    on everywhere. ``DLROVER_TPU_AOT_CACHE=0`` turns it off."""
    return envspec.get_bool(EnvKey.AOT_CACHE)


# ----------------------------------------------------------- fingerprinting


def _canonical(value: Any) -> Any:
    if dataclasses.is_dataclass(value) and not isinstance(value, type):
        return _canonical(dataclasses.asdict(value))
    if isinstance(value, dict):
        return {str(k): _canonical(v) for k, v in sorted(value.items())}
    if isinstance(value, (list, tuple)):
        return [_canonical(v) for v in value]
    if isinstance(value, (str, int, float, bool)) or value is None:
        return value
    return repr(value)


def abstract_signature(tree: Any) -> list:
    """Shape/dtype/sharding-spec triples of a pytree of abstract args —
    the part of the fingerprint that pins the program's calling
    convention (a resharded batch dim or a changed accumulation factor
    must map to a different executable)."""
    import jax

    sig = []
    for leaf in jax.tree_util.tree_leaves(tree):
        sharding = getattr(leaf, "sharding", None)
        spec = getattr(sharding, "spec", None)
        sig.append([
            list(getattr(leaf, "shape", ())),
            str(getattr(leaf, "dtype", "?")),
            repr(spec) if spec is not None else "",
        ])
    return sig


@functools.lru_cache(maxsize=1)
def code_digest() -> str:
    """sha256 of this package's Python sources (paths and bytes, read
    once a process: ~2 MB, ~20 ms). It rides every fingerprint because
    the other inputs name what a program is compiled FOR (topology,
    config, calling convention), not what it computes: a change to a
    kernel or a layer leaves them all as they were, and without this an
    executable cached by the code before the change would be served to
    the code after it."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    digest = hashlib.sha256()
    for folder, dirs, files in os.walk(root):
        dirs.sort()
        for name in sorted(f for f in files if f.endswith(".py")):
            path = os.path.join(folder, name)
            digest.update(os.path.relpath(path, root).encode())
            with open(path, "rb") as f:
                digest.update(f.read())
    return digest.hexdigest()[:32]


def compile_fingerprint(
    *,
    num_nodes: int,
    total_devices: int,
    mesh_axes: dict,
    model: Any,
    strategy: Any,
    args_signature: Any = None,
    extra: dict | None = None,
) -> tuple[str, dict]:
    """(key, inputs): the cache key is ``<topology_tag>/<digest>`` and
    ``inputs`` is the raw material (stored beside the artifact so a
    reader verifies the match instead of trusting the digest)."""
    import jax

    strategy_json = (
        strategy.to_json() if hasattr(strategy, "to_json")
        else json.dumps(_canonical(strategy))
    )
    inputs = {
        "num_nodes": int(num_nodes),
        "total_devices": int(total_devices),
        "mesh_axes": _canonical(dict(mesh_axes)),
        "model": _canonical(model),
        "strategy": json.loads(strategy_json),
        "args": _canonical(args_signature) if args_signature else [],
        "jax": jax.__version__,
        "code": code_digest(),
        "platform": jax.default_backend(),
        "extra": _canonical(extra or {}),
    }
    digest = hashlib.sha256(
        json.dumps(inputs, sort_keys=True).encode()
    ).hexdigest()[:32]
    tag = topology_tag(total_devices, num_nodes)
    return f"{tag}/{digest}", inputs


def stage_key(base_key: str, *, stage: int, num_stages: int, phase: str,
              interleave: int = 1) -> str:
    """Per-stage compile-cache key for an MPMD pipeline program
    (``parallel/mpmd.py``): the stage index, stage count, chunk config
    and program phase (``fwd``/``bwd``/``update``) ride IN the key —
    readable in cache listings and scannable by prefix. The ``pp``
    marker directly after the topology tag lets the agent's reshard
    decision count per-stage executables with one coverage scan
    (``<tag>/pp``); ``base_key`` must come from
    :func:`compile_fingerprint` with the same stage facts in ``extra``
    (the digest is what actually pins the program)."""
    tag, digest = base_key.split("/", 1)
    return (f"{tag}/pp{int(stage)}of{int(num_stages)}"
            f"v{max(1, int(interleave))}{phase}_{digest}")


def verify_key(base_key: str, *, depth: int) -> str:
    """Per-depth compile-cache key for a speculative-decode verify
    program (DESIGN.md §31): the draft depth rides IN the key — one
    entry per member of the pow2 depth ladder, scannable by prefix
    (``<tag>/sv``) just like the pipeline-stage keys. ``base_key``
    must come from :func:`compile_fingerprint` with the serving slot
    geometry in the strategy facts."""
    tag, digest = base_key.split("/", 1)
    return f"{tag}/sv{int(depth)}_{digest}"


# ------------------------------------------------------- artifact envelope


def executable_stats(compiled) -> dict:
    """Cheap post-compile facts worth caching beside the executable —
    the program's FLOPs (XLA cost analysis), the number the live MFU
    gauge needs, and how many Pallas kernels it holds
    (``tpu_custom_call`` in its text: the evidence that a kernel
    attention config got its kernel). Computed ONCE at compile time and
    stored in the envelope, so a warm cache load never re-lowers or
    re-prints the program just to count."""
    from dlrover_tpu.utils.profiler import executable_flops

    stats = {}
    flops = executable_flops(compiled)
    if flops > 0:
        stats["flops"] = flops
    kernels = compiled.as_text().count("tpu_custom_call")
    if kernels:
        stats["pallas_calls"] = kernels
    return stats


def _execution_device_ids(compiled) -> list[int]:
    """Ids of the devices ``compiled`` runs on, in assignment order —
    read off its argument/result shardings."""
    import jax

    shardings = jax.tree_util.tree_leaves(
        (compiled.input_shardings, compiled.output_shardings)
    )
    if not shardings:
        return []
    return [d.id for d in shardings[0]._device_assignment]


def serialize_executable_blob(compiled, inputs: dict,
                              stats: dict | None = None) -> bytes:
    """Envelope a compiled (AOT) executable: magic + crc32 + pickle of
    the serialize_executable triple, the devices it was compiled for,
    the fingerprint inputs, and post-compile ``stats``
    (``executable_stats``; None = compute)."""
    from jax.experimental.serialize_executable import serialize

    payload, in_tree, out_tree = serialize(compiled)
    body = pickle.dumps({
        "exe": payload,
        "in_tree": in_tree,
        "out_tree": out_tree,
        "devices": _execution_device_ids(compiled),
        "inputs": inputs,
        "stats": executable_stats(compiled) if stats is None else stats,
        "created": time.time(),
    })
    crc = zlib.crc32(body) & 0xFFFFFFFF
    return _ENVELOPE_MAGIC + crc.to_bytes(4, "big") + body


def _parse_blob(blob: bytes) -> dict | None:
    """CRC-checked envelope record, or None on any damage."""
    try:
        if not blob.startswith(_ENVELOPE_MAGIC):
            return None
        off = len(_ENVELOPE_MAGIC)
        crc = int.from_bytes(blob[off:off + 4], "big")
        body = blob[off + 4:]
        if (zlib.crc32(body) & 0xFFFFFFFF) != crc:
            logger.warning("compile-cache artifact failed CRC; ignoring")
            return None
        record = pickle.loads(body)
        return record if isinstance(record, dict) else None
    except Exception as e:  # noqa: BLE001 - any damage is a miss
        logger.warning("compile-cache artifact unusable: %s", e)
        return None


def blob_stats(blob: bytes) -> dict:
    """The cached post-compile stats of an envelope ({} on damage or
    pre-stats blobs) — read WITHOUT deserializing the executable."""
    record = _parse_blob(blob)
    stats = (record or {}).get("stats")
    return dict(stats) if isinstance(stats, dict) else {}


def load_executable_blob(blob: bytes, expect_inputs: dict | None = None):
    """Deserialize an envelope back into a callable executable, loaded
    onto the devices it was compiled for (left to itself,
    ``deserialize_and_load`` takes every device of the backend, and a
    one-device program then cannot be called in a process that sees
    more). Returns None (a miss) on any damage, fingerprint-input
    mismatch, or a device this process does not have."""
    import jax
    from jax.experimental.serialize_executable import deserialize_and_load

    try:
        record = _parse_blob(blob)
        if record is None:
            return None
        if expect_inputs is not None and record.get("inputs") != \
                expect_inputs:
            # digest collision or stale writer: same key, different
            # program inputs — must read as a miss, never a wrong load
            logger.warning("compile-cache fingerprint mismatch; ignoring")
            return None
        by_id = {d.id: d for d in jax.devices()}
        devices = [by_id[i] for i in record.get("devices") or ()]
        return deserialize_and_load(
            record["exe"], record["in_tree"], record["out_tree"],
            execution_devices=devices or None,
        )
    except Exception as e:  # noqa: BLE001 - any damage is a miss
        logger.warning("compile-cache artifact unusable: %s", e)
        return None


# ----------------------------------------------------------------- client


def cache_root() -> str:
    """The ONE compile-cache directory (XLA's persistent cache at its
    top, the serialized AOT executables under ``aot/``), shared by every
    incarnation, the parked standby and the serving replicas on this
    host. ``JAX_COMPILATION_CACHE_DIR`` places it from outside; unset,
    it is a fixed git-ignored directory in the checkout — the path is
    part of XLA's cache key, so it never moves with a job name, a pid
    or a temp dir. Keys carry the model, strategy, topology and the
    package's code digest, so jobs (and versions of this code) that
    share it cannot cross-hit."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))),
        ".compile_cache",
    )


def default_local_dir() -> str:
    """Where the AOT artifact layer lives: ``<cache_root>/aot``."""
    return os.path.join(cache_root(), "aot")


class CompileCacheClient:
    """Two-layer artifact cache: node-local directory in front of the
    master store. ``master_client=None`` (standalone notebooks, tests)
    degrades to the local layer only."""

    def __init__(self, local_dir: str | None = None, master_client=None,
                 max_local_files: int = 32):
        self.local_dir = local_dir or default_local_dir()
        self.max_local_files = max_local_files
        self._master = master_client
        if self._master is None and os.environ.get(EnvKey.MASTER_ADDR):
            from dlrover_tpu.agent.master_client import MasterClient

            try:
                self._master = MasterClient.singleton()
            except RuntimeError:
                self._master = None

    def _path(self, key: str) -> str:
        return os.path.join(self.local_dir, key.replace("/", "_") + ".aot")

    def get(self, key: str) -> tuple[bytes, str] | None:
        """(blob, layer) or None. A local hit also refreshes mtime so
        LRU pruning keeps live topologies resident."""
        path = self._path(key)
        try:
            with open(path, "rb") as f:
                blob = f.read()
            os.utime(path, None)
            cache_hits_total.labels("local").inc()
            return blob, "local"
        except OSError:
            cache_misses_total.labels("local").inc()
        if self._master is not None:
            try:
                got = self._master.compile_cache_get(key)
            except (ConnectionError, RuntimeError, OSError) as e:
                logger.warning("master compile-cache get failed: %s", e)
                got = None
            if got is not None:
                blob = got[0]
                self._write_local(key, blob)
                return blob, "master"
        return None

    def put(self, key: str, blob: bytes, meta: dict | None = None) -> None:
        self._write_local(key, blob)
        cache_puts_total.labels("local").inc()
        if self._master is not None:
            try:
                self._master.compile_cache_put(key, blob, meta or {})
            except (ConnectionError, RuntimeError, OSError) as e:
                logger.warning("master compile-cache put failed: %s", e)

    def _write_local(self, key: str, blob: bytes) -> None:
        try:
            os.makedirs(self.local_dir, exist_ok=True)
            path = self._path(key)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)  # atomic: readers never see torn files
            self._prune()
        except OSError as e:
            logger.warning("compile-cache local write failed: %s", e)

    def _prune(self) -> None:
        try:
            files = [
                os.path.join(self.local_dir, f)
                for f in os.listdir(self.local_dir) if f.endswith(".aot")
            ]
            if len(files) <= self.max_local_files:
                return
            files.sort(key=lambda p: os.path.getmtime(p))
            for p in files[:len(files) - self.max_local_files]:
                os.unlink(p)
        except OSError:
            pass


def launder(tree: Any):
    """Rebuild a pytree of arrays through a jitted copy so every leaf
    owns proper per-device buffers.

    Required before handing a RESTORED state to a cached (deserialized)
    executable that donates its inputs: ``jax.device_put`` on the CPU
    backend may ADOPT an aligned host buffer (and ``device_get`` hands
    out views), so the per-device "copies" of a restored leaf can share
    one host allocation. A deserialized ``Compiled`` skips pjit's input
    re-staging and, with donation, performs its updates in place — each
    device's ``step + 1`` then lands on the SAME buffer and compounds
    (observed: +8 per call on an 8-device mesh, weight corruption when
    the buffers alias the shm arena). A jitted copy is exactly pjit's
    re-staging, paid once per restore instead of silently never.

    States produced by jit programs (``compiled.init``, a previous step
    call) are already properly staged; only host-built trees (snapshot
    restore, ``reshard_state`` output) need this.

    Leaves are grouped by device set before the jitted copy: an MPMD
    state's stages live on disjoint submeshes (``parallel/mpmd.py``)
    and one jitted program cannot span device sets — each group gets
    its own copy program, same re-staging guarantee.
    """
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    groups: dict[tuple, list[int]] = {}
    for i, leaf in enumerate(leaves):
        devs = ()
        if isinstance(leaf, jax.Array):
            devs = tuple(sorted(
                d.id for d in getattr(leaf.sharding, "device_set", ())
            ))
        groups.setdefault(devs, []).append(i)
    copy = jax.jit(lambda xs: [jnp.copy(x) for x in xs])
    out = list(leaves)
    for idxs in groups.values():
        for i, copied in zip(idxs, copy([leaves[i] for i in idxs])):
            out[i] = copied
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------- load-or-compile


@dataclasses.dataclass
class AotStep:
    fn: Callable            # the executable, original pytree signature
    cache_hit: bool
    source: str             # "local" | "master" | "compiled" | "disabled"
    seconds: float          # load (hit) or compile+publish (miss) time
    key: str = ""
    # compiled-program FLOPs per call (XLA cost analysis) — computed at
    # compile time and cached in the envelope, so warm loads feed the
    # live MFU gauge without re-lowering; 0.0 when unknown
    flops: float = 0.0
    # Pallas kernels in the program (``executable_stats``), cached the
    # same way
    pallas_calls: int = 0


def load_or_compile(
    key: str,
    inputs: dict,
    compile_fn: Callable[[], Any],
    cache: CompileCacheClient | None = None,
) -> AotStep:
    """The elastic-recovery compile path: serve the executable from the
    cache when this (topology, model, strategy, shapes) was compiled by
    ANY earlier incarnation — promoted standby, pre-failure fallback
    precompile, another node — else compile via ``compile_fn`` (which
    must return an AOT-compiled executable, i.e. ``jit(...).lower(
    *abstract).compile()``) and publish the result."""
    start = time.monotonic()
    if not aot_cache_enabled():
        compiled = compile_fn()
        stats = executable_stats(compiled)
        return AotStep(fn=compiled, cache_hit=False, source="disabled",
                       seconds=time.monotonic() - start, key=key,
                       flops=stats.get("flops", 0.0),
                       pallas_calls=stats.get("pallas_calls", 0))
    cache = cache or CompileCacheClient()
    got = cache.get(key)
    if got is not None:
        loaded = load_executable_blob(got[0], expect_inputs=inputs)
        if loaded is not None:
            dur = time.monotonic() - start
            stats = blob_stats(got[0])
            get_journal().emit("compile_cache", dur=dur, hit=True,
                               layer=got[1], key=key,
                               remote_parent=spawn_ctx())
            logger.info("compile cache HIT (%s) for %s in %.2fs",
                        got[1], key, dur)
            return AotStep(fn=loaded, cache_hit=True, source=got[1],
                           seconds=dur, key=key,
                           flops=float(stats.get("flops", 0.0) or 0.0),
                           pallas_calls=int(stats.get("pallas_calls", 0)))
    compiled = compile_fn()
    stats = executable_stats(compiled)
    try:
        blob = serialize_executable_blob(compiled, inputs, stats=stats)
        cache.put(key, blob, meta={"inputs": inputs, "bytes": len(blob),
                                   "stats": stats})
    except Exception as e:  # noqa: BLE001 - publishing is best-effort
        logger.warning("compile-cache publish failed: %s", e)
    dur = time.monotonic() - start
    get_journal().emit("compile_cache", dur=dur, hit=False,
                       layer="none", key=key, remote_parent=spawn_ctx())
    logger.info("compile cache MISS for %s; compiled+published in %.2fs",
                key, dur)
    return AotStep(fn=compiled, cache_hit=False, source="compiled",
                   seconds=dur, key=key,
                   flops=float(stats.get("flops", 0.0) or 0.0),
                   pallas_calls=int(stats.get("pallas_calls", 0)))


# --------------------------------------------------- fallback pre-compiler


class FallbackPrecompiler:
    """Ahead-of-time compilation of the N−1/N+1 fallback topologies.

    After each successful rendezvous the trainer starts this daemon; it
    walks the candidate world sizes, asks ``build_fn(n_nodes)`` for
    ``(key, inputs, compile_fn)`` (None = that world is infeasible —
    indivisible mesh, no spare devices), compiles off the training path
    (XLA compilation is host-side work; like ``parallel/dry_run.py`` it
    needs no exclusive accelerator access), and publishes the artifact.
    When a node later dies, the surviving incarnation's
    ``load_or_compile`` for the N−1 world is a cache hit and recovery
    skips the cold compile entirely.

    ``budget_s`` bounds total background compile time; already-cached
    topologies are skipped so re-arming after every rendezvous is
    cheap.
    """

    def __init__(
        self,
        build_fn: Callable[[int], tuple[str, dict, Callable] | None],
        world_sizes: Sequence[int],
        cache: CompileCacheClient | None = None,
        budget_s: float = 600.0,
        delay_s: float = 1.0,
    ):
        self.build_fn = build_fn
        self.world_sizes = [n for n in world_sizes if n >= 1]
        self.cache = cache or CompileCacheClient()
        self.budget_s = budget_s
        self.delay_s = delay_s
        self.results: dict[int, str] = {}  # n_nodes -> outcome
        self._done = threading.Event()
        self._thread: threading.Thread | None = None

    def start(self) -> "FallbackPrecompiler":
        self._thread = threading.Thread(
            target=self._run, name="aot-fallback", daemon=True
        )
        self._thread.start()
        return self

    def wait(self, timeout: float = 600.0) -> bool:
        return self._done.wait(timeout)

    def _run(self) -> None:
        # let the live incarnation's own first compile win the host's
        # compile threads before background work starts
        time.sleep(self.delay_s)
        deadline = time.monotonic() + self.budget_s
        try:
            for n in self.world_sizes:
                if time.monotonic() > deadline:
                    self.results[n] = "budget_exhausted"
                    continue
                t0 = time.monotonic()
                try:
                    built = self.build_fn(n)
                    if built is None:
                        self.results[n] = "infeasible"
                        continue
                    key, inputs, compile_fn = built
                    if self.cache.get(key) is not None:
                        self.results[n] = "already_cached"
                        continue
                    compiled = compile_fn()
                    blob = serialize_executable_blob(compiled, inputs)
                    self.cache.put(key, blob,
                                   meta={"inputs": inputs,
                                         "bytes": len(blob)})
                    self.results[n] = "published"
                    get_journal().emit(
                        "aot_fallback", dur=time.monotonic() - t0,
                        nodes=n, key=key, ok=True,
                    )
                    logger.info(
                        "fallback topology %d nodes pre-compiled and "
                        "published in %.1fs (%s)", n,
                        time.monotonic() - t0, key,
                    )
                except Exception as e:  # noqa: BLE001 - a failed fallback
                    # compile must never touch the live incarnation
                    self.results[n] = f"error: {e}"
                    get_journal().emit(
                        "aot_fallback", dur=time.monotonic() - t0,
                        nodes=n, ok=False,
                    )
                    logger.warning(
                        "fallback precompile for %d nodes failed: %s", n, e
                    )
        finally:
            self._done.set()
