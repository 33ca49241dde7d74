"""The one registry of every ``DLROVER_TPU_*`` environment variable.

Before this module existed, the env surface was 100+ scattered
``os.environ`` reads: some through ``EnvKey`` constants, some raw string
literals, with defaults duplicated (and drifting) at call sites and no
record of which vars are safe to flip on a live job versus baked in at
process start. ``native/analyze`` rule ``env-registry`` (DESIGN.md §19)
now machine-enforces the contract this module declares:

- every ``EnvKey`` constant has exactly one ``EnvVar`` entry here (and
  vice versa), so a var cannot be added without declaring its default,
  restart semantics and DESIGN.md anchor;
- ``DLROVER_TPU_*`` string literals may appear ONLY in
  ``common/constants.py`` and this file — call sites go through
  ``EnvKey``/the helpers below, so the name is always greppable from
  the registry;
- a module-level (import-time) env read is only legal for vars declared
  ``restart_required=True`` — an import-time read of a "live-tunable"
  var would silently freeze it per process;
- every registered var appears verbatim in DESIGN.md (the generated
  reference table, ``python -m native.analyze --env-table``), mirroring
  the metric-name documentation contract.

Helpers read ``os.environ`` live (monkeypatch/test friendly) and apply
the registered default; ``restart_required`` is metadata enforcement,
not runtime caching.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

from dlrover_tpu.common.constants import EnvKey


@dataclasses.dataclass(frozen=True)
class EnvVar:
    """Declaration of one environment variable.

    ``restart_required=True`` means the value is bound at process start
    (import-time read, process identity, logger/backend configuration)
    — changing it on a live job has no effect until the next
    incarnation. ``anchor`` names the DESIGN.md section that explains
    the subsystem the var belongs to.
    """

    name: str
    default: Optional[str]
    help: str
    anchor: str
    restart_required: bool = False


# NOTE for the reader adding a var: the name literal must ALSO exist as
# an EnvKey constant (the analyzer enforces the bijection), and the
# generated table in DESIGN.md §19 must be refreshed via
# ``python -m native.analyze --env-table``.
SPECS: tuple[EnvVar, ...] = (
    # ------------------------------------------------- identity / placement
    EnvVar("DLROVER_TPU_JOB_NAME", None,
           "job name; keys shared caches and shm namespaces", "§1",
           restart_required=True),
    EnvVar("DLROVER_TPU_MASTER_ADDR", None,
           "master RPC endpoint host:port (MasterClient singleton binds "
           "at first use)", "§1", restart_required=True),
    EnvVar("DLROVER_TPU_NODE_ID", "0",
           "this node's stable id, assigned by the launcher", "§1",
           restart_required=True),
    EnvVar("DLROVER_TPU_NODE_RANK", "0",
           "rank within the current rendezvous round", "§1",
           restart_required=True),
    EnvVar("DLROVER_TPU_NODE_NUM", "1",
           "world size of the current rendezvous round", "§1",
           restart_required=True),
    EnvVar("DLROVER_TPU_COORDINATOR", None,
           "jax.distributed coordinator address for this round", "§2",
           restart_required=True),
    EnvVar("DLROVER_TPU_RESTART_COUNT", "0",
           "incarnation counter the agent bumps per respawn", "§6",
           restart_required=True),
    EnvVar("DLROVER_TPU_ACCELERATOR", None,
           "accelerator kind hint set by the launcher", "§2",
           restart_required=True),
    EnvVar("DLROVER_TPU_DEVICE_COUNT", None,
           "override visible device count (virtual meshes, tests)", "§2",
           restart_required=True),
    EnvVar("DLROVER_TPU_INIT_TIMEOUT", None,
           "jax.distributed.initialize join timeout (s); launcher "
           "scales with node count", "§2", restart_required=True),
    EnvVar("DLROVER_TPU_GLOBAL_RANK", None,
           "probe child's rank in a network-check subgroup", "§6",
           restart_required=True),
    EnvVar("DLROVER_TPU_PROBE_TIMEOUT", "300",
           "network-check probe budget in seconds (read at module "
           "import)", "§6", restart_required=True),
    EnvVar("DLROVER_TPU_MOCK_ERR_RANK", None,
           "test hook: rank that raises a mock training error", "§15",
           restart_required=True),
    # ------------------------------------------------------- config handoff
    EnvVar("DLROVER_TPU_PARAL_CONFIG", None,
           "path of the agent-mirrored paral-config file the trainer "
           "hot-reloads", "§6", restart_required=True),
    EnvVar("DLROVER_TPU_IPC_DIR", None,
           "directory for cross-process handshake files (standby "
           "payloads, config mirror, chaos legs); default tempdir",
           "§16", restart_required=True),
    EnvVar("DLROVER_TPU_SHM_PREFIX", "dlrover_tpu",
           "POSIX shm name prefix (read once at import: every shm name "
           "derives from it)", "§11", restart_required=True),
    # ----------------------------------------------------------- checkpoint
    EnvVar("DLROVER_TPU_SNAPSHOT_INTERVAL", None,
           "'auto' arms the master's Young-Daly cadence tuner; other "
           "values keep the trainer CLI cadence", "§16"),
    EnvVar("DLROVER_TPU_SNAPSHOT_FULL_EVERY", "10",
           "every Kth metrics-snapshot push is full; pushes between "
           "suppress unchanged families (0/1 = always full)", "§22"),
    EnvVar("DLROVER_TPU_BUDDY", "1",
           "'0' disables buddy replication of shm snapshots", "§16"),
    EnvVar("DLROVER_TPU_BUDDY_INTERVAL", "2.0",
           "seconds between buddy snapshot pushes", "§16"),
    EnvVar("DLROVER_TPU_BUDDY_MAX_BYTES", str(64 << 30),
           "upper bound on one pushed buddy snapshot", "§16"),
    EnvVar("DLROVER_TPU_CKPT_PERSIST_REPLICAS", "1",
           "DP replica copies of each shard persisted to storage; 2 "
           "enables per-shard twin rollback at restore", "§20"),
    EnvVar("DLROVER_TPU_CKPT_PERSIST_WORKERS", "4",
           "concurrent chunk writers per host in the parallel persist "
           "path", "§20"),
    EnvVar("DLROVER_TPU_CKPT_PERSIST_CHUNK_MB", "64",
           "chunk size (MB) of the chunked concurrent storage writes",
           "§20"),
    # -------------------------------------------------------- warm recovery
    EnvVar("DLROVER_TPU_STANDBY", "1",
           "'0' disables the pre-spawned standby trainer", "§16"),
    EnvVar("DLROVER_TPU_STANDBY_FILE", None,
           "internal: promotion-payload path the agent hands a parked "
           "standby child", "§16", restart_required=True),
    EnvVar("DLROVER_TPU_PREEMPTION_FILE", None,
           "preemption notice file path ({node_id} substituted); "
           "fires save-before-kill when it appears", "§16"),
    EnvVar("DLROVER_TPU_PREEMPTION_URL", None,
           "preemption notice poll URL (GCE maintenance-event "
           "convention)", "§16"),
    # -------------------------------------------------------- compile cache
    EnvVar("DLROVER_TPU_AOT_CACHE", "1",
           "'0' disables the serialized-AOT-executable cache", "§17"),
    EnvVar("DLROVER_TPU_FALLBACK_AOT", None,
           "force the fallback-topology precompiler on/off (default: "
           "on when multi-node)", "§17"),
    # ------------------------------------------------------------ telemetry
    EnvVar("DLROVER_TPU_METRICS_PORT", None,
           "Prometheus exposition port (unset = exposition off)", "§12",
           restart_required=True),
    EnvVar("DLROVER_TPU_JOURNAL_DIR", None,
           "event-journal directory (unset = no journal)", "§12"),
    EnvVar("DLROVER_TPU_JOURNAL_MAX_MB", None,
           "journal size cap in MB before atomic rotation to .1", "§14"),
    EnvVar("DLROVER_TPU_TRACE_ID", None,
           "job-wide trace id minted by the master; adopted via the "
           "rendezvous payload", "§12"),
    EnvVar("DLROVER_TPU_TRACE_SAMPLE", "1.0",
           "head-sampling rate [0,1] for per-request serving traces; "
           "incidents and control-plane traces are always sampled",
           "§27"),
    EnvVar("DLROVER_TPU_TRACE_SEED", None,
           "makes span ids deterministic (per-name counter streams) "
           "so seeded chaos/fleetsim runs produce byte-identical trace "
           "trees; unset = random ids", "§27"),
    EnvVar("DLROVER_TPU_SPAN_NS", None,
           "internal: span-id namespace disambiguating co-located "
           "processes (e.g. the standalone master) in the TRACE_SEED "
           "deterministic id stream", "§27",
           restart_required=True),
    EnvVar("DLROVER_TPU_SPAN_CTX", None,
           "internal: spawn-time span context (trace:span) the agent "
           "hands its children so recovery spans attach under the "
           "incident that respawned them", "§27",
           restart_required=True),
    EnvVar("DLROVER_TPU_LOG_JSON", None,
           "'1' switches process logs to JSON lines", "§12",
           restart_required=True),
    EnvVar("DLROVER_TPU_LOG_LEVEL", "INFO",
           "root log level for framework loggers", "§12",
           restart_required=True),
    EnvVar("DLROVER_TPU_BUNDLE_DIR", None,
           "flight-recorder bundle root (default <journal dir>/bundles)",
           "§14"),
    EnvVar("DLROVER_TPU_BUNDLES", "1",
           "'0' disables automatic debug bundles on hang/crash", "§14"),
    EnvVar("DLROVER_TPU_EFFICIENCY_JOURNAL_EVERY", "25",
           "steps between metrics_sample journal points "
           "(0 disables)", "§18"),
    # ---------------------------------------------------------------- chaos
    EnvVar("DLROVER_TPU_CHAOS", None,
           "JSON fault plan (path or inline); read ONCE at chaos "
           "package import", "§15", restart_required=True),
    # ------------------------------------------------------------ autopilot
    EnvVar("DLROVER_TPU_DEVICE_HBM_BYTES", None,
           "stated per-device memory envelope in bytes for backends "
           "whose runtime reports none (CPU); the planner's "
           "AOT feasibility filter uses it", "§24"),
    EnvVar("DLROVER_TPU_AUTOPILOT_MAX_RETUNES", "2",
           "per-job bound on closed-loop autopilot retunes; 0 keeps "
           "the controller observe-only", "§24"),
    # ----------------------------------------------------- embedding fabric
    EnvVar("DLROVER_TPU_EMBEDDING_MAX_STALENESS", "8",
           "async-apply staleness bound in steps (lookup version minus "
           "applied version); the training step back-pressures past it",
           "§25"),
    EnvVar("DLROVER_TPU_EMBEDDING_REPLICAS", "1",
           "copies of each embedding shard block persisted per "
           "checkpoint; 2 adds the ring-successor twin that per-shard "
           "rollback restores from", "§25"),
    EnvVar("DLROVER_TPU_EMBEDDING_FLUSH_MS", "5",
           "embedding gradient flusher idle poll interval (ms)", "§25"),
    EnvVar("DLROVER_TPU_EMBEDDING_QUEUE", "64",
           "bounded embedding send-queue depth in apply batches; a "
           "full queue blocks apply() like the staleness bound", "§25"),
    # ------------------------------------------------- master crash-failover
    EnvVar("DLROVER_TPU_MASTER_STATE_DIR", None,
           "directory for the master's full-state snapshot (v2: ack "
           "ledger, rendezvous, autopilot, compile-cache spill); unset "
           "= snapshots off, a master crash loses control-plane state",
           "§26"),
    EnvVar("DLROVER_TPU_MASTER_PORT_FILE", None,
           "atomic port file agents re-resolve the master address "
           "from after a master restart (the standalone launcher "
           "exports it automatically)", "§26"),
    EnvVar("DLROVER_TPU_REDELIVERY_QUEUE", "64",
           "bound on the agent-side redelivery queue of unacked "
           "PersistAckReport/FailureReport messages replayed on "
           "reconnect (oldest dropped past the bound)", "§26"),
    EnvVar("DLROVER_TPU_DEGRADED_WARN_S", "30",
           "seconds between repeated 'master unreachable' warnings "
           "while an agent link is degraded (the outage itself is one "
           "journal instant + a counter, not log spam)", "§26"),
    # --------------------------------------------- hierarchical control plane
    EnvVar("DLROVER_TPU_RACK_ID", None,
           "rack this agent belongs to; the launcher points the agent "
           "at that rack's sub-master instead of the root (unset = "
           "flat topology, dial the root directly)", "§28",
           restart_required=True),
    EnvVar("DLROVER_TPU_RACK_PORT_FILE", None,
           "the rack sub-master's own atomic port file: agents "
           "re-resolve a restarted sub-master from it (target-keyed "
           "twin of DLROVER_TPU_MASTER_PORT_FILE; a stale/missing file "
           "degrades the agent to the root)", "§28"),
    EnvVar("DLROVER_TPU_RACK_CACHE_MB", "256",
           "byte bound (MB) on the sub-master's rack-local "
           "compile-cache LRU mirror; misses fall through to the root",
           "§28"),
    EnvVar("DLROVER_TPU_RACK_FLUSH_S", "1.0",
           "seconds between a sub-master's merged upstream pushes "
           "(aggregated heartbeats, metrics deltas, persist-acks go up "
           "as one batch per tick)", "§28"),
    EnvVar("DLROVER_TPU_RACK_WORLD_CHUNK", "512",
           "max comm-world members per RackWorldResponse: bigger "
           "worlds stream as cursor-chunked pulls so no single root "
           "RPC is O(world) (the §28 bounded-RPC rule)", "§28"),
    EnvVar("DLROVER_TPU_RACK_MERGE_MAX", "2",
           "max metrics snapshots per merged upstream push; a burst "
           "drains as several bounded pushes in one flush tick so the "
           "root's per-RPC handler time stays flat", "§28"),
    # ------------------------------------------------ partition tolerance
    EnvVar("DLROVER_TPU_RACK_LEASE_S", "10",
           "rack sub-master lease: every accepted merge tick renews "
           "it; a sub-master past its lease fails closed (serves no "
           "comm world, redirects agents to the root) and the root "
           "expires the rack from its registered census", "§30"),
    EnvVar("DLROVER_TPU_RACK_RETRY_S", "5",
           "seconds (jittered ±20%) between an agent's re-probes of "
           "its rack port file while pinned to the direct-to-root "
           "fallback; between probes the re-dial sticks to the last "
           "working target instead of flapping", "§30"),
    EnvVar("DLROVER_TPU_LINK_STALE_S", "60",
           "degraded-mode staleness bound: after this long without "
           "master contact a MasterLink reports stale and consumers "
           "(gateway scale mirror, agent config mirror) stop acting "
           "on mirrored config until the link recovers", "§30"),
    # ------------------------------------------- serving memory observatory
    EnvVar("DLROVER_TPU_SERVING_OBSERVATORY", "1",
           "measure-only serving observatory (KV page pressure, "
           "prefix-share headroom, draft-acceptance shadowing); 0 "
           "disables all three instruments on engines built after the "
           "flip", "§29"),
    EnvVar("DLROVER_TPU_OBSERVATORY_SAMPLE_EVERY", "32",
           "decode steps between kv_pool journal samples / gauge "
           "refreshes", "§29"),
    # ------------------------------------------------- serving raw speed
    EnvVar("DLROVER_TPU_KV_COW", "1",
           "copy-on-write KV page sharing: admission dedups full "
           "prefix pages against resident matching chain digests and "
           "capacity counts unique pages; 0 reverts to private pages",
           "§31"),
    EnvVar("DLROVER_TPU_SPEC_DEPTH", "0",
           "max speculative self-draft depth k: the n-gram drafter "
           "proposes up to k tokens verified in one wide forward; 0 "
           "disables speculation (plain decode)", "§31"),
)

SPEC_BY_NAME: dict[str, EnvVar] = {spec.name: spec for spec in SPECS}


def _check_bijection() -> None:
    """Fail the import when EnvKey and the registry drift — the same
    contract rule ``env-registry`` enforces statically, kept dynamic
    too so a drifted tree cannot even start."""
    keys = {
        value for attr, value in vars(EnvKey).items()
        if not attr.startswith("_") and isinstance(value, str)
    }
    registered = set(SPEC_BY_NAME)
    missing = keys - registered
    unknown = registered - keys
    if missing or unknown:
        raise RuntimeError(
            "envspec drift: EnvKey constants without a registry entry "
            f"{sorted(missing)}; registry entries without an EnvKey "
            f"constant {sorted(unknown)}"
        )


_check_bijection()


def spec(name: str) -> EnvVar:
    return SPEC_BY_NAME[name]


def get(name: str, default: Optional[str] = None) -> Optional[str]:
    """Live read with the registered default ( ``default`` overrides
    it for call sites that need a contextual fallback)."""
    fallback = default if default is not None \
        else SPEC_BY_NAME[name].default
    value = os.environ.get(name)
    return value if value not in (None, "") else fallback


def get_bool(name: str) -> bool:
    """The framework's switch convention: anything but '0' is on (so
    defaults can be on without the launcher exporting anything)."""
    return get(name) != "0"


def get_int(name: str, default: Optional[int] = None) -> Optional[int]:
    raw = get(name, None if default is None else str(default))
    if raw is None:
        return None
    try:
        return int(float(raw))
    except ValueError:
        return default if default is not None else int(
            SPEC_BY_NAME[name].default or 0
        )


def get_float(name: str, default: Optional[float] = None
              ) -> Optional[float]:
    raw = get(name, None if default is None else str(default))
    if raw is None:
        return None
    try:
        return float(raw)
    except ValueError:
        return default if default is not None else float(
            SPEC_BY_NAME[name].default or 0
        )


def markdown_table() -> str:
    """The DESIGN.md §19 reference table — generated, never hand-edited
    (rule ``env-registry`` fails when a registered var is missing from
    DESIGN.md, mirroring the metric-name contract)."""
    lines = [
        "| variable | default | restart req. | anchor | purpose |",
        "|---|---|---|---|---|",
    ]
    for s in SPECS:
        default = "—" if s.default is None else f"`{s.default}`"
        restart = "yes" if s.restart_required else "no"
        lines.append(
            f"| `{s.name}` | {default} | {restart} | {s.anchor} | "
            f"{s.help} |"
        )
    return "\n".join(lines)
