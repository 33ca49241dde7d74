"""Framework-wide constants.

Mirrors the capability surface of the reference constants module
(dlrover/python/common/constants.py) with TPU-native vocabulary: node types
are TPU hosts rather than PS/worker pods, accelerators are TPU chips, and the
distribution strategies are mesh-axis based rather than PS/AllReduce based.
"""

from __future__ import annotations

import enum
import os


class PlatformType(str, enum.Enum):
    LOCAL = "local"
    KUBERNETES = "k8s"
    RAY = "ray"


class NodeType(str, enum.Enum):
    MASTER = "master"
    HOST = "host"  # a TPU host VM (runs one agent + one training process)
    CPU_WORKER = "cpu_worker"  # auxiliary CPU pod (data preprocessing)


class NodeStatus(str, enum.Enum):
    INITIAL = "initial"
    PENDING = "pending"
    RUNNING = "running"
    SUCCEEDED = "succeeded"
    FAILED = "failed"
    DELETED = "deleted"
    UNKNOWN = "unknown"

    @classmethod
    def terminal(cls) -> set["NodeStatus"]:
        return {cls.SUCCEEDED, cls.FAILED, cls.DELETED}


class NodeEventType(str, enum.Enum):
    ADDED = "added"
    MODIFIED = "modified"
    DELETED = "deleted"


class NodeExitReason(str, enum.Enum):
    SUCCEEDED = "succeeded"
    KILLED = "killed"
    OOM = "oom"
    FATAL_ERROR = "fatal_error"
    HARDWARE_ERROR = "hardware_error"
    PREEMPTED = "preempted"
    UNKNOWN = "unknown"


class JobExitReason(str, enum.Enum):
    SUCCEEDED = "succeeded"
    NODE_OOM = "node_oom"
    NODE_ERROR = "node_error"
    RDZV_TIMEOUT = "rdzv_timeout"
    HANG_ERROR = "hang_error"
    UNCOMPLETED_TIMEOUT = "uncompleted_timeout"
    EARLY_STOP = "early_stop"
    UNKNOWN = "unknown"


class RendezvousName(str, enum.Enum):
    TRAINING = "training"
    NETWORK_CHECK = "network-check"


class TaskType(str, enum.Enum):
    TRAINING = "training"
    EVALUATION = "evaluation"
    PREDICTION = "prediction"


class CheckpointStorageType(str, enum.Enum):
    MEMORY = "memory"
    DISK = "disk"


class ParallelAxis(str, enum.Enum):
    """Named mesh axes for the parallel layer.

    The reference builds torch process groups per named dim
    (atorch/atorch/distributed/distributed.py:321 create_parallel_group);
    here axes are dims of one ``jax.sharding.Mesh``.
    """

    DATA = "data"
    FSDP = "fsdp"
    TENSOR = "tensor"
    SEQUENCE = "sequence"
    EXPERT = "expert"
    PIPELINE = "pipeline"


class TrainingExceptionLevel(str, enum.Enum):
    PROCESS_ERROR = "process_error"
    NODE_ERROR = "node_error"
    RDZV_ERROR = "rdzv_error"
    WARNING = "warning"
    INFO = "info"


# Agent <-> training-process environment variable contract.
class EnvKey:
    JOB_NAME = "DLROVER_TPU_JOB_NAME"
    MASTER_ADDR = "DLROVER_TPU_MASTER_ADDR"
    NODE_ID = "DLROVER_TPU_NODE_ID"
    NODE_RANK = "DLROVER_TPU_NODE_RANK"
    NODE_NUM = "DLROVER_TPU_NODE_NUM"
    COORDINATOR = "DLROVER_TPU_COORDINATOR"
    RESTART_COUNT = "DLROVER_TPU_RESTART_COUNT"
    PARAL_CONFIG_PATH = "DLROVER_TPU_PARAL_CONFIG"
    MOCK_ERR_RANK = "DLROVER_TPU_MOCK_ERR_RANK"
    DEVICE_COUNT_OVERRIDE = "DLROVER_TPU_DEVICE_COUNT"
    # coordination-service join timeout (seconds) for
    # jax.distributed.initialize — the launcher scales it with the node
    # count (reference analog: auto_configure_params' comm timeouts,
    # dlrover/python/elastic_agent/torch/training.py:143)
    INIT_TIMEOUT = "DLROVER_TPU_INIT_TIMEOUT"
    ACCELERATOR = "DLROVER_TPU_ACCELERATOR"
    # telemetry (dlrover_tpu/telemetry/): exposition port (unset = fully
    # off), event-journal directory (unset = no journal), the job trace
    # id the master mints, and JSON log format
    METRICS_PORT = "DLROVER_TPU_METRICS_PORT"
    JOURNAL_DIR = "DLROVER_TPU_JOURNAL_DIR"
    TRACE_ID = "DLROVER_TPU_TRACE_ID"
    LOG_JSON = "DLROVER_TPU_LOG_JSON"
    # causal trace fabric (DESIGN.md §27): head-sampling rate for
    # per-request serving traces (incidents/control-plane are always
    # sampled), the seed that makes span ids deterministic under the
    # chaos/fleetsim replay discipline, and the spawn-time span context
    # an agent hands its children so trainer-side recovery spans attach
    # under the incident that respawned them
    TRACE_SAMPLE = "DLROVER_TPU_TRACE_SAMPLE"
    TRACE_SEED = "DLROVER_TPU_TRACE_SEED"
    SPAN_CTX = "DLROVER_TPU_SPAN_CTX"
    # span-id namespace: disambiguates co-located processes that would
    # otherwise share a deterministic id stream under TRACE_SEED (the
    # standalone master and the agent both run with no NODE_ID)
    SPAN_NS = "DLROVER_TPU_SPAN_NS"
    # flight recorder (telemetry/bundle.py, telemetry/journal.py): where
    # crash/hang debug bundles land (default <journal dir>/bundles), the
    # journal size cap in MB (0/unset = unbounded), and the "1"-default
    # switch for automatic bundles on hang/crash verdicts
    BUNDLE_DIR = "DLROVER_TPU_BUNDLE_DIR"
    JOURNAL_MAX_MB = "DLROVER_TPU_JOURNAL_MAX_MB"
    BUNDLES = "DLROVER_TPU_BUNDLES"
    # chaos harness (dlrover_tpu/chaos/): a JSON fault plan (file path
    # or inline JSON). Unset = injection compiled out to one boolean
    # check at every point (read once, at chaos package import).
    CHAOS = "DLROVER_TPU_CHAOS"
    # warm recovery (agent/standby.py): "0" disables the pre-spawned
    # standby trainer the agent promotes on worker death; STANDBY_FILE
    # is the internal handshake path the agent hands a standby child
    STANDBY = "DLROVER_TPU_STANDBY"
    STANDBY_FILE = "DLROVER_TPU_STANDBY_FILE"
    # "auto" lets the master's Young-Daly tuner
    # (checkpoint/interval_tuner.py) drive the shm snapshot cadence via
    # the paral-config push; unset/other keeps the trainer's CLI value
    SNAPSHOT_INTERVAL = "DLROVER_TPU_SNAPSHOT_INTERVAL"
    # delta-compressed metrics-snapshot pushes
    # (telemetry/snapshot_delta.py): every Kth push is a full snapshot,
    # the ones between suppress unchanged families; 0/1 = always full
    SNAPSHOT_FULL_EVERY = "DLROVER_TPU_SNAPSHOT_FULL_EVERY"
    # directory for cross-process handshake files (standby promotion
    # payloads, paral-config mirror, chaos scenario legs); default
    # tempdir — co-hosted jobs override to avoid collisions
    IPC_DIR = "DLROVER_TPU_IPC_DIR"
    SHM_PREFIX = "DLROVER_TPU_SHM_PREFIX"
    # serialized-AOT-executable cache ("0" disables; DESIGN.md §17) and
    # the example's force-switch for the fallback-topology precompiler
    AOT_CACHE = "DLROVER_TPU_AOT_CACHE"
    FALLBACK_AOT = "DLROVER_TPU_FALLBACK_AOT"
    # efficiency observatory (DESIGN.md §18): the journal cadence of
    # metrics_sample points
    EFFICIENCY_JOURNAL_EVERY = "DLROVER_TPU_EFFICIENCY_JOURNAL_EVERY"
    # buddy-replication of shm snapshots (checkpoint/buddy.py): "0"
    # disables, interval between pushes, per-push byte cap
    BUDDY = "DLROVER_TPU_BUDDY"
    BUDDY_INTERVAL = "DLROVER_TPU_BUDDY_INTERVAL"
    BUDDY_MAX_BYTES = "DLROVER_TPU_BUDDY_MAX_BYTES"
    # network-check probe budget (agent/node_check.py, read at import)
    # and the probe child's rank assignment
    PROBE_TIMEOUT = "DLROVER_TPU_PROBE_TIMEOUT"
    GLOBAL_RANK = "DLROVER_TPU_GLOBAL_RANK"
    LOG_LEVEL = "DLROVER_TPU_LOG_LEVEL"
    # preemption/maintenance-notice sources (agent/preemption.py)
    PREEMPTION_FILE = "DLROVER_TPU_PREEMPTION_FILE"
    PREEMPTION_URL = "DLROVER_TPU_PREEMPTION_URL"
    # per-host parallel checkpoint persist (DESIGN.md §20): how many
    # DP replicas of each shard are written to storage (2 enables
    # per-shard twin rollback), the concurrent chunk writers per host,
    # and the chunk size for the chunked object-store writes
    CKPT_PERSIST_REPLICAS = "DLROVER_TPU_CKPT_PERSIST_REPLICAS"
    CKPT_PERSIST_WORKERS = "DLROVER_TPU_CKPT_PERSIST_WORKERS"
    CKPT_PERSIST_CHUNK_MB = "DLROVER_TPU_CKPT_PERSIST_CHUNK_MB"
    # strategy autopilot (DESIGN.md §24): the stated per-device memory
    # envelope for backends whose runtime reports none (CPU —
    # the planner's feasibility filter), and the per-job bound on
    # closed-loop retunes the master-side controller may apply
    DEVICE_HBM_BYTES = "DLROVER_TPU_DEVICE_HBM_BYTES"
    AUTOPILOT_MAX_RETUNES = "DLROVER_TPU_AUTOPILOT_MAX_RETUNES"
    # elastic embedding fabric (DESIGN.md §25): the async-apply
    # staleness bound (steps of un-flushed gradient the trainer may run
    # ahead; back-pressures the step past it), the checkpoint replica
    # count (2 writes each shard block to its ring successor too,
    # enabling per-shard twin rollback at restore), the background
    # flusher's idle poll interval, and the bounded send-queue depth
    EMBEDDING_MAX_STALENESS = "DLROVER_TPU_EMBEDDING_MAX_STALENESS"
    EMBEDDING_REPLICAS = "DLROVER_TPU_EMBEDDING_REPLICAS"
    EMBEDDING_FLUSH_MS = "DLROVER_TPU_EMBEDDING_FLUSH_MS"
    EMBEDDING_QUEUE = "DLROVER_TPU_EMBEDDING_QUEUE"
    # master crash-failover (DESIGN.md §26): where the master persists
    # its full-state snapshot (unset = snapshots off), the atomic port
    # file agents re-resolve a restarted master's address from, the
    # agent-side redelivery queue bound for unacked one-way reports,
    # and the rate limit on "master unreachable" warnings while degraded
    MASTER_STATE_DIR = "DLROVER_TPU_MASTER_STATE_DIR"
    MASTER_PORT_FILE = "DLROVER_TPU_MASTER_PORT_FILE"
    REDELIVERY_QUEUE = "DLROVER_TPU_REDELIVERY_QUEUE"
    DEGRADED_WARN_S = "DLROVER_TPU_DEGRADED_WARN_S"
    # hierarchical control plane (DESIGN.md §28): the rack this agent
    # belongs to (assigns it to a rack sub-master), the sub-master's
    # own atomic port file (target-keyed re-dial, same mechanism as the
    # root's), the byte bound on the rack-local compile-cache mirror,
    # and the sub-master's merged-upstream-push cadence
    RACK_ID = "DLROVER_TPU_RACK_ID"
    RACK_PORT_FILE = "DLROVER_TPU_RACK_PORT_FILE"
    RACK_CACHE_MB = "DLROVER_TPU_RACK_CACHE_MB"
    RACK_FLUSH_S = "DLROVER_TPU_RACK_FLUSH_S"
    RACK_WORLD_CHUNK = "DLROVER_TPU_RACK_WORLD_CHUNK"
    RACK_MERGE_MAX = "DLROVER_TPU_RACK_MERGE_MAX"
    # partition tolerance (DESIGN.md §30): the rack lease the merge
    # tick refreshes (expiry fails the sub-master closed and lets the
    # root expire the rack), the jittered re-probe cadence of a
    # fallback-pinned agent's rack target, and the degraded-mode bound
    # after which mirrored config is too stale to act on
    RACK_LEASE_S = "DLROVER_TPU_RACK_LEASE_S"
    RACK_RETRY_S = "DLROVER_TPU_RACK_RETRY_S"
    LINK_STALE_S = "DLROVER_TPU_LINK_STALE_S"
    # serving memory observatory (DESIGN.md §29): the measure-only
    # off-switch and the kv_pool sample cadence (decode steps)
    SERVING_OBSERVATORY = "DLROVER_TPU_SERVING_OBSERVATORY"
    OBSERVATORY_SAMPLE_EVERY = "DLROVER_TPU_OBSERVATORY_SAMPLE_EVERY"
    # serving raw speed (DESIGN.md §31): copy-on-write page sharing in
    # the paged KV pool, and the max self-drafted speculative-decode
    # verify depth (0 = plain decode)
    KV_COW = "DLROVER_TPU_KV_COW"
    SPEC_DEPTH = "DLROVER_TPU_SPEC_DEPTH"


class Defaults:
    MASTER_PORT = 0  # 0 -> pick a free port
    HEARTBEAT_INTERVAL_S = 15.0
    HEARTBEAT_DEAD_WINDOW_S = 300.0
    RDZV_WAIT_TIMEOUT_S = 600.0
    RDZV_POLL_INTERVAL_S = 0.2
    MONITOR_INTERVAL_S = 1.0
    MAX_RESTARTS = 3
    SPEED_WINDOW_S = 6.0
    RPC_TIMEOUT_S = 30.0
    # overridable so parallel test runs / co-hosted jobs can't collide on
    # POSIX shm names (children inherit the env, so agent+trainer agree).
    # Import-time read by design (envspec marks it restart_required):
    # every shm name derives from it, so it must be frozen per process.
    SHM_PREFIX = os.environ.get(EnvKey.SHM_PREFIX, "dlrover_tpu")
