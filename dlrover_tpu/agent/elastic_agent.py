"""Per-host elastic agent: rendezvous, spawn, monitor, restart.

Reference analog: dlrover/python/elastic_agent/torch/training.py
(ElasticTrainingAgent:349, _invoke_run:547, _membership_changed:676,
launch_agent:695). TPU-native differences:

- one training *process per host* owning all local TPU chips (torch runs one
  per GPU); the agent spawns exactly one child and the JAX runtime fans out
  over local devices.
- a completed rendezvous yields the JAX coordinator address; the child calls
  ``jax.distributed.initialize`` from env instead of joining a TCPStore.
- restart-in-place: on child failure or membership change the agent asks the
  flash-checkpoint saver to persist the latest shm snapshot, then respawns
  the child, which restores from shm in seconds (SURVEY.md §5.4).
"""

from __future__ import annotations

import dataclasses
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
from enum import Enum

from dlrover_tpu import chaos
from dlrover_tpu.common import envspec
from dlrover_tpu.common.accelerator import sniff_accelerator
from dlrover_tpu.common.constants import (
    Defaults,
    EnvKey,
    NodeEventType,
    NodeExitReason,
    NodeStatus,
    TrainingExceptionLevel,
)
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.common.rpc import find_free_port
from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.telemetry.journal import (
    current_ctx,
    get_journal,
    set_trace_id,
)
from dlrover_tpu.telemetry.metrics import registry

logger = get_logger(__name__)

_restarts_total = registry().counter(
    "dlrover_tpu_agent_restarts_total",
    "trainer respawns by kind (failure vs planned)",
    label_names=("kind",),
)
_incarnation_gauge = registry().gauge(
    "dlrover_tpu_agent_incarnation",
    "current trainer incarnation number on this node",
)
_rdzv_wait_seconds = registry().histogram(
    "dlrover_tpu_agent_rdzv_wait_seconds",
    "agent-observed rendezvous wait (join -> completed world)",
)
_reshard_choices = registry().counter(
    "dlrover_tpu_agent_reshard_choice_total",
    "recovery rendezvous outcomes by path: covered=true means the "
    "compile cache already holds an executable for the new topology "
    "and the incarnation takes the reshard-with-fallback path",
    label_names=("covered",),
)


class RunResult(str, Enum):
    SUCCEEDED = "succeeded"
    FAILED = "failed"
    # this host should be replaced, not restarted-in-place: the launcher
    # exits with a distinct code the operator/scaler keys on
    NODE_RELAUNCH = "node_relaunch"


@dataclasses.dataclass
class AgentConfig:
    job_name: str = "local"
    master_addr: str = ""
    node_id: int = 0
    entrypoint: list[str] = dataclasses.field(default_factory=list)
    max_restarts: int = Defaults.MAX_RESTARTS
    monitor_interval_s: float = Defaults.MONITOR_INTERVAL_S
    heartbeat_interval_s: float = Defaults.HEARTBEAT_INTERVAL_S
    rdzv_timeout_s: float = Defaults.RDZV_WAIT_TIMEOUT_S
    network_check: bool = False
    exclude_straggler: bool = False
    local_devices: int = 0  # 0 -> autodetect
    host_ip: str = "127.0.0.1"
    topology_key: str = ""
    save_on_failure: bool = True
    comm_port_base: int = 0  # 0 -> pick free ports
    # node-local hang detection (agent/hang_detector.py): restart the
    # trainer when its reported step stops advancing for this long.
    # 0 disables. The grace covers (re)compilation after every spawn.
    hang_timeout_s: float = 0.0
    hang_startup_grace_s: float = 600.0


def _detect_local_devices() -> int:
    override = os.environ.get(EnvKey.DEVICE_COUNT_OVERRIDE)
    if override:
        return int(override)
    # TPU chips must be counted from their kernel device nodes: importing
    # jax here would initialize libtpu and steal the (exclusive-access)
    # chips from the trainer child this agent is about to spawn
    kind, count = sniff_accelerator()
    if kind == "tpu":
        return count
    # nothing visible in /dev or sysfs (a sealed VM may show neither,
    # and the CPU test mesh never does): ask a short-lived child, which
    # has released whatever it touched by the time it is reaped
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(jax.local_device_count())"],
        capture_output=True, text=True, timeout=120,
    )
    if out.returncode != 0:
        raise RuntimeError(
            "cannot count local devices: the probe child failed: "
            + out.stderr.strip()[-500:]
        )
    return int(out.stdout.split()[-1])


class ElasticAgent:
    """Runs one elastic training lifecycle on this host."""

    def __init__(self, config: AgentConfig, client: MasterClient | None = None):
        self._config = config
        # rack attach (DESIGN.md §28): when the launcher placed this
        # node behind a rack sub-master it sets DLROVER_TPU_RACK_ID and
        # points master_addr at the sub-master. The client then
        # re-dials target-keyed: the rack's own port file first, the
        # root's as the degraded direct-to-root fallback — and prefers
        # the rack file again on every re-dial, so a respawned
        # sub-master reclaims its agents automatically.
        rack_port_file = envspec.get(EnvKey.RACK_PORT_FILE) \
            if envspec.get(EnvKey.RACK_ID) else None
        self._client = client or MasterClient(
            config.master_addr, config.node_id,
            port_file=rack_port_file,
            fallback_port_file=envspec.get(EnvKey.MASTER_PORT_FILE)
            if rack_port_file else None,
        )
        self._proc: subprocess.Popen | None = None
        # failure restarts (consume the failover budget) vs the incarnation
        # counter (any respawn — failures, membership changes, config)
        self._restart_count = 0
        self._incarnation = 0
        self._stopped = threading.Event()
        self._local_devices = config.local_devices or _detect_local_devices()
        self._ckpt_saver = None  # wired by agent/ckpt_saver.py start()
        self._resource_monitor = None
        self._config_tuner = None
        self._buddy_server = None
        self._buddy_replicator = None
        self._preemption_watcher = None
        self._metrics_server = None
        self._world: dict[int, int] = {}
        self._master_link = None  # agent/master_link.py, set at run()
        self._standby = None  # agent/standby.py StandbyManager
        self._node_rank = -1
        self._pending_action = ""
        # span context (§27) of the config push that requested a restart:
        # the planned node_restart attaches under the master's verdict
        self._pending_restart_sctx = ""
        self._action_lock = threading.Lock()
        self._hang = None
        if config.hang_timeout_s > 0:
            from dlrover_tpu.agent.hang_detector import HangDetector

            self._hang = HangDetector(
                config.node_id,
                timeout_s=config.hang_timeout_s,
                startup_grace_s=config.hang_startup_grace_s,
            )

    # ------------------------------------------------------------ rendezvous

    def _rendezvous(self) -> tuple[int, int, str]:
        """Join the training rendezvous; return (rank, num_nodes, coordinator).

        The advertised address carries a freshly picked port the JAX
        coordination service will bind if this node becomes rank 0.
        """
        port = self._config.comm_port_base or find_free_port(
            self._config.host_ip
        )
        addr = f"{self._config.host_ip}:{port}"
        wait_start = time.time()
        join_deadline = wait_start + self._config.rdzv_timeout_s
        while True:
            try:
                self._client.join_rendezvous(
                    addr=addr,
                    local_devices=self._local_devices,
                    topology_key=self._config.topology_key,
                )
                break
            except (ConnectionError, TimeoutError, OSError) as e:
                # a master mid-restart must delay the rendezvous, not
                # kill the agent (§26): re-resolve from the port file
                # and retry inside the rendezvous budget
                if time.time() >= join_deadline:
                    raise
                logger.warning("rendezvous join failed (%s); "
                               "re-dialing the master", e)
                self._client.maybe_redial()
                time.sleep(0.5)
        world = self._client.wait_comm_world(
            timeout=self._config.rdzv_timeout_s
        )
        self._world = world.world
        self._node_rank = world.world[self._config.node_id]
        # adopt the master-minted job trace id before journaling: this
        # agent's spans (and the trainer child, via inherited env) link
        # into the job-wide trace
        set_trace_id(world.trace_id)
        waited = time.time() - wait_start
        _rdzv_wait_seconds.observe(waited)
        get_journal().emit(
            "rendezvous_wait", dur=waited, round=world.round,
            rank=self._node_rank, nodes=len(world.world),
            remote_parent=world.sctx,
        )
        logger.info(
            "rendezvous round %d: rank %d of %d nodes, coordinator %s",
            world.round, self._node_rank, len(world.world), world.coordinator,
        )
        self._reshard_decision(world)
        return self._node_rank, len(world.world), world.coordinator

    def _reshard_decision(self, world) -> None:
        """Choose the recovery path for the world this round produced:
        when the master's compile cache already holds an executable for
        the new topology (published by the pre-failure incarnation or
        the fallback-AOT daemon), the upcoming incarnation is a
        *reshard* event — it will load the program instead of cold
        compiling — and the journal records the choice so the recovery
        trail reads ``reshard`` rather than a cold compile. No coverage
        means today's restart path, unchanged (DESIGN.md §17). The
        event also records the newest VERIFIED storage step: a
        multi-host reshard whose missing shards have no live copy falls
        back to storage (``reshard_state``'s piece registry, DESIGN.md
        §20) — the journal shows up front whether that net exists."""
        from dlrover_tpu.master.kv_store import node_topology_prefix

        try:
            # scan by world size, not device count: the program key pins
            # the exact device topology, but the agent's chip count and
            # the trainer's jax device count can differ (virtual test
            # meshes), and the question here is only "does the N-node
            # world have a pre-compiled program"
            resp = self._client.compile_cache_query(
                node_topology_prefix(len(world.world))
            )
        except (ConnectionError, RuntimeError, OSError) as e:
            logger.warning("compile-cache coverage query failed: %s", e)
            return
        covered = bool(resp.covered)
        stage_execs = self._stage_coverage(len(world.world),
                                           world.total_devices)
        _reshard_choices.labels(str(covered).lower()).inc()
        if covered:
            get_journal().emit(
                "reshard", nodes=len(world.world),
                devices=world.total_devices,
                executables=resp.executables,
                stage_executables=stage_execs,
                shrink=bool(world.reshard),
                storage_step=self._verified_storage_step(),
            )
            logger.info(
                "recovery is a reshard event: %d pre-compiled "
                "executable(s) for %d nodes / %d devices%s%s",
                resp.executables, len(world.world), world.total_devices,
                f" ({stage_execs} per-stage pipeline programs — the "
                "incarnation reloads per stage)" if stage_execs else "",
                " (membership shrink)" if world.reshard else "",
            )

    def _stage_coverage(self, nodes: int, total_devices: int) -> int:
        """Per-stage MPMD program coverage for this world (DESIGN.md
        §21): stage keys carry a ``pp`` marker right after the topology
        tag (``compile_cache.stage_key``), so one prefix scan counts
        them. An MPMD job's recovery is per-stage — this is the
        evidence that only the affected stage will compile cold. Note
        stage submeshes are a SLICE of the world, so the scan uses the
        per-stage device count when the world divides evenly; 0 simply
        means "not an MPMD job" and is not journaled as coverage."""
        from dlrover_tpu.master.kv_store import topology_tag

        count = 0
        seen = set()
        for per_stage_devices in {total_devices, *(
            total_devices // p for p in (2, 4, 8)
            if total_devices % p == 0 and total_devices // p >= 1
        )}:
            prefix = topology_tag(per_stage_devices, nodes) + "/pp"
            if prefix in seen:
                continue
            seen.add(prefix)
            try:
                resp = self._client.compile_cache_query(prefix)
                count += int(resp.executables)
            except (ConnectionError, RuntimeError, OSError):
                return 0
        return count

    def _verified_storage_step(self) -> int:
        """Newest fully-verified checkpoint step in storage (-1 = none
        / unknown): the reshard's missing-shard fallback source."""
        if self._ckpt_saver is None:
            return -1
        try:
            header = self._ckpt_saver.shm_handler.header() or {}
            ckpt_dir = header.get("ckpt_dir") or ""
            if not ckpt_dir:
                return -1
            from dlrover_tpu.common.storage import PosixDiskStorage
            from dlrover_tpu.checkpoint.integrity import (
                resolve_restore_step,
            )

            got = resolve_restore_step(PosixDiskStorage(), ckpt_dir)
            return -1 if got is None else got[0]
        except Exception:  # noqa: BLE001 - evidence only, never blocks
            return -1

    # ----------------------------------------------------------- child mgmt

    def _child_env_update(self, rank: int, num_nodes: int,
                          coordinator: str) -> dict[str, str]:
        """The env-var contract one trainer incarnation runs under —
        shared by cold spawns and standby promotions."""
        update = {
            EnvKey.JOB_NAME: self._config.job_name,
            EnvKey.MASTER_ADDR: self._client._client.addr,
            EnvKey.NODE_ID: str(self._config.node_id),
            EnvKey.NODE_RANK: str(rank),
            EnvKey.NODE_NUM: str(num_nodes),
            EnvKey.COORDINATOR: coordinator,
            EnvKey.RESTART_COUNT: str(self._incarnation),
        }
        trace = os.environ.get(EnvKey.TRACE_ID)
        if trace:
            # a parked standby was spawned before the first rendezvous
            # delivered the job trace id: promotion must carry it
            update[EnvKey.TRACE_ID] = trace
        # span context (§27): a child spawned inside a recovery incident
        # attaches its restore/recompile spans under it. Unconditional so
        # a stale inherited value never leaks into a healthy incarnation.
        update[EnvKey.SPAN_CTX] = current_ctx()
        if self._config_tuner is not None:
            update[EnvKey.PARAL_CONFIG_PATH] = self._config_tuner.path
        return update

    def _spawn(self, rank: int, num_nodes: int, coordinator: str
               ) -> subprocess.Popen:
        env = dict(os.environ)
        env.update(self._child_env_update(rank, num_nodes, coordinator))
        logger.info(
            "spawning training process (incarnation %d, failures %d): %s",
            self._incarnation, self._restart_count,
            " ".join(self._config.entrypoint),
        )
        if self._hang is not None:
            # every incarnation recompiles: fresh grace period
            self._hang.reset()
        _incarnation_gauge.set(self._incarnation)
        return subprocess.Popen(
            self._config.entrypoint, env=env, start_new_session=True
        )

    def _respawn(self, rank: int, num_nodes: int, coordinator: str
                 ) -> subprocess.Popen:
        """Warm path first: promote the parked standby (it has already
        paid spawn + imports and may have a restore prefetch running),
        then re-arm a fresh one in the background. Cold `_spawn` when
        standbys are off, dead, or never armed."""
        if self._standby is not None:
            proc = self._standby.promote(
                self._child_env_update(rank, num_nodes, coordinator)
            )
            if proc is not None:
                if self._hang is not None:
                    self._hang.reset()
                _incarnation_gauge.set(self._incarnation)
                self._standby.arm_async()
                return proc
        return self._spawn(rank, num_nodes, coordinator)

    def _arm_standby(self) -> None:
        from dlrover_tpu.agent.standby import StandbyManager, standby_enabled

        if not standby_enabled() or not self._config.entrypoint:
            return
        if self._standby is None:
            self._standby = StandbyManager(
                self._config.entrypoint, self._config.node_id
            )
        self._standby.arm_async()

    def _prepare_standby_restore(self) -> None:
        """Failure time, post-persist: point the parked standby at the
        checkpoint dir so its storage restore prefetch overlaps the
        rendezvous round this agent is about to run."""
        if self._standby is None or self._ckpt_saver is None:
            return
        try:
            header = self._ckpt_saver.shm_handler.header()
        except Exception:  # noqa: BLE001 - prefetch is best-effort
            return
        if header:
            ckpt_dir = header.get("ckpt_dir") or ""
            if ckpt_dir:
                self._standby.prepare(ckpt_dir)

    def _kill_child(self) -> None:
        if self._proc is None or self._proc.poll() is not None:
            return
        try:
            os.killpg(self._proc.pid, signal.SIGTERM)
            try:
                self._proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                os.killpg(self._proc.pid, signal.SIGKILL)
                self._proc.wait(timeout=10)
        except ProcessLookupError:
            pass

    # ------------------------------------------------------------ main loop

    def run(self) -> RunResult:
        from dlrover_tpu.telemetry.bundle import install_sigusr2
        from dlrover_tpu.telemetry.exposition import start_from_env

        self._metrics_server = start_from_env()
        # operator runbook: `kill -USR2 <agent pid>` captures a full
        # flight-recorder bundle (incl. the live trainer's stacks) on
        # demand without disturbing the job
        install_sigusr2(
            on_bundle=self._report_bundle,
            child_pid_fn=lambda: (
                self._proc.pid
                if self._proc is not None and self._proc.poll() is None
                else None
            ),
        )
        self._start_heartbeat()
        self._start_ckpt_saver()
        self._start_resource_monitor()
        self._start_config_tuner()
        self._start_buddy_replication()
        self._start_preemption_watcher()
        try:
            if self._config.network_check:
                self._run_network_check()
            return self._invoke_run()
        finally:
            self._stopped.set()
            if self._preemption_watcher is not None:
                self._preemption_watcher.stop()
            if self._resource_monitor is not None:
                self._resource_monitor.stop()
            if self._config_tuner is not None:
                self._config_tuner.stop()
            if self._buddy_replicator is not None:
                self._buddy_replicator.stop()
            if self._buddy_server is not None:
                self._buddy_server.stop()
            if self._metrics_server is not None:
                self._metrics_server.stop()
            if self._standby is not None:
                self._standby.discard()
            self._kill_child()

    def _invoke_run(self) -> RunResult:
        rank, num_nodes, coordinator = self._rendezvous()
        self._restore_from_buddy()
        self._proc = self._spawn(rank, num_nodes, coordinator)
        # arm the warm standby only after the live trainer exists: the
        # first spawn must never queue behind the standby's import cost
        self._arm_standby()
        hang = self._hang
        while True:
            time.sleep(self._config.monitor_interval_s)
            code = self._proc.poll()
            if code == 0:
                logger.info("training process succeeded")
                self._client.report_node_event(
                    NodeEventType.MODIFIED, NodeStatus.SUCCEEDED.value,
                    NodeExitReason.SUCCEEDED,
                )
                self._client.report_job_exit(success=True)
                return RunResult.SUCCEEDED
            if code is not None:
                outcome = self._handle_failure(code)
                if outcome is not None:
                    return outcome
                continue
            if hang is not None and hang.check():
                # wedged trainer: the kill surfaces as a failure exit on
                # the next poll and flows through the normal restart and
                # failover budget (the reference's HangingDetector
                # relaunch). _handle_failure owns the master report — a
                # second report here would double-trigger master-side
                # recovery actions.
                logger.warning(
                    "hang detected: no training progress past step %d "
                    "for %.0fs; killing the wedged trainer",
                    hang.last_step(), self._config.hang_timeout_s,
                )
                # flight recorder FIRST: the wedged child's C-level
                # stack dump (SIGUSR2 -> faulthandler) is only readable
                # while it is still alive
                self._write_bundle(
                    "hang",
                    child_pid=(self._proc.pid
                               if self._proc is not None else None),
                    extra={"last_step": hang.last_step(),
                           "timeout_s": self._config.hang_timeout_s},
                )
                self._kill_child()
                continue
            if chaos.ENABLED:
                self._chaos_kill_check()
            # healthy: check for membership changes / master actions
            action = self._master_action()
            if action == "restart":
                self._restart_workers(reason="master restart action")
            elif action.startswith("profile"):
                self._arm_profile(action)
            elif self._membership_changed():
                self._restart_workers(reason="membership change")

    def _arm_profile(self, action: str) -> None:
        """Master-requested on-demand profiler capture ("profile:<K>"):
        hand the request to the live trainer via the bundle-root file
        (telemetry/efficiency.py) — the trainer owns the jax runtime,
        so the capture must run there, not here."""
        from dlrover_tpu.telemetry.efficiency import arm_profile_request

        try:
            steps = max(1, int(action.split(":", 1)[1]))
        except (IndexError, ValueError):
            steps = 5
        arm_profile_request(self._config.node_id, steps)
        logger.info("profiler capture armed for the trainer "
                    "(%d steps)", steps)

    def _chaos_kill_check(self) -> None:
        """Chaos plan ``agent_kill_trainer`` point: kill the live trainer
        with a chosen signal once its reported step matches the rule
        (e.g. ``{"match": {"step_gte": 8}, "args": {"sig": 9}}`` — the
        agent then observes exit code -sig and runs the normal failover
        ladder). The step comes from the hang detector's progress file,
        so the kill lands at a training position, not a wall-clock one.
        """
        from dlrover_tpu.agent.hang_detector import progress_path

        step = -1
        try:
            with open(progress_path(self._config.node_id)) as f:
                step = int(json.load(f)["step"])
        except (OSError, ValueError, KeyError):
            pass
        fault = chaos.fire("agent_kill_trainer", step=step,
                           incarnation=self._incarnation)
        if fault is None or self._proc is None \
                or self._proc.poll() is not None:
            return
        sig = int(fault.args.get("sig", signal.SIGKILL))
        logger.warning("chaos: killing trainer with signal %d at step %d",
                       sig, step)
        try:
            os.killpg(self._proc.pid, sig)
        except ProcessLookupError:
            pass

    def _handle_failure(self, exit_code: int) -> RunResult | None:
        """Classify the exit and act on it; None means restarted, keep
        monitoring. Reference: training.py:356-360 exit-code semantics +
        dist_job_manager.py:561 _should_relaunch."""
        from dlrover_tpu.agent.failure_policy import (
            FailureAction,
            classify_exit,
            decide,
        )

        reason = classify_exit(exit_code)
        action = decide(reason, self._restart_count,
                        self._config.max_restarts)
        logger.warning(
            "training process exited with code %d (%s) -> %s",
            exit_code, reason.value, action.value,
        )
        if exit_code != 0:
            # pre-respawn flight recorder: journal tail, metrics and env
            # as they were when the worker died (the child is gone — any
            # stale armed stack dump it left is scooped up, not poked)
            self._write_bundle(
                "crash",
                extra={"exit_code": exit_code, "reason": reason.value,
                       "action": action.value},
            )
        def _report_failure() -> None:
            self._client.report_failure(
                error_data=f"exit code {exit_code} ({reason.value})",
                restart_count=self._restart_count,
                level=(
                    TrainingExceptionLevel.NODE_ERROR
                    if reason in (NodeExitReason.HARDWARE_ERROR,
                                  NodeExitReason.OOM)
                    else TrainingExceptionLevel.PROCESS_ERROR
                ),
            )

        if action == FailureAction.RELAUNCH_NODE:
            _report_failure()
            # persist the snapshot first: the replacement host restores
            # from storage, not from this host's shm
            self._persist_checkpoint(reason="node relaunch")
            self._report_terminal(
                NodeStatus.FAILED.value, reason,
                f"exit code {exit_code}",
            )
            return RunResult.NODE_RELAUNCH
        if action == FailureAction.GIVE_UP:
            _report_failure()
            logger.error(
                "no failovers remain (%d used); job failed",
                self._restart_count,
            )
            self._report_terminal(
                NodeStatus.FAILED.value, NodeExitReason.FATAL_ERROR,
                f"exit code {exit_code}",
            )
            try:
                self._client.report_job_exit(
                    success=False, reason=f"exit code {exit_code}"
                )
            except (ConnectionError, TimeoutError, OSError) as e:
                logger.warning("job-exit report failed: %s", e)
            return RunResult.FAILED
        _restarts_total.labels("failure").inc()
        with get_journal().span(
            "node_restart", kind="failure", exit_code=exit_code,
            incarnation=self._incarnation + 1,
        ):
            # incident root (§27): opened at failure detection so the
            # failure report and every recovery phase below — persist,
            # rendezvous, restore, respawn, and the trainer child's own
            # restore/recompile (via SPAN_CTX) — journal as children
            _report_failure()
            self._persist_checkpoint(reason="process failure")
            # the persist is durable: the standby's restore prefetch can
            # now run concurrently with the rendezvous round below
            self._prepare_standby_restore()
            self._recover_shards()
            self._restart_count += 1
            self._incarnation += 1
            rank, num_nodes, coordinator = self._rendezvous()
            self._proc = self._respawn(rank, num_nodes, coordinator)
        return None

    def _report_terminal(self, status: str, exit_reason, message: str
                         ) -> None:
        """Terminal node-status reports must not crash the ladder when
        the master is mid-restart (§26): the outcome is also visible
        through the launcher exit code either way."""
        try:
            self._client.report_node_event(
                NodeEventType.MODIFIED, status, exit_reason, message
            )
        except (ConnectionError, TimeoutError, OSError) as e:
            logger.warning("terminal node event report failed: %s", e)

    def _restart_workers(self, reason: str) -> None:
        """Planned restart (membership change / config update): bumps the
        incarnation but does NOT consume the failover budget — only
        failures do (reference: _remaining_failovers decrements on failure
        only, training.py:594)."""
        logger.info("restarting workers: %s", reason)
        _restarts_total.labels("planned").inc()
        with self._action_lock:
            push_sctx = self._pending_restart_sctx
            self._pending_restart_sctx = ""
        with get_journal().span(
            "node_restart", kind="planned", reason=reason,
            incarnation=self._incarnation + 1, remote_parent=push_sctx,
        ):
            self._persist_checkpoint(reason=reason)
            self._kill_child()
            self._prepare_standby_restore()
            self._recover_shards()
            self._incarnation += 1
            rank, num_nodes, coordinator = self._rendezvous()
            self._proc = self._respawn(rank, num_nodes, coordinator)

    def _write_bundle(self, reason: str, child_pid: int | None = None,
                      extra: dict | None = None) -> str | None:
        """Capture a flight-recorder bundle and report its path to the
        master; best-effort and off via DLROVER_TPU_BUNDLES=0."""
        if os.environ.get(EnvKey.BUNDLES, "1") == "0":
            return None
        from dlrover_tpu.telemetry.bundle import write_bundle

        path = write_bundle(reason, node_id=self._config.node_id,
                            child_pid=child_pid, extra=extra)
        if path:
            self._report_bundle(path, reason)
        return path

    def _report_bundle(self, path: str, reason: str) -> None:
        try:
            self._client.report_debug_bundle(path, reason, proc="agent")
        except (ConnectionError, RuntimeError, OSError) as e:
            logger.warning("debug bundle report failed: %s", e)

    def _recover_shards(self) -> None:
        """Give the dead trainer's in-flight data shards back to the queue.

        Restart-in-place keeps this node alive, so the master's
        heartbeat-dead recovery never fires for it (reference analog:
        dist_job_manager relaunch path re-queuing worker shards).
        """
        try:
            self._client.recover_shards()
        except (ConnectionError, RuntimeError, OSError) as e:
            logger.warning("shard recovery request failed: %s", e)

    def _membership_changed(self) -> bool:
        try:
            return self._client.num_nodes_waiting() > 0
        except ConnectionError:
            return False

    def _master_action(self) -> str:
        with self._action_lock:
            action, self._pending_action = self._pending_action, ""
        return action

    # ------------------------------------------------------------- services

    def _start_heartbeat(self) -> None:
        from dlrover_tpu.agent.master_link import MasterLink

        # degraded-mode link (DESIGN.md §26): a master outage is ONE
        # journal instant + a counter (rate-limited warnings), the
        # trainer keeps stepping, and every failed tick re-resolves
        # the master address from the port file so a restarted master
        # is picked up within one heartbeat
        link = MasterLink(self._client, component="agent")
        self._master_link = link

        def loop():
            while not self._stopped.is_set():
                try:
                    action = self._client.report_heartbeat(
                        self._restart_count
                    )
                    if action:
                        with self._action_lock:
                            self._pending_action = action
                    # piggyback this node's metrics snapshot on the
                    # heartbeat cadence so the master's exposition
                    # endpoint serves job-wide series
                    self._client.report_metrics(registry().snapshot())
                    link.ok()
                except (ConnectionError, RuntimeError, OSError) as e:
                    link.failed(e)
                    if link.stale():
                        # a control action mirrored before the outage
                        # must not fire minutes later (§30): the master
                        # re-issues it on the next heartbeat if it
                        # still wants it
                        with self._action_lock:
                            self._pending_action = ""
                            self._pending_restart_sctx = ""
                self._stopped.wait(self._config.heartbeat_interval_s)

        threading.Thread(target=loop, name="agent-heartbeat",
                         daemon=True).start()

    def _start_ckpt_saver(self) -> None:
        from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver

        self._ckpt_saver = AsyncCheckpointSaver.start(
            node_id=self._config.node_id
        )

    def _start_resource_monitor(self) -> None:
        from dlrover_tpu.agent.resource_monitor import ResourceMonitor

        self._resource_monitor = ResourceMonitor(
            self._client,
            interval_s=self._config.heartbeat_interval_s,
            tpu_chips=self._local_devices,
        )
        self._resource_monitor.start()

    def _start_config_tuner(self) -> None:
        from dlrover_tpu.agent.config_tuner import ParalConfigTuner

        def on_update(config: dict) -> None:
            if config.get("restart_required") and self._proc is not None \
                    and self._proc.poll() is None:
                # recompile-class knobs apply at the next incarnation
                with self._action_lock:
                    self._pending_action = "restart"
                    self._pending_restart_sctx = config.get("sctx", "")

        self._config_tuner = ParalConfigTuner(
            self._client, on_update=on_update
        )
        self._config_tuner.start()

    def _start_buddy_replication(self) -> None:
        """Peer-redundant shm snapshots over DCN (checkpoint/buddy.py):
        this agent serves its peers' pushes and streams its own node's
        new snapshots to the master-assigned ring buddy. Disable with
        DLROVER_TPU_BUDDY=0."""
        if not envspec.get_bool(EnvKey.BUDDY):
            return
        from dlrover_tpu.checkpoint.buddy import (
            BuddyReplicator,
            BuddyServer,
        )

        try:
            self._buddy_server = BuddyServer(
                host=self._config.host_ip
            ).start()
            self._client.report_buddy_endpoint(self._buddy_server.addr)
        except (OSError, ConnectionError, RuntimeError) as e:
            logger.warning("buddy server unavailable: %s", e)
            self._buddy_server = None
            return
        interval = envspec.get_float(EnvKey.BUDDY_INTERVAL)
        self._buddy_replicator = BuddyReplicator(
            self._ckpt_saver.shm_handler, self._client,
            interval_s=interval,
        )
        self._buddy_replicator.start()

    def _start_preemption_watcher(self) -> None:
        """Arm the maintenance/preemption-notice watcher
        (agent/preemption.py); inert unless a notice source env is set."""
        from dlrover_tpu.agent.preemption import PreemptionWatcher

        watcher = PreemptionWatcher(
            self._on_preemption_notice, node_id=self._config.node_id,
            poll_interval_s=min(1.0, self._config.monitor_interval_s),
        )
        if watcher.enabled:
            self._preemption_watcher = watcher.start()

    def _on_preemption_notice(self) -> None:
        """The kill is coming: protect the snapshot while the host is
        still alive, then arm the master's fast relaunch. Order matters —
        the buddy push is what the <10s no-storage restore needs; the
        storage persist is the belt-and-braces fallback."""
        start = time.monotonic()
        # master first: it is a cheap RPC, and if the kill lands during
        # the (slow, multi-GB) replication/persist below, the master
        # must already be on the short dead-window or the relaunch waits
        # the full heartbeat window
        try:
            self._client.report_preemption_notice()
        except (ConnectionError, RuntimeError, OSError) as e:
            logger.warning("could not report preemption notice: %s", e)
        replicated = False
        if self._buddy_replicator is not None:
            try:
                # replicate_once is a no-op when the buddy already holds
                # the current step — "protected" either way
                self._buddy_replicator.replicate_once()
                replicated = True
            except Exception:  # noqa: BLE001 - keep preparing
                logger.exception("pre-kill buddy replication failed")
        self._persist_checkpoint(reason="preemption notice")
        logger.warning(
            "preemption prepare done in %.2fs (buddy replicated: %s)",
            time.monotonic() - start, replicated,
        )

    def _restore_from_buddy(self) -> None:
        """Pre-spawn: if this host's shm snapshot is gone (node relaunch
        on a fresh VM — TPU preemption), pull it back from the buddy so
        the trainer's restore-from-shm path works unchanged and storage
        stays the last resort (<10s budget, SURVEY §7 hard-parts).

        Independent of the local BuddyServer: fetching OUR snapshot only
        needs the buddy's server — a recycled VM whose own server failed
        to bind must still restore."""
        if not envspec.get_bool(EnvKey.BUDDY) \
                or self._ckpt_saver is None:
            return
        handler = self._ckpt_saver.shm_handler
        if handler.header() is not None:
            return  # local snapshot alive; nothing to do
        from dlrover_tpu.checkpoint.buddy import fetch_snapshot

        try:
            buddy = self._client.query_buddy()
        except (ConnectionError, RuntimeError, OSError) as e:
            logger.warning("buddy query failed: %s", e)
            return
        if not buddy.found:
            return
        start = time.monotonic()
        got = fetch_snapshot(buddy.addr, self._config.node_id)
        if got is None:
            logger.info("buddy node %d holds no snapshot for us",
                        buddy.buddy_node_id)
            return
        header, payload = got
        handler.write_raw(header, payload)
        logger.info(
            "restored snapshot step %s (%d bytes) from buddy node %d "
            "in %.2fs", header.get("step"), len(payload),
            buddy.buddy_node_id, time.monotonic() - start,
        )

    def _persist_checkpoint(self, reason: str) -> None:
        """Flush the latest in-memory snapshot to storage before a restart.

        Reference analog: the breakpoint save (ckpt_saver.py:631
        save_shm_to_storage) triggered from training.py:590-610.
        """
        if self._ckpt_saver is None:
            return
        try:
            if self._config.save_on_failure:
                self._ckpt_saver.save_shm_to_storage(reason=reason)
        except Exception:  # noqa: BLE001 - never let persist break restart
            logger.exception("breakpoint checkpoint persist failed")
        finally:
            # a trainer that died holding the shm writer lock must not
            # disable checkpointing for the rest of the job
            self._ckpt_saver.reset_writer_lock()

    # -------------------------------------------------------- network check

    def _run_network_check(self) -> None:
        """Pre-training collective probe with ≤2-round fault bisection.

        Reference analog: NodeCheckElasticAgent.run (training.py:805,956) +
        NetworkCheckRendezvousManager (reference rdzv_manager.py:349).
        Probe round 0 runs in master-assigned pairs; nodes whose pair failed
        are re-paired with known-good partners in round 1, so one bad node
        cannot condemn its healthy neighbor.
        """
        from dlrover_tpu.agent.node_check import run_node_check

        port = find_free_port(self._config.host_ip)
        self._client.join_rendezvous(
            addr=f"{self._config.host_ip}:{port}",
            local_devices=self._local_devices,
            rdzv_name="network-check",
            topology_key=self._config.topology_key,
        )
        world = self._client.wait_comm_world(
            rdzv_name="network-check", timeout=self._config.rdzv_timeout_s
        )
        global_rank = world.world[self._config.node_id]
        for probe_round in (0, 1):
            group = self._wait_probe_group(probe_round)
            if group is None or not group.needed:
                break
            elapsed, ok, local = run_node_check(
                node_rank=group.world[self._config.node_id],
                num_nodes=len(group.world),
                coordinator=group.coordinator,
                global_rank=global_rank,
            )
            self._client.report_network_check(probe_round, ok, elapsed,
                                              local_time=local)
        deadline = time.time() + 120
        while time.time() < deadline:
            status = self._client.get_network_check_status()
            if status.completed:
                bad = set(status.abnormal_nodes)
                if self._config.exclude_straggler:
                    bad |= set(status.straggler_nodes)
                if self._config.node_id in bad:
                    raise RuntimeError(
                        "this node failed the network check; excluding"
                    )
                return
            time.sleep(0.5)
        logger.warning("network check status never completed; proceeding")

    def _wait_probe_group(self, probe_round: int, timeout: float = 300.0):
        deadline = time.time() + timeout
        while time.time() < deadline:
            group = self._client.get_network_check_group(probe_round)
            if group.ready:
                return group
            time.sleep(0.5)
        logger.warning("probe round %d group never became ready", probe_round)
        return None


def launch_agent(config: AgentConfig) -> RunResult:
    agent = ElasticAgent(config)
    return agent.run()
