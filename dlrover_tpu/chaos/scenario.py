"""Chaos scenario spec + runner: drive a local job through a scheduled
fault sequence and check recovery invariants.

A ``Scenario`` is a seed plus job *legs*; each leg runs the elastic
example under ``dlrover_tpu.run --standalone`` with that leg's fault
plan installed through ``DLROVER_TPU_CHAOS`` (inherited by the master,
agent, and trainer processes). Legs share one checkpoint directory and
one journal, so a later leg restores what an earlier, sabotaged leg
persisted — the cross-restart corruption cases (bit-flipped newest
shard, torn tracker) that can't be exercised inside a single process
tree, because a respawned-in-place trainer restores from shared memory
and never touches storage.

Recovery invariants checked by ``ScenarioResult.assert_invariants``:

- every leg reaches its target step with its expected exit code
  (zero lost data shards: the at-least-once sharding re-runs whatever
  the faults rolled back, and the run still completes);
- the checkpoint directory's newest VERIFIED step equals the final
  step (restore-time verification would accept exactly what the job
  durably committed — nothing corrupt is reachable);
- recovery after the injected kill is bounded (``max_recovery_s``);
- every injected fault left a ``chaos_fault`` journal line
  (``trail["faults"]`` length matches the plan's firing budget).

The canonical *trail* is replay-comparable: two runs of the same
scenario with the same seed must produce an identical trail (the
tier-1 determinism assertion in tests/test_chaos.py).
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from typing import Any

from dlrover_tpu.common.constants import EnvKey
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.common.storage import atomic_write_file

logger = get_logger(__name__)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_EXAMPLE = os.path.join(REPO, "examples", "train_transformer.py")

# journal names treated as recovery evidence in the canonical trail
RECOVERY_EVENTS = (
    "node_restart", "ckpt_verify_failed", "ckpt_rollback",
    "ckpt_shard_rollback", "state_rollback", "degraded_mode", "reshard",
    "embedding_scale", "embedding_restore",
)


@dataclasses.dataclass
class JobLeg:
    """One elastic job run inside a scenario."""

    name: str
    max_steps: int
    faults: list[dict] = dataclasses.field(default_factory=list)
    cli_args: list[str] = dataclasses.field(default_factory=list)
    train_args: list[str] = dataclasses.field(default_factory=list)
    expect_rc: int = 0


@dataclasses.dataclass
class Scenario:
    name: str
    seed: int
    legs: list[JobLeg]
    max_recovery_s: float = 120.0

    def planned_firings(self) -> int:
        """Upper bound on chaos_fault lines this scenario should emit
        (only rules with a finite ``times`` budget are countable)."""
        total = 0
        for leg in self.legs:
            for rule in leg.faults:
                total += int(rule.get("times", 1)) or 0
        return total


@dataclasses.dataclass
class LegResult:
    name: str
    rc: int
    result: dict | None     # the trainer's --result-file payload
    tail: str
    elapsed_s: float


@dataclasses.dataclass
class ScenarioResult:
    scenario: Scenario
    legs: list[LegResult]
    trail: dict
    recovery_seconds: float | None
    verified_step: int | None
    goodput: float | None
    work_dir: str

    @property
    def completed(self) -> bool:
        return all(
            leg.rc == spec.expect_rc
            and (spec.expect_rc != 0 or (
                leg.result is not None
                and leg.result.get("final_step") == spec.max_steps))
            for leg, spec in zip(self.legs, self.scenario.legs)
        )

    def assert_invariants(self) -> None:
        for leg, spec in zip(self.legs, self.scenario.legs):
            assert leg.rc == spec.expect_rc, (
                f"leg {leg.name}: rc {leg.rc} != {spec.expect_rc}\n"
                f"{leg.tail}"
            )
            if spec.expect_rc == 0:
                assert leg.result is not None, \
                    f"leg {leg.name}: no result file\n{leg.tail}"
                assert leg.result["final_step"] == spec.max_steps, (
                    f"leg {leg.name}: lost progress — final step "
                    f"{leg.result['final_step']} != {spec.max_steps}"
                )
        final = self.legs[-1].result
        if final is not None:
            assert self.verified_step == final["final_step"], (
                f"newest verified step {self.verified_step} != final "
                f"step {final['final_step']} (lost or corrupt shards)"
            )
        planned = self.scenario.planned_firings()
        assert len(self.trail["faults"]) == planned, (
            f"{len(self.trail['faults'])} chaos_fault journal lines for "
            f"{planned} planned firings: {self.trail['faults']}"
        )
        if self.recovery_seconds is not None:
            assert self.recovery_seconds <= self.scenario.max_recovery_s, (
                f"recovery took {self.recovery_seconds:.1f}s "
                f"(bound {self.scenario.max_recovery_s:.0f}s)"
            )


# ------------------------------------------------------------------ journal


def _read_journal(journal_dir: str) -> list[dict]:
    events: list[dict] = []
    base = os.path.join(journal_dir, "events.jsonl")
    for path in (base + ".1", base):
        try:
            with open(path, encoding="utf-8") as f:
                for line in f:
                    try:
                        events.append(json.loads(line))
                    except json.JSONDecodeError:
                        continue  # torn final line of a killed writer
        except OSError:
            continue
    return events


def fault_trail(journal_dir: str) -> dict:
    """Canonical, replay-comparable fault/recovery trail.

    Chaos firings are reduced to sorted ``(point, action, k)`` triples
    (k = per-(point,action) occurrence index): invariant to journal
    interleaving across processes/threads, sensitive to any change in
    what actually fired. Recovery events keep their deterministic
    fields (verify kind + step, rollback from/to, restart kind) and are
    sorted the same way.
    """
    events = _read_journal(journal_dir)
    fault_counts: dict[tuple[str, str], int] = {}
    faults: list[list[Any]] = []
    recovery: list[list[Any]] = []
    for e in events:
        name = e.get("name")
        if name == "chaos_fault":
            key = (e.get("point", "?"), e.get("action", "?"))
            k = fault_counts.get(key, 0)
            fault_counts[key] = k + 1
            faults.append([key[0], key[1], k])
        elif name == "node_restart" and e.get("ev") == "b":
            recovery.append(["node_restart", e.get("kind", "")])
        elif name == "ckpt_verify_failed":
            recovery.append(["ckpt_verify_failed", e.get("kind", ""),
                             e.get("step", -1)])
        elif name == "ckpt_rollback":
            recovery.append(["ckpt_rollback", e.get("from_step", -1),
                             e.get("to_step", -1)])
        elif name == "ckpt_shard_rollback":
            recovery.append(["ckpt_shard_rollback", e.get("step", -1),
                             e.get("writer", ""), e.get("kind", "")])
        elif name == "state_rollback":
            recovery.append(["state_rollback"])
        elif name == "degraded_mode":
            recovery.append(["degraded_mode", e.get("state", "")])
        elif name == "reshard":
            # the reshard-recovery choice (agent) and the state remap
            # (mesh) share the name; keep only the deterministic fields
            recovery.append(["reshard", e.get("nodes", 0),
                             bool(e.get("shrink", False))])
        elif name == "embedding_scale":
            # ring scale events are deterministic given stable member
            # ids + seeded rows: moved counts replay exactly (§25)
            recovery.append(["embedding_scale", e.get("from_n", 0),
                             e.get("to_n", 0), e.get("moved", -1),
                             bool(e.get("ok", False))])
        elif name == "embedding_restore":
            recovery.append(["embedding_restore", e.get("step", -1),
                             e.get("rows", -1), e.get("from_w", 0),
                             e.get("to_w", 0)])
    return {"faults": sorted(faults), "recovery": sorted(recovery)}


def _recovery_seconds(journal_dir: str) -> float | None:
    """Injected trainer kill -> the respawned trainer's restore."""
    events = _read_journal(journal_dir)
    t_kill = None
    for e in events:
        if e.get("name") == "chaos_fault" \
                and e.get("point") == "agent_kill_trainer":
            t_kill = e["t"]
            break
    if t_kill is None:
        return None
    restores = [
        e["t"] for e in events
        if e.get("name") == "ckpt_restore" and e.get("t", 0) > t_kill
    ]
    return min(restores) - t_kill if restores else None


# ------------------------------------------------------------------- runner


def run_scenario(scenario: Scenario, work_dir: str, *,
                 env_extra: dict | None = None,
                 example: str = DEFAULT_EXAMPLE,
                 deadline_s: float = 600.0,
                 goodput_leg: int = 0) -> ScenarioResult:
    """Run every leg, then assemble the trail + invariant inputs.

    The runner owns all shared paths (ckpt dir, journal, per-leg plan
    files, IPC dirs — each leg gets a FRESH IPC dir, so a later leg's
    trainer cannot shortcut recovery through the previous leg's shm
    snapshot and must exercise the storage restore path).
    """
    os.makedirs(work_dir, exist_ok=True)
    ckpt_dir = os.path.join(work_dir, "ckpt")
    journal_dir = os.path.join(work_dir, "journal")
    goodput_log = os.path.join(work_dir, "goodput.jsonl")
    deadline = time.monotonic() + deadline_s
    legs: list[LegResult] = []
    ipc_dirs: list[str] = []
    try:
        for i, leg in enumerate(scenario.legs):
            plan_path = os.path.join(work_dir, f"plan_{leg.name}.json")
            # the leg subprocess reads this via DLROVER_TPU_CHAOS:
            # publish atomically (a torn plan would silently disable
            # injection and desync the replay trail)
            atomic_write_file(
                json.dumps({"seed": scenario.seed, "faults": leg.faults}),
                plan_path,
            )
            env = dict(os.environ)
            env.update(env_extra or {})
            env.setdefault("JAX_PLATFORMS", "cpu")
            env.setdefault(EnvKey.DEVICE_COUNT_OVERRIDE, "1")
            # hermetic compile cache, shared across this scenario's legs
            # but never across scenarios/test runs — a stale hit would
            # silently turn a cold-compile assertion warm
            if "JAX_COMPILATION_CACHE_DIR" not in (env_extra or {}):
                env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
                    work_dir, "compile_cache")
            # IPC dirs hold AF_UNIX sockets, whose path limit (~108
            # chars) a nested work_dir easily exceeds: keep them short
            # and top-level, removed in the finally below
            ipc_dir = tempfile.mkdtemp(prefix=f"chaos{i}_")
            ipc_dirs.append(ipc_dir)
            env.update({
                EnvKey.CHAOS: plan_path,
                EnvKey.JOURNAL_DIR: journal_dir,
                EnvKey.IPC_DIR: ipc_dir,
                # deterministic span ids (§27): two runs of the same
                # seeded scenario assemble byte-identical trace trees.
                # The leg name is part of the seed — every leg restarts
                # its processes (resetting the per-process span counter),
                # so legs sharing a seed would repeat id streams into the
                # same journal and collide in the assembler's id map
                EnvKey.TRACE_SEED:
                    f"{scenario.name}:{leg.name}:{scenario.seed}",
                # each leg is its own JOB: pin a deterministic per-leg
                # trace id so the auditor's per-job invariant scoping
                # sees leg B's round 1 as a fresh job, not a reissue —
                # and so a trace id leaked into the harness process's
                # environ can never glue the legs together
                EnvKey.TRACE_ID:
                    f"{scenario.name}:{leg.name}:{scenario.seed}",
                "PYTHONPATH": (env.get("PYTHONPATH", "")
                               + os.pathsep + REPO),
            })
            result_file = os.path.join(work_dir,
                                       f"result_{leg.name}.json")
            cmd = [
                sys.executable, "-m", "dlrover_tpu.run", "--standalone",
                "--monitor-interval", "0.3", "--max-restarts", "3",
                *leg.cli_args,
                example, "--",
                "--model", "tiny", "--global-batch", "8", "--seq", "128",
                "--log-interval", "5",
                "--ckpt-dir", ckpt_dir,
                "--result-file", result_file,
                "--max-steps", str(leg.max_steps),
                *([] if i != goodput_leg
                  else ["--goodput-log", goodput_log]),
                *leg.train_args,
            ]
            budget = deadline - time.monotonic()
            if budget <= 10:
                legs.append(LegResult(leg.name, -1, None,
                                      "scenario deadline exhausted", 0.0))
                break
            t0 = time.monotonic()
            logger.info("chaos leg %s: %d faults, %d steps",
                        leg.name, len(leg.faults), leg.max_steps)
            try:
                proc = subprocess.run(
                    cmd, env=env, cwd=REPO, timeout=budget,
                    capture_output=True, text=True,
                )
                rc, tail = proc.returncode, (proc.stdout
                                             + proc.stderr)[-3000:]
            except subprocess.TimeoutExpired as e:
                rc = -2
                tail = ((e.stdout or b"")[-3000:].decode(errors="replace")
                        if isinstance(e.stdout, bytes)
                        else str(e.stdout or "")[-3000:])
            result = None
            if os.path.exists(result_file):
                try:
                    with open(result_file, encoding="utf-8") as f:
                        result = json.load(f)
                except (OSError, json.JSONDecodeError):
                    pass
            legs.append(LegResult(leg.name, rc, result, tail,
                                  time.monotonic() - t0))
    finally:
        # never leak a detached standalone master or wedged trainer
        subprocess.run(["pkill", "-9", "-f", example],
                       capture_output=True)
        subprocess.run(
            ["pkill", "-9", "-f", "dlrover_tpu.master.job_master"],
            capture_output=True,
        )
        for d in ipc_dirs:
            shutil.rmtree(d, ignore_errors=True)

    # snapshot the trail BEFORE the verification pass below, which can
    # emit its own journal events if the caller journals to the same dir
    trail = fault_trail(journal_dir)
    recovery_s = _recovery_seconds(journal_dir)

    from dlrover_tpu.checkpoint.integrity import resolve_restore_step
    from dlrover_tpu.common.storage import PosixDiskStorage

    verified = resolve_restore_step(PosixDiskStorage(), ckpt_dir)
    goodput = None
    if os.path.exists(goodput_log):
        try:
            from dlrover_tpu.utils.goodput import compute_goodput

            goodput = compute_goodput(goodput_log).goodput
        except Exception:  # noqa: BLE001 - diagnostics only
            logger.exception("goodput aggregation failed")
    # trail-invariant audit (§30): every chaos scenario ends by proving
    # the merged journals violate none of the safety invariants
    from dlrover_tpu.telemetry.audit import assert_clean

    assert_clean(journal_dir, context=f"scenario {scenario.name}")
    return ScenarioResult(
        scenario=scenario,
        legs=legs,
        trail=trail,
        recovery_seconds=recovery_s,
        verified_step=verified[0] if verified else None,
        goodput=goodput,
        work_dir=work_dir,
    )


# ------------------------------------------------------------------- canned


def canned_sharded_scenario(seed: int = 4242) -> dict:
    """The sharded-persist acceptance schedule (DESIGN.md §20): N=3
    hosts save step 4 (committed, one primary + one ring twin per
    shard), then step 8's save loses host 2 mid-write (injected ENOSPC
    = the host died before its shard landed — no done marker, no ack,
    no commit), step 4's primary shard 0 is bit-flipped on its way to
    disk, and a restore-time read of shard 1 is slowed
    (``storage_read``). ``run_sharded_scenario`` replays it: the
    restore on M=N−1 hosts must land on step 4 — the newest FULLY
    verified step — bit-exactly, through a per-shard twin rollback.
    """
    return {
        "seed": seed,
        "faults": [
            # host 2 dies mid-sharded-save of step 8
            {"point": "storage_write", "action": "enospc",
             "match": {"path_contains": "step-8/",
                       "path_suffix": "node_2.bin"},
             "times": 1},
            # the committed step's primary shard 0 rots on disk
            {"point": "storage_write", "action": "bit_flip",
             "match": {"path_contains": "step-4/",
                       "path_suffix": "node_0.bin"},
             "times": 1},
            # a sick disk slows one verification read at restore
            {"point": "storage_read", "action": "slow",
             "args": {"s": 0.05},
             "match": {"path_suffix": "node_1.bin"},
             "times": 1},
        ],
    }


@dataclasses.dataclass
class ShardedScenarioResult:
    restored_step: int | None
    bad_writers: list[str]
    restored_crc: int           # crc32 over the assembled restored rows
    expected_crc: int           # crc32 over the step-4 source rows
    trail: dict

    @property
    def bit_exact(self) -> bool:
        return self.restored_crc == self.expected_crc

    def assert_invariants(self) -> None:
        assert self.restored_step == 4, (
            f"restore landed on {self.restored_step}, not the newest "
            "fully-verified step 4"
        )
        assert self.bit_exact, "restored rows are not bit-exact"
        assert "0" in self.bad_writers, (
            "the bit-flipped shard 0 was not excluded via per-shard "
            f"rollback (bad={self.bad_writers})"
        )


def run_sharded_scenario(work_dir: str, *, seed: int = 4242,
                         hosts: int = 3, rows: int = 24,
                         cols: int = 16) -> ShardedScenarioResult:
    """Drive the canned sharded-save schedule IN PROCESS.

    Multi-host persist is simulated with ``hosts`` solo-mode
    ``ShardedCheckpointEngine`` instances sharing one checkpoint dir
    (the jax CPU backend cannot run true multi-process collectives in
    this container; the storage/commit/verify path under test is
    process-count-agnostic). Host ``i`` owns rows ``[i*k, (i+1)*k)`` as
    replica 0 and carries host ``i-1``'s rows as the replica-1 ring
    twin (``DLROVER_TPU_CKPT_PERSIST_REPLICAS=2``).
    """
    import zlib

    import numpy as np

    from dlrover_tpu import chaos
    from dlrover_tpu.checkpoint.integrity import resolve_restore_plan
    from dlrover_tpu.checkpoint.sharded import (
        ShardedCheckpointEngine,
        assemble,
        storage_piece_registry,
    )
    from dlrover_tpu.common.storage import PosixDiskStorage

    assert rows % hosts == 0
    k = rows // hosts
    os.makedirs(work_dir, exist_ok=True)
    ckpt_dir = os.path.join(work_dir, "ckpt")
    journal_dir = os.path.join(work_dir, "journal")
    spec = canned_sharded_scenario(seed)
    spec["faults"] = [dict(r) for r in spec["faults"]]

    def state_at(step: int) -> np.ndarray:
        rng = np.random.default_rng(seed + step)
        return rng.standard_normal((rows, cols)).astype(np.float32)

    def host_pieces(data: np.ndarray, i: int) -> tuple[dict, dict]:
        pieces, index = {}, {}
        for replica, owner in ((0, i), (1, (i - 1) % hosts)):
            key = f"w::piece{replica}"
            pieces[key] = data[owner * k:(owner + 1) * k]
            index[key] = {
                "path": "w", "global_shape": [rows, cols],
                "dtype": "float32",
                "index": [[owner * k, (owner + 1) * k], [0, cols]],
                "replica": replica, "persist": True,
            }
        return pieces, index

    prev_env = os.environ.get(EnvKey.CKPT_PERSIST_REPLICAS)
    prev_journal = os.environ.get(EnvKey.JOURNAL_DIR)
    os.environ[EnvKey.CKPT_PERSIST_REPLICAS] = "2"
    os.environ[EnvKey.JOURNAL_DIR] = journal_dir
    chaos.install({"seed": seed, "faults": spec["faults"]})
    engines = []
    try:
        engines = [
            ShardedCheckpointEngine(
                ckpt_dir, node_id=i, node_rank=i, world_size=hosts,
            )
            for i in range(hosts)
        ]
        for step in (4, 8):
            data = state_at(step)
            for i, eng in enumerate(engines):
                pieces, index = host_pieces(data, i)
                eng.snapshot_pieces(step, pieces, index)
                try:
                    # rank-0 last so its commit wait sees the peers
                    if i != 0:
                        eng._solo_saver._persist_step(step)
                except OSError as e:
                    logger.warning("host %d lost mid-save of step %d: "
                                   "%s", i, step, e)
            try:
                # join the commit only for the step that CAN commit:
                # step 8's waiter must not stall the schedule (it polls
                # in the background and dies with the saver, exactly
                # like a real agent outliving a dead peer)
                engines[0]._solo_saver._persist_step(
                    step, commit_block_s=20.0 if step == 4 else 0.0
                )
            except OSError as e:
                logger.warning("host 0 lost mid-save of step %d: %s",
                               step, e)
        # restore on M = N-1 fresh hosts, storage only
        storage = PosixDiskStorage()
        plan = resolve_restore_plan(storage, ckpt_dir)
        restored_step = plan.step if plan else None
        bad = sorted(plan.bad_pieces) if plan else []
        restored_crc = -1
        if plan is not None:
            registry = storage_piece_registry(
                storage, ckpt_dir, plan.step, plan.num_shards,
                bad_pieces=plan.bad_pieces,
            )
            m = hosts - 1
            parts = []
            bounds = [round(rows * j / m) for j in range(m + 1)]
            for j in range(m):  # each surviving host pulls its slice
                parts.append(assemble(
                    [[bounds[j], bounds[j + 1]], [0, cols]],
                    np.dtype("float32"), registry["w"],
                ))
            restored = np.concatenate(parts, axis=0)
            restored_crc = zlib.crc32(restored.tobytes()) & 0xFFFFFFFF
    finally:
        chaos.uninstall()
        for eng in engines:
            try:
                eng.shm_handler.close(unlink=True)
                eng.close()
            except Exception:  # noqa: BLE001 - cleanup best-effort
                pass
        if prev_env is None:
            os.environ.pop(EnvKey.CKPT_PERSIST_REPLICAS, None)
        else:
            os.environ[EnvKey.CKPT_PERSIST_REPLICAS] = prev_env
        if prev_journal is None:
            os.environ.pop(EnvKey.JOURNAL_DIR, None)
        else:
            os.environ[EnvKey.JOURNAL_DIR] = prev_journal
    expected = state_at(4)
    from dlrover_tpu.telemetry.audit import assert_clean

    assert_clean(journal_dir, context="sharded scenario")
    return ShardedScenarioResult(
        restored_step=restored_step,
        bad_writers=bad,
        restored_crc=restored_crc,
        expected_crc=zlib.crc32(expected.tobytes()) & 0xFFFFFFFF,
        trail=fault_trail(journal_dir),
    )


def canned_embedding_scenario(seed: int = 4242) -> dict:
    """The embedding-fabric acceptance schedule (DESIGN.md §25): a
    3-server ring persists step 4 (verified, replicas=2), then a scale
    3→4 loses the new shard server mid-migration — the first
    ``import_rows`` push lands, every later one hits a dead connection
    (``embedding_msg`` reset, enough firings to exhaust the migrate
    retries) — so the coordinator must roll the scale back zero-loss;
    a respawned destination re-runs the scale to completion. Step 8's
    save then bit-flips shard server emb-0's file on its way to disk
    (``storage_write``), and the restore must land on step 8 anyway via
    the per-shard twin rollback (emb-0's block verifies in its ring
    successor's file). ``run_embedding_scenario`` replays it.
    """
    return {
        "seed": seed,
        "faults": [
            # the new shard server dies mid-migration: the first row
            # push lands, then the wire goes dead — 3 firings cover
            # every migrate retry so phase 1 provably fails
            {"point": "embedding_msg", "action": "reset",
             "match": {"op": "import_rows"},
             "after": 1, "times": 3},
            # the newest step's primary shard rots on its way to disk
            {"point": "storage_write", "action": "bit_flip",
             "match": {"path_contains": "step-8/",
                       "path_suffix": "node_emb-0.bin"},
             "times": 1},
        ],
    }


@dataclasses.dataclass
class EmbeddingScenarioResult:
    moved: int                  # rows moved by the successful re-scale
    total_rows: int             # ring row count at the scale event
    restored_step: int | None
    restored_crc: int           # crc32 over the reassembled restored rows
    expected_crc: int           # crc32 over the pre-persist source rows
    rows_after_rollback: int    # ring rows right after the failed scale
    trail: dict

    @property
    def bit_exact(self) -> bool:
        return self.restored_crc == self.expected_crc

    @property
    def moved_frac(self) -> float:
        return self.moved / max(1, self.total_rows)

    def assert_invariants(self) -> None:
        assert self.rows_after_rollback == self.total_rows, (
            "the failed scale lost rows: "
            f"{self.rows_after_rollback} != {self.total_rows}"
        )
        assert 0 < self.moved_frac <= 1.6 / 4, (
            f"3→4 scale moved {self.moved_frac:.2f} of rows; the ring "
            "bound is ~1/N"
        )
        assert self.restored_step == 8, (
            f"restore landed on {self.restored_step}, not the newest "
            "verified step 8 (twin rollback should cover the bit flip)"
        )
        assert self.bit_exact, "restored rows are not row-exact"


def run_embedding_scenario(work_dir: str, *, seed: int = 4242,
                           dim: int = 8, rows: int = 96
                           ) -> EmbeddingScenarioResult:
    """Drive the canned embedding schedule IN PROCESS (CPU-only).

    A real multi-host fabric runs the same ``FabricShardServer``
    processes over TCP; in-process servers exercise the identical wire
    protocol (every call crosses a real socket), so the
    migration-rollback and twin-restore paths under test are
    deployment-agnostic.
    """
    import zlib

    import numpy as np

    from dlrover_tpu import chaos
    from dlrover_tpu.embedding.fabric import (
        FabricClient,
        FabricShardServer,
        start_local_fabric,
    )

    os.makedirs(work_dir, exist_ok=True)
    ckpt_dir = os.path.join(work_dir, "ckpt")
    journal_dir = os.path.join(work_dir, "journal")
    spec = canned_embedding_scenario(seed)

    prev_journal = os.environ.get(EnvKey.JOURNAL_DIR)
    os.environ[EnvKey.JOURNAL_DIR] = journal_dir
    coord = None
    servers: list = []
    client = None
    try:
        coord, servers = start_local_fabric(
            3, dim=dim, num_slots=2, seed=seed, replicas=2,
            ckpt_dir=ckpt_dir,
        )
        client = FabricClient(coordinator_addr=coord.addr, dim=dim,
                              async_apply=False, retry_window_s=20.0)
        rng = np.random.default_rng(seed)
        ids = rng.choice(1 << 20, size=rows, replace=False).astype(
            np.int64
        )
        client.lookup(ids)
        for _ in range(4):
            client.apply("adam", ids,
                         rng.standard_normal((rows, dim)).astype(
                             np.float32), lr=1e-2)
        client.persist(4)

        chaos.install({"seed": spec["seed"], "faults": spec["faults"]})
        # the destination that will die mid-migration
        doomed = FabricShardServer(dim=dim, num_slots=2,
                                   member="emb-3", seed=seed,
                                   host="127.0.0.1").start()
        members4 = {s.member: s.addr for s in servers}
        members4["emb-3"] = doomed.addr
        total = coord.total_rows()
        try:
            coord.scale(members4, migrate_retries=3)
            raise AssertionError(
                "scale survived the mid-migration kill"
            )
        except Exception:  # noqa: BLE001 - the injected failure
            pass
        # rollback left the OLD ring serving every row
        rows_after_rollback = coord.total_rows()
        # the "killed" server really dies; a respawn takes its place
        doomed.stop()
        respawn = FabricShardServer(dim=dim, num_slots=2,
                                    member="emb-3", seed=seed,
                                    host="127.0.0.1").start()
        servers.append(respawn)
        members4["emb-3"] = respawn.addr
        route = coord.scale(members4, migrate_retries=3)
        moved = int(_read_moved(journal_dir, version=route.version))
        client.refresh_route()
        for _ in range(4):
            client.apply("adam", ids,
                         rng.standard_normal((rows, dim)).astype(
                             np.float32), lr=1e-2)
        expected = client.export(with_slots=True)
        order = np.argsort(expected["keys"], kind="stable")
        expected_crc = zlib.crc32(
            expected["values"][order].tobytes()
        ) & 0xFFFFFFFF
        client.persist(8)    # emb-0's file bit-flips on the way down

        # sabotage the live tables so only a real restore can match
        for s in servers:
            if s.table is not None and len(s.table):
                snap = s.table.export(with_slots=False)
                s.table.remove(snap["keys"])
        restored = coord.restore()
        restored_step = restored["step"] if restored else None
        got = client.export(with_slots=True)
        order = np.argsort(got["keys"], kind="stable")
        restored_crc = zlib.crc32(
            got["values"][order].tobytes()
        ) & 0xFFFFFFFF
    finally:
        chaos.uninstall()
        if client is not None:
            client.close()
        if coord is not None:
            coord.stop()
        for s in servers:
            s.stop()
        if prev_journal is None:
            os.environ.pop(EnvKey.JOURNAL_DIR, None)
        else:
            os.environ[EnvKey.JOURNAL_DIR] = prev_journal
    from dlrover_tpu.telemetry.audit import assert_clean

    assert_clean(journal_dir, context="embedding scenario")
    return EmbeddingScenarioResult(
        moved=moved,
        total_rows=total,
        restored_step=restored_step,
        restored_crc=restored_crc,
        expected_crc=expected_crc,
        rows_after_rollback=rows_after_rollback,
        trail=fault_trail(journal_dir),
    )


def master_kill_trail(journal_dir: str) -> dict:
    """Canonical, replay-comparable trail of a master-kill scenario
    (DESIGN.md §26): master restarts (epoch sequence), agent epoch-fence
    reconciles, rendezvous rounds, autopilot retunes, snapshot
    rollbacks and rack sub-master failovers (§28) — occurrence-indexed
    and sorted like the chaos fault trail, so two seeded runs compare
    verbatim."""
    entries: list[list[Any]] = []
    for e in _read_journal(journal_dir):
        name = e.get("name")
        if name == "master_restore":
            entries.append(["master_restore", e.get("epoch", -1),
                            e.get("version", 0),
                            e.get("components", "")])
        elif name == "agent_reconcile":
            entries.append(["agent_reconcile", e.get("node", -1),
                            e.get("old_epoch", 0), e.get("new_epoch", 0)])
        elif name == "rdzv_round":
            entries.append(["rdzv_round", e.get("round", 0),
                            e.get("nodes", 0), bool(e.get("fast")),
                            bool(e.get("reshard"))])
        elif name == "autopilot_retune":
            entries.append(["autopilot_retune", e.get("from_plan", ""),
                            e.get("to_plan", ""), e.get("path", "")])
        elif name in ("state_rollback", "state_legacy_snapshot"):
            entries.append([name])
        elif name == "degraded_mode":
            entries.append(["degraded_mode", e.get("component", ""),
                            e.get("state", "")])
        elif name == "submaster_failover":
            entries.append(["submaster_failover", e.get("rack", ""),
                            e.get("old_epoch", 0),
                            e.get("new_epoch", 0)])
    counts: dict[str, int] = {}
    indexed: list[list[Any]] = []
    for entry in entries:
        key = json.dumps(entry)
        k = counts.get(key, 0)
        counts[key] = k + 1
        indexed.append(entry + [k])
    return {"events": sorted(indexed, key=json.dumps)}


@dataclasses.dataclass
class MasterKillScenarioResult:
    """What survived four SIGKILLs of the root master (§26
    acceptance) plus one SIGKILL of a rack sub-master (§28)."""

    epochs: list[int]              # epoch of each restarted master
    round_after_restart: int       # rendezvous round completed on M2
    commit_step: int | None        # newest verified step post-commit
    commit_writers: list[str]      # writers in the commit_w<W> manifest
    dense_writers: list[str]       # dense ledger writers (group "")
    embedding_writers: list[str]   # embedding ledger writers
    compile_cache_warm: bool       # CompileCacheGet hit after restart
    retune_events: int             # autopilot_retune journal lines
    retunes_used_final: int        # budget charged per the final state
    restart_actions: int           # "restart" actions agents received
    trail: dict
    # §28 sub-master kill leg: rack epoch before/after the SIGKILL and
    # the rendezvous round that completed THROUGH the respawned tier
    sub_epochs: list[int] = dataclasses.field(default_factory=list)
    sub_round: int = 0

    def assert_invariants(self) -> None:
        assert self.epochs == [2, 3, 4, 5], (
            f"master epochs not monotonic across restarts: {self.epochs}"
        )
        assert self.round_after_restart == 2, (
            "the mid-rendezvous restart did not continue the round "
            f"sequence (round {self.round_after_restart})"
        )
        assert self.commit_step == 4, (
            f"the in-flight step never committed (verified step "
            f"{self.commit_step})"
        )
        assert sorted(self.commit_writers) == ["0", "1"], (
            f"commit manifest incomplete: {self.commit_writers}"
        )
        assert sorted(self.dense_writers) == ["0", "1"] \
            and self.embedding_writers == ["emb-0"], (
            "restored ledger mixed the dense and embedding groups: "
            f"dense={self.dense_writers} emb={self.embedding_writers}"
        )
        assert self.compile_cache_warm, \
            "restarted master answered CompileCacheGet cold"
        assert self.retune_events == 1 and self.retunes_used_final == 1, (
            f"retune budget double-charged or phantom retune: "
            f"{self.retune_events} events, {self.retunes_used_final} used"
        )
        assert self.restart_actions == 0, (
            f"trainers were asked to restart {self.restart_actions} "
            "times during master failover"
        )
        # §28: the root mints the rack epoch above its own (5 after
        # four restarts), and the sub-master SIGKILL re-mints above the
        # predecessor — the fence the rack's agents reconcile on
        assert self.sub_epochs == [6, 7], (
            f"rack epochs not re-minted across the sub-master kill: "
            f"{self.sub_epochs}"
        )
        assert self.sub_round == 3, (
            "the round interrupted by the sub-master kill did not "
            f"complete through the respawned tier (round "
            f"{self.sub_round})"
        )


def run_master_kill_scenario(work_dir: str, *, seed: int = 4242
                             ) -> MasterKillScenarioResult:
    """SIGKILL a REAL master subprocess at three in-flight points —
    mid-rendezvous, mid-commit-wait, mid-autopilot-streak (plus once
    more post-retune to pin the budget) — and drive typed
    ``MasterClient`` agents through the §26 failover machinery: port
    re-resolve from the atomic port file, epoch-fence reconcile,
    redelivery replay, restored ack ledger/rendezvous/autopilot state.
    The kill points are state-based (the snapshot provably contains the
    in-flight mutation before the SIGKILL lands), so the trail is
    replay-identical across runs of the same seed.

    A fifth leg SIGKILLs a REAL rack sub-master (§28) mid-rendezvous-
    round: its agents re-resolve the rack's target-keyed port file,
    fence on the rack epoch the root re-mints, and the interrupted
    round completes through the respawned tier — zero trainer
    restarts, and the ``submaster_failover`` event lands in the same
    replay-comparable trail."""
    import zlib

    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.autopilot.planner import Plan
    from dlrover_tpu.checkpoint import integrity
    from dlrover_tpu.checkpoint.integrity import resolve_restore_step
    from dlrover_tpu.common.rpc import RpcClient
    from dlrover_tpu.common.storage import PosixDiskStorage

    os.makedirs(work_dir, exist_ok=True)
    state_dir = os.path.join(work_dir, "state")
    journal_dir = os.path.join(work_dir, "journal")
    ckpt_dir = os.path.join(work_dir, "ckpt")
    port_file = os.path.join(work_dir, "master.port")
    log_path = os.path.join(work_dir, "master.log")
    os.makedirs(state_dir, exist_ok=True)

    env = dict(os.environ)
    env.update({
        EnvKey.JOURNAL_DIR: journal_dir,
        EnvKey.TRACE_ID: f"mk{seed}",
        EnvKey.TRACE_SEED: f"mk:{seed}",
        # budget 1 makes "not double-charged" sharp: one retune total,
        # across however many master incarnations
        EnvKey.AUTOPILOT_MAX_RETUNES: "1",
        "PYTHONPATH": env.get("PYTHONPATH", "") + os.pathsep + REPO,
    })
    prev_env = {
        k: os.environ.get(k)
        for k in (EnvKey.MASTER_PORT_FILE, EnvKey.JOURNAL_DIR)
    }
    os.environ[EnvKey.MASTER_PORT_FILE] = port_file
    os.environ[EnvKey.JOURNAL_DIR] = journal_dir

    log = open(log_path, "ab")
    procs: list[subprocess.Popen] = []

    def spawn_master(prev_port: str) -> str:
        proc = subprocess.Popen(
            [sys.executable, "-m", "dlrover_tpu.master.job_master",
             "--job-name", "mk", "--min-nodes", "2", "--max-nodes", "2",
             "--rdzv-timeout", "60", "--state-dir", state_dir,
             "--port-file", port_file],
            env=env, cwd=REPO, stdout=log, stderr=log,
        )
        procs.append(proc)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"master exited early rc={proc.returncode}"
                )
            try:
                with open(port_file) as f:
                    text = f.read().strip()
                if text and text != prev_port:
                    return text
            except OSError:
                pass
            time.sleep(0.05)
        raise TimeoutError("master never published its port")

    def sigkill_master() -> None:
        proc = procs[-1]
        os.kill(proc.pid, 9)
        proc.wait(timeout=10)

    def read_state() -> dict:
        try:
            with open(os.path.join(state_dir, "mk.state.json")) as f:
                wrapped = json.load(f)
            return json.loads(wrapped["body"])
        except (OSError, ValueError, KeyError):
            return {}

    def wait_state(pred, what: str, timeout: float = 15.0) -> dict:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            state = read_state()
            if state and pred(state):
                return state
            time.sleep(0.05)
        raise TimeoutError(f"master snapshot never showed: {what}")

    actions: list[str] = []

    def reconnect(agent: MasterClient, timeout: float = 20.0) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            agent.maybe_redial()
            try:
                actions.append(agent.report_heartbeat(0))
                return
            except (ConnectionError, TimeoutError, OSError):
                time.sleep(0.1)
        raise TimeoutError("agent could not reconnect to the master")

    def trainer_push(agent: MasterClient, cum: list[float]) -> None:
        # one trainer-role snapshot whose step-histogram delta reads as
        # 1.0 s/step — 10x the armed plan's 0.1 s prediction
        cum[0] += 1.0
        cum[1] += 1
        agent.report_metrics([{
            "name": "dlrover_tpu_train_step_seconds",
            "type": "histogram", "help": "", "buckets": [],
            "samples": [{"labels": {}, "buckets": [],
                         "sum": cum[0], "count": int(cum[1])}],
        }], role="trainer")

    a0 = a1 = ra0 = ra1 = None
    try:
        port = spawn_master("")
        addr = f"127.0.0.1:{port}"

        def make_agent(nid: int) -> MasterClient:
            return MasterClient(
                addr, nid,
                transport=RpcClient(addr, retries=2, deadline_s=4.0,
                                    backoff_base_s=0.05,
                                    backoff_max_s=0.2),
            )

        a0, a1 = make_agent(0), make_agent(1)
        a0.join_rendezvous("127.0.0.1:7770", 4)
        a1.join_rendezvous("127.0.0.1:7771", 4)
        assert a0.wait_comm_world(timeout=30).round == 1
        actions.append(a0.report_heartbeat(0))
        actions.append(a1.report_heartbeat(0))
        # the artifact a restarted master must keep serving warm
        blob = (b"mkblob" * 11)[: 64]
        a0.compile_cache_put(f"n2t8/mk{seed % 100:02d}", blob,
                             {"seed": seed})

        # ---- kill 1: mid-rendezvous (a respawned node has re-joined,
        # its peer has not) -------------------------------------------
        a0.join_rendezvous("127.0.0.1:7770", 4)

        def _mid_rendezvous(s: dict) -> bool:
            # the kill must land with the FULL in-flight picture
            # durable: round 1 completed, node 0 re-joined (round
            # invalidated), and the compile-cache artifact spilled —
            # an earlier snapshot (round 0's join) also shows node 0
            # waiting and would make the trail non-deterministic
            rdzv = s.get("rendezvous", {}).get("training", {})
            return (
                int(rdzv.get("round", 0)) == 1
                and [int(w.get("node_id", -1))
                     for w in rdzv.get("waiting", ())] == [0]
                and bool(s.get("compile_cache"))
            )

        wait_state(_mid_rendezvous, "round 1 + node 0 re-joined + "
                                    "spilled compile cache")
        sigkill_master()
        spawn_master(port)
        reconnect(a1)
        a1.join_rendezvous("127.0.0.1:7771", 4)
        w0 = a0.wait_comm_world(timeout=30)
        w1 = a1.wait_comm_world(timeout=30)
        assert w0.round == w1.round, "agents disagree on the round"
        round_after_restart = w0.round
        epochs = [a0.master_epoch]
        warm = a0.compile_cache_get(f"n2t8/mk{seed % 100:02d}")
        compile_cache_warm = warm is not None and warm[0] == blob
        port = open(port_file).read().strip()

        # ---- kill 2: mid-commit-wait (one dense writer + the
        # embedding fabric have acked; the other dense writer has not) -
        sdir = os.path.join(ckpt_dir, "step-4")
        entries: dict[str, dict] = {}
        for nid in (0, 1):
            payload = bytes([seed % 256, nid]) * 64
            atomic_write_file(payload,
                              os.path.join(sdir, f"node_{nid}.bin"))
            atomic_write_file(json.dumps({"metas": {}}),
                              os.path.join(sdir,
                                           f"node_{nid}.meta.json"))
            entries[str(nid)] = {
                "crc32": zlib.crc32(payload) & 0xFFFFFFFF,
                "bytes": len(payload), "pieces": {},
            }
        a0.report_persist_ack(4, 1, {"crc32": 1, "bytes": 8},
                              writer_id="emb-0", group="embedding")
        a1.report_persist_ack(4, 2, entries["1"])
        wait_state(
            lambda s: {
                (e["group"], w)
                for e in s.get("persist_acks", {}).get("acks", ())
                for w in e.get("shards", {})
            } >= {("embedding", "emb-0"), ("", "1")},
            "embedding + dense acks in the ledger",
        )
        sigkill_master()
        spawn_master(port)
        reconnect(a0)
        reconnect(a1)
        a0.report_persist_ack(4, 2, entries["0"])
        dense = a0.persist_status(4, 2)
        emb = a1.persist_status(4, 1, group="embedding")
        dense_writers = sorted(dense.shards)
        embedding_writers = sorted(emb.shards)
        commit_step = None
        commit_writers: list[str] = []
        if dense.complete:
            # rank-0's commit wait completes against the RESTORED
            # ledger: the terminal manifest lands, the tracker moves
            storage = PosixDiskStorage()
            integrity.write_commit(storage, sdir, 4, 2,
                                   dict(dense.shards))
            storage.write(json.dumps({"step": 4, "num_shards": 2}),
                          os.path.join(ckpt_dir, "latest"))
            got = resolve_restore_step(storage, ckpt_dir)
            if got is not None:
                commit_step = got[0]
            with open(os.path.join(sdir, "commit_w2")) as f:
                commit_writers = sorted(
                    json.load(f).get("shards", {}))
        epochs.append(a0.master_epoch)
        port = open(port_file).read().strip()

        # ---- kill 3: mid-autopilot-streak (armed plan + a building
        # contradiction streak, retune not yet fired) ------------------
        plan = Plan(name="mk-a", schedule="spmd",
                    mesh_axes={"data": 1}, pred_step_s=0.1,
                    source="history", fingerprint="mk-a", n_devices=1)
        alt = Plan(name="mk-b", schedule="spmd",
                   mesh_axes={"data": 1}, pred_step_s=0.1,
                   source="history", fingerprint="mk-b", n_devices=1,
                   rank=1)
        a0.report_autopilot_plan(plan.to_json(), [alt.to_json()],
                                 step_batch=8)
        cum = [0.0, 0.0]
        for _ in range(4):      # streak 2 of the 3 needed: mid-flight
            trainer_push(a0, cum)
        wait_state(lambda s: s.get("autopilot", {}).get("plan"),
                   "armed autopilot plan")
        sigkill_master()
        spawn_master(port)
        reconnect(a0)
        for _ in range(5):      # re-earn the contradiction: ONE retune
            trainer_push(a0, cum)
        cfg = a0.get_paral_config()
        assert cfg.autopilot_plan, "retune never reached paral config"
        for _ in range(4):      # budget spent: must NOT retune again
            trainer_push(a0, cum)
        state = wait_state(
            lambda s: s.get("autopilot", {}).get("retunes_used", 0) >= 1,
            "charged retune budget",
        )
        epochs.append(a0.master_epoch)
        port = open(port_file).read().strip()

        # ---- kill 4: post-retune — the restored budget must read as
        # SPENT (no phantom second retune) -----------------------------
        sigkill_master()
        spawn_master(port)
        reconnect(a0)
        for _ in range(5):
            trainer_push(a0, cum)
        state = wait_state(
            lambda s: s.get("autopilot", {}).get("retunes_used", 0) >= 1,
            "retune budget restored as spent",
        )
        retunes_used_final = int(
            state.get("autopilot", {}).get("retunes_used", 0))
        epochs.append(a0.master_epoch)

        # ---- kill 5 (§28): SIGKILL the rack SUB-MASTER mid-
        # rendezvous-round. The rack tier's own failover: agents
        # re-resolve the rack's target-keyed port file, fence on the
        # rack epoch the root re-mints, and the interrupted round
        # completes — with zero trainer restarts --------------------
        rack_port_file = os.path.join(work_dir, "rack.port")
        port = open(port_file).read().strip()
        root_addr = f"127.0.0.1:{port}"
        # the sub-master's upstream redial resolves the ROOT's port
        # file; the parent set it in os.environ after ``env`` was taken
        sub_env = dict(env)
        sub_env[EnvKey.MASTER_PORT_FILE] = port_file

        def spawn_submaster(prev_port: str) -> str:
            proc = subprocess.Popen(
                [sys.executable, "-m", "dlrover_tpu.master.submaster",
                 "--rack-id", "rackA", "--master-addr", root_addr,
                 "--port-file", rack_port_file,
                 "--flush-interval", "0.1"],
                env=sub_env, cwd=REPO, stdout=log, stderr=log,
            )
            procs.append(proc)
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if proc.poll() is not None:
                    raise RuntimeError(
                        f"sub-master exited early rc={proc.returncode}"
                    )
                try:
                    with open(rack_port_file) as f:
                        text = f.read().strip()
                    if text and text != prev_port:
                        return text
                except OSError:
                    pass
                time.sleep(0.05)
            raise TimeoutError("sub-master never published its port")

        rack_port = spawn_submaster("")

        def make_rack_agent(nid: int) -> MasterClient:
            rack_addr = f"127.0.0.1:{rack_port}"
            return MasterClient(
                rack_addr, nid,
                transport=RpcClient(rack_addr, retries=2,
                                    deadline_s=4.0,
                                    backoff_base_s=0.05,
                                    backoff_max_s=0.2),
                port_file=rack_port_file,
                fallback_port_file=port_file,
            )

        ra0, ra1 = make_rack_agent(0), make_rack_agent(1)
        actions.append(ra0.report_heartbeat(0))
        actions.append(ra1.report_heartbeat(0))
        sub_epochs = [ra0.master_epoch]
        # node 0 re-joins THROUGH the rack: buffered at the sub-master
        # and pushed upstream as a RackJoinRequest batch at its flush
        ra0.join_rendezvous("127.0.0.1:7770", 4)

        def _rack_join_pushed(s: dict) -> bool:
            # the kill must land mid-round with the rack's join durable
            # at the ROOT (round 2 invalidated, node 0 waiting): the
            # in-flight picture the respawned tier completes from
            rdzv = s.get("rendezvous", {}).get("training", {})
            return (
                int(rdzv.get("round", 0)) == 2
                and [int(w.get("node_id", -1))
                     for w in rdzv.get("waiting", ())] == [0]
                and bool(s.get("racks", {}).get("epochs"))
            )

        wait_state(_rack_join_pushed,
                   "rack join pushed upstream mid-round")
        sub_proc = procs[-1]
        os.kill(sub_proc.pid, 9)
        sub_proc.wait(timeout=10)
        rack_port = spawn_submaster(rack_port)
        reconnect(ra0)
        reconnect(ra1)
        # the respawned incarnation lost its buffered join floors:
        # re-join (idempotent at the root — newest join wins) so the
        # sub serves these agents the NEW round, never a stale mirror
        ra0.join_rendezvous("127.0.0.1:7770", 4)
        ra1.join_rendezvous("127.0.0.1:7771", 4)
        rw0 = ra0.wait_comm_world(timeout=30)
        rw1 = ra1.wait_comm_world(timeout=30)
        assert rw0.round == rw1.round, \
            "rack agents disagree on the post-failover round"
        sub_round = rw0.round
        actions.append(ra0.report_heartbeat(0))
        actions.append(ra1.report_heartbeat(0))
        sub_epochs.append(ra0.master_epoch)
    finally:
        for proc in procs:
            try:
                proc.kill()
                proc.wait(timeout=5)
            except (ProcessLookupError, subprocess.TimeoutExpired):
                pass
        for agent in (a0, a1, ra0, ra1):
            if agent is not None:
                agent.close()
        log.close()
        for key, value in prev_env.items():
            if value is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = value

    retune_events = sum(
        1 for e in _read_journal(journal_dir)
        if e.get("name") == "autopilot_retune"
    )
    from dlrover_tpu.telemetry.audit import assert_clean

    assert_clean(journal_dir, context="master-kill scenario")
    return MasterKillScenarioResult(
        epochs=epochs,
        round_after_restart=round_after_restart,
        commit_step=commit_step,
        commit_writers=commit_writers,
        dense_writers=dense_writers,
        embedding_writers=embedding_writers,
        compile_cache_warm=compile_cache_warm,
        retune_events=retune_events,
        retunes_used_final=retunes_used_final,
        restart_actions=sum(1 for a in actions if a == "restart"),
        trail=master_kill_trail(journal_dir),
        sub_epochs=sub_epochs,
        sub_round=sub_round,
    )


def _read_moved(journal_dir: str, version: int) -> int:
    """Moved-row count of the ``embedding_scale`` event that committed
    ``version`` (the journal is the scale's evidence of record)."""
    for e in _read_journal(journal_dir):
        if e.get("name") == "embedding_scale" and e.get("ok") \
                and int(e.get("version", -1)) == version:
            return int(e.get("moved", -1))
    return -1


def canned_scenario(seed: int = 1234, *, kill_step: int = 7,
                    save_interval: int = 6, max_steps: int = 14,
                    resume_steps: int = 20) -> Scenario:
    """The acceptance schedule: trainer SIGKILLed mid-save (an injected
    slow fsync stretches the step-``save_interval`` persist so the kill
    provably lands inside it), the newest shard bit-flipped on its way
    to disk, and the master RPC flaking on the post-kill re-join. Leg 2
    restores from storage in a fresh process tree and must roll back to
    the newest verified step.
    """
    leg1 = JobLeg(
        name="train_kill_mid_save",
        max_steps=max_steps,
        faults=[
            {"point": "storage_write", "action": "slow_fsync",
             "args": {"s": 2.0},
             "match": {"path_contains": f"step-{save_interval}/",
                       "path_suffix": ".bin"},
             "times": 1},
            {"point": "agent_kill_trainer", "action": "kill",
             "args": {"sig": 9},
             "match": {"step_gte": kill_step}, "times": 1},
            {"point": "rpc_call", "action": "drop",
             "match": {"msg": "JoinRendezvousRequest"},
             "after": 1, "times": 1},
            {"point": "storage_write", "action": "bit_flip",
             "match": {"path_contains": f"step-{max_steps}/",
                       "path_suffix": ".bin"},
             "times": 1},
        ],
        train_args=["--ckpt-interval", str(save_interval),
                    "--mem-ckpt-interval", "2", "--step-delay", "0.15"],
    )
    leg2 = JobLeg(
        name="restore_verify_rollback",
        max_steps=resume_steps,
        faults=[],
        train_args=["--ckpt-interval", str(save_interval),
                    "--mem-ckpt-interval", "2"],
    )
    return Scenario(name="kill_flip_flake", seed=seed, legs=[leg1, leg2])
