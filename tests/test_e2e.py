"""End-to-end: CLI -> standalone master -> agent -> training subprocess.

Reference analog: the agent e2e tests against a local master
(dlrover/python/tests/test_elastic_training_agent.py with
start_local_master, SURVEY.md §4) and the chaosblade process-kill scenario
(docs/tech_report/fault_tolerance_exps.md) — here as hermetic subprocess
tests: inject a crash (or SIGKILL) into the trainer and assert automatic
re-rendezvous + restore-from-shm + run completion.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLE = os.path.join(REPO, "examples", "train_transformer.py")


def _env(tmp_path) -> dict:
    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",  # children force the CPU backend
            "DLROVER_TPU_DEVICE_COUNT": "1",
            "DLROVER_TPU_IPC_DIR": str(tmp_path / "ipc"),
            "PYTHONPATH": REPO,
        }
    )
    return env


def _cli_cmd(tmp_path, cli_args: list[str], train_args: list[str]
             ) -> tuple[list[str], str]:
    result_file = str(tmp_path / "result.json")
    cmd = [
        sys.executable, "-m", "dlrover_tpu.run", "--standalone",
        "--monitor-interval", "0.3", *cli_args,
        EXAMPLE, "--",
        # conftest's XLA_FLAGS reaches the children: the trainer sees 8
        # virtual CPU devices, so the batch shards dp=8
        "--model", "tiny", "--global-batch", "8", "--seq", "128",
        "--log-interval", "5",
        "--ckpt-dir", str(tmp_path / "ckpt"),
        "--result-file", result_file,
        *train_args,
    ]
    return cmd, result_file


@pytest.mark.timeout(300)
def test_cli_standalone_trains_to_completion(tmp_path):
    cmd, result_file = _cli_cmd(tmp_path, [], ["--max-steps", "10"])
    proc = subprocess.run(
        cmd, env=_env(tmp_path), cwd=REPO, timeout=280,
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.load(open(result_file))
    assert result["final_step"] == 10
    assert result["resumed_from"] == 0
    assert result["restart_count"] == 0


@pytest.mark.timeout(300)
def test_injected_crash_recovers_from_shm(tmp_path):
    cmd, result_file = _cli_cmd(
        tmp_path, ["--max-restarts", "2"],
        ["--max-steps", "20", "--crash-at-step", "8"],
    )
    proc = subprocess.run(
        cmd, env=_env(tmp_path), cwd=REPO, timeout=280,
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.load(open(result_file))
    assert result["final_step"] == 20
    # restored from the shm snapshot taken just before the crash
    assert result["resumed_from"] >= 6
    assert result["restart_count"] == 1


@pytest.mark.timeout(300)
def test_sigkill_recovers(tmp_path):
    """External SIGKILL of the training process (chaosblade process-kill)."""
    marker = f"sigkill-{os.getpid()}"
    cmd, result_file = _cli_cmd(
        tmp_path, ["--max-restarts", "2", "--job-name", marker],
        ["--max-steps", "40", "--dataset-size", "4000"],
    )
    proc = subprocess.Popen(
        cmd, env=_env(tmp_path), cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    try:
        # wait until the trainer reports progress, then kill -9 it. The
        # pattern must match only the trainer child — the CLI's own cmdline
        # also contains the script path (as an argument after -m
        # dlrover_tpu.run), and killing the CLI orphans its children.
        killed = False
        deadline = time.time() + 240
        while time.time() < deadline and proc.poll() is None:
            if not killed:
                out = subprocess.run(
                    ["pgrep", "-f", f"^{sys.executable} {EXAMPLE}"],
                    capture_output=True, text=True,
                )
                from dlrover_tpu.agent.standby import parked_standby_pids

                # never aim the kill at the parked warm standby (same
                # cmdline as the live trainer)
                standbys = parked_standby_pids(str(tmp_path / "ipc"))
                pids = [int(p) for p in out.stdout.split()
                        if int(p) not in standbys]
                ckpt_meta = tmp_path / "ckpt" / "latest"
                if pids and ckpt_meta.exists():
                    # a snapshot exists: safe to kill and still recover
                    os.kill(pids[0], signal.SIGKILL)
                    killed = True
            time.sleep(0.5)
        stdout, _ = proc.communicate(timeout=60)
        assert killed, f"never found a trainer to kill:\n{stdout[-3000:]}"
        assert proc.returncode == 0, stdout[-3000:]
        result = json.load(open(result_file))
        assert result["final_step"] == 40
        assert result["restart_count"] >= 1
        assert result["resumed_from"] > 0
    finally:
        if proc.poll() is None:
            proc.kill()
        # the standalone master runs in its own session; don't leak it if
        # the run went sideways
        subprocess.run(["pkill", "-f", f"job-name {marker}"],
                       capture_output=True)


@pytest.mark.timeout(300)
# slow tier (tier-1 envelope): among the heaviest bodies in this
# file on XLA:CPU; core behavior stays covered by the lighter
# tests in-tier. `pytest tests/` still runs it.
@pytest.mark.slow
def test_fsdp_sharded_ckpt_crash_recovers(tmp_path):
    """FSDP strategy + per-shard snapshots: crash -> reshard-on-load."""
    cmd, result_file = _cli_cmd(
        tmp_path, ["--max-restarts", "2"],
        ["--max-steps", "20", "--crash-at-step", "8",
         "--strategy", "fsdp", "--sharded-ckpt"],
    )
    proc = subprocess.run(
        cmd, env=_env(tmp_path), cwd=REPO, timeout=280,
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.load(open(result_file))
    assert result["final_step"] == 20
    assert result["resumed_from"] >= 6
    assert result["restart_count"] == 1


@pytest.mark.timeout(480)
# slow tier (tier-1 envelope): among the heaviest bodies in this
# file on XLA:CPU; core behavior stays covered by the lighter
# tests in-tier. `pytest tests/` still runs it.
@pytest.mark.slow
def test_pipeline_strategy_crash_recovers(tmp_path):
    """GPipe pipeline strategy: crash mid-run -> restore + completion
    (recovery must hold for pipeline-sharded state, not just dp/fsdp).
    Generous budget: the pipeline program compiles once per incarnation."""
    cmd, result_file = _cli_cmd(
        tmp_path, ["--max-restarts", "2"],
        ["--max-steps", "12", "--crash-at-step", "5",
         "--strategy", "pipeline"],
    )
    proc = subprocess.run(
        cmd, env=_env(tmp_path), cwd=REPO, timeout=460,
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.load(open(result_file))
    assert result["final_step"] == 12
    assert result["resumed_from"] >= 3
    assert result["restart_count"] == 1


@pytest.mark.timeout(300)
# slow tier (tier-1 envelope): among the heaviest bodies in this
# file on XLA:CPU; core behavior stays covered by the lighter
# tests in-tier. `pytest tests/` still runs it.
@pytest.mark.slow
def test_network_check_then_train(tmp_path):
    """--network-check runs the probe rendezvous + payload before training."""
    cmd, result_file = _cli_cmd(
        tmp_path, ["--network-check"], ["--max-steps", "5"],
    )
    proc = subprocess.run(
        cmd, env=_env(tmp_path), cwd=REPO, timeout=280,
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.load(open(result_file))
    assert result["final_step"] == 5


@pytest.mark.timeout(300)
# slow tier (tier-1 envelope): among the heaviest bodies in this file —
# the exit-code ladder / parity it exercises is also unit-covered.
# `pytest tests/` still runs it.
@pytest.mark.slow
def test_restarts_exhausted_fails_job(tmp_path):
    cmd, result_file = _cli_cmd(
        tmp_path, ["--max-restarts", "1"],
        ["--max-steps", "20", "--crash-at-step", "6", "--crash-always"],
    )
    proc = subprocess.run(
        cmd, env=_env(tmp_path), cwd=REPO, timeout=280,
        capture_output=True, text=True,
    )
    assert proc.returncode == 1, proc.stdout[-2000:]
    assert not os.path.exists(result_file)


@pytest.mark.timeout(300)
# slow tier (tier-1 envelope): among the heaviest bodies in this file —
# the exit-code ladder / parity it exercises is also unit-covered.
# `pytest tests/` still runs it.
@pytest.mark.slow
def test_oom_exit_restarts_in_place(tmp_path):
    """Exit code 210 (OOM contract) restarts and recovers like software."""
    cmd, result_file = _cli_cmd(
        tmp_path, ["--max-restarts", "2"],
        ["--max-steps", "16", "--crash-at-step", "6", "--crash-exit", "210"],
    )
    proc = subprocess.run(
        cmd, env=_env(tmp_path), cwd=REPO, timeout=280,
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.load(open(result_file))
    assert result["final_step"] == 16
    # >= 1: the OOM restart, plus possibly a paral-config restart when the
    # master's grad-accum suggestion lands before the run finishes
    assert result["restart_count"] >= 1


@pytest.mark.timeout(300)
# slow tier (tier-1 envelope): among the heaviest bodies in this file —
# the exit-code ladder / parity it exercises is also unit-covered.
# `pytest tests/` still runs it.
@pytest.mark.slow
def test_hardware_exit_escalates_to_node_relaunch(tmp_path):
    """Exit code 211 -> agent exits with the node-relaunch code (3) after
    persisting the snapshot, instead of restarting on the bad host."""
    cmd, result_file = _cli_cmd(
        tmp_path, ["--max-restarts", "3"],
        ["--max-steps", "30", "--crash-at-step", "6", "--crash-exit", "211"],
    )
    proc = subprocess.run(
        cmd, env=_env(tmp_path), cwd=REPO, timeout=280,
        capture_output=True, text=True,
    )
    assert proc.returncode == 3, (proc.returncode, proc.stdout[-2000:])
    assert not os.path.exists(result_file)
    # the breakpoint snapshot was persisted for the replacement host
    assert (tmp_path / "ckpt" / "latest").exists()
