"""Agent-side hang detection: unit logic + wedged-trainer e2e.

Reference analog: atorch/atorch/fault_tolerance/hanging_detector.py:86
(progress-timeout relaunch) — unit-tested with an injected clock, then
driven end-to-end: a trainer that wedges mid-run is killed by the agent
and the job completes on the restart.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import pytest

from dlrover_tpu.agent.hang_detector import HangDetector, ProgressReporter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLE = os.path.join(REPO, "examples", "train_transformer.py")


class TestHangDetector:
    def test_startup_grace_then_hang(self, tmp_ipc_dir):
        d = HangDetector(node_id=5, timeout_s=10, startup_grace_s=30)
        d.reset()
        t0 = time.monotonic()
        assert not d.check(now=t0 + 29)       # still in grace
        assert d.check(now=t0 + 31)           # no report ever -> hung

    def test_progress_then_stall(self, tmp_ipc_dir):
        rep = ProgressReporter(node_id=6, min_interval_s=0)
        d = HangDetector(node_id=6, timeout_s=10, startup_grace_s=30)
        d.reset()
        t0 = time.monotonic()
        rep.report(3)
        assert not d.check(now=t0 + 100)      # fresh progress resets
        assert d.last_step() == 3
        # same step rewritten: NOT progress
        rep.report(3)
        assert not d.check(now=t0 + 105)      # within timeout of advance
        assert d.check(now=t0 + 111)          # stalled past timeout
        # step advances again: recovers
        rep.report(4)
        assert not d.check(now=t0 + 200)

    def test_reset_clears_stale_file(self, tmp_ipc_dir):
        rep = ProgressReporter(node_id=7, min_interval_s=0)
        rep.report(42)
        d = HangDetector(node_id=7, timeout_s=5, startup_grace_s=30)
        d.reset()  # a new incarnation must not credit the old file's step
        assert not os.path.exists(
            __import__(
                "dlrover_tpu.agent.hang_detector",
                fromlist=["progress_path"],
            ).progress_path(7)
        )

    def test_reporter_rate_limit(self, tmp_ipc_dir):
        from dlrover_tpu.agent.hang_detector import progress_path

        rep = ProgressReporter(node_id=8, min_interval_s=3600)
        rep.report(1)
        rep.report(2)  # dropped by the rate limit
        data = json.load(open(progress_path(8)))
        assert data["step"] == 1


@pytest.mark.timeout(300)
# slow tier (tier-1 envelope): among the heaviest bodies in this
# file on XLA:CPU; core behavior stays covered by the lighter
# tests in-tier. `pytest tests/` still runs it.
@pytest.mark.slow
def test_wedged_trainer_restarted_by_agent(tmp_path):
    """e2e: trainer wedges at step 8; the agent's detector kills it; the
    restart resumes from the shm snapshot and completes the run."""
    result_file = str(tmp_path / "result.json")
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "DLROVER_TPU_DEVICE_COUNT": "1",
        "DLROVER_TPU_IPC_DIR": str(tmp_path / "ipc"),
        "PYTHONPATH": REPO,
    })
    cmd = [
        sys.executable, "-m", "dlrover_tpu.run", "--standalone",
        "--monitor-interval", "0.3", "--max-restarts", "2",
        "--hang-timeout", "4", "--hang-startup-grace", "120",
        EXAMPLE, "--",
        "--model", "tiny", "--global-batch", "8", "--seq", "128",
        "--log-interval", "5", "--ckpt-dir", str(tmp_path / "ckpt"),
        "--result-file", result_file,
        "--max-steps", "20", "--hang-at-step", "8",
    ]
    proc = subprocess.run(
        cmd, env=env, cwd=REPO, timeout=280,
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.load(open(result_file))
    assert result["final_step"] == 20
    assert result["restart_count"] == 1
    # the detector reported the wedge before killing
    assert "hang detected" in proc.stderr or "wedged" in proc.stderr, \
        proc.stderr[-2000:]
