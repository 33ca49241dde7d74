"""Goodput accounting: aggregator math + live monitor + elastic e2e.

The reference's headline claim is goodput 69% -> 95% via elastic fault
tolerance (dlrover README.md:54-55). utils/goodput.py implements the
accounting; bench.py publishes the on-chip number. These tests pin the
math on synthetic logs and prove the end-to-end flow (trainer writes
events across incarnations, aggregator dedups rolled-back steps) on the
CPU mesh.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from dlrover_tpu.master.speed_monitor import SpeedMonitor
from dlrover_tpu.utils.goodput import (
    GoodputRecorder,
    compute_goodput,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLE = os.path.join(REPO, "examples", "train_transformer.py")


def _write_log(path, events):
    with open(path, "w") as f:
        for ev in events:
            f.write(json.dumps(ev) + "\n")


def test_steady_run_has_goodput_near_one(tmp_path):
    log = tmp_path / "g.jsonl"
    events = [{"ev": "start", "t": 100.0, "restart": 0}]
    # 10s compile inside step 1, then 20 steady 1s steps
    events.append({"ev": "step", "step": 1, "t": 110.0})
    for i in range(2, 22):
        events.append({"ev": "step", "step": i, "t": 110.0 + (i - 1)})
    _write_log(log, events)
    r = compute_goodput(str(log))
    assert r.n_steps == 21
    assert r.n_incarnations == 1
    assert r.median_step_s == pytest.approx(1.0)
    # warm window: first step onward (21s of window, 21 credited steps)
    assert r.goodput == pytest.approx(1.0, abs=0.01)
    # cold window includes the 10s compile: 21 / 30
    assert r.goodput_cold == pytest.approx(21.0 / 30.0, abs=0.01)


def test_restart_gap_and_redone_steps_count_as_lost(tmp_path):
    log = tmp_path / "g.jsonl"
    events = [{"ev": "start", "t": 0.0, "restart": 0}]
    # steps 1..10 at 1s each
    for i in range(1, 11):
        events.append({"ev": "step", "step": i, "t": float(i)})
    # crash; restart at t=30 (20s lost), resume from ckpt at step 8:
    # steps 9,10 are RE-executed (their first runs are waste)
    events.append({"ev": "start", "t": 30.0, "restart": 1})
    for j, step in enumerate([9, 10, 11, 12, 13, 14]):
        events.append({"ev": "step", "step": step, "t": 31.0 + j})
    _write_log(log, events)
    r = compute_goodput(str(log))
    assert r.n_incarnations == 2
    assert r.n_steps == 14
    assert r.redone_steps == 2
    assert r.median_step_s == pytest.approx(1.0)
    # warm window: t=0 (first step at 1.0 minus median) .. t=36 -> 36s,
    # 14 credited steps
    assert r.total_s == pytest.approx(36.0, abs=0.01)
    assert r.goodput == pytest.approx(14.0 / 36.0, abs=0.01)
    assert r.lost_s == pytest.approx(22.0, abs=0.1)


def test_external_window_widens_total(tmp_path):
    log = tmp_path / "g.jsonl"
    _write_log(log, [
        {"ev": "start", "t": 10.0, "restart": 0},
        {"ev": "step", "step": 1, "t": 11.0},
        {"ev": "step", "step": 2, "t": 12.0},
        {"ev": "done", "t": 12.0},
    ])
    r = compute_goodput(str(log), start_time=0.0, end_time=20.0)
    assert r.total_cold_s == pytest.approx(20.0)
    assert r.goodput_cold == pytest.approx(2.0 / 20.0, abs=0.01)


def test_recorder_round_trip_and_torn_tail(tmp_path):
    log = tmp_path / "g.jsonl"
    rec = GoodputRecorder(str(log), restart_count=0)
    for i in range(1, 6):
        rec.step(i)
    rec.close()
    # simulate a SIGKILL mid-write: torn trailing line must be ignored
    with open(log, "a") as f:
        f.write('{"ev": "step", "step": 6, "t": 1')
    r = compute_goodput(str(log))
    assert r.n_steps == 5
    assert r.n_incarnations == 1


def test_multi_log_picks_most_complete(tmp_path):
    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    _write_log(a, [
        {"ev": "start", "t": 0.0},
        {"ev": "step", "step": 1, "t": 1.0},
    ])
    _write_log(b, [
        {"ev": "start", "t": 0.0},
        {"ev": "step", "step": 1, "t": 1.0},
        {"ev": "step", "step": 2, "t": 2.0},
    ])
    r = compute_goodput([str(a), str(b)])
    assert r.n_steps == 2


def test_empty_log_raises(tmp_path):
    log = tmp_path / "g.jsonl"
    log.write_text("")
    with pytest.raises(ValueError):
        compute_goodput(str(log))


def test_speed_monitor_live_goodput():
    mon = SpeedMonitor()
    t0 = mon._start_time
    # 10 steps at 1s cadence
    for i in range(1, 11):
        mon.report_step(i, timestamp=t0 + i)
    assert mon.goodput(now=t0 + 10) == pytest.approx(1.0, abs=0.05)
    # 20s outage (rollback to step 8, re-reports don't advance)
    mon.report_step(8, timestamp=t0 + 30)
    for i in range(9, 16):
        mon.report_step(i, timestamp=t0 + 30 + (i - 8))
    g = mon.goodput(now=t0 + 37)
    assert 0.3 < g < 0.55  # ~15 productive seconds over 37


@pytest.mark.timeout(300)
def test_e2e_goodput_log_across_crash(tmp_path):
    """Standalone elastic run with an injected crash: the goodput log
    spans both incarnations and the aggregator sees the rollback."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "DLROVER_TPU_DEVICE_COUNT": "1",
        "DLROVER_TPU_IPC_DIR": str(tmp_path / "ipc"),
        "PYTHONPATH": REPO,
    })
    log = str(tmp_path / "goodput.jsonl")
    result_file = str(tmp_path / "result.json")
    cmd = [
        sys.executable, "-m", "dlrover_tpu.run", "--standalone",
        "--monitor-interval", "0.3", "--max-restarts", "2",
        EXAMPLE, "--",
        "--model", "tiny", "--global-batch", "8", "--seq", "128",
        "--max-steps", "20", "--crash-at-step", "8",
        "--ckpt-dir", str(tmp_path / "ckpt"),
        "--goodput-log", log, "--result-file", result_file,
        "--log-interval", "5",
    ]
    proc = subprocess.run(cmd, env=env, cwd=REPO, timeout=280,
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    result = json.load(open(result_file))
    assert result["final_step"] == 20

    r = compute_goodput(log)
    assert r.n_incarnations == 2
    assert r.n_steps == 20
    # crash at step 8 after the step-7 snapshot: step 8 re-executes
    assert r.redone_steps >= 1
    assert 0.0 < r.goodput <= 1.0
