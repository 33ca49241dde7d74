"""Opt-in elastic soak: repeated random SIGKILLs over a long 2-node run.

Gated behind DLROVER_TPU_SOAK=1 (≈6-8 min wall): the CI-speed kill
scenarios live in test_multinode_e2e.py; this drives MANY kills against
one job to surface races that single-kill tests can't (validated in r03:
5 kills, 900/900 steps, both launchers exit 0).
"""

from __future__ import annotations

import json
import os
import random
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLE = os.path.join(REPO, "examples", "train_transformer.py")

pytestmark = pytest.mark.skipif(
    os.environ.get("DLROVER_TPU_SOAK") != "1",
    reason="soak is opt-in: set DLROVER_TPU_SOAK=1 (~8 min)",
)


@pytest.mark.timeout(900)
def test_soak_many_kills(tmp_path):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "DLROVER_TPU_DEVICE_COUNT": "4",
        "DLROVER_TPU_IPC_DIR": str(tmp_path / "ipc"),
        "PYTHONPATH": REPO,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
    })
    port_file = str(tmp_path / "port")
    master = subprocess.Popen(
        [sys.executable, "-m", "dlrover_tpu.master.job_master",
         "--min-nodes", "2", "--max-nodes", "2",
         "--port-file", port_file],
        env=env, cwd=REPO, start_new_session=True,
        stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT,
    )
    deadline = time.time() + 30
    while not (os.path.exists(port_file)
               and open(port_file).read().strip()):
        assert time.time() < deadline, "master did not start"
        time.sleep(0.2)
    addr = "127.0.0.1:" + open(port_file).read().strip()

    def launcher(nid):
        return subprocess.Popen(
            [sys.executable, "-m", "dlrover_tpu.run",
             "--master-addr", addr, "--node-id", str(nid),
             "--nnodes", "2", "--monitor-interval", "0.3",
             "--max-restarts", "10",
             EXAMPLE, "--",
             "--model", "tiny", "--seq", "128", "--global-batch", "8",
             "--ckpt-dir", str(tmp_path / "ckpt"),
             "--dataset-size", "400000", "--epochs", "1000",
             "--max-steps", "900", "--mem-ckpt-interval", "10",
             "--ckpt-interval", "200", "--step-delay", "0.03",
             "--result-file", str(tmp_path / f"result_{nid}.json"),
             "--log-interval", "100"],
            env=env, cwd=REPO, start_new_session=True,
            stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT,
        )

    launchers = [launcher(0), launcher(1)]
    rng = random.Random(0)
    kills = 0
    try:
        deadline = time.time() + 840
        next_kill = time.time() + 45
        while time.time() < deadline:
            if all(p.poll() is not None for p in launchers):
                break
            if (time.time() >= next_kill and kills < 5
                    and (tmp_path / "ckpt" / "latest").exists()):
                out = subprocess.run(
                    ["pgrep", "-f", f"^{sys.executable} {EXAMPLE}"],
                    capture_output=True, text=True)
                from dlrover_tpu.agent.standby import parked_standby_pids

                # aim at live trainers only, not parked warm standbys
                standbys = parked_standby_pids(str(tmp_path / "ipc"))
                pids = [int(p) for p in out.stdout.split()
                        if int(p) not in standbys]
                if pids:
                    os.kill(rng.choice(pids), signal.SIGKILL)
                    kills += 1
                next_kill = time.time() + rng.uniform(30, 60)
            time.sleep(1)
        rcs = [p.poll() for p in launchers]
        assert rcs == [0, 0], rcs
        assert kills >= 3, f"only {kills} kills landed"
        results = [
            json.load(open(tmp_path / f"result_{nid}.json"))
            for nid in (0, 1)
            if (tmp_path / f"result_{nid}.json").exists()
        ]
        assert any(r["final_step"] == 900 for r in results), results
    finally:
        for p in launchers:
            if p.poll() is None:
                # whole process group: launchers spawn trainer children
                # that must not outlive a failed test
                try:
                    os.killpg(p.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
        if master.poll() is None:
            os.killpg(master.pid, signal.SIGKILL)
