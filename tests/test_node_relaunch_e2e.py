"""Node relaunch end to end: hardware fault -> the master REPLACES the host.

Reference analog: _should_relaunch -> _relaunch_node -> PodScaler
(dist_job_manager.py:561,605). Locally: an in-process master wires
LocalProcessScaler as its relaunch hook; the trainer exits with the
hardware code (211), the agent persists the snapshot and exits with the
node-relaunch code, the master's hook respawns a fresh launcher for the
same node id, and the job completes from the restored checkpoint.
"""

from __future__ import annotations

import json
import os
import sys
import time

import pytest

from dlrover_tpu.cluster.crd import ScalePlan
from dlrover_tpu.cluster.scaler import LocalProcessScaler
from dlrover_tpu.master.job_master import JobMaster

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLE = os.path.join(REPO, "examples", "train_transformer.py")


@pytest.mark.timeout(300)
def test_hardware_fault_relaunches_node_and_completes(
    tmp_path, monkeypatch
):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("DLROVER_TPU_DEVICE_COUNT", "1")
    monkeypatch.setenv("DLROVER_TPU_IPC_DIR", str(tmp_path / "ipc"))
    monkeypatch.setenv("PYTHONPATH", REPO)

    master = JobMaster(min_nodes=1, max_nodes=1, rdzv_timeout=5.0)
    result_file = str(tmp_path / "result.json")
    scaler = LocalProcessScaler(
        master_addr="",  # filled after prepare()
        entrypoint=[
            "--monitor-interval", "0.3", "--max-restarts", "2",
            EXAMPLE, "--",
            "--model", "tiny", "--seq", "128", "--global-batch", "8",
            "--max-steps", "20",
            "--ckpt-dir", str(tmp_path / "ckpt"),
            "--result-file", result_file,
            "--log-interval", "5",
            "--crash-at-step", "6", "--crash-exit", "211",
            "--crash-once-file", str(tmp_path / "crashed.marker"),
        ],
    )
    master.node_manager._relaunch_hook = scaler.relaunch_node
    master.prepare()
    scaler._master_addr = master.addr
    try:
        scaler.scale(ScalePlan(replica_resources={"worker": 1}))
        ok = master.run(poll_interval_s=0.2, all_exited_grace_s=5.0)
        assert ok, "job did not finish successfully"
        result = json.load(open(result_file))
        assert result["final_step"] == 20
        # the replacement incarnation restored the breakpoint snapshot
        assert result["resumed_from"] >= 4
        assert os.path.exists(tmp_path / "crashed.marker")
        # exactly one relaunch was recorded on the node
        nodes = {n.node_id: n for n in master.node_manager.all_nodes()}
        assert nodes[0].relaunch_count == 1
    finally:
        scaler.stop_all()
        master.stop()
