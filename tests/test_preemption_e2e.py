"""TPU preemption-notice path, end to end.

The scenario SURVEY §7 calls the hard part ("restart-in-place vs
preemption"): the platform announces the kill, the agent protects the
snapshot BEFORE dying (buddy replication over DCN + master notice), the
VM dies taking its shared memory with it, and the replacement host
restores from the buddy with ZERO storage reads — storage persistence is
disabled outright in this test, so a successful resume proves the buddy
path. Reference analog: the breakpoint-save semantics of
dlrover ckpt_saver.py:631 extended to advance notice.
"""

from __future__ import annotations

import json
import os
import signal
import sys
import time

import pytest

from dlrover_tpu.cluster.crd import ScalePlan
from dlrover_tpu.cluster.scaler import LocalProcessScaler
from dlrover_tpu.master.job_master import JobMaster

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLE = os.path.join(REPO, "examples", "train_transformer.py")


def _steps_logged(log: str) -> int:
    try:
        with open(log) as f:
            return sum(1 for line in f if '"step"' in line)
    except OSError:
        return 0


# slow tier: a REAL 2-node job — jax's CPU backend in this container
# cannot run multiprocess collectives ("Multiprocess computations aren't
# implemented on the CPU backend"), so every trainer spawn dies at state
# init and the test burns ~120s failing. Same disposition as
# tests/test_multinode_e2e.py and test_buddy's node-kill e2e; a plain
# `pytest tests/` (or any multi-host-capable backend) still runs it.
@pytest.mark.slow
@pytest.mark.timeout(300)
def test_preemption_notice_buddy_restore_no_storage(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("DLROVER_TPU_DEVICE_COUNT", "2")
    # children inherit the env: 2 virtual devices per node, dp=4
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=2")
    monkeypatch.setenv("DLROVER_TPU_IPC_DIR", str(tmp_path / "ipc"))
    monkeypatch.setenv("PYTHONPATH", REPO)
    monkeypatch.setenv("DLROVER_TPU_BUDDY_INTERVAL", "0.3")
    notice_dir = tmp_path / "notices"
    notice_dir.mkdir()
    monkeypatch.setenv(
        "DLROVER_TPU_PREEMPTION_FILE",
        str(notice_dir / "preempt-{node_id}"),
    )

    master = JobMaster(min_nodes=2, max_nodes=2, rdzv_timeout=20.0)
    master.node_manager._preempt_dead_window_s = 3.0
    # agents heartbeat every 0.5s below; the derived-window floor
    # (2*interval+slack) must track that, not the 15s prod default
    master.node_manager._heartbeat_interval_s = 0.5
    log = str(tmp_path / "goodput.jsonl")
    result_file = str(tmp_path / "result.json")
    scaler = LocalProcessScaler(
        master_addr="",
        entrypoint=[
            "--monitor-interval", "0.3", "--max-restarts", "2",
            "--heartbeat-interval", "0.5",
            "--no-save-on-failure",          # storage stays EMPTY
            EXAMPLE, "--",
            "--model", "tiny", "--seq", "128", "--global-batch", "8",
            "--max-steps", "40",
            "--ckpt-dir", str(tmp_path / "ckpt"),
            "--ckpt-interval", "1000000",    # no periodic storage saves
            "--mem-ckpt-interval", "2",
            "--goodput-log", log,
            "--result-file", result_file,
            "--log-interval", "10",
            "--step-delay", "0.3",
        ],
    )
    master.node_manager._relaunch_hook = scaler.relaunch_node
    master.prepare()
    scaler._master_addr = master.addr
    try:
        scaler.scale(ScalePlan(replica_resources={"worker": 2}))
        # let training make progress and snapshots replicate
        deadline = time.time() + 120
        while _steps_logged(log) < 16 and time.time() < deadline:
            time.sleep(0.5)
        assert _steps_logged(log) >= 16, "training never progressed"

        # 1. the notice lands on node 0
        (notice_dir / "preempt-0").write_text("TERMINATE")
        # give the watcher (1s poll) time to replicate + report
        deadline = time.time() + 30
        while time.time() < deadline:
            nodes = {n.node_id: n for n in master.node_manager.all_nodes()}
            if nodes[0].preempting_since:
                break
            time.sleep(0.3)
        assert nodes[0].preempting_since, "master never got the notice"

        # 2. the VM dies: SIGKILL the whole launcher tree. The snapshot
        # meta dict and writer lock are unix-socket servers inside the
        # agent process, so the kill destroys the host's snapshot state
        # exactly like a preempted VM losing its memory — the relaunched
        # agent sees header()=None and must go to the buddy.
        proc = scaler._procs[0]
        os.killpg(proc.pid, signal.SIGKILL)
        proc.wait(timeout=10)
        scaler._procs.pop(0, None)
        # the kill consumed the notice (a fresh replacement VM would not
        # see the old event)
        (notice_dir / "preempt-0").unlink()

        # 3. the master's short dead-window relaunches node 0; the fresh
        # agent restores from node 1's buddy server and the job finishes
        ok = master.run(poll_interval_s=0.2, all_exited_grace_s=5.0)
        assert ok, "job did not finish after preemption"
        result = json.load(open(result_file))
        assert result["final_step"] == 40
        # the replacement incarnation resumed from a replicated snapshot
        assert result["resumed_from"] >= 2
        # zero storage READS: nothing was persisted before completion
        # (the only step dir allowed is the final end-of-training save),
        # so the recovery could not have come from storage
        ckpt_dir = tmp_path / "ckpt"
        persisted = (
            [p for p in os.listdir(ckpt_dir) if p.startswith("step-")]
            if ckpt_dir.exists() else []
        )
        assert persisted in ([], ["step-40"]), (
            f"storage was written during recovery: {persisted}"
        )
        nodes = {n.node_id: n for n in master.node_manager.all_nodes()}
        assert nodes[0].relaunch_count == 1
        # re-registration cleared the preemption arm
        assert nodes[0].preempting_since == 0.0
    finally:
        scaler.stop_all()
        master.stop()


class TestWatcherUnit:
    def test_fires_once_on_file(self, tmp_path):
        from dlrover_tpu.agent.preemption import PreemptionWatcher

        fired = []
        f = tmp_path / "notice-3"
        w = PreemptionWatcher(
            lambda: fired.append(1), node_id=3,
            poll_interval_s=0.05,
            notice_file=str(tmp_path / "notice-{node_id}"),
        )
        assert w.enabled
        w.start()
        time.sleep(0.2)
        assert fired == []
        f.write_text("TERMINATE")
        deadline = time.time() + 5
        while not fired and time.time() < deadline:
            time.sleep(0.05)
        assert fired == [1]
        time.sleep(0.2)
        assert fired == [1]  # one-shot
        w.stop()

    def test_disabled_without_source(self):
        from dlrover_tpu.agent.preemption import PreemptionWatcher

        w = PreemptionWatcher(lambda: None, notice_file="",
                              notice_url="")
        assert not w.enabled

    def test_master_short_window_and_clear_on_reregister(self):
        from dlrover_tpu.master.node_manager import NodeManager

        dead = []
        nm = NodeManager(dead_window_s=1000.0, on_node_dead=dead.append,
                         preempt_dead_window_s=0.2,
                         heartbeat_interval_s=0.05)
        nm.ensure_node(0)
        nm.report_heartbeat(0)
        nm.report_preemption(0, deadline_s=30.0)
        time.sleep(0.4)
        nm._check_dead_nodes()
        assert dead == [0]
        # the replacement registers: armed flag cleared, normal window
        node = nm.ensure_node(0)
        assert node.preempting_since == 0.0

    def test_armed_window_spans_heartbeat_cadence(self):
        """Advisor r04: with the armed window == the heartbeat interval
        a still-alive node racing its own cadence (heartbeat delayed by
        the pre-kill prepare) was falsely declared dead mid-prepare.
        The effective window must span >=2 cadences + slack."""
        from dlrover_tpu.master.node_manager import NodeManager

        dead = []
        nm = NodeManager(dead_window_s=1000.0, on_node_dead=dead.append,
                         preempt_dead_window_s=0.2,
                         heartbeat_interval_s=0.2)
        assert nm._effective_preempt_window() >= 0.4
        nm.ensure_node(0)
        nm.report_heartbeat(0)
        nm.report_preemption(0, deadline_s=30.0)
        # a heartbeat lands a full cadence late (delayed by the
        # prepare) — inside the derived window, so the node lives
        time.sleep(0.3)
        nm._check_dead_nodes()
        assert dead == []
        # prod geometry: 15s cadence forces a >=30s armed window even
        # when the configured preempt window is shorter
        nm2 = NodeManager(preempt_dead_window_s=15.0,
                          heartbeat_interval_s=15.0)
        assert nm2._effective_preempt_window() >= 33.0

    def test_heartbeat_past_ttl_disarms_silence_does_not(self):
        """Survival evidence is a HEARTBEAT past the advertised kill
        window (live migration); mere elapsed time must NOT disarm —
        a node killed late in its window is silent exactly then
        (review findings, rounds 4a+4b)."""
        from dlrover_tpu.master.node_manager import NodeManager

        dead = []
        nm = NodeManager(dead_window_s=1000.0, on_node_dead=dead.append,
                         preempt_dead_window_s=0.2,
                         heartbeat_interval_s=0.05)
        nm.ensure_node(0)
        nm.report_heartbeat(0)
        nm.report_preemption(0, deadline_s=30.0)
        node = nm.all_nodes()[0]
        # silence past the TTL: the short window still applies -> dead
        node.preempting_since = time.time() - 10_000
        node.heartbeat_time = time.time() - 10.0
        nm._check_dead_nodes()
        assert dead == [0]
        # ...whereas a heartbeat past the TTL disarms
        nm.ensure_node(1)
        nm.report_heartbeat(1)
        nm.report_preemption(1, deadline_s=30.0)
        node1 = [n for n in nm.all_nodes() if n.node_id == 1][0]
        node1.preempting_since = time.time() - 10_000
        nm.report_heartbeat(1)
        assert node1.preempting_since == 0.0
        nm._check_dead_nodes()
        assert dead == [0]  # node 1 stays alive on the normal window

    def test_url_source_fires_on_maintenance_event(self):
        """The metadata-URL notice source (GCE maintenance-event
        convention): NONE means keep running, anything else fires."""
        import http.server
        import threading as th

        from dlrover_tpu.agent.preemption import PreemptionWatcher

        body = {"value": b"NONE"}

        class H(http.server.BaseHTTPRequestHandler):
            def do_GET(self):
                assert self.headers.get("Metadata-Flavor") == "Google"
                self.send_response(200)
                self.send_header("Content-Length",
                                 str(len(body["value"])))
                self.end_headers()
                self.wfile.write(body["value"])

            def log_message(self, *a):
                pass

        srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), H)
        th.Thread(target=srv.serve_forever, daemon=True).start()
        fired = []
        w = PreemptionWatcher(
            lambda: fired.append(1), poll_interval_s=0.05,
            notice_file="",
            notice_url=f"http://127.0.0.1:{srv.server_address[1]}/",
        )
        try:
            assert w.enabled
            w.start()
            time.sleep(0.3)
            assert fired == []          # NONE: no notice
            body["value"] = b"TERMINATE_ON_HOST_MAINTENANCE"
            deadline = time.time() + 5
            while not fired and time.time() < deadline:
                time.sleep(0.05)
            assert fired == [1]
        finally:
            w.stop()
            srv.shutdown()
            srv.server_close()
