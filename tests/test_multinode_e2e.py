"""Multi-node elastic training on localhost: 2 masters-worth of reality.

Two launcher processes (agents), one master, one jax.distributed world over
CPU+Gloo — training genuinely sharded across processes. The kill test is
the reference's headline scenario (SURVEY.md §5.3 elastic recovery): kill
one node's trainer mid-run, both agents re-rendezvous, training resumes
from a consistent checkpoint.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

import pytest

# Slow tier: a genuine jax.distributed world over CPU+Gloo. This
# container's jax CPU backend cannot complete multi-process collectives
# (known since the telemetry PR — see CHANGES.md), so under tier-1 these
# four e2es burned ~60 s failing by timeout on every run without
# asserting anything. The slow tier keeps them collected by a plain
# `pytest tests/` on hosts whose backend supports the multi-process
# world (VERDICT.md: "move the slowest e2e bodies behind a tiered
# marker the driver still runs").
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLE = os.path.join(REPO, "examples", "train_transformer.py")


def _env(tmp_path) -> dict:
    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",
            "DLROVER_TPU_DEVICE_COUNT": "4",
            "DLROVER_TPU_IPC_DIR": str(tmp_path / "ipc"),
            # cross-process event journal: master mints the trace id,
            # agents adopt it from the rendezvous payload, trainers
            # inherit it through the child env
            "DLROVER_TPU_JOURNAL_DIR": str(tmp_path / "journal"),
            "PYTHONPATH": REPO,
            # 4 virtual devices per process -> 8 global over 2 nodes
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        }
    )
    return env


def _start_master(tmp_path, env, min_nodes=2, max_nodes=2,
                  extra=()) -> tuple[subprocess.Popen, str]:
    port_file = str(tmp_path / "master_port")
    proc = subprocess.Popen(
        [sys.executable, "-m", "dlrover_tpu.master.job_master",
         "--min-nodes", str(min_nodes), "--max-nodes", str(max_nodes),
         "--port-file", port_file, *extra],
        env=env, cwd=REPO, start_new_session=True,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    deadline = time.time() + 30
    while time.time() < deadline:
        if os.path.exists(port_file) and open(port_file).read().strip():
            return proc, f"127.0.0.1:{open(port_file).read().strip()}"
        time.sleep(0.1)
    proc.kill()
    raise TimeoutError("master did not start")


def _launcher(tmp_path, env, node_id: int, train_args: list[str]
              ) -> subprocess.Popen:
    cmd = [
        sys.executable, "-m", "dlrover_tpu.run",
        "--master-addr", open(str(tmp_path / "master_addr")).read(),
        "--node-id", str(node_id), "--nnodes", "2",
        "--monitor-interval", "0.3", "--max-restarts", "2",
        EXAMPLE, "--",
        "--model", "tiny", "--seq", "128",
        "--global-batch", "8",
        "--ckpt-dir", str(tmp_path / "ckpt"),
        "--result-file", str(tmp_path / f"result_{node_id}.json"),
        "--log-interval", "5",
        *train_args,
    ]
    return subprocess.Popen(
        cmd, env=env, cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )


def _run_two_nodes(tmp_path, train_args, kill_after_ckpt=False,
                   timeout=420):
    env = _env(tmp_path)
    master, addr = _start_master(tmp_path, env)
    (tmp_path / "master_addr").write_text(addr)
    launchers = [
        _launcher(tmp_path, env, nid, train_args) for nid in (0, 1)
    ]
    killed = False
    try:
        deadline = time.time() + timeout
        while time.time() < deadline:
            if all(p.poll() is not None for p in launchers):
                break
            if kill_after_ckpt and not killed \
                    and (tmp_path / "ckpt" / "latest").exists():
                out = subprocess.run(
                    ["pgrep", "-f", f"^{sys.executable} {EXAMPLE}"],
                    capture_output=True, text=True,
                )
                from dlrover_tpu.agent.standby import parked_standby_pids

                # aim at live trainers only, not parked warm standbys
                standbys = parked_standby_pids(str(tmp_path / "ipc"))
                pids = [int(p) for p in out.stdout.split()
                        if int(p) not in standbys]
                if pids:
                    os.kill(pids[-1], signal.SIGKILL)
                    killed = True
            time.sleep(0.5)
        outs = []
        for p in launchers:
            try:
                out, _ = p.communicate(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
                out, _ = p.communicate()
            outs.append(out)
        return launchers, outs, killed
    finally:
        for p in launchers:
            if p.poll() is None:
                p.kill()
        if master.poll() is None:
            try:
                os.killpg(master.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        # only this test's trainers (their ckpt dir): a sibling xdist
        # worker runs the same example
        subprocess.run(["pkill", "-9", "-f", str(tmp_path)],
                       capture_output=True)


def _elastic_launcher(env, addr, tmp_path, nid: int,
                      nnodes: str = "2:3") -> subprocess.Popen:
    """Launcher for the elastic grow/shrink scenarios (min:max world)."""
    cmd = [
        sys.executable, "-m", "dlrover_tpu.run",
        "--master-addr", addr,
        "--node-id", str(nid), "--nnodes", nnodes,
        "--monitor-interval", "0.3", "--max-restarts", "2",
        # NB: the agent's --rdzv-timeout is how long it WAITS for a
        # round; the master's --rdzv-timeout is when a round COMPLETES
        # with fewer than max nodes. Setting them equal makes the
        # client deadline race the completion. 150 (not 90): a sibling
        # xdist worker's jax compiles can starve every child here for
        # tens of seconds on a one-core host.
        "--heartbeat-interval", "2", "--rdzv-timeout", "150",
        EXAMPLE, "--",
        "--model", "tiny", "--seq", "128",
        "--global-batch", "24",
        "--ckpt-dir", str(tmp_path / "ckpt"),
        "--ckpt-interval", "5",
        "--result-file", str(tmp_path / f"result_{nid}.json"),
        "--log-interval", "5",
        "--max-steps", "30", "--epochs", "50",
    ]
    return subprocess.Popen(
        cmd, env=env, cwd=REPO, start_new_session=True,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )


def _drain(proc: subprocess.Popen, timeout: float = 30) -> str:
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, _ = proc.communicate()
    return out


def _kill_all(launchers, master, tmp_path) -> None:
    for p in (launchers.values() if isinstance(launchers, dict)
              else launchers):
        if p.poll() is None:
            try:
                os.killpg(p.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
    if master.poll() is None:
        try:
            os.killpg(master.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    subprocess.run(["pkill", "-9", "-f", str(tmp_path)],
                   capture_output=True)


@pytest.mark.timeout(500)
def test_two_node_training_completes(tmp_path):
    launchers, outs, _ = _run_two_nodes(
        tmp_path, ["--max-steps", "12"],
    )
    for p, out in zip(launchers, outs):
        assert p.returncode == 0, out[-3000:]
    result = json.load(open(tmp_path / "result_0.json"))
    assert result["final_step"] == 12
    assert result["num_nodes"] == 2
    assert not os.path.exists(tmp_path / "result_1.json")  # rank 1 silent


@pytest.mark.timeout(500)
def test_three_nodes_shrink_to_two_on_node_loss(tmp_path):
    """THE elastic headline: a 3-node world permanently loses a node
    (launcher+trainer killed); the master declares it dead, survivors
    re-rendezvous as a 2-node world, and training resumes from the
    sharded checkpoint RESHARDED from 12 devices onto 8."""
    env = _env(tmp_path)
    master, addr = _start_master(
        tmp_path, env, min_nodes=2, max_nodes=3,
        # short enough for a timely dead-node verdict, long enough that
        # a starved-but-live node's heartbeat (interval 2) can't miss
        # the window under a contended core
        extra=["--rdzv-timeout", "10", "--dead-window", "9"],
    )

    launchers = {
        nid: _elastic_launcher(env, addr, tmp_path, nid)
        for nid in (0, 1, 2)
    }
    killed = False
    try:
        deadline = time.time() + 360
        while time.time() < deadline:
            if all(p.poll() is not None
                   for nid, p in launchers.items() if nid != 2):
                break
            if not killed and (tmp_path / "ckpt" / "latest").exists():
                # permanently remove node 2: launcher AND its trainer
                os.killpg(launchers[2].pid, signal.SIGKILL)
                killed = True
            time.sleep(0.5)
        assert killed, "checkpoint never appeared"
        outs = {nid: _drain(launchers[nid]) for nid in (0, 1)}
        for nid in (0, 1):
            assert launchers[nid].returncode == 0, outs[nid][-4000:]
        result = json.load(open(tmp_path / "result_0.json"))
        assert result["final_step"] == 30
        assert result["num_nodes"] == 2       # the world actually shrank
        assert result["resumed_from"] > 0     # resharded restore
    finally:
        _kill_all(launchers, master, tmp_path)


@pytest.mark.timeout(500)
def test_two_nodes_grow_to_three_on_join(tmp_path):
    """The scale-UP half of elasticity: a third node joins mid-run; the
    running agents detect the membership change, checkpoint, restart as
    a 3-node world, and training finishes with all three."""
    env = _env(tmp_path)
    master, addr = _start_master(
        tmp_path, env, min_nodes=2, max_nodes=3,
        extra=["--rdzv-timeout", "8"],
    )

    launchers = {
        nid: _elastic_launcher(env, addr, tmp_path, nid)
        for nid in (0, 1)
    }
    joined = False
    try:
        deadline = time.time() + 360
        while time.time() < deadline:
            # break when every launcher spawned SO FAR has exited: a
            # pre-join startup failure must fail fast, not burn the
            # whole deadline
            if all(p.poll() is not None for p in launchers.values()):
                break
            if not joined and (tmp_path / "ckpt" / "latest").exists():
                # the 2-node world is training: bring in node 2
                launchers[2] = _elastic_launcher(env, addr, tmp_path, 2)
                joined = True
            time.sleep(0.5)
        assert joined, "checkpoint never appeared"
        outs = {nid: _drain(p) for nid, p in launchers.items()}
        for nid, p in launchers.items():
            assert p.returncode == 0, (nid, outs[nid][-4000:])
        result = json.load(open(tmp_path / "result_0.json"))
        assert result["final_step"] == 30
        assert result["num_nodes"] == 3       # the world actually grew
        assert result["resumed_from"] > 0     # restored mid-run
    finally:
        _kill_all(launchers, master, tmp_path)


@pytest.mark.timeout(500)
def test_two_node_kill_one_trainer_recovers(tmp_path):
    goodput_log = str(tmp_path / "goodput.jsonl")
    launchers, outs, killed = _run_two_nodes(
        tmp_path, ["--max-steps", "30", "--ckpt-interval", "5",
                   "--goodput-log", goodput_log],
        kill_after_ckpt=True,
    )
    assert killed, "never saw a checkpoint to kill after"
    for p, out in zip(launchers, outs):
        assert p.returncode == 0, out[-4000:]
    result = json.load(open(tmp_path / "result_0.json"))
    assert result["final_step"] == 30
    assert result["num_nodes"] == 2
    assert result["resumed_from"] > 0
    joint = "\n".join(outs)
    assert "resumed from step" in joint
    # goodput accounting over the CPU-mesh multinode failure scenario
    # (the reference's headline metric, measured for real in bench.py)
    from dlrover_tpu.utils.goodput import compute_goodput

    r = compute_goodput(goodput_log)
    assert r.n_steps == 30
    assert r.n_incarnations >= 2
    assert 0.0 < r.goodput <= 1.0
    # telemetry acceptance: the report over the journal this run produced
    # agrees with goodput's (total - productive) within 5%, and the trace
    # id propagated master -> agents -> trainers
    from dlrover_tpu.telemetry.report import build_report, load_events

    events = load_events(str(tmp_path / "journal"))
    assert events, "journal never written"
    traces = {e["trace"] for e in events if e.get("trace")}
    assert len(traces) == 1, f"expected one job trace, got {traces}"
    procs = {e["proc"] for e in events}
    assert len(procs) >= 2, f"journal only saw {procs}"
    names = {e["name"] for e in events}
    assert "rdzv_round" in names          # master-side span
    assert "node_restart" in names        # agent-side recovery span
    report = build_report(str(tmp_path / "journal"),
                          goodput_log=goodput_log)
    assert abs(report.lost_s - r.lost_s) <= 0.05 * max(r.lost_s, 0.1)
    assert report.categories["respawn"] > 0.0
