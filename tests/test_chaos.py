"""Chaos harness: deterministic fault injection + recovery invariants.

Reference analog: the chaosblade fault-tolerance experiments
(docs/tech_report/fault_tolerance_exps.md), made hermetic and
replayable: seeded count-matched fault plans (dlrover_tpu/chaos/)
injected at the RPC / storage / process-management trust boundaries,
with the acceptance scenario (trainer killed mid-save, newest shard
bit-flipped, master RPC flaking) driven end to end twice and its
fault/recovery journal trail compared across runs.
"""

from __future__ import annotations

import importlib.util
import json
import os
import time

import pytest

from dlrover_tpu import chaos
from dlrover_tpu.common import rpc, serde, storage

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    yield
    chaos.uninstall()


# ----------------------------------------------------------------- gating


def test_disabled_is_a_hard_noop(monkeypatch, tmp_path):
    """With no plan installed, no chaos code runs on any hot path: the
    sites gate on ``chaos.ENABLED`` before calling ``fire`` at all."""
    assert chaos.ENABLED is False

    def _boom(*a, **k):  # noqa: ARG001
        raise AssertionError("chaos.fire called with chaos disabled")

    monkeypatch.setattr(chaos, "fire", _boom)

    @serde.register_message
    class ChaosPingA:
        x: int = 0

    server = rpc.RpcServer(lambda m: ChaosPingA(x=m.x + 1), host="127.0.0.1")
    server.start()
    try:
        client = rpc.RpcClient(f"127.0.0.1:{server.port}")
        assert client.call(ChaosPingA(x=1)).x == 2
        client.close()
    finally:
        server.stop()
    storage.atomic_write_file(b"clean", str(tmp_path / "f.bin"))
    assert open(tmp_path / "f.bin", "rb").read() == b"clean"


def test_malformed_plan_disables_chaos(monkeypatch):
    from dlrover_tpu.chaos.injector import controller_from_environ

    monkeypatch.setenv("DLROVER_TPU_CHAOS", "{not json")
    assert controller_from_environ() is None
    monkeypatch.setenv("DLROVER_TPU_CHAOS", "/nonexistent/plan.json")
    assert controller_from_environ() is None


# ----------------------------------------------------------- rule matching


def test_rule_matching_and_occurrence_window():
    ctl = chaos.ChaosController.from_spec({"seed": 3, "faults": [
        {"point": "p", "action": "a",
         "match": {"step_gte": 5, "path_suffix": ".bin"},
         "after": 1, "times": 2},
    ]})
    # context misses: wrong suffix, low step, missing key
    assert ctl.fire("p", step=9, path="x.meta") is None
    assert ctl.fire("p", step=2, path="x.bin") is None
    assert ctl.fire("p", step=9) is None
    # first real match skipped (after=1), next two fire, then exhausted
    assert ctl.fire("p", step=5, path="a.bin") is None
    assert ctl.fire("p", step=5, path="a.bin") is not None
    assert ctl.fire("p", step=9, path="b.bin") is not None
    assert ctl.fire("p", step=9, path="b.bin") is None


def test_seeded_firing_is_deterministic():
    spec = {"seed": 11, "faults": [
        {"point": "p", "action": "a", "prob": 0.4, "times": 0},
        {"point": "q", "action": "b", "prob": 0.7, "times": 0},
    ]}
    runs = []
    for _ in range(2):
        ctl = chaos.ChaosController.from_spec(spec)
        pattern = []
        for i in range(60):
            point = "p" if i % 2 else "q"
            pattern.append(ctl.fire(point) is not None)
        runs.append(pattern)
    assert runs[0] == runs[1]
    assert any(runs[0]) and not all(runs[0])
    # a different seed gives a different pattern (overwhelmingly)
    ctl = chaos.ChaosController.from_spec({**spec, "seed": 12})
    other = [ctl.fire("p" if i % 2 else "q") is not None
             for i in range(60)]
    assert other != runs[0]


def test_every_fault_leaves_a_journal_line(monkeypatch, tmp_path):
    monkeypatch.setenv("DLROVER_TPU_JOURNAL_DIR", str(tmp_path))
    ctl = chaos.install({"seed": 1, "faults": [
        {"point": "p", "action": "a", "times": 3},
    ]})
    for _ in range(5):
        ctl.fire("p", step=4)
    events = [
        json.loads(line)
        for line in open(tmp_path / "events.jsonl", encoding="utf-8")
    ]
    faults = [e for e in events if e["name"] == "chaos_fault"]
    assert [f["seq"] for f in faults] == [0, 1, 2]
    assert all(f["point"] == "p" and f["action"] == "a" and f["step"] == 4
               for f in faults)


# --------------------------------------------------------------- rpc faults


@serde.register_message
class ChaosPingB:
    x: int = 0


def _echo_server():
    server = rpc.RpcServer(lambda m: ChaosPingB(x=m.x + 1), host="127.0.0.1")
    server.start()
    return server, ChaosPingB


def test_rpc_drop_and_reset_retry_with_backoff_and_counts():
    server, Ping = _echo_server()
    before = rpc._retry_total.labels().value
    chaos.install({"seed": 1, "faults": [
        {"point": "rpc_call", "action": "drop", "times": 2},
    ]})
    try:
        client = rpc.RpcClient(f"127.0.0.1:{server.port}",
                               backoff_base_s=0.01)
        assert client.call(Ping(x=1)).x == 2  # drop, drop, ok
        chaos.install({"seed": 1, "faults": [
            {"point": "rpc_call", "action": "reset", "times": 1},
        ]})
        assert client.call(Ping(x=5)).x == 6  # reset, ok
        assert rpc._retry_total.labels().value - before >= 3
        client.close()
    finally:
        server.stop()


def test_rpc_garbled_frame_survived_by_server_and_client():
    server, Ping = _echo_server()
    chaos.install({"seed": 1, "faults": [
        {"point": "rpc_call", "action": "garble", "times": 1},
    ]})
    try:
        client = rpc.RpcClient(f"127.0.0.1:{server.port}",
                               backoff_base_s=0.01)
        assert client.call(Ping(x=3)).x == 4   # garbled then retried
        assert client.call(Ping(x=7)).x == 8   # server still healthy
        client.close()
    finally:
        server.stop()


def test_rpc_per_call_deadline_exceeded():
    before = rpc._deadline_total.labels().value
    client = rpc.RpcClient("127.0.0.1:1", retries=10_000,
                           backoff_base_s=0.02, backoff_max_s=0.05,
                           deadline_s=0.3)
    t0 = time.monotonic()
    with pytest.raises(ConnectionError, match="deadline"):
        client.call(rpc.RpcError(error=""))
    assert time.monotonic() - t0 < 5.0
    assert rpc._deadline_total.labels().value == before + 1


def test_rpc_delay_fault_only_slows_the_call():
    server, Ping = _echo_server()
    chaos.install({"seed": 1, "faults": [
        {"point": "rpc_call", "action": "delay", "args": {"s": 0.2},
         "times": 1},
    ]})
    try:
        client = rpc.RpcClient(f"127.0.0.1:{server.port}")
        t0 = time.monotonic()
        assert client.call(Ping(x=1)).x == 2
        assert time.monotonic() - t0 >= 0.2
        client.close()
    finally:
        server.stop()


# ------------------------------------------------------------ storage faults


def test_storage_bit_flip_is_deterministic(tmp_path):
    blobs = []
    for _ in range(2):
        chaos.install({"seed": 9, "faults": [
            {"point": "storage_write", "action": "bit_flip",
             "match": {"path_suffix": ".bin"}, "times": 1},
        ]})
        path = str(tmp_path / f"f{len(blobs)}.bin")
        storage.atomic_write_file(b"\x00" * 256, path)
        blobs.append(open(path, "rb").read())
        chaos.uninstall()
    assert blobs[0] == blobs[1] != b"\x00" * 256
    assert len(blobs[0]) == 256


def test_storage_enospc_and_torn(tmp_path):
    chaos.install({"seed": 2, "faults": [
        {"point": "storage_write", "action": "enospc",
         "match": {"path_suffix": ".a"}, "times": 1},
        {"point": "storage_write", "action": "torn",
         "args": {"frac": 0.25}, "match": {"path_suffix": ".b"},
         "times": 1},
    ]})
    with pytest.raises(OSError, match="space"):
        storage.atomic_write_file(b"x" * 10, str(tmp_path / "f.a"))
    assert not os.path.exists(tmp_path / "f.a")
    with pytest.raises(OSError, match="torn"):
        storage.atomic_write_file(b"y" * 100, str(tmp_path / "f.b"))
    # the torn write left a PARTIAL file at the final path
    assert os.path.getsize(tmp_path / "f.b") == 25


def test_storage_slow_fsync_delays_but_completes(tmp_path):
    chaos.install({"seed": 2, "faults": [
        {"point": "storage_write", "action": "slow_fsync",
         "args": {"s": 0.2}, "times": 1},
    ]})
    t0 = time.monotonic()
    storage.atomic_write_file(b"z" * 8, str(tmp_path / "s.bin"))
    assert time.monotonic() - t0 >= 0.2
    assert open(tmp_path / "s.bin", "rb").read() == b"z" * 8


# ------------------------------------------------------------------- lint


def test_fault_point_lint_passes_and_catches_undocumented(tmp_path):
    spec = importlib.util.spec_from_file_location(
        "check_metric_names",
        os.path.join(REPO, "native", "check_metric_names.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    names, problems = mod.scan_fault_points()
    assert problems == []
    assert {"rpc_call", "storage_write", "agent_kill_trainer"} <= set(names)
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        'chaos.fire("totally_undocumented_point", x=1)\n'
    )
    _, problems = mod.scan_fault_points(str(pkg))
    assert any("totally_undocumented_point" in p for p in problems)
    (pkg / "mod.py").write_text("chaos.fire(f\"dyn_{x}\")\n")
    _, problems = mod.scan_fault_points(str(pkg))
    assert any("non-literal" in p for p in problems)


# -------------------------------------------------- gateway degraded mode


class _FakeMasterClient:
    def __init__(self):
        self.down = False
        self.kv: bytes | None = None

    def report_metrics(self, samples, role="agent"):  # noqa: ARG002
        if self.down:
            raise ConnectionError("master unreachable")

    def kv_get(self, key):  # noqa: ARG002
        if self.down:
            raise ConnectionError("master unreachable")
        return self.kv


class _RecordingScaler:
    def __init__(self):
        self.plans = []

    def scale(self, plan):
        self.plans.append(plan)


def test_gateway_degraded_mode(monkeypatch, tmp_path):
    import types

    monkeypatch.setenv("DLROVER_TPU_JOURNAL_DIR", str(tmp_path))
    from dlrover_tpu.gateway.control import MasterLink, _degraded_gauge

    client = _FakeMasterClient()
    scaler = _RecordingScaler()
    gw = types.SimpleNamespace(master_link=None)
    link = MasterLink(gw, client, scaler=scaler, interval_s=60)
    assert gw.master_link is link

    client.kv = b"3"
    link.tick()
    assert not link.degraded
    assert len(scaler.plans) == 1
    assert scaler.plans[0].replica_resources == {"serving": 3}

    # master goes away: degraded entered ONCE, no exception escapes,
    # no further control actions
    client.down = True
    link.tick()
    link.tick()
    assert link.degraded
    assert _degraded_gauge.labels().value == 1
    assert len(scaler.plans) == 1

    # master returns: degraded exits, control resumes
    client.down = False
    client.kv = b"2"
    link.tick()
    assert not link.degraded
    assert _degraded_gauge.labels().value == 0
    assert scaler.plans[-1].replica_resources == {"serving": 2}

    events = [
        json.loads(line)
        for line in open(tmp_path / "events.jsonl", encoding="utf-8")
    ]
    modes = [e["state"] for e in events if e["name"] == "degraded_mode"]
    assert modes == ["enter", "exit"]


def test_gateway_keeps_serving_while_degraded(monkeypatch):
    """Control-plane loss must not fail data-plane submits: a Gateway
    with an unreachable master still serves from its replica pool."""
    from dlrover_tpu.gateway.control import MasterLink
    from dlrover_tpu.gateway.server import Gateway

    class _Engine:
        slots = 4

        def submit(self, prompt, params, on_token=None):  # noqa: ARG002
            self._last = (len(prompt), params)
            return 1

        def step(self):
            pass

        def poll_results(self):
            import types as t

            if getattr(self, "_last", None) is None:
                return []
            self._last = None
            return [t.SimpleNamespace(id=1, tokens=[7, 8],
                                      finish_reason="stop")]

    gw = Gateway(lambda: _Engine(), replicas=1)
    try:
        client = _FakeMasterClient()
        client.down = True
        link = MasterLink(gw, client, interval_s=60)
        link.tick()
        assert link.degraded and gw.stats()["degraded"]
        result = gw.generate([1, 2, 3], timeout=30)
        assert result.tokens == [7, 8]
    finally:
        gw.stop()


# ------------------------------------------------- the acceptance scenario


def _scenario_env(tmp_path) -> dict:
    return {
        "JAX_PLATFORMS": "cpu",
        "DLROVER_TPU_DEVICE_COUNT": "1",
        # warm recovery is a recovery path: the acceptance scenario must
        # stay deterministic WITH standby promotion in the loop (pinned
        # explicitly, independent of the feature's default)
        "DLROVER_TPU_STANDBY": "1",
    }


@pytest.mark.timeout(560)
def test_seeded_scenario_recovers_and_replays_identically(tmp_path):
    """The acceptance run: trainer SIGKILLed mid-save, newest shard
    bit-flipped, master RPC dropped on the re-join — completes with
    zero lost shards, restores from the newest VERIFIED step, and two
    runs with the same seed leave an identical fault/recovery trail."""
    from dlrover_tpu.chaos.scenario import canned_scenario, run_scenario

    results = []
    for run in ("run_a", "run_b"):
        res = run_scenario(
            canned_scenario(seed=20260804),
            str(tmp_path / run),
            env_extra=_scenario_env(tmp_path),
            deadline_s=250,
        )
        res.assert_invariants()
        results.append(res)

    for res in results:
        leg1, leg2 = res.legs
        # leg 1: killed once mid-save, recovered in place, completed
        assert leg1.result["restart_count"] == 1
        assert leg1.result["final_step"] == 14
        # leg 2 (fresh process tree): the newest step (14) was
        # bit-flipped on disk, so restore must roll back to the newest
        # verified step (12) — never the corrupt one, never step 0
        assert leg2.result["resumed_from"] == 12
        assert leg2.result["final_step"] == 20
        assert res.verified_step == 20
        # every planned fault fired exactly once and was journaled
        assert sorted(f[:2] for f in res.trail["faults"]) == sorted([
            ["agent_kill_trainer", "kill"],
            ["rpc_call", "drop"],
            ["storage_write", "bit_flip"],
            ["storage_write", "slow_fsync"],
        ])
        recovery_names = {r[0] for r in res.trail["recovery"]}
        assert {"node_restart", "ckpt_verify_failed",
                "ckpt_rollback"} <= recovery_names
        assert ["ckpt_rollback", 14, 12] in res.trail["recovery"]
        assert res.recovery_seconds is not None

    # determinism: identical fault/recovery journal trail across runs
    assert results[0].trail == results[1].trail

    # §27: the kill's incident trace assembles across the agent and the
    # respawned trainer, its category breakdown reconciles with the
    # report vocabulary, and the seeded span-id discipline makes the
    # incident trees byte-identical across the two runs
    from dlrover_tpu.telemetry import trace as trace_mod

    skeletons = []
    for run, res in zip(("run_a", "run_b"), results):
        jdir = str(tmp_path / run / "journal")
        roots = trace_mod.build_forest(trace_mod.load_spans([jdir]))
        incidents = [r for r in trace_mod.find_incident_roots(roots)
                     if r.span.fields.get("kind") == "failure"]
        assert incidents, "no failure incident tree assembled"
        inc = incidents[0]
        names = {n.span.name for n in inc.walk()}
        # the recovery phases attached under the incident root: the
        # agent's rendezvous and (cross-process, via SPAN_CTX) the
        # respawned trainer's restore
        assert "rendezvous_wait" in names
        assert "ckpt_restore" in names
        assert inc.n_procs() >= 2
        cats = trace_mod.incident_breakdown(inc)
        assert cats.get("restore", 0) > 0
        assert cats.get("rendezvous", 0) > 0
        # kill -> restore read off the TREE agrees with the journal-
        # timestamp recovery number (same bound bench.py asserts)
        from dlrover_tpu.chaos.scenario import _read_journal
        t_kill = next(e["t"] for e in _read_journal(jdir)
                      if e.get("name") == "chaos_fault"
                      and e.get("point") == "agent_kill_trainer")
        restore_end = min(n.end for n in inc.walk()
                          if n.span.name == "ckpt_restore")
        assert restore_end - t_kill == pytest.approx(
            res.recovery_seconds, rel=0.10)
        assert trace_mod.critical_path(inc)[-1].get("name") in names

        # byte-identical modulo the save-before-restart persist: that
        # span is opportunistic BY DESIGN (it fires only if a fresher
        # shm snapshot won the race with the kill signal), so its
        # presence is the one legitimately timing-dependent bit of an
        # otherwise deterministic incident tree
        def prune(sk):
            sk["children"] = [
                prune(c) for c in sk["children"]
                if c["name"] not in ("ckpt_persist", "ckpt_persist_shard")
            ]
            return sk

        skeletons.append(json.dumps(
            [prune(trace_mod.tree_skeleton(i)) for i in incidents],
            sort_keys=True))
    assert skeletons[0] == skeletons[1]


@pytest.mark.timeout(300)
def test_standby_promotion_is_deterministic_under_kill_chaos(tmp_path):
    """Warm-standby promotion IS the recovery path when the chaos
    harness kills the trainer: the respawn must be served by promoting
    the parked standby (standby_promote journal span present), the job
    must still complete losing nothing, and two seeded runs must leave
    an identical fault/recovery trail — promotion gets the same
    deterministic-replay guarantee as a cold respawn."""
    from dlrover_tpu.chaos.scenario import (
        JobLeg,
        Scenario,
        _read_journal,
        run_scenario,
    )

    def scenario():
        return Scenario(
            name="standby_kill", seed=424242,
            legs=[JobLeg(
                name="kill_promote", max_steps=14,
                faults=[{"point": "agent_kill_trainer", "action": "kill",
                         "args": {"sig": 9},
                         "match": {"step_gte": 8}, "times": 1}],
                train_args=["--ckpt-interval", "1000000",
                            "--mem-ckpt-interval", "2",
                            "--step-delay", "0.15"],
            )],
        )

    results = []
    for run in ("run_a", "run_b"):
        work = str(tmp_path / run)
        res = run_scenario(
            scenario(), work,
            env_extra=_scenario_env(tmp_path), deadline_s=140,
        )
        res.assert_invariants()
        assert res.legs[0].result["restart_count"] == 1
        assert res.legs[0].result["final_step"] == 14
        # the kill recovered from a warm shm snapshot, not from step 0.
        # The kill dispatches on the step the AGENT observed (>= 8), so
        # on a slow host it can land before the step-8 snapshot
        # (mem-ckpt-interval 2) is taken — warm recovery then resumes
        # from the previous snapshot, one interval behind
        assert res.legs[0].result["resumed_from"] >= 6
        # the respawn was a PROMOTION: the agent journaled the
        # standby_promote span around handing over the payload
        events = _read_journal(os.path.join(work, "journal"))
        promotes = [e for e in events
                    if e.get("name") == "standby_promote"]
        assert promotes, "no standby_promote span: respawn went cold"
        results.append(res)

    assert results[0].trail == results[1].trail
