"""Efficiency observatory (ISSUE 7): live MFU, step-phase attribution,
on-demand profiler capture, exposition conformance.

Acceptance surface, hermetic on the CPU backend:

- a warm AOT compile-cache load returns the compiled program's FLOPs
  from the envelope WITHOUT re-invoking the compile function;
- the step-phase histograms observed by a real ``ElasticTrainer`` loop
  account for (approximately) the whole step wall time, and the
  journal carries ``metrics_sample``/``step_phase`` points;
- the straggler detector attributes a planted slow node's verdict to
  its dominant phase (journal evidence + ``straggler_phase`` gauge
  label);
- a profile request round-trips: request file -> K-step
  ``jax.profiler`` capture -> debug bundle containing a non-empty
  xplane trace; the master's ``ProfileRequest`` RPC queues the
  heartbeat action that arms it;
- the master's one-scrape exposition parses under a strict Prometheus
  text-format conformance parser (family grouping, meta-once,
  histogram bucket discipline);
- ``report --format json`` emits one document with the steady-state
  efficiency rows; the timeline renders journaled samples as counter
  tracks across a journal rotation.
"""

from __future__ import annotations

import glob
import json
import math
import os
import re
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dlrover_tpu.common import messages as m
from dlrover_tpu.common import serde
from dlrover_tpu.common.constants import EnvKey
from dlrover_tpu.parallel import compile_cache as cc
from dlrover_tpu.telemetry import efficiency as eff
from dlrover_tpu.telemetry import journal as journal_mod
from dlrover_tpu.telemetry.anomaly import StragglerDetector
from dlrover_tpu.telemetry.exposition import render, render_grouped
from dlrover_tpu.telemetry.metrics import MetricsRegistry, registry
from dlrover_tpu.telemetry.report import build_report, load_events
from dlrover_tpu.telemetry.report import main as report_main
from dlrover_tpu.telemetry.timeline import build_trace


@pytest.fixture()
def journal_dir(tmp_path, monkeypatch):
    monkeypatch.setenv(EnvKey.JOURNAL_DIR, str(tmp_path / "journal"))
    monkeypatch.delenv(EnvKey.JOURNAL_MAX_MB, raising=False)
    monkeypatch.setattr(journal_mod, "_cached", None)
    yield str(tmp_path / "journal")
    journal_mod._cached = None


@pytest.fixture()
def bundle_dir(tmp_path, monkeypatch):
    monkeypatch.setenv(EnvKey.BUNDLE_DIR, str(tmp_path / "bundles"))
    yield str(tmp_path / "bundles")


# ------------------------------------------------------- FLOPs AOT cache


class TestFlopsCache:
    def test_warm_load_serves_cached_flops(self, tmp_path):
        """The envelope carries executable_stats; a warm hit feeds the
        MFU gauge without re-lowering (the compile_fn is NOT called)."""
        calls = []

        def compile_fn():
            calls.append(1)
            return jax.jit(lambda x: x @ x).lower(
                jax.ShapeDtypeStruct((32, 32), jnp.float32)
            ).compile()

        d = str(tmp_path / "aot")
        cold = cc.load_or_compile(
            "t1/kf", {"a": 1}, compile_fn,
            cache=cc.CompileCacheClient(local_dir=d),
        )
        assert not cold.cache_hit
        assert cold.flops > 0  # 2*32^3 up to backend accounting
        assert len(calls) == 1

        warm = cc.load_or_compile(
            "t1/kf", {"a": 1}, compile_fn,
            cache=cc.CompileCacheClient(local_dir=d),
        )
        assert warm.cache_hit
        assert len(calls) == 1  # no recompile, no re-lower
        assert warm.flops == cold.flops
        # and the loaded executable still runs
        y = warm.fn(jnp.ones((32, 32)))
        assert float(y[0, 0]) == 32.0

    def test_blob_stats_damage_reads_empty(self):
        assert cc.blob_stats(b"garbage") == {}
        compiled = jax.jit(lambda x: x + 1).lower(
            jax.ShapeDtypeStruct((4,), jnp.float32)
        ).compile()
        blob = cc.serialize_executable_blob(compiled, {"k": 1},
                                            stats={"flops": 12.0})
        assert cc.blob_stats(blob) == {"flops": 12.0}
        # flip a payload byte: CRC must turn stats into a miss too
        corrupt = bytearray(blob)
        corrupt[-1] ^= 0xFF
        assert cc.blob_stats(bytes(corrupt)) == {}


# ------------------------------------------------------------ monitor math


class TestEfficiencyMonitor:
    def test_mfu_and_gauge_readback(self):
        mon = eff.EfficiencyMonitor(
            model="m-test", strategy="s-test", flops_per_step=1e9,
            peak_flops=1e12, num_devices=2, journal_every=0,
        )
        for i in range(1, 5):
            mon.end_step(i, 0.01)
        # 1e9 / 0.01 / (1e12 * 2) = 0.05
        assert mon.mfu() == pytest.approx(0.05, rel=1e-6)
        assert eff.live_mfu("m-test", "s-test") == pytest.approx(
            0.05, abs=1e-4
        )

    def test_host_blocked_fraction(self):
        mon = eff.EfficiencyMonitor(model="m-hb", strategy="s",
                                    journal_every=0)
        # host-bound step: data_wait dwarfs block
        mon.observe_phase("data_wait", 0.5)
        mon.observe_phase("block", 0.01)
        mon.end_step(1, 0.51)
        # device-bound step
        mon.observe_phase("data_wait", 0.001)
        mon.observe_phase("block", 0.5)
        mon.end_step(2, 0.501)
        assert mon.host_blocked_frac() == pytest.approx(0.5)

    def test_no_peak_no_gauge(self):
        mon = eff.EfficiencyMonitor(model="m-np", strategy="s",
                                    flops_per_step=1e9, peak_flops=None,
                                    journal_every=0)
        mon.end_step(1, 0.01)
        assert mon.mfu() is None
        assert eff.live_mfu("m-np", "s") is None


# ---------------------------------------------- trainer phase integration


@pytest.mark.timeout(180)
def test_phase_histograms_account_for_step_time(journal_dir):
    """Run a real (tiny) compiled train loop: the five phase histograms
    must account for ~the whole step wall, and the journal must carry
    the metrics_sample points and the per-step train_step points (with
    every phase as a ``<phase>_s`` field) the report and timeline
    consume."""
    import optax

    from dlrover_tpu.models import transformer as T
    from dlrover_tpu.parallel import strategy as S
    from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer
    from dlrover_tpu.trainer.train_step import compile_train

    cfg = T.CONFIGS["tiny"]
    strat = S.dp()
    mesh = strat.build_mesh(jax.devices()[:1])
    compiled = compile_train(
        strategy=strat, mesh=mesh,
        loss_fn=lambda p, b: T.loss_fn(p, b, cfg),
        init_params_fn=lambda rng: T.init_params(cfg, rng),
        logical_params=T.logical_axes(cfg),
        optimizer=optax.adamw(1e-3),
    )

    def snap():
        out = {}
        for metric in registry().snapshot():
            if metric["name"] in ("dlrover_tpu_step_phase_seconds",
                                  "dlrover_tpu_train_step_seconds"):
                for s in metric["samples"]:
                    key = (metric["name"],
                           s["labels"].get("phase", ""))
                    out[key] = (s["sum"], s["count"])
        return out

    before = snap()
    trainer = ElasticTrainer(compiled, global_batch_size=2,
                             micro_batch_size=2, model_name="tiny")
    trainer.efficiency._journal_every = 2

    def batches():
        rng = np.random.default_rng(0)
        for _ in range(6):
            yield {"tokens": rng.integers(
                0, cfg.vocab_size, (1, 2, 33), dtype=np.int32)}

    trainer.run_batches(compiled.init(jax.random.PRNGKey(0)), batches())
    after = snap()

    def delta(name, phase=""):
        b = before.get((name, phase), (0.0, 0))
        a = after.get((name, phase), (0.0, 0))
        return a[0] - b[0], a[1] - b[1]

    step_sum, step_count = delta("dlrover_tpu_train_step_seconds")
    assert step_count == 6
    phase_sum = 0.0
    for phase in ("h2d", "dispatch", "block"):
        ps, pc = delta("dlrover_tpu_step_phase_seconds", phase)
        assert pc == 6, phase
        phase_sum += ps
    dw_sum, dw_count = delta("dlrover_tpu_step_phase_seconds",
                             "data_wait")
    assert dw_count == 6
    # h2d+dispatch+block tile the train_step wall (data_wait/ckpt sit
    # outside it); generous bounds — this is a wall-clock assertion
    assert phase_sum <= step_sum * 1.10 + 0.05
    assert phase_sum >= step_sum * 0.5

    events = load_events(os.path.join(journal_dir, "events.jsonl"))
    names = {e["name"] for e in events}
    assert "metrics_sample" in names and "step_phase" not in names
    steps = [e for e in events if e["name"] == "train_step"]
    assert [e["step"] for e in steps] == [2, 3, 4, 5, 6]
    assert all({f"{p}_s" for p in eff.PHASES} <= set(e) for e in steps)
    # dur is the loop's cadence: it holds the phases, data_wait included
    assert all(e["dur"] >= e["h2d_s"] + e["dispatch_s"] + e["block_s"]
               + e["data_wait_s"] - 1e-4 for e in steps)
    samples = [e for e in events if e["name"] == "metrics_sample"]
    # the phases are journaled once, on the train_step points
    assert samples and all("phases" not in s for s in samples)
    # CPU backend has no known peak: mfu must be null, never wrong
    assert all(s["mfu"] is None for s in samples)


# ------------------------------------------------ straggler-phase verdict


def _trainer_snapshot(step_sum: float, step_count: int,
                      phase_s: dict[str, float] | None = None,
                      phase_count: int = 0) -> list[dict]:
    """A pushed registry snapshot: step histogram + phase histograms
    (cumulative, like a real trainer's)."""
    snap = [{
        "name": "dlrover_tpu_train_step_seconds",
        "type": "histogram", "help": "", "buckets": [1.0],
        "samples": [{"labels": {}, "buckets": [step_count, 0],
                     "sum": step_sum, "count": step_count}],
    }]
    if phase_s:
        snap.append({
            "name": "dlrover_tpu_step_phase_seconds",
            "type": "histogram", "help": "", "buckets": [1.0],
            "samples": [
                {"labels": {"phase": p},
                 "buckets": [phase_count, 0],
                 "sum": s, "count": phase_count}
                for p, s in phase_s.items()
            ],
        })
    return snap


class TestStragglerPhase:
    def test_verdict_carries_dominant_phase(self, journal_dir):
        det = StragglerDetector(min_points=2)
        cum: dict[int, list] = {}
        for rounds in range(4):
            for nid in range(4):
                step_s = 0.5 if nid == 2 else 0.1
                prev = cum.setdefault(nid, [0.0, 0, {}])
                prev[0] += step_s * 10
                prev[1] += 10
                # the slow node's time goes to data_wait; peers are
                # device-bound
                phases = {"data_wait": 0.4 if nid == 2 else 0.01,
                          "block": 0.05}
                for p, v in phases.items():
                    prev[2][p] = prev[2].get(p, 0.0) + v * 10
                det.observe_snapshot(nid, _trainer_snapshot(
                    prev[0], prev[1],
                    phase_s=prev[2], phase_count=prev[1],
                ))
        assert det.stragglers() == [2]
        events = load_events(os.path.join(journal_dir, "events.jsonl"))
        flagged = [e for e in events if e["name"] == "straggler_verdict"
                   and e["state"] == "flagged"]
        assert [(e["node"], e["phase"]) for e in flagged] == \
            [(2, "data_wait")]
        # the score gauge carries the phase label while flagged
        from dlrover_tpu.telemetry.anomaly import _score_gauge

        samples = {tuple(sorted(s["labels"].items())): s["value"]
                   for s in _score_gauge.samples()}
        key = (("node", "2"), ("straggler_phase", "data_wait"))
        assert samples[key] == pytest.approx(5.0, rel=0.01)

    def test_clear_resets_phase_label(self, journal_dir):
        det = StragglerDetector(min_points=2, window=8)
        cum: dict[int, list] = {}

        def feed(rounds, slow_id):
            for _ in range(rounds):
                for nid in range(4):
                    step_s = 0.5 if nid == slow_id else 0.1
                    prev = cum.setdefault(nid, [0.0, 0, {}])
                    prev[0] += step_s * 10
                    prev[1] += 10
                    prev[2]["ckpt"] = prev[2].get("ckpt", 0.0) + (
                        4.0 if nid == slow_id else 0.1)
                    det.observe_snapshot(nid, _trainer_snapshot(
                        prev[0], prev[1], phase_s=prev[2],
                        phase_count=prev[1],
                    ))

        feed(3, slow_id=1)
        assert det.stragglers() == [1]
        feed(12, slow_id=-1)  # recovery
        assert det.stragglers() == []
        events = load_events(os.path.join(journal_dir, "events.jsonl"))
        verdicts = [(e["state"], e.get("phase"))
                    for e in events if e["name"] == "straggler_verdict"]
        assert verdicts[0] == ("flagged", "ckpt")
        assert verdicts[-1][0] == "cleared"
        from dlrover_tpu.telemetry.anomaly import _score_gauge

        samples = {tuple(sorted(s["labels"].items())): s["value"]
                   for s in _score_gauge.samples()}
        # the stale flagged-phase series was zeroed on re-attribution
        assert samples.get((("node", "1"),
                            ("straggler_phase", "ckpt")), 0.0) == 0.0


# -------------------------------------------------------- profile capture


class TestProfileCapture:
    @pytest.mark.timeout(120)
    def test_request_to_bundle_roundtrip(self, journal_dir, bundle_dir):
        """request file -> K-step capture -> bundle with a non-empty
        xplane trace, journaled and counted."""
        reported = []
        mon = eff.EfficiencyMonitor(model="m-prof", strategy="s",
                                    node_id=7, journal_every=0,
                                    on_bundle=reported.append)
        assert eff.arm_profile_request(7, steps=2) is not None
        f = jax.jit(lambda x: x @ x)
        x = jnp.ones((64, 64))
        for i in range(1, 6):
            jax.block_until_ready(f(x))
            mon.end_step(i, 0.001)
        # request consumed, capture finished, no second capture
        assert not os.path.exists(eff.profile_request_path(7))
        bundles = glob.glob(os.path.join(bundle_dir, "bundle_*_profile_*"))
        assert len(bundles) == 1
        xplanes = glob.glob(os.path.join(bundles[0], "profile", "**",
                                         "*.xplane.pb"), recursive=True)
        assert xplanes and os.path.getsize(xplanes[0]) > 0
        manifest = json.load(open(os.path.join(bundles[0],
                                               "manifest.json")))
        assert manifest["attached"] == ["profile"]
        assert manifest["extra"]["steps"] == 2
        assert reported == bundles
        events = load_events(os.path.join(journal_dir, "events.jsonl"))
        caps = [e for e in events if e["name"] == "profile_capture"]
        assert len(caps) == 1 and caps[0]["steps"] == 2

    def test_profile_request_rpc_queues_heartbeat_action(self, tmp_path,
                                                         monkeypatch):
        """ProfileRequest -> NodeManager.send_action -> the node's next
        heartbeat delivers profile:<K> (the agent then arms the request
        file); unknown nodes are refused."""
        monkeypatch.delenv(EnvKey.METRICS_PORT, raising=False)
        from dlrover_tpu.master.job_master import JobMaster

        master = JobMaster(job_name="eff-test", port=0, min_nodes=1,
                           max_nodes=1)
        try:
            handle = master.servicer.handle
            assert handle(m.NodeHeartbeat(node_id=0)).action == ""
            resp = handle(serde.decode(serde.encode(
                m.ProfileRequest(node_id=0, steps=3))))
            assert isinstance(resp, m.ProfileResponse) and resp.armed
            assert handle(m.NodeHeartbeat(node_id=0)).action == \
                "profile:3"
            # delivered once
            assert handle(m.NodeHeartbeat(node_id=0)).action == ""
            refused = handle(m.ProfileRequest(node_id=9, steps=3))
            assert not refused.armed and refused.reason
        finally:
            master._server._server.server_close()

    def test_capture_error_is_contained(self, bundle_dir, monkeypatch):
        """A failing profiler must not take down the step loop."""
        mon = eff.EfficiencyMonitor(model="m-err", strategy="s",
                                    node_id=8, journal_every=0)
        eff.arm_profile_request(8, steps=1)
        monkeypatch.setattr(
            jax.profiler, "start_trace",
            lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom")),
        )
        mon.end_step(1, 0.001)  # must not raise
        mon.end_step(2, 0.001)
        assert mon._capture_dir is None


# ------------------------------------------------- exposition conformance


_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^{}]*\})? (\S+)$"
)
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_exposition(text: str) -> dict[str, dict]:
    """Strict Prometheus text-format conformance parse.

    Enforces: HELP/TYPE precede a family's samples, TYPE exactly once,
    all of a family's samples contiguous (no interleaving), histogram
    series limited to _bucket/_sum/_count with cumulative monotonic
    buckets ending at le="+Inf" == _count. Returns family -> info.
    """
    families: dict[str, dict] = {}
    current: str | None = None

    def family_of(sample_name: str) -> str:
        for suffix in ("_bucket", "_sum", "_count"):
            base = sample_name[:-len(suffix)] \
                if sample_name.endswith(suffix) else None
            if base and families.get(base, {}).get("type") == "histogram":
                return base
        return sample_name

    for lineno, line in enumerate(text.splitlines(), 1):
        assert line.strip() == line and line, f"line {lineno}: whitespace"
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            kind, rest = line[2:6], line[7:]
            name, _, value = rest.partition(" ")
            fam = families.setdefault(
                name, {"type": None, "help": None, "samples": [],
                       "closed": False})
            assert not fam["samples"], \
                f"line {lineno}: meta after samples for {name}"
            if kind == "HELP":
                assert fam["help"] is None, f"duplicate HELP {name}"
                assert value, f"line {lineno}: empty HELP for {name}"
                fam["help"] = value
            else:
                assert fam["type"] is None, f"duplicate TYPE {name}"
                assert value in ("counter", "gauge", "histogram",
                                 "untyped"), value
                fam["type"] = value
            continue
        match = _SAMPLE_RE.match(line)
        assert match, f"line {lineno}: unparseable sample {line!r}"
        name, labels_text, value = match.groups()
        float("+inf" if value == "+Inf" else value)  # numeric
        labels = dict(_LABEL_RE.findall(labels_text or ""))
        fam_name = family_of(name)
        fam = families.get(fam_name)
        assert fam is not None and fam["type"] is not None, \
            f"line {lineno}: sample {name} before # TYPE"
        if current != fam_name:
            assert not fam["closed"], \
                f"line {lineno}: family {fam_name} interleaved"
            if current is not None:
                families[current]["closed"] = True
            current = fam_name
        if fam["type"] == "histogram":
            assert name.endswith(("_bucket", "_sum", "_count")), name
            if name.endswith("_bucket"):
                assert "le" in labels, f"line {lineno}: bucket sans le"
        else:
            assert name == fam_name
        fam["samples"].append((name, labels, value))

    for fam_name, fam in families.items():
        if fam["type"] != "histogram":
            continue
        series: dict[tuple, list] = {}
        counts: dict[tuple, float] = {}
        for name, labels, value in fam["samples"]:
            key = tuple(sorted((k, v) for k, v in labels.items()
                               if k != "le"))
            if name.endswith("_bucket"):
                series.setdefault(key, []).append(
                    (math.inf if labels["le"] == "+Inf"
                     else float(labels["le"]), float(value))
                )
            elif name.endswith("_count"):
                counts[key] = float(value)
        for key, buckets in series.items():
            les = [le for le, _ in buckets]
            values = [v for _, v in buckets]
            assert les == sorted(les), f"{fam_name}: le out of order"
            assert les[-1] == math.inf, f"{fam_name}: no +Inf bucket"
            assert values == sorted(values), \
                f"{fam_name}: non-cumulative buckets"
            assert values[-1] == counts.get(key), \
                f"{fam_name}: +Inf bucket != _count"
    return families


class TestExpositionConformance:
    def test_full_default_registry_parses(self):
        # the process registry holds every family the imported modules
        # registered (trainer, master, telemetry, ...); all must render
        # promtool-parseable with non-empty help
        text = render()
        families = parse_exposition(text)
        assert "dlrover_tpu_mfu" in families
        assert "dlrover_tpu_step_phase_seconds" in families
        for name, fam in families.items():
            assert fam["help"], f"{name} rendered without HELP"

    def test_grouped_master_scrape_parses(self):
        """The master's one-scrape shape: its own registry + per-node
        snapshots sharing families — grouped, meta emitted once."""
        master = MetricsRegistry()
        master.counter("dlrover_tpu_conf_total", "requests",
                       label_names=("kind",)).labels("a").inc(2)
        node = MetricsRegistry()
        node.counter("dlrover_tpu_conf_total", "requests",
                     label_names=("kind",)).labels("a").inc(5)
        node.histogram("dlrover_tpu_conf_seconds", "latency",
                       buckets=(0.5, 1.0)).observe(0.7)
        text = render_grouped([
            (master.snapshot(), {"role": "master"}),
            (node.snapshot(), {"node": "0", "role": "trainer"}),
            (node.snapshot(), {"node": "1", "role": "trainer"}),
        ])
        families = parse_exposition(text)
        assert len(families["dlrover_tpu_conf_total"]["samples"]) == 3
        # node-only family got its meta from the node snapshot
        assert families["dlrover_tpu_conf_seconds"]["help"] == "latency"
        assert text.count("# TYPE dlrover_tpu_conf_total") == 1

    def test_live_master_metrics_text_parses(self, tmp_path,
                                             monkeypatch):
        monkeypatch.delenv(EnvKey.METRICS_PORT, raising=False)
        from dlrover_tpu.master.job_master import JobMaster

        master = JobMaster(job_name="conf-test", port=0, min_nodes=1,
                           max_nodes=1)
        try:
            reg = MetricsRegistry()
            reg.counter("dlrover_tpu_conf_pushed_total", "pushed").inc(4)
            master.servicer.handle(m.MetricsSnapshotRequest(
                node_id=3, role="trainer", samples=reg.snapshot(),
            ))
            families = parse_exposition(master.metrics_text())
            assert "dlrover_tpu_conf_pushed_total" in families
            assert "dlrover_tpu_master_rpc_seconds" in families
        finally:
            master._server._server.server_close()


# --------------------------------------------- report + timeline surfaces


def _write_journal_line(f, **ev):
    f.write(json.dumps(ev) + "\n")


def _sample_event(t, step, mfu, proc="node0", **extra):
    return dict(t=t, trace="tr", span=f"ms{step}", name="metrics_sample",
                ev="p", proc=proc, pid=1, step=step, mfu=mfu,
                step_s=0.1, host_blocked_frac=0.25, **extra)


class TestReportEfficiency:
    def _journal(self, path):
        t0 = 1000.0
        with open(path, "w") as f:
            for i, step in enumerate((5, 10, 15)):
                _write_journal_line(f, **_sample_event(
                    t0 + i, step, 0.5 + 0.1 * i))
                _write_journal_line(
                    f, t=t0 + i, trace="tr", span=f"ts{step}",
                    name="train_step", ev="p", proc="node0", pid=1,
                    dur=0.1, step=step, data_wait_s=0.01, block_s=0.08)
            # incarnation 1 after a restart
            _write_journal_line(
                f, t=t0 + 10, trace="tr", span="nr1", name="node_restart",
                ev="p", proc="node0", pid=1, incarnation=1, dur=1.0)
            _write_journal_line(f, **_sample_event(t0 + 20, 20, 0.3))

    def test_efficiency_rows_per_incarnation(self, tmp_path):
        path = str(tmp_path / "events.jsonl")
        self._journal(path)
        report = build_report(path)
        assert len(report.efficiency) == 2
        inc0, inc1 = report.efficiency
        assert inc0["incarnation"] == 0 and inc0["samples"] == 3
        assert inc0["mfu_mean"] == pytest.approx(0.6, abs=1e-6)
        assert inc0["mfu_min"] == 0.5 and inc0["mfu_max"] == 0.7
        assert inc0["host_blocked_pct"] == 25.0
        assert inc0["phase_s"]["block"] == pytest.approx(0.08)
        assert inc0["phase_pct"]["block"] == pytest.approx(80.0)
        assert inc1["incarnation"] == 1
        assert inc1["mfu_mean"] == pytest.approx(0.3)

    def test_format_json_cli(self, tmp_path, capsys):
        path = str(tmp_path / "events.jsonl")
        self._journal(path)
        assert report_main(["--journal", path, "--format", "json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert set(doc) >= {"total_s", "lost_s", "categories",
                            "incarnations", "efficiency"}
        assert doc["efficiency"][0]["mfu_mean"] == pytest.approx(0.6)
        # text mode renders the steady-state table
        assert report_main(["--journal", path]) == 0
        out = capsys.readouterr().out
        assert "steady-state efficiency" in out

    def test_timeline_counter_tracks_across_rotation(self, tmp_path):
        """metrics_sample and train_step points split across a journal
        rotation render as ph='C' counter events: the mfu lane from the
        samples, the stacked phase lane from each step's own phases."""
        live = str(tmp_path / "events.jsonl")
        step = dict(trace="tr", name="train_step", ev="p", proc="node0",
                    pid=1, dur=0.1, data_wait_s=0.01, h2d_s=0.002,
                    dispatch_s=0.003, block_s=0.08, ckpt_s=0.0)
        with open(live + ".1", "w") as f:
            _write_journal_line(f, **_sample_event(1000.0, 5, 0.5))
            _write_journal_line(f, t=1000.5, span="ts1", step=5, **step)
            # a point from before the phases rode on it: a lane, no counter
            _write_journal_line(
                f, t=1000.6, trace="tr", span="ts0", name="train_step",
                ev="p", proc="node0", pid=1, dur=0.1, step=6)
        with open(live, "w") as f:
            _write_journal_line(f, **_sample_event(1001.0, 10, 0.6))
            _write_journal_line(f, t=1001.5, span="ts2", step=10, **step)
        trace = build_trace([live])
        counters = [e for e in trace["traceEvents"] if e["ph"] == "C"]
        mfu = [e for e in counters if e["name"] == "mfu"]
        assert [e["args"]["mfu"] for e in mfu] == [0.5, 0.6]
        phases = [e for e in counters
                  if e["name"] == "step_phase_seconds"]
        assert len(phases) == 2
        assert phases[0]["args"] == {"data_wait": 0.01, "h2d": 0.002,
                                     "dispatch": 0.003, "block": 0.08,
                                     "ckpt": 0.0}
        # metrics_sample is a counter source, not a span lane; train_step
        # keeps its lane beside the counter it feeds
        assert not any(e.get("name") == "metrics_sample"
                       for e in trace["traceEvents"] if e["ph"] != "C")
        assert sum(e.get("name") == "train_step" and e["ph"] == "X"
                   for e in trace["traceEvents"]) == 3
        assert trace["otherData"]["n_counter_samples"] == 4


# -------------------------------------------------- live standalone e2e


@pytest.mark.slow
@pytest.mark.timeout(300)
def test_profile_request_against_running_standalone_job(tmp_path):
    """The acceptance path end to end: a ProfileRequest RPC against a
    live ``dlrover_tpu.run --standalone`` job produces a debug bundle
    containing a non-empty xplane trace, without restarting the job."""
    import subprocess
    import sys
    import threading
    import time

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    example = os.path.join(repo, "examples", "train_transformer.py")
    bundles = str(tmp_path / "bundles")
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "DLROVER_TPU_DEVICE_COUNT": "1",
        "DLROVER_TPU_IPC_DIR": str(tmp_path / "ipc"),
        "DLROVER_TPU_JOURNAL_DIR": str(tmp_path / "journal"),
        "DLROVER_TPU_BUNDLE_DIR": bundles,
        "DLROVER_TPU_STANDBY": "0",
        "PYTHONPATH": repo,
    })
    cmd = [
        sys.executable, "-m", "dlrover_tpu.run", "--standalone",
        "--monitor-interval", "0.3", "--heartbeat-interval", "0.5",
        "--job-name", f"profile-e2e-{os.getpid()}",
        example, "--",
        "--model", "tiny", "--global-batch", "8", "--seq", "128",
        "--max-steps", "2000", "--step-delay", "0.05",
        "--ckpt-dir", str(tmp_path / "ckpt"),
    ]
    proc = subprocess.Popen(cmd, env=env, cwd=repo, text=True,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE)
    addr_holder: list[str] = []

    def _scan(stream):
        for line in stream:
            match = re.search(r"standalone master at (\S+)", line)
            if match and not addr_holder:
                addr_holder.append(match.group(1))

    threads = [threading.Thread(target=_scan, args=(proc.stderr,),
                                daemon=True),
               threading.Thread(target=_scan, args=(proc.stdout,),
                                daemon=True)]
    for t in threads:
        t.start()
    try:
        deadline = time.monotonic() + 120
        while not addr_holder and time.monotonic() < deadline:
            assert proc.poll() is None, "job exited before serving"
            time.sleep(0.2)
        assert addr_holder, "master address never logged"

        from dlrover_tpu.agent.master_client import MasterClient

        client = MasterClient(addr_holder[0], node_id=0)
        try:
            armed = False
            while time.monotonic() < deadline and not armed:
                # the node registers at its first heartbeat; retry
                armed = client.request_profile(0, steps=3).armed
                if not armed:
                    time.sleep(0.5)
            assert armed, "node 0 never became profilable"

            xplanes: list[str] = []
            while time.monotonic() < deadline and not xplanes:
                assert proc.poll() is None, "job exited mid-capture"
                xplanes = glob.glob(os.path.join(
                    bundles, "bundle_*_profile_*", "profile", "**",
                    "*.xplane.pb"), recursive=True)
                time.sleep(0.5)
            assert xplanes, "no xplane trace landed in a bundle"
            assert os.path.getsize(xplanes[0]) > 0
            listed = client.list_debug_bundles()
            assert any(b.reason == "profile" for b in listed)
        finally:
            client.close()
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            proc.kill()
        # only THIS test's trainer (its ckpt dir) and master (its job
        # name): the suite runs files on parallel workers, and a broad
        # pattern kills a sibling test's job
        subprocess.run(["pkill", "-9", "-f", str(tmp_path)],
                       capture_output=True)
        subprocess.run(
            ["pkill", "-9", "-f", f"job-name profile-e2e-{os.getpid()}"],
            capture_output=True,
        )


# ------------------------------------------------------------ name lint


def test_metric_and_label_contract_lint():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_metric_names",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "native",
            "check_metric_names.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    names, problems = mod.scan()
    assert not problems, problems
    assert any(n.startswith("dlrover_tpu_mfu") for n in names)
    assert "dlrover_tpu_step_phase_seconds" in names
    assert mod.check_contract_labels() == []
    # a missing DESIGN.md entry for a contract family must be caught
    with tempfile.NamedTemporaryFile("w", suffix=".md") as f:
        f.write("nothing documented here\n")
        f.flush()
        missing = mod.check_documented(
            {"dlrover_tpu_mfu": ["x.py:1"]}, design_path=f.name)
        assert missing
