"""Strategy autopilot (DESIGN.md §24): planner determinism, the one
fingerprint vocabulary, controller hysteresis + bounded retunes, the
retune-path matrix, the master push wiring, and the ISSUE-13 acceptance
closed loop — plan via AOT enumeration, train, seeded contradiction,
exactly one journaled no-restart retune, same loss as launching the
winner directly."""

from __future__ import annotations

import functools
import json
import math
import os
import time

import numpy as np
import pytest

from dlrover_tpu.autopilot import (
    AutopilotController,
    Plan,
    PlanHistory,
    canonical_strategy_json,
    choose_path,
    enumerate_plans,
    plan_fingerprint,
    shape_key,
)
from dlrover_tpu.common.constants import EnvKey
from dlrover_tpu.parallel.strategy import dp, mpmd, zero1

TINY_SEQ = 16
TINY_BATCH = 8


def _tiny_cfg():
    from dlrover_tpu.models import transformer as tfm

    return tfm.CONFIGS["tiny"]


def _planner_kwargs(**over):
    import optax

    from dlrover_tpu.models import transformer as tfm

    cfg = _tiny_cfg()
    kw = dict(
        model="tiny",
        loss_fn_for=lambda s, m: tfm.make_loss_fn(cfg, s, m),
        init_params_fn=functools.partial(tfm.init_params, cfg),
        logical_params=tfm.logical_axes(cfg),
        optimizer=optax.adamw(1e-3),
        example_batch={
            "tokens": np.zeros((1, TINY_BATCH, TINY_SEQ + 1), np.int32)
        },
        batch=TINY_BATCH,
        seq=TINY_SEQ,
        model_cfg=cfg,
    )
    kw.update(over)
    return kw


def _mk_plan(strategy, schedule="spmd", pred=0.01, source="model",
             **over):
    sj = canonical_strategy_json(strategy)
    fields = dict(
        name=f"{strategy.name}/{schedule}",
        strategy_json=sj,
        schedule=schedule,
        mesh_axes=dict(strategy.mesh_axes),
        pred_step_s=pred,
        analytic_step_s=pred,
        source=source,
        fingerprint=plan_fingerprint(sj, schedule),
        model="tiny", n_devices=8, batch=TINY_BATCH, seq=TINY_SEQ,
    )
    fields.update(over)
    return Plan(**fields)


# --------------------------------------------------------- envelope input


def test_device_hbm_bytes_env_override(monkeypatch):
    """ISSUE-13 satellite: CPU backends state the REAL
    envelope through DLROVER_TPU_DEVICE_HBM_BYTES instead of the
    conservative default (0 on CPU = fit check silently skipped)."""
    from dlrover_tpu.parallel.auto import device_hbm_bytes

    monkeypatch.delenv(EnvKey.DEVICE_HBM_BYTES, raising=False)
    assert device_hbm_bytes() == 0  # CPU default: no envelope
    monkeypatch.setenv(EnvKey.DEVICE_HBM_BYTES, str(8 << 30))
    assert device_hbm_bytes() == 8 << 30


# ----------------------------------------------- one fingerprint vocabulary


class TestFingerprintVocabulary:
    def test_canonical_json_is_format_invariant(self):
        s = zero1()
        indented = s.to_json()                      # indent=2 format
        compact = canonical_strategy_json(s)
        assert "\n" not in compact
        assert canonical_strategy_json(indented) == compact
        assert canonical_strategy_json(json.loads(indented)) == compact

    def test_shape_key_matches_engine_service_schema(self):
        """The autopilot reads exactly the key the engine service
        writes: a measurement reported through the typed client (the
        path parallel/search.py's successive-halving winner takes)
        must come back from a PlanHistory lookup at the same key."""
        from dlrover_tpu.parallel.engine_service import (
            StrategyEngineClient,
            StrategyEngineService,
        )

        svc = StrategyEngineService(port=0).start()
        try:
            client = StrategyEngineClient(svc.addr, timeout=10.0)
            # report with the VERBOSE json (what a Strategy object
            # serializes to) — the vocabulary must normalize it
            client.report_measurement(
                "tiny", 8, zero1().to_json(), 0.042,
                batch=TINY_BATCH, seq=TINY_SEQ, mfu=0.37,
            )
            hist = PlanHistory(client=client)
            got = hist.lookup("tiny", 8, TINY_BATCH, TINY_SEQ)
            key = canonical_strategy_json(zero1())
            assert got[key]["step_time_s"] == pytest.approx(0.042)
            assert got[key]["mfu"] == pytest.approx(0.37)
            # the service's own measured-history fast path serves the
            # same entry (shape_key alignment end to end)
            prop = client.propose("tiny", 8, batch=TINY_BATCH,
                                  seq=TINY_SEQ)
            assert prop.found and prop.source == "measured"
            assert canonical_strategy_json(prop.strategy_json) == key
            client.close()
        finally:
            svc.stop()

    def test_sqlite_history_persists_mfu(self, tmp_path):
        db = str(tmp_path / "hist.sqlite")
        h = PlanHistory(db_path=db)
        assert h.record(dp(), 0.08, model="tiny", n_devices=8,
                        batch=TINY_BATCH, seq=TINY_SEQ, mfu=0.5)
        h.close()
        h2 = PlanHistory(db_path=db)
        got = h2.lookup("tiny", 8, TINY_BATCH, TINY_SEQ)
        entry = got[canonical_strategy_json(dp())]
        assert entry == {"step_time_s": pytest.approx(0.08),
                         "mfu": pytest.approx(0.5)}
        h2.close()

    def test_shape_key_tuple_shape(self):
        assert shape_key("tiny", 8, 8, 16, 0.0) == ("tiny", 8, 8, 16,
                                                    0.0)

    def test_record_key_matches_lookup_under_env_envelope(
            self, monkeypatch):
        """The end-of-run record must key on the SAME hbm_gb the
        planner's lookup derives from the device envelope: with
        DLROVER_TPU_DEVICE_HBM_BYTES set (or a real TPU peak), a
        record that omits hbm_gb lands under a different shape key
        and cross-job seeding silently never happens."""
        from dlrover_tpu.parallel.engine_service import (
            StrategyEngineService,
        )

        monkeypatch.setenv(EnvKey.DEVICE_HBM_BYTES, str(8 << 30))
        hist = PlanHistory(service=StrategyEngineService())
        kwargs = _planner_kwargs()
        ranked = enumerate_plans(points=[(dp(), "spmd")], history=hist,
                                 **kwargs)
        plan = ranked.winner
        assert plan.source == "model"
        assert plan.hbm_gb == pytest.approx(8.0)
        # the trainer's end-of-run record: keyed by the plan's STAMPED
        # shape fields, exactly what examples/train_transformer.py and
        # bench.py now pass
        assert hist.record(
            plan.strategy_json, 0.033, model=plan.model,
            n_devices=plan.n_devices, batch=plan.batch, seq=plan.seq,
            hbm_gb=plan.hbm_gb,
        )
        ranked2 = enumerate_plans(points=[(dp(), "spmd")],
                                  history=hist, **kwargs)
        assert ranked2.winner.source == "history"
        assert ranked2.winner.pred_step_s == pytest.approx(0.033)
        hist.close()


# ----------------------------------------------------------------- planner


class TestPlanner:
    def test_seeded_determinism_and_mpmd_point(self):
        """Same inputs -> identical ranked list (ISSUE-13 satellite),
        with the MPMD schedule point enumerated beside the SPMD one.
        Two points only: each extra SPMD point costs a full AOT compile
        per run and the property is point-count-independent (the
        closed-loop acceptance test ranks a 2-SPMD field)."""
        points = [(dp(), "spmd"), (mpmd(pipeline_size=2), "mpmd")]
        runs = []
        for _ in range(2):
            ranked = enumerate_plans(
                points=list(points), **_planner_kwargs()
            )
            runs.append([
                (p.name, p.schedule, p.fingerprint,
                 round(p.pred_step_s, 9), p.source, p.rank)
                for p in ranked.plans
            ])
        assert runs[0] == runs[1]
        names = [r[0] for r in runs[0]]
        assert "mpmd/mpmd" in names
        # every plan is launch-complete: strategy parses, mesh recorded
        ranked_names = {p.name for p in ranked.plans}
        assert ranked_names == set(names)
        for p in ranked.plans:
            assert p.strategy().name
            assert p.pred_step_s > 0

    def test_envelope_filters_oom_points(self):
        """A 1-byte envelope rejects everything -> the planner refuses
        to emit an OOM-infeasible plan rather than guessing."""
        with pytest.raises(RuntimeError, match="no candidate point"):
            enumerate_plans(
                points=[(dp(), "spmd")],
                hbm_capacity_bytes=1,
                **_planner_kwargs(),
            )

    def test_history_outranks_and_calibrates(self):
        """Measured entries re-score their plan (source=history) and
        calibrate the unmeasured plans' analytic scale — a measured
        winner is never shadowed by an optimistic estimate."""
        from dlrover_tpu.autopilot.planner import (
            RankedPlans,
            _rescore_from_history,
        )
        from dlrover_tpu.parallel.engine_service import (
            StrategyEngineService,
        )

        from dlrover_tpu.parallel.strategy import fsdp

        p_z1 = _mk_plan(zero1(), pred=3e-4, rank=0)
        p_dp = _mk_plan(dp(), pred=4e-4, rank=1)
        p_fs = _mk_plan(fsdp(), pred=5e-4, rank=2)
        ranked = RankedPlans(plans=[p_z1, p_dp, p_fs])
        svc = StrategyEngineService()  # in-process, never started
        hist = PlanHistory(service=svc)
        # measured: the analytic order inverts at this shape — dp runs
        # 4x FASTER than zero1 despite the worse estimate
        hist.record(zero1(), 0.08, model="tiny", n_devices=8,
                    batch=TINY_BATCH, seq=TINY_SEQ)
        hist.record(dp(), 0.02, model="tiny", n_devices=8,
                    batch=TINY_BATCH, seq=TINY_SEQ)
        _rescore_from_history(ranked, hist)
        assert ranked.winner.name == "dp/spmd"
        assert ranked.winner.source == "history"
        assert ranked.winner.pred_step_s == pytest.approx(0.02)
        z1 = next(p for p in ranked.plans if p.name == "zero1/spmd")
        assert z1.source == "history"
        assert z1.pred_step_s == pytest.approx(0.08)
        # the unmeasured fsdp was rescaled by the median
        # measured/analytic factor, not left at its raw 5e-4 estimate
        # (a raw optimistic estimate would shadow the measured winner)
        factor = (0.08 / 3e-4 + 0.02 / 4e-4) / 2
        fs = next(p for p in ranked.plans if p.name == "fsdp/spmd")
        assert fs.source == "model"
        assert fs.pred_step_s == pytest.approx(5e-4 * factor)
        hist.close()


# -------------------------------------------------------------- controller


class TestController:
    def _controller(self, fired, **over):
        kw = dict(tolerance=1.5, clear_ratio=1.2, action_streak=3,
                  min_points=2, window=4, max_retunes=2,
                  on_retune=fired.append)
        kw.update(over)
        return AutopilotController(**kw)

    def test_transient_dip_does_not_retune(self):
        fired = []
        c = self._controller(fired, window=3)
        c.arm(_mk_plan(zero1(), pred=0.01, source="history"),
              [_mk_plan(dp(), pred=0.012)])
        # a two-push dip builds a streak (1, 2) but recovery drops the
        # rolling median under the clear ratio before the action streak
        # (3) is reached: hysteresis resets and nothing ever fires
        for v in (0.011, 0.011, 0.05, 0.05, 0.011, 0.011, 0.011,
                  0.05, 0.05, 0.011, 0.011, 0.011):
            c.observe_step_time(v)
        assert fired == []
        assert c.retunes_used == 0
        assert c.plan.name == "zero1/spmd"

    def test_sustained_contradiction_retunes_once(self, tmp_path,
                                                  monkeypatch):
        monkeypatch.setenv(EnvKey.JOURNAL_DIR, str(tmp_path))
        fired = []
        c = self._controller(fired, max_retunes=1)
        c.arm(_mk_plan(zero1(), pred=0.01, source="history"),
              [_mk_plan(dp(), pred=0.012)])
        for _ in range(20):  # way past the streak: the clamp holds
            c.observe_step_time(0.05)
        assert len(fired) == 1
        d = fired[0]
        assert d.from_plan.name == "zero1/spmd"
        assert d.to_plan.name == "dp/spmd"
        assert d.path == "hot"
        assert d.evidence["ratio"] == pytest.approx(5.0)
        assert c.retunes_used == 1
        # decision trail: exactly one autopilot_retune with evidence
        lines = []
        for root, _dirs, files in os.walk(tmp_path):
            for f in files:
                if f.endswith(".jsonl"):
                    with open(os.path.join(root, f)) as fh:
                        lines += [json.loads(ln) for ln in fh
                                  if "autopilot_retune" in ln]
        assert len(lines) == 1
        ev = lines[0]
        assert ev["path"] == "hot"
        assert ev["measured_step_s"] == pytest.approx(0.05)
        assert ev["pred_step_s"] == pytest.approx(0.01)
        assert ev["streak"] >= 3

    def test_model_plan_calibrates_before_judging(self):
        """An analytic (source=model) prediction is replaced by the
        first healthy window — absolute roofline scale is never
        treated as a contradiction — then a real degradation fires."""
        fired = []
        c = self._controller(fired)
        # absurdly optimistic analytic pred: 50x off, like CPU
        c.arm(_mk_plan(zero1(), pred=0.001, source="model"),
              [_mk_plan(dp(), pred=0.0012)])
        for _ in range(6):
            c.observe_step_time(0.05)  # healthy steady state
        assert fired == []            # calibrated, not contradicted
        assert c.plan.pred_step_s == pytest.approx(0.05)
        for _ in range(8):
            c.observe_step_time(0.2)  # real 4x degradation
        assert len(fired) == 1

    def test_bounded_retunes_clamp(self):
        fired = []
        c = self._controller(fired, max_retunes=2)
        c.arm(_mk_plan(zero1(), pred=0.01, source="history"),
              [_mk_plan(dp(), pred=0.01, source="history"),
               _mk_plan(dp(grad_compression=True), pred=0.011,
                        source="history")])
        for _ in range(60):  # every plan keeps contradicting
            c.observe_step_time(0.08)
        assert len(fired) == 2
        assert c.retunes_used == 2

    def test_snapshot_delta_mining(self):
        """observe_snapshot extracts per-push mean step time from the
        cumulative histogram exactly like telemetry/anomaly.py."""
        fired = []
        c = self._controller(fired, min_points=2, action_streak=2)
        c.arm(_mk_plan(zero1(), pred=0.01, source="history"),
              [_mk_plan(dp(), pred=0.012)])

        def push(total, count, mfu=None):
            fam = [{"name": "dlrover_tpu_train_step_seconds",
                    "type": "histogram",
                    "samples": [{"sum": total, "count": count}]}]
            if mfu is not None:
                fam.append({"name": "dlrover_tpu_mfu", "type": "gauge",
                            "samples": [{"labels": {}, "value": mfu}]})
            return c.observe_snapshot(0, fam)

        push(0.5, 10, mfu=0.4)       # 0.05/step — contradiction builds
        push(1.0, 20)
        push(1.5, 30)
        assert len(fired) == 1
        assert fired[0].evidence["mfu"] == pytest.approx(0.4)

    def test_retune_path_matrix(self):
        """hot (knobs only) vs reshard (mesh change) vs reschedule
        (SPMD<->MPMD) — the decision table of DESIGN.md §24."""
        from dlrover_tpu.parallel.strategy import fsdp

        cur = _mk_plan(zero1())
        assert choose_path(cur, _mk_plan(dp())) == "hot"
        assert choose_path(cur, _mk_plan(fsdp())) == "reshard"
        assert choose_path(
            cur, _mk_plan(mpmd(pipeline_size=2), schedule="mpmd")
        ) == "reschedule"
        # schedule wins over mesh: mpmd's mesh also differs, but the
        # runtime rebuild is the mechanism that applies it
        mp = _mk_plan(mpmd(pipeline_size=2), schedule="mpmd",
                      mesh_axes={"data": 4})
        assert choose_path(cur, mp) == "reschedule"

    def test_applicability_veto_falls_through(self):
        fired = []
        c = self._controller(
            fired,
            applicable=lambda cur, t: t.schedule == cur.schedule,
        )
        c.arm(_mk_plan(zero1(), pred=0.01, source="history"),
              [_mk_plan(mpmd(pipeline_size=2), schedule="mpmd",
                        pred=0.005),
               _mk_plan(dp(), pred=0.012)])
        for _ in range(10):
            c.observe_step_time(0.05)
        assert len(fired) == 1
        # the faster mpmd alternative was vetoed; dp applied instead
        assert fired[0].to_plan.name == "dp/spmd"


# ------------------------------------------ master-side applicability


class TestPlanApplicable:
    """plan_applicable: the device-free mirror of apply.can_apply the
    servicer wires as the controller's predicate — an alternative the
    trainer would veto is never armed, journaled, or charged."""

    def test_schedule_gate(self):
        from dlrover_tpu.autopilot.apply import plan_applicable

        cur = _mk_plan(zero1())
        assert plan_applicable(cur, _mk_plan(dp()))
        assert not plan_applicable(
            cur, _mk_plan(mpmd(pipeline_size=2), schedule="mpmd")
        )

    def test_batch_divisibility_from_stamped_world(self):
        """dp width resolves arithmetically from the plan's stamped
        mesh_axes/n_devices — the master never builds a mesh over its
        OWN devices (which are not the trainer's)."""
        from dlrover_tpu.autopilot.apply import plan_applicable

        cur = _mk_plan(zero1())
        wide = _mk_plan(dp(), mesh_axes={"data": 8})
        assert plan_applicable(cur, wide, step_batch=8)
        assert not plan_applicable(cur, wide, step_batch=4)
        # -1 (fill) axes resolve against the stamped world too
        fill = _mk_plan(dp())  # mesh_axes={"data": -1}, n_devices=8
        assert not plan_applicable(cur, fill, step_batch=4)

    def test_unbuildable_mesh_rejected(self):
        from dlrover_tpu.autopilot.apply import plan_applicable

        cur = _mk_plan(zero1())
        bad = _mk_plan(dp(), mesh_axes={"data": 3})  # 3 ∤ 8 devices
        assert not plan_applicable(cur, bad, step_batch=8)


def test_swap_compiled_resets_step_window():
    """A retune's program swap re-bases the rolling step window: the
    post-swap median (what the autopilot history records, attributed
    to the NEW plan) must never span pre-retune steps."""
    import types

    from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer

    mesh = dp().build_mesh()
    fake = types.SimpleNamespace(mesh=mesh, strategy=None,
                                 flops_per_step=0.0)
    trainer = ElasticTrainer(fake, global_batch_size=TINY_BATCH,
                             micro_batch_size=1, model_name="tiny")
    trainer.efficiency.end_step(1, 0.04)
    trainer.efficiency.end_step(2, 0.04)
    assert trainer.efficiency.step_seconds() == pytest.approx(0.04)
    trainer.swap_compiled(fake)
    assert trainer.efficiency.step_seconds() is None
    trainer.efficiency.end_step(3, 0.01)
    assert trainer.efficiency.step_seconds() == pytest.approx(0.01)


# ---------------------------------------------------- master push wiring


def test_master_arms_and_pushes_retune(tmp_path, monkeypatch):
    """AutopilotPlanReport arms the servicer's controller; trainer
    snapshot pushes feed it; a sustained contradiction lands the target
    plan in ParalConfig (hot channel, no restart_required). The
    servicer's applicability predicate (plan_applicable over the
    reported step_batch) skips alternatives the trainer's apply path
    would veto — the pushed plan is always one that actually applies,
    so the budget/journal/baseline never charge a phantom retune."""
    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.master.job_master import JobMaster

    monkeypatch.setenv(EnvKey.JOURNAL_DIR, str(tmp_path))
    master = JobMaster(port=0, rdzv_timeout=2.0)
    master.prepare()
    try:
        c = MasterClient(master.addr, 0)
        plan = _mk_plan(zero1(), pred=0.01, source="history")
        # two faster-but-inapplicable alternatives ranked ahead of the
        # one the trainer can actually morph to
        mp = _mk_plan(mpmd(pipeline_size=2), schedule="mpmd",
                      pred=0.004, source="history")
        bad = _mk_plan(dp(grad_compression=True), pred=0.005,
                       source="history", mesh_axes={"data": 3})
        alt = _mk_plan(dp(), pred=0.012, source="history")
        c.report_autopilot_plan(
            plan.to_json(),
            [mp.to_json(), bad.to_json(), alt.to_json()],
            step_batch=TINY_BATCH,
        )
        total = 0.0
        count = 0
        for _ in range(8):
            total += 0.5   # 0.05s/step — 5x the plan's prediction
            count += 10
            c.report_metrics(
                [{"name": "dlrover_tpu_train_step_seconds",
                  "type": "histogram",
                  "samples": [{"sum": total, "count": count}]}],
                role="trainer",
            )
        cfg = c.get_paral_config()
        assert cfg.autopilot_plan, "retune never reached ParalConfig"
        pushed = Plan.from_json(cfg.autopilot_plan)
        assert pushed.fingerprint == alt.fingerprint
        assert not cfg.restart_required
        assert cfg.version >= 1
        c.close()
    finally:
        master.stop()


# -------------------------------------------- acceptance: the closed loop


def _batch_stream(n_steps, seed=1234):
    for i in range(n_steps):
        g = np.random.Generator(np.random.Philox(key=seed + i))
        yield {"tokens": g.integers(
            0, _tiny_cfg().vocab_size,
            (1, TINY_BATCH, TINY_SEQ + 1), dtype=np.int32,
        )}


def _launch(plan, kwargs):
    import jax

    from dlrover_tpu.trainer.train_step import compile_train

    strategy = plan.strategy()
    mesh = strategy.build_mesh()
    compiled = compile_train(
        strategy=strategy,
        mesh=mesh,
        loss_fn=kwargs["loss_fn_for"](strategy, mesh),
        init_params_fn=kwargs["init_params_fn"],
        logical_params=kwargs["logical_params"],
        optimizer=kwargs["optimizer"],
    )
    return compiled, compiled.init(jax.random.PRNGKey(0))


def _run(compiled, state, n_steps, trainer_hook=None):
    import jax

    from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer

    trainer = ElasticTrainer(
        compiled, global_batch_size=TINY_BATCH,
        micro_batch_size=TINY_BATCH // 8, model_name="tiny",
    )
    if trainer_hook is not None:
        trainer.retune_hook = trainer_hook
    losses = []
    state = trainer.run_batches(
        state, _batch_stream(n_steps), max_steps=n_steps,
        on_step=lambda s, m: losses.append(
            float(jax.device_get(m["loss"]))
        ),
    )
    return trainer, state, losses


@pytest.mark.timeout(300)
def test_closed_loop_acceptance(tmp_path, monkeypatch):
    """ISSUE-13 acceptance: `--strategy auto` semantics end to end —
    AOT enumeration picks a feasible ranked plan, the job trains, a
    seeded wrong estimate triggers exactly one journaled retune that
    applies in-process (no restart), and the run converges to the same
    loss as launching the retune target directly."""
    monkeypatch.setenv(EnvKey.JOURNAL_DIR, str(tmp_path / "journal"))
    from dlrover_tpu.autopilot import apply as autopilot_apply

    kwargs = _planner_kwargs()
    ranked = enumerate_plans(
        points=[(dp(), "spmd"), (zero1(), "spmd")], **kwargs
    )
    assert len(ranked.plans) == 2  # both feasible via AOT enumeration
    launch, alt = ranked.plans
    n_steps = 12

    # seeded contradiction: the launched plan carries a WRONG estimate
    # (10x optimistic, stamped as a measurement so no calibration
    # forgives it) — the ISSUE's "injected slow phase / wrong estimate"
    launch.pred_step_s = 1e-4
    launch.source = "history"

    decisions = []
    ctrl = AutopilotController(
        tolerance=1.5, clear_ratio=1.2, action_streak=3, min_points=3,
        max_retunes=1,
    )
    ctrl.arm(launch, [alt])
    compiled, state = _launch(launch, kwargs)
    last_t = [time.monotonic()]

    def hook(step, st):
        now = time.monotonic()
        measured = now - last_t[0]
        last_t[0] = now
        decision = ctrl.observe_step_time(measured)
        if decision is None:
            return None
        applied = autopilot_apply.apply_plan(
            decision.to_plan,
            state=st,
            loss_fn_for=kwargs["loss_fn_for"],
            init_params_fn=kwargs["init_params_fn"],
            logical_params=kwargs["logical_params"],
            optimizer=kwargs["optimizer"],
            path=decision.path,
        )
        decisions.append(decision)
        return applied.compiled, applied.state

    trainer, state, losses = _run(compiled, state, n_steps,
                                  trainer_hook=hook)
    assert len(losses) == n_steps          # trained through the retune
    assert len(decisions) == 1             # exactly one retune
    assert decisions[0].to_plan.fingerprint == alt.fingerprint
    assert trainer.compiled.strategy.name == alt.strategy().name

    # exactly one journaled autopilot_retune with the evidence trail
    retunes = []
    jdir = str(tmp_path / "journal")
    for root, _dirs, files in os.walk(jdir):
        for f in files:
            if f.endswith(".jsonl"):
                with open(os.path.join(root, f)) as fh:
                    retunes += [json.loads(ln) for ln in fh
                                if "autopilot_retune" in ln]
    assert len(retunes) == 1
    assert retunes[0]["to_fingerprint"] == alt.fingerprint
    assert retunes[0]["pred_step_s"] == pytest.approx(1e-4)

    # convergence: same final loss as launching the retune target
    # directly over the identical seeded batch stream (dp and zero1
    # are the same math in different layouts)
    compiled_b, state_b = _launch(alt, kwargs)
    _, _, losses_b = _run(compiled_b, state_b, n_steps)
    assert losses[-1] == pytest.approx(losses_b[-1], rel=2e-3)
