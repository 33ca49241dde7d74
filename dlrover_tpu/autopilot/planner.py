"""Autopilot planner: feasible (strategy × mesh × schedule) points,
ranked, as typed plans.

The ``auto_accelerate`` front half (PAPER.md §1), built from parts the
repo already owns: every candidate point is AOT-lowered on the host
(``parallel/dry_run.py`` — per-device peak memory and FLOPs without
touching a chip), filtered by the device-memory envelope
(``parallel/auto.py device_hbm_bytes``, overridable via
``DLROVER_TPU_DEVICE_HBM_BYTES`` for CPU backends), and ranked
by the schedule-aware roofline (``parallel/cost_model.py``). The MPMD
schedule axis (2412.14374) enters as an extra point per eligible stage
count, costed with the per-stage heterogeneous estimates behind
``--schedule auto``.

Measured history outranks the model: when
:class:`~dlrover_tpu.autopilot.history.PlanHistory` holds a measurement
for a point at this exact workload shape, that point is re-scored from
the measurement (``source="history"`` — the Brain-style cross-job
learning), so a fleet's second job with the same model/mesh fingerprint
starts from evidence, not estimates.

The winner (and the full ranked list — the controller's retune menu)
is journaled as ``autopilot_plan`` and returned as typed
:class:`Plan` records the trainer launches directly through the
existing ``load_or_compile`` path.
"""

from __future__ import annotations

import dataclasses
import json
import math
import statistics
from typing import Any, Optional, Sequence

from dlrover_tpu.autopilot.history import (
    PlanHistory,
    canonical_strategy_json,
    plan_fingerprint,
)
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.telemetry.journal import get_journal
from dlrover_tpu.telemetry.metrics import registry

logger = get_logger(__name__)

_plans_total = registry().counter(
    "dlrover_tpu_autopilot_plans_total",
    "autopilot plans emitted, by ranking evidence of the winner "
    "(model = analytic cost model, history = measured history)",
    label_names=("source",),
)
_feasible_points = registry().gauge(
    "dlrover_tpu_autopilot_feasible_points",
    "candidate (strategy x mesh x schedule) points that AOT-compiled "
    "and fit the device-memory envelope in the latest planner run",
)
_pred_step_gauge = registry().gauge(
    "dlrover_tpu_autopilot_pred_step_seconds",
    "the launched plan's predicted step time (cost model or measured "
    "history) — the controller's contradiction baseline",
)

# bump when the enumeration or ranking changes in a way that must
# invalidate persisted plan caches
_PLANNER_VERSION = 1


@dataclasses.dataclass
class Plan:
    """One launchable point: strategy + mesh + schedule with its
    prediction — everything the trainer needs to launch through
    ``load_or_compile`` and the controller needs to judge the launch."""

    name: str = "dp"
    strategy_json: str = ""
    schedule: str = "spmd"            # "spmd" | "mpmd"
    mesh_axes: dict = dataclasses.field(default_factory=dict)
    pred_step_s: float = 0.0
    # the raw cost-model estimate, kept beside pred_step_s (which may
    # be a measurement or a calibrated estimate) so a cache reload can
    # re-run the history calibration from scratch
    analytic_step_s: float = 0.0
    pred_peak_bytes: int = 0
    pred_flops: float = 0.0
    source: str = "model"             # "model" | "history"
    fingerprint: str = ""
    # workload identity (history.shape_key fields)
    model: str = ""
    n_devices: int = 0
    batch: int = 0
    seq: int = 0
    hbm_gb: float = 0.0
    rank: int = 0

    def strategy(self):
        from dlrover_tpu.parallel.strategy import Strategy

        return Strategy.from_json(self.strategy_json)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), sort_keys=True)

    @classmethod
    def from_json(cls, text: str) -> "Plan":
        return cls(**json.loads(text))


@dataclasses.dataclass
class RankedPlans:
    """Planner output: ``plans[0]`` is the launch, the tail is the
    controller's retune menu; ``reports`` keeps every dry-run (also the
    infeasible ones — the journal's evidence that OOM points were seen
    and rejected, never launched)."""

    plans: list = dataclasses.field(default_factory=list)
    reports: list = dataclasses.field(default_factory=list)
    from_cache: bool = False

    @property
    def winner(self) -> Plan:
        return self.plans[0]

    def alternatives(self) -> list:
        return self.plans[1:]

    def to_json(self) -> str:
        return json.dumps({
            "version": _PLANNER_VERSION,
            "plans": [dataclasses.asdict(p) for p in self.plans],
        }, indent=2)

    @classmethod
    def from_json(cls, text: str) -> "RankedPlans":
        data = json.loads(text)
        if data.get("version") != _PLANNER_VERSION:
            raise ValueError("planner version mismatch")
        return cls(plans=[Plan(**p) for p in data["plans"]],
                   from_cache=True)


def default_points(num_devices: int, *, mpmd_stages: int = 0
                   ) -> list[tuple[Any, str]]:
    """The enumeration: strategy presets in preference order (cheapest
    collectives first, ``parallel/auto.py``) each as an SPMD point,
    plus an MPMD pipeline point per eligible stage count — the
    schedule axis the MPMD scheduling work (2412.14374) argues for."""
    from dlrover_tpu.parallel import strategy as st
    from dlrover_tpu.parallel.auto import default_candidates

    points: list[tuple[Any, str]] = [
        (s, "spmd") for s in default_candidates(num_devices)
    ]
    if mpmd_stages > 1 and num_devices % mpmd_stages == 0 \
            and num_devices // mpmd_stages >= 1:
        points.append((st.mpmd(pipeline_size=mpmd_stages), "mpmd"))
    return points


def _mpmd_estimate(strategy, base_report, *, model_cfg, batch: int,
                   seq: int, num_devices: int, hw=None):
    """(est_step_s, peak_bytes) for an MPMD point, derived from the
    base SPMD dry-run: the per-stage programs run the SAME math, so the
    roofline work/traffic terms carry over and only the schedule terms
    (per-stage heterogeneous 1F1B fill/drain + boundary p2p) are new.
    Peak memory divides by the stage count — each stage's devices hold
    only that stage's params/optimizer state (the §21 ZeRO split) plus
    in-flight microbatch activations (bounded by the 1F1B window, ≤ the
    monolith's activation set)."""
    from dlrover_tpu.parallel.cost_model import (
        PipelineSchedule,
        estimate_step_time,
    )

    extra = strategy.extra or {}
    stages = int(extra.get("pipeline_stages", 2) or 2)
    micro = int(extra.get("pipeline_microbatches", 0) or 0) or stages
    stage_times: tuple = ()
    if model_cfg is not None:
        try:
            from dlrover_tpu.parallel.mpmd import estimate_stage_times

            stage_times = tuple(estimate_stage_times(
                model_cfg, num_stages=stages, step_batch=batch,
                seq=seq, microbatches=micro, hw=hw,
            ))
        except Exception:  # noqa: BLE001 - fall back to uniform stages
            stage_times = ()
    act_bytes = 0.0
    if model_cfg is not None:
        try:
            import numpy as np

            dt = np.dtype(getattr(model_cfg, "dtype", "float32")).itemsize
            act_bytes = (batch / micro) * seq * model_cfg.d_model * dt
        except Exception:  # noqa: BLE001
            act_bytes = 0.0
    est = estimate_step_time(
        flops=base_report.flops,
        bytes_accessed=base_report.bytes_accessed,
        hw=hw,
        schedule=PipelineSchedule(
            kind="mpmd_1f1b", num_stages=stages, num_microbatches=micro,
            activation_bytes=act_bytes, stage_time_s=stage_times,
        ),
    )
    peak = int(math.ceil(base_report.hbm_bytes / stages)) \
        if base_report.hbm_bytes else 0
    return est.est_step_s, peak


def enumerate_plans(
    *,
    model: str,
    loss_fn_for,
    init_params_fn,
    logical_params,
    optimizer,
    example_batch,
    batch: int,
    seq: int,
    devices: Sequence | None = None,
    points: Sequence[tuple[Any, str]] | None = None,
    hbm_capacity_bytes: Optional[int] = None,
    history: PlanHistory | None = None,
    model_cfg=None,
    mpmd_stages: int = 0,
    hw=None,
) -> RankedPlans:
    """Enumerate, AOT-filter, rank; emit the typed plan list.

    Deterministic by construction: the point list is a fixed preference
    order, scores come from the (deterministic) AOT analyses and cost
    model or from history, and ties break on preference index — two
    runs over the same inputs produce the identical ranked list.
    """
    import jax
    import numpy as np

    from dlrover_tpu.parallel.auto import device_hbm_bytes
    from dlrover_tpu.parallel.dry_run import DryRunReport, dry_run
    from dlrover_tpu.trainer.train_step import compile_train

    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if points is None:
        points = default_points(n, mpmd_stages=mpmd_stages)
    if hbm_capacity_bytes is None:
        hbm_capacity_bytes = device_hbm_bytes(devices[0])
    hbm_gb = round(hbm_capacity_bytes / 2**30, 3) \
        if hbm_capacity_bytes else 0.0

    def build_step(strategy):
        mesh = strategy.build_mesh(devices)
        compiled = compile_train(
            strategy=strategy,
            mesh=mesh,
            loss_fn=loss_fn_for(strategy, mesh),
            init_params_fn=init_params_fn,
            logical_params=logical_params,
            optimizer=optimizer,
        )
        state_abstract = jax.eval_shape(
            compiled.init, jax.random.PRNGKey(0)
        )
        state_abstract = jax.tree.map(
            lambda leaf, s: jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=s
            ),
            state_abstract, compiled.state_shardings,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
        )
        batch_abstract = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                np.shape(a), np.asarray(a).dtype,
                sharding=compiled.batch_sharding,
            ),
            example_batch,
        )
        return compiled.step, (state_abstract, batch_abstract)

    measured = history.lookup(model, n, batch, seq, hbm_gb) \
        if history is not None else {}

    reports: list[DryRunReport] = []
    scored: list[tuple[float, int, Plan]] = []
    base_spmd_report: DryRunReport | None = None
    for idx, (strategy, schedule) in enumerate(points):
        if schedule == "mpmd":
            # per-stage programs are never one jit program: cost the
            # point off the base SPMD dry-run instead of compiling P×3
            # stage programs here (the launch path compiles them once,
            # through the per-stage compile cache)
            if base_spmd_report is None:
                logger.info("autopilot: no feasible SPMD base for the "
                            "mpmd point; skipping")
                continue
            est_s, peak = _mpmd_estimate(
                strategy, base_spmd_report, model_cfg=model_cfg,
                batch=batch, seq=seq, num_devices=n, hw=hw,
            )
            r = DryRunReport(
                strategy_name=strategy.name, ok=True,
                flops=base_spmd_report.flops, hbm_bytes=peak,
                bytes_accessed=base_spmd_report.bytes_accessed,
                est_step_s=est_s,
            )
        else:
            r = dry_run(build_step, strategy, hw=hw)
        reports.append(r)
        fits = r.fits(hbm_capacity_bytes) if hbm_capacity_bytes else r.ok
        if not fits:
            logger.info(
                "autopilot: %s/%s infeasible (%s, peak %.2f GB > "
                "envelope %.2f GB)", r.strategy_name, schedule,
                r.error or "OOM", r.hbm_bytes / 2**30,
                hbm_capacity_bytes / 2**30 if hbm_capacity_bytes else 0,
            )
            continue
        if schedule == "spmd" and base_spmd_report is None:
            base_spmd_report = r
        sj = canonical_strategy_json(strategy)
        plan = Plan(
            name=f"{strategy.name}/{schedule}",
            strategy_json=sj,
            schedule=schedule,
            mesh_axes=dict(strategy.mesh_axes),
            pred_step_s=r.est_step_s,
            analytic_step_s=r.est_step_s,
            pred_peak_bytes=int(r.hbm_bytes),
            pred_flops=r.flops,
            source="model",
            fingerprint=plan_fingerprint(sj, schedule),
            model=model, n_devices=n, batch=batch, seq=seq,
            hbm_gb=hbm_gb,
        )
        seen = measured.get(sj)
        if seen and seen.get("step_time_s", 0) > 0:
            plan.pred_step_s = seen["step_time_s"]
            plan.source = "history"
        scored.append((r.est_step_s, idx, plan))
    _calibrate_model_preds(scored)
    if not scored:
        raise RuntimeError(
            "autopilot: no candidate point compiled and fit the "
            "device-memory envelope: "
            + "; ".join(f"{r.strategy_name}: {r.error or 'OOM'}"
                        for r in reports)
        )
    scored.sort(key=lambda t: (
        t[2].pred_step_s if t[2].pred_step_s > 0 else math.inf, t[1],
    ))
    plans = []
    for rank, (_, _, plan) in enumerate(scored):
        plan.rank = rank
        plans.append(plan)
    ranked = RankedPlans(plans=plans, reports=reports)
    _journal_plan(ranked)
    return ranked


def _calibrate_model_preds(scored: list) -> None:
    """Put model- and history-sourced predictions on ONE scale.

    The roofline's constants rank candidates against each other but
    its absolute scale is backend-dependent (parallel/cost_model.py
    says so outright) — mixing raw analytic estimates with real
    measurements would let an optimistic estimate outrank a measured
    winner forever. Every plan that has BOTH (analytic est, measured
    step) yields a scale factor; the median factor rescales the plans
    history never saw, so the ranking compares measured-vs-calibrated
    instead of measured-vs-wishful. ``scored`` rows are
    ``(analytic_est_s, preference_idx, plan)`` mutated in place."""
    factors = [
        plan.pred_step_s / est
        for est, _, plan in scored
        if plan.source == "history" and est > 0 and plan.pred_step_s > 0
    ]
    if not factors:
        return
    factor = statistics.median(factors)
    for est, _, plan in scored:
        if plan.source == "model" and est > 0:
            plan.pred_step_s = est * factor


def _journal_plan(ranked: RankedPlans) -> None:
    win = ranked.winner
    _plans_total.labels(win.source).inc()
    _feasible_points.set(len(ranked.plans))
    _pred_step_gauge.set(round(win.pred_step_s, 6))
    get_journal().emit(
        "autopilot_plan",
        plan=win.name, fingerprint=win.fingerprint,
        schedule=win.schedule, source=win.source,
        pred_step_s=round(win.pred_step_s, 6),
        pred_peak_gb=round(win.pred_peak_bytes / 2**30, 3),
        model=win.model, n_devices=win.n_devices, batch=win.batch,
        seq=win.seq, feasible=len(ranked.plans),
        ranked=[p.name for p in ranked.plans],
        cached=ranked.from_cache,
    )
    logger.info(
        "autopilot plan: %s (source=%s, pred %.4fs/step, %d feasible "
        "points)", win.name, win.source, win.pred_step_s,
        len(ranked.plans),
    )


def _workload_fingerprint(init_params_fn, example_batch, n_devices: int,
                          batch: int, seq: int, model: str,
                          mpmd_stages: int) -> str:
    """Cache key for a persisted plan list: everything that determines
    the planner's answer (mirrors ``parallel/auto.py``'s strategy-cache
    fingerprint — a hit for a DIFFERENT workload would launch a plan
    that never passed this workload's fit check)."""
    import hashlib

    import jax
    import numpy as np

    shapes = jax.tree_util.tree_map(
        lambda l: (tuple(l.shape), str(l.dtype)),
        jax.eval_shape(init_params_fn, jax.random.PRNGKey(0)),
    )
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    param_sig = sorted((jax.tree_util.keystr(p), v) for p, v in flat)
    batch_sig = sorted(
        (k, tuple(np.shape(v)), str(np.asarray(v).dtype))
        for k, v in example_batch.items()
    )
    blob = repr((param_sig, batch_sig, n_devices, batch, seq, model,
                 mpmd_stages, _PLANNER_VERSION))
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def load_or_plan(cache_path: str, **kwargs) -> RankedPlans:
    """``enumerate_plans`` with a persisted result, the
    ``load_strategy`` analog: an elastic restart reuses the ranked list
    instead of burning the recovery window on N candidate AOT compiles.
    Keyed by the workload fingerprint; any change (shapes, world size,
    planner version) re-runs the search. History still wins: a cached
    list whose winner came from the analytic model is re-ranked against
    the (cheap) history lookup so fresh measurements are never shadowed
    by a stale cache."""
    import os

    import jax

    devices = kwargs.get("devices")
    n = len(devices) if devices is not None else len(jax.devices())
    fp = _workload_fingerprint(
        kwargs["init_params_fn"], kwargs["example_batch"], n,
        kwargs["batch"], kwargs["seq"], kwargs["model"],
        kwargs.get("mpmd_stages", 0),
    )
    history: PlanHistory | None = kwargs.get("history")
    try:
        with open(cache_path) as f:
            data = json.load(f)
        if data.get("fingerprint") == fp:
            ranked = RankedPlans.from_json(json.dumps(data["ranked"]))
            if history is not None:
                _rescore_from_history(ranked, history)
            _journal_plan(ranked)
            logger.info("autopilot: reusing cached plan list from %s",
                        cache_path)
            return ranked
    except (OSError, ValueError, KeyError, TypeError):
        pass
    ranked = enumerate_plans(**kwargs)
    try:
        os.makedirs(os.path.dirname(cache_path) or ".", exist_ok=True)
        tmp = f"{cache_path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump({
                "fingerprint": fp,
                "ranked": json.loads(ranked.to_json()),
            }, f, indent=2)
        os.replace(tmp, cache_path)
    except OSError as e:  # cache is best-effort
        logger.warning("could not persist plan cache: %s", e)
    return ranked


def _rescore_from_history(ranked: RankedPlans,
                          history: PlanHistory) -> None:
    """Re-run the history substitution + calibration over a cached plan
    list, from the stored analytic estimates — measurements recorded
    since the cache was written must never be shadowed by it."""
    win = ranked.winner
    measured = history.lookup(win.model, win.n_devices, win.batch,
                              win.seq, win.hbm_gb)
    rows = []
    for plan in ranked.plans:
        seen = measured.get(canonical_strategy_json(plan.strategy_json))
        if seen and seen.get("step_time_s", 0) > 0:
            plan.pred_step_s = seen["step_time_s"]
            plan.source = "history"
        elif plan.analytic_step_s > 0:
            plan.pred_step_s = plan.analytic_step_s
            plan.source = "model"
        rows.append((plan.analytic_step_s, plan.rank, plan))
    _calibrate_model_preds(rows)
    ranked.plans.sort(
        key=lambda p: (p.pred_step_s if p.pred_step_s > 0 else math.inf,
                       p.rank)
    )
    for rank, plan in enumerate(ranked.plans):
        plan.rank = rank
