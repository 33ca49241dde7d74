"""Measured-feedback strategy search: successive halving over the knob
cross-product, seeded by the roofline.

Reference analog: atorch's acceleration engine does not stop at analytic
estimates — it tunes with Bayesian optimization and combination search
over optimization-method combinations
(atorch/atorch/auto/engine/sg_algo/bayes_opt_sg.py:1,
sg_algo/combination_sg.py, sg_algo/hebo/). TPU-native shape: the
roofline (parallel/dry_run.py AOT compile + parallel/cost_model.py) is
the cheap seeding pass — it filters OOM candidates and orders the field
without touching the chips — then *successive halving* spends real timed
steps only on survivors, doubling measurement depth per rung while
halving the field, so the total chip time is ~2x a single candidate's
budget regardless of how many combinations the cross-product opened.

The search runs on the TARGET mesh (measured time on a virtual CPU mesh
says nothing about TPU); the winner and its measured step time feed the
strategy-engine service's measured history
(parallel/engine_service.py), which is how the tuning is shared across
jobs.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable, Sequence

import jax
import numpy as np

from dlrover_tpu.common.log import get_logger
from dlrover_tpu.parallel.dry_run import dry_run
from dlrover_tpu.parallel.strategy import Strategy

logger = get_logger(__name__)


def expand_candidates(
    base: Sequence[Strategy],
    *,
    remat: Sequence[str] = ("none", "dots_no_batch"),
    int8: Sequence[bool] = (False, True),
    grad_accum: Sequence[int] = (1, 2),
    model_remat: Sequence[tuple] | None = None,
) -> list[Strategy]:
    """Cross the base presets with the tunable knobs.

    ``model_remat`` entries are ``(remat_scan, remat_policy,
    remat_interval)`` tuples carried in ``extra`` (consumed by
    models/transformer.py resolve_config); ``None`` leaves the model's
    own remat configuration untouched.
    """
    out: list[Strategy] = []
    for s in base:
        for r in remat:
            for q in int8:
                for a in grad_accum:
                    for mr in (model_remat or (None,)):
                        extra = dict(s.extra)
                        if q:
                            extra["int8_matmuls"] = True
                        tag = f"r={r},int8={int(q)},acc={a}"
                        if mr is not None:
                            scan, policy, interval = mr
                            extra.update(
                                remat_scan=bool(scan),
                                remat_policy=policy,
                                remat_interval=int(interval),
                            )
                            tag += f",mr={policy}/{interval}"
                        out.append(dataclasses.replace(
                            s, name=f"{s.name}[{tag}]", remat=r,
                            grad_accum=a, extra=extra,
                        ))
    return out


def _reshape_accum(batch: Any, accum: int) -> Any | None:
    """[A0, B, ...] example batch -> [accum, A0*B/accum, ...] or None
    when the global batch doesn't divide."""
    def one(a):
        a = np.asarray(a)
        total = a.shape[0] * a.shape[1]
        if total % accum:
            return None
        return a.reshape(accum, total // accum, *a.shape[2:])

    leaves = [one(a) for a in jax.tree_util.tree_leaves(batch)]
    if any(v is None for v in leaves):
        return None
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(batch), leaves
    )


def measured_search(
    *,
    loss_fn_for: Callable,     # (strategy, mesh) -> loss_fn
    init_params_fn,
    logical_params,
    optimizer,
    example_batch,             # pytree of np arrays [accum, batch, ...]
    devices: Sequence | None = None,
    candidates: Sequence[Strategy] | None = None,
    expand: bool = True,
    top_k: int = 6,
    rungs: Sequence[int] = (3, 8),
    keep: float = 0.5,
    hbm_capacity_bytes: int | None = None,
    hw=None,
    engine_client=None,
    engine_key: dict | None = None,
    surrogate_rounds: int = 1,
    surrogate_proposals: int = 2,
) -> tuple[Strategy, dict]:
    """Roofline-seeded successive halving + GP-surrogate acquisition;
    returns (winner, report).

    After the halving rungs, a Gaussian-process surrogate
    (parallel/surrogate.py — the bayes_opt_sg.py analog) is fitted on
    EVERY timed measurement and proposes up to ``surrogate_proposals``
    configs per round from the candidates the roofline seeding ranked
    OUTSIDE the measured top-k; each proposal is measured at the final
    rung depth and can take the win. ``surrogate_rounds=0`` disables.

    Report: ``{"roofline": [(name, est_s, fits)], "rungs":
    [{name: measured_s}], "roofline_pick": name, "surrogate":
    [{name: measured_s}], "winner": name, "winner_step_s": s}``. When
    ``engine_client`` is given, every measurement is reported to the
    engine service — the service's observation store is the persisted
    posterior a later search warm-starts from — and the winner feeds
    the measured history that serves ``propose(objective="fastest")``.
    """
    from dlrover_tpu.parallel.auto import (
        default_candidates,
        device_hbm_bytes,
    )
    from dlrover_tpu.trainer.train_step import compile_train

    devices = list(devices if devices is not None else jax.devices())
    if candidates is None:
        candidates = default_candidates(len(devices))
    if expand:
        candidates = expand_candidates(candidates)
    if hbm_capacity_bytes is None:
        hbm_capacity_bytes = device_hbm_bytes(devices[0])

    def build(strategy: Strategy):
        mesh = strategy.build_mesh(devices)
        compiled = compile_train(
            strategy=strategy,
            mesh=mesh,
            loss_fn=loss_fn_for(strategy, mesh),
            init_params_fn=init_params_fn,
            logical_params=logical_params,
            optimizer=optimizer,
        )
        return compiled

    def abstract_args(strategy: Strategy, compiled, batch):
        state = jax.eval_shape(compiled.init, jax.random.PRNGKey(0))
        state = jax.tree.map(
            lambda leaf, s: jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=s
            ),
            state, compiled.state_shardings,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
        )
        b = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                np.shape(a), np.asarray(a).dtype,
                sharding=compiled.batch_sharding,
            ),
            batch,
        )
        return state, b

    # ---- seeding pass: AOT roofline, filters OOM / non-dividing accum
    seeded: list[tuple[Strategy, Any, Any, float]] = []
    roofline_rows = []
    for s in candidates:
        batch = _reshape_accum(example_batch, max(1, s.grad_accum))
        if batch is None:
            roofline_rows.append((s.name, math.inf, False))
            continue
        try:
            compiled = build(s)
        except Exception as e:  # noqa: BLE001 - candidate, not crash
            logger.info("candidate %s failed to build: %s", s.name, e)
            roofline_rows.append((s.name, math.inf, False))
            continue
        r = dry_run(
            lambda _s, c=compiled, b=batch: (
                c.step, abstract_args(_s, c, b)
            ),
            s, hw=hw,
        )
        fits = r.fits(hbm_capacity_bytes) if hbm_capacity_bytes else r.ok
        roofline_rows.append((s.name, r.est_step_s or math.inf, fits))
        if fits:
            seeded.append((s, compiled, batch, r.est_step_s or math.inf))
    if not seeded:
        raise RuntimeError(
            "measured_search: no candidate compiled and fit memory"
        )
    seeded.sort(key=lambda t: t[3])
    roofline_pick = seeded[0][0].name
    field = seeded[:top_k]

    # ---- successive halving with real timed steps
    rung_rows: list[dict] = []
    measured: dict[str, float] = {}
    for depth in rungs:
        row: dict[str, float] = {}
        for s, compiled, batch, _ in field:
            try:
                t = _time_steps(compiled, batch, depth)
            except Exception as e:  # noqa: BLE001 - drop the candidate
                logger.info("candidate %s failed measuring: %s",
                            s.name, e)
                t = math.inf
            row[s.name] = t
            measured[s.name] = t
        rung_rows.append(row)
        field.sort(key=lambda item: row[item[0].name])
        field = [f for f in field
                 if math.isfinite(row[f[0].name])] or field[:1]
        survivors = max(1, int(math.ceil(len(field) * keep)))
        field = field[:survivors]
        if len(field) == 1:
            break
    winner = field[0][0]
    winner_s = measured[winner.name]

    # ---- surrogate acquisition: fit a GP on every timed result and
    # measure the configs it says are promising among the seeded
    # candidates halving never touched (top_k cut them before any
    # measurement). by_name carries their already-compiled programs.
    surrogate_rows: list[dict] = []
    if surrogate_rounds > 0:
        from dlrover_tpu.parallel.surrogate import surrogate_propose

        by_name = {s.name: (s, compiled, batch)
                   for s, compiled, batch, _ in seeded}
        pool = [s for s, _, _, _ in seeded]
        for _ in range(surrogate_rounds):
            observations = [
                (by_name[n][0], t) for n, t in measured.items()
                if n in by_name
            ]
            try:
                proposals = surrogate_propose(
                    observations, pool, n=surrogate_proposals
                )
            except Exception as e:  # noqa: BLE001 - optional layer
                logger.warning("surrogate propose failed: %s", e)
                break
            if not proposals:
                break
            row: dict[str, float] = {}
            for s, ei in proposals:
                _, compiled, batch = by_name[s.name]
                try:
                    t = _time_steps(compiled, batch, rungs[-1])
                except Exception as e:  # noqa: BLE001 - drop it
                    logger.info("surrogate pick %s failed: %s",
                                s.name, e)
                    t = math.inf
                row[s.name] = t
                measured[s.name] = t
                logger.info("surrogate pick %s (EI %.3g): %.4fs",
                            s.name, ei, t)
                if t < winner_s:
                    winner, winner_s = s, t
            surrogate_rows.append(row)

    report = {
        "roofline": roofline_rows,
        "roofline_pick": roofline_pick,
        "rungs": rung_rows,
        "surrogate": surrogate_rows,
        "winner": winner.name,
        "winner_step_s": winner_s,
    }
    logger.info(
        "measured search: winner %s at %.4fs/step (roofline pick was "
        "%s)", winner.name, winner_s, roofline_pick,
    )
    if engine_client is not None:
        # every finite measurement feeds the service's observation
        # store (the persisted surrogate posterior); the service keeps
        # the fastest as the measured-history winner. Client + service
        # normalize through autopilot/history.py's ONE fingerprint
        # vocabulary (shape_key + canonical strategy JSON), so the
        # winner written here is exactly what a later autopilot
        # planner's history lookup reads back (pinned by
        # tests/test_autopilot.py).
        name_to_strategy = {s.name: s for s, _, _, _ in seeded}
        try:
            for cand_name, t in measured.items():
                if not math.isfinite(t):
                    continue
                cand = name_to_strategy.get(cand_name)
                if cand is None:
                    continue
                engine_client.report_measurement(
                    strategy=cand, step_time_s=t, **(engine_key or {}),
                )
        except Exception as e:  # noqa: BLE001 - telemetry, not critical
            logger.warning("engine measurement report failed: %s", e)
    return winner, report


def _time_steps(compiled, batch, steps: int) -> float:
    """Mean wall time per global step over one run of ``steps``."""
    state = compiled.init(jax.random.PRNGKey(0))
    step_batch = jax.device_put(batch, compiled.batch_sharding)
    state, m = compiled.step(state, step_batch)  # compile + warmup
    jax.block_until_ready(m["loss"])
    t0 = time.monotonic()
    for _ in range(steps):
        state, m = compiled.step(state, step_batch)
    jax.block_until_ready(m["loss"])
    return (time.monotonic() - t0) / steps
