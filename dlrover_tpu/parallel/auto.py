"""Automatic strategy selection: the ``auto_accelerate`` front door.

Reference analog: atorch's strategy search (auto/accelerate.py:406 with
the engine/planner loop generating candidates and the dry-runner scoring
them). TPU-native: candidates are Strategy presets in preference order
(cheapest collectives first); each is AOT-compiled (parallel/dry_run.py)
and the first one whose peak per-device memory fits HBM wins — seconds of
compile time instead of minutes of trial training.
"""

from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import numpy as np

from dlrover_tpu.common.log import get_logger
from dlrover_tpu.parallel.dry_run import pick_strategy
from dlrover_tpu.parallel.mesh import data_parallel_size
from dlrover_tpu.parallel.strategy import (
    Strategy,
    dp,
    fsdp,
    fsdp_tp,
    zero1,
    zero2,
)

logger = get_logger(__name__)


def device_hbm_bytes(device=None) -> int:
    """Per-device memory budget: what the runtime reports, else the
    peaks table's HBM size for a TPU (an unknown ``device_kind``
    raises), else 0 = no check (CPU).

    ``DLROVER_TPU_DEVICE_HBM_BYTES`` (DESIGN.md §24) wins outright: a
    CPU backend whose runtime reports nothing can state the REAL target
    envelope, so the autopilot planner's feasibility filter rejects OOM
    plans instead of silently skipping the check."""
    import jax as _jax

    from dlrover_tpu.common import envspec
    from dlrover_tpu.common.constants import EnvKey

    stated = envspec.get_int(EnvKey.DEVICE_HBM_BYTES)
    if stated is not None and stated > 0:
        return stated
    device = device or _jax.devices()[0]
    try:
        stats = device.memory_stats()
        if stats and stats.get("bytes_limit"):
            return int(stats["bytes_limit"])
    except Exception:  # noqa: BLE001
        pass
    if device.platform != "tpu":
        return 0
    from dlrover_tpu.utils.profiler import device_peaks

    return device_peaks(device).hbm_bytes


def default_candidates(num_devices: int) -> list[Strategy]:
    """Preference order: replicated DP (no param collectives), ZeRO-1
    (dp + sharded optimizer state — fits when params do but params+Adam
    don't), then FSDP (param gathers), then FSDP x TP (per-layer
    collectives)."""
    candidates = [dp()]
    if num_devices > 1:
        candidates.append(zero1())
        candidates.append(zero2())
        candidates.append(fsdp())
    if num_devices >= 4:
        candidates.append(fsdp_tp(tensor_size=2))
    return candidates


def auto_strategy(
    *,
    loss_fn_for,           # (strategy, mesh) -> loss_fn(params, batch)
    init_params_fn,
    logical_params,
    optimizer,
    example_batch,          # pytree of np arrays [accum, batch, ...]
    devices: Sequence | None = None,
    candidates: Sequence[Strategy] | None = None,
    hbm_capacity_bytes: int | None = None,
    objective: str = "fastest",
    hw=None,
) -> tuple[Strategy, list]:
    """Pick the best candidate that compiles and fits memory.

    ``objective="fastest"`` (default) ranks fitting candidates by the
    roofline step-time estimate (parallel/cost_model.py); "first_fit"
    keeps the preference-order behavior. Returns (strategy, dry-run
    reports). ``loss_fn_for`` lets the caller bind attention/constraint
    choices per strategy (make_loss_fn).
    """
    from dlrover_tpu.trainer.train_step import compile_train

    devices = list(devices if devices is not None else jax.devices())
    if candidates is None:
        candidates = default_candidates(len(devices))
    if hbm_capacity_bytes is None:
        hbm_capacity_bytes = device_hbm_bytes(devices[0])

    def build_step(strategy: Strategy):
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

    best, reports = pick_strategy(
        build_step, list(candidates),
        hbm_capacity_bytes=hbm_capacity_bytes,
        objective=objective, hw=hw,
    )
    logger.info("auto strategy selected: %s", best.name)
    return best, reports


# bump when the search algorithm or the preset definitions change in a
# way that should invalidate persisted strategy caches (it is folded
# into the workload fingerprint alongside the candidate names)
_SEARCH_VERSION = 2


def _workload_fingerprint(kwargs: dict, n_devices: int) -> str:
    """Hash of everything that determines auto_strategy's answer: the
    abstract parameter tree, batch shapes, objective, HBM budget,
    device count, AND the candidate set + search version — a cache hit
    for a DIFFERENT model/batch would hand back a strategy that never
    passed this workload's fit check, and a cache written before a
    preset was added (e.g. the round-3 zero1/zero2 candidates) must not
    pin the old pick across upgrades."""
    import hashlib

    def sig(tree):
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        return sorted(
            (jax.tree_util.keystr(p), v) for p, v in flat
        )

    shapes = jax.tree_util.tree_map(
        lambda l: (tuple(l.shape), str(l.dtype)),
        jax.eval_shape(kwargs["init_params_fn"], jax.random.PRNGKey(0)),
    )
    batch_shapes = jax.tree_util.tree_map(
        lambda a: (tuple(np.shape(a)), str(np.asarray(a).dtype)),
        kwargs["example_batch"],
    )
    cands = kwargs.get("candidates")
    cand_names = [
        c.name for c in (cands if cands is not None
                         else default_candidates(n_devices))
    ]
    blob = repr((
        sig(shapes),
        sig(batch_shapes),
        kwargs.get("objective", "fastest"),
        kwargs.get("hbm_capacity_bytes"),
        n_devices,
        cand_names,
        _SEARCH_VERSION,
    ))
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def cached_auto_strategy(cache_path: str, **kwargs) -> tuple[Strategy, list]:
    """auto_strategy with a persisted result: the load_strategy analog.

    Reference: auto_accelerate's ``load_strategy`` (accelerate.py:467)
    — tune once, then every later run (and every elastic RESTART, where
    re-searching would burn the recovery window with N candidate
    compiles) reloads the picked strategy. The cache is keyed by a
    workload fingerprint (param/batch shapes, objective, HBM budget,
    device count): any change re-runs the search.
    """
    import dataclasses as _dc
    import json as _json
    import os as _os

    devices = kwargs.get("devices")
    n = len(devices) if devices is not None else len(jax.devices())
    fp = _workload_fingerprint(kwargs, n)
    try:
        with open(cache_path) as f:
            data = _json.load(f)
        if data.get("fingerprint") == fp:
            strategy = Strategy(**data["strategy"])
            logger.info(
                "reusing tuned strategy %r from %s (%d devices)",
                strategy.name, cache_path, n,
            )
            return strategy, []
    except (OSError, ValueError, KeyError, TypeError):
        pass
    strategy, reports = auto_strategy(**kwargs)
    try:
        _os.makedirs(_os.path.dirname(cache_path) or ".", exist_ok=True)
        # pid-suffixed temp + atomic replace: concurrent cold-starting
        # processes on a shared output_dir each write their own file
        # (identical content) — last writer wins, never interleaved
        tmp = f"{cache_path}.{_os.getpid()}.tmp"
        with open(tmp, "w") as f:
            _json.dump({
                "fingerprint": fp,
                "devices": n,
                "strategy": _dc.asdict(strategy),
            }, f, indent=2)
        _os.replace(tmp, cache_path)
    except OSError as e:  # cache is best-effort
        logger.warning("could not persist strategy cache: %s", e)
    return strategy, reports
