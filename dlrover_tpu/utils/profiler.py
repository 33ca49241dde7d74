"""Profiling: compiled FLOPs, MFU accounting, step timing, trace export.

Reference analog: ATorch's AProfiler (atorch/atorch/utils/prof.py:38 —
monkey-patches torch functionals to count FLOPs/MACs per module) and the
GPU timeline tracer (utils/tracer.py). XLA makes the counting half free:
``jit(f).lower(...).compile().cost_analysis()`` reports the compiled
program's exact FLOPs, so MFU comes from arithmetic instead of per-op
formula tables; the timeline half is ``jax.profiler`` (xplane traces for
Perfetto/TensorBoard).
"""

from __future__ import annotations

import contextlib
import dataclasses
import statistics
import time
from typing import Any, Callable

import numpy as np

from dlrover_tpu.common.log import get_logger

logger = get_logger(__name__)

@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float   # FLOP/s
    int8_ops: float     # OP/s
    hbm_bytes: int
    hbm_bps: float      # bytes/s


# The one peaks table, keyed by ``device_kind`` as JAX reports it.
# Source: Google Cloud documentation, "TPU v5e" system architecture —
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s. A device
# that is not here is an error (``device_peaks``), never a default: add
# its published row.
_V5E_PEAKS = ChipPeaks(bf16_flops=197e12, int8_ops=393e12,
                       hbm_bytes=16 * 10**9, hbm_bps=819e9)
PEAKS = {
    "TPU v5 lite": _V5E_PEAKS,
    "TPU v5e": _V5E_PEAKS,
}


def device_peaks(device=None) -> ChipPeaks:
    """Published peaks of ``device`` (default: device 0); raises for a
    ``device_kind`` the table does not hold."""
    import jax

    device = device or jax.devices()[0]
    kind = getattr(device, "device_kind", "")
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(
            f"device kind {kind!r} (platform {device.platform}) is not "
            f"in the peaks table (utils/profiler.py PEAKS: "
            f"{sorted(PEAKS)}); add its published peaks"
        ) from None


def device_peak_flops(device=None) -> float | None:
    """bf16 peak FLOP/s of one chip. None on the CPU (the test
    substrate has no peak, so MFU gauges stay off there); any other
    device must be in the table."""
    import jax

    device = device or jax.devices()[0]
    if device.platform == "cpu":
        return None
    return device_peaks(device).bf16_flops


def executable_flops(compiled) -> float:
    """FLOPs of an ALREADY-compiled executable (no lower/compile).

    Works for both fresh ``jit(f).lower(...).compile()`` results and
    deserialized AOT executables; this backend's ``cost_analysis``
    returns a list of dicts, which is unwrapped. Returns 0.0 when the
    backend doesn't report a cost analysis.
    """
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        return float((cost or {}).get("flops", 0.0))
    except Exception:  # noqa: BLE001 - profiling must never break training
        logger.exception("cost analysis failed")
        return 0.0


def compiled_flops(fn: Callable, *args, **kwargs) -> float:
    """Exact FLOPs of the compiled program for these (abstract) args.

    ``fn`` must be a ``jax.jit``-wrapped callable; compilation hits the
    same cache as execution, so calling this after a warmup step is cheap.
    Returns 0.0 when the backend doesn't report a cost analysis.
    """
    try:
        return executable_flops(fn.lower(*args, **kwargs).compile())
    except Exception:  # noqa: BLE001 - profiling must never break training
        logger.exception("cost analysis failed")
        return 0.0


@dataclasses.dataclass
class StepStats:
    steps: int = 0
    mean_s: float = 0.0
    p50_s: float = 0.0
    p90_s: float = 0.0
    min_s: float = 0.0
    flops_per_step: float = 0.0
    tflops_per_s: float = 0.0
    mfu: float | None = None

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


class StepProfiler:
    """Accumulates per-step wall times; computes throughput + MFU.

    The caller is responsible for synchronizing before ``stop`` marks
    (device_get of a step output); dispatch-only timing would lie.
    """

    def __init__(self, flops_per_step: float = 0.0,
                 peak_flops: float | None = None,
                 num_devices: int = 1):
        self._flops = flops_per_step
        self._peak = peak_flops
        self._num_devices = max(1, num_devices)
        self._times: list[float] = []
        self._t0: float | None = None

    def start(self) -> None:
        self._t0 = time.monotonic()

    def stop(self) -> None:
        if self._t0 is not None:
            self._times.append(time.monotonic() - self._t0)
            self._t0 = None

    @contextlib.contextmanager
    def step(self):
        self.start()
        try:
            yield
        finally:
            self.stop()

    def stats(self) -> StepStats:
        if not self._times:
            return StepStats()
        ts = sorted(self._times)
        mean = statistics.fmean(ts)
        flops_per_s = self._flops / mean if mean > 0 else 0.0
        mfu = None
        if self._peak:
            mfu = flops_per_s / (self._peak * self._num_devices)
        return StepStats(
            steps=len(ts),
            mean_s=round(mean, 5),
            p50_s=round(ts[len(ts) // 2], 5),
            p90_s=round(ts[int(len(ts) * 0.9)], 5),
            min_s=round(ts[0], 5),
            flops_per_step=self._flops,
            tflops_per_s=round(flops_per_s / 1e12, 2),
            mfu=round(mfu, 4) if mfu is not None else None,
        )


@contextlib.contextmanager
def trace(log_dir: str):
    """xplane timeline trace (view in TensorBoard/Perfetto/xprof).

    Reference analog: the torch.profiler timeline export in AProfiler.
    """
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        logger.info("profile trace written to %s", log_dir)


def profile_train_step(step_fn: Callable, state: Any, batch: Any,
                       steps: int = 20, sync: Callable[[Any], None]
                       | None = None) -> tuple[Any, StepStats]:
    """Convenience: time ``steps`` chained executions of a compiled train
    step, with compiled-FLOPs-based MFU. ``sync(metrics)`` forces
    completion (default: device_get of the first output leaf)."""
    import jax

    flops = compiled_flops(step_fn, state, batch)

    def default_sync(out):
        jax.device_get(jax.tree_util.tree_leaves(out)[0])

    sync = sync or default_sync
    # warmup
    state, out = step_fn(state, batch)
    sync(out)
    t0 = time.monotonic()
    for _ in range(steps):
        state, out = step_fn(state, batch)
    sync(out)
    per = (time.monotonic() - t0) / steps
    flops_per_s = flops / per if per > 0 else 0.0
    peak = device_peak_flops()
    # one timed interval over N chained steps: only the mean is real —
    # percentile fields stay 0 (use StepProfiler for order statistics)
    stats = StepStats(
        steps=steps,
        mean_s=round(per, 5),
        flops_per_step=flops,
        tflops_per_s=round(flops_per_s / 1e12, 2),
        mfu=round(flops_per_s / (peak * jax.device_count()), 4)
        if peak else None,
    )
    return state, stats


# ------------------------------------------------------------ breakdown
#
# Reference analog: atorch's AProfiler per-op FLOP formula table
# (atorch/utils/prof.py:482-720 — monkey-patched torch functionals
# counting MACs per module). The JAX shape is cleaner: trace once to a
# jaxpr and charge each equation from its static shapes — control flow
# included (scan bodies multiply by trip count), no patching, no
# execution.

_ELEMENTWISE = frozenset({
    "add", "sub", "mul", "div", "max", "min", "pow", "exp", "log",
    "tanh", "logistic", "rsqrt", "sqrt", "erf", "neg", "sign", "abs",
    "floor", "ceil", "round", "clamp", "select_n", "and", "or", "not",
    "xor", "integer_pow", "cos", "sin",
})
_REDUCE = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "argmax", "argmin", "cumsum",
    "cumlogsumexp", "cummax", "cummin", "cumprod",
})


def _size(v) -> float:
    try:
        return float(np.prod(v.aval.shape)) if v.aval.shape else 1.0
    except Exception:  # noqa: BLE001
        return 0.0


def _dot_flops(eqn) -> float:
    (lhs_contract, _), _ = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval.shape
    out = eqn.outvars[0].aval.shape
    k = 1.0
    for d in lhs_contract:
        k *= lhs[d]
    return 2.0 * float(np.prod(out) if out else 1.0) * k


def _conv_flops(eqn) -> float:
    rhs = eqn.invars[1].aval.shape  # kernel
    out = eqn.outvars[0].aval.shape
    dn = eqn.params["dimension_numbers"]
    # kernel contributes spatial * in-feature MACs per output element;
    # the kernel's in-feature dim is ALREADY C_in/groups by JAX's
    # conv contract, so grouped/depthwise needs no extra division
    k = 1.0
    for i, d in enumerate(rhs):
        if i != dn.rhs_spec[0]:  # skip the out-feature dim
            k *= d
    return 2.0 * float(np.prod(out)) * k


def _jaxpr_flops(jaxpr, acc: dict, mult: float = 1.0) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            acc["dot_general"] = acc.get("dot_general", 0.0) + \
                mult * _dot_flops(eqn)
        elif name == "conv_general_dilated":
            acc["conv"] = acc.get("conv", 0.0) + mult * _conv_flops(eqn)
        elif name == "scan":
            inner = eqn.params["jaxpr"].jaxpr
            _jaxpr_flops(inner, acc, mult * eqn.params["length"])
        elif name == "while":
            # trip count is dynamic: charge one iteration and flag it
            acc["_dynamic_while"] = 1.0
            _jaxpr_flops(eqn.params["body_jaxpr"].jaxpr, acc, mult)
        elif name == "cond":
            # branches are alternatives; charge the heaviest
            best: dict = {}
            for br in eqn.params["branches"]:
                trial: dict = {}
                _jaxpr_flops(br.jaxpr, trial, mult)
                if sum(v for k, v in trial.items()
                       if not k.startswith("_")) > \
                   sum(v for k, v in best.items()
                       if not k.startswith("_")):
                    best = trial
            for k, v in best.items():
                acc[k] = acc.get(k, 0.0) + v
        elif "jaxpr" in eqn.params:  # pjit/remat/closed_call/custom_*
            inner = eqn.params["jaxpr"]
            _jaxpr_flops(getattr(inner, "jaxpr", inner), acc, mult)
        elif "call_jaxpr" in eqn.params:
            inner = eqn.params["call_jaxpr"]
            _jaxpr_flops(getattr(inner, "jaxpr", inner), acc, mult)
        elif name in _ELEMENTWISE:
            acc["elementwise"] = acc.get("elementwise", 0.0) + \
                mult * _size(eqn.outvars[0])
        elif name in _REDUCE:
            acc["reduce"] = acc.get("reduce", 0.0) + \
                mult * _size(eqn.invars[0])


def flops_breakdown(fn: Callable, *args, **kwargs) -> dict[str, float]:
    """Analytic FLOPs of ``fn`` by op class, from one abstract trace.

    Returns ``{"dot_general": ..., "conv": ..., "elementwise": ...,
    "reduce": ..., "total": ...}`` (matmul/conv FLOPs are the MXU
    work; elementwise/reduce counts are VPU op counts, kept separate
    because they price differently). Charges scan bodies by trip
    count; a dynamic ``while`` is charged one iteration and flagged
    with ``{"_dynamic_while": 1.0}``.
    """
    import jax

    jaxpr = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    acc: dict[str, float] = {}
    _jaxpr_flops(jaxpr.jaxpr, acc)
    acc["total"] = sum(
        v for k, v in acc.items() if not k.startswith("_")
    )
    return acc
