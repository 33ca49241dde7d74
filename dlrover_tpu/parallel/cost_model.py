"""Analytic step-time estimation for strategy ranking.

Reference analog: ATorch scores candidate parallelization strategies by
throughput — BO over dry-run timings (atorch/auto/engine/
acceleration_engine.py:13) and an MIP tensor-planner
(atorch/auto/opt_lib/shard_planners/). The TPU-native equivalent needs no
trial training: XLA's AOT compile already yields the per-device FLOP
count, the bytes touched, and — in the HLO itself — every collective the
partitioner inserted. A roofline over those three numbers ranks
strategies in milliseconds.

    est_step_s = max(compute_t, hbm_t) + ici_t + dcn_t

where compute_t = flops / (peak x efficiency), hbm_t = bytes_accessed /
HBM bandwidth, and the collective terms come from summing the wire
volume of every all-reduce / all-gather / reduce-scatter / all-to-all /
collective-permute in the compiled module (each is per-device in an SPMD
program). max() models XLA's elementwise/matmul overlap; collectives are
charged unoverlapped — conservative, but uniform across candidates, and
ranking is all selection needs.
"""

from __future__ import annotations

import dataclasses
import re

from dlrover_tpu.common.log import get_logger

logger = get_logger(__name__)

# Constants for explicit specs and offline ranking (per chip, v5e
# class). Absolute accuracy is not the goal — candidates are ranked
# against each other under the SAME constants. ``for_device`` takes the
# compute and memory peaks of a live TPU from the peaks table instead.
_V5E = dict(peak_flops=1.97e14, hbm_bps=8.19e11, ici_bps=9.0e10,
            dcn_bps=6.25e9, mxu_efficiency=0.5)


@dataclasses.dataclass
class HardwareSpec:
    peak_flops: float = _V5E["peak_flops"]
    hbm_bps: float = _V5E["hbm_bps"]
    ici_bps: float = _V5E["ici_bps"]
    dcn_bps: float = _V5E["dcn_bps"]
    mxu_efficiency: float = _V5E["mxu_efficiency"]

    @classmethod
    def for_device(cls, device=None) -> "HardwareSpec":
        """Spec for the live backend: a TPU's compute and memory peaks
        come from the peaks table (an unknown ``device_kind`` raises);
        the link and efficiency terms are this model's own constants."""
        import jax

        device = device or jax.devices()[0]
        if device.platform == "tpu":
            from dlrover_tpu.utils.profiler import device_peaks

            peaks = device_peaks(device)
            return cls(**{**_V5E, "peak_flops": peaks.bf16_flops,
                          "hbm_bps": peaks.hbm_bps})
        # CPU / virtual test meshes: small constants so comm terms are
        # visible relative to compute in unit tests
        return cls(peak_flops=2e11, hbm_bps=5e10, ici_bps=2e10,
                   dcn_bps=2e9, mxu_efficiency=1.0)


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

# `%x = f32[128,64]{1,0} all-gather(...)` and the async `-start` forms.
# `-done` ops carry no new volume (same buffer) and don't match because
# the regex requires the opname to be followed directly by `(` or `-start(`.
_COLLECTIVE_RE = re.compile(
    r"=\s+(?P<type>\(?[a-z0-9]+\[[^=]*?)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|ragged-all-to-all)(?:-start)?\(",
)
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")


def _shape_bytes(type_text: str) -> int:
    """Total bytes of every array shape in an HLO type expression
    (handles tuple types from async -start ops by taking the LARGEST
    member: start tuples alias (operand, result) of the same transfer)."""
    sizes = []
    for dtype, dims in _SHAPE_RE.findall(type_text):
        unit = _DTYPE_BYTES.get(dtype)
        if unit is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        sizes.append(n * unit)
    return max(sizes, default=0)


# Ring-algorithm wire multiplier per result byte: an all-reduce moves
# ~2x its tensor over the wire (reduce-scatter + all-gather phases);
# gather/scatter/a2a/permute move ~1x their larger side.
_WIRE_FACTOR = {
    "all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
    "all-to-all": 1.0, "collective-permute": 1.0,
    "ragged-all-to-all": 1.0,
}


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Per-device wire bytes by collective kind in a compiled module."""
    out: dict[str, float] = {}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        op = m.group("op")
        nbytes = _shape_bytes(m.group("type")) * _WIRE_FACTOR[op]
        out[op] = out.get(op, 0.0) + nbytes
    return out


@dataclasses.dataclass
class PipelineSchedule:
    """Schedule shape of a pipelined candidate, for the bubble + p2p
    terms of :func:`estimate_step_time`.

    ``kind``:

    - ``"spmd_gpipe"`` / ``"spmd_interleaved"``: the single-program
      SPMD-roll schedules of ``parallel/pipeline.py`` — every ring step
      runs in lockstep, so each of the ``vM + P - 1`` slots is paced by
      the SLOWEST stage (at 1/v of its per-microbatch work when
      interleaved).
    - ``"mpmd_1f1b"``: the per-stage-program runtime
      (``parallel/mpmd.py``) — stages advance independently, so the
      fill/drain ramp pays each stage's own cost once and steady state
      is paced only by the slowest stage:
      ``T = (M - 1) * max_s(t_s) + sum_s(t_s)``.

    ``stage_time_s``: optional per-stage per-microbatch fwd+bwd times
    for heterogeneous stages; when absent, stages are assumed uniform
    and derived from the roofline work term. ``activation_bytes``: size
    of one microbatch's boundary activation — every stage handoff moves
    it once forward and once backward (the inter-stage p2p wire term
    the SPMD roll pays as collective-permutes inside the HLO and MPMD
    pays as explicit device-to-device transfers).
    """

    kind: str = "spmd_gpipe"
    num_stages: int = 1
    num_microbatches: int = 0
    interleave: int = 1
    activation_bytes: float = 0.0
    stage_time_s: tuple = ()

    def shape(self) -> tuple[int, int, int]:
        P = max(1, int(self.num_stages))
        M = int(self.num_microbatches) or P
        v = max(1, int(self.interleave))
        return P, M, v


def pipeline_schedule_time(schedule: PipelineSchedule,
                           work_s: float) -> tuple[float, float]:
    """(scheduled_s, bubble_s) for one step whose ideal (bubble-free)
    per-device work is ``work_s``.

    Uniform stages: every schedule degrades to
    ``work_s * (1 + (P-1)/(vM))`` — the classic bubble fraction
    ``(P-1)/(vM+P-1)`` of the total. Heterogeneous stages are where the
    kinds separate: the lockstep SPMD roll charges every slot at the
    slowest stage's pace, MPMD 1F1B pays other stages' cost only during
    fill/drain (the ISSUE's "stages with heterogeneous cost no longer
    pay the slowest stage's bubble").
    """
    P, M, v = schedule.shape()
    if P <= 1:
        return work_s, 0.0
    times = [float(t) for t in (schedule.stage_time_s or ())]
    if len(times) != P:
        # ``work_s`` is PER-DEVICE (one stage's work over all M
        # microbatches under pipeline sharding), so the uniform
        # per-microbatch stage time is work_s / M
        times = [work_s / M] * P
    t_max = max(times)
    # bubble-free floor: all stages perfectly overlapped, wall time set
    # by the busiest device
    ideal = M * t_max
    if schedule.kind == "mpmd_1f1b":
        sched = (M - 1) * t_max + sum(times)
    else:
        # lockstep SPMD roll: vM + P - 1 ring steps of 1/v-sized work,
        # each paced by the slowest stage
        sched = (v * M + P - 1) * t_max / v
    sched = max(sched, ideal)
    return sched, sched - ideal


@dataclasses.dataclass
class StepTimeEstimate:
    est_step_s: float = 0.0
    compute_s: float = 0.0
    hbm_s: float = 0.0
    ici_s: float = 0.0
    dcn_s: float = 0.0
    comm_bytes: float = 0.0
    by_collective: dict = dataclasses.field(default_factory=dict)
    # schedule-aware terms (0 / "" without a pipeline schedule)
    bubble_s: float = 0.0
    bubble_frac: float = 0.0
    p2p_s: float = 0.0
    schedule_kind: str = ""


def estimate_step_time(
    *,
    flops: float,
    bytes_accessed: float,
    hlo_text: str = "",
    hw: HardwareSpec | None = None,
    dcn_fraction: float = 0.0,
    schedule: PipelineSchedule | None = None,
) -> StepTimeEstimate:
    """Roofline step time from AOT compile artifacts (all per-device).

    ``dcn_fraction``: share of collective wire volume that crosses DCN
    instead of ICI. The HLO alone cannot tell which replica groups span
    hosts, so single-slice estimation (the default) charges everything
    at ICI bandwidth; callers ranking multi-slice candidates over a
    hybrid mesh pass the fraction their mesh layout implies (e.g. the
    dp-over-DCN share from parallel/mesh.py's hybrid builder).

    ``schedule``: pipeline schedule shape. Without it the estimate is
    schedule-blind (the pre-MPMD behavior, unchanged); with it the work
    term is stretched by the schedule's fill/drain bubble — lockstep
    for the SPMD roll, per-stage-independent for MPMD 1F1B — and an
    explicit inter-stage p2p wire term is charged for the boundary
    activations (2 crossings per microbatch per boundary: fwd
    activation + bwd cotangent).
    """
    hw = hw or HardwareSpec.for_device()
    by = collective_bytes(hlo_text) if hlo_text else {}
    comm = sum(by.values())
    compute_s = flops / (hw.peak_flops * hw.mxu_efficiency) if flops else 0.0
    hbm_s = bytes_accessed / hw.hbm_bps if bytes_accessed else 0.0
    ici_s = comm * (1.0 - dcn_fraction) / hw.ici_bps
    dcn_s = comm * dcn_fraction / hw.dcn_bps
    work_s = max(compute_s, hbm_s)
    bubble_s = 0.0
    bubble_frac = 0.0
    p2p_s = 0.0
    kind = ""
    if schedule is not None and schedule.num_stages > 1:
        P, M, _v = schedule.shape()
        kind = schedule.kind
        work_s, bubble_s = pipeline_schedule_time(schedule, work_s)
        bubble_frac = bubble_s / work_s if work_s else 0.0
        # a stage's device sends + receives one boundary activation per
        # microbatch in each direction (fwd activation, bwd cotangent)
        p2p_s = 2.0 * M * schedule.activation_bytes / hw.ici_bps
    return StepTimeEstimate(
        est_step_s=work_s + ici_s + dcn_s + p2p_s,
        compute_s=compute_s,
        hbm_s=hbm_s,
        ici_s=ici_s,
        dcn_s=dcn_s,
        comm_bytes=comm,
        by_collective=by,
        bubble_s=bubble_s,
        bubble_frac=bubble_frac,
        p2p_s=p2p_s,
        schedule_kind=kind,
    )


def rank_schedules(
    candidates: dict[str, PipelineSchedule],
    *,
    flops: float,
    bytes_accessed: float,
    hw: HardwareSpec | None = None,
) -> list[tuple[str, StepTimeEstimate]]:
    """Rank pipeline schedule candidates for ONE model geometry,
    fastest first — the MPMD-vs-SPMD gate (``parallel/mpmd.py``'s
    ``choose_schedule`` and the example's ``--schedule auto`` consume
    the head). Same constants across candidates, so only the schedule
    terms separate them."""
    hw = hw or HardwareSpec.for_device()
    ranked = [
        (name,
         estimate_step_time(flops=flops, bytes_accessed=bytes_accessed,
                            hw=hw, schedule=sched))
        for name, sched in candidates.items()
    ]
    ranked.sort(key=lambda pair: pair[1].est_step_s)
    return ranked
