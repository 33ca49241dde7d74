"""Efficiency observatory: live MFU, step-phase attribution, on-demand
profiler capture (DESIGN.md §18).

The lost-time report answers "why did the job lose time to failures";
this module answers "where does a *healthy* step go". Three pieces, all
riding the existing telemetry substrate:

- **Live MFU** — the trainer is told the MODEL's FLOPs per step (what
  the mathematics needs: no recompute, the causal half of attention —
  ``TransformerConfig.train_flops_per_token``); dividing by the rolling
  mean step time × per-device peak FLOPs gives model-FLOPs utilization
  as a continuously updated ``dlrover_tpu_mfu{model,strategy}`` gauge.
  The compiled program's own FLOPs (``utils/profiler.executable_flops``,
  cached in the AOT envelope so a warm compile-cache load never
  re-lowers — ``parallel/compile_cache.py``) count recomputed work too
  and feed a second gauge, ``dlrover_tpu_hfu``: the two differ by the
  recompute the strategy bought. Both ride the trainer's existing
  metrics-snapshot pushes, so the master's one-scrape exposition shows
  them job-wide per node.
- **Step-phase attribution** — every step is split into
  ``data_wait | h2d | dispatch | block | ckpt`` phases
  (``dlrover_tpu_step_phase_seconds{phase}`` histograms). ``block`` is
  the ``jax.block_until_ready`` delta after dispatch, so host-blocked
  time (data starvation, H2D staging, checkpoint stalls) separates
  cleanly from device compute. The master's straggler detector
  (``telemetry/anomaly.py``) mines the same histograms out of the
  pushed snapshots to attribute a straggler verdict to its dominant
  phase.
- **On-demand profiler capture** — a ``ProfileRequest`` RPC to the
  master arms ``jax.profiler.start_trace``/``stop_trace`` on a chosen
  node for K steps (master → agent over the heartbeat action channel,
  agent → trainer over an atomically-renamed request file under the
  bundle root — the same no-IPC pattern as the SIGUSR2 stack dump).
  The xplane trace ships through the debug-bundle transport
  (``telemetry/bundle.py``), so a live MFU regression can be drilled
  into without restarting the job.

Journaling: every ``journal_every`` steps the monitor emits one
``metrics_sample`` point (rolling mfu / step time / host-blocked
fraction — the MFU counter track of ``telemetry/timeline.py``). Every
step's phases ride the trainer's ``train_step`` point
(``trainer/elastic_trainer.py``) and nowhere else: ``telemetry/report.py``
averages them and the timeline stacks them.

Like all telemetry, nothing here may take down the instrumented path:
capture and journaling failures are swallowed and counted.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import tempfile
import time
import uuid
from collections import deque
from typing import Callable, Optional

from dlrover_tpu.common.constants import EnvKey
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.telemetry.bundle import bundle_root, write_bundle
from dlrover_tpu.telemetry.journal import get_journal
from dlrover_tpu.telemetry.metrics import registry

logger = get_logger(__name__)

# one vocabulary with telemetry/anomaly.py and telemetry/report.py
PHASES = ("data_wait", "h2d", "dispatch", "block", "ckpt")
# phases the HOST is responsible for; a step is "host-blocked" when they
# outweigh the device wait (block) — the MFU-regression smoking gun
HOST_PHASES = ("data_wait", "h2d", "dispatch", "ckpt")

# phases sit well below the control-plane default buckets: sub-ms H2D
# and dispatch must not all land in the first bucket
_PHASE_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
)

_mfu_gauge = registry().gauge(
    "dlrover_tpu_mfu",
    "live model-FLOPs utilization: the model's FLOPs per step (no "
    "recompute, causal half of attention) / (rolling mean step seconds "
    "x per-device peak FLOPs x devices); unset when the device has no "
    "known peak (CPU) or the trainer was not told the model's FLOPs",
    label_names=("model", "strategy"),
)
_flops_gauge = registry().gauge(
    "dlrover_tpu_mfu_flops_per_step",
    "model FLOPs per train step feeding the live MFU gauge",
    label_names=("model", "strategy"),
)
_hfu_gauge = registry().gauge(
    "dlrover_tpu_hfu",
    "live hardware-FLOPs utilization: the compiled program's FLOPs per "
    "step (XLA cost analysis, recomputed work included; cached in the "
    "AOT compile-cache envelope) over the same denominator as "
    "dlrover_tpu_mfu",
    label_names=("model", "strategy"),
)
_phase_seconds = registry().histogram(
    "dlrover_tpu_step_phase_seconds",
    "train-step wall time split by phase: data_wait (batch iterator), "
    "h2d (host-to-device staging), dispatch (step call), block "
    "(block_until_ready delta = device compute remainder), ckpt "
    "(snapshot/persist on the step path)",
    label_names=("phase",),
    buckets=_PHASE_BUCKETS,
)
# the wire name telemetry/anomaly.py mines out of pushed snapshots
PHASE_METRIC = _phase_seconds.name
_profile_captures = registry().counter(
    "dlrover_tpu_profile_captures_total",
    "on-demand jax.profiler captures by outcome (ok/error/discarded)",
    label_names=("outcome",),
)
_profile_armed = registry().gauge(
    "dlrover_tpu_profile_capture_active",
    "1 while a profiler capture is recording on this process",
)


def live_mfu(model: str, strategy: str) -> float | None:
    """Current value of this process's ``dlrover_tpu_mfu`` gauge for a
    (model, strategy) pair, or None while unset — the read-back the
    bench stages use to assert the live gauge agrees with their own
    MFU arithmetic."""
    value = _mfu_gauge.labels(model or "unknown", strategy or "unknown").value
    return value if value > 0 else None


def journal_sample_every(default: int = 25) -> int:
    """Cadence (in steps) of metrics_sample journal points;
    ``DLROVER_TPU_EFFICIENCY_JOURNAL_EVERY`` overrides, 0 disables."""
    raw = (os.environ.get(EnvKey.EFFICIENCY_JOURNAL_EVERY) or "").strip()
    if not raw:
        return default
    try:
        return max(0, int(raw))
    except ValueError:
        return default


# ------------------------------------------------------ profile requests
#
# Agent -> trainer handoff without new IPC: the agent (which receives
# the master's "profile:K" heartbeat action) atomically renames a small
# JSON request file into a deterministic path under the bundle root;
# the trainer's monitor stats that path once per step (a ~1us syscall)
# and consumes it. Same pattern as the SIGUSR2 stack-dump file.


def profile_request_path(node_id: int) -> str:
    return os.path.join(bundle_root(), f"profile_request_node{node_id}.json")


def arm_profile_request(node_id: int, steps: int,
                        out_root: str | None = None) -> str | None:
    """Write the capture request the trainer's monitor consumes;
    returns the request path (None on failure). Never raises."""
    path = (os.path.join(out_root, f"profile_request_node{node_id}.json")
            if out_root else profile_request_path(node_id))
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"steps": max(1, int(steps)),
                       "id": uuid.uuid4().hex[:8],
                       "t": time.time()}, f)
        os.replace(tmp, path)
    except OSError as e:
        logger.warning("could not arm profile request: %s", e)
        return None
    get_journal().emit("profile_request", node=node_id, steps=steps,
                       path=path)
    return path


class EfficiencyMonitor:
    """Per-trainer efficiency accounting driven from the step loop.

    The trainer calls ``observe_phase(phase, seconds)`` as each phase
    completes and ``end_step(step, step_seconds)`` once per step; the
    monitor keeps rolling windows, publishes the MFU gauge, journals
    rate-limited samples, and runs the profiler-capture state machine.
    """

    def __init__(self, *, model: str = "", strategy: str = "",
                 flops_per_step: float = 0.0,
                 peak_flops: float | None = None,
                 num_devices: int = 1,
                 window: int = 64,
                 journal_every: int | None = None,
                 node_id: int | None = None,
                 on_bundle: Optional[Callable[[str], None]] = None):
        self.model = model or "unknown"
        self.strategy = strategy or "unknown"
        self.peak_flops = peak_flops
        self.num_devices = max(1, num_devices)
        self._flops = 0.0
        self._executable_flops = 0.0
        self._mfu_child = _mfu_gauge.labels(self.model, self.strategy)
        self._flops_child = _flops_gauge.labels(self.model, self.strategy)
        self._hfu_child = _hfu_gauge.labels(self.model, self.strategy)
        if flops_per_step:
            self.set_flops(flops_per_step)
        self._phase_children = {p: _phase_seconds.labels(p) for p in PHASES}
        self._acc = {p: 0.0 for p in PHASES}   # current step's phases
        self._last_phases = dict(self._acc)    # last completed step's
        self._steps = deque(maxlen=max(2, window))
        self._blocked = deque(maxlen=max(2, window))  # host-blocked bools
        self._journal_every = (journal_sample_every()
                               if journal_every is None else journal_every)
        self._node_id = (int(os.environ.get(EnvKey.NODE_ID, "0"))
                         if node_id is None else node_id)
        self._on_bundle = on_bundle
        # profiler capture state
        self._capture_dir: str | None = None
        self._capture_left = 0
        self._capture_steps = 0
        self._capture_t0 = 0.0

    # ----------------------------------------------------------- accounting

    def set_flops(self, flops_per_step: float) -> None:
        """Install the MODEL's FLOPs per step: what the MFU gauge
        divides (no recompute, causal half of attention)."""
        self._flops = float(flops_per_step or 0.0)
        if self._flops > 0:
            self._flops_child.set(self._flops)

    @property
    def flops_per_step(self) -> float:
        return self._flops

    def set_executable_flops(self, flops_per_step: float) -> None:
        """Install the compiled program's FLOPs (once per incarnation;
        warm AOT loads read it from the cache envelope): what the HFU
        gauge divides."""
        self._executable_flops = float(flops_per_step or 0.0)

    @property
    def executable_flops(self) -> float:
        return self._executable_flops

    def observe_phase(self, phase: str, seconds: float) -> None:
        child = self._phase_children.get(phase)
        if child is None:
            return
        seconds = max(0.0, float(seconds))
        child.observe(seconds)
        self._acc[phase] += seconds

    def _utilization(self, flops: float) -> float | None:
        if not (flops > 0 and self.peak_flops and self._steps):
            return None
        mean = statistics.fmean(self._steps)
        if mean <= 0:
            return None
        return flops / mean / (self.peak_flops * self.num_devices)

    def mfu(self) -> float | None:
        """Rolling-window MFU, or None when peak/FLOPs are unknown."""
        return self._utilization(self._flops)

    def hfu(self) -> float | None:
        """The same over the compiled program's FLOPs."""
        return self._utilization(self._executable_flops)

    def step_seconds(self) -> float | None:
        """Rolling-window MEDIAN step cadence — the measured step time
        the autopilot records into the plan history (robust to the
        first dispatch's compile spike); None before any step."""
        if not self._steps:
            return None
        return statistics.median(self._steps)

    def reset_window(self) -> None:
        """Drop the rolling step/blocked windows. A retune swaps the
        running program mid-job: the post-swap median (what the
        autopilot history records, attributed to the NEW plan) must
        never span steps executed under the old one."""
        self._steps.clear()
        self._blocked.clear()

    def host_blocked_frac(self) -> float:
        if not self._blocked:
            return 0.0
        return sum(self._blocked) / len(self._blocked)

    def end_step(self, step: int, step_seconds: float) -> dict:
        """Close out one step: fold the phase accumulator, refresh the
        MFU gauge, journal a sample on cadence, advance any capture.
        Returns the step's phases in seconds."""
        self._steps.append(max(0.0, float(step_seconds)))
        host = sum(self._acc[p] for p in HOST_PHASES)
        self._blocked.append(host > self._acc["block"])
        self._last_phases = dict(self._acc)
        for p in PHASES:
            self._acc[p] = 0.0
        mfu = self.mfu()
        if mfu is not None:
            self._mfu_child.set(round(mfu, 4))
        hfu = self.hfu()
        if hfu is not None:
            self._hfu_child.set(round(hfu, 4))
        if self._journal_every and step % self._journal_every == 0:
            self._journal_sample(step, mfu)
        self._drive_capture(step)
        return self._last_phases

    def _journal_sample(self, step: int, mfu: float | None) -> None:
        get_journal().emit(
            "metrics_sample", step=step,
            mfu=round(mfu, 4) if mfu is not None else None,
            step_s=round(statistics.fmean(self._steps), 6),
            host_blocked_frac=round(self.host_blocked_frac(), 4),
        )

    # ------------------------------------------------------ profiler capture

    def _drive_capture(self, step: int) -> None:
        try:
            if self._capture_dir is not None:
                self._capture_left -= 1
                if self._capture_left <= 0:
                    self._finish_capture(step)
                return
            req = self._consume_request()
            if req is not None:
                self._start_capture(step, req)
        except Exception:  # noqa: BLE001 - never break the step loop
            logger.exception("profiler capture failed")
            _profile_captures.labels("error").inc()
            self._abort_capture()

    def _consume_request(self) -> dict | None:
        path = profile_request_path(self._node_id)
        try:
            if not os.path.exists(path):
                return None
            with open(path) as f:
                req = json.load(f)
            os.unlink(path)
            return req if isinstance(req, dict) else None
        except (OSError, ValueError):
            try:
                os.unlink(path)
            except OSError:
                pass
            return None

    def _start_capture(self, step: int, req: dict) -> None:
        import jax

        steps = max(1, int(req.get("steps", 1) or 1))
        self._capture_dir = tempfile.mkdtemp(prefix="dlrover_tpu_profile_")
        self._capture_left = steps
        self._capture_steps = steps
        self._capture_t0 = time.monotonic()
        jax.profiler.start_trace(self._capture_dir)
        _profile_armed.set(1.0)
        logger.info("profiler capture armed for %d steps at step %d "
                    "(request %s)", steps, step, req.get("id", "?"))

    def _finish_capture(self, step: int) -> None:
        import jax

        trace_dir, self._capture_dir = self._capture_dir, None
        _profile_armed.set(0.0)
        jax.profiler.stop_trace()
        dur = time.monotonic() - self._capture_t0
        path = write_bundle(
            "profile", node_id=self._node_id,
            extra={"steps": self._capture_steps, "end_step": step,
                   "capture_seconds": round(dur, 4),
                   "mfu": self.mfu(), "model": self.model,
                   "strategy": self.strategy},
            attach={"profile": trace_dir},
        )
        shutil.rmtree(trace_dir, ignore_errors=True)
        if path is None:
            _profile_captures.labels("error").inc()
            return
        _profile_captures.labels("ok").inc()
        get_journal().emit("profile_capture", dur=dur, step=step,
                           steps=self._capture_steps, path=path)
        if self._on_bundle is not None:
            try:
                self._on_bundle(path)
            except Exception:  # noqa: BLE001 - reporting is best-effort
                logger.exception("profile bundle report failed")

    def _abort_capture(self) -> None:
        if self._capture_dir is None:
            return
        import jax

        trace_dir, self._capture_dir = self._capture_dir, None
        _profile_armed.set(0.0)
        try:
            jax.profiler.stop_trace()
        except Exception:  # noqa: BLE001 - already stopped / never started
            pass
        shutil.rmtree(trace_dir, ignore_errors=True)

    def close(self) -> None:
        """Stop a capture left running (trainer exiting mid-capture)."""
        if self._capture_dir is not None:
            _profile_captures.labels("discarded").inc()
            self._abort_capture()


def main(argv: list[str] | None = None) -> int:
    """Operator CLI: arm a profiler capture on a running job's node.

    ``python -m dlrover_tpu.telemetry.efficiency --node 0 --steps 5``
    sends a ``ProfileRequest`` to the master (address from
    ``--master`` or ``DLROVER_TPU_MASTER_ADDR``); the capture lands as
    a debug bundle on the target node and is listed by the master's
    bundle ledger.
    """
    parser = argparse.ArgumentParser(
        "python -m dlrover_tpu.telemetry.efficiency",
        description="arm an on-demand jax.profiler capture on one node",
    )
    parser.add_argument("--node", type=int, required=True,
                        help="target node id")
    parser.add_argument("--steps", type=int, default=5,
                        help="capture this many train steps")
    parser.add_argument("--master", default="",
                        help="master addr (default: "
                             "$DLROVER_TPU_MASTER_ADDR)")
    args = parser.parse_args(argv)
    addr = args.master or os.environ.get(EnvKey.MASTER_ADDR, "")
    if not addr:
        print("no master address (set --master or "
              f"{EnvKey.MASTER_ADDR})")
        return 2
    from dlrover_tpu.agent.master_client import MasterClient

    client = MasterClient(addr, node_id=args.node)
    try:
        resp = client.request_profile(args.node, steps=args.steps)
    finally:
        client.close()
    if resp.armed:
        print(f"profile armed on node {args.node} for {args.steps} steps; "
              "watch the master bundle ledger for the capture")
        return 0
    print(f"profile NOT armed: {resp.reason or 'node not running'}")
    return 1


if __name__ == "__main__":
    raise SystemExit(main())
