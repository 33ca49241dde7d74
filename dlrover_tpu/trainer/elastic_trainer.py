"""ElasticTrainer: fixed global batch under changing world size.

Reference analog: dlrover/trainer/torch/elastic/trainer.py:181
(ElasticTrainer with GradientState and _ElasticOptimizer: gradient
accumulation steps are recomputed from the live world size so the effective
global batch — and therefore the loss trajectory — is invariant to
elasticity). TPU-native difference: a membership change restarts the process
and recompiles the step anyway (XLA bakes the mesh into the program), so the
accumulation factor is resolved once per incarnation, not per optimizer call.
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, Iterator

import jax
import numpy as np

from dlrover_tpu.common.constants import EnvKey
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.parallel.mesh import data_parallel_size
from dlrover_tpu.telemetry.efficiency import EfficiencyMonitor
from dlrover_tpu.telemetry.journal import annotate, get_journal, spawn_ctx
from dlrover_tpu.telemetry.metrics import registry
from dlrover_tpu.trainer.train_step import CompiledTrain, TrainState

logger = get_logger(__name__)

_step_seconds = registry().histogram(
    "dlrover_tpu_train_step_seconds",
    "train_step wall time (staging, dispatch and the wait for the "
    "previous step; first call of an incarnation carries the XLA "
    "compile)",
)
_steps_total = registry().counter(
    "dlrover_tpu_train_steps_total",
    "optimizer steps executed by this process",
)
_compile_seconds = registry().histogram(
    "dlrover_tpu_compile_seconds",
    "first-dispatch wall time per incarnation (trace + XLA compile, or "
    "the AOT executable's near-zero re-dispatch; device compute of the "
    "step itself is excluded)",
)


class BatchAssembler:
    """Shape sample streams into [accum, batch, ...] step batches."""

    def __init__(self, accum: int, batch_size: int):
        self.accum = accum
        self.batch_size = batch_size

    def batches(
        self, samples: Iterator[Any],
        collate: Callable[[list], dict[str, np.ndarray]],
    ) -> Iterator[dict[str, np.ndarray]]:
        need = self.accum * self.batch_size
        buf: list = []
        for s in samples:
            buf.append(s)
            if len(buf) == need:
                flat = collate(buf)
                yield {
                    k: v.reshape((self.accum, self.batch_size) + v.shape[1:])
                    for k, v in flat.items()
                }
                buf = []


class ElasticTrainer:
    """Drives a compiled train step at a fixed global batch.

    ``compiled`` is either a ``CompiledTrain`` (one SPMD program) or
    any duck-type of it — the MPMD pipeline runtime
    (``parallel.mpmd.MpmdTrain``) plugs in here unchanged: its ``mesh``
    is stage 0's submesh (whose data axis is the batch-sharding world),
    its ``step`` is the host-side 1F1B scheduler, and its per-stage
    metrics (``dlrover_tpu_pipeline_*``) ride the same snapshot pushes
    as everything else.
    """

    def __init__(
        self,
        compiled: "CompiledTrain | Any",
        global_batch_size: int,
        micro_batch_size: int,
        report_step_interval: int = 1,
        master_client=None,
        model_name: str = "",
        model_flops_per_step: float = 0.0,
    ):
        self.compiled = compiled
        dp = data_parallel_size(compiled.mesh)
        if global_batch_size % (micro_batch_size * dp):
            raise ValueError(
                f"global batch {global_batch_size} not divisible by "
                f"micro_batch {micro_batch_size} × dp {dp}"
            )
        self.accum = global_batch_size // (micro_batch_size * dp)
        self.global_batch_size = global_batch_size
        # per-step GLOBAL batch dim of the compiled step (sharded over dp)
        self.step_batch_size = micro_batch_size * dp
        # multi-process SPMD: each node assembles only the rows its own
        # devices consume; jax assembles the global array from per-process
        # shards (make_array_from_process_local_data). The master's data
        # sharding already hands each node distinct samples.
        self.num_processes = jax.process_count()
        if self.step_batch_size % self.num_processes:
            raise ValueError(
                f"per-step batch {self.step_batch_size} not divisible by "
                f"{self.num_processes} processes"
            )
        self.local_step_batch = self.step_batch_size // self.num_processes
        self.assembler = BatchAssembler(self.accum, self.local_step_batch)
        self._report_interval = report_step_interval
        self._host_step = 0  # avoids blocking on the device step counter
        # node-local progress heartbeat for the agent's hang detector
        # (agent/hang_detector.py); file writes, rate-limited, never on
        # the device-dispatch path
        from dlrover_tpu.agent.hang_detector import ProgressReporter

        self._progress = ProgressReporter()
        self._first_dispatch = True
        self._last_metrics_push = float("-inf")
        self._metrics_push_interval_s = 1.0
        self._client = master_client
        if self._client is None and os.environ.get(EnvKey.MASTER_ADDR):
            from dlrover_tpu.agent.master_client import MasterClient

            self._client = MasterClient.singleton()
        # efficiency observatory (telemetry/efficiency.py): live MFU +
        # step-phase attribution + on-demand profiler capture. The block
        # phase is a LAGGED wait: once step N is dispatched the host
        # waits for step N-1's replicated metrics, so one step is always
        # in flight and the attribution costs no host/device overlap.
        # block is then ~0 when the host is the bottleneck and ~a step
        # when the device is.
        self._prev_metrics: Any = None  # the one extra reference held
        from dlrover_tpu.utils.profiler import device_peak_flops

        self.efficiency = EfficiencyMonitor(
            model=model_name,
            strategy=getattr(compiled.strategy, "name", "") or "",
            flops_per_step=model_flops_per_step,
            peak_flops=device_peak_flops(),
            num_devices=jax.device_count(),
            on_bundle=self._report_profile_bundle,
        )
        self.efficiency.set_executable_flops(
            getattr(compiled, "flops_per_step", 0.0))
        self._last_step_end = 0.0
        # autopilot retune hook (autopilot/apply.py, DESIGN.md §24):
        # called once per step with (step, state); returning
        # (new_compiled, new_state) swaps the running program in place
        # — the no-restart strategy retune path
        self.retune_hook = None
        logger.info(
            "elastic trainer: dp=%d accum=%d global_batch=%d (fixed)",
            dp, self.accum, global_batch_size,
        )

    def swap_compiled(self, compiled: "CompiledTrain | Any") -> None:
        """Install a retuned step program mid-run (same batch geometry
        — the applier's ``can_apply`` guards that). The next dispatch
        is treated as a first dispatch so its compile/load cost lands
        in the recompile cost class, and the HFU gauge re-bases on the
        new program's FLOPs; the rolling step window resets so the
        post-retune median (the value the autopilot history records
        against the new plan) never spans pre-retune steps."""
        self.compiled = compiled
        self._first_dispatch = True
        flops = getattr(compiled, "flops_per_step", 0.0) or 0.0
        if flops > 0:
            self.efficiency.set_executable_flops(flops)
        self.efficiency.reset_window()
        logger.info(
            "swapped compiled step program (strategy %s)",
            getattr(getattr(compiled, "strategy", None), "name", "?"),
        )

    def _report_profile_bundle(self, path: str) -> None:
        """List an on-demand profiler capture in the master's bundle
        ledger, next to crash/hang bundles."""
        if self._client is None:
            return
        node = os.environ.get(EnvKey.NODE_ID, "?")
        self._client.report_debug_bundle(
            path, "profile", proc=f"node{node} trainer"
        )

    def train_step(self, state: TrainState, batch: dict
                   ) -> tuple[TrainState, dict]:
        # host-side counter: reading state.step would block async dispatch
        step = self._host_step + 1
        with annotate("train_step", step_num=step):
            return self._train_step(state, batch, step)

    def _train_step(self, state: TrainState, batch: dict, step: int
                    ) -> tuple[TrainState, dict]:
        step_start = time.monotonic()
        with annotate("h2d"):
            if self.num_processes > 1:
                sharding = self.compiled.batch_sharding
                batch = jax.tree.map(
                    lambda x: jax.make_array_from_process_local_data(
                        sharding, np.ascontiguousarray(x),
                        (x.shape[0], x.shape[1] * self.num_processes)
                        + x.shape[2:],
                    ),
                    batch,
                )
            else:
                batch = jax.device_put(batch, self.compiled.batch_sharding)
        t_dispatch = time.monotonic()
        self.efficiency.observe_phase("h2d", t_dispatch - step_start)
        with annotate("dispatch"):
            state, metrics = self.compiled.step(state, batch)
        t_block = time.monotonic()
        # up to dispatch-return: on a first call this carries the trace
        # + XLA compile (or the AOT executable's ~0 re-dispatch), never
        # the step's device compute — that lands in the block phase
        dispatch_wall = t_block - step_start
        self.efficiency.observe_phase("dispatch", t_block - t_dispatch)
        # the host-vs-device separator, one step late: this step is
        # already queued behind the previous one, so waiting for the
        # previous step's replicated metrics leaves the device busy
        # while it tells how long the host had to wait for it
        waited_for, self._prev_metrics = self._prev_metrics, metrics
        if waited_for is not None:
            with annotate("block"):
                jax.block_until_ready(waited_for)
            del waited_for
        self.efficiency.observe_phase("block", time.monotonic() - t_block)
        self._host_step = step
        step_wall = time.monotonic() - step_start
        _step_seconds.observe(step_wall)
        _steps_total.inc()
        # step cadence (previous end -> this end) feeds the rolling MFU:
        # it includes data_wait/callbacks/ckpt, i.e. real throughput
        now = time.monotonic()
        cadence = (now - self._last_step_end if self._last_step_end
                   else step_wall)
        self._last_step_end = now
        phases = self.efficiency.end_step(step, cadence)
        if self._first_dispatch:
            # the incarnation's first call traces + compiles (or loads
            # the persistent compile cache) before dispatching — the
            # recompile cost class the lost-time report attributes.
            # Timed to dispatch-return (pre-block), so the first step's
            # own device compute never inflates the recompile category;
            # the report's median netting stays as a clamp for journals
            # from builds where dispatch was synchronous.
            self._first_dispatch = False
            _compile_seconds.observe(dispatch_wall)
            # cache_hit distinguishes the warm path (AOT executable
            # served by the compile cache — this event times only the
            # load + one step) from a cold XLA compile; the lost-time
            # report splits the recompile category on it
            hit = getattr(self.compiled, "cache_hit", None)
            # spawn_ctx (§27): the incarnation's recompile attaches
            # under the recovery incident that respawned this trainer
            get_journal().emit(
                "compile", dur=dispatch_wall, step=step,
                cache_hit=bool(hit) if hit is not None else None,
                remote_parent=spawn_ctx(),
            )
            self._maybe_install_flops(state, batch)
        else:
            # one line a step: how long the step took the loop (its
            # cadence) and where the host spent it
            get_journal().emit(
                "train_step", dur=cadence, step=step,
                **{f"{p}_s": round(v, 6) for p, v in phases.items()},
            )
        self._progress.report(step)
        if self._client is not None and step % self._report_interval == 0:
            try:
                self._client.report_step(step)
                # HBM is only observable from the process that owns the
                # chips: report it alongside the step (the agent's monitor
                # covers host cpu/mem; the master merges partial reports)
                from dlrover_tpu.agent.resource_monitor import (
                    local_hbm_used_mb,
                )

                hbm = local_hbm_used_mb()
                if hbm > 0:
                    self._client.report_resource(
                        cpu_percent=0.0, used_memory_mb=0, used_hbm_mb=hbm
                    )
                # push the registry snapshot (rate-limited): carries the
                # step-duration histogram the master's continuous
                # straggler detector consumes (telemetry/anomaly.py) and
                # the per-device HBM gauges, both re-exposed under this
                # node's label by the master's /metrics
                now = time.monotonic()
                if (now - self._last_metrics_push
                        >= self._metrics_push_interval_s):
                    self._last_metrics_push = now
                    self._client.report_metrics(
                        registry().snapshot(), role="trainer"
                    )
            except (ConnectionError, RuntimeError, OSError) as e:
                # telemetry is best-effort: a master mid-failover answers
                # with RpcError (surfaced as RuntimeError) — don't kill
                # the training loop over it
                logger.warning("step report failed: %s", e)
        return state, metrics

    def _maybe_install_flops(self, state: TrainState, batch: dict) -> None:
        """Plain-jit fallback for the live HFU gauge: when the AOT path
        didn't supply FLOPs and the device has a known peak (real TPU —
        never on the CPU test backend), count the compiled program once
        via the already-populated compile cache. Uses the NEW state's
        avals (the donated input's buffers are gone, its avals are not
        what ``.lower`` needs anyway)."""
        if self.efficiency.executable_flops > 0 \
                or not self.efficiency.peak_flops \
                or not hasattr(self.compiled.step, "lower"):
            return
        try:
            from dlrover_tpu.utils.profiler import compiled_flops

            flops = compiled_flops(self.compiled.step, state, batch)
            if flops > 0:
                self.efficiency.set_executable_flops(flops)
        except Exception:  # noqa: BLE001 - MFU is telemetry, not training
            logger.exception("post-compile FLOPs count failed")

    def run(
        self,
        state: TrainState,
        samples: Iterator[Any],
        collate: Callable[[list], dict[str, np.ndarray]],
        max_steps: int | None = None,
        on_step: Callable[[int, dict], None] | None = None,
        checkpointer: Callable[[int, TrainState], None] | None = None,
        checkpoint_interval: int = 0,
    ) -> TrainState:
        return self.run_batches(
            state, self.assembler.batches(samples, collate),
            max_steps=max_steps, on_step=on_step,
            checkpointer=checkpointer,
            checkpoint_interval=checkpoint_interval,
        )

    def run_batches(
        self,
        state: TrainState,
        batches: Iterator[dict],
        max_steps: int | None = None,
        on_step: Callable[[int, dict], None] | None = None,
        checkpointer: Callable[[int, TrainState], None] | None = None,
        checkpoint_interval: int = 0,
    ) -> TrainState:
        """Train over pre-assembled [accum, local_batch, ...] batches
        (e.g. a PrefetchLoader)."""
        start = time.monotonic()
        # one sync at entry so a restored state's step carries forward
        self._host_step = int(state.step)
        if max_steps is not None and self._host_step >= max_steps:
            # a restored finished job must not assemble (and discard) a
            # batch, let alone run extra steps
            logger.info("restored at step %d >= max_steps %d; nothing to do",
                        self._host_step, max_steps)
            return state
        # data_wait/ckpt are observed here (train_step owns h2d/dispatch/
        # block); the ckpt phase of step N folds into step N+1's
        # accumulator — per-step attribution is one step skewed for it,
        # aggregate histograms are exact
        it = iter(batches)
        try:
            while True:
                t0 = time.monotonic()
                try:
                    with annotate("data_wait"):
                        batch = next(it)
                except StopIteration:
                    break
                self.efficiency.observe_phase(
                    "data_wait", time.monotonic() - t0
                )
                state, metrics = self.train_step(state, batch)
                step = self._host_step
                if on_step is not None:
                    # metrics stay on device: fetching here would
                    # serialize host and device every step; callbacks
                    # device_get at their own cadence
                    with annotate("on_step", step=step):
                        on_step(step, metrics)
                if (checkpointer is not None and checkpoint_interval
                        and step % checkpoint_interval == 0):
                    t0 = time.monotonic()
                    with annotate("ckpt", step=step):
                        checkpointer(step, state)
                    self.efficiency.observe_phase(
                        "ckpt", time.monotonic() - t0
                    )
                if self.retune_hook is not None:
                    swapped = self.retune_hook(step, state)
                    if swapped is not None:
                        new_compiled, state = swapped
                        self.swap_compiled(new_compiled)
                if max_steps is not None and step >= max_steps:
                    break
        finally:
            self._prev_metrics = None
            # a capture armed mid-loop must not leak an open trace
            self.efficiency.close()
        logger.info(
            "training loop exited at step %d after %.1fs",
            self._host_step, time.monotonic() - start,
        )
        return state
