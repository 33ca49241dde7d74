"""High-level Trainer: the HF-Trainer-class UX over the strategy layer.

Reference analog: atorch/atorch/trainer/atorch_trainer.py:129 (AtorchTrainer:
train/eval loops, logging, checkpoint save policies with rotation, best-model
tracking, resume semantics) and atorch/atorch/trainer/atorch_args.py:21
(AtorchArguments). TPU-native differences:

- The reference wraps a mutable torch module and drives auto_accelerate
  imperatively; here the model surface is a ``loss_fn`` factory compiled once
  into a single SPMD program (``trainer/train_step.py``), and the Trainer owns
  only host-side control flow — epochs, logging cadence, eval cadence, save
  policy, resume. Everything under ``jit`` stays pure.
- Checkpointing is the flash-checkpoint engine (shm snapshot + async persist,
  ``checkpoint/engine.py``), so ``save_steps`` costs sub-second blocking time
  and rotation/best-model bookkeeping happens against the committed tracker.
- Metric tensors stay on device between logging steps: the loop never calls
  ``device_get`` per step, preserving async dispatch (the reference's
  equivalent concern is CUDA-stream sync in its logging hot path).
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import time
from typing import Any, Callable, Iterable, Sequence

import jax
import numpy as np
import optax

from dlrover_tpu.agent.ckpt_saver import read_tracker, step_dir
from dlrover_tpu.checkpoint.engine import CheckpointEngine
from dlrover_tpu.checkpoint.shm_handler import _leaf_paths
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.parallel.mesh import batch_axes, data_parallel_size
from dlrover_tpu.parallel.strategy import PRESETS, Strategy
from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer
from dlrover_tpu.trainer.train_step import CompiledTrain, compile_train

logger = get_logger(__name__)

IntervalStrategy = str  # "no" | "steps" | "epoch"


@dataclasses.dataclass
class TrainingArguments:
    """Host-side training configuration (AtorchArguments analog).

    Batch semantics: ``global_batch_size`` is invariant under elasticity
    (the ElasticTrainer resolves gradient accumulation from the live dp
    size); ``micro_batch_size`` is the per-device-step slice.
    """

    output_dir: str = "trainer_out"
    max_steps: int = -1                  # >0 overrides num_train_epochs
    num_train_epochs: float = 1.0
    global_batch_size: int = 32
    micro_batch_size: int = 0            # 0 -> one accumulation step
    eval_batch_size: int = 0             # 0 -> global_batch_size
    seed: int = 0
    shuffle: bool = True

    logging_steps: int = 10
    logging_first_step: bool = True

    eval_strategy: IntervalStrategy = "no"
    eval_steps: int = 0                  # used when eval_strategy == "steps"

    save_strategy: IntervalStrategy = "no"
    save_steps: int = 0                  # used when save_strategy == "steps"
    save_total_limit: int | None = None
    # flash-checkpoint hot path: shm-only snapshots between persisted saves
    # (0 disables). Restart-in-place restores from the newest snapshot even
    # if it was never persisted.
    memory_save_steps: int = 0

    metric_for_best_model: str | None = None   # e.g. "eval_loss"
    greater_is_better: bool = False
    load_best_model_at_end: bool = False

    resume_from_checkpoint: bool = True

    def __post_init__(self):
        if self.micro_batch_size <= 0:
            self.micro_batch_size = self.global_batch_size
        if self.eval_batch_size <= 0:
            self.eval_batch_size = self.global_batch_size
        if self.eval_strategy == "steps" and self.eval_steps <= 0:
            raise ValueError("eval_strategy='steps' requires eval_steps > 0")
        if self.save_strategy == "steps" and self.save_steps <= 0:
            raise ValueError("save_strategy='steps' requires save_steps > 0")
        if self.load_best_model_at_end and not self.metric_for_best_model:
            self.metric_for_best_model = "eval_loss"

    # ---- serialization (config-system parity: Strategy-style round trip)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=2)

    @classmethod
    def from_json(cls, text: str) -> "TrainingArguments":
        return cls(**json.loads(text))


@dataclasses.dataclass
class TrainerState:
    """Host-side progress bookkeeping, persisted as trainer_state.json.

    The device-side step counter lives in TrainState; this mirror carries
    what the devices can't: epoch position, log history, best-model metric.
    """

    global_step: int = 0
    epoch: float = 0.0
    best_metric: float | None = None
    best_step: int | None = None
    log_history: list = dataclasses.field(default_factory=list)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=2)

    @classmethod
    def from_json(cls, text: str) -> "TrainerState":
        return cls(**json.loads(text))


@dataclasses.dataclass
class TrainerControl:
    """Mutable flow-control flags callbacks may set (HF TrainerControl)."""

    should_training_stop: bool = False
    should_log: bool = False
    should_evaluate: bool = False
    should_save: bool = False


class TrainerCallback:
    """Hook points around the loop. Mutate ``control`` to steer flow."""

    def on_train_begin(self, args, state, control, **kw): ...
    def on_epoch_begin(self, args, state, control, **kw): ...
    def on_step_end(self, args, state, control, **kw): ...
    def on_log(self, args, state, control, logs=None, **kw): ...
    def on_evaluate(self, args, state, control, metrics=None, **kw): ...
    def on_save(self, args, state, control, **kw): ...
    def on_epoch_end(self, args, state, control, **kw): ...
    def on_train_end(self, args, state, control, **kw): ...
    # fired when train() is about to re-raise an exception; release
    # resources here (on_train_end does NOT fire on the failure path)
    def on_train_error(self, args, state, control, **kw): ...


class LoggingCallback(TrainerCallback):
    """Default logger: structured line per log event + JSONL file."""

    def __init__(self, path: str | None = None):
        self._path = path

    def on_log(self, args, state, control, logs=None, **kw):
        if not logs:
            return
        logger.info(
            "step %d: %s", state.global_step,
            " ".join(f"{k}={v:.5g}" if isinstance(v, float) else f"{k}={v}"
                     for k, v in logs.items()),
        )
        if self._path:
            with open(self._path, "a") as f:
                f.write(json.dumps(
                    {"step": state.global_step, **logs}) + "\n")


class GoodputCallback(TrainerCallback):
    """Write the per-step goodput event log (utils/goodput.py) from the
    Trainer loop; aggregate offline with ``compute_goodput``."""

    def __init__(self, path: str):
        self._path = path
        self._recorder = None

    def on_train_begin(self, args, state, control, **kw):
        from dlrover_tpu.common.constants import EnvKey
        from dlrover_tpu.utils.goodput import GoodputRecorder

        restart = int(os.environ.get(EnvKey.RESTART_COUNT, "0"))
        self._recorder = GoodputRecorder(self._path, restart)

    def on_step_end(self, args, state, control, **kw):
        if self._recorder is not None:
            self._recorder.step(state.global_step)

    def on_train_end(self, args, state, control, **kw):
        if self._recorder is not None:
            self._recorder.done()
            self._recorder.close()
            self._recorder = None

    def on_train_error(self, args, state, control, **kw):
        # no "done" event: a crashed incarnation looks the same as a
        # SIGKILLed one to the aggregator — only release the handle
        if self._recorder is not None:
            self._recorder.close()
            self._recorder = None


class EarlyStoppingCallback(TrainerCallback):
    """Stop after ``patience`` evaluations without improvement."""

    def __init__(self, patience: int = 3, threshold: float = 0.0):
        self.patience = patience
        self.threshold = threshold
        self._bad_evals = 0
        # own best-so-far: state.best_metric is already updated to THIS
        # eval by the time callbacks fire, so comparing against it would
        # count every new best as "no improvement"
        self._best: float | None = None

    def on_evaluate(self, args, state, control, metrics=None, **kw):
        key = args.metric_for_best_model or "eval_loss"
        value = (metrics or {}).get(key)
        if value is None:
            return
        sign = 1.0 if args.greater_is_better else -1.0
        if self._best is None or sign * (value - self._best) > self.threshold:
            self._best = value
            self._bad_evals = 0
        else:
            self._bad_evals += 1
            if self._bad_evals >= self.patience:
                logger.info(
                    "early stop: %s stalled for %d evals", key, self.patience
                )
                control.should_training_stop = True


class CallbackHandler:
    def __init__(self, callbacks: Sequence[TrainerCallback]):
        self.callbacks = list(callbacks)

    def fire(self, event: str, args, state, control, **kw):
        for cb in self.callbacks:
            getattr(cb, event)(args, state, control, **kw)


def _default_collate(samples: list) -> dict[str, np.ndarray]:
    if isinstance(samples[0], dict):
        return {
            k: np.stack([s[k] for s in samples]) for k in samples[0]
        }
    return {"batch": np.stack(samples)}


class Trainer:
    """Train/eval/save driver over one compiled SPMD step.

    Model surface (mirrors compile_train):
      - ``loss_fn_for(strategy, mesh) -> loss_fn(params, micro_batch)`` or a
        plain ``loss_fn`` when it doesn't depend on the layout;
      - ``init_params_fn(rng)`` + ``logical_params`` (axis names) so the
        strategy layer can place every tensor;
      - ``optimizer`` (optax), optionally ``lr_schedule(step)`` for logging;
      - ``model_flops_per_step``: what the mathematics needs per global
        step (``TransformerConfig.train_flops_per_token`` x tokens), for
        ``dlrover_tpu_mfu``; left out, only ``dlrover_tpu_hfu`` (the
        executable's count) is published.

    Data surface: ``train_dataset`` is a Sequence (len/getitem -> epoch +
    shuffle semantics) or any re-iterable; ``collate_fn(list) -> dict of
    np.ndarray`` stacks samples. Elastic runs pass a master-fed
    ElasticDataset here unchanged.
    """

    def __init__(
        self,
        *,
        args: TrainingArguments,
        optimizer: optax.GradientTransformation,
        init_params_fn: Callable[..., Any],
        logical_params: Any,
        loss_fn: Callable[[Any, Any], jax.Array] | None = None,
        loss_fn_for: Callable[[Strategy, Any], Callable] | None = None,
        train_dataset: Iterable | None = None,
        eval_dataset: Iterable | None = None,
        collate_fn: Callable[[list], dict[str, np.ndarray]] | None = None,
        compute_metrics: Callable[[Any, Any], dict] | None = None,
        strategy: Strategy | str | None = None,
        callbacks: Sequence[TrainerCallback] | None = None,
        lr_schedule: Callable[[int], float] | None = None,
        engine: CheckpointEngine | None = None,
        example_batch: Any | None = None,
        model_flops_per_step: float = 0.0,
    ):
        self.args = args
        self.train_dataset = train_dataset
        self.eval_dataset = eval_dataset
        self.collate_fn = collate_fn or _default_collate
        self.compute_metrics = compute_metrics
        self.lr_schedule = lr_schedule

        if strategy == "auto":
            # auto_accelerate-style search, cached in output_dir (the
            # load_strategy analog): restarts reuse the tuned pick.
            # ``example_batch`` carries ONE SAMPLE's shapes; the real
            # [accum=1, global_batch, ...] layout is derived from args
            # so the fit check sizes the workload actually trained
            # (full global batch in one step — the conservative bound).
            if example_batch is None:
                raise ValueError(
                    "strategy='auto' requires example_batch (per-sample "
                    "shapes; the Trainer adds the batch dims)"
                )
            lf_for = loss_fn_for
            if lf_for is None:
                if loss_fn is None:
                    raise ValueError(
                        "strategy='auto' requires loss_fn or loss_fn_for"
                    )
                lf_for = lambda s, m: loss_fn  # noqa: E731

            from dlrover_tpu.parallel.auto import cached_auto_strategy

            gb = args.global_batch_size
            sized_batch = jax.tree_util.tree_map(
                lambda a: np.zeros(
                    (1, gb, *np.shape(a)), np.asarray(a).dtype
                ),
                example_batch,
            )
            strategy, _ = cached_auto_strategy(
                os.path.join(args.output_dir, "strategy.json"),
                loss_fn_for=lf_for,
                init_params_fn=init_params_fn,
                logical_params=logical_params,
                optimizer=optimizer,
                example_batch=sized_batch,
            )
        elif isinstance(strategy, str):
            strategy = PRESETS[strategy]()
        elif strategy is None:
            strategy = PRESETS["dp"]()
        self.strategy = strategy
        self.mesh = strategy.build_mesh()
        if loss_fn_for is not None:
            loss_fn = loss_fn_for(strategy, self.mesh)
        if loss_fn is None:
            raise ValueError("need loss_fn or loss_fn_for")
        self._eval_loss_fn = loss_fn

        self.compiled: CompiledTrain = compile_train(
            strategy=strategy,
            mesh=self.mesh,
            loss_fn=loss_fn,
            init_params_fn=init_params_fn,
            logical_params=logical_params,
            optimizer=optimizer,
        )
        self.elastic = ElasticTrainer(
            self.compiled,
            global_batch_size=args.global_batch_size,
            micro_batch_size=args.micro_batch_size,
            model_flops_per_step=model_flops_per_step,
        )

        os.makedirs(args.output_dir, exist_ok=True)
        self.ckpt_dir = os.path.join(args.output_dir, "checkpoints")
        self._owns_engine = engine is None
        self.engine = engine or CheckpointEngine(self.ckpt_dir)
        self.state = TrainerState()
        self.control = TrainerControl()
        log_path = os.path.join(args.output_dir, "log_history.jsonl")
        self.callback_handler = CallbackHandler(
            [LoggingCallback(log_path)] + list(callbacks or [])
        )
        self._eval_step_fn = None
        self._train_state = None  # device TrainState, set by train()
        self._last_save_step = -1

    # ------------------------------------------------------------ data plumbing

    def _steps_per_epoch(self) -> int | None:
        ds = self.train_dataset
        if ds is not None and hasattr(ds, "__len__"):
            return max(1, len(ds) // self.args.global_batch_size)
        return None

    def _epoch_samples(self, epoch: int, skip_steps: int = 0) -> Iterable:
        """One epoch's sample stream (seeded shuffle for Sequences).

        Multi-process SPMD: every process derives the same permutation,
        truncates it to a multiple of the process count (unequal
        per-process counts would desync the collective step), then takes
        its strided slice — each remaining sample lands on exactly one
        process and every process yields the same number of step batches.
        Elastic runs use a master-fed dataset instead (pre-sharded).

        ``skip_steps`` drops already-consumed step batches at the SAMPLE
        level (mid-epoch resume) — slicing here instead of draining
        assembled batches keeps restart-in-place sub-second.
        """
        ds = self.train_dataset
        np_ = self.elastic.num_processes
        skip_samples = skip_steps * self.elastic.assembler.accum \
            * self.elastic.assembler.batch_size
        if hasattr(ds, "__len__") and hasattr(ds, "__getitem__"):
            order = np.arange(len(ds))
            if self.args.shuffle:
                order = np.random.default_rng(
                    self.args.seed + epoch).permutation(len(ds))
            if np_ > 1:
                order = order[:len(order) - len(order) % np_]
                order = order[jax.process_index()::np_]
            return (ds[int(i)] for i in order[skip_samples:])
        import itertools

        return itertools.islice(iter(ds), skip_samples, None)

    def _sample_iter(self, ds: Iterable, shard: bool = True) -> Iterable:
        """Eval sample stream, optionally sharded across processes.

        Sharded Sequences are padded by wrap-around to a process-count
        multiple then strided: equal batch counts on every process
        (collective safety), every sample scored at least once
        (drop_last=False; the <np wrapped samples weigh double in the
        mean). ``shard=False`` (predict) and plain iterables read the
        full stream on every process.
        """
        np_ = self.elastic.num_processes
        if hasattr(ds, "__len__") and hasattr(ds, "__getitem__"):
            if shard and np_ > 1:
                idx = list(range(len(ds)))
                idx += idx[:(-len(idx)) % np_]  # wrap-pad to a multiple
                idx = idx[jax.process_index()::np_]
            else:
                idx = range(len(ds))
            return (ds[int(i)] for i in idx)
        return iter(ds)

    @staticmethod
    def _batched(samples: Iterable, n: int) -> Iterable[tuple[list, int]]:
        """(buffer, true_count) chunks of n samples; the last chunk is
        padded by repetition so compiled shapes stay static, with
        true_count telling the caller how many rows are real."""
        buf: list = []
        for s in samples:
            buf.append(s)
            if len(buf) == n:
                yield buf, n
                buf = []
        if buf:
            true = len(buf)
            yield (buf * math.ceil(n / true))[:n], true

    def _eval_local_batch(self) -> int:
        """Per-process eval batch: global eval batch rounded up to a
        multiple of the data-parallel extent (sharding divisibility),
        split across processes, never zero."""
        dp = data_parallel_size(self.mesh)
        global_bsz = max(self.args.eval_batch_size, dp)
        global_bsz = ((global_bsz + dp - 1) // dp) * dp
        return max(1, global_bsz // self.elastic.num_processes)

    def num_examples(self) -> int | None:
        ds = self.train_dataset
        return len(ds) if ds is not None and hasattr(ds, "__len__") else None

    # ------------------------------------------------------------------ resume

    def _init_or_resume(self) -> Any:
        state = self.compiled.init(jax.random.PRNGKey(self.args.seed))
        if not self.args.resume_from_checkpoint:
            return state
        shard_of = dict(_leaf_paths(self.compiled.state_shardings))
        loaded = self.engine.load(
            state,
            put=lambda name, arr: jax.device_put(arr, shard_of[name]),
            zero_copy=True,
        )
        if loaded is None:
            return state
        step, state = loaded
        self.state.global_step = step
        ts_path = os.path.join(self.args.output_dir, "trainer_state.json")
        if os.path.exists(ts_path):
            with open(ts_path) as f:
                saved = TrainerState.from_json(f.read())
            # the checkpoint step wins over the (possibly newer) json
            saved.global_step = step
            self.state = saved
        logger.info("resumed at step %d", step)
        return state

    # ---------------------------------------------------------------- training

    def train(self) -> TrainerState:
        try:
            return self._train()
        except BaseException:
            # resource-releasing hook for callbacks holding files/threads
            # (on_train_end only fires on the success path)
            self.callback_handler.fire(
                "on_train_error", self.args, self.state, self.control
            )
            raise

    def _train(self) -> TrainerState:
        args = self.args
        state = self._init_or_resume()
        steps_per_epoch = self._steps_per_epoch()
        if args.max_steps > 0:
            total_steps = args.max_steps
        elif steps_per_epoch is not None:
            total_steps = int(steps_per_epoch * args.num_train_epochs)
        else:
            raise ValueError(
                "max_steps required for datasets without __len__"
            )
        self.callback_handler.fire(
            "on_train_begin", args, self.state, self.control
        )
        pending_metrics: list = []
        last_log_step = self.state.global_step
        last_log_time = time.monotonic()

        def flush_logs(step: int):
            nonlocal pending_metrics, last_log_step, last_log_time
            if not pending_metrics:
                return
            fetched = jax.device_get(pending_metrics)
            logs = {
                k: float(np.mean([m[k] for m in fetched]))
                for k in fetched[0]
            }
            now = time.monotonic()
            dsteps = step - last_log_step
            if dsteps > 0 and now > last_log_time:
                rate = dsteps / (now - last_log_time)
                logs["steps_per_sec"] = rate
                logs["samples_per_sec"] = rate * args.global_batch_size
            if self.lr_schedule is not None:
                logs["learning_rate"] = float(self.lr_schedule(step))
            if steps_per_epoch:
                self.state.epoch = step / steps_per_epoch
                logs["epoch"] = round(self.state.epoch, 4)
            pending_metrics = []
            last_log_step, last_log_time = step, now
            self.state.log_history.append(
                {"step": step, **logs})
            self.callback_handler.fire(
                "on_log", args, self.state, self.control, logs=logs
            )

        epoch = int(self.state.global_step // steps_per_epoch
                    ) if steps_per_epoch else 0
        done = self.state.global_step >= total_steps
        while not done and not self.control.should_training_stop:
            self.callback_handler.fire(
                "on_epoch_begin", args, self.state, self.control
            )
            # mid-epoch resume: same seed -> same order, so skipping the
            # consumed steps' samples realigns the stream
            skip = (self.state.global_step % steps_per_epoch
                    if steps_per_epoch else 0)
            batches = self.elastic.assembler.batches(
                self._epoch_samples(epoch, skip_steps=skip),
                self.collate_fn,
            )
            made_progress = False
            for batch in batches:
                made_progress = True
                state, metrics = self.elastic.train_step(state, batch)
                self.state.global_step += 1
                step = self.state.global_step
                pending_metrics.append(metrics)
                self.callback_handler.fire(
                    "on_step_end", args, self.state, self.control
                )
                if (self.control.should_log
                        or (args.logging_first_step
                            and step == 1)
                        or (args.logging_steps
                            and step % args.logging_steps == 0)):
                    self.control.should_log = False
                    flush_logs(step)
                if (self.control.should_evaluate
                        or (args.eval_strategy == "steps"
                            and step % args.eval_steps == 0)):
                    self.control.should_evaluate = False
                    self._evaluate_during_training(state)
                if (self.control.should_save
                        or (args.save_strategy == "steps"
                            and step % args.save_steps == 0)):
                    self.control.should_save = False
                    self._save_checkpoint(step, state)
                elif (args.memory_save_steps
                        and step % args.memory_save_steps == 0):
                    # zero-stall where safe; the engine self-gates
                    # (sharded/CPU fall back to the sync path)
                    self.engine.save_to_memory_async(step, state)
                if step >= total_steps or self.control.should_training_stop:
                    break
            if not made_progress:
                # a non-restartable stream ran dry short of total_steps:
                # stop rather than spin on empty epochs
                logger.warning(
                    "dataset exhausted at step %d (< %d); stopping",
                    self.state.global_step, total_steps,
                )
                break
            epoch += 1
            if steps_per_epoch:
                self.state.epoch = self.state.global_step / steps_per_epoch
            if (args.eval_strategy == "epoch"
                    and not self.control.should_training_stop):
                self._evaluate_during_training(state)
            if (args.save_strategy == "epoch"
                    and not self.control.should_training_stop):
                self._save_checkpoint(self.state.global_step, state)
            self.callback_handler.fire(
                "on_epoch_end", args, self.state, self.control
            )
            done = self.state.global_step >= total_steps
        flush_logs(self.state.global_step)
        state = self._finalize(state)
        self._train_state = state
        self.callback_handler.fire(
            "on_train_end", args, self.state, self.control
        )
        return self.state

    def _finalize(self, state):
        args = self.args
        if args.save_strategy != "no":
            step = self.state.global_step
            if self._last_save_step < step:
                self._save_checkpoint(step, state)
            waited = self.engine.wait_for_persist(step)
            if waited:
                # in-loop rotations see whatever the async persister had
                # committed at the time; with the final step durable,
                # this pass makes the retained set deterministic
                self._rotate_checkpoints(step)
            else:
                # the final step never became durable: rotating now
                # could delete the only restorable older step
                logger.warning(
                    "final checkpoint (step %d) not durable after "
                    "%.0fs (newest committed: %d); skipping rotation",
                    step, waited.waited_s, waited.persisted_step,
                )
        if args.load_best_model_at_end and self.state.best_step is not None:
            best = self.state.best_step
            if best != self.state.global_step:
                loaded = self._load_step(best, state)
                if loaded is None:
                    logger.warning(
                        "best-model reload failed (step %d not restorable);"
                        " keeping the final weights", best,
                    )
                else:
                    state = loaded
                    logger.info(
                        "loaded best model (step %d, %s=%.5g)", best,
                        args.metric_for_best_model, self.state.best_metric,
                    )
        return state

    def _load_step(self, step: int, template):
        """The pinned-step restore, or None when it can't be honored."""
        if not self.engine.replicated:
            logger.warning(
                "best-model reload needs the replicated engine"
            )
            return None
        # NB: a later step's commit also satisfies this wait — the pinned
        # load below is what actually verifies step N is on disk
        waited = self.engine.wait_for_persist(step)
        if not waited:
            logger.warning(
                "best-model step %d not durable after %.0fs; the "
                "pinned reload will likely fail", step, waited.waited_s,
            )
        shard_of = dict(_leaf_paths(self.compiled.state_shardings))
        loaded = self.engine.load(
            template,
            put=lambda name, arr: jax.device_put(arr, shard_of[name]),
            zero_copy=True,
            step=step,
        )
        return None if loaded is None else loaded[1]

    # ------------------------------------------------------------- checkpoints

    def _durable_save(self, step: int, state) -> bool:
        """save_to_storage with a bounded retry: the snapshot skips while
        the async persister holds the shm lock, and silently dropping a
        scheduled save would hand a restart an older step."""
        for _ in range(20):
            if self.engine.save_to_storage(step, state):
                return True
            time.sleep(0.25)
        logger.warning(
            "checkpoint at step %d dropped: persister busy for >5s", step
        )
        return False

    def _save_checkpoint(self, step: int, state) -> None:
        if not self._durable_save(step, state):
            return
        self._last_save_step = step
        with open(os.path.join(
                self.args.output_dir, "trainer_state.json"), "w") as f:
            f.write(self.state.to_json())
        self.callback_handler.fire(
            "on_save", self.args, self.state, self.control
        )
        self._rotate_checkpoints(step)

    def _persisted_steps(self) -> list[int]:
        steps = []
        for name in self.engine.storage.listdir(self.ckpt_dir):
            if name.startswith("step-"):
                try:
                    steps.append(int(name.split("-", 1)[1]))
                except ValueError:
                    continue
        return sorted(steps)

    def _rotate_checkpoints(self, current_step: int) -> None:
        """Delete oldest persisted checkpoints beyond save_total_limit.

        Never deletes: the best step (when best-model tracking is on), the
        tracker-committed step, or anything the async persister hasn't
        committed yet (a newer uncommitted dir isn't counted against the
        limit — deleting it would race the persister).
        """
        limit = self.args.save_total_limit
        if not limit or limit < 1:
            return
        committed = read_tracker(self.engine.storage, self.ckpt_dir)
        committed_step = committed[0] if committed else -1
        protected = {committed_step, current_step}
        if self.args.load_best_model_at_end and self.state.best_step:
            protected.add(self.state.best_step)
        all_steps = self._persisted_steps()
        # deletable: committed (persister is done with them) and unprotected
        deletable = [
            s for s in all_steps if s <= committed_step and s not in protected
        ]
        n_kept_always = len(all_steps) - len(deletable)
        allowed = max(0, limit - n_kept_always)
        drop = deletable[:len(deletable) - allowed] if allowed else deletable
        for s in drop:
            self.engine.storage.delete(step_dir(self.ckpt_dir, s))
            logger.info("rotated out checkpoint step %d", s)

    # ------------------------------------------------------------- evaluation

    def _build_eval_step(self):
        if self._eval_step_fn is not None:
            return self._eval_step_fn
        from jax.sharding import NamedSharding, PartitionSpec

        axes = batch_axes(self.mesh)
        spec = PartitionSpec(
            axes if len(axes) > 1 else (axes[0] if axes else None)
        )
        self._eval_batch_sharding = NamedSharding(self.mesh, spec)
        replicated = NamedSharding(self.mesh, PartitionSpec())
        loss_fn = self._eval_loss_fn
        metrics_fn = self.compute_metrics

        def _eval(params, batch):
            out = {"eval_loss": loss_fn(params, batch)}
            if metrics_fn is not None:
                out.update({
                    f"eval_{k}": v for k, v in metrics_fn(
                        params, batch).items()
                })
            return out

        self._eval_step_fn = jax.jit(
            _eval,
            in_shardings=(self.compiled.state_shardings.params,
                          self._eval_batch_sharding),
            out_shardings=replicated,
        )
        return self._eval_step_fn

    def _put_eval_batch(self, batch: dict) -> dict:
        sharding = self._eval_batch_sharding
        if self.elastic.num_processes > 1:
            return jax.tree.map(
                lambda x: jax.make_array_from_process_local_data(
                    sharding, np.ascontiguousarray(x),
                    (x.shape[0] * self.elastic.num_processes,)
                    + x.shape[1:],
                ),
                batch,
            )
        return jax.device_put(batch, sharding)

    def evaluate(self, eval_dataset: Iterable | None = None,
                 params: Any | None = None) -> dict[str, float]:
        """Mean metrics over the eval set (sharded forward, no grads)."""
        ds = eval_dataset if eval_dataset is not None else self.eval_dataset
        if ds is None:
            raise ValueError("no eval_dataset")
        if params is None:
            if self._train_state is None:
                raise ValueError("no params: train first or pass params")
            params = self._train_state.params
        eval_step = self._build_eval_step()
        local_bsz = self._eval_local_batch()
        per_batch: list = []
        # padding keeps the compiled shape; weighting is by batch, matching
        # the reference's drop_last=False mean
        for buf, _true in self._batched(self._sample_iter(ds), local_bsz):
            batch = self.collate_fn(buf)
            per_batch.append(eval_step(params, self._put_eval_batch(batch)))
        if not per_batch:
            return {}
        fetched = jax.device_get(per_batch)
        return {
            k: float(np.mean([m[k] for m in fetched])) for k in fetched[0]
        }

    def _evaluate_during_training(self, state) -> None:
        metrics = self.evaluate(params=state.params)
        self.state.log_history.append(
            {"step": self.state.global_step, **metrics})
        key = self.args.metric_for_best_model
        if key and key in metrics:
            value = metrics[key]
            sign = 1.0 if self.args.greater_is_better else -1.0
            if (self.state.best_metric is None
                    or sign * (value - self.state.best_metric) > 0):
                self.state.best_metric = value
                self.state.best_step = self.state.global_step
                if self.args.load_best_model_at_end:
                    # the best step must be durable to be reloadable
                    self._durable_save(self.state.global_step, state)
        self.callback_handler.fire(
            "on_evaluate", self.args, self.state, self.control,
            metrics=metrics,
        )

    def predict(self, dataset: Iterable,
                forward_fn: Callable[[Any, Any], Any],
                params: Any | None = None) -> list:
        """Run ``forward_fn(params, batch)`` over a dataset; returns host
        arrays per batch (the reference's Trainer.predict analog).
        Every process reads the FULL dataset (complete outputs
        everywhere; multi-process runs duplicate the forward work)."""
        if params is None:
            if self._train_state is None:
                raise ValueError("no params: train first or pass params")
            params = self._train_state.params
        self._build_eval_step()  # for the batch sharding
        fn = jax.jit(forward_fn)
        local_bsz = self._eval_local_batch()
        outs: list = []
        for buf, true in self._batched(
                self._sample_iter(dataset, shard=False),
                local_bsz):
            batch = self.collate_fn(buf)
            out = jax.device_get(fn(params, self._put_eval_batch(batch)))
            if true < local_bsz:
                # drop the padding rows so callers see len(dataset) outputs
                out = jax.tree.map(lambda x: x[:true], out)
            outs.append(out)
        return outs

    # ---------------------------------------------------------------- cleanup

    def close(self) -> None:
        if self._owns_engine:
            self.engine.close()
