"""High-level Trainer: loops, logging, eval, save policies, resume, best.

Reference analog: the AtorchTrainer surface
(atorch/atorch/trainer/atorch_trainer.py:129 — train/evaluate/save with
save_total_limit rotation, metric_for_best_model + load_best_model_at_end,
resume_from_checkpoint) exercised the way the reference's trainer tests do:
tiny model, synthetic data, assertions on host-side state.
"""

from __future__ import annotations

import json
import os

import numpy as np
import optax
import pytest

import jax

from dlrover_tpu.agent.ckpt_saver import read_tracker
from dlrover_tpu.common.storage import PosixDiskStorage
from dlrover_tpu.models import mlp
from dlrover_tpu.trainer.trainer import (
    EarlyStoppingCallback,
    Trainer,
    TrainerCallback,
    TrainingArguments,
)

SIZES = (8, 16, 4)


def _dataset(n=64, seed=0):
    rng = np.random.default_rng(seed)
    xs = rng.normal(size=(n, SIZES[0])).astype(np.float32)
    # learnable rule: class = argmax of 4 fixed random projections
    w = np.asarray(jax.random.normal(jax.random.PRNGKey(9), (SIZES[0], 4)))
    ys = np.argmax(xs @ w, axis=-1).astype(np.int32)
    return [{"x": xs[i], "y": ys[i]} for i in range(n)]


def _trainer(tmp_path, train_n=64, callbacks=None, model_flops_per_step=0.0,
             **arg_overrides):
    args = TrainingArguments(
        output_dir=str(tmp_path / "out"),
        global_batch_size=16,
        micro_batch_size=2,
        logging_steps=5,
        **arg_overrides,
    )
    return Trainer(
        args=args,
        optimizer=optax.adam(1e-2),
        init_params_fn=lambda rng: mlp.init_params(rng, SIZES),
        logical_params=mlp.logical_axes(SIZES),
        loss_fn=mlp.loss_fn,
        train_dataset=_dataset(train_n),
        eval_dataset=_dataset(32, seed=1),
        callbacks=callbacks,
        lr_schedule=lambda step: 1e-2,
        model_flops_per_step=model_flops_per_step,
    )


@pytest.mark.parametrize("flops", [0.0, 3.5e9])
def test_trainer_tells_the_mfu_gauge_its_models_flops(tmp_ipc_dir, tmp_path,
                                                      flops):
    """Only a trainer told its model's FLOPs publishes ``dlrover_tpu_mfu``;
    the executable's count goes to ``dlrover_tpu_hfu`` either way."""
    t = _trainer(tmp_path, model_flops_per_step=flops)
    try:
        assert t.elastic.efficiency.flops_per_step == flops
    finally:
        t.close()


@pytest.mark.timeout(120)
def test_train_logs_and_loss_decreases(tmp_ipc_dir, tmp_path):
    t = _trainer(tmp_path, max_steps=30)
    try:
        state = t.train()
        assert state.global_step == 30
        losses = [e["loss"] for e in state.log_history if "loss" in e]
        assert len(losses) >= 3
        assert losses[-1] < losses[0]
        tail = [e for e in state.log_history if "steps_per_sec" in e]
        assert tail and tail[-1]["learning_rate"] == pytest.approx(1e-2)
        # the default LoggingCallback mirrored history to a JSONL file
        log_file = os.path.join(t.args.output_dir, "log_history.jsonl")
        lines = [json.loads(x) for x in open(log_file)]
        assert lines and lines[0]["step"] == 1  # logging_first_step
    finally:
        t.close()


@pytest.mark.timeout(120)
def test_epoch_semantics_and_epoch_eval(tmp_ipc_dir, tmp_path):
    # 64 samples / global 16 = 4 steps per epoch; 2 epochs = 8 steps
    t = _trainer(tmp_path, num_train_epochs=2.0, eval_strategy="epoch")
    try:
        state = t.train()
        assert state.global_step == 8
        assert state.epoch == pytest.approx(2.0)
        evals = [e for e in state.log_history if "eval_loss" in e]
        assert len(evals) == 2  # one per epoch
    finally:
        t.close()


@pytest.mark.timeout(120)
def test_early_stopping_and_control_flow(tmp_ipc_dir, tmp_path):
    # threshold so high no improvement ever counts: first eval sets best,
    # second eval trips patience=1 -> stop at step 10
    cb = EarlyStoppingCallback(patience=1, threshold=1e9)
    t = _trainer(
        tmp_path, max_steps=100, eval_strategy="steps", eval_steps=5,
        metric_for_best_model="eval_loss", callbacks=[cb],
    )
    try:
        state = t.train()
        assert state.global_step == 10
    finally:
        t.close()


@pytest.mark.timeout(120)
def test_callback_can_stop_training(tmp_ipc_dir, tmp_path):
    class StopAt(TrainerCallback):
        def on_step_end(self, args, state, control, **kw):
            if state.global_step >= 7:
                control.should_training_stop = True

    t = _trainer(tmp_path, max_steps=50, callbacks=[StopAt()])
    try:
        assert t.train().global_step == 7
    finally:
        t.close()


@pytest.mark.timeout(180)
# slow tier (tier-1 envelope): among the heaviest bodies in this
# file on XLA:CPU; core behavior stays covered by the lighter
# tests in-tier. `pytest tests/` still runs it.
@pytest.mark.slow
def test_save_rotation_resume(tmp_ipc_dir, tmp_path):
    t = _trainer(
        tmp_path, max_steps=20, save_strategy="steps", save_steps=5,
        save_total_limit=2,
    )
    ckpt_dir = t.ckpt_dir
    try:
        t.train()
        assert t.engine.wait_for_persist(20)
        storage = PosixDiskStorage()
        committed = read_tracker(storage, ckpt_dir)
        assert committed is not None and committed[0] == 20
        kept = sorted(
            int(d.split("-")[1])
            for d in storage.listdir(ckpt_dir) if d.startswith("step-")
        )
        assert 20 in kept
        assert len(kept) <= 2
        assert 5 not in kept  # oldest rotated out
    finally:
        t.close()

    # resume: fresh Trainer on the same output_dir continues at step 20
    t2 = _trainer(tmp_path, max_steps=24, save_strategy="steps", save_steps=5)
    try:
        state = t2.train()
        assert state.global_step == 24
        # resumed history from trainer_state.json was preserved
        assert any(e["step"] <= 20 for e in state.log_history)
        assert int(t2._train_state.step) == 24
    finally:
        t2.close()


@pytest.mark.timeout(180)
def test_load_best_model_at_end(tmp_ipc_dir, tmp_path):
    # greater_is_better on eval_loss makes the FIRST eval (highest loss,
    # least-trained params) the "best" — so the reload at the end must
    # restore early-step weights, observable via a re-evaluation.
    t = _trainer(
        tmp_path, max_steps=20, eval_strategy="steps", eval_steps=5,
        save_strategy="steps", save_steps=5,
        metric_for_best_model="eval_loss", greater_is_better=True,
        load_best_model_at_end=True,
    )
    try:
        state = t.train()
        assert state.best_step == 5
        final = t.evaluate(params=t._train_state.params)
        assert final["eval_loss"] == pytest.approx(
            state.best_metric, rel=1e-4
        )
        # sanity: training really did reduce the loss past the "best"
        evals = [e["eval_loss"] for e in state.log_history
                 if "eval_loss" in e]
        assert min(evals) < state.best_metric
    finally:
        t.close()


def test_training_arguments_validation_and_roundtrip(tmp_path):
    with pytest.raises(ValueError):
        TrainingArguments(eval_strategy="steps")
    with pytest.raises(ValueError):
        TrainingArguments(save_strategy="steps")
    args = TrainingArguments(
        output_dir=str(tmp_path), save_strategy="steps", save_steps=3,
        load_best_model_at_end=True,
    )
    assert args.metric_for_best_model == "eval_loss"
    clone = TrainingArguments.from_json(args.to_json())
    assert clone == args


@pytest.mark.timeout(120)
def test_goodput_callback_writes_log(tmp_ipc_dir, tmp_path):
    from dlrover_tpu.trainer.trainer import GoodputCallback
    from dlrover_tpu.utils.goodput import compute_goodput

    log = str(tmp_path / "gp.jsonl")
    t = _trainer(tmp_path, max_steps=12,
                 callbacks=[GoodputCallback(log)])
    try:
        t.train()
    finally:
        t.close()
    report = compute_goodput(log)
    assert report.n_steps == 12
    assert report.n_incarnations == 1
    assert report.goodput > 0.5


@pytest.mark.timeout(570)
def test_strategy_auto_with_cache(tmp_ipc_dir, tmp_path):
    """strategy='auto': the Trainer runs the cached search (the
    load_strategy analog) and trains; a second Trainer on the same
    output_dir reuses the pick without re-searching."""
    import json
    import time

    def make(out):
        args = TrainingArguments(
            output_dir=str(out), global_batch_size=16,
            micro_batch_size=2, max_steps=3,
        )
        return Trainer(
            args=args,
            optimizer=optax.adam(1e-2),
            init_params_fn=lambda rng: mlp.init_params(rng, SIZES),
            logical_params=mlp.logical_axes(SIZES),
            loss_fn=mlp.loss_fn,  # plain form: auto wraps it itself
            train_dataset=_dataset(48),
            strategy="auto",
            # per-sample shapes; the Trainer derives [1, global, ...]
            example_batch={
                "x": np.zeros((SIZES[0],), np.float32),
                "y": np.zeros((), np.int32),
            },
        )

    out = tmp_path / "auto_out"
    t1 = make(out)
    t1.train()
    cache = json.load(open(out / "strategy.json"))
    assert cache["strategy"]["name"]
    t0 = time.monotonic()
    t2 = make(out)  # second construction must reload, not re-search
    assert time.monotonic() - t0 < 30, "auto search re-ran despite cache"
    assert t2.strategy.name == t1.strategy.name

    # missing example_batch is an error, not a silent dp fallback
    with pytest.raises(ValueError, match="example_batch"):
        Trainer(
            args=TrainingArguments(output_dir=str(tmp_path / "x"),
                                   global_batch_size=16, max_steps=1),
            optimizer=optax.adam(1e-2),
            init_params_fn=lambda rng: mlp.init_params(rng, SIZES),
            logical_params=mlp.logical_axes(SIZES),
            loss_fn=mlp.loss_fn,
            strategy="auto",
        )
