#!/usr/bin/env python
"""Round benchmark, staged: each stage runs under its own deadline and the
cumulative result JSON line is re-printed (flushed) after EVERY stage, so a
driver timeout can never zero out the round's evidence — the last complete
line on stdout is always a valid result (round-3 lesson: one overrunning
stage + single end-of-run print produced rc=124 / parsed=null and lost all
validated numbers).

Budget model: BENCH_BUDGET_S (default 1740 s) is a HARD envelope: a stage
only starts when the remaining budget covers its gate (the full per-stage
deadline, or min_deadline_s for the adaptive tail stages whose window
scales with the budget they are given), and its SIGALRM never exceeds the
remaining budget, so the run can never overshoot (r04: the est-based gate
let one stage overrun by 200 s and the driver's kill timer fired). A SIGALRM per-stage
deadline stops a wedged stage without killing the run; after every stage
the cumulative line AND a compact headline-only line are re-printed
(single atomic os.write), so any tail byte-window capture ends with a
complete, parseable headline line.

Headline metric: checkpoint save blocking time for a GPT-2-small-class
(~1.5 GB) train state, against the reference Flash Checkpoint bar of 0.5 s
(BASELINE.md: Megatron GPT-1.5B save 151 s -> 0.5 s on an A100 node).

Note on fidelity: the checkpoint numbers are measured on the host-side
snapshot path (numpy state -> shm arena memcpy + commit), with D2H
excluded and noted. No number from this file is on the ledger; ROADMAP
S1 replaces it with benchmark cells. Until then a chip stage that finds
no chip fails, nothing is measured at the ``tiny`` config under a chip
key name, and the run exits non-zero when any stage raised.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import os
import signal
import sys
import tempfile
import time

import numpy as np

from dlrover_tpu.utils.profiler import compiled_flops, device_peak_flops


def _require_tpu(stage: str) -> None:
    """A chip stage that finds no chip is an error, not a silent skip
    or a CPU number under the chip's key name."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"stage {stage} measures the chip; JAX found platform "
            f"{dev.platform!r}")

CKPT_SAVE_BASELINE_S = 0.5  # reference FCP blocking bar (BASELINE.md)


class StageTimeout(Exception):
    pass


def _recompute_factor(cfg) -> float:
    """Backward-recompute multiplier on model FLOPs for the hw-util
    estimate (fwd:bwd ~ 1:2; recomputed fraction f of a forward adds
    f/3 of total)."""
    if not cfg.remat_scan or cfg.remat_policy not in ("nothing", "full"):
        return 1.0  # dots saved: only elementwise recompute
    k = max(1, cfg.remat_interval)
    return 1.0 + (k - 1 if k > 1 else k) / (3.0 * k)


def _train_one(extra: dict, prefix: str, model: str, batch: int, seq: int,
               steps: int, cfg_overrides: dict,
               optimizer: str = "adamw") -> None:
    """Measure one training-step geometry on the live chip and record
    MFU/step-time under ``prefix``-ed keys. ``optimizer``: "adamw" or
    "adam8bit" (optimizers/low_bit.py — frees ~2/3 of the moment memory,
    which is what lets the medium geometry keep its dot activations)."""
    import jax
    import optax

    from dlrover_tpu.models import transformer as tfm
    from dlrover_tpu.parallel import strategy as strat_lib
    from dlrover_tpu.trainer.train_step import compile_train

    dev = jax.devices()[0]
    cfg = dataclasses.replace(tfm.CONFIGS[model], **cfg_overrides)
    seq = min(cfg.max_seq_len, seq)

    if optimizer == "adam8bit":
        from dlrover_tpu.optimizers import adam_8bit

        opt = adam_8bit(1e-4)
    else:
        opt = optax.adamw(1e-4)
    strat = strat_lib.dp()
    mesh = strat.build_mesh(jax.devices()[:1])
    # make_loss_fn, NOT a bare partial(loss_fn, cfg=...): the bare form
    # leaves attention_fn=None which silently falls back to dense — the
    # r01-r03 MFU numbers were all dense-attention numbers and
    # gpt2-medium at b32 OOMs outright on the materialized [B,H,S,S]
    # logits (23.2 GB vs 15.75 GB HBM, measured r04)
    compiled = compile_train(
        strategy=strat,
        mesh=mesh,
        loss_fn=tfm.make_loss_fn(cfg, strat, mesh),
        init_params_fn=lambda rng: tfm.init_params(cfg, rng),
        logical_params=tfm.logical_axes(cfg),
        optimizer=opt,
    )
    state = compiled.init(jax.random.PRNGKey(0))
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, batch, seq + 1), dtype=np.int32
    )
    step_batch = jax.device_put({"tokens": tokens}, compiled.batch_sharding)

    t0 = time.monotonic()
    state, metrics = compiled.step(state, step_batch)
    jax.block_until_ready(metrics["loss"])
    compile_s = time.monotonic() - t0
    for _ in range(2):  # warmup
        state, metrics = compiled.step(state, step_batch)
    jax.block_until_ready(metrics["loss"])

    t0 = time.monotonic()
    for _ in range(steps):
        state, metrics = compiled.step(state, step_batch)
    loss = float(jax.block_until_ready(metrics["loss"]))
    step_s = (time.monotonic() - t0) / steps

    n_params = cfg.param_count
    tokens_per_step = batch * seq
    # PaLM-style accounting: 6N per token + attention 12*L*S*d per token.
    # MFU uses this model-FLOPs number (excludes remat recompute); the
    # compiled count from XLA's cost analysis rides along for hardware
    # utilization.
    flops_per_token = 6 * n_params + 12 * cfg.n_layers * seq * cfg.d_model
    flops_per_step = flops_per_token * tokens_per_step
    xla_flops = compiled_flops(compiled.step, state, step_batch)
    peak = device_peak_flops(dev)
    on_tpu = dev.platform == "tpu"
    extra.update({
        f"{prefix}model": model,
        f"{prefix}n_params": n_params,
        f"{prefix}batch": batch,
        f"{prefix}seq": seq,
        f"{prefix}compile_s": round(compile_s, 2),
        f"{prefix}step_time_s": round(step_s, 4),
        f"{prefix}tokens_per_s": round(tokens_per_step / step_s),
        f"{prefix}tflops_per_s": round(flops_per_step / step_s / 1e12, 1),
        f"{prefix}mfu":
            round(flops_per_step / step_s / peak, 4) if peak else None,
        # model-FLOPs MFU understates device work under activation
        # remat; the recompute factor depends on the policy: full
        # recompute re-runs ~a forward (4/3 total), interleaved
        # remat_interval=k re-runs (k-1)/k of one (1 + (k-1)/(3k)), and
        # dots-saved policies recompute only elementwise ops (~1).
        f"{prefix}mfu_hw_est": (
            round(flops_per_step * _recompute_factor(cfg) / step_s
                  / peak, 4)
            if peak and on_tpu else None),
        # raw XLA cost analysis; undercounts lax.scan/while bodies, so it
        # is NOT a utilization figure — recorded for cross-round tracking
        f"{prefix}xla_cost_analysis_flops": xla_flops,
        f"{prefix}loss": round(loss, 4),
    })
    extra["device"] = dev.device_kind

    # live-gauge agreement (DESIGN.md §18 acceptance): drive the
    # efficiency monitor with the SAME model-FLOPs number and measured
    # step times a live trainer would see, then read the
    # dlrover_tpu_mfu gauge back — proving the gauge plumbing (labels,
    # rolling window, registry) reproduces the bench headline
    if peak:
        from dlrover_tpu.telemetry.efficiency import (
            EfficiencyMonitor,
            live_mfu,
        )

        mon = EfficiencyMonitor(
            model=model, strategy="dp", flops_per_step=flops_per_step,
            peak_flops=peak, num_devices=1, journal_every=0,
        )
        for i in range(1, steps + 1):
            mon.end_step(i, step_s)
        live = live_mfu(model, "dp")
        bench_mfu = extra.get(f"{prefix}mfu")
        extra[f"{prefix}mfu_live"] = (round(live, 4)
                                      if live is not None else None)
        extra[f"{prefix}mfu_live_agree"] = (
            abs(live - bench_mfu) <= 0.10 * bench_mfu
            if live is not None and bench_mfu else None
        )


def _mpmd_leg(extra: dict, prefix: str, model: str, batch: int, seq: int,
              steps: int = 3, stages: int = 2, microbatches: int = 4
              ) -> None:
    """MPMD pipeline rider beside the MFU headline (DESIGN.md §21):
    build the per-stage runtime, run a few steps, and report the
    measured 1F1B schedule bubble against its bound plus the per-stage
    compile and ZeRO optimizer-sharding evidence. Needs >= ``stages``
    devices (on the single-chip TPU bench host only the bound is
    emitted)."""
    import dataclasses as _dc

    import jax
    import optax

    from dlrover_tpu.models import transformer as tfm
    from dlrover_tpu.parallel import strategy as strat_lib
    from dlrover_tpu.parallel.pipeline import bubble_fraction

    cfg = tfm.CONFIGS[model]
    extra[f"{prefix}bubble_frac_bound"] = round(
        bubble_fraction(stages, microbatches), 4)
    if len(jax.devices()) < stages:
        extra[f"{prefix}mpmd_note"] = (
            f"measured leg needs >= {stages} devices; bound only"
        )
        return
    from dlrover_tpu.parallel.mpmd import MpmdTrain

    cfg = _dc.replace(cfg, dtype="float32")
    seq = min(cfg.max_seq_len, seq)
    per = len(jax.devices()) // stages
    step_batch = microbatches * per * max(
        1, batch // (microbatches * per))
    mt = MpmdTrain(
        cfg, strat_lib.mpmd(stages), optax.adamw(1e-4),
        num_stages=stages, microbatches=microbatches, seq=seq,
        step_batch=step_batch,
    )
    state = mt.init(jax.random.PRNGKey(0))
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, step_batch, seq + 1), dtype=np.int32
    )
    batch_dev = jax.device_put({"tokens": tokens}, mt.batch_sharding)
    losses = []
    t0 = time.monotonic()
    for _ in range(steps):
        state, metrics = mt.step(state, batch_dev)
        losses.append(float(jax.device_get(metrics["loss"])))
    step_s = (time.monotonic() - t0) / steps
    by0 = mt.opt_bytes[0]
    extra.update({
        f"{prefix}bubble_frac": round(mt.last_bubble_frac, 4),
        f"{prefix}bubble_le_bound":
            mt.last_bubble_frac <= mt.bubble_bound + 1e-9,
        f"{prefix}stage_compile_s": round(
            max(p.compile_seconds for p in mt.stages), 2),
        f"{prefix}stage_compile_warm":
            bool(mt.cache_hit),
        f"{prefix}mpmd_step_time_s": round(step_s, 4),
        f"{prefix}mpmd_loss": round(losses[-1], 4),
        # ZeRO weight-update sharding evidence: optimizer bytes per
        # device, sharded vs replicated counterfactual
        f"{prefix}opt_bytes_sharded": by0["sharded"],
        f"{prefix}opt_bytes_replicated": by0["replicated"],
    })


def _stage_recompile_leg(extra: dict) -> None:
    """Per-stage recompile evidence beside the goodput headline
    (DESIGN.md §21): cold-build the MPMD stage programs into a
    hermetic cache, evict ONE stage's artifacts (= that stage's
    replacement trainer lost its local cache), rebuild, and assert the
    journal shows cold ``pipeline_stage_compile`` entries for exactly
    that stage while the other P−1 hit the cache."""
    import dataclasses as _dc
    import json as _json

    import jax
    import optax

    if len(jax.devices()) < 2:
        extra["goodput_stage_recompile_note"] = "needs >= 2 devices"
        return
    import glob as _glob

    from dlrover_tpu.models import transformer as tfm
    from dlrover_tpu.parallel import compile_cache as cc
    from dlrover_tpu.parallel import strategy as strat_lib
    from dlrover_tpu.parallel.mpmd import MpmdTrain

    cfg = _dc.replace(tfm.CONFIGS["tiny"], n_layers=4, dtype="float32")
    work = tempfile.mkdtemp(prefix="bench_mpmd_recompile_")
    old_journal = os.environ.get("DLROVER_TPU_JOURNAL_DIR")
    os.environ["DLROVER_TPU_JOURNAL_DIR"] = os.path.join(work, "jr")
    # the stage artifacts live in the one compile cache; a cold build
    # starts from none of them
    for f in _glob.glob(os.path.join(cc.default_local_dir(), "*pp?of2*")):
        os.unlink(f)
    try:
        def build():
            t0 = time.monotonic()
            mt = MpmdTrain(
                cfg, strat_lib.mpmd(2), optax.sgd(1e-2), num_stages=2,
                microbatches=4, seq=32, step_batch=16,
            )
            return mt, time.monotonic() - t0

        _, cold_s = build()
        n_events = sum(1 for _ in open(
            os.path.join(work, "jr", "events.jsonl")))
        for f in _glob.glob(
                os.path.join(cc.default_local_dir(), "*pp0of2*")):
            os.unlink(f)
        mt, rebuild_s = build()
        events = [
            _json.loads(line) for line in open(
                os.path.join(work, "jr", "events.jsonl"))
        ][n_events:]
        events = [e for e in events
                  if e["name"] == "pipeline_stage_compile"]
        cold_stages = sorted({e["stage"] for e in events
                              if not e["hit"]})
        warm_stages = sorted({e["stage"] for e in events if e["hit"]})
        extra.update({
            "goodput_stage_cold_build_s": round(cold_s, 2),
            "goodput_stage_rebuild_s": round(rebuild_s, 2),
            "goodput_stage_recompile_cold_stages": cold_stages,
            "goodput_stage_recompile_warm_stages": warm_stages,
            # THE assertion: a one-stage failure recompiles one stage
            "goodput_stage_recompile_only_failed":
                cold_stages == [0] and warm_stages == [1],
        })
    finally:
        if old_journal is None:
            os.environ.pop("DLROVER_TPU_JOURNAL_DIR", None)
        else:
            os.environ["DLROVER_TPU_JOURNAL_DIR"] = old_journal


def bench_train_step(extra: dict) -> None:
    """Training MFU. Headline geometry is gpt2-medium (d_model=1024 —
    compute-bound on the MXU: bf16 matmul chains reach 0.76+ utilization
    there vs 0.58-0.64 at gpt2-small's d_model=768, examples/mfu_probe.py);
    gpt2-small rides along as the bandwidth-bound secondary for
    cross-round comparability (r02 0.382, r03 0.393)."""
    _require_tpu("mfu")

    # Headline FIRST so a stage deadline can only cost the secondary.
    # Config from the r04 on-chip sweep (17 candidates): b24 +
    # interleaved remat (remat_interval=2: only every other layer
    # recomputes in backward) + dots_no_batch for the rematted ones +
    # splash + 16-chunk CE + 8-bit Adam (the int8 moments are what buy
    # the headroom: f32 AdamW OOMs every >=0.5-class config). Sweep
    # landmarks: b32 full-recompute 0.437 (adamw) / 0.455 (8-bit),
    # b16 int2 0.485, b16 int2+dots 0.513, b24 int2 0.517,
    # b24 int2+dots 0.520 (pick); b32 int2 0.510, every dots config
    # >=b32 and all f32-Adam variants OOM (16.1-30.3G vs 15.75G).
    medium_err = None
    try:
        _train_one(
            extra, "medium_", "gpt2-medium",
            batch=int(os.environ.get("BENCH_MEDIUM_BATCH", "24")),
            seq=int(os.environ.get("BENCH_SEQ", "1024")),
            steps=int(os.environ.get("BENCH_MEDIUM_STEPS", "20")),
            cfg_overrides=dict(
                remat_scan=True, remat_policy="dots_no_batch",
                remat_interval=2, attention="splash", ce_chunks=16,
                scan_unroll=int(os.environ.get("BENCH_MEDIUM_UNROLL",
                                               "8")),
            ),
            optimizer="adam8bit",
        )
        extra["mfu_medium"] = extra.get("medium_mfu")
    except Exception as e:  # noqa: BLE001 - keep the secondary alive
        medium_err = f"{type(e).__name__}: {e}"
        extra["mfu_medium_error"] = medium_err[:300]

    # gpt2-large third geometry (r04 Weak #5: 0.434 with b12 + full
    # recompute). The r05 19-config on-chip sweep: full recompute
    # scales b12 0.430 -> b16 0.457 -> b24 0.480 -> b32 0.488-0.491
    # (ce_chunks=32), regresses at b40 and OOMs the compile at b48+;
    # every activation-saving policy (save_attn / save_attn_ffn /
    # dots / interleaved) exceeds HBM at the viable batches, and
    # offload_attn_ffn compiled only for tiny configs in round 5.
    # b32+ce32 is the measured peak —
    # 0.49 model-FLOPs MFU == ~0.65 hardware utilization with the 4/3
    # full-recompute factor. Config is env-pinned; errors must not
    # cost the small/medium numbers.
    if os.environ.get("BENCH_LARGE", "1") != "0":
        try:
            overrides = dict(
                remat_scan=True,
                remat_policy=os.environ.get("BENCH_LARGE_POLICY", "full"),
                attention="splash",
                ce_chunks=int(os.environ.get("BENCH_LARGE_CE", "32")),
                scan_unroll=int(os.environ.get("BENCH_LARGE_UNROLL",
                                               "4")),
            )
            interval = int(os.environ.get("BENCH_LARGE_INTERVAL", "1"))
            if interval > 1:
                overrides["remat_interval"] = interval
            _train_one(
                extra, "large_", "gpt2-large",
                batch=int(os.environ.get("BENCH_LARGE_BATCH", "32")),
                seq=int(os.environ.get("BENCH_SEQ", "1024")),
                steps=int(os.environ.get("BENCH_LARGE_STEPS", "10")),
                cfg_overrides=overrides,
                optimizer="adam8bit",
            )
            extra["mfu_large"] = extra.get("large_mfu")
        except Exception as e:  # noqa: BLE001 - rider geometry
            extra["mfu_large_error"] = f"{type(e).__name__}: {e}"[:300]
        try:
            # MPMD schedule evidence beside the large headline (the
            # single-chip bench host emits the 1F1B bound; multi-chip
            # hosts run the measured leg)
            _mpmd_leg(extra, "large_", "gpt2-large",
                      batch=int(os.environ.get("BENCH_LARGE_BATCH",
                                               "32")),
                      seq=int(os.environ.get("BENCH_SEQ", "1024")))
        except Exception as e:  # noqa: BLE001 - rider leg
            extra["large_mpmd_error"] = f"{type(e).__name__}: {e}"[:300]

    # gpt2-small secondary. NOTE: the r03 "bandwidth-bound ceiling"
    # analysis (0.393 MFU, ~85% of the d_model=768 matmul roofline) was
    # measured with attention silently DENSE (the bare-loss_fn bug fixed
    # above); with splash actually engaged the same geometry measures
    # 0.61 MFU (r04) — the dense [B,H,S,S] logit traffic, not d_model,
    # was the ceiling.
    _train_one(
        extra, "", os.environ.get("BENCH_MODEL", "gpt2-small"),
        batch=int(os.environ.get("BENCH_BATCH", "32")),
        seq=int(os.environ.get("BENCH_SEQ", "1024")),
        steps=int(os.environ.get("BENCH_STEPS", "30")),
        cfg_overrides=dict(
            remat_scan=True, remat_policy="dots_no_batch",
            attention="splash", ce_chunks=16, scan_unroll=12,
        ),
    )
    if medium_err:
        raise RuntimeError(f"medium geometry failed: {medium_err}")


def bench_long_context(extra: dict) -> None:
    """gpt2-small @ 4k tokens: Pallas flash attention without remat vs the
    best dense config (dense needs per-layer remat to fit at all)."""
    import jax
    import optax

    from dlrover_tpu.models import transformer as tfm
    from dlrover_tpu.parallel import strategy as strat_lib
    from dlrover_tpu.trainer.train_step import compile_train

    _require_tpu("long_context")
    seq = int(os.environ.get("BENCH_LC_SEQ", "4096"))
    batch = int(os.environ.get("BENCH_LC_BATCH", "2"))
    steps = int(os.environ.get("BENCH_LC_STEPS", "10"))

    def run(attention: str, remat: bool, window: int = 0) -> float:
        cfg = dataclasses.replace(
            tfm.CONFIGS["gpt2-small"], remat_scan=remat,
            attention=attention, max_seq_len=seq,
            attention_window=window,
        )
        strat = strat_lib.dp()
        mesh = strat.build_mesh(jax.devices()[:1])
        compiled = compile_train(
            strategy=strat, mesh=mesh,
            loss_fn=tfm.make_loss_fn(cfg, strat, mesh),
            init_params_fn=lambda rng: tfm.init_params(cfg, rng),
            logical_params=tfm.logical_axes(cfg),
            optimizer=optax.adamw(1e-4),
        )
        state = compiled.init(jax.random.PRNGKey(0))
        tokens = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (1, batch, seq + 1), dtype=np.int32
        )
        b = jax.device_put({"tokens": tokens}, compiled.batch_sharding)
        state, m = compiled.step(state, b)
        float(jax.device_get(m["loss"]))
        t0 = time.monotonic()
        for _ in range(steps):
            state, m = compiled.step(state, b)
        float(jax.device_get(m["loss"]))
        return (time.monotonic() - t0) / steps

    # flash first and unconditionally: the headline numbers must survive
    # a failure in the other kernels (dense barely fits at this seq)
    flash_s = run("flash", False)
    best_s = flash_s
    extra.update(
        lc_seq=seq,
        lc_flash_step_s=round(flash_s, 4),
        lc_flash_tokens_per_s=round(batch * seq / flash_s),
    )
    try:
        splash_s = run("splash", False)
        best_s = min(flash_s, splash_s)
        extra["lc_splash_step_s"] = round(splash_s, 4)
    except Exception as e:  # noqa: BLE001 - splash is optional
        extra["lc_splash_error"] = f"{type(e).__name__}"
    try:
        window_s = run("splash", False, window=seq // 4)
        extra["lc_window_step_s"] = round(window_s, 4)
    except Exception as e:  # noqa: BLE001 - window entry is optional
        extra["lc_window_error"] = f"{type(e).__name__}"
    extra["lc_best_tokens_per_s"] = round(batch * seq / best_s)
    try:
        dense_s = run("dense", True)
        extra.update(
            lc_dense_remat_step_s=round(dense_s, 4),
            lc_flash_speedup=round(dense_s / flash_s, 2),
            lc_best_speedup=round(dense_s / best_s, 2),
        )
    except Exception as e:  # noqa: BLE001 - baseline is optional
        extra["lc_dense_error"] = f"{type(e).__name__}"


def _disk_bw_probe(dir_path: str, mb: int = 128) -> float:
    """Measured sequential write bandwidth (GB/s) incl. fsync — the
    disk-leg sizes are derived from THIS, so a slow or full /tmp can
    never push the stage into its SIGALRM (r04 lesson: the 12 GB persist
    + cold-restore legs at ~0.2 GB/s burned the whole 600 s deadline)."""
    path = os.path.join(dir_path, "bw_probe.bin")
    chunk = os.urandom(1 << 20)
    t0 = time.monotonic()
    try:
        with open(path, "wb") as f:
            for _ in range(mb):
                f.write(chunk)
            f.flush()
            os.fsync(f.fileno())
        dt = time.monotonic() - t0
    finally:
        try:
            os.remove(path)
        except OSError:
            pass
    return (mb / 1024) / max(dt, 1e-6)


def bench_checkpoint(extra: dict, gb: float | None = None,
                     prefix: str = "ckpt_") -> None:
    """Host-side snapshot/restore path. Default ~1.5 GB GPT-2-small-class
    state; called again with ``gb`` ~12 for the 1B-param config
    (BASELINE configs 2-3; reference flash_checkpoint.md GPT-2 1.5B).

    Save-block headline: for the big state the engine's COW (fork)
    snapshot is the production mode — blocking cost is the fork, the
    child does the arena memcpy (this host has ONE core, so the direct
    path is memcpy-roofline-bound at ~7 GB/s and the reference's
    per-shard threadpool answer cannot apply). The direct number is
    reported alongside for honesty, as is the child's copy wall time.

    Disk legs are sized from a measured bandwidth probe and extrapolated
    to the full state when capped, so they can't blow the stage deadline.
    """
    os.environ.setdefault("DLROVER_TPU_IPC_DIR",
                          tempfile.mkdtemp(prefix="bench_ipc_"))
    from dlrover_tpu.checkpoint.engine import CheckpointEngine

    if gb is None:
        gb = float(os.environ.get("BENCH_CKPT_GB", "1.5"))
    n = int(gb * (1 << 30) / 12)  # params + adam mu/nu, fp32
    # distinct resident pages are what the timing needs; arange-based
    # fills build them ~4x faster than standard_normal on this one-core
    # host (the 12 GB variant was spending ~50 s of its stage deadline
    # just generating random numbers)
    base = np.arange(n, dtype=np.float32)
    state = {
        "params": {"w": base},
        "mu": {"w": base * 0.5 + 1.0},
        "nu": {"w": base * 0.25 + 2.0},
    }
    state_gb = 3 * n * 4 / (1 << 30)
    big = state_gb >= 4.0

    ckpt_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
    engine = CheckpointEngine(ckpt_dir, node_id=int(os.getpid()) % 100000)
    # each leg lands in `extra` AS MEASURED: a stage deadline hitting
    # the slow tail must keep the numbers already taken, not void the
    # stage (the r04 second rehearsal lost ckpt1b exactly that way)
    extra[f"{prefix}state_gb"] = round(state_gb, 2)
    sub_engine = None
    sub_dir = None
    try:
        engine.snapshot_mode = "direct"
        t0 = time.monotonic()
        engine.save_to_memory(1, state)  # warmup: arena creation+faults
        warm_s = time.monotonic() - t0
        direct_reps = 1 if big else 3
        direct_times = []
        for i in range(direct_reps):
            t0 = time.monotonic()
            ok = engine.save_to_memory(2 + i, state)
            direct_times.append(time.monotonic() - t0)
            assert ok
        direct_s = sorted(direct_times)[len(direct_times) // 2]
        step = 2 + direct_reps - 1
        # COW (fork) saves: blocking = fork; child copy rides along
        engine.snapshot_mode = "cow"
        cow_times, copy_times = [], []
        for i in range(3):
            engine.wait_snapshot(timeout=120)  # prior child, untimed —
            # matches production cadence (training steps between saves)
            t0 = time.monotonic()
            ok = engine.save_to_memory(step + 1 + i, state)
            cow_times.append(time.monotonic() - t0)
            assert ok
            engine.wait_snapshot(timeout=120)
            copy_times.append(engine.last_snapshot_info.get("copy_s"))
        step = step + 3
        cow_s = sorted(cow_times)[1]
        copies = [c for c in copy_times if c is not None]
        # the BIG state's headline is the COW path (production mode for
        # states whose direct copy would block >0.5 s); the small state
        # keeps the direct path as its cross-round-comparable headline
        extra[f"{prefix}save_block_s"] = round(cow_s if big else direct_s,
                                               3)
        extra[f"{prefix}save_block_direct_s"] = round(direct_s, 3)
        extra[f"{prefix}save_block_cow_s"] = round(cow_s, 4)
        if copies:
            extra[f"{prefix}copy_s"] = round(sorted(copies)[1], 3)
        extra[f"{prefix}arena_warmup_s"] = round(warm_s, 3)
        engine.snapshot_mode = "direct"

        # the production restore path (what examples/train_transformer.py
        # runs): zero-copy arena views handed straight to the consumer
        # (device_put with target shardings in the real flow; a full
        # read stands in for it here)
        restore_times = []
        for _ in range(3):
            t0 = time.monotonic()
            loaded = engine.load(state, put=lambda _n, a: a.sum(),
                                 zero_copy=True)
            restore_times.append(time.monotonic() - t0)
            assert loaded is not None and loaded[0] == step
        extra[f"{prefix}restore_s"] = round(sorted(restore_times)[1], 3)

        # host-side materialization (np consumers); rides along —
        # dominated by destination page faults, not the snapshot read
        # (the zero-copy view path above reads the same arena at
        # ~6.6 GB/s; the np.array materialize crawls at ~0.06 GB/s on
        # this host — measured r04 AND r05, so on the big state the
        # leg times a bounded arena-view slice and extrapolates
        # rather than paying the full 60+ s inside the deadline).
        if big:
            m_n = int(1.5 * (1 << 30) / 4)
            snap = engine.shm_handler.load_arrays(copy=False)
            assert snap is not None and snap[0] == step
            t0 = time.monotonic()
            mat = np.array(snap[1]["params/w"][:m_n])
            mat_s = time.monotonic() - t0
            mat_gb = m_n * 4 / (1 << 30)
            np.testing.assert_array_equal(
                mat[:1024], state["params"]["w"][:1024])
            del mat, snap
            extra[f"{prefix}restore_copy_full_est_s"] = round(
                mat_s * state_gb / mat_gb, 1)
        else:
            t0 = time.monotonic()
            loaded = engine.load(state)
            mat_s = time.monotonic() - t0
            mat_gb = state_gb
            assert loaded is not None and loaded[0] == step
            np.testing.assert_array_equal(
                loaded[1]["params"]["w"][:1024],
                state["params"]["w"][:1024])
            del loaded
        extra[f"{prefix}restore_copy_s"] = round(mat_s, 3)
        extra[f"{prefix}restore_copy_gb"] = round(mat_gb, 2)

        # ---- disk legs, sized by measured bandwidth ----
        disk_bw = _disk_bw_probe(ckpt_dir)
        extra[f"{prefix}disk_write_gbps"] = round(disk_bw, 3)
        # the 128 MB probe overestimates sustained /tmp bandwidth ~8x
        # (page-cache burst vs the 0.06 GB/s a 4 GB persist measured),
        # so the hard 1.5 GB ceiling, not the probe, is the real cap
        cap_s = float(os.environ.get("BENCH_PERSIST_CAP_S", "25"))
        persist_gb = min(state_gb, max(0.5, disk_bw * cap_s * 0.9), 1.5)
        if persist_gb >= state_gb * 0.95:
            p_engine, p_state, p_gb = engine, state, state_gb
            p_step = step
        else:
            # subsampled state on its own engine/dir; extrapolate
            m = int(persist_gb * (1 << 30) / 12)
            p_state = {k: {"w": v["w"][:m]} for k, v in state.items()}
            p_gb = 3 * m * 4 / (1 << 30)
            sub_dir = tempfile.mkdtemp(prefix="bench_ckpt_sub_")
            sub_engine = CheckpointEngine(
                sub_dir, node_id=(int(os.getpid()) + 1) % 100000)
            p_engine = sub_engine
            p_engine.save_to_memory(1, p_state)
            p_step = 1
            extra[f"{prefix}persist_capped_gb"] = round(p_gb, 2)
        t0 = time.monotonic()
        p_engine.save_to_storage(p_step + 1, p_state)
        persisted = p_engine.wait_for_persist(
            p_step + 1, timeout=max(60, cap_s * 3))
        p_s = time.monotonic() - t0
        extra[f"{prefix}persist_async_s"] = (
            round(p_s, 2) if persisted else None)
        if persisted and p_gb < state_gb * 0.95:
            extra[f"{prefix}persist_async_full_est_s"] = round(
                p_s * state_gb / p_gb, 1)

        # cold storage restore: the path a REAL preemption runs (fresh
        # host: no shm). Drop the shm header so load() takes the storage
        # branch (round-2 Weak #6: this leg was never measured).
        if persisted:
            p_engine.shm_handler.clear()
            t0 = time.monotonic()
            loaded = p_engine.load(p_state)
            cold_s = time.monotonic() - t0
            extra[f"{prefix}cold_storage_restore_s"] = round(cold_s, 2)
            if p_gb < state_gb * 0.95:
                extra[f"{prefix}cold_storage_restore_full_est_s"] = round(
                    cold_s * state_gb / p_gb, 1)
            assert loaded is not None and loaded[0] == p_step + 1
            np.testing.assert_array_equal(
                loaded[1]["params"]["w"][:1024],
                p_state["params"]["w"][:1024]
            )

        # ---- sharded parallel persist + topology-change restore ----
        # (DESIGN.md §20): N simulated hosts each persist only their
        # own slice through the chunked parallel writer, then M=N-1
        # fresh hosts reassemble — the save@N / restore@N-1 leg the
        # elastic shrink runs. Reported beside the single-writer
        # numbers above; the acceptance bar is that these do NOT grow
        # with host count (each host touches 1/N of the state).
        _bench_sharded_parallel(extra, p_state, prefix)
    finally:
        # the 12 GB variant leaves its weight in /tmp otherwise — six
        # stale runs filled the disk to 100% during r04 and slowed the
        # very persist leg this stage measures. Nested finally: the
        # stage alarm can fire INSIDE engine.close()'s bounded waits,
        # and the rmtree must survive that too.
        import shutil

        try:
            try:
                # UNLINK the arenas, not just close: the segments are
                # pid-keyed and deliberately survive process death (the
                # restart-in-place design), so every bench run would
                # otherwise leak its arena in /dev/shm — four stale
                # 12 GB arenas (103 GB of tmpfs) from r04/r05 runs were
                # exactly the "memory pressure" starving later stages
                engine.wait_snapshot(timeout=60)
                engine.shm_handler.close(unlink=True)
                engine.close()
            finally:
                if sub_engine is not None:
                    sub_engine.shm_handler.close(unlink=True)
                    sub_engine.close()
        finally:
            shutil.rmtree(ckpt_dir, ignore_errors=True)
            if sub_dir:
                shutil.rmtree(sub_dir, ignore_errors=True)
    if prefix == "ckpt_":
        extra["ckpt_note"] = (
            "host-side snapshot path; D2H excluded. ckpt_restore_s "
            "times the production zero-copy view path; "
            "cold_storage_restore_s is the fresh-host storage read; "
            "save_block headline = direct copy (small state) / COW fork "
            "(big state), both reported"
        )


def _bench_sharded_parallel(extra: dict, state: dict, prefix: str,
                            hosts: int = 4) -> None:
    """Save@N / restore@N−1 through the §20 sharded path.

    Each simulated host owns a contiguous 1/N row range of every leaf
    (replica 0, persist-flagged), snapshots it, and persists through
    its own solo saver — all N persists run concurrently, as N real
    agents would. The restore wall time is M=N−1 hosts concurrently
    assembling THEIR new (wider) slices from the committed step's piece
    registry, verified bit-exact against the source.
    """
    import threading

    from dlrover_tpu.checkpoint.integrity import resolve_restore_plan
    from dlrover_tpu.checkpoint.sharded import (
        ShardedCheckpointEngine,
        assemble,
        storage_piece_registry,
    )
    from dlrover_tpu.common.storage import PosixDiskStorage

    shard_dir = tempfile.mkdtemp(prefix="bench_ckpt_shard_")
    leaves = {f"{k}/w": v["w"] for k, v in state.items()}
    n = len(next(iter(leaves.values())))
    bounds = [round(n * i / hosts) for i in range(hosts + 1)]
    base_id = (int(os.getpid()) + 10) % 100000
    engines = []
    try:
        engines = [
            ShardedCheckpointEngine(
                shard_dir, node_id=base_id + i, node_rank=i,
                world_size=hosts,
            )
            for i in range(hosts)
        ]
        for i, eng in enumerate(engines):
            pieces, index = {}, {}
            for name, arr in leaves.items():
                key = f"{name}::p0"
                pieces[key] = arr[bounds[i]:bounds[i + 1]]
                index[key] = {
                    "path": name, "global_shape": [n],
                    "dtype": str(arr.dtype),
                    "index": [[bounds[i], bounds[i + 1]]],
                    "replica": 0, "persist": True,
                }
            eng.snapshot_pieces(1, pieces, index)

        def _persist(i: int) -> None:
            eng = engines[i]
            eng._solo_saver._persist_step(
                1, commit_block_s=60.0 if i == 0 else 0.0
            )

        t0 = time.monotonic()
        threads = [threading.Thread(target=_persist, args=(i,))
                   for i in range(hosts)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        extra[f"{prefix}persist_parallel_s"] = round(
            time.monotonic() - t0, 2)

        storage = PosixDiskStorage()
        plan = resolve_restore_plan(storage, shard_dir)
        assert plan is not None and plan.step == 1, plan
        m = hosts - 1
        new_bounds = [round(n * j / m) for j in range(m + 1)]
        outs: list[dict] = [{} for _ in range(m)]

        def _restore(j: int) -> None:
            registry = storage_piece_registry(
                storage, shard_dir, plan.step, plan.num_shards,
                bad_pieces=plan.bad_pieces,
            )
            for name in leaves:
                outs[j][name] = assemble(
                    [[new_bounds[j], new_bounds[j + 1]]],
                    np.dtype(leaves[name].dtype), registry[name],
                )

        t0 = time.monotonic()
        threads = [threading.Thread(target=_restore, args=(j,))
                   for j in range(m)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        extra[f"{prefix}restore_parallel_s"] = round(
            time.monotonic() - t0, 2)
        # topology-change bit-exactness: N-host save == (N-1)-host view
        got = np.concatenate([outs[j]["params/w"] for j in range(m)])
        np.testing.assert_array_equal(got[:4096],
                                      leaves["params/w"][:4096])
        np.testing.assert_array_equal(got[-4096:],
                                      leaves["params/w"][-4096:])
        extra[f"{prefix}shard_hosts"] = hosts
    finally:
        import shutil

        for eng in engines:
            try:
                eng.shm_handler.close(unlink=True)
                eng.close()
            except Exception:  # noqa: BLE001 - cleanup best-effort
                pass
        shutil.rmtree(shard_dir, ignore_errors=True)


def _run_elastic_job(work: str, env: dict, train_args: list[str],
                     max_steps: int, kills: int, deadline_s: float,
                     example: str) -> tuple[int, str, int, float, float]:
    """Run the example under ``dlrover_tpu.run --standalone``, SIGKILLing
    the trainer ``kills`` times at evenly-spaced step thresholds.
    Returns (exit_code, tail, kills_done, t_launch, t_exit)."""
    import signal as _signal
    import subprocess

    repo = os.path.dirname(os.path.abspath(__file__))
    log = os.path.join(work, "goodput.jsonl")
    job_log = os.path.join(work, "job.log")
    t_launch = time.time()
    # stdout to a FILE, not a pipe: nobody drains a pipe during the run,
    # and a full 64KB pipe buffer blocks every child's write — the whole
    # elastic job wedges mid-scenario (seen in verification). Own
    # session so a deadline overrun kills the whole tree with one killpg.
    log_f = open(job_log, "wb")
    proc = subprocess.Popen(
        [sys.executable, "-m", "dlrover_tpu.run", "--standalone",
         "--max-restarts", str(kills + 2), "--monitor-interval", "0.3",
         example, "--", *train_args, "--max-steps", str(max_steps)],
        env=env, cwd=repo, stdout=log_f,
        stderr=subprocess.STDOUT, start_new_session=True,
    )

    def _kill_tree() -> None:
        try:
            os.killpg(proc.pid, _signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        # the standalone master detaches into its own session (run.py
        # launch_local_master), so killpg misses it — an orphaned master
        # would keep holding its port and IPC names
        subprocess.run(
            ["pkill", "-9", "-f", "dlrover_tpu.master.job_master"],
            capture_output=True,
        )

    def _steps_logged() -> int:
        try:
            with open(log) as f:
                return sum(1 for line in f if '"step"' in line)
        except OSError:
            return 0

    kill_at = [max(5, max_steps * (i + 1) // (kills + 1))
               for i in range(kills)]
    killed = 0
    deadline = time.time() + deadline_s
    try:
        while proc.poll() is None and time.time() < deadline:
            if killed < kills and _steps_logged() >= kill_at[killed]:
                out = subprocess.run(
                    ["pgrep", "-f", f"^{sys.executable} {example}"],
                    capture_output=True, text=True,
                )
                from dlrover_tpu.agent.standby import parked_standby_pids

                # a parked warm standby has the same cmdline as the live
                # trainer: killing it would waste the injection AND turn
                # the next recovery cold
                standbys = parked_standby_pids(env.get("DLROVER_TPU_IPC_DIR"))
                pids = [int(p) for p in out.stdout.split()
                        if int(p) not in standbys]
                if pids:
                    os.kill(pids[-1], _signal.SIGKILL)
                    killed += 1
            time.sleep(0.25)
        if proc.poll() is None:
            _kill_tree()
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            _kill_tree()
            proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            _kill_tree()
        log_f.close()
    try:
        with open(job_log, "rb") as f:
            f.seek(max(0, os.path.getsize(job_log) - 2000))
            tail = f.read().decode(errors="replace")
    except OSError:
        tail = ""
    return proc.returncode, tail, killed, t_launch, time.time()


def _snapshot_cost_s(log_path: str, mem_interval: int) -> float:
    """Estimate per-snapshot overhead from a calibration log: snapshot
    steps are the top 1/interval fraction of durations; overhead =
    their typical duration minus the pure-step median."""
    import statistics

    durs = []
    prev = None
    with open(log_path) as f:
        for line in f:
            try:
                ev = json.loads(line)
            except json.JSONDecodeError:
                continue
            if ev.get("ev") == "step" and prev is not None:
                durs.append(ev["t"] - prev)
            if "t" in ev:
                prev = ev["t"]
    if len(durs) < 2 * mem_interval:
        return 0.0
    durs = durs[1:]  # first step may carry compile
    durs.sort()
    median = statistics.median(durs)
    n_snap = max(1, len(durs) // mem_interval)
    snap_typical = statistics.median(durs[-n_snap:])
    return max(0.0, snap_typical - median)


def _goodput_scenario(extra: dict, prefix: str, child_env: dict,
                      target_s: float, kills: int,
                      stage_budget_s: float = 1800.0,
                      cal: tuple[float, float] | None = None,
                      safety: float = 1.5) -> None:
    """One full goodput measurement (calibrate -> inject-and-measure).
    ``stage_budget_s`` bounds calibration + measured run together.
    ``cal`` = (step_s, snap_s) from an earlier scenario on the same
    backend skips the calibration run (sound on CPU: there is no
    persistent compile cache to warm there). ``safety`` is the
    headroom factor between the remaining budget and the measured
    window (1.5 default; low-kill scenarios can afford less)."""
    import math
    import shutil

    from dlrover_tpu.utils.goodput import compute_goodput

    repo = os.path.dirname(os.path.abspath(__file__))
    example = os.path.join(repo, "examples", "train_transformer.py")
    model = os.environ.get("BENCH_GOODPUT_MODEL", "tiny")
    work = tempfile.mkdtemp(prefix="bench_goodput_")
    log = os.path.join(work, "goodput.jsonl")
    journal_dir = os.path.join(work, "journal")
    env = dict(os.environ)
    env.update(child_env)
    env.update({
        "DLROVER_TPU_IPC_DIR": os.path.join(work, "ipc"),
        # the PR-1 journal is the evidence source for the per-failure
        # phase breakdown emitted below — every goodput headline ships
        # with its respawn/rendezvous/restore/recompile/redone split
        "DLROVER_TPU_JOURNAL_DIR": journal_dir,
        # warm recovery on (the default) — pinned so an outer env can't
        # silently bench the cold path
        "DLROVER_TPU_STANDBY": env.get("DLROVER_TPU_STANDBY", "1"),
        # the compile caches (XLA's and the AOT executables, DESIGN.md
        # §17) stay where the environment or the checkout puts them
        # (compile_cache.cache_root): the calibration run warms them, so
        # measured-run respawns load the executable instead of
        # recompiling — a fresh temp dir per run would never hit
        "PYTHONPATH": env.get("PYTHONPATH", "") + os.pathsep + repo,
    })

    def train_args(mem_interval: int) -> list[str]:
        return [
            "--model", model, "--global-batch", "8",
            "--ckpt-dir", os.path.join(work, "ckpt"),
            "--mem-ckpt-interval", str(mem_interval),
            "--ckpt-interval", "1000000",
            "--epochs", "1000000",
            "--goodput-log", log,
            "--result-file", os.path.join(work, "result.json"),
            "--log-interval", "500",
        ]

    t_stage0 = time.monotonic()
    try:
        # ---- calibration: steady step time + per-snapshot cost (also
        # warms the compile cache so measured-run restarts don't compile)
        cal_interval = 5
        if cal is None:
            rc, tail, _, _, _ = _run_elastic_job(
                work, env,
                train_args(cal_interval) + ["--dataset-size", "100000"],
                max_steps=60, kills=0,
                deadline_s=min(900, stage_budget_s * 0.45),
                example=example)
            if rc != 0:
                extra[f"{prefix}error"] = f"calibration rc={rc}: {tail}"
                return
            cal_report = compute_goodput(log)
            step_s = max(1e-4, cal_report.median_step_s)
            snap_s = _snapshot_cost_s(log, cal_interval)
        else:
            step_s, snap_s = max(1e-4, cal[0]), cal[1]
        extra[f"{prefix}cal_step_s"] = round(step_s, 5)
        remaining = stage_budget_s - (time.monotonic() - t_stage0) - 60
        target_s = max(60.0, min(target_s, remaining / safety))
        total_steps = max(120, min(200000, int(target_s / step_s)))
        # snapshot cadence that balances snapshot overhead against
        # rollback re-compute: minimize steps/interval*snap +
        # kills*interval/2*step  ->  interval* = sqrt(2*steps*snap /
        # (kills*step)); clamped so there is always rollback coverage
        if snap_s > 0 and kills > 0:
            interval = int(math.sqrt(
                2 * total_steps * snap_s / (kills * step_s)))
        else:
            interval = cal_interval
        interval = max(1, min(interval, total_steps // 8))
        if os.path.exists(log):
            os.remove(log)
        shutil.rmtree(os.path.join(work, "ckpt"), ignore_errors=True)
        shutil.rmtree(os.path.join(work, "ipc"), ignore_errors=True)
        # the phase breakdown must describe the MEASURED run only
        shutil.rmtree(journal_dir, ignore_errors=True)

        rc, tail, killed, t_launch, t_exit = _run_elastic_job(
            work, env,
            train_args(interval) + ["--dataset-size",
                                    str(total_steps * 40)],
            max_steps=total_steps, kills=kills,
            deadline_s=max(120, remaining), example=example)
        report = compute_goodput(log, start_time=t_launch,
                                 end_time=t_exit)
        # North-star normalization (BASELINE.md: >=95% goodput at ONE
        # injected preemption per hour). The harness compresses time —
        # killed/total_s is 20-30x the baseline's failure rate — so the
        # raw window number charges 20-30 failures/hour of restart cost.
        # Decompose the measured loss into per-failure cost + steady
        # snapshot overhead and price it at the baseline's rate. The
        # per-failure cost keeps rollback re-compute as measured
        # (conservative: the snapshot cadence was tuned for the
        # stressed rate, not the 1/hour one).
        n_snaps = report.n_steps // max(1, interval)
        fail_lost_s = max(0.0, report.lost_s - n_snaps * snap_s)
        per_failure_s = fail_lost_s / killed if killed else 0.0
        step_cost = report.median_step_s + snap_s / max(1, interval)
        f_snap = (snap_s / max(1, interval)) / step_cost
        goodput_hourly = max(
            0.0, 1.0 - per_failure_s / 3600.0 - f_snap
        )
        extra.update({
            f"{prefix}goodput": round(report.goodput, 4),
            f"{prefix}goodput_cold": round(report.goodput_cold, 4),
            # the measured window's failure rate, ALWAYS beside the
            # goodput headline: the harness compresses time, so a raw
            # "0.7558" is meaningless without its "@ 26/hr" qualifier
            # (the baseline bar is >=0.95 at 1/hr)
            f"{prefix}failures_per_hr": round(
                killed * 3600.0 / max(report.total_s, 1e-9), 1),
            f"{prefix}per_failure_cost_s": round(per_failure_s, 2),
            f"{prefix}snapshot_overhead_frac": round(f_snap, 5),
            # the north-star number: measured failure cost at the
            # baseline's 1-preemption-per-hour rate
            f"{prefix}goodput_at_baseline_rate": round(goodput_hourly, 4),
            f"{prefix}failures_injected": killed,
            f"{prefix}incarnations": report.n_incarnations,
            f"{prefix}steps": report.n_steps,
            f"{prefix}redone_steps": report.redone_steps,
            f"{prefix}median_step_s": round(report.median_step_s, 5),
            f"{prefix}snapshot_cost_s": round(snap_s, 4),
            f"{prefix}snapshot_interval": interval,
            f"{prefix}total_s": round(report.total_s, 1),
            f"{prefix}exit_code": rc,
        })
        # per-failure phase breakdown from the journal (same vocabulary
        # as telemetry/report): where each failure's lost time went.
        # Union seconds per category / failures injected.
        try:
            from dlrover_tpu.telemetry.report import build_report

            lrep = build_report(journal_dir, goodput_log=log,
                                end_time=t_exit)
            denom = max(1, killed)
            # recompile_warm_s vs recompile_cold_s: the compile-cache
            # proof — a warm recovery's "recompile" is an executable
            # load, and this split shows it (DESIGN.md §17)
            for cat in ("respawn", "rendezvous", "restore",
                        "recompile", "recompile_warm",
                        "recompile_cold", "redone"):
                extra[f"{prefix}{cat}_s"] = round(
                    lrep.categories.get(cat, 0.0) / denom, 2)
            extra[f"{prefix}unattributed_s"] = round(
                lrep.unattributed_s / denom, 2)
            # steady-state efficiency beside the lost-time numbers
            # (telemetry/efficiency.py journal samples): where a
            # HEALTHY step's time goes in the same artifact. Live MFU
            # appears only on devices with a known peak (not the CPU
            # harness).
            eff_rows = lrep.efficiency
            if eff_rows:
                def _mean_of(key):
                    vals = [r[key] for r in eff_rows
                            if r.get(key) is not None]
                    return sum(vals) / len(vals) if vals else None

                blocked = _mean_of("host_blocked_pct")
                if blocked is not None:
                    extra[f"{prefix}host_blocked_pct"] = round(blocked, 1)
                mfu_live = _mean_of("mfu_mean")
                if mfu_live is not None:
                    extra[f"{prefix}live_mfu"] = round(mfu_live, 4)
                phases: dict[str, list[float]] = {}
                for r in eff_rows:
                    for p, v in (r.get("phase_s") or {}).items():
                        phases.setdefault(p, []).append(v)
                for p, vals in sorted(phases.items()):
                    extra[f"{prefix}phase_{p}_ms"] = round(
                        1e3 * sum(vals) / len(vals), 3)
        except Exception as e:  # noqa: BLE001 - breakdown is evidence,
            # not a reason to lose the headline numbers
            extra[f"{prefix}phase_breakdown_error"] = str(e)
        if rc != 0:
            extra[f"{prefix}tail"] = tail
    finally:
        import subprocess

        subprocess.run(["pkill", "-9", "-f", example],
                       capture_output=True)
        subprocess.run(
            ["pkill", "-9", "-f", "dlrover_tpu.master.job_master"],
            capture_output=True,
        )
        shutil.rmtree(work, ignore_errors=True)


def _cpu_child_env() -> dict:
    return {"JAX_PLATFORMS": "cpu",
            "DLROVER_TPU_DEVICE_COUNT": "8",
            "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_"
                            "count=8").strip()}


def bench_goodput(extra: dict, stage_budget_s: float = 900.0) -> None:
    """The reference's headline metric: goodput under injected failures.

    Runs the elastic example under ``dlrover_tpu.run --standalone``,
    SIGKILLs the trainer BENCH_GOODPUT_KILLS times mid-run (the agent
    re-rendezvouses, respawns, restores from the shm snapshot), then
    aggregates the per-step goodput log (utils/goodput.py: rolled-back
    re-runs, restart downtime, snapshot overhead and recompiles all
    count as lost). Bar: >=0.95 with >=2 failures (reference
    README.md:54-55, BASELINE.md north star).

    Trainer children run on the CPU backend — goodput here is a
    *systems* metric (restart/rendezvous/restore/snapshot fraction).
    ``goodput_tpu`` runs the same harness with the chip in the loop as
    a separate stage.
    """
    if os.environ.get("BENCH_GOODPUT", "1") == "0":
        return
    target_s = float(os.environ.get("BENCH_GOODPUT_S", "240"))
    kills = int(os.environ.get("BENCH_GOODPUT_KILLS", "2"))

    _goodput_scenario(
        extra, "goodput_sys_", child_env=_cpu_child_env(),
        target_s=target_s, kills=kills, stage_budget_s=stage_budget_s,
    )
    # headline aliases (the systems scenario is THE goodput number);
    # failures_per_hr rides along so the headline can never be read
    # at-the-bar without its rate qualifier (VERDICT r5 item 9)
    for k in ("goodput", "goodput_cold", "goodput_at_baseline_rate",
              "per_failure_cost_s", "failures_injected", "failures_per_hr",
              "incarnations", "steps", "median_step_s", "total_s",
              "respawn_s", "rendezvous_s", "restore_s", "recompile_s",
              "redone_s"):
        if f"goodput_sys_{k}" in extra:
            name = k if k.startswith("goodput") else f"goodput_{k}"
            extra[name] = extra[f"goodput_sys_{k}"]
    try:
        # per-stage recompile evidence (DESIGN.md §21): an MPMD
        # single-stage failure must cold-compile ONLY the failed stage
        _stage_recompile_leg(extra)
    except Exception as e:  # noqa: BLE001 - rider leg
        extra["goodput_stage_recompile_error"] = (
            f"{type(e).__name__}: {e}"[:300])


def bench_goodput_lowrate(extra: dict,
                          stage_budget_s: float = 620.0) -> None:
    """Near-baseline-rate goodput in the DRIVER'S evidence (r04 Weak #4:
    the 20.7-min/one-kill run lived only in prose). One injected SIGKILL
    across a ~420 s measured window (~8 failures/hr vs the main stage's
    ~30/hr and the baseline's 1/hr), so the raw number — not just the
    decomposed at-baseline projection — is close to deployment shape.
    Reuses the main goodput stage's calibration (same CPU backend, same
    model) so the whole budget goes to the measured window."""
    if os.environ.get("BENCH_GOODPUT_LOWRATE", "1") == "0":
        return
    cal = None
    if "goodput_sys_median_step_s" in extra:
        cal = (extra["goodput_sys_median_step_s"],
               extra.get("goodput_sys_snapshot_cost_s", 0.0))
    _goodput_scenario(
        extra, "goodput_lowrate_", child_env=_cpu_child_env(),
        target_s=float(os.environ.get("BENCH_GOODPUT_LOWRATE_S", "420")),
        kills=1, stage_budget_s=stage_budget_s, cal=cal, safety=1.25,
    )
    if "goodput_lowrate_goodput" in extra:
        # the lowrate twin: _goodput_scenario already emitted
        # goodput_lowrate_failures_per_hr beside the headline
        extra["goodput_lowrate_raw"] = extra["goodput_lowrate_goodput"]


def bench_goodput_tpu(extra: dict, stage_budget_s: float = 700.0) -> None:
    """Goodput with the real chip in the loop. The trainer children
    need the chip, and a chip belongs to one process: this stage runs
    only from a bench process that has not touched JAX — alone, as
    ``python bench.py goodput_tpu`` — and finds the chip by sniffing."""
    if os.environ.get("BENCH_GOODPUT_TPU", "1") == "0":
        return
    from dlrover_tpu.common.accelerator import sniff_accelerator

    if sniff_accelerator()[0] != "tpu":
        raise RuntimeError("stage goodput_tpu measures the chip; the "
                           "accelerator sniff found none")
    if "jax" in sys.modules:
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            raise RuntimeError(
                "this process already holds the chip, so the trainer "
                "children cannot: run `python bench.py goodput_tpu` "
                "alone")
    _goodput_scenario(
        extra, "goodput_tpu_", child_env={},
        target_s=float(os.environ.get("BENCH_GOODPUT_TPU_S", "180")),
        kills=int(os.environ.get("BENCH_GOODPUT_KILLS", "2")),
        stage_budget_s=stage_budget_s,
    )


def bench_soak(extra: dict, stage_budget_s: float = 300.0) -> None:
    """Bounded many-kill soak (round-3 Weak #7: the production-shaped
    scenario must run in the default bench, not only behind an opt-in
    env). CPU backend, one elastic job, BENCH_SOAK_KILLS (>=3) SIGKILLs
    at step thresholds; reports kills delivered, steps completed and
    whether the job still exited clean."""
    if os.environ.get("BENCH_SOAK", "1") == "0":
        return
    import shutil

    kills = int(os.environ.get("BENCH_SOAK_KILLS", "4"))
    max_steps = int(os.environ.get("BENCH_SOAK_STEPS", "120"))
    repo = os.path.dirname(os.path.abspath(__file__))
    example = os.path.join(repo, "examples", "train_transformer.py")
    work = tempfile.mkdtemp(prefix="bench_soak_")
    env = dict(os.environ)
    env.update(_cpu_child_env())
    env.update({
        "DLROVER_TPU_IPC_DIR": os.path.join(work, "ipc"),
        "PYTHONPATH": env.get("PYTHONPATH", "") + os.pathsep + repo,
    })
    log = os.path.join(work, "goodput.jsonl")
    try:
        rc, tail, killed, t_launch, t_exit = _run_elastic_job(
            work, env,
            ["--model", "tiny", "--global-batch", "8",
             "--ckpt-dir", os.path.join(work, "ckpt"),
             "--mem-ckpt-interval", "5",
             "--ckpt-interval", "1000000",
             "--epochs", "1000000",
             "--dataset-size", str(max_steps * 40),
             "--goodput-log", log,
             "--result-file", os.path.join(work, "result.json"),
             "--log-interval", "500"],
            max_steps=max_steps, kills=kills,
            deadline_s=stage_budget_s - 30, example=example)
        steps_done = 0
        try:
            steps = []
            with open(log) as f:
                for line in f:
                    if '"step"' not in line:
                        continue
                    # a SIGKILL landing mid-write leaves a truncated
                    # line; it must not void the whole stage
                    try:
                        steps.append(json.loads(line).get("step", -1))
                    except json.JSONDecodeError:
                        continue
            steps_done = max(steps, default=-1) + 1
        except OSError:
            pass
        extra.update(
            soak_kills=killed,
            soak_steps_completed=steps_done,
            soak_target_steps=max_steps,
            soak_exit_code=rc,
            soak_wall_s=round(t_exit - t_launch, 1),
            soak_completed=bool(rc == 0 and steps_done >= max_steps),
        )
        if rc != 0:
            extra["soak_tail"] = tail[-500:]
    finally:
        import subprocess

        subprocess.run(["pkill", "-9", "-f", example],
                       capture_output=True)
        subprocess.run(
            ["pkill", "-9", "-f", "dlrover_tpu.master.job_master"],
            capture_output=True,
        )
        shutil.rmtree(work, ignore_errors=True)


def bench_chaos(extra: dict, stage_budget_s: float = 300.0) -> None:
    """Replay the canned chaos schedule (trainer SIGKILLed mid-save, the
    newest shard bit-flipped on its way to disk, master RPC dropped on
    the re-join) against a local elastic job and report recovery time
    and goodput-under-faults beside the clean-goodput headlines
    (dlrover_tpu/chaos/scenario.py; DESIGN.md §15.2)."""
    import shutil

    repo = os.path.dirname(os.path.abspath(__file__))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from dlrover_tpu.chaos.scenario import canned_scenario, run_scenario

    work = tempfile.mkdtemp(prefix="bench_chaos_")
    try:
        scenario = canned_scenario(
            seed=int(os.environ.get("BENCH_CHAOS_SEED", "1234"))
        )
        res = run_scenario(scenario, work, env_extra=_cpu_child_env(),
                           deadline_s=max(90, stage_budget_s - 30))
        extra["chaos_completed"] = res.completed
        extra["chaos_faults_injected"] = len(res.trail["faults"])
        extra["chaos_rollbacks"] = sum(
            1 for r in res.trail["recovery"] if r[0] == "ckpt_rollback"
        )
        extra["chaos_verified_step"] = res.verified_step
        if res.recovery_seconds is not None:
            extra["chaos_recovery_seconds"] = round(res.recovery_seconds, 2)
            # §27 reconciliation: the assembled incident tree must
            # contain the respawned trainer's ckpt_restore (attached
            # via SPAN_CTX), and kill -> that restore must agree with
            # chaos_recovery_seconds within 10% — disagreement means
            # the trace fabric lost a recovery hop
            try:
                from dlrover_tpu.chaos.scenario import _read_journal
                from dlrover_tpu.telemetry import trace as trace_mod

                jdir = os.path.join(work, "journal")
                t_kill = next(
                    (e["t"] for e in _read_journal(jdir)
                     if e.get("name") == "chaos_fault"
                     and e.get("point") == "agent_kill_trainer"), None)
                incidents = [
                    r for r in trace_mod.find_incident_roots(
                        trace_mod.build_forest(
                            trace_mod.load_spans([jdir])))
                    if r.span.fields.get("kind") == "failure"
                    and (t_kill is None or r.end > t_kill)]
                if t_kill is None or not incidents:
                    raise RuntimeError(
                        "no failure incident tree after the kill")
                inc = min(incidents, key=lambda n: n.start)
                restores = [n for n in inc.walk()
                            if n.span.name == "ckpt_restore"]
                if not restores:
                    raise RuntimeError(
                        "no ckpt_restore attached under the incident")
                trace_rec = min(r.end for r in restores) - t_kill
                segs = trace_mod.critical_path(inc)
                top = max(segs, key=lambda s: s["self_s"])
                frac = abs(trace_rec - res.recovery_seconds) \
                    / max(res.recovery_seconds, 1e-9)
                extra["chaos_trace_recovery_s"] = round(trace_rec, 2)
                extra["chaos_trace_critical_path_top"] = (
                    f"{top['name']}={top['self_s']:.2f}s")
                extra["chaos_trace_agreement_frac"] = round(frac, 4)
                extra["chaos_trace_agrees_10pct"] = frac <= 0.10
                if frac > 0.10:
                    raise RuntimeError(
                        f"incident trace recovery {trace_rec:.2f}s vs "
                        f"chaos_recovery_seconds "
                        f"{res.recovery_seconds:.2f}s: off by "
                        f"{frac:.0%}")
            except Exception as e:  # noqa: BLE001 - keep stage numbers
                extra["chaos_trace_error"] = repr(e)
                extra.setdefault("chaos_trace_agrees_10pct", False)
        if res.goodput is not None:
            # goodput of the sabotaged leg: restart + re-join retries +
            # rolled-back steps all charged, same accounting as the
            # clean goodput stage
            extra["chaos_goodput"] = round(res.goodput, 4)
        if not res.completed and res.legs:
            extra["chaos_tail"] = res.legs[-1].tail[-1500:]
        # §30 trail-invariant audit: run_scenario already asserted a
        # clean trail internally; re-run the auditor here so the
        # headline records the checked-invariant count explicitly
        try:
            from dlrover_tpu.telemetry.audit import audit_journal_dir

            findings = audit_journal_dir(os.path.join(work, "journal"))
            extra["chaos_audit_ok"] = not findings
            extra["chaos_audit_findings"] = len(findings)
        except Exception as e:  # noqa: BLE001 - keep stage numbers
            extra["chaos_audit_ok"] = False
            extra["chaos_audit_error"] = repr(e)
        # §30 partition leg: a rack-wide split against a 1-second rack
        # lease — the sub-master fails closed, agents finish the round
        # direct-to-root, and the healed rack is re-admitted under its
        # original epoch. Headline: seconds from the link opening to
        # re-admission.
        try:
            from dlrover_tpu.chaos.partition_scenarios import (
                run_rack_split_scenario,
            )

            pres = run_rack_split_scenario(
                os.path.join(work, "partition"),
                seed=int(os.environ.get("BENCH_CHAOS_SEED", "1234")),
            )
            pres.assert_invariants()
            extra["chaos_partition_recovery_s"] = round(
                pres.recovery_s, 2)
            extra["chaos_partition_redirected"] = pres.redirected
            extra["chaos_partition_restarts"] = pres.restart_actions
        except Exception as e:  # noqa: BLE001 - keep stage numbers
            extra["chaos_partition_error"] = repr(e)
        # §30 jitter audit: one seeded fleetsim netsplit wave measures
        # the reconnect burst the master absorbs after a heal under
        # the production full-jitter backoff (common/rpc)
        try:
            from dlrover_tpu.fleetsim.profile import FleetProfile
            from dlrover_tpu.fleetsim.sim import FleetSimulator

            sprof = FleetProfile(
                name="chaos_partition_wave", seed=1234, nodes=200,
                duration_s=30.0, failures=0, ckpt_interval_s=10.0,
                partitions=1, partition_s=4.0, partition_frac=0.3,
            )
            sres = FleetSimulator(sprof).run()
            extra["chaos_partition_wave_recovery_s"] = (
                round(sres.partition_recovery_s, 3)
                if sres.partition_recovery_s is not None else None)
            extra["chaos_reconnect_burst_p99"] = \
                sres.reconnect_burst_p99
        except Exception as e:  # noqa: BLE001 - keep stage numbers
            extra["chaos_partition_wave_error"] = repr(e)
    finally:
        shutil.rmtree(work, ignore_errors=True)


def bench_control_plane(extra: dict,
                        stage_budget_s: float = 300.0) -> None:
    """Master-saturation stage (DESIGN.md §22; runs on CPU, no devices).

    Drives the real in-process JobMaster with seeded simulated fleets
    (dlrover_tpu/fleetsim) at >=2 node-count tiers and reports where the
    control plane's time goes: master_rpc_p99_ms / master_joins_per_s /
    snapshot_ingest_ms per tier, plus the measured win of the
    delta-compressed snapshot pushes (same 1k profile, delta vs full —
    wire bytes and ingest cost). The per-tier ``master_rpc`` journal
    rows also land in telemetry/report.py's master_saturation section,
    whose dominant cost center per tier is echoed here.
    """
    import shutil

    repo = os.path.dirname(os.path.abspath(__file__))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from dlrover_tpu.common.constants import EnvKey
    from dlrover_tpu.fleetsim import FleetProfile, FleetSimulator

    t_start = time.monotonic()
    seed = int(os.environ.get("BENCH_CP_SEED", "2026"))

    def tier_profile(nodes: int, full_every: int = 10,
                     racks: int = 0) -> FleetProfile:
        # churn (failure + death waves) only at the small tier: each
        # wave re-distributes the O(nodes)-sized comm world to every
        # agent — the measured O(nodes^2) cost that at 5k nodes would
        # eat the stage deadline for no extra signal
        churn = nodes <= 1000
        return FleetProfile(
            name=f"cp{nodes}_f{full_every}" + (
                f"_r{racks}" if racks else ""),
            seed=seed,
            nodes=nodes,
            racks=racks,
            duration_s=45.0 if churn else 30.0,
            snapshot_interval_s=15.0 if churn else 20.0,
            heartbeat_interval_s=15.0,
            straggler_frac=0.004 if churn else 0.0,
            failures=1 if churn else 0,
            deaths=1 if churn else 0,
            ckpt_interval_s=20.0,
            # the real per-node registry is ~58 families of which a
            # handful change between pushes (§12.1): shape the
            # synthetic snapshots accordingly so the delta comparison
            # measures the production ratio, not a toy one
            families=40,
            changed_families=3,
            snapshot_full_every=full_every,
        )

    journal_dir = tempfile.mkdtemp(prefix="bench_cp_journal_")
    prev_journal = os.environ.get(EnvKey.JOURNAL_DIR)
    os.environ[EnvKey.JOURNAL_DIR] = journal_dir
    tiers_done: list[int] = []

    flat_tiers: list[int] = []

    def record_tier(nodes: int, res, racked: bool = False) -> None:
        tiers_done.append(nodes)
        extra[f"cp_master_rpc_p99_ms_n{nodes}"] = round(
            res.overall_p99_ms(), 3)
        extra[f"cp_rounds_n{nodes}"] = len(res.rounds)
        extra[f"cp_sim_wall_s_n{nodes}"] = round(res.wall_s, 1)
        if racked:
            # per-agent RPCs terminate at the sub-masters: the root-side
            # join/snapshot rows that the flat keys read do not exist
            return
        flat_tiers.append(nodes)
        extra[f"cp_master_joins_per_s_n{nodes}"] = round(
            res.joins_per_s())
        extra[f"cp_join_mean_ms_n{nodes}"] = round(
            res.join_mean_ms(), 4)
        extra[f"cp_snapshot_ingest_ms_n{nodes}"] = round(
            res.snapshot_ingest_mean_ms(), 4)

    try:
        # delta-compressed snapshot pushes vs full, same seeded 1k
        # profile: wire bytes + master ingest cost per push. Full runs
        # FIRST so the delta (production-shape) run's master_rpc rows
        # are the ones the report keeps for the 1k tier.
        full = FleetSimulator(tier_profile(1000, full_every=1)).run()
        delta = FleetSimulator(tier_profile(1000, full_every=10)).run()
        assert delta.trail == full.trail, \
            "delta/full runs must replay the same event trail"
        record_tier(1000, delta)

        # §26 master-restart leg at 1k: the sim snapshots the live
        # master, rebuilds it from the snapshot mid-run, and measures
        # reconvergence — virtual seconds until every agent's
        # epoch-fence reconcile landed, plus the re-registered curve
        restart_profile = tier_profile(1000)
        restart_profile.name = "cp1000_mr"
        restart_profile.master_restarts = 1
        mr = FleetSimulator(restart_profile).run()
        assert mr.master_recovery_s is not None, \
            "master restart never reconverged"
        extra["cp_master_recovery_s_n1000"] = round(
            mr.master_recovery_s, 3)
        extra["cp_reregistered_nodes_n1000"] = (
            mr.reregistered_curve[-1][1] if mr.reregistered_curve
            else 0)
        extra["cp_reregistered_curve_n1000"] = [
            [dt, n] for dt, n in mr.reregistered_curve[:: max(
                1, len(mr.reregistered_curve) // 20)]
        ]

        # ~wall cost scales with nodes^2 (the O(world)-sized comm-world
        # response goes to every agent): gate the big tiers on what is
        # left of the stage budget
        for nodes, est_s in ((5000, 160),):
            left = stage_budget_s - (time.monotonic() - t_start)
            if left < est_s + 30:
                break
            record_tier(nodes, FleetSimulator(tier_profile(nodes)).run())
        extra["cp_tiers"] = tiers_done

        # §28 racked 10k tier: the fleet behind nodes//64 sub-masters,
        # the root seeing only per-rack merged pushes / batched joins /
        # world pulls. One death exercises the comm-world diff path at
        # scale (survivors reshard; racks pull the new world as a diff
        # against their acked round instead of a full re-send).
        left = stage_budget_s - (time.monotonic() - t_start)
        if left >= 90 + 30:
            nodes = 10000
            racks = nodes // 64
            rp = tier_profile(nodes, racks=racks)
            rp.name = f"cp{nodes}_r{racks}"
            rp.deaths = 1
            res = FleetSimulator(rp).run()
            record_tier(nodes, res, racked=True)
            extra[f"cp_racks_n{nodes}"] = racks
            root_calls = sum(r["calls"] for r in res.rpc.values())
            extra[f"cp_root_calls_n{nodes}"] = root_calls
            extra[f"cp_root_calls_per_agent_n{nodes}"] = round(
                root_calls / nodes, 3)
            rack_join = res.rpc.get("RackJoinRequest")
            if rack_join:
                extra[f"cp_rack_join_mean_ms_n{nodes}"] = \
                    rack_join["mean_ms"]
            d = res.to_dict()
            extra["cp_world_diff_bytes_frac"] = \
                d["world_diff_bytes_frac"]
            # the tier's whole point: root load (and thus its p99)
            # stays ~flat as the fleet grows 10x past the 1k tier
            p99_1k = extra.get("cp_master_rpc_p99_ms_n1000")
            p99_10k = extra[f"cp_master_rpc_p99_ms_n{nodes}"]
            if p99_1k:
                extra["cp_rack_p99_ratio_10k_vs_1k"] = round(
                    p99_10k / p99_1k, 2)
                extra["cp_rack_p99_within_2x_1k"] = bool(
                    p99_10k < 2.0 * p99_1k)
                assert p99_10k < 2.0 * p99_1k, (
                    f"racked 10k master rpc p99 {p99_10k:.2f}ms vs "
                    f"{p99_1k:.2f}ms at 1k — the rack tier is not "
                    "holding root load flat"
                )

        # the join hot path must stay ~flat across tiers (the §22 O(1)
        # rendezvous contract): report the measured ratio. Flat tiers
        # only — in rack mode joins reach the root pre-batched.
        if len(flat_tiers) >= 2:
            lo, hi = flat_tiers[0], flat_tiers[-1]
            lo_ms = extra[f"cp_join_mean_ms_n{lo}"]
            hi_ms = extra[f"cp_join_mean_ms_n{hi}"]
            if lo_ms > 0:
                ratio = hi_ms / lo_ms
                extra["cp_join_cost_ratio"] = round(ratio, 2)
                # the simulator assertion behind the §22 O(1) claim: a
                # per-join O(world) regression shows up as ~nodes-ratio
                # growth (5-10x across these tiers), far past this bound
                extra["cp_join_cost_flat"] = bool(ratio < 4.0)
                assert ratio < 4.0, (
                    f"join handling cost grew {ratio:.1f}x from {lo} "
                    f"to {hi} nodes — the O(1) rendezvous contract is "
                    "broken"
                )
        extra["cp_snapshot_wire_bytes_full"] = full.snapshot_wire_bytes()
        extra["cp_snapshot_wire_bytes_delta"] = \
            delta.snapshot_wire_bytes()
        extra["cp_snapshot_ingest_ms_full"] = round(
            full.snapshot_ingest_mean_ms(), 4)
        extra["cp_snapshot_ingest_ms_delta"] = round(
            delta.snapshot_ingest_mean_ms(), 4)
        if full.snapshot_wire_bytes():
            extra["cp_snapshot_wire_reduction"] = round(
                1.0 - delta.snapshot_wire_bytes()
                / full.snapshot_wire_bytes(), 4)
        if full.snapshot_ingest_mean_ms():
            extra["cp_snapshot_ingest_reduction"] = round(
                1.0 - delta.snapshot_ingest_mean_ms()
                / full.snapshot_ingest_mean_ms(), 4)

        # fold the journal's master_rpc rows through the report: the
        # dominant cost center per tier is the headline diagnosis
        from dlrover_tpu.telemetry.report import build_report

        saturation = build_report(journal_dir).master_saturation
        extra["cp_dominant"] = {
            str(tier["nodes"]): tier["dominant"]
            for tier in saturation if tier["nodes"] in tiers_done
        }
    finally:
        if prev_journal is None:
            os.environ.pop(EnvKey.JOURNAL_DIR, None)
        else:
            os.environ[EnvKey.JOURNAL_DIR] = prev_journal
        shutil.rmtree(journal_dir, ignore_errors=True)


def bench_serving(extra: dict) -> None:
    """Continuous-batching decode throughput (serving/engine.py).

    gpt2-small, 8 slots, block decode: tokens/s at steady state. The
    per-token host round trip is what decode_block amortizes — both
    block=1 and block=32 are reported so its cost is visible rather
    than baked in.
    """
    if os.environ.get("BENCH_SERVING", "1") == "0":
        return
    import jax

    _require_tpu("serving")

    from dlrover_tpu.models import transformer as tfm
    from dlrover_tpu.serving import InferenceEngine, SamplingParams

    cfg = tfm.CONFIGS["gpt2-small"]
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    def run(block: int) -> float:
        eng = InferenceEngine(params, cfg, slots=8, max_len=512,
                              prefill_len=128, decode_block=block)
        sp = SamplingParams(temperature=0.8, top_p=0.95,
                            max_new_tokens=128)
        # warmup wave compiles prefill/install/step programs
        eng.submit(list(rng.integers(0, cfg.vocab_size, 16)), sp)
        eng.run()
        # block=1 pays a host round trip per token, so its wave is half
        # the headline's — the tok/s RATE is unchanged, the stage just
        # stops spending ~35 s of envelope re-measuring a known tax
        for _ in range(16 if block > 1 else 8):
            eng.submit(list(rng.integers(0, cfg.vocab_size, 64)), sp)
        t0 = time.monotonic()
        results = eng.run()
        wall = time.monotonic() - t0
        toks = sum(len(r.tokens) for r in results)
        return toks / wall

    # block=32 (the headline) first so a stage deadline costs the
    # round-trip-dominated block=1 number, not the headline
    extra["serving_toks_per_s"] = round(run(32), 1)
    extra["serving_config"] = "gpt2-small slots=8 prompt=64 gen=128"

    def run_shared_prefix(entries: int) -> float:
        # the RLHF rollout shape: every prompt shares a 448-token
        # system prefix (7 of 8 prefill chunks); tiny generations so
        # the measured wall IS time-to-first-tokens — the thing the
        # prefix cache removes (a hit skips 7 of 9 per-request
        # dispatches: 7 chunk prefills kept -> 1, + install + decode)
        eng = InferenceEngine(params, cfg, slots=8, max_len=512,
                              prefill_len=64, decode_block=4,
                              prefix_cache_entries=entries)
        sys_prefix = list(rng.integers(0, cfg.vocab_size, 448))
        sp = SamplingParams(temperature=0.8, top_p=0.95,
                            max_new_tokens=4)
        eng.submit(sys_prefix + [1], sp)
        eng.run()  # warmup: compiles + (with entries) seeds the cache
        t0 = time.monotonic()
        for _ in range(16):
            eng.submit(
                sys_prefix + list(rng.integers(0, cfg.vocab_size, 8)),
                sp,
            )
        results = eng.run()
        wall = time.monotonic() - t0
        assert len(results) == 16
        return wall / 16  # s per request, prefill-dominated

    cold = run_shared_prefix(0)
    warm = run_shared_prefix(16)
    extra["serving_prefix_cold_s_per_req"] = round(cold, 4)
    extra["serving_prefix_cached_s_per_req"] = round(warm, 4)
    extra["serving_prefix_cache_speedup"] = round(cold / warm, 2)

    extra["serving_toks_per_s_block1"] = round(run(1), 1)


def bench_gateway(extra: dict) -> None:
    """Disagg-vs-unified A/B over an open-loop MULTI-TENANT trace with
    per-tenant SLO accounting (gateway/: prefill+decode pools, paged
    KV, chunked admission — DESIGN.md §23).

    Three tenant shapes stress different pools: `chat` (shared system
    prompt, medium decode — the prefix-cache/affinity shape),
    `summarize` (long prefill, short decode — the TTFT killer) and
    `generate` (short prompt, long decode — the slot pinner). The same
    seeded trace runs against a unified gateway and a disaggregated
    one (prefill pool + paged decode pool); per tenant we report TTFT
    p95 (submit -> first token), inter-token p95 (per-token arrival
    stamps) and goodput (fraction meeting the tenant's TTFT SLO). The
    disagg leg keeps the PR-2 mid-run replica kill (zero failed
    requests, autoscaler restore). The stall bound — beside a live
    batch an engine step admits <= one prefill chunk per decode step of
    its block (`decode_block` 8 here) — is reported from the
    `dlrover_tpu_engine_decode_stall_seconds` histogram, expressed in
    single-chunk units.

    Runs on CPU with the tiny config (same structure, smaller trace)
    so the A/B evidence exists in every container; gpt2-small on TPU.
    """
    if os.environ.get("BENCH_GATEWAY", "1") == "0":
        return
    import jax

    from dlrover_tpu.gateway import (
        DisaggAutoscaler,
        Gateway,
        GatewayAutoscaler,
        PoolScaler,
    )
    from dlrover_tpu.models import transformer as tfm
    from dlrover_tpu.serving import InferenceEngine, SamplingParams
    from dlrover_tpu.serving import engine as engine_mod

    _require_tpu("gateway")
    cfg = tfm.CONFIGS["gpt2-small"]
    geo = dict(slots=4, max_len=256, prefill_len=64,
               decode_block=8, kv_pages=48)
    n_requests, rate_hz, replicas = 48, 8.0, 2
    # (prompt_len, max_new) per tenant shape; sys prefix for chat
    shapes = {"chat": (32, 32), "summarize": (192, 8),
              "generate": (16, 96)}
    sys_len = 128
    ttft_slo = {"chat": 2.0, "summarize": 4.0, "generate": 2.0}
    P = geo["prefill_len"]
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))

    def make_factory(kv_pages):
        def engine_factory():
            return InferenceEngine(params, cfg, prefix_cache_entries=8,
                                   **dict(geo, kv_pages=kv_pages))
        return engine_factory

    # the seeded multi-tenant trace, shared verbatim by both legs:
    # chat = shared-system-prompt + medium decode, summarize =
    # long-prefill short-decode, generate = short-prompt long-decode
    rng = np.random.default_rng(0)
    system_prompt = list(rng.integers(0, cfg.vocab_size, sys_len))
    tenants = ("chat", "summarize", "generate")
    trace = []
    for i in range(n_requests):
        tenant = tenants[i % 3]
        plen, max_new = shapes[tenant]
        prompt = list(rng.integers(0, cfg.vocab_size, plen))
        if tenant == "chat":
            prompt = system_prompt + prompt
        sp = SamplingParams(temperature=0.8, top_p=0.95,
                            max_new_tokens=max_new)
        trace.append((i / rate_hz, tenant, prompt, sp))

    def pctl(values, q):
        if not values:
            return None
        values = sorted(values)
        return values[int(q * (len(values) - 1))]

    stall_bounds = engine_mod._decode_stall_seconds.buckets

    def stall_buckets():
        samp = engine_mod._decode_stall_seconds.samples()
        return (list(samp[0]["buckets"]) if samp
                else [0] * (len(stall_bounds) + 1))

    def run_leg(disagg: bool) -> dict:
        # the unified leg runs the PR-2 data plane (dense slots, no
        # pool split) as the A/B baseline; the disagg leg runs the §23
        # plane (paged decode pool + dedicated prefill pool). Token
        # identity between the two is pinned by tests/test_disagg.py —
        # this measures latency shape, not correctness.
        gateway = Gateway(
            make_factory(geo["kv_pages"] if disagg else 0),
            replicas=replicas, prefill_len=P,
            prefill_replicas=1 if disagg else 0,
            admission_deadline_s=300.0, health_interval_s=0.2, seed=0,
        )
        autoscaler = None
        try:
            deadline = time.monotonic() + 180
            while (len(gateway.pool.ready_replicas()) < replicas
                   and time.monotonic() < deadline):
                time.sleep(0.2)
            if disagg:
                while (len(gateway.prefill_pool.ready_replicas()) < 1
                       and time.monotonic() < deadline):
                    time.sleep(0.2)
            # warmup wave: compiles prefill/install/step on every pool
            # — slots+1 concurrent medium decodes also force one
            # park/resume cycle on the paged leg, so the gather/scatter
            # jits never compile inside the measured trace
            warm = [gateway.submit(
                trace[j][2], SamplingParams(
                    temperature=0.8,
                    max_new_tokens=min(geo["prefill_len"] + 4, 12)),
            ) for j in range(geo["slots"] + 1)]
            for f in warm:
                f.result(timeout=300)
            if disagg:
                autoscaler = DisaggAutoscaler(
                    gateway,
                    PoolScaler(gateway.prefill_pool, group="prefill"),
                    PoolScaler(gateway.pool, group="decode"),
                    min_prefill=1, max_prefill=1,
                    min_decode=replicas, max_decode=replicas,
                    interval_s=0.5,
                ).start()
            else:
                autoscaler = GatewayAutoscaler(
                    gateway, PoolScaler(gateway.pool),
                    min_replicas=replicas, max_replicas=replicas,
                    interval_s=0.5,
                ).start()
            stall_start = stall_buckets()
            futures, failed = [], 0
            t0 = time.monotonic()
            for _, (t_off, tenant, prompt, sp) in enumerate(trace):
                # open loop: arrivals keyed to the clock, not
                # completions
                delay = t0 + t_off - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                futures.append((tenant, gateway.submit(prompt, sp)))
            # kill when most of the backlog has drained, in BOTH legs:
            # A/B symmetry, zero-drop evidence, and a pre-kill stall
            # window untainted by the replacement replica's compiles
            kill_deadline = time.monotonic() + 120
            while (gateway.admission.pending > n_requests // 4
                   and time.monotonic() < kill_deadline):
                time.sleep(0.02)
            stall_prekill = stall_buckets()
            ready = gateway.pool.ready_replicas()
            if ready:
                orphans = gateway.pool.kill_replica(ready[0].id)
                if disagg:
                    extra["gateway_kill_orphans"] = orphans
            per_tenant = {t: {"ttft": [], "itl": [], "ok": 0, "n": 0}
                          for t in tenants}
            latencies = []
            for tenant, fut in futures:
                rec = per_tenant[tenant]
                rec["n"] += 1
                try:
                    res = fut.result(timeout=300)
                except Exception:  # noqa: BLE001 - count, don't crash
                    failed += 1
                    continue
                latencies.append(res.total_s)
                ttft = res.queue_s + res.prefill_s
                rec["ttft"].append(ttft)
                rec["itl"].extend(
                    b - a for a, b in zip(res.token_times,
                                          res.token_times[1:]))
                if ttft <= ttft_slo[tenant]:
                    rec["ok"] += 1
            wall = time.monotonic() - t0
            leg = {
                "req_per_s": round(len(latencies) / wall, 2),
                "p95_s": round(pctl(latencies, 0.95), 3)
                if latencies else None,
                "failed": failed,
                "ttft_p95_s": round(pctl(
                    [t for r in per_tenant.values()
                     for t in r["ttft"]], 0.95) or 0.0, 3),
                "itl_p95_s": round(pctl(
                    [t for r in per_tenant.values()
                     for t in r["itl"]], 0.95) or 0.0, 4),
                "tenants": {
                    t: {
                        "ttft_p95_s": round(
                            pctl(rec["ttft"], 0.95) or 0.0, 3),
                        "itl_p95_s": round(
                            pctl(rec["itl"], 0.95) or 0.0, 4),
                        "goodput": round(rec["ok"] / rec["n"], 3)
                        if rec["n"] else None,
                    }
                    for t, rec in per_tenant.items()
                },
            }
            leg["stall_delta"] = [
                b - a for a, b in zip(stall_start, stall_prekill)]
            restore_deadline = time.monotonic() + 60
            while (gateway.pool.live_count() < replicas
                   and time.monotonic() < restore_deadline):
                time.sleep(0.2)
            leg["replicas_restored"] = gateway.pool.live_count()
            return leg
        finally:
            if autoscaler is not None:
                autoscaler.stop()
            gateway.stop()

    # one-chunk reference time: the unit of the stall-bound assertion
    probe = make_factory(0)()
    run = probe.prefill_begin(list(rng.integers(0, cfg.vocab_size, P)))
    probe.prefill_step(run)                      # compile
    run2 = probe.prefill_begin(list(rng.integers(0, cfg.vocab_size, P)))
    t0 = time.monotonic()
    probe.prefill_step(run2)
    chunk_s = time.monotonic() - t0
    del probe

    unified = run_leg(disagg=False)
    # journal the disagg leg (§27): the assembled request traces and
    # their critical paths ship as headline evidence below
    trace_dir = tempfile.mkdtemp(prefix="bench_gw_trace_")
    prev_jdir = os.environ.get("DLROVER_TPU_JOURNAL_DIR")
    os.environ["DLROVER_TPU_JOURNAL_DIR"] = trace_dir
    # dense kv_pool sampling (§29): the leg is short, so the default
    # cadence would yield too few observatory points to summarize
    prev_cadence = os.environ.get("DLROVER_TPU_OBSERVATORY_SAMPLE_EVERY")
    os.environ["DLROVER_TPU_OBSERVATORY_SAMPLE_EVERY"] = "8"
    try:
        disagg = run_leg(disagg=True)
    finally:
        if prev_jdir is None:
            os.environ.pop("DLROVER_TPU_JOURNAL_DIR", None)
        else:
            os.environ["DLROVER_TPU_JOURNAL_DIR"] = prev_jdir
        if prev_cadence is None:
            os.environ.pop("DLROVER_TPU_OBSERVATORY_SAMPLE_EVERY", None)
        else:
            os.environ["DLROVER_TPU_OBSERVATORY_SAMPLE_EVERY"] = \
                prev_cadence

    # decode-stall p99 from the disagg leg's PRE-KILL histogram delta,
    # expressed in single-chunk units: the bounded-stall reading (<= 1
    # chunk per decode step of the block by construction, so <= 8 here;
    # conservative bucket upper bounds absorb scheduler noise)
    delta = disagg["stall_delta"]
    total = sum(delta)
    p99_s = 0.0
    if total:
        acc = 0
        for i, n in enumerate(delta):
            acc += n
            if acc >= 0.99 * total:
                p99_s = float(
                    stall_bounds[min(i, len(stall_bounds) - 1)])
                break
    extra["gateway_stall_p99_s"] = round(p99_s, 4)
    extra["gateway_chunk_s"] = round(chunk_s, 4)
    extra["gateway_stall_p99_bound_chunks"] = round(
        p99_s / max(chunk_s, 1e-6), 2)

    extra["gateway_req_per_s"] = disagg["req_per_s"]
    extra["gateway_p95_s"] = disagg["p95_s"]
    extra["gateway_failed"] = unified["failed"] + disagg["failed"]
    extra["gateway_replicas_restored"] = disagg.get(
        "replicas_restored")
    extra["gateway_ttft_p95_s"] = disagg["ttft_p95_s"]
    extra["gateway_itl_p95_s"] = disagg["itl_p95_s"]
    extra["gateway_ttft_p95_unified_s"] = unified["ttft_p95_s"]
    if disagg["ttft_p95_s"]:
        extra["gateway_disagg_ttft_speedup"] = round(
            unified["ttft_p95_s"] / disagg["ttft_p95_s"], 2)
    for t in tenants:
        for k, v in disagg["tenants"][t].items():
            extra[f"gateway_{t}_{k}"] = v
        extra[f"gateway_{t}_ttft_p95_unified_s"] = \
            unified["tenants"][t]["ttft_p95_s"]
    extra["gateway_config"] = (
        f"gpt2-small decode x{replicas} + "
        f"prefill x1 slots={geo['slots']} kv_pages={geo['kv_pages']} "
        f"P={P} rate={rate_hz}/s n={n_requests} "
        f"kill@backlog<{n_requests // 4} (both legs) vs unified "
        f"x{replicas} dense"
    )

    # assemble the disagg leg's request traces (§27): the slowest
    # request's critical path names where its TTFT went, and the phase
    # children must tile its wall (the 5% acceptance bound lives in
    # tests/test_gateway.py — here the fraction is evidence)
    import shutil
    try:
        from dlrover_tpu.telemetry import trace as trace_mod
        roots = trace_mod.build_forest(
            trace_mod.load_spans([trace_dir]))
        reqs = [r for r in trace_mod.find_request_roots(roots)
                if r.span.fields.get("disagg")]
        if reqs:
            slowest = max(reqs, key=lambda n: n.dur)
            segs = trace_mod.critical_path(slowest)
            top = max(segs, key=lambda s: s["self_s"])
            phases = trace_mod.request_phases(slowest)
            phase_sum = sum(v for k, v in phases.items()
                            if k != "wall_s")
            extra["gateway_trace_requests"] = len(reqs)
            extra["gateway_trace_critical_path_s"] = round(
                slowest.dur, 4)
            extra["gateway_trace_critical_path_hops"] = len(segs)
            extra["gateway_trace_critical_path_top"] = (
                f"{top['name']}={top['self_s']:.4f}s")
            extra["gateway_trace_phase_sum_frac"] = round(
                phase_sum / max(slowest.dur, 1e-9), 4)
    except Exception as e:  # noqa: BLE001 - trace evidence is a rider
        extra["gateway_trace_error"] = repr(e)

    # serving-observatory headlines (§29) from the disagg leg's
    # journaled kv_pool samples: page-pool pressure, COW share
    # headroom and the speculative-decoding acceptance prior —
    # ROADMAP-3's before/after baseline
    try:
        from dlrover_tpu.telemetry.report import load_events
        kv = [e for e in load_events(trace_dir)
              if e.get("name") == "kv_pool"]
        if kv:
            occ = sorted(float(e.get("occupancy", 0.0) or 0.0)
                         for e in kv)
            last = kv[-1]
            extra["gateway_kv_samples"] = len(kv)
            extra["gateway_kv_occupancy_p95"] = round(
                occ[min(len(occ) - 1, int(0.95 * len(occ)))], 4)
            extra["gateway_kv_high_water"] = int(max(
                int(e.get("high_water", 0) or 0) for e in kv))
            extra["gateway_pages_shareable_frac"] = round(max(
                float(e.get("shareable_frac", 0.0) or 0.0)
                for e in kv), 4)
            extra["gateway_cow_multiplier"] = round(max(
                float(e.get("cow_multiplier", 0.0) or 0.0)
                for e in kv), 4)
            # cumulative counters: the final sample is the aggregate
            extra["gateway_draft_accept_rate"] = round(
                float(last.get("accept_rate", 0.0) or 0.0), 4)
            extra["gateway_draft_tokens_scored"] = int(
                last.get("scored", 0) or 0)
            extra["gateway_accept_run_p50"] = int(
                last.get("accept_run_p50", 0) or 0)
            extra["gateway_accept_run_p95"] = int(
                last.get("accept_run_p95", 0) or 0)
    except Exception as e:  # noqa: BLE001 - observatory is a rider
        extra["gateway_kv_error"] = repr(e)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)

    # §31 live-lever A/B riders: the two §29 instruments promoted to
    # live levers, each isolated on a direct engine pair (the gateway
    # A/B above keeps its mixed-tenant trace; these measure the lever).
    from dlrover_tpu.common.constants import EnvKey

    saved_env = {k: os.environ.get(k) for k in
                 (EnvKey.SPEC_DEPTH, EnvKey.KV_COW,
                  "DLROVER_TPU_SERVING_OBSERVATORY",
                  "DLROVER_TPU_OBSERVATORY_SAMPLE_EVERY")}
    os.environ["DLROVER_TPU_SERVING_OBSERVATORY"] = "1"
    os.environ["DLROVER_TPU_OBSERVATORY_SAMPLE_EVERY"] = "8"
    try:
        # --- speculative decoding: spec-vs-plain on a self-predictable
        # greedy trace (the regime the n-gram drafter serves; random
        # prompts under a random-init model fall into cycles, so the
        # order-2 drafter has real runs to ride). Two warm passes per
        # leg: the jit block ladder's shapes depend on the evolving
        # accept-run prior, so pass 1 alone leaves cold compiles that
        # would land inside the timed pass.
        spec_geo = dict(geo, slots=2, max_len=256, decode_block=4,
                        kv_pages=64)
        spec_prompts = [
            [454, 126, 12, 214, 262, 346], [229, 389, 164, 351],
            [485, 180, 384, 142, 241, 56], [4, 47, 391, 116],
            [21, 485, 24], [443, 88, 403],
        ]
        spec_prompts += spec_prompts[:2]
        spec_trace = [
            (p, SamplingParams(temperature=0.0, max_new_tokens=200,
                               seed=900 + i))
            for i, p in enumerate(spec_prompts)
        ]

        def spec_build(depth):
            os.environ[EnvKey.SPEC_DEPTH] = str(depth)
            eng = InferenceEngine(params, cfg, **spec_geo)
            if depth:
                eng.warm_aot_verify()
            for _ in range(2):
                for p, sp in spec_trace:
                    eng.submit(p, sp)
                eng.run()
            return eng

        def spec_pass(eng, toks):
            t0 = time.monotonic()
            ids = [eng.submit(p, sp) for p, sp in spec_trace]
            out = {r.id: r.tokens for r in eng.run()}
            dt = time.monotonic() - t0
            pass_toks = [out[i] for i in ids]
            if toks is not None and pass_toks != toks:
                raise RuntimeError("spec leg nondeterministic")
            return dt, pass_toks

        # INTERLEAVED best-of-4: host speed drifts over the seconds a
        # leg takes (shared cores, frequency scaling), so timing the
        # legs sequentially hands whichever ran on the faster stretch
        # a bias larger than the lever's margin. Alternating passes
        # samples both legs across the same drift; min is the
        # least-contended estimate per leg (the bench_int8
        # best-of-compiles convention).
        p_eng, s_eng = spec_build(0), spec_build(4)
        plain_s = spec_s = None
        plain_toks = spec_toks = None
        # gc paused for the timed window: by this point the stage's
        # disagg A/B has grown the heap enough that gen-2 collections
        # land mid-pass, and they fall disproportionately on whichever
        # leg allocates more per step — a measurement artifact, not
        # engine cost. Collect once up front, time, restore.
        gc.collect()
        gc_was_enabled = gc.isenabled()
        gc.disable()
        try:
            for _ in range(4):
                dt, plain_toks = spec_pass(p_eng, plain_toks)
                plain_s = dt if plain_s is None else min(plain_s, dt)
                dt, spec_toks = spec_pass(s_eng, spec_toks)
                spec_s = dt if spec_s is None else min(spec_s, dt)
        finally:
            if gc_was_enabled:
                gc.enable()
        extra["gateway_spec_identical"] = plain_toks == spec_toks
        extra["gateway_spec_speedup"] = round(plain_s / spec_s, 3)
        extra["gateway_spec_accept_rate_live"] = round(
            s_eng.spec_accept_rate, 4)
        extra["gateway_spec_extra_tokens"] = s_eng.spec_extra_tokens_total
        extra["gateway_spec_collapsed"] = s_eng.spec_collapsed_total
    except Exception as e:  # noqa: BLE001 - riders must not kill bench
        extra["gateway_spec_error"] = repr(e)
    try:
        # --- COW KV pages: at a FIXED page budget, how many requests
        # with a shared system prefix can hold pages concurrently
        # (active + parked + reserving), on vs off. Prefixes are page
        # aligned so full prompt pages dedup against resident chains;
        # off-leg admissions block at the reserve step instead.
        # Decode runs span several pages so victims become parkable
        # (the anti-thrash quantum is one decoded page) and the holder
        # census exercises parked sharers, not just the two actives.
        from dlrover_tpu.serving.observatory import digest_share_stats

        pg = spec_geo["prefill_len"]     # page_size defaults to P
        sys_pages = 2
        req_pages = 2 * sys_pages        # sys + 1 tail + decode span
        uniq = req_pages - sys_pages
        cow_sys = list(rng.integers(0, cfg.vocab_size, sys_pages * pg))
        cow_geo = dict(spec_geo, max_len=req_pages * pg,
                       kv_pages=req_pages + 3 * uniq)
        cow_trace = []
        for i in range(8):
            tail = list(rng.integers(0, cfg.vocab_size, pg))
            cow_trace.append((cow_sys + tail, SamplingParams(
                temperature=0.0, max_new_tokens=(uniq - 1) * pg,
                seed=700 + i)))

        def cow_leg(on):
            os.environ[EnvKey.KV_COW] = "1" if on else "0"
            os.environ[EnvKey.SPEC_DEPTH] = "0"
            eng = InferenceEngine(params, cfg, **cow_geo)
            for p, sp in cow_trace:
                eng.submit(p, sp)
            peak, saved_frac, pred_frac, guard = 0, 0.0, 0.0, 0
            while eng.outstanding and guard < 100000:
                guard += 1
                eng.step()
                holders = (sum(p is not None for p in eng._slot_pages)
                           + len(eng._parked)
                           + (1 if eng._pending is not None else 0))
                peak = max(peak, holders)
                used = eng.kv_pages - len(eng._free_pages)
                saved = eng.cow_pages_saved
                if used + saved:
                    saved_frac = max(saved_frac,
                                     saved / (used + saved))
                rids = ([r.id for r in eng._active if r is not None]
                        + [pk.req.id for pk in eng._parked])
                share = digest_share_stats(
                    [eng._digest_store.pages(r) for r in rids])
                pred_frac = max(pred_frac, share["shareable_frac"])
            return eng, peak, saved_frac, pred_frac

        on_eng, peak_on, saved_on, pred_on = cow_leg(True)
        _, peak_off, _, pred_off = cow_leg(False)
        extra["gateway_cow_admitted_gain"] = round(
            peak_on / max(peak_off, 1), 2)
        extra["gateway_cow_pages_saved_frac"] = round(saved_on, 4)
        extra["gateway_cow_shareable_frac_pred"] = round(
            max(pred_on, pred_off), 4)
        extra["gateway_cow_shared_total"] = on_eng.cow_pages_shared_total
        extra["gateway_cow_peak_holders"] = f"{peak_on}on/{peak_off}off"
    except Exception as e:  # noqa: BLE001 - riders must not kill bench
        extra["gateway_cow_error"] = repr(e)
    finally:
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def bench_int8(extra: dict) -> None:
    """int8 MXU path vs bf16 on the llama-7B FFN stack (d=4096,
    d_ff=11008, 4 layers, 8192 tokens): forward + both grad
    contractions — the matmuls the quantized VJP accelerates.

    Microbench, not the full model, deliberately: the full 2-layer
    model-level grad measured 4.5-5.7s of which ~3.9s was the 32k-vocab
    CE/embedding path (int8 doesn't touch it, and its layouts proved
    unstable across compiles — the same config measured 1.9x and 0.82x
    on different runs). The FFN stack is what int8 claims to speed up.
    Sync is a full-reduction scalar: fetching any real grad leaf would
    ship ~90MB to the host, and a sliced fingerprint lets XLA
    dead-code-eliminate the backward entirely (both measured failure
    modes of earlier versions of this stage).

    Baseline pinning (round-3 Weak #6: bf16 layouts vary compile to
    compile, 128-173 TF/s): each impl is compiled in BENCH_INT8_COMPILES
    fresh jit instances and the fastest compilation's steady-state time
    is the quoted number, so the ratio compares best-layout to
    best-layout instead of whatever layout one compile happened to pick.
    """
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops.quantization import int8_matmul

    _require_tpu("int8")

    d, d_ff, tokens, n_layers = 4096, 11008, 8192, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 3 * n_layers + 1)
    params = [
        {"g": jax.random.normal(ks[3 * i], (d, d_ff), jnp.bfloat16) * .02,
         "u": jax.random.normal(ks[3 * i + 1], (d, d_ff),
                                jnp.bfloat16) * .02,
         "d": jax.random.normal(ks[3 * i + 2], (d_ff, d),
                                jnp.bfloat16) * .02}
        for i in range(n_layers)
    ]
    x = jax.random.normal(ks[-1], (tokens, d), jnp.bfloat16)

    def make_step(mm):
        def loss(params):
            h = x
            for w in params:
                gate = jax.nn.silu(mm(h, w["g"]))
                up = mm(h, w["u"])
                h = h + mm(gate * up, w["d"])
            return jnp.sum(h.astype(jnp.float32) ** 2) / tokens

        def step(params):
            g = jax.grad(loss)(params)
            return sum(jnp.sum(v.astype(jnp.float32))
                       for w in g for v in w.values())

        return step

    n_compiles = int(os.environ.get("BENCH_INT8_COMPILES", "2"))

    def run(mm) -> tuple[float, list[float]]:
        times = []
        for c in range(n_compiles):
            # a fresh jit of a fresh function object defeats jax's
            # C++-level executable cache, forcing an independent
            # compilation whose layout assignment can differ
            step = make_step(mm)
            f = jax.jit(lambda p, _c=c: step(p))
            float(jax.device_get(f(params)))
            float(jax.device_get(f(params)))
            t0 = time.monotonic()
            n = 10
            for _ in range(n):
                out = f(params)
            float(jax.device_get(out))
            times.append((time.monotonic() - t0) / n)
        return min(times), times

    bf16_s, bf16_all = run(lambda a, b: a @ b)
    int8_s, int8_all = run(int8_matmul)
    # contractions: 3 matmuls x (fwd + dx + dw) x L, minus layer 0's
    # g/u dx dots (their input is the closure constant x, so JAX emits
    # no transpose for them); each is 2*T*d*d_ff FLOPs
    flops = (3 * 3 * n_layers - 2) * 2 * tokens * d * d_ff
    extra.update(
        int8_ffn_bf16_s=round(bf16_s, 4),
        int8_ffn_s=round(int8_s, 4),
        int8_ffn_speedup=round(bf16_s / int8_s, 2),
        int8_ffn_bf16_tflops=round(flops / bf16_s / 1e12, 1),
        int8_ffn_bf16_compiles=[round(t, 4) for t in bf16_all],
        int8_ffn_compiles=[round(t, 4) for t in int8_all],
        int8_note=("llama-7B FFN stack (d=4096, ff=11008, L=4, 8k "
                   "tokens), fwd+bwd matmuls via ops/quantization.py; "
                   "best-of-N fresh compiles per impl"),
    )


def bench_checkpoint_1b(extra: dict) -> None:
    """GPT-2-1.5B-class (~1B-param, 12 GB fp32 state) checkpoint config
    (BASELINE configs 2-3; reference flash_checkpoint.md:317). Skipped
    with a note when host RAM can't hold state + arena + page cache."""
    gb = float(os.environ.get("BENCH_CKPT_1B_GB", "12"))
    try:
        avail_kb = int(next(
            line.split()[1]
            for line in open("/proc/meminfo")
            if line.startswith("MemAvailable")
        ))
    except (OSError, StopIteration, ValueError):
        avail_kb = 0
    if avail_kb and avail_kb < gb * 3 * (1 << 20):
        extra["ckpt1b_skipped"] = (
            f"need ~{gb * 3:.0f}GB RAM, have {avail_kb >> 20}GB"
        )
        return
    bench_checkpoint(extra, gb=gb, prefix="ckpt1b_")


def bench_7b_aot(extra: dict, stage_budget_s: float = 600.0) -> None:
    """Llama-7B FSDP on a virtual v5p-128 mesh, AOT: compiles the full
    sharded train step and reports per-device memory/FLOPs/collectives
    without touching hardware (parallel/aot_report.py). Subprocess so
    the 128-device CPU backend can't collide with the live TPU client."""
    import subprocess

    if os.environ.get("BENCH_7B_AOT", "1") == "0":
        return
    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (env.get("XLA_FLAGS", "")
                      + " --xla_force_host_platform_device_count=128"
                      ).strip(),
        "PYTHONPATH": env.get("PYTHONPATH", "") + os.pathsep + repo,
    })
    proc = subprocess.run(
        [sys.executable, "-m", "dlrover_tpu.parallel.aot_report",
         "--model", os.environ.get("BENCH_AOT_MODEL", "llama2-7b"),
         "--strategy", "fsdp", "--batch", "128", "--seq", "4096"],
        env=env, cwd=repo, capture_output=True, text=True,
        timeout=max(60, stage_budget_s - 15),
    )
    line = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() \
        else ""
    try:
        extra["aot_7b"] = json.loads(line)
    except json.JSONDecodeError:
        extra["aot_7b_error"] = (proc.stderr or line)[-400:]


def bench_autopilot(extra: dict) -> None:
    """Strategy autopilot (DESIGN.md §24.5), CPU-runnable: (a) plan the
    tiny config via AOT enumeration, train it, record the measurement
    into a per-run history, re-plan — the cached list must re-rank from
    the measured entry (journaled `autopilot_plan source=history`) and
    agree with a fresh measurement within 25%; (b) a seeded forced-
    contradiction leg (wrong-estimate injection) times the closed-loop
    retune and reports the post-retune MFU delta under a synthetic CPU
    peak."""
    import functools
    import statistics

    import jax
    import optax

    from dlrover_tpu.autopilot import (
        AutopilotController,
        PlanHistory,
        load_or_plan,
    )
    from dlrover_tpu.autopilot import apply as autopilot_apply
    from dlrover_tpu.models import transformer as tfm
    from dlrover_tpu.parallel.strategy import dp, zero1
    from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer
    from dlrover_tpu.trainer.train_step import compile_train

    cfg = tfm.CONFIGS["tiny"]
    seq, bsz, steps = 16, 8, 14
    n_dev = len(jax.devices())
    # synthetic peak so MFU is computable on CPU (the cost model's own
    # CPU constant); on a real TPU the true peak applies upstream
    peak = 2e11

    kwargs = dict(
        model="tiny",
        loss_fn_for=lambda s, m: tfm.make_loss_fn(cfg, s, m),
        init_params_fn=functools.partial(tfm.init_params, cfg),
        logical_params=tfm.logical_axes(cfg),
        optimizer=optax.adamw(1e-3),
        example_batch={
            "tokens": np.zeros((1, bsz, seq + 1), np.int32)
        },
        batch=bsz, seq=seq, model_cfg=cfg,
        points=[(dp(), "spmd"), (zero1(), "spmd")],
    )

    def batches(n, seed=4242):
        for i in range(n):
            g = np.random.Generator(np.random.Philox(key=seed + i))
            yield {"tokens": g.integers(
                0, cfg.vocab_size, (1, bsz, seq + 1), dtype=np.int32
            )}

    def launch(plan):
        strategy = plan.strategy()
        mesh = strategy.build_mesh()
        compiled = compile_train(
            strategy=strategy, mesh=mesh,
            loss_fn=kwargs["loss_fn_for"](strategy, mesh),
            init_params_fn=kwargs["init_params_fn"],
            logical_params=kwargs["logical_params"],
            optimizer=kwargs["optimizer"],
        )
        return compiled, compiled.init(jax.random.PRNGKey(0))

    def run(compiled, state, n, hook=None):
        trainer = ElasticTrainer(
            compiled, global_batch_size=bsz,
            micro_batch_size=max(1, bsz // n_dev), model_name="tiny",
        )
        trainer.retune_hook = hook
        step_walls: list[float] = []
        last = [time.monotonic()]

        def on_step(_s, m):
            jax.device_get(m["loss"])  # pace host to device on CPU
            now = time.monotonic()
            step_walls.append(now - last[0])
            last[0] = now

        trainer.run_batches(state, batches(n), max_steps=n,
                            on_step=on_step)
        return trainer, step_walls

    with tempfile.TemporaryDirectory() as tmp:
        hist = PlanHistory(db_path=os.path.join(tmp, "hist.sqlite"))
        cache = os.path.join(tmp, "plan.json")
        ranked = load_or_plan(cache, history=hist, **kwargs)
        plan = ranked.winner
        compiled, state = launch(plan)
        _, walls = run(compiled, state, steps)
        measured = statistics.median(walls[1:])  # drop the compile step
        # key by the plan's stamped hbm_gb: the re-plan's lookup uses
        # the same envelope-derived key (nonzero whenever
        # DLROVER_TPU_DEVICE_HBM_BYTES or a real TPU states a peak)
        hist.record(plan.strategy_json, measured, model="tiny",
                    n_devices=n_dev, batch=bsz, seq=seq,
                    hbm_gb=plan.hbm_gb,
                    mfu=plan.pred_flops / measured / (peak * n_dev))

        # ---- history-seeded re-planning: cached list, measured entry
        ranked2 = load_or_plan(cache, history=hist, **kwargs)
        plan2 = ranked2.winner
        extra["autopilot_plan_source"] = plan2.source
        extra["autopilot_pred_step_s"] = round(plan2.pred_step_s, 5)
        compiled2, state2 = launch(plan2)
        _, walls2 = run(compiled2, state2, steps)
        remeasured = statistics.median(walls2[1:])
        extra["autopilot_measured_step_s"] = round(remeasured, 5)
        agree = (min(plan2.pred_step_s, remeasured)
                 / max(plan2.pred_step_s, remeasured)
                 if plan2.pred_step_s and remeasured else 0.0)
        extra["autopilot_agreement"] = round(agree, 3)
        if plan2.source != "history":
            raise RuntimeError(
                "history-seeded re-plan did not reuse the measured "
                f"entry (source={plan2.source})"
            )

        # ---- forced contradiction: wrong-estimate injection fires
        # exactly one retune; time it and report the MFU delta
        bad = ranked2.plans[0]
        alt = ranked2.plans[1]
        bad.pred_step_s = measured / 10.0
        bad.source = "history"
        ctrl = AutopilotController(
            tolerance=1.5, clear_ratio=1.2, action_streak=3,
            min_points=3, max_retunes=1,
        )
        ctrl.arm(bad, [alt])
        compiled3, state3 = launch(bad)
        apply_s: list[float] = []
        retuned_step: list[int] = []
        last = [time.monotonic()]

        def hook(step, st):
            now = time.monotonic()
            d = ctrl.observe_step_time(now - last[0])
            last[0] = now
            if d is None:
                return None
            applied = autopilot_apply.apply_plan(
                d.to_plan, state=st,
                loss_fn_for=kwargs["loss_fn_for"],
                init_params_fn=kwargs["init_params_fn"],
                logical_params=kwargs["logical_params"],
                optimizer=kwargs["optimizer"],
                path=d.path,
            )
            apply_s.append(applied.seconds)
            retuned_step.append(step)
            return applied.compiled, applied.state

        _trainer3, walls3 = run(compiled3, state3, steps, hook=hook)
        if retuned_step:
            k = retuned_step[0]  # 1-based step the decision fired on
            # decision -> resumed training: walls3[k] spans from the
            # hook's decision stamp through apply (program build/load +
            # state move/launder) to the first completed step on the
            # new plan (the hook re-bases last[] before applying)
            first_post = walls3[k] if len(walls3) > k else 0.0
            extra["autopilot_retune_seconds"] = round(first_post, 4)
            extra["autopilot_apply_s"] = round(apply_s[0], 4)
            pre = statistics.median(walls3[1:k]) if k > 1 \
                else walls3[0]
            post = statistics.median(walls3[k + 1:]) \
                if len(walls3) > k + 1 else first_post
            mfu_pre = plan2.pred_flops / pre / (peak * n_dev) \
                if pre else 0.0
            mfu_post = plan2.pred_flops / post / (peak * n_dev) \
                if post else 0.0
            extra["autopilot_retune_mfu_delta"] = round(
                mfu_post - mfu_pre, 4
            )
        extra["autopilot_retunes"] = len(retuned_step)
        hist.close()


def _hist_p95_bound(name: str, before: dict | None = None) -> float:
    """p95 upper-bound bucket of a registry histogram (optionally net of
    a ``before`` bucket snapshot) — the PR-12 stall-bucket idiom: exact
    p95s need raw samples, bucket bounds are what the scrape exposes."""
    from dlrover_tpu.telemetry.metrics import registry

    for fam in registry().snapshot():
        if fam["name"] != name:
            continue
        bounds = list(fam["buckets"]) + [float("inf")]
        for s in fam["samples"]:
            per = [float(c) for c in s.get("buckets", ())]
            if before is not None:
                prev = before.get(name, [0.0] * len(per))
                per = [c - p for c, p in zip(per, prev)]
            total = sum(per)
            if total <= 0:
                return 0.0
            running = 0.0
            for bound, c in zip(bounds, per):
                running += c
                if running >= 0.95 * total:
                    return bound
    return 0.0


def _hist_buckets(name: str) -> dict:
    from dlrover_tpu.telemetry.metrics import registry

    for fam in registry().snapshot():
        if fam["name"] == name:
            for s in fam["samples"]:
                return {name: [float(c) for c in s.get("buckets", ())]}
    return {}


def bench_embedding(extra: dict) -> None:
    """Elastic embedding fabric (DESIGN.md §25), CPU-only in-process:
    a 3-server hash ring under a seeded recsys-shaped lookup+apply load
    with async gradient streaming, surviving a seeded churn leg — shard
    server emb-1 killed mid-run (respawned, ring re-routed, rows
    restored from the verified checkpoint) and a 3→4 grow mid-run.
    Reports `lookups_per_s`, `apply_lag_p95`, `staleness_p95`, and
    `embedding_scale_moved_frac` (the ~1/N migration bound evidence).
    """
    import threading

    from dlrover_tpu.common.constants import EnvKey
    from dlrover_tpu.embedding.fabric import (
        FabricClient,
        FabricShardServer,
        start_local_fabric,
    )

    dim, fields, batch = 16, 8, 256
    steps, kill_at, grow_at = 240, 80, 160
    seed = 4242
    prev_journal = os.environ.get(EnvKey.JOURNAL_DIR)
    with tempfile.TemporaryDirectory() as tmp:
        journal_dir = os.path.join(tmp, "journal")
        ckpt_dir = os.path.join(tmp, "ckpt")
        os.environ[EnvKey.JOURNAL_DIR] = journal_dir
        coord = None
        servers: list = []
        client = None
        churn_err: list = []
        try:
            coord, servers = start_local_fabric(
                3, dim=dim, seed=seed, replicas=2, ckpt_dir=ckpt_dir,
            )
            client = FabricClient(
                coordinator_addr=coord.addr, dim=dim,
                retry_window_s=60.0,
            )
            rng = np.random.default_rng(seed)
            lag_before = _hist_buckets(
                "dlrover_tpu_embedding_apply_lag_seconds"
            )

            def churn_kill():
                try:
                    victim = servers[1]
                    victim.stop()          # rows gone with the process
                    fresh = FabricShardServer(
                        dim=dim, num_slots=2, member=victim.member,
                        seed=seed, host="127.0.0.1",
                    ).start()
                    servers[1] = fresh
                    # same ring, new addr: the route bump re-dials every
                    # client; only the dead shard's rows refill from the
                    # newest verified checkpoint
                    coord.repair(victim.member, fresh.addr)
                except Exception as e:  # noqa: BLE001 - surfaced below
                    churn_err.append(f"kill leg: {e}")

            def churn_grow():
                try:
                    grown = FabricShardServer(
                        dim=dim, num_slots=2, member="emb-3",
                        seed=seed, host="127.0.0.1",
                    ).start()
                    servers.append(grown)
                    coord.scale({s.member: s.addr for s in servers})
                except Exception as e:  # noqa: BLE001 - surfaced below
                    churn_err.append(f"grow leg: {e}")

            lookup_s: list[float] = []
            staleness: list[int] = []
            threads: list[threading.Thread] = []
            total_ids = 0
            t_run = time.monotonic()
            for step in range(1, steps + 1):
                ids = (rng.zipf(1.3, size=(batch, fields)).astype(
                    np.int64) % 1_000_000)
                t0 = time.monotonic()
                emb = client.lookup(ids)
                lookup_s.append(time.monotonic() - t0)
                total_ids += ids.size
                grads = (emb * 1e-3).reshape(-1, dim)
                client.apply("adam", ids, grads, lr=1e-2)
                staleness.append(client.staleness())
                if step == kill_at // 2:
                    client.persist(step)   # the churn leg's restore point
                if step in (kill_at, grow_at):
                    th = threading.Thread(
                        target=churn_kill if step == kill_at
                        else churn_grow, daemon=True,
                    )
                    th.start()
                    threads.append(th)
            for th in threads:
                th.join(timeout=60.0)
            client.drain(timeout=60.0)
            run_wall = time.monotonic() - t_run
            if churn_err:
                raise RuntimeError("; ".join(churn_err))

            extra["embedding_lookups_per_s"] = round(
                total_ids / sum(lookup_s)
            )
            extra["embedding_steps_per_s"] = round(steps / run_wall, 1)
            extra["embedding_apply_lag_p95_s"] = _hist_p95_bound(
                "dlrover_tpu_embedding_apply_lag_seconds", lag_before
            )
            extra["embedding_staleness_p95"] = float(
                np.percentile(staleness, 95)
            )
            # the grow's journaled evidence: moved rows / ring rows
            moved_frac = None
            for e in _bench_read_journal(journal_dir):
                if (e.get("name") == "embedding_scale" and e.get("ok")
                        and e.get("to_n") == 4):
                    moved_frac = (e["moved"]
                                  / max(1, e.get("total_rows", 0)))
            if moved_frac is None:
                raise RuntimeError("no journaled 3->4 embedding_scale")
            extra["embedding_scale_moved_frac"] = round(moved_frac, 4)
            if not moved_frac or moved_frac > 1.6 / 4:
                raise RuntimeError(
                    f"3->4 moved {moved_frac:.2f} of rows; ring bound "
                    "is ~1/N"
                )
        finally:
            if client is not None:
                client.close()
            if coord is not None:
                coord.stop()
            for s in servers:
                s.stop()
            if prev_journal is None:
                os.environ.pop(EnvKey.JOURNAL_DIR, None)
            else:
                os.environ[EnvKey.JOURNAL_DIR] = prev_journal


def _bench_read_journal(journal_dir: str) -> list[dict]:
    events = []
    try:
        with open(os.path.join(journal_dir, "events.jsonl"),
                  encoding="utf-8") as f:
            for line in f:
                try:
                    events.append(json.loads(line))
                except json.JSONDecodeError:
                    continue
    except OSError:
        pass
    return events


# ---------------------------------------------------------------------------
# Stage harness
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Stage:
    name: str
    fn: object          # callable(extra) or callable(extra, stage_budget_s)
    est_s: float        # expected cost (r05 rehearsal actuals; informational)
    deadline_s: float   # SIGALRM ceiling for the stage
    pass_budget: bool = False  # fn accepts stage_budget_s kwarg
    # stages that can do useful bounded work with LESS than their full
    # deadline (their measurement window scales with stage_budget_s) set
    # this lower gate: the stage starts whenever the remaining envelope
    # covers min_deadline_s, and its SIGALRM becomes min(deadline_s,
    # remaining) — the hard-envelope invariant (alarm <= remaining)
    # holds either way. 0 means the gate is the full deadline.
    min_deadline_s: float = 0.0


STAGES = [
    # headline stages first: by minute ~10 every number the round is
    # judged on has been emitted at least once. A stage only STARTS when
    # the remaining envelope covers its gate (r04 lesson: the est-based
    # gate let ckpt1b legally overrun the envelope by 200 s), so the run
    # can never exceed BENCH_BUDGET_S. Estimates track the r05
    # rehearsal actuals on this host (1473.7 s total, rc=0).
    Stage("ckpt", bench_checkpoint, est_s=45, deadline_s=150),
    Stage("ckpt1b", bench_checkpoint_1b, est_s=350, deadline_s=400),
    Stage("goodput", bench_goodput, est_s=290, deadline_s=420,
          pass_budget=True),
    Stage("mfu", bench_train_step, est_s=170, deadline_s=520),
    Stage("serving", bench_serving, est_s=200, deadline_s=340),
    Stage("gateway", bench_gateway, est_s=120, deadline_s=300),
    Stage("soak", bench_soak, est_s=105, deadline_s=160,
          pass_budget=True),
    Stage("chaos", bench_chaos, est_s=130, deadline_s=300,
          pass_budget=True, min_deadline_s=180),
    # control-plane saturation (CPU-only, no devices): 1k tier + the
    # delta-snapshot comparison fit in ~60 s; the 5k flat tier and the
    # 10k racked tier (§28, ~60 s — the rack fan-in makes 10k cheaper
    # than 5k flat) ride when the budget allows
    Stage("control_plane", bench_control_plane, est_s=300,
          deadline_s=560, pass_budget=True, min_deadline_s=90),
    Stage("int8", bench_int8, est_s=275, deadline_s=450),
    # strategy autopilot (CPU-runnable): plan-vs-measured agreement,
    # history-seeded re-planning, seeded forced-contradiction retune
    Stage("autopilot", bench_autopilot, est_s=60, deadline_s=200),
    # elastic embedding fabric (CPU-only, in-process): seeded churn —
    # shard-server kill+repair and a 3→4 ring grow mid-run
    Stage("embedding", bench_embedding, est_s=60, deadline_s=200),
    Stage("aot7b", bench_7b_aot, est_s=15, deadline_s=120,
          pass_budget=True),
    Stage("long_context", bench_long_context, est_s=80, deadline_s=300),
    # adaptive tail: lowrate sizes its measured window to whatever
    # envelope remains (>=260 s buys a ~160 s window at safety 1.25 on
    # top of the reused calibration), so it converts leftover budget
    # into driver-captured raw-goodput evidence instead of a skip
    Stage("goodput_lowrate", bench_goodput_lowrate, est_s=420,
          deadline_s=600, pass_budget=True, min_deadline_s=260),
    Stage("goodput_tpu", bench_goodput_tpu, est_s=250, deadline_s=420,
          pass_budget=True, min_deadline_s=320),
]

# the compact tail line: every number the round is judged on, small
# enough that ANY tail byte-window keeps it intact (r04 lesson: the
# cumulative line put ckpt/goodput FIRST and the driver's tail window
# cropped exactly those)
HEADLINE_KEYS = [
    "goodput", "goodput_at_baseline_rate", "goodput_lowrate_raw",
    "goodput_lowrate_failures_per_hr", "mfu", "mfu_medium", "mfu_large",
    "bubble_frac", "stage_compile_s",
    "goodput_stage_recompile_only_failed",
    "ckpt_save_block_s", "ckpt_restore_s", "ckpt1b_save_block_s",
    "ckpt1b_copy_s", "ckpt1b_restore_s", "ckpt1b_persist_parallel_s",
    "ckpt1b_restore_parallel_s", "serving_toks_per_s",
    "serving_prefix_cache_speedup", "gateway_req_per_s",
    "gateway_p95_s", "gateway_failed", "gateway_ttft_p95_s",
    "gateway_itl_p95_s", "gateway_ttft_p95_unified_s",
    "gateway_disagg_ttft_speedup", "gateway_stall_p99_bound_chunks",
    "int8_ffn_speedup", "autopilot_agreement", "autopilot_pred_step_s",
    "autopilot_retune_seconds", "autopilot_retune_mfu_delta",
    "embedding_lookups_per_s", "embedding_apply_lag_p95_s",
    "embedding_staleness_p95", "embedding_scale_moved_frac",
    "soak_completed", "soak_kills",
    "chaos_completed", "chaos_recovery_seconds", "chaos_goodput",
    "chaos_audit_ok", "chaos_partition_recovery_s",
    "chaos_reconnect_burst_p99",
    "cp_master_rpc_p99_ms_n1000", "cp_master_rpc_p99_ms_n5000",
    "cp_master_rpc_p99_ms_n10000", "cp_rack_p99_ratio_10k_vs_1k",
    "cp_rack_p99_within_2x_1k", "cp_racks_n10000",
    "cp_root_calls_per_agent_n10000", "cp_world_diff_bytes_frac",
    "cp_master_joins_per_s_n1000", "cp_master_joins_per_s_n5000",
    "cp_snapshot_ingest_ms_n1000", "cp_join_cost_ratio",
    "cp_snapshot_wire_reduction", "cp_snapshot_ingest_reduction",
    "cp_master_recovery_s_n1000", "cp_reregistered_nodes_n1000",
    "lc_best_speedup", "bench_total_s",
    "gateway_kv_occupancy_p95", "gateway_kv_high_water",
    "gateway_pages_shareable_frac", "gateway_cow_multiplier",
    "gateway_draft_accept_rate", "gateway_draft_tokens_scored",
    "gateway_accept_run_p50", "gateway_accept_run_p95",
    "gateway_spec_speedup", "gateway_spec_accept_rate_live",
    "gateway_spec_identical", "gateway_cow_admitted_gain",
    "gateway_cow_pages_saved_frac", "gateway_cow_shareable_frac_pred",
]


# ------------------------------------------------- trajectory compare
#
# `bench.py --compare OLD.json NEW.json` reads two committed
# BENCH_r0*.json wrappers (or raw bench stdout captures) and diffs
# their headline dicts. Keys are gated by CATEGORY, not blanket
# percentage: raw latencies and throughputs swing wildly across rounds
# whose stage configs legitimately changed (r06 ran 2 control-plane
# tiers in a 500s budget, r07 ran 3 in 1200s), so only genuine quality
# signals fail the run —
#   - failure counts (substring "fail"/"error"): any >10% increase,
#     or any increase from zero;
#   - booleans that flip true -> false;
#   - dimensionless quality ratios (goodput/mfu/*_speedup/
#     *_agreement/*_rate/*_completed): a >10% DROP.
# Everything else prints as an informational delta.

def _load_headline(path: str) -> dict:
    """Headline dict from a bench output file: the wrapper's embedded
    tail (committed BENCH_r0*.json shape) or raw stdout — in either
    case the LAST parseable line carrying a "headline" object wins
    (bench emits cumulative lines per stage; the last is the sweep)."""
    with open(path) as f:
        text = f.read()
    try:
        wrapper = json.loads(text)
    except json.JSONDecodeError:
        wrapper = None
    if isinstance(wrapper, dict):
        if isinstance(wrapper.get("headline"), dict):
            return wrapper["headline"]
        if isinstance(wrapper.get("tail"), str):
            text = wrapper["tail"]
    head = None
    for line in text.splitlines():
        line = line.strip()
        if '"headline"' not in line:
            continue
        try:
            doc = json.loads(line)
        except json.JSONDecodeError:
            continue  # the tail byte-window may crop older lines
        if isinstance(doc, dict) and isinstance(doc.get("headline"),
                                                dict):
            head = doc["headline"]
    if head is None:
        raise ValueError(f"no headline line found in {path}")
    return head


_QUALITY_SUFFIXES = ("_speedup", "_agreement", "_rate", "_completed",
                     "_frac_ok", "_gain", "_saved_frac", "_rate_live")


def _compare_category(key: str) -> str:
    low = key.lower()
    if "fail" in low or "error" in low:
        return "failure"
    if ("goodput" in low or "mfu" in low
            or low.endswith(_QUALITY_SUFFIXES)):
        return "quality"
    return "info"


def compare_headlines(old: dict, new: dict,
                      threshold: float = 0.10) -> tuple[list[str],
                                                        list[str]]:
    """Diff two headline dicts; returns (report lines, regressions)."""
    lines: list[str] = []
    regressions: list[str] = []
    for key in sorted(set(old) | set(new)):
        a, b = old.get(key), new.get(key)
        if a is None or b is None:
            lines.append(f"  {key:<36} "
                         f"{'(new)' if a is None else '(gone)'}  "
                         f"{b if a is None else a}")
            continue
        if isinstance(a, bool) or isinstance(b, bool):
            mark = ""
            if bool(a) and not bool(b):
                mark = "  << REGRESSION (true -> false)"
                regressions.append(key)
            lines.append(f"  {key:<36} {a} -> {b}{mark}")
            continue
        if not (isinstance(a, (int, float))
                and isinstance(b, (int, float))):
            if a != b:
                lines.append(f"  {key:<36} {a} -> {b}")
            continue
        delta = (b - a) / abs(a) if a else None
        pct = f"{100 * delta:+.1f}%" if delta is not None else "n/a"
        cat = _compare_category(key)
        mark = ""
        if cat == "failure" and (b > a * (1 + threshold)
                                 if a else b > a):
            mark = f"  << REGRESSION (failures up {pct})"
            regressions.append(key)
        elif cat == "quality" and a > 0 and b < a * (1 - threshold):
            mark = f"  << REGRESSION ({pct} on a quality metric)"
            regressions.append(key)
        lines.append(f"  {key:<36} {a} -> {b}  ({pct}){mark}")
    return lines, regressions


def compare_main(old_path: str, new_path: str) -> int:
    try:
        old = _load_headline(old_path)
        new = _load_headline(new_path)
    except (OSError, ValueError) as e:
        print(f"compare: {e}", file=sys.stderr)
        return 2
    lines, regressions = compare_headlines(old, new)
    print(f"headline diff: {old_path} -> {new_path}")
    print("\n".join(lines))
    if regressions:
        print(f"REGRESSIONS ({len(regressions)}): "
              f"{', '.join(regressions)}")
        return 1
    print("no gated regressions "
          "(failure counts, booleans, quality ratios all held)")
    return 0


def _result_line(extra: dict) -> str:
    save_s = extra.get("ckpt_save_block_s")
    return json.dumps({
        "metric": "ckpt_save_block_s",
        "value": save_s,
        "unit": "s",
        "vs_baseline":
            round(CKPT_SAVE_BASELINE_S / save_s, 2) if save_s else None,
        "extra": extra,
    })


def _headline_line(extra: dict, errors: list[str]) -> str:
    save_s = extra.get("ckpt_save_block_s")
    head = {k: extra[k] for k in HEADLINE_KEYS if k in extra}
    if errors:
        head["n_errors"] = len(errors)
    return json.dumps({
        "metric": "ckpt_save_block_s",
        "value": save_s,
        "unit": "s",
        "vs_baseline":
            round(CKPT_SAVE_BASELINE_S / save_s, 2) if save_s else None,
        "headline": head,
    })


def main(argv: list[str] | None = None) -> int:
    argv = list(argv or [])
    # trajectory compare mode: must intercept BEFORE stage selection
    # (the filter below drops "-"-prefixed args, which would turn the
    # two file operands into unknown stage names)
    if "--compare" in argv:
        i = argv.index("--compare")
        paths = argv[i + 1: i + 3]
        if len(paths) != 2 or any(p.startswith("-") for p in paths):
            print("usage: bench.py --compare OLD.json NEW.json",
                  file=sys.stderr)
            return 2
        return compare_main(paths[0], paths[1])
    extra: dict = {}
    errors: list[str] = []
    # optional stage-name filter: `python bench.py control_plane chaos`
    # runs only the named stages. Explicit argv only — callers invoking
    # main() in-process (the harness tests) always get the full sweep.
    selected = [a for a in argv if not a.startswith("-")]
    unknown = [s for s in selected
               if s not in {st.name for st in STAGES}]
    if unknown:
        print(f"unknown stage(s) {unknown}; "
              f"known: {[st.name for st in STAGES]}", file=sys.stderr)
        return 2
    # 1740 not 1800: the envelope must also absorb interpreter + jax
    # startup (~25 s) under a driver kill timer that may be exactly 30
    # minutes of WALL clock, not of bench time
    budget = float(os.environ.get("BENCH_BUDGET_S", "1740"))
    t_start = time.monotonic()
    extra["bench_budget_s"] = budget
    stage_times: dict = {}
    extra["stage_times"] = stage_times
    def emit() -> None:
        # one os.write of the whole buffer: Python signal handlers run
        # between bytecodes, never inside a C syscall, so the write is
        # atomic w.r.t. the SIGTERM handler — a handler-side emit can
        # never splice into a half-flushed line (r04 advisor finding on
        # the reentrant print). The leading newline re-anchors
        # line-start even if some library left a partial line on stdout.
        if errors:
            extra["errors"] = errors
        buf = ("\n" + _result_line(extra) + "\n"
               + _headline_line(extra, errors) + "\n")
        os.write(1, buf.encode())

    def on_alarm(signum, frame):  # noqa: ARG001
        raise StageTimeout()

    def on_term(signum, frame):  # noqa: ARG001
        errors.append("SIGTERM: flushed partial results")
        # ALWAYS emit here: even if the handler interrupted an emit
        # mid-buffer-build, this emit writes its own complete buffer in
        # one os.write (the interrupted one simply never lands — its
        # content is a subset of this one's)
        emit()
        # re-raise default so the driver still sees the termination
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
        os.kill(os.getpid(), signal.SIGTERM)

    signal.signal(signal.SIGALRM, on_alarm)
    signal.signal(signal.SIGTERM, on_term)

    for st in STAGES:
        if selected and st.name not in selected:
            continue
        left = budget - (time.monotonic() - t_start)
        gate = st.min_deadline_s or st.deadline_s
        if left < gate:
            stage_times[st.name] = f"skipped ({left:.0f}s left < " \
                                   f"gate {gate:.0f}s)"
            continue
        alarm_s = int(min(st.deadline_s, left))
        t0 = time.monotonic()
        signal.alarm(alarm_s)
        try:
            if st.pass_budget:
                st.fn(extra, stage_budget_s=alarm_s)
            else:
                st.fn(extra)
        except StageTimeout:
            errors.append(f"{st.name}: stage deadline ({alarm_s}s) hit")
        except Exception as e:  # noqa: BLE001
            errors.append(f"{st.name}: {type(e).__name__}: {e}")
        finally:
            signal.alarm(0)
        stage_times[st.name] = round(time.monotonic() - t0, 1)
        extra["bench_total_s"] = round(time.monotonic() - t_start, 1)
        emit()

    extra["bench_total_s"] = round(time.monotonic() - t_start, 1)
    emit()
    # a skipped tail is a successful bounded run; a stage that raised
    # or hit its deadline is not
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
