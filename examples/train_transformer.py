"""Elastic transformer training example.

The TPU-native analog of the reference's GPT-2 + Flash Checkpoint example
(BASELINE.md config 2; reference flow dlrover/trainer/torch/elastic_run.py
-> user script with Checkpointer). Run it under the agent:

    python -m dlrover_tpu.run --standalone examples/train_transformer.py \
        -- --model tiny --max-steps 50

Kill the training process mid-run: the agent persists the shm snapshot,
re-rendezvouses, respawns this script, and it resumes from the in-memory
checkpoint — the wow-path this example exists to demonstrate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

# runnable from a checkout without installing the package
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def parse_args(argv=None):
    p = argparse.ArgumentParser("train_transformer")
    p.add_argument("--model", default="tiny")
    p.add_argument("--attention", default="",
                   help="override the model's attention impl "
                        "(dense|flash|splash|ring|ulysses)")
    p.add_argument("--remat", default="",
                   help="per-layer remat policy (e.g. dots_no_batch, "
                        "save_attn); empty = model default")
    p.add_argument("--ce-chunks", type=int, default=0,
                   help="blockwise cross-entropy chunks (0 = model "
                        "default)")
    p.add_argument("--strategy", default="dp",
                   help="strategy preset name (parallel/strategy.py), "
                        "or 'auto' for the autopilot planner "
                        "(autopilot/planner.py: AOT-enumerated "
                        "strategy x mesh x schedule, cost-model/"
                        "history ranked, closed-loop retuned)")
    p.add_argument("--autopilot-history", default="",
                   help="measured-history sqlite for --strategy auto "
                        "(empty = <ckpt-dir>/autopilot_history.sqlite, "
                        "'0' disables history seeding/recording)")
    p.add_argument("--schedule", default="spmd",
                   choices=["spmd", "mpmd", "auto"],
                   help="pipeline runtime: spmd = the single-program "
                        "roll (parallel/pipeline.py), mpmd = per-stage "
                        "programs + host 1F1B (parallel/mpmd.py, "
                        "per-stage compile cache + recovery), auto = "
                        "cost-model gate (parallel/cost_model.py)")
    p.add_argument("--objective", default="clm", choices=["clm", "mlm"],
                   help="clm: causal next-token; mlm: BERT-class "
                        "bidirectional masked-LM (models/encoder.py)")
    p.add_argument("--max-steps", type=int, default=50)
    p.add_argument("--global-batch", type=int, default=8)
    p.add_argument("--micro-batch", type=int, default=0,
                   help="0 -> global_batch / dp (no accumulation)")
    p.add_argument("--seq", type=int, default=0, help="0 -> model max")
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--ckpt-dir", default="/tmp/dlrover_tpu_ckpt")
    p.add_argument("--ckpt-interval", type=int, default=10,
                   help="persist to storage every N steps")
    p.add_argument("--mem-ckpt-interval", type=int, default=1,
                   help="shm snapshot every N steps")
    p.add_argument("--dataset-size", type=int, default=100000)
    p.add_argument("--data-file", default="",
                   help="flat binary token file (trainer/token_dataset "
                        "pack_tokens format); empty = synthetic data")
    p.add_argument("--epochs", type=int, default=10)
    p.add_argument("--shard-size", type=int, default=256)
    p.add_argument("--sharded-ckpt", action="store_true",
                   help="per-shard snapshots + reshard-on-load (FSDP-style)")
    p.add_argument("--result-file", default="")
    p.add_argument("--goodput-log", default="",
                   help="append per-step goodput events (JSONL) here; "
                        "aggregate with utils/goodput.compute_goodput")
    p.add_argument("--log-interval", type=int, default=10)
    p.add_argument("--crash-at-step", type=int, default=0,
                   help="fault injection: hard-exit at this step "
                        "(first incarnation only unless --crash-always)")
    p.add_argument("--crash-always", action="store_true",
                   help="crash at --crash-at-step in every incarnation")
    p.add_argument("--crash-exit", type=int, default=17,
                   help="exit code for the injected crash (210=OOM, "
                        "211=hardware per the failure contract)")
    p.add_argument("--step-delay", type=float, default=0.0,
                   help="sleep this long after each step (fault-injection "
                        "tests pace the run so kills land at a known "
                        "training position)")
    p.add_argument("--crash-once-file", default="",
                   help="crash only if this marker file is absent "
                        "(created before crashing) — survives node "
                        "relaunches, unlike the restart-count gate")
    p.add_argument("--hang-at-step", type=int, default=0,
                   help="fault injection: wedge forever at this step "
                        "(first incarnation only) — exercises the "
                        "agent's hang detector")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)

    import jax
    import optax

    from dlrover_tpu.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.checkpoint.shm_handler import _leaf_paths
    from dlrover_tpu.models import transformer as tfm
    from dlrover_tpu.parallel.mesh import data_parallel_size
    from dlrover_tpu.parallel.strategy import PRESETS
    from dlrover_tpu.trainer import bootstrap
    from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer
    from dlrover_tpu.trainer.train_step import compile_train

    import dataclasses

    if not args.sharded_ckpt \
            and not os.environ.get("DLROVER_TPU_STANDBY_FILE"):
        # overlapped restore: kick off the storage read + integrity
        # verification NOW, so it runs concurrently with the
        # distributed/coordination bring-up inside init_from_env and the
        # XLA compile below; engine.load() joins it before the first
        # step. A STANDBY must not prefetch here — it is parked long
        # before the failure, so this read would see pre-failure state;
        # its prefetch starts from the agent's post-persist `.prepare`
        # signal instead (agent/standby.py), which is always fresh.
        from dlrover_tpu.checkpoint.engine import start_restore_prefetch

        start_restore_prefetch(args.ckpt_dir)

    ctx = bootstrap.init_from_env()
    print(f"[trainer] {bootstrap.describe_devices()}", flush=True)
    cfg = tfm.CONFIGS[args.model]
    if args.attention:
        cfg = dataclasses.replace(cfg, attention=args.attention)
    if args.remat:
        cfg = dataclasses.replace(cfg, remat_scan=True,
                                  remat_policy=args.remat)
    if args.ce_chunks:
        cfg = dataclasses.replace(cfg, ce_chunks=args.ce_chunks)
    seq = args.seq or cfg.max_seq_len

    if args.objective == "mlm":
        from dlrover_tpu.models.encoder import (
            encoder_config,
            make_mlm_loss_fn,
        )

        cfg = encoder_config(cfg)

        def loss_for(s, m):
            return make_mlm_loss_fn(cfg, s, m)
    else:
        def loss_for(s, m):
            return tfm.make_loss_fn(cfg, s, m)

    autopilot_plan = None
    autopilot_ranked = None
    autopilot_history = None
    if args.strategy == "auto":
        # the autopilot planner (DESIGN.md §24): AOT-enumerate feasible
        # (strategy x mesh x schedule) points, rank by the cost model
        # seeded from measured history, and launch the winner as a
        # typed Plan. Cached next to the checkpoints so an elastic
        # restart reuses the ranked list instead of burning the
        # recovery window on N candidate compiles.
        from dlrover_tpu.autopilot import PlanHistory, load_or_plan

        bsz = max(1, args.global_batch)
        if args.objective == "mlm":
            example_batch = {
                "tokens": np.zeros((1, bsz, seq), np.int32),
                "targets": np.zeros((1, bsz, seq), np.int32),
                "mlm_mask": np.ones((1, bsz, seq), bool),
            }
        else:
            example_batch = {
                "tokens": np.zeros((1, bsz, seq + 1), np.int32)
            }
        if args.autopilot_history != "0":
            autopilot_history = PlanHistory(
                db_path=args.autopilot_history or os.path.join(
                    args.ckpt_dir, "autopilot_history.sqlite"
                )
            )
        n_dev = len(jax.devices())
        ranked = load_or_plan(
            os.path.join(args.ckpt_dir, "autopilot_plan.json"),
            model=args.model,
            loss_fn_for=loss_for,
            init_params_fn=lambda rng: tfm.init_params(cfg, rng),
            logical_params=tfm.logical_axes(cfg),
            optimizer=optax.adamw(args.lr),
            example_batch=example_batch,
            batch=bsz, seq=seq,
            history=autopilot_history,
            model_cfg=cfg,
            # the MPMD schedule axis: only for the clm stage programs
            # and only when the world splits into whole stages
            mpmd_stages=(2 if args.objective == "clm"
                         and n_dev % 2 == 0 and n_dev >= 4 else 0),
        )
        autopilot_ranked = ranked
        autopilot_plan = ranked.winner
        strategy = autopilot_plan.strategy()
        print(f"[trainer] autopilot plan: {autopilot_plan.name} "
              f"(source={autopilot_plan.source}, pred "
              f"{autopilot_plan.pred_step_s:.4f}s/step, "
              f"{len(ranked.plans)} feasible)", flush=True)
    else:
        strategy = PRESETS[args.strategy]()

    # ---- schedule resolution (DESIGN.md §21): the MPMD runtime builds
    # per-stage programs instead of one SPMD step; the "auto" gate asks
    # the schedule-aware cost model which schedule this geometry favors
    schedule = args.schedule
    sx = getattr(strategy, "extra", {}) or {}
    if sx.get("mpmd"):
        schedule = "mpmd"
    pp_stages = int(sx.get("pipeline_stages", 0) or 0) or 2
    if schedule == "auto":
        from dlrover_tpu.parallel.mpmd import choose_schedule

        schedule, ests = choose_schedule(
            cfg, num_stages=pp_stages,
            step_batch=max(1, args.global_batch), seq=seq,
            microbatches=int(sx.get("pipeline_microbatches", 0) or 0),
            interleave=int(sx.get("pipeline_interleave", 1) or 1),
        )
        print(f"[trainer] schedule gate picked {schedule} "
              f"(est step s: { {k: round(v, 6) for k, v in ests.items()} })",
              flush=True)
    mpmd_mode = schedule == "mpmd"
    if mpmd_mode and args.objective == "mlm":
        raise SystemExit("--schedule mpmd supports the clm objective "
                         "only (the stage programs are token->CE)")

    if mpmd_mode:
        # stage submeshes are built by the runtime; dp is stage 0's
        # data axis (the batch-sharding world)
        compiled = None
        mesh = None
        dp = max(1, len(jax.devices()) // pp_stages)
    else:
        mesh = strategy.build_mesh()
        compiled = compile_train(
            strategy=strategy,
            mesh=mesh,
            loss_fn=loss_for(strategy, mesh),
            init_params_fn=lambda rng: tfm.init_params(cfg, rng),
            logical_params=tfm.logical_axes(cfg),
            optimizer=optax.adamw(args.lr),
        )
        dp = data_parallel_size(mesh)
    # honor the master's paral-config suggestion (e.g. OOM -> higher grad
    # accumulation at a fixed global batch) unless the user pinned one
    from dlrover_tpu.agent.config_tuner import ParalConfigReader

    paral = ParalConfigReader()
    micro = args.micro_batch
    if not micro:
        suggested_accum = int(paral.get("grad_accum_steps", 0) or 0)
        if suggested_accum > 0:
            micro = max(1, args.global_batch // (dp * suggested_accum))
            print(f"[trainer] paral-config: accum={suggested_accum} -> "
                  f"micro_batch={micro}", flush=True)
        else:
            micro = max(1, args.global_batch // dp)

    # ---- elastic compile cache (DESIGN.md §17): the train-step
    # executable for this exact (topology, model, strategy, shapes) may
    # already exist — compiled by the pre-failure incarnation, by the
    # fallback-AOT daemon for this world size, or by another node — so
    # recovery loads it in ~0.1s instead of re-paying the XLA compile.
    # state/batch abstracts come from eval_shape: no compile, no arrays.
    from dlrover_tpu.parallel import compile_cache as cc

    cache_client = cc.CompileCacheClient()
    if mpmd_mode:
        # per-stage programs, each load_or_compile'd under its own
        # stage fingerprint (DESIGN.md §21) — recovery after a
        # single-stage failure recompiles only that stage
        from dlrover_tpu.parallel.mpmd import MpmdTrain

        accum = max(1, args.global_batch // (micro * dp))
        compiled = MpmdTrain(
            cfg, strategy, optax.adamw(args.lr),
            num_stages=pp_stages,
            microbatches=int(sx.get("pipeline_microbatches", 0) or 0),
            seq=seq, step_batch=micro * dp, accum=accum,
            cache=cache_client, num_nodes=ctx.num_nodes,
            extra_fingerprint={"lr": args.lr,
                               "objective": args.objective},
        )
        mesh = compiled.mesh
        state_abs = compiled.abstract_state()
        print(f"[trainer] mpmd runtime: {compiled.num_stages} stages x "
              f"{compiled.microbatches} microbatches, "
              f"{'warm' if compiled.cache_hit else 'cold'} stage "
              f"programs, bubble bound "
              f"{compiled.bubble_bound:.3f}", flush=True)
    else:
        state_abs = jax.eval_shape(compiled.init, jax.random.PRNGKey(0))

    def _batch_abstract(mesh_, compiled_, micro_, accum_):
        step_batch = micro_ * data_parallel_size(mesh_)
        if args.objective == "mlm":
            shapes = {"tokens": ((accum_, step_batch, seq), np.int32),
                      "targets": ((accum_, step_batch, seq), np.int32),
                      "mlm_mask": ((accum_, step_batch, seq), np.bool_)}
        else:
            shapes = {"tokens": ((accum_, step_batch, seq + 1), np.int32)}
        return {
            k: jax.ShapeDtypeStruct(shp, dt,
                                    sharding=compiled_.batch_sharding)
            for k, (shp, dt) in shapes.items()
        }

    if not mpmd_mode:
        accum = max(1, args.global_batch // (micro * dp))
        state_abs_sharded = jax.tree.map(
            lambda leaf, sh: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                                  sharding=sh),
            state_abs, compiled.state_shardings,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
        )
        batch_abs = _batch_abstract(mesh, compiled, micro, accum)
        key, key_inputs = cc.compile_fingerprint(
            num_nodes=ctx.num_nodes,
            total_devices=len(jax.devices()),
            mesh_axes=dict(mesh.shape),
            model=cfg,
            strategy=strategy,
            args_signature=cc.abstract_signature((state_abs_sharded,
                                                  batch_abs)),
            extra={"lr": args.lr, "objective": args.objective},
        )
        aot = cc.load_or_compile(
            key, key_inputs,
            compile_fn=lambda: compiled.step.lower(
                state_abs_sharded, batch_abs).compile(),
            cache=cache_client,
        )
        compiled.step = aot.fn
        compiled.cache_hit = aot.cache_hit
        # the compiled program's FLOPs ride the AOT envelope (a warm
        # load never re-lowers just to count) and feed the live MFU
        # gauge
        compiled.flops_per_step = aot.flops
        verb = ("loaded from compile cache" if aot.cache_hit
                else "compiled")
        print(f"[trainer] train step {verb} in {aot.seconds:.2f}s "
              f"({aot.source}); "
              f"{aot.pallas_calls} Pallas custom calls in it",
              flush=True)

    # multi-node state is sharded across processes: only the sharded
    # engine can snapshot it (each node persists its addressable pieces)
    if args.sharded_ckpt or ctx.num_nodes > 1:
        from dlrover_tpu.checkpoint.sharded import ShardedCheckpointEngine

        state = compiled.init(jax.random.PRNGKey(0))
        engine = ShardedCheckpointEngine(
            args.ckpt_dir, node_id=ctx.node_id, node_rank=ctx.node_rank,
            world_size=ctx.num_nodes,
        )
        loaded = engine.load_sharded(state, compiled.state_shardings)
    else:
        engine = CheckpointEngine(args.ckpt_dir, node_id=ctx.node_id,
                                  node_rank=ctx.node_rank,
                                  world_size=ctx.num_nodes)
        shard_of = dict(_leaf_paths(compiled.state_shardings))
        # restore against the ABSTRACT template: every leaf arrives via
        # device_put from the snapshot, so a successful restore never
        # pays the init program's compile (the other recompile-class
        # cost on the recovery path)
        try:
            loaded = engine.load(
                state_abs,
                put=lambda name, arr: jax.device_put(arr, shard_of[name]),
                zero_copy=True,
            )
        except (KeyError, ValueError) as e:
            # snapshot from an older model/optimizer shape: fall back to
            # a fresh init rather than installing mismatched leaves
            print(f"[trainer] snapshot incompatible ({e}); starting "
                  "fresh", flush=True)
            loaded = None
        if loaded is None:
            state = compiled.init(jax.random.PRNGKey(0))
    resumed_from = 0
    if loaded is not None:
        resumed_from, state = loaded
        # drop the tuple's reference: the restored leaves must leave
        # the device once laundered, or a state of a third of HBM
        # (gpt2-medium with AdamW on a 16 GB chip) stays there twice
        loaded = None
        # restored leaves were built by device_put from host buffers;
        # the AOT step executable donates its inputs and skips pjit's
        # input re-staging, so they must be rebuilt into proper
        # per-device buffers first (see compile_cache.launder —
        # skipping this corrupts state on the CPU backend)
        state = cc.launder(state)
        print(f"[trainer] resumed from step {resumed_from}", flush=True)

    trainer = ElasticTrainer(
        compiled,
        global_batch_size=args.global_batch,
        micro_batch_size=micro,
        model_name=args.model,
        # what the MFU gauge divides: the model's own FLOPs, whatever
        # the strategy recomputes
        model_flops_per_step=(
            args.global_batch * seq * cfg.train_flops_per_token(seq)),
    )

    # ---- autopilot closed loop (DESIGN.md §24): arm the master-side
    # controller with the launched plan + ranked alternatives (it rides
    # the trainer's metrics-snapshot pushes), and hot-apply any retune
    # it sends back through the paral-config channel — the job never
    # restarts for a strategy change.
    if autopilot_plan is not None and not mpmd_mode:
        from dlrover_tpu.common.constants import EnvKey

        if ctx.node_rank == 0 and os.environ.get(EnvKey.MASTER_ADDR):
            from dlrover_tpu.agent.master_client import MasterClient

            try:
                MasterClient.singleton().report_autopilot_plan(
                    autopilot_plan.to_json(),
                    [p.to_json()
                     for p in autopilot_ranked.alternatives()],
                    step_batch=trainer.step_batch_size,
                )
            except (ConnectionError, RuntimeError, OSError) as e:
                print(f"[trainer] autopilot plan report failed: {e}",
                      flush=True)

        from dlrover_tpu.autopilot import Plan
        from dlrover_tpu.autopilot import apply as autopilot_apply

        apply_batch = {
            k: np.zeros(v.shape, v.dtype) for k, v in batch_abs.items()
        }

        vetoed: set = set()

        def _retune_hook(step: int, st):
            nonlocal autopilot_plan
            pj = paral.get("autopilot_plan", "")
            if not pj:
                return None
            try:
                target = Plan.from_json(pj)
            except (ValueError, TypeError, KeyError):
                return None
            if target.fingerprint == autopilot_plan.fingerprint \
                    or target.fingerprint in vetoed:
                return None
            if not autopilot_apply.can_apply(
                    autopilot_plan, target,
                    step_batch=trainer.step_batch_size):
                vetoed.add(target.fingerprint)
                print(f"[trainer] autopilot retune to {target.name} "
                      "not applicable in-process; ignoring", flush=True)
                return None
            applied = autopilot_apply.apply_plan(
                target,
                state=st,
                loss_fn_for=loss_for,
                init_params_fn=lambda rng: tfm.init_params(cfg, rng),
                logical_params=tfm.logical_axes(cfg),
                optimizer=optax.adamw(args.lr),
                model_cfg=cfg,
                path="hot" if dict(target.mesh_axes)
                == dict(autopilot_plan.mesh_axes) else "reshard",
                cache=cache_client,
                num_nodes=ctx.num_nodes,
                example_batch=apply_batch,
                extra_fingerprint={"lr": args.lr,
                                   "objective": args.objective},
            )
            autopilot_plan = target
            print(f"[trainer] autopilot retune applied: {target.name} "
                  f"in {applied.seconds:.2f}s (no restart)", flush=True)
            return applied.compiled, applied.state

        trainer.retune_hook = _retune_hook

    # ---- fallback-topology AOT daemon: pre-compile the N−1/N+1 worlds
    # in the background and publish them to the compile cache, so a
    # membership change finds its executable already resident. Compile
    # is host-side (parallel/dry_run.py does the same offline), so this
    # never touches the accelerator's execution stream. Multi-node only
    # by default: a standalone world has no neighbor topologies.
    from dlrover_tpu.common.constants import EnvKey

    fallback_on = os.environ.get(EnvKey.FALLBACK_AOT, "")
    if (fallback_on != "0" and (ctx.num_nodes > 1 or fallback_on == "1")
            and cc.aot_cache_enabled() and not mpmd_mode):
        def _build_for_nodes(n_nodes: int):
            devices = jax.devices()
            per_node = max(1, len(devices) // ctx.num_nodes)
            subset = devices[:n_nodes * per_node]
            if n_nodes == ctx.num_nodes or not subset \
                    or len(subset) != n_nodes * per_node:
                return None
            try:
                fb_mesh = strategy.build_mesh(subset)
            except (ValueError, AssertionError):
                return None  # mesh axes don't divide this world
            fb = compile_train(
                strategy=strategy, mesh=fb_mesh,
                loss_fn=loss_for(strategy, fb_mesh),
                init_params_fn=lambda rng: tfm.init_params(cfg, rng),
                logical_params=tfm.logical_axes(cfg),
                optimizer=optax.adamw(args.lr),
            )
            fb_dp = data_parallel_size(fb_mesh)
            fb_micro = max(1, args.global_batch // fb_dp)
            if args.global_batch % (fb_micro * fb_dp):
                return None
            fb_accum = args.global_batch // (fb_micro * fb_dp)
            fb_state = jax.eval_shape(fb.init, jax.random.PRNGKey(0))
            fb_state = jax.tree.map(
                lambda leaf, sh: jax.ShapeDtypeStruct(
                    leaf.shape, leaf.dtype, sharding=sh),
                fb_state, fb.state_shardings,
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
            )
            fb_batch = _batch_abstract(fb_mesh, fb, fb_micro, fb_accum)
            fb_key, fb_inputs = cc.compile_fingerprint(
                num_nodes=n_nodes,
                total_devices=len(subset),
                mesh_axes=dict(fb_mesh.shape),
                model=cfg,
                strategy=strategy,
                args_signature=cc.abstract_signature((fb_state, fb_batch)),
                extra={"lr": args.lr, "objective": args.objective},
            )
            return fb_key, fb_inputs, (
                lambda: fb.step.lower(fb_state, fb_batch).compile()
            )

        cc.FallbackPrecompiler(
            _build_for_nodes,
            world_sizes=[ctx.num_nodes - 1, ctx.num_nodes + 1],
            cache=cache_client,
        ).start()

    # ---- data: master-fed dynamic shards under the agent, local otherwise
    vocab = cfg.vocab_size
    rng_seed = 1234

    packed = None
    if args.data_file:
        # real data: flat binary token file, windowed (the master's
        # shard indices address windows)
        from dlrover_tpu.trainer.token_dataset import PackedTokenDataset

        packed = PackedTokenDataset(args.data_file, seq=seq)
        args.dataset_size = len(packed)

        def tokens_for(idx: int) -> np.ndarray:
            return packed[idx]["tokens"]
    else:
        def tokens_for(idx: int) -> np.ndarray:
            g = np.random.Generator(np.random.Philox(key=rng_seed + idx))
            return g.integers(0, vocab, seq + 1, dtype=np.int32)

    from dlrover_tpu.trainer.data import ElasticDataset, PrefetchLoader

    dataset = ElasticDataset(
        args.dataset_size, name="synthetic", shard_size=args.shard_size,
        num_epochs=args.epochs, shuffle=True, under_agent=ctx.under_agent,
    )
    if args.objective == "mlm":
        mask_id = vocab - 1

        def sample_fn(idx: int):
            # mask keyed per sample index (an independent Philox stream
            # from tokens_for): a resumed run reproduces the exact same
            # corruption, like the token stream itself
            t = tokens_for(idx)[:seq]
            g = np.random.Generator(
                np.random.Philox(key=(rng_seed << 32) ^ idx)
            )
            return t, g.random(t.shape) < 0.15

        def collate(samples):
            t = np.stack([s[0] for s in samples])
            m = np.stack([s[1] for s in samples])
            return {
                "tokens": np.where(m, mask_id, t).astype(np.int32),
                "targets": t,
                "mlm_mask": m,
            }
    else:
        sample_fn = tokens_for

        def collate(samples):
            return {"tokens": np.stack(samples)}

    loader = PrefetchLoader(
        dataset,
        sample_fn=sample_fn,
        collate=collate,
        accum=trainer.accum,
        batch_size=trainer.local_step_batch,
        config_reader=paral,
    )

    on_cpu = jax.devices()[0].platform == "cpu"

    def mem_interval() -> int:
        # Young-Daly tuned cadence from the master (paral-config push,
        # hot-applied — snapshot cadence is not compile-baked); the CLI
        # value stands until the tuner's first retune arrives
        suggested = int(paral.get("snapshot_interval", 0) or 0)
        return suggested if suggested > 0 else args.mem_ckpt_interval

    def checkpointer(step: int, st) -> None:
        if os.environ.get("DLROVER_TPU_DEBUG_LEAF"):
            import jax as _j
            print(f"[dbg] host={step} leaf={int(_j.device_get(st.step))}",
                  flush=True)
        if step % mem_interval() == 0:
            if step % args.ckpt_interval == 0:
                engine.save_to_storage(step, st)
            else:
                # zero-stall where safe; the engine self-gates
                # (sharded/CPU fall back to the sync path)
                engine.save_to_memory_async(step, st)

    losses: list[float] = []
    goodput = None
    if args.goodput_log and ctx.node_rank == 0:
        from dlrover_tpu.utils.goodput import GoodputRecorder

        goodput = GoodputRecorder(args.goodput_log,
                                  restart_count=ctx.restart_count)

    def _should_crash() -> bool:
        if args.crash_once_file:
            try:
                # O_EXCL create makes the once-claim atomic even when
                # several nodes share the marker path
                with open(args.crash_once_file, "x") as f:
                    f.write("crashed")
                return True
            except FileExistsError:
                return False
        return args.crash_always or ctx.restart_count == 0

    # On CPU, pace the host to the device each step: dispatch runs ahead
    # of execution by hundreds of steps there, so host-side step events
    # (goodput log) and snapshot timings would charge queue-drain waits
    # to the wrong step. In-process fetch is ~free on CPU; on TPU async
    # dispatch is the point.
    pace_host = on_cpu

    def on_step(step: int, metrics: dict) -> None:
        if pace_host:
            jax.device_get(metrics["loss"])
        if goodput is not None:
            goodput.step(step)
        if args.hang_at_step and step == args.hang_at_step \
                and ctx.restart_count == 0:
            print(f"[trainer] injected hang at step {step}", flush=True)
            while True:  # wedged: alive but no progress
                time.sleep(3600)
        if args.crash_at_step and step == args.crash_at_step \
                and _should_crash():
            print(f"[trainer] injected crash at step {step} "
                  f"(exit {args.crash_exit})", flush=True)
            sys.stdout.flush()
            os._exit(args.crash_exit)
        if step % args.log_interval == 0:
            loss = float(jax.device_get(metrics["loss"]))
            losses.append(loss)
            print(f"[trainer] step {step} loss {loss:.4f}", flush=True)
        if args.step_delay > 0:
            # sync first so the delay paces the DEVICE, not just dispatch
            jax.device_get(metrics["loss"])
            time.sleep(args.step_delay)

    start = time.monotonic()
    state = trainer.run_batches(
        state,
        iter(loader),
        max_steps=args.max_steps,
        on_step=on_step,
        checkpointer=checkpointer,
        checkpoint_interval=1,
    )
    loader.close()
    final_step = int(state.step)
    if goodput is not None:
        goodput.done()
        goodput.close()
    # persist this run's measurement into the autopilot history: the
    # next job with the same workload fingerprint ranks from evidence
    # (journaled `autopilot_plan source=history`) instead of the model
    if autopilot_plan is not None and autopilot_history is not None \
            and ctx.node_rank == 0:
        measured = trainer.efficiency.step_seconds()
        if measured and measured > 0:
            # key the record by the plan's STAMPED shape fields — the
            # planner's lookup keys on the same tuple (incl. hbm_gb
            # from the device envelope), and a mismatched key would
            # silently never seed a later ranking
            autopilot_history.record(
                autopilot_plan.strategy_json, measured,
                model=autopilot_plan.model or args.model,
                n_devices=autopilot_plan.n_devices or len(jax.devices()),
                batch=autopilot_plan.batch or max(1, args.global_batch),
                seq=autopilot_plan.seq or seq,
                hbm_gb=autopilot_plan.hbm_gb,
                mfu=trainer.efficiency.mfu(),
            )
            print(f"[trainer] autopilot history: recorded "
                  f"{measured:.4f}s/step for {autopilot_plan.name}",
                  flush=True)
    if autopilot_history is not None:
        autopilot_history.close()
    engine.save_to_storage(final_step, state)
    waited = engine.wait_for_persist(final_step, timeout=120)
    if not waited:
        print(f"[train] WARNING: final step {final_step} not durable "
              f"(newest committed: {waited.persisted_step})", flush=True)
    engine.close()

    if args.result_file and ctx.node_rank == 0:
        with open(args.result_file, "w") as f:
            json.dump(
                {
                    "final_step": final_step,
                    "resumed_from": resumed_from,
                    "restart_count": ctx.restart_count,
                    "num_nodes": ctx.num_nodes,
                    "last_loss": losses[-1] if losses else None,
                    "wall_s": round(time.monotonic() - start, 2),
                },
                f,
            )
    print(f"[trainer] done at step {final_step}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
