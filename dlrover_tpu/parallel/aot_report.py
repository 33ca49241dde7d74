"""AOT strategy report: compile a training step for a virtual mesh and
print per-device memory/FLOPs/collective volume — no chips needed.

Reference analog: ATorch's dry-runner/analyser sizing a strategy before
committing cluster resources (atorch/auto/analyser/analyser.py:14). XLA
gives the numbers ahead-of-time: ``jit(...).lower().compile()`` yields
memory_analysis()/cost_analysis() for the target program, so a Llama-7B
FSDP plan for a v5p-128 pod can be validated on a laptop.

Usage (the launcher must point JAX at a virtual mesh BEFORE python
starts, e.g.):

    JAX_PLATFORMS=cpu \\
    XLA_FLAGS=--xla_force_host_platform_device_count=128 \\
    python -m dlrover_tpu.parallel.aot_report \\
        --model llama2-7b --strategy fsdp --batch 128 --seq 4096

Prints ONE JSON line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time


def main(argv=None) -> int:
    p = argparse.ArgumentParser("dlrover-tpu aot-report")
    p.add_argument("--model", default="llama2-7b")
    p.add_argument("--strategy", default="fsdp",
                   help="preset name (parallel/strategy.py PRESETS)")
    p.add_argument("--batch", type=int, default=128,
                   help="global batch size")
    p.add_argument("--seq", type=int, default=4096)
    p.add_argument("--remat", default="dots_no_batch")
    p.add_argument("--attention", default="")
    args = p.parse_args(argv)

    import jax
    import numpy as np
    import optax

    from dlrover_tpu.models import transformer as tfm
    from dlrover_tpu.parallel.dry_run import dry_run
    from dlrover_tpu.parallel.strategy import PRESETS
    from dlrover_tpu.trainer.train_step import compile_train

    cfg = tfm.CONFIGS[args.model]
    replace = {"max_seq_len": args.seq}
    if args.remat:
        replace.update(remat_scan=True, remat_policy=args.remat)
    if args.attention:
        replace["attention"] = args.attention
    cfg = dataclasses.replace(cfg, **replace)
    devices = jax.devices()
    strategy = PRESETS[args.strategy]()

    # ONE compiled program feeds both the analytic sizing and the AOT
    # dry-run — two builds would inevitably drift apart
    mesh = strategy.build_mesh(devices)
    compiled = compile_train(
        strategy=strategy, mesh=mesh,
        loss_fn=tfm.make_loss_fn(cfg, strategy, mesh),
        init_params_fn=lambda rng: tfm.init_params(cfg, rng),
        logical_params=tfm.logical_axes(cfg),
        optimizer=optax.adamw(1e-4),
    )
    state_abs = jax.eval_shape(compiled.init, jax.random.PRNGKey(0))

    # analytic per-device train-state footprint straight from the
    # shardings (XLA's memory_analysis on the CPU backend reports
    # global, not per-device, sizes — misleading for pod sizing)
    state_bytes = 0
    for leaf, sh in zip(
        jax.tree_util.tree_leaves(state_abs),
        jax.tree_util.tree_leaves(
            compiled.state_shardings,
            is_leaf=lambda x: hasattr(x, "shard_shape"),
        ),
    ):
        shard = sh.shard_shape(leaf.shape)
        n = 1
        for d in shard:
            n *= d
        state_bytes += n * leaf.dtype.itemsize

    def build_step(_strat):
        state_abstract = jax.tree.map(
            lambda leaf, s: jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=s
            ),
            state_abs, compiled.state_shardings,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
        )
        batch_abstract = {
            "tokens": jax.ShapeDtypeStruct(
                (1, args.batch, args.seq + 1), np.int32,
                sharding=compiled.batch_sharding,
            )
        }
        return compiled.step, (state_abstract, batch_abstract)

    t0 = time.monotonic()
    report = dry_run(build_step, strategy)
    line = {
        "model": args.model,
        "strategy": report.strategy_name,
        "devices": len(devices),
        "params": cfg.param_count,
        "batch": args.batch,
        "seq": args.seq,
        "ok": report.ok,
        "error": report.error[:300],
        "state_gb_per_device": round(state_bytes / 2**30, 3),
        # global-view XLA numbers (CPU backend); flops undercounts scan
        # bodies — recorded for cross-round tracking, not for sizing
        "xla_memory_analysis_gb": round(report.hbm_bytes / 2**30, 2),
        "xla_flops": report.flops,
        "comm_bytes": report.comm_bytes,
        "compile_s": round(time.monotonic() - t0, 1),
    }
    # analytic per-op-class FLOPs from the jaxpr (scan-aware, unlike
    # XLA's cost analysis above) — the Analyser's params/flops/memory
    # triple, completing the per-device sizing with true model FLOPs
    try:
        from dlrover_tpu.utils.profiler import flops_breakdown

        # reuse the already-traced state shapes (one build feeds all
        # numbers, per the design note above) rather than re-tracing
        # init, and RESOLVE the config so strategy extras that change
        # the model (attention kind/window, int8, pipeline shape) are
        # the ones counted — resolve_config's documented contract
        params_abs = state_abs.params
        rcfg = tfm.resolve_config(cfg, strategy)
        tokens = jax.ShapeDtypeStruct(
            (args.batch, args.seq + 1), np.int32
        )
        bd = flops_breakdown(
            lambda p, b: tfm.loss_fn(p, b, cfg=rcfg),
            params_abs, {"tokens": tokens},
        )
        line["analytic_fwd_flops"] = bd.get("total", 0.0)
        line["analytic_fwd_matmul_flops"] = bd.get("dot_general", 0.0)
    except Exception as e:  # noqa: BLE001 - sizing must still print
        line["analytic_fwd_flops_error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(line))
    return 0 if report.ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
