"""`correct` for a training cell, in a process of its own that holds the chip
after the job has let it go.

At the published widths and the cell's own batch: seeded weights (the
benchmark's, float32) and a seeded batch go through the program's loss
function as the job builds it (``tfm.make_loss_fn`` under the cell's
attention kernel, remat policy and ce-chunks, on the mesh ``compile_train``
uses) and through the plain reference. Compared: the loss, and the norm of
the gradient by the worst leaf. ``--control fp8`` puts the reference at the
precision below the configuration's in the program's place: the run that
must come out as not correct.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

from benchmark import harness, program


def program_loss_and_grads(cfg_file: dict, job: dict, params, tokens,
                           rehearse: bool):
    import jax

    from dlrover_tpu.models import transformer as tfm
    from dlrover_tpu.parallel.strategy import PRESETS

    base = program.program_config(cfg_file, rehearse)
    if not rehearse:
        base = dataclasses.replace(
            base, attention=job["attention"], remat_scan=True,
            remat_policy=job["remat"], ce_chunks=int(job["ce_chunks"]))
    strategy = PRESETS["dp"]()
    mesh = strategy.build_mesh()
    loss_fn = tfm.make_loss_fn(base, strategy, mesh)
    fn = jax.jit(jax.value_and_grad(
        lambda p, t: loss_fn(p, {"tokens": t})))
    value, grads = fn(params, tokens)
    return float(value), grads


def main(argv=None) -> int:
    p = argparse.ArgumentParser("benchmark.check_train")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--out", required=True)
    p.add_argument("--control", default="", choices=("", "fp8"))
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)

    workload = harness.load_json(os.path.join(
        harness.BENCH_DIR, "workloads", f"{args.workload}.json"))
    cfg = (program.tiny_config() if args.rehearse else harness.load_json(
        os.path.join(harness.BENCH_DIR, "configs",
                     f"{workload['config']}.json")))
    job, limits = workload["job"], workload["limits"]

    import jax
    import numpy as np

    from benchmark import traffic
    from benchmark.reference import gpt2
    from dlrover_tpu.trainer import bootstrap

    bootstrap.setup_compilation_cache()
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    t0 = time.monotonic()
    seq = 128 if args.rehearse else job["seq"]
    tokens = traffic.rng_for(args.seed, 0x636865).integers(
        0, cfg["vocab_size"], (job["global_batch"], seq + 1), dtype=np.int32)
    params = gpt2.init_params(cfg, args.seed)

    if args.control:
        loss_p, grads = gpt2.loss_and_grads(params, tokens, rows=2,
                                            precision=args.control)
    else:
        loss_p, grads = program_loss_and_grads(cfg, job, params, tokens,
                                               args.rehearse)
    norms_p = gpt2.leaf_norms(grads)
    del grads
    t1 = time.monotonic()
    loss_r, grads = gpt2.loss_and_grads(params, tokens, rows=2)
    norms_r = gpt2.leaf_norms(grads)
    del grads
    checks = [
        {"name": "loss_gap", "value": abs(loss_p - loss_r),
         "limit": limits["loss_gap"]},
        {"name": "grad_norm_gap", "value": gpt2.norm_gap(norms_p, norms_r),
         "limit": limits["grad_norm_gap"]},
    ]
    with open(args.out, "w") as f:
        json.dump({"device": device, "checks": checks,
                   "loss_program": loss_p, "loss_reference": loss_r,
                   "control": args.control,
                   "program_s": t1 - t0,
                   "reference_s": time.monotonic() - t1}, f)
    print(json.dumps(checks), file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
