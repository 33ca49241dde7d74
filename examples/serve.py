"""Serve a trained checkpoint with the continuous-batching engine.

The inference-side twin of examples/train_transformer.py: restore the
flash checkpoint it wrote (shm fast path or storage), then serve token
prompts through serving/engine.py. Prompts are one-per-line token id
lists ("12 7 99") on stdin or --prompt args; each line returns the
sampled continuation.

    python examples/train_transformer.py ... --ckpt-dir /tmp/ckpt
    python examples/serve.py --model tiny --ckpt-dir /tmp/ckpt \
        --prompt "5 9 2" --prompt "7 7 7" --max-new 32
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# runnable from a checkout without installing the package
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def parse_args(argv=None):
    p = argparse.ArgumentParser("serve")
    p.add_argument("--model", default="tiny")
    p.add_argument("--ckpt-dir", default="",
                   help="flash-checkpoint dir to restore params from "
                        "(a dir without a checkpoint is an error); "
                        "empty = random init (smoke testing)")
    p.add_argument("--prompt", action="append", default=[],
                   help="space-separated token ids; repeatable. "
                        "Reads stdin lines when omitted")
    p.add_argument("--max-new", type=int, default=32)
    p.add_argument("--temperature", type=float, default=0.8)
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--top-p", type=float, default=1.0)
    p.add_argument("--eos-id", type=int, default=-1)
    p.add_argument("--seed", type=int, default=-1,
                   help="per-request sampling seed (same seed -> same "
                        "continuation regardless of batching); -1 = "
                        "engine-generated")
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--max-len", type=int, default=0)
    p.add_argument("--prefill-len", type=int, default=0)
    p.add_argument("--decode-block", type=int, default=16)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)

    import jax

    from dlrover_tpu.models import transformer as tfm
    from dlrover_tpu.serving import InferenceEngine, SamplingParams
    from dlrover_tpu.trainer import bootstrap

    bootstrap.setup_compilation_cache()
    print(bootstrap.describe_devices(), file=sys.stderr)
    cfg = tfm.CONFIGS[args.model]
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))

    if args.ckpt_dir:
        from dlrover_tpu.checkpoint.engine import CheckpointEngine

        engine = CheckpointEngine(args.ckpt_dir)
        # the training state holds params under .params; serve only them
        from dlrover_tpu.trainer.train_step import TrainState

        import jax.numpy as jnp
        import optax

        template = TrainState(
            step=jnp.zeros((), jnp.int32), params=params,
            opt_state=optax.adamw(1e-3).init(params),
        )
        loaded = engine.load(template)
        engine.close()
        if loaded is None:
            raise SystemExit(f"no checkpoint found in {args.ckpt_dir}")
        step, state = loaded
        params = state.params
        # serve only the parameters: the template and the optimizer
        # moments (three more copies of the model) leave the device here
        del template, state, loaded
        print(f"restored step {step} from {args.ckpt_dir}",
              file=sys.stderr)

    eng = InferenceEngine(
        params, cfg, slots=args.slots, max_len=args.max_len or 0,
        prefill_len=args.prefill_len or 0,
        decode_block=args.decode_block,
    )
    sp = SamplingParams(
        temperature=args.temperature, top_k=args.top_k,
        top_p=args.top_p, max_new_tokens=args.max_new,
        eos_id=None if args.eos_id < 0 else args.eos_id,
        seed=None if args.seed < 0 else args.seed,
    )

    lines = args.prompt or [ln.strip() for ln in sys.stdin
                            if ln.strip()]
    for line in lines:
        eng.submit([int(t) for t in line.split()], sp)
    t0 = time.monotonic()
    results = eng.run()
    wall = time.monotonic() - t0
    total = sum(len(r.tokens) for r in results)
    for r in sorted(results, key=lambda r: r.id):
        print(json.dumps({
            "prompt": r.prompt, "tokens": r.tokens,
            "finish_reason": r.finish_reason,
        }))
    print(
        f"{len(results)} requests, {total} tokens in {wall:.2f}s "
        f"({total / max(wall, 1e-9):.0f} tok/s)", file=sys.stderr,
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
