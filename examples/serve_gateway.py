"""Serve a checkpoint through the elastic gateway (HTTP front door).

The pool-of-replicas twin of examples/serve.py: N continuous-batching
engine replicas behind admission control, least-loaded + prefix-affinity
routing, preemption draining, and a telemetry-driven autoscaler that
resizes the pool through the ScalePlan path.

    python examples/serve_gateway.py --model tiny --replicas 2 \
        --max-replicas 4 --port 8000
    curl -s localhost:8000/v1/generate \
        -d '{"prompt": [5, 9, 2], "max_new_tokens": 16}'
    curl -s localhost:8000/healthz
    curl -s localhost:8000/metrics | grep dlrover_tpu_gateway

Kill tolerance demo: start with --preemption-file '/tmp/pre-{node_id}',
then `touch /tmp/pre-0` — replica 0 finishes its in-flight requests,
detaches, and the autoscaler brings a replacement.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

# runnable from a checkout without installing the package
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def parse_args(argv=None):
    p = argparse.ArgumentParser("serve_gateway")
    p.add_argument("--model", default="tiny")
    p.add_argument("--ckpt-dir", default="",
                   help="flash-checkpoint dir to restore params from "
                        "(a dir without a checkpoint is an error); "
                        "empty = random init (smoke testing)")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--replicas", type=int, default=2,
                   help="initial replica count (autoscaler floor "
                        "unless --min-replicas says otherwise)")
    p.add_argument("--min-replicas", type=int, default=0,
                   help="0 = use --replicas")
    p.add_argument("--max-replicas", type=int, default=4)
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--max-len", type=int, default=0)
    p.add_argument("--prefill-len", type=int, default=64)
    p.add_argument("--decode-block", type=int, default=8)
    p.add_argument("--prefix-cache-entries", type=int, default=8)
    p.add_argument("--prefill-replicas", type=int, default=0,
                   help="> 0 disaggregates: a prefill pool of this "
                        "size ships paged KV bundles to the decode "
                        "pool (DESIGN.md §23)")
    p.add_argument("--max-prefill-replicas", type=int, default=0,
                   help="0 = use --prefill-replicas")
    p.add_argument("--kv-pages", type=int, default=0,
                   help="physical KV pages per engine (paged "
                        "admission + park/resume; 0 = dense slots)")
    p.add_argument("--page-size", type=int, default=0,
                   help="tokens per KV page (default: prefill-len)")
    p.add_argument("--admission-deadline", type=float, default=30.0,
                   help="seconds of estimated queue wait past which "
                        "the gateway answers 429 + Retry-After")
    p.add_argument("--target-p95", type=float, default=0.0,
                   help="autoscaler latency objective in seconds "
                        "(0 = scale on queue/occupancy only)")
    p.add_argument("--autoscale-interval", type=float, default=2.0)
    p.add_argument("--preemption-file", default="",
                   help="notice-file template with {node_id} = replica "
                        "id (defaults to DLROVER_TPU_PREEMPTION_FILE)")
    return p.parse_args(argv)


def _load_params(args, cfg):
    import jax

    from dlrover_tpu.models import transformer as tfm

    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    if not args.ckpt_dir:
        return params
    import jax.numpy as jnp
    import optax

    from dlrover_tpu.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.trainer.train_step import TrainState

    engine = CheckpointEngine(args.ckpt_dir)
    template = TrainState(
        step=jnp.zeros((), jnp.int32), params=params,
        opt_state=optax.adamw(1e-3).init(params),
    )
    loaded = engine.load(template)
    engine.close()
    if loaded is None:
        raise SystemExit(f"no checkpoint found in {args.ckpt_dir}")
    step, state = loaded
    print(f"restored step {step} from {args.ckpt_dir}", file=sys.stderr)
    return state.params


def main(argv=None) -> int:
    args = parse_args(argv)

    from dlrover_tpu.gateway import (
        DisaggAutoscaler,
        Gateway,
        GatewayAutoscaler,
        GatewayHTTPServer,
        PoolScaler,
    )
    from dlrover_tpu.models import transformer as tfm
    from dlrover_tpu.serving import InferenceEngine
    from dlrover_tpu.telemetry import exposition
    from dlrover_tpu.trainer import bootstrap

    bootstrap.setup_compilation_cache()
    print(bootstrap.describe_devices(), file=sys.stderr)
    cfg = tfm.CONFIGS[args.model]
    params = _load_params(args, cfg)

    def engine_factory():
        return InferenceEngine(
            params, cfg, slots=args.slots,
            max_len=args.max_len or 0,
            prefill_len=args.prefill_len,
            decode_block=args.decode_block,
            prefix_cache_entries=args.prefix_cache_entries,
            kv_pages=args.kv_pages,
            page_size=args.page_size,
        )

    gateway = Gateway(
        engine_factory, replicas=args.replicas,
        prefill_len=args.prefill_len,
        prefill_replicas=args.prefill_replicas,
        admission_deadline_s=args.admission_deadline,
        preemption_file=args.preemption_file or None,
    )
    if args.prefill_replicas:
        autoscaler = DisaggAutoscaler(
            gateway,
            PoolScaler(gateway.prefill_pool, group="prefill"),
            PoolScaler(gateway.pool, group="decode"),
            min_prefill=args.prefill_replicas,
            max_prefill=max(args.max_prefill_replicas,
                            args.prefill_replicas),
            min_decode=args.min_replicas or args.replicas,
            max_decode=max(args.max_replicas,
                           args.min_replicas or args.replicas),
            interval_s=args.autoscale_interval,
            target_p95_s=args.target_p95,
        ).start()
    else:
        autoscaler = GatewayAutoscaler(
            gateway, PoolScaler(gateway.pool),
            min_replicas=args.min_replicas or args.replicas,
            max_replicas=max(args.max_replicas,
                             args.min_replicas or args.replicas),
            interval_s=args.autoscale_interval,
            target_p95_s=args.target_p95,
        ).start()
    server = GatewayHTTPServer(gateway, host=args.host,
                               port=args.port).start()
    exposition.start_from_env()  # optional extra bare /metrics port
    print(f"gateway on http://{args.host}:{server.port} "
          f"({args.replicas} x {args.model}, {args.slots} slots each); "
          "POST /v1/generate, GET /healthz, GET /metrics",
          file=sys.stderr)
    try:
        while True:
            time.sleep(5)
            stats = gateway.stats()
            print(f"[gateway] ready={stats['ready']} "
                  f"queue={stats['queue_depth']} "
                  f"occ={stats['slot_occupancy']:.2f}", file=sys.stderr)
    except KeyboardInterrupt:
        pass
    finally:
        server.stop()
        autoscaler.stop()
        gateway.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
