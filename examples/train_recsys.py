"""Sparse recommendation training: KvVariable embeddings + JAX dense tower.

The TPU-native analog of the reference's tfplus DeepRec PS-worker
recommendation path (BASELINE.md config 5; tfplus/kv_variable/python/ops/
embedding_ops.py over the C++ KvVariable kernels). Architecture: unbounded
sparse ids live in the host-side C++ table (dlrover_tpu/embedding); each
step gathers the batch's rows into a dense [B, F, dim] block that goes to
the device; the dense tower trains under jit; embedding-row gradients come
back with jax.grad and apply host-side via sparse GroupAdam.

Run standalone or under the agent:
    python -m dlrover_tpu.run --standalone examples/train_recsys.py -- \
        --steps 200
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def parse_args(argv=None):
    p = argparse.ArgumentParser("train_recsys")
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--fields", type=int, default=8,
                   help="sparse feature fields per example")
    p.add_argument("--dim", type=int, default=16)
    p.add_argument("--id-space", type=int, default=1_000_000)
    p.add_argument("--lr", type=float, default=1e-2)
    p.add_argument("--group-lasso", type=float, default=0.0)
    p.add_argument("--sparse-optimizer", default="adam",
                   choices=["adam", "group_adam", "adagrad",
                            "group_adagrad", "ftrl", "group_ftrl",
                            "radam"],
                   help="host-side sparse optimizer for the embedding "
                        "table (reference: tfplus training_ops.cc "
                        "family)")
    p.add_argument("--ckpt-dir", default="")
    p.add_argument("--result-file", default="")
    p.add_argument("--log-interval", type=int, default=50)
    p.add_argument("--spill-dir", default="",
                   help="hybrid storage: spill cold rows (freq <= "
                        "--spill-max-freq) to a file in this dir every "
                        "--spill-interval steps, bounding host memory")
    p.add_argument("--spill-interval", type=int, default=100)
    p.add_argument("--spill-max-freq", type=int, default=1)
    p.add_argument("--incremental-ckpt", action="store_true",
                   help="with --ckpt-dir: base+delta embedding "
                        "checkpoints (only changed rows per save) every "
                        "--log-interval steps")
    p.add_argument("--table-shards", type=int, default=0,
                   help="shard the embedding table across N server "
                        "processes (the elastic-PS analog, "
                        "embedding/service.py); 0 = in-process table")
    p.add_argument("--table-coordinator", default="",
                   help="connect to an existing embedding coordinator "
                        "instead of spawning local shard servers")
    p.add_argument("--fabric", type=int, default=0,
                   help="elastic embedding fabric (DESIGN.md §25): run "
                        "the table as a consistent-hash ring of N "
                        "in-process shard servers with async gradient "
                        "streaming and verified shard checkpoints")
    p.add_argument("--fabric-coordinator", default="",
                   help="connect to an existing fabric coordinator "
                        "(host:port) instead of spawning a local ring")
    p.add_argument("--sync-apply", action="store_true",
                   help="fabric only: block every step on the sparse "
                        "update instead of streaming it asynchronously")
    p.add_argument("--serve-port", type=int, default=0,
                   help="fabric only: serve the LIVE training ring "
                        "over HTTP on this port (POST "
                        "/v1/embedding/lookup — the train+serve-from-"
                        "one-table path; 0 = off)")
    return p.parse_args(argv)


def _start_fabric(args):
    """Fabric-mode table: ring client (async apply), optional restore,
    optional live-serving HTTP front door. Returns (client, cleanup,
    persist_fn) — persist_fn(step) runs the drain barrier + verified
    ring checkpoint when a checkpoint dir is configured."""
    from dlrover_tpu.embedding.fabric import FabricClient, start_local_fabric

    coord = None
    servers: list = []
    http = None
    serve_client = None
    fabric_ckpt = (os.path.join(args.ckpt_dir, "embedding-fabric")
                   if args.ckpt_dir else "")
    if args.fabric_coordinator:
        coord_addr = args.fabric_coordinator
    else:
        coord, servers = start_local_fabric(
            args.fabric, dim=args.dim, num_slots=2, seed=1234,
            ckpt_dir=fabric_ckpt,
        )
        coord_addr = coord.addr
    client = FabricClient(coordinator_addr=coord_addr, dim=args.dim,
                          async_apply=not args.sync_apply)
    restored = None
    if coord is not None and fabric_ckpt:
        restored = coord.restore()
        if restored:
            print(f"[recsys] fabric restored step {restored['step']} "
                  f"({restored['rows']} rows from a "
                  f"{restored['num_shards']}-shard save onto "
                  f"{len(client.route.members)} shards)", flush=True)
            client.resume_from(restored["applied_version"])
    if args.serve_port:
        from dlrover_tpu.gateway.server import GatewayHTTPServer

        serve_client = FabricClient(coordinator_addr=coord_addr,
                                    dim=args.dim, mode="serve")
        http = GatewayHTTPServer(
            None, host="127.0.0.1", port=args.serve_port,
            embedding_client=serve_client,
        ).start()
        print(f"[recsys] live embedding lookups on port {http.port}",
              flush=True)

    def persist_fn(step: int) -> None:
        info = client.persist(step)
        print(f"[recsys] fabric ckpt step {step}: {info['rows']} rows "
              f"across {info['num_shards']} shards "
              f"(applied v{info['applied_version']})", flush=True)

    def cleanup() -> None:
        if http is not None:
            http.stop()
        if serve_client is not None:
            serve_client.close()
        client.close()
        if coord is not None:
            coord.stop()
        for s in servers:
            s.stop()

    # an external coordinator owns its own checkpoint dir; a local ring
    # persists only when --ckpt-dir gave it one
    can_persist = bool(fabric_ckpt or args.fabric_coordinator)
    return client, cleanup, (persist_fn if can_persist else None)


def _spawn_sharded_table(args, ckpt_dir: str):
    """Spawn --table-shards local shard-server processes + coordinator;
    returns (client, cleanup). The multi-host deployment runs the same
    ``python -m dlrover_tpu.embedding.service`` servers on CPU hosts and
    passes --table-coordinator instead."""
    import atexit
    import subprocess

    from dlrover_tpu.embedding.service import (
        EmbeddingCoordinator,
        ShardedKvClient,
    )

    procs, addrs = [], []

    def _kill_procs():
        for p_ in procs:
            p_.terminate()
        for p_ in procs:
            try:
                p_.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p_.kill()

    try:
        for i in range(args.table_shards):
            cmd = [sys.executable, "-m", "dlrover_tpu.embedding.service",
                   "--dim", str(args.dim), "--host", "127.0.0.1",
                   "--index", str(i),
                   "--num-shards", str(args.table_shards)]
            if ckpt_dir:
                cmd += ["--ckpt-dir",
                        os.path.join(ckpt_dir, "embedding-shards")]
            if args.spill_dir:
                cmd += ["--spill-dir", args.spill_dir]
            proc = subprocess.Popen(
                cmd, stdout=subprocess.PIPE, text=True,
                env={**os.environ, "JAX_PLATFORMS": "cpu"},
            )
            procs.append(proc)
            line = proc.stdout.readline().strip()
            if not line.startswith("PORT "):
                raise RuntimeError(
                    f"shard server {i} failed to start: {line!r}")
            addrs.append(f"127.0.0.1:{line.split()[1]}")
        coord = EmbeddingCoordinator(addrs, host="127.0.0.1").start()
        client = ShardedKvClient(
            coordinator_addr=f"127.0.0.1:{coord.port}", dim=args.dim
        )
    except BaseException:
        _kill_procs()
        raise

    def cleanup():
        if procs:
            client.close()
            coord.stop()
            _kill_procs()
            procs.clear()

    # a mid-training crash must not orphan the server processes (their
    # main loop sleeps forever); atexit covers every interpreter exit
    # path short of SIGKILL, and cleanup() is idempotent for the
    # success path's explicit call
    atexit.register(cleanup)
    return client, cleanup


def main(argv=None) -> int:
    args = parse_args(argv)

    import jax
    import jax.numpy as jnp
    import optax

    from dlrover_tpu.embedding import KvEmbeddingTable
    from dlrover_tpu.trainer import bootstrap

    ctx = bootstrap.init_from_env()
    sharded_cleanup = None
    inc_mgr = None
    fabric_persist = None
    if args.fabric or args.fabric_coordinator:
        table, sharded_cleanup, fabric_persist = _start_fabric(args)
    elif args.table_coordinator:
        from dlrover_tpu.embedding.service import ShardedKvClient

        table = ShardedKvClient(
            coordinator_addr=args.table_coordinator, dim=args.dim
        )
    elif args.table_shards:
        table, sharded_cleanup = _spawn_sharded_table(args, args.ckpt_dir)
        if args.incremental_ckpt and args.ckpt_dir:
            restored = table.ckpt_restore()
            if any(restored):
                print(f"[recsys] sharded table restored at versions "
                      f"{restored} ({len(table)} rows)", flush=True)
    else:
        table = KvEmbeddingTable(dim=args.dim, num_slots=2, seed=1234)
        if args.spill_dir:
            os.makedirs(args.spill_dir, exist_ok=True)
            table.enable_spill(os.path.join(
                args.spill_dir, f"recsys-{ctx.node_id}.spill"
            ))
        if args.incremental_ckpt and args.ckpt_dir:
            from dlrover_tpu.embedding.kv_table import (
                IncrementalCheckpointManager,
            )

            # node-scoped like the spill file and the CheckpointEngine:
            # each node's table has its own base/delta chain
            inc_mgr = IncrementalCheckpointManager(
                table,
                os.path.join(args.ckpt_dir, f"embedding-inc-{ctx.node_id}"),
            )
            restored = inc_mgr.restore()
            if restored:
                print(f"[recsys] embedding table restored at version "
                      f"{restored} ({len(table)} rows)", flush=True)

    # dense tower: concat field embeddings -> MLP -> logit
    d_in = args.fields * args.dim
    k0, k1 = jax.random.split(jax.random.PRNGKey(0))
    params = {
        "w1": jax.random.normal(k0, (d_in, 64), jnp.float32) / np.sqrt(d_in),
        "b1": jnp.zeros((64,)),
        "w2": jax.random.normal(k1, (64, 1), jnp.float32) / 8.0,
        "b2": jnp.zeros((1,)),
    }
    optimizer = optax.adam(args.lr)
    opt_state = optimizer.init(params)

    def forward(params, emb):
        x = emb.reshape(emb.shape[0], -1)
        h = jax.nn.relu(x @ params["w1"] + params["b1"])
        return (h @ params["w2"] + params["b2"])[:, 0]

    def loss_fn(params, emb, labels):
        logits = forward(params, emb)
        return optax.sigmoid_binary_cross_entropy(logits, labels).mean()

    @jax.jit
    def train_step(params, opt_state, emb, labels):
        loss, (grads, emb_grads) = jax.value_and_grad(
            loss_fn, argnums=(0, 1)
        )(params, emb, labels)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss, emb_grads

    rng = np.random.default_rng(7)

    def make_batch():
        ids = rng.zipf(1.3, size=(args.batch, args.fields)).astype(
            np.int64
        ) % args.id_space
        # learnable synthetic signal: the first field's id parity — each
        # hot id's embedding can memorize its label
        labels = (ids[:, 0] % 2).astype(np.float32)
        return ids, labels

    losses = []
    start = time.monotonic()
    for step in range(1, args.steps + 1):
        ids, labels = make_batch()
        emb = table.lookup(ids)                          # host gather
        params, opt_state, loss, emb_grads = train_step(
            params, opt_state, jnp.asarray(emb), jnp.asarray(labels)
        )
        kwargs = {"lr": args.lr}                         # host sparse update
        if args.group_lasso and args.sparse_optimizer != "radam":
            kwargs["group_lasso"] = args.group_lasso
        table.apply(args.sparse_optimizer, ids, np.asarray(emb_grads),
                    **kwargs)
        if step % args.log_interval == 0:
            losses.append(float(loss))
            print(f"[recsys] step {step} loss {losses[-1]:.4f} "
                  f"table={len(table)}", flush=True)
            if fabric_persist is not None:
                try:
                    fabric_persist(step)
                except (OSError, RuntimeError, TimeoutError) as e:
                    # a failed ring save never blocks training; the
                    # next interval (and the final save) retry it
                    print(f"[recsys] fabric ckpt postponed: {e}",
                          flush=True)
            elif inc_mgr is not None:
                try:
                    path = inc_mgr.save()
                    print(f"[recsys] incremental ckpt: "
                          f"{os.path.basename(path)}", flush=True)
                except OSError as e:
                    # the manager parks the drained changes; the next
                    # interval's save retries them — keep training
                    print(f"[recsys] incremental ckpt postponed: {e}",
                          flush=True)
            elif (args.incremental_ckpt and args.ckpt_dir
                  and hasattr(table, "ckpt_save")):
                paths = table.ckpt_save()
                print(f"[recsys] sharded incremental ckpt: "
                      f"{[os.path.basename(p) for p in paths]}",
                      flush=True)
        if (args.spill_dir and hasattr(table, "evict")
                and step % args.spill_interval == 0):
            spilled = table.evict(max_freq=args.spill_max_freq)
            if spilled:
                print(f"[recsys] spilled {spilled} cold rows "
                      f"(disk={table.disk_rows})", flush=True)
    wall = time.monotonic() - start

    if args.ckpt_dir:
        from dlrover_tpu.checkpoint.engine import CheckpointEngine

        engine = CheckpointEngine(args.ckpt_dir, node_id=ctx.node_id)
        if fabric_persist is not None:
            # the ring checkpoints itself (drain barrier + verified
            # shard manifest); the engine carries only the dense tower
            fabric_persist(args.steps)
            state = {"dense": params}
        else:
            state = {"dense": params, "embedding": table.export()}
        engine.save_to_storage(args.steps, state)
        waited = engine.wait_for_persist(args.steps, timeout=120)
        if not waited:
            print("[recsys] WARNING: final checkpoint not durable "
                  f"(newest committed: {waited.persisted_step})",
                  flush=True)
        engine.close()
        print(f"[recsys] checkpointed {len(table)} rows", flush=True)

    if args.result_file:
        with open(args.result_file, "w") as f:
            json.dump(
                {
                    "final_step": args.steps,
                    "last_loss": losses[-1] if losses else None,
                    "first_loss": losses[0] if losses else None,
                    "table_rows": len(table),
                    "examples_per_s": round(args.steps * args.batch / wall),
                    **({"staleness": table.staleness()}
                       if hasattr(table, "staleness") else {}),
                },
                f,
            )
    print(f"[recsys] done: {args.steps * args.batch / wall:.0f} examples/s",
          flush=True)
    if sharded_cleanup is not None:
        sharded_cleanup()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
