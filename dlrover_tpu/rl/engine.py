"""PPO model engine: actor/critic/reference under the strategy layer.

Reference analog: ATorch's RL model_engine
(atorch/atorch/rl/model_engine/model_engine.py:1 — per-model
parallelization strategies, a vLLM generation backend, weight sync
between trainer and inference engines). TPU-native shape: every model
lives on ONE jax mesh; "per-model strategy" means per-model SHARDING
RULES compiled into the same SPMD programs — the actor/critic trains
under its strategy's partition specs (with optimizer-state sharding
derived ZeRO-style), the frozen reference model can use a different
(e.g. memory-lean, tensor-only) layout, and "weight sync" between train
and inference engines is the identity: the KV-cached decode
(models/decode.py) jit-shares the very parameter buffers the update
step produces, so generation is never stale.

The single-host PPOTrainer (rl/ppo.py) stays as the compact reference
implementation; ShardedPPOTrainer reuses its rollout/update logic with
sharded jits, so the algorithm has exactly one source of truth.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from dlrover_tpu.common.log import get_logger
from dlrover_tpu.models import transformer as tfm
from dlrover_tpu.parallel.mesh import batch_axes
from dlrover_tpu.parallel.strategy import Strategy
from dlrover_tpu.rl.ppo import (
    PPOConfig,
    PPOTrainer,
    init_actor_critic,
    ppo_loss,
    sample,
    sequence_logprobs_and_values,
)
from dlrover_tpu.trainer.train_step import derive_opt_specs

logger = get_logger(__name__)


def actor_critic_logical(cfg: tfm.TransformerConfig) -> dict:
    """Logical axes for the actor+value-head tree: the transformer reuses
    the pretraining rules; the value head (one d_model vector) replicates
    (its name is outside every rule table)."""
    return {
        "model": tfm.logical_axes(cfg),
        "value_head": ("value_dim",),
    }


class ShardedPPOTrainer(PPOTrainer):
    """PPOTrainer whose models, optimizer state, rollout, and update run
    sharded over a mesh — per-model strategies included.

    ``strategy`` shards the trained actor/critic (params + Adam state +
    batch); ``ref_strategy`` (default: same rules) lays out the frozen
    reference model, which carries no optimizer state and may prefer a
    different split. The KV-cached decode runs inside jit on the same
    mesh with the actor's shardings, batch over the data axes.
    """

    def __init__(self, cfg: tfm.TransformerConfig, ppo: PPOConfig,
                 reward_fn, key: jax.Array,
                 strategy: Strategy | None = None,
                 ref_strategy: Strategy | None = None,
                 devices=None, optimizer=None,
                 store_rollouts: bool = False):
        import optax

        from dlrover_tpu.rl.ppo import ReplayBuffer

        from dlrover_tpu.parallel.strategy import dp as dp_preset

        self.cfg = cfg
        self.ppo = ppo
        self.reward_fn = reward_fn
        self.strategy = strategy or dp_preset()
        self.mesh = self.strategy.build_mesh(devices)
        mesh = self.mesh
        self.buffer = ReplayBuffer() if store_rollouts else None

        logical = actor_critic_logical(cfg)
        param_specs = self.strategy.specs(logical, mesh)
        param_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), param_specs,
            is_leaf=lambda x: isinstance(x, PartitionSpec),
        )
        ref_rules = (ref_strategy or self.strategy)
        ref_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            ref_rules.specs(logical, mesh),
            is_leaf=lambda x: isinstance(x, PartitionSpec),
        )

        # data-parallel batch layout for [B, ...] rollout fields
        axes = batch_axes(mesh)
        dp_spec = PartitionSpec(
            axes if len(axes) > 1 else (axes[0] if axes else None)
        )
        self._dp_sharding = NamedSharding(mesh, dp_spec)
        replicated = NamedSharding(mesh, PartitionSpec())

        self.params = jax.jit(
            partial(init_actor_critic, cfg), out_shardings=param_shardings
        )(key)
        # the frozen reference starts as the actor's weights, laid out
        # under ITS strategy (reference model_engine: one strategy per
        # model). Identity-jit rather than device_put: leaves whose ref
        # sharding equals the actor's would otherwise ALIAS the actor
        # buffers, and the first donated update would delete them out
        # from under the reference model.
        self.ref_params = jax.jit(
            lambda t: t, out_shardings=ref_shardings
        )(self.params)

        self.opt = optimizer or optax.adam(ppo.learning_rate)
        opt_specs = derive_opt_specs(self.opt, self.params, param_specs)
        opt_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), opt_specs,
            is_leaf=lambda x: isinstance(x, PartitionSpec),
        )
        self.opt_state = jax.jit(
            self.opt.init, out_shardings=opt_shardings
        )(self.params)

        # ---- sharded jits: same algorithm objects as the base class
        if cfg.moe_experts:
            self._sample = jax.jit(
                lambda params, prompts, key: sample(
                    params, prompts, cfg, ppo, key
                ),
                in_shardings=(param_shardings, self._dp_sharding, None),
            )
        else:
            from dlrover_tpu.models.decode import generate

            self._sample = jax.jit(
                lambda params, prompts, key: generate(
                    params["model"], prompts, cfg, ppo.gen_len, key,
                    temperature=ppo.temperature,
                ),
                in_shardings=(param_shardings, self._dp_sharding, None),
            )
        self._logp_values = jax.jit(
            partial(sequence_logprobs_and_values, cfg=cfg),
            # ref params arrive with THEIR shardings; jit resolves both
            # layouts against the same program via the arg shardings
            in_shardings=(None, self._dp_sharding),
        )

        def update(params, opt_state, batch):
            (loss, metrics), grads = jax.value_and_grad(
                ppo_loss, has_aux=True
            )(params, batch, cfg, ppo)
            updates, opt_state = self.opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            metrics["loss"] = loss
            return params, opt_state, metrics

        batch_shardings = {
            "tokens": self._dp_sharding,
            "old_logp": self._dp_sharding,
            "advantages": self._dp_sharding,
            "returns": self._dp_sharding,
            "gen_mask": self._dp_sharding,
            "score_mean": replicated,
        }
        self._update = jax.jit(
            update,
            in_shardings=(param_shardings, opt_shardings,
                          batch_shardings),
            out_shardings=(param_shardings, opt_shardings, None),
            donate_argnums=(0, 1),
        )
        self._serving = None
        logger.info(
            "sharded ppo engine: mesh %s, actor strategy %s, ref %s",
            dict(mesh.shape), self.strategy.name, ref_rules.name,
        )

    # ------------------------------------------------- serving rollouts

    def enable_serving_rollouts(self, *, slots: int = 8,
                                decode_block: int = 8,
                                max_len: int = 0,
                                prefix_cache_entries: int = 8,
                                seed: int = 0) -> None:
        """Route rollout generation through the continuous-batching
        serving engine (serving/engine.py) instead of the in-mesh decode.

        Reference analog: ATorch's train<->inference engine split, where
        PPO rollouts run on a vLLM backend that receives the trainer's
        weights each iteration
        (atorch/atorch/rl/model_engine/model_engine.py:1,
        rl/inference_backend/vllm_backend.py:1). TPU-native: both
        engines live on one mesh, so the per-iteration "weight sync" is
        handing the serving engine the actor's parameter BUFFERS (no
        staleness window; the engine keeps them in the dtype its
        products run in, one conversion a push and none a decode
        step); the decode itself is the same
        ``sample_logits`` used by the in-mesh path, so sampling
        semantics cannot drift between backends.
        """
        from dlrover_tpu.serving import InferenceEngine

        max_len = max_len or self.cfg.max_seq_len
        # prefix caching pays for itself exactly in the rollout shape
        # (every prompt in a PPO batch shares the task's system
        # prefix); the per-iteration weight push invalidates it, which
        # is also why entries stay modest — reuse only lives within
        # one iteration's rollout wave
        self._serving = InferenceEngine(
            self.params["model"], self.cfg, slots=slots,
            max_len=max_len, decode_block=decode_block,
            prefix_cache_entries=prefix_cache_entries,
        )
        del seed  # kept for API stability; seeds derive from the key

    # ---------------------------------------- disaggregated serving

    def enable_remote_rollouts(self, addr: str | None = None, *,
                               slots: int = 8, decode_block: int = 8,
                               max_len: int = 0,
                               prefix_cache_entries: int = 8,
                               worker_env: dict | None = None) -> None:
        """Route rollouts through a serving worker in a SEPARATE
        process, with versioned networked weight sync — the full
        disaggregated form of the reference's vLLM inference backend
        (atorch/rl/inference_backend/vllm_backend.py:1). The in-mesh
        and one-process serving paths stay available; this one
        exercises the hard part: cross-engine weight transfer and
        version skew.

        ``addr`` connects to an existing worker; None spawns one as a
        child process (its own JAX runtime — a CPU mesh in tests, an
        inference slice in production). Each ``_generate`` pushes the
        actor weights ONLY when the trainer's version advanced, and
        every rollout RPC pins ``expect_version``: a worker holding
        stale weights answers with a structured version error instead
        of silently generating from them."""
        from dlrover_tpu.rl.serving_worker import (
            RemoteServingClient,
            spawn_worker,
        )

        self._remote_proc = None
        if addr is None:
            addr, self._remote_proc = spawn_worker(env=worker_env)
        self._remote = RemoteServingClient(addr)
        self._remote.init(
            self.cfg, slots=slots,
            max_len=max_len or self.cfg.max_seq_len,
            decode_block=decode_block,
            prefix_cache_entries=prefix_cache_entries,
        )
        self._weights_version = 0
        self._remote_pushed = -1

    def close_remote(self) -> None:
        remote = getattr(self, "_remote", None)
        if remote is not None:
            # only stop a worker THIS trainer spawned: an addr-connected
            # worker may be a shared inference slice other trainers are
            # still rolling out against
            if getattr(self, "_remote_proc", None) is not None:
                remote.stop_worker()
            remote.close()
            self._remote = None
        proc = getattr(self, "_remote_proc", None)
        if proc is not None:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except Exception:  # noqa: BLE001
                proc.kill()
            self._remote_proc = None

    def _remote_generate(self, prompts: np.ndarray,
                         key: jax.Array) -> jax.Array:
        import numpy as _np

        if self._remote_pushed != self._weights_version:
            # full-tree host fetch + push. Deliberately synchronous:
            # PPO is on-policy, so the rollout MUST see this
            # iteration's weights (the version pin below enforces it)
            host_params = jax.device_get(self.params["model"])
            self._remote.push_weights(self._weights_version,
                                      host_params)
            self._remote_pushed = self._weights_version
        seeds = [
            int(jax.random.randint(
                jax.random.fold_in(key, i), (), 0, 2**31 - 1
            ))
            for i in range(len(prompts))
        ]
        gen = self._remote.rollout(
            _np.asarray(prompts, _np.int32), seeds,
            gen_len=self.ppo.gen_len,
            temperature=self.ppo.temperature,
            expect_version=self._weights_version,
        )
        tokens = _np.concatenate(
            [_np.asarray(prompts, _np.int32),
             _np.asarray(gen, _np.int32)], axis=1,
        )
        return jax.device_put(jnp.asarray(tokens), self._dp_sharding)

    def train_step(self, prompts: np.ndarray, key: jax.Array) -> dict:
        metrics = super().train_step(prompts, key)
        if getattr(self, "_remote", None) is not None:
            # the update loop just produced new actor weights
            self._weights_version += 1
        return metrics

    def _generate(self, prompts: np.ndarray, key: jax.Array) -> jax.Array:
        if getattr(self, "_remote", None) is not None:
            return self._remote_generate(prompts, key)
        if self._serving is None:
            return super()._generate(prompts, key)
        import numpy as _np

        from dlrover_tpu.serving import SamplingParams

        # per-iteration weight handoff: the engine's jitted programs
        # take params as an argument, so handing it the freshly
        # updated actor buffers IS the sync step (it converts what its
        # products read to cfg.dtype, once, and keeps that)
        self._serving.params = self.params["model"]
        # per-request seeds DERIVED FROM THE CALLER'S KEY: rollout stays
        # a function of (params, prompts, key) on this backend too —
        # a counter would make resumed runs replaying the same key
        # stream irreproducible. fold_in also keeps identical prompts
        # in one batch from collapsing to identical continuations.
        seeds = [
            int(jax.random.randint(
                jax.random.fold_in(key, i), (), 0, 2**31 - 1
            ))
            for i in range(len(prompts))
        ]
        rids = [
            self._serving.submit(
                list(map(int, row)),
                SamplingParams(
                    temperature=self.ppo.temperature,
                    max_new_tokens=self.ppo.gen_len,
                    seed=seeds[i],
                ),
            )
            for i, row in enumerate(_np.asarray(prompts))
        ]
        results = {r.id: r for r in self._serving.run()}
        gen = _np.stack([
            _np.asarray(results[rid].tokens[:self.ppo.gen_len],
                        _np.int32)
            for rid in rids
        ])
        tokens = _np.concatenate(
            [_np.asarray(prompts, _np.int32), gen], axis=1
        )
        return jax.device_put(jnp.asarray(tokens), self._dp_sharding)
