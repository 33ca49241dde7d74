"""Jitted train-step factory: strategy in, compiled SPMD step out.

Reference analog: the tail of auto_accelerate (atorch/atorch/auto/
accelerate.py:406 model_transform + returned optim/dataloader wiring). In
torch the strategy mutates the model (FSDP wrap, TP module swap, AMP hooks);
here it parameterizes one ``jax.jit``: parameter/optimizer-state shardings,
bf16 compute casts, remat policy, and gradient accumulation all become
compile-time properties of a single XLA program.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from dlrover_tpu.common.log import get_logger
from dlrover_tpu.parallel.mesh import batch_axes
from dlrover_tpu.parallel.partition import constrain as _constrain
from dlrover_tpu.parallel.strategy import Strategy

logger = get_logger(__name__)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    step: jax.Array
    params: Any
    opt_state: Any


def _path_names(path) -> tuple[str, ...]:
    names = []
    for p in path:
        if hasattr(p, "key"):
            names.append(str(p.key))
        elif hasattr(p, "name"):
            names.append(str(p.name))
        elif hasattr(p, "idx"):
            names.append(str(p.idx))
        else:
            names.append(str(p))
    return tuple(names)


def derive_opt_specs(optimizer, params: Any, param_specs: Any) -> Any:
    """PartitionSpecs for the optimizer state (ZeRO: follow the params).

    Optax states embed parameter-structured subtrees (Adam's mu/nu); each
    opt-state leaf whose path ends with a parameter's path inherits that
    parameter's spec, everything else (counts, scalars) replicates. This is
    the reference's ZeRO/FSDP optimizer-state sharding
    (atorch/atorch/auto/opt_lib/zero_optimization.py:115) as a spec-mapping.
    """
    param_leaves = {
        _path_names(path): spec
        for path, spec in jax.tree_util.tree_flatten_with_path(
            param_specs,
            is_leaf=lambda x: isinstance(x, PartitionSpec),
        )[0]
    }
    opt_shape = jax.eval_shape(optimizer.init, params)

    def spec_of(path, leaf) -> PartitionSpec:
        names = _path_names(path)
        for p_path, spec in param_leaves.items():
            if len(names) >= len(p_path) and names[-len(p_path):] == p_path:
                if leaf.shape:  # scalars always replicate
                    return spec
        return PartitionSpec()

    flat, treedef = jax.tree_util.tree_flatten_with_path(opt_shape)
    return jax.tree_util.tree_unflatten(
        treedef, [spec_of(p, l) for p, l in flat]
    )


def zero_shard_specs(specs: Any, example: Any, mesh: Mesh) -> Any:
    """ZeRO-style cross-replica sharding (Xu et al., 2004.13336): give
    every REPLICATED leaf's first divisible dim to the mesh's data axes,
    leaving already-sharded leaves untouched. Used for optimizer-state
    sharding by the ``zero1``/``zero2`` strategies here and by the MPMD
    per-stage weight-update programs (``parallel/mpmd.py``) — the math
    is identical to replicated (a layout choice, not an algorithm
    change); XLA derives the update all-gather from the out shardings.
    ``specs``/``example`` are same-structure trees of PartitionSpec and
    array(-shape) leaves."""
    z_axes = batch_axes(mesh)
    z_n = 1
    for a in z_axes:
        z_n *= mesh.shape[a]
    z_axis = z_axes if len(z_axes) > 1 else (
        z_axes[0] if z_axes else None)

    def _spec(spec, leaf):
        if spec != PartitionSpec() or leaf.ndim == 0 or z_axis is None:
            return spec
        for d, size in enumerate(leaf.shape):
            if size % z_n == 0 and size >= z_n:
                return PartitionSpec(*([None] * d), z_axis)
        return spec

    return jax.tree.map(
        _spec, specs, example,
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )


@dataclasses.dataclass
class CompiledTrain:
    """Everything a training loop needs, pre-sharded and jitted."""

    mesh: Mesh
    strategy: Strategy
    state_shardings: TrainState
    batch_sharding: Any
    init: Callable[..., TrainState]          # (rng, *init_args) -> state
    step: Callable[[TrainState, Any], tuple[TrainState, dict]]
    constrain: Callable[[jax.Array, tuple], jax.Array]
    # set by the elastic compile-cache path (parallel/compile_cache.py)
    # when `step` was swapped for a pre-compiled AOT executable: True =
    # served from cache (warm), False = compiled cold this incarnation,
    # None = plain jit path (compiles lazily at the first dispatch)
    cache_hit: bool | None = None
    # compiled-program FLOPs per step call (XLA cost analysis), fed to
    # the live MFU gauge (telemetry/efficiency.py). Set by the AOT path
    # (AotStep.flops — cached in the compile-cache envelope so warm
    # loads never re-lower); 0.0 = unknown (plain jit path on a device
    # with no known peak never needs it)
    flops_per_step: float = 0.0


def compile_train(
    *,
    strategy: Strategy,
    mesh: Mesh,
    loss_fn: Callable[[Any, Any], jax.Array],
    init_params_fn: Callable[..., Any],
    logical_params: Any,
    optimizer: optax.GradientTransformation,
    batch_spec: PartitionSpec | None = None,
    init_args: tuple = (),
) -> CompiledTrain:
    """Build the sharded init and train-step functions.

    ``loss_fn(params, micro_batch) -> scalar``; gradient accumulation over a
    leading accum dim of the batch is handled here (reference analog:
    ElasticTrainer's fixed-global-batch accumulation,
    dlrover/trainer/torch/elastic/trainer.py:181 — but resolved statically
    per compile instead of per optimizer call).
    """
    rules = strategy.rule_table()
    pin = partial(_constrain, rules=rules, mesh=mesh)

    param_specs = strategy.specs(logical_params, mesh)
    param_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), param_specs,
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )
    if batch_spec is None:
        # batch leaves are [accum, per_step_batch, ...]: shard the batch
        # dim (1) over the data axes, never the accumulation dim (0)
        axes = batch_axes(mesh)
        batch_spec = PartitionSpec(
            None,
            axes if len(axes) > 1 else (axes[0] if axes else None),
        )
    batch_sharding = NamedSharding(mesh, batch_spec)

    def _init(rng, *args) -> TrainState:
        params = init_params_fn(rng, *args)
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=optimizer.init(params),
        )

    # shardings for the full state
    example = jax.eval_shape(_init, jax.random.PRNGKey(0), *init_args)
    opt_specs = derive_opt_specs(optimizer, example.params, param_specs)
    extra = getattr(strategy, "extra", {}) or {}
    if extra.get("zero1") or extra.get("zero2"):
        # ZeRO-1: optimizer state shards over the data axes even though
        # params stay replicated — each leaf's first divisible dim gets
        # the axis; the update all-gather comes from out_shardings. The
        # math is identical to dp (layout, not algorithm).
        opt_specs = zero_shard_specs(opt_specs, example.opt_state, mesh)
    state_shardings = TrainState(
        step=NamedSharding(mesh, PartitionSpec()),
        params=param_shardings,
        opt_state=jax.tree.map(
            lambda s: NamedSharding(mesh, s), opt_specs,
            is_leaf=lambda x: isinstance(x, PartitionSpec),
        ),
    )

    init = jax.jit(_init, out_shardings=state_shardings)

    policy = strategy.remat_policy()
    grad_loss = loss_fn
    if policy is not None:
        grad_loss = jax.checkpoint(loss_fn, policy=policy)
    value_and_grad = jax.value_and_grad(grad_loss)

    def _loss_and_grads(params: Any, batch: Any) -> tuple[jax.Array, Any]:
        # batch leaves: [accum, per_step_batch, ...]
        accum = jax.tree_util.tree_leaves(batch)[0].shape[0]

        if accum == 1:
            return value_and_grad(
                params, jax.tree.map(lambda x: x[0], batch)
            )

        def micro(carry, mb):
            loss_acc, grads_acc = carry
            loss, grads = value_and_grad(params, mb)
            return (
                loss_acc + loss,
                jax.tree.map(jnp.add, grads_acc, grads),
            ), None

        zero = (
            jnp.zeros((), jnp.float32),
            jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            ),
        )
        (loss, grads), _ = jax.lax.scan(micro, zero, batch)
        return loss / accum, jax.tree.map(lambda g: g / accum, grads)

    compute = _loss_and_grads
    if extra.get("grad_compression"):
        # int8-quantized gradient reduce across the data axes (reference:
        # ATorch's quant-reduce comm compression). The grad psum XLA would
        # insert implicitly is replaced by an explicit shard_map region:
        # local grads -> quantized all-gather -> local dequant mean.
        # Scope matches the reference's DDP compression: params must be
        # replicated (the data axes are the only reduction).
        from dlrover_tpu.ops.collectives import (
            quantized_tree_mean,
            shard_map_nocheck,
        )

        sharded = [
            s for s in jax.tree_util.tree_leaves(
                param_specs,
                is_leaf=lambda x: isinstance(x, PartitionSpec),
            ) if s != PartitionSpec()
        ]
        if sharded:
            raise ValueError(
                "grad_compression requires replicated parameters (pure "
                f"data parallelism); found sharded specs {sharded[:3]}"
            )
        axes = batch_axes(mesh)

        axis_sizes = dict(mesh.shape)

        def _local(params, batch):
            loss, grads = _loss_and_grads(params, batch)
            grads = quantized_tree_mean(grads, axes, axis_sizes)
            return jax.lax.pmean(loss, axes), grads

        compute = shard_map_nocheck(
            _local,
            mesh=mesh,
            in_specs=(PartitionSpec(), batch_spec),
            out_specs=(PartitionSpec(), PartitionSpec()),
        )

    # ZeRO-2: constrain gradients to the moment shards' layout so the
    # cross-data-axis gradient sum lowers to a reduce_scatter and each
    # device updates only its shard (the all-gather moves to the
    # parameter update, where ZeRO-1 already pays it)
    grad_constraint = None
    if extra.get("zero2"):
        # the param-shaped moment layout: run the PARAM specs through
        # the same first-divisible-dim rule the moments used, so a
        # zero2 strategy with sharded params keeps grads and moments on
        # one layout instead of resharding between them
        mu_specs = zero_shard_specs(param_specs, example.params, mesh)

        def grad_constraint(grads):
            return jax.tree.map(
                lambda g, s: jax.lax.with_sharding_constraint(
                    g, NamedSharding(mesh, s)
                ),
                grads, mu_specs,
            )

    def _step(state: TrainState, batch: Any) -> tuple[TrainState, dict]:
        loss, grads = compute(state.params, batch)
        if grad_constraint is not None:
            grads = grad_constraint(grads)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(
                grads, state.opt_state, state.params
            )
            params = optax.apply_updates(state.params, updates)
        new_state = TrainState(
            step=state.step + 1, params=params, opt_state=opt_state
        )
        metrics = {
            "loss": loss.astype(jnp.float32),
            "grad_norm": optax.global_norm(grads).astype(jnp.float32),
        }
        return new_state, metrics

    replicated = NamedSharding(mesh, PartitionSpec())
    step = jax.jit(
        _step,
        in_shardings=(state_shardings, batch_sharding),
        out_shardings=(state_shardings,
                       {"loss": replicated, "grad_norm": replicated}),
        donate_argnums=(0,),
    )

    return CompiledTrain(
        mesh=mesh,
        strategy=strategy,
        state_shardings=state_shardings,
        batch_sharding=batch_sharding,
        init=init,
        step=step,
        constrain=pin,
    )
