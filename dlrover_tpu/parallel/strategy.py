"""Acceleration strategies: the ``auto_accelerate`` analog.

Reference analog: atorch/atorch/auto/accelerate.py:406 (auto_accelerate),
auto/strategy.py (strategy serialization), auto/opt_lib/** (the optimization
library: FSDP/TP/AMP/checkpoint wrappers). In torch each optimization is an
imperative model transform; on TPU the whole bundle reduces to declarative
inputs of one ``jax.jit``:

- parallel "groups"      -> mesh axis sizes (MeshSpec)
- FSDP/TP/SP wrappers    -> logical->mesh sharding rules
- AMP                    -> compute dtype (bf16 matmuls, f32 reductions)
- activation checkpoint  -> jax.checkpoint policy applied to the step fn
- ZeRO optimizer states  -> optimizer-state sharding rules (same table)

A Strategy is a plain serializable record, so it can be saved next to a
checkpoint and reloaded (reference: load_strategy, accelerate.py:467).
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any

import jax

from dlrover_tpu.common.log import get_logger
from dlrover_tpu.parallel.mesh import MeshSpec, build_mesh
from dlrover_tpu.parallel.partition import (
    Rules,
    tree_shardings,
    tree_specs,
)

logger = get_logger(__name__)

# jax.checkpoint policies by name (serialization-friendly).
REMAT_POLICIES = {
    "none": None,
    "full": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.dots_saveable,
    "dots_no_batch": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    "everything": jax.checkpoint_policies.everything_saveable,
}


@dataclasses.dataclass
class Strategy:
    """One complete acceleration plan for a model."""

    name: str = "dp"
    mesh_axes: dict[str, int] = dataclasses.field(
        default_factory=lambda: {"data": -1}
    )
    dcn_axes: dict[str, int] = dataclasses.field(default_factory=dict)
    # logical axis name -> mesh axis (str), tuple of axes, or None
    rules: list[list] = dataclasses.field(default_factory=list)
    compute_dtype: str = "bfloat16"
    # master weights: params (and optimizer states) stay f32; the bf16
    # casts happen at use sites inside the model (mixed precision with
    # master weights — the AMP shape that is safe by default on TPU)
    param_dtype: str = "float32"
    remat: str = "none"  # key into REMAT_POLICIES
    grad_accum: int = 1
    extra: dict = dataclasses.field(default_factory=dict)

    # ------------------------------------------------------------- building

    def mesh_spec(self) -> MeshSpec:
        return MeshSpec(axes=dict(self.mesh_axes), dcn_axes=dict(self.dcn_axes))

    def build_mesh(self, devices=None) -> jax.sharding.Mesh:
        return build_mesh(self.mesh_spec(), devices=devices)

    def rule_table(self) -> Rules:
        return [
            (name, tuple(ax) if isinstance(ax, list) else ax)
            for name, ax in self.rules
        ]

    def shardings(self, logical_tree: Any, mesh) -> Any:
        return tree_shardings(logical_tree, self.rule_table(), mesh)

    def specs(self, logical_tree: Any, mesh) -> Any:
        return tree_specs(logical_tree, self.rule_table(), mesh)

    def remat_policy(self):
        if self.remat not in REMAT_POLICIES:
            raise ValueError(
                f"unknown remat policy {self.remat!r}; "
                f"known: {sorted(REMAT_POLICIES)}"
            )
        return REMAT_POLICIES[self.remat]

    # --------------------------------------------------------- serialization

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=2)

    @classmethod
    def from_json(cls, text: str) -> "Strategy":
        return cls(**json.loads(text))

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.to_json())

    @classmethod
    def load(cls, path: str) -> "Strategy":
        with open(path) as f:
            return cls.from_json(f.read())


# Rule fragments shared by the presets. Logical names are the vocabulary the
# bundled models use (models/transformer.py); user models may extend freely.
# No ["vocab", "fsdp"] rule: it only ever decided the embedding table
# (every other weight's embed dim claims the axis first), and published
# vocabularies do not divide by a chip count (GPT-2's 50257 = 29 x 1733),
# so the table shards over its embed dim like every other weight.
_FSDP_RULES = [
    ["embed", "fsdp"],          # shard the big embed dim of every weight
    ["batch", ["data", "fsdp"]],
]
_TP_RULES = [
    ["heads", "tensor"],        # attention heads across tensor axis
    ["mlp", "tensor"],          # ffn hidden dim across tensor axis
    ["vocab", "tensor"],        # vocab-parallel embedding / lm head
    ["kv_heads", "tensor"],
]
_SP_RULES = [
    ["sequence", "sequence"],   # activation sequence dim across seq axis
]
_EP_RULES = [
    ["expert", "expert"],
]


def dp(num_devices: int = -1, grad_compression: bool = False) -> Strategy:
    """Pure data parallel: params replicated, batch split.

    ``grad_compression`` ships the gradient reduce as int8 (reference:
    ATorch's quant-reduce comm compression) — worthwhile when the data
    axis spans DCN, where that reduce is the slowest hop of the step.
    """
    return Strategy(
        name="dp",
        mesh_axes={"data": num_devices},
        rules=[["batch", ["data", "fsdp"]]],
        extra={"grad_compression": "int8"} if grad_compression else {},
    )


def zero1(data_size: int = -1) -> Strategy:
    """ZeRO-1: pure data parallelism with SHARDED optimizer state.

    Params and grads stay replicated (one psum, like dp); the Adam
    moments shard over the data axis, cutting optimizer memory by the
    axis size — the middle ground when params fit HBM but params+Adam
    don't, without fsdp's per-layer param gathers. XLA inserts the
    update all-gather from the output shardings; the math is bit-for-dp
    (it is a layout choice, not an algorithm change). Reference:
    atorch Zero1Optimization (auto/opt_lib/zero_optimization.py:115).
    """
    return Strategy(
        name="zero1",
        mesh_axes={"data": data_size},
        rules=[["batch", "data"]],
        extra={"zero1": True},
    )


def zero2(data_size: int = -1) -> Strategy:
    """ZeRO-2: ZeRO-1 plus reduce-scattered gradients.

    Gradients are constrained to the optimizer state's sharding before
    the update, so XLA lowers the cross-data-axis gradient sum to a
    reduce_scatter (half the wire bytes of an all-reduce) and each
    device holds only its gradient shard while updating its moment
    shard; the update all-gather restores replicated params. Same math
    as dp/zero1. Reference: atorch Zero2Optimization
    (auto/opt_lib/zero_optimization.py:158).
    """
    return Strategy(
        name="zero2",
        mesh_axes={"data": data_size},
        rules=[["batch", "data"]],
        extra={"zero1": True, "zero2": True},
    )


def fsdp(fsdp_size: int = -1, remat: str = "dots",
         int8: bool = False) -> Strategy:
    """ZeRO-3-style fully sharded data parallel (param gather per layer).

    ``int8`` routes the layer-stack projections through the MXU's int8
    path (ops/quantization.py) — the fp8/TransformerEngine-optimization
    analog. Measured on v5e: 1.2x forward / 1.6x grad step at
    d_model=4096; a LOSS at gpt2-small-class geometry where the step is
    HBM-bandwidth-bound, so it is opt-in on the large-model strategies
    rather than a default.
    """
    return Strategy(
        name="fsdp",
        mesh_axes={"fsdp": fsdp_size},
        rules=list(_FSDP_RULES),
        remat=remat,
        extra={"int8_matmuls": True} if int8 else {},
    )


def tp(tensor_size: int = 2, data_size: int = -1,
       remat: str = "none") -> Strategy:
    """Megatron-style tensor parallel × data parallel."""
    return Strategy(
        name="tp",
        mesh_axes={"data": data_size, "tensor": tensor_size},
        rules=[["batch", ["data", "fsdp"]]] + [list(r) for r in _TP_RULES],
        remat=remat,
    )


def fsdp_tp(tensor_size: int = 2, fsdp_size: int = -1,
            remat: str = "dots", int8: bool = False) -> Strategy:
    """2D: FSDP across hosts × TP inside the fast ICI neighborhood.

    ``int8``: see :func:`fsdp`.
    """
    return Strategy(
        name="fsdp_tp",
        mesh_axes={"fsdp": fsdp_size, "tensor": tensor_size},
        rules=list(_FSDP_RULES) + [list(r) for r in _TP_RULES],
        remat=remat,
        extra={"int8_matmuls": True} if int8 else {},
    )


def long_context(sequence_size: int = 2, data_size: int = -1,
                 remat: str = "dots") -> Strategy:
    """Sequence/context parallel for long sequences (ring attention)."""
    return Strategy(
        name="long_context",
        mesh_axes={"data": data_size, "sequence": sequence_size},
        rules=[["batch", ["data", "fsdp"]]] + [list(r) for r in _SP_RULES],
        remat=remat,
        extra={"attention": "ring"},
    )


def ulysses(sequence_size: int = 2, data_size: int = -1,
            remat: str = "dots") -> Strategy:
    """Sequence parallel via all-to-all head redistribution
    (ops/ulysses.py) — the alternative to ring attention when the head
    count comfortably divides by the sequence axis."""
    return Strategy(
        name="ulysses",
        mesh_axes={"data": data_size, "sequence": sequence_size},
        rules=[["batch", ["data", "fsdp"]]] + [list(r) for r in _SP_RULES],
        remat=remat,
        extra={"attention": "ulysses"},
    )


def sliding_window(window: int = 1024, data_size: int = -1,
                   remat: str = "dots") -> Strategy:
    """Local (sliding-window) attention via the splash kernel.

    Single-device long-context alternative to ring attention: each query
    sees the last ``window`` keys and the sparse kernel skips masked
    blocks, so step cost is O(S * window) instead of O(S^2).
    """
    return Strategy(
        name="sliding_window",
        mesh_axes={"data": data_size},
        rules=[["batch", ["data", "fsdp"]]],
        remat=remat,
        extra={"attention": "splash", "attention_window": int(window)},
    )


def pipeline(pipeline_size: int = 2, data_size: int = -1,
             microbatches: int = 0, remat: str = "none",
             interleave: int = 1) -> Strategy:
    """Pipeline over the "pipeline" axis × data parallel.

    The layer-stack dim shards over the pipeline axis so each stage's
    weights (and their optimizer states — ZeRO for free) live only on that
    stage's devices; parallel/pipeline.py supplies the schedule:
    ``interleave=1`` GPipe, ``>1`` the Megatron-interleaved circular
    schedule (1F1B-class bubble, reference
    atorch/auto/opt_lib/pipeline_parallel_optimization.py:56).
    """
    return Strategy(
        name="pipeline",
        mesh_axes={"data": data_size, "pipeline": pipeline_size},
        rules=[
            ["batch", ["data", "fsdp"]],
            ["layers", "pipeline"],
            ["stages", "pipeline"],
        ],
        remat=remat,
        extra={
            "pipeline_stages": pipeline_size,
            "pipeline_microbatches": microbatches,
            "pipeline_interleave": interleave,
        },
    )


def mpmd(pipeline_size: int = 2, microbatches: int = 0) -> Strategy:
    """MPMD pipeline (parallel/mpmd.py): per-stage compiled programs on
    disjoint device submeshes, host-side 1F1B schedule, ZeRO-sharded
    weight update per stage (2412.14374 + 2004.13336).

    Unlike the SPMD presets this strategy does NOT describe one mesh:
    each stage builds its own ``{"data": devices/P}`` submesh and the
    optimizer state shards over that data axis (``zero1`` semantics per
    stage). ``mesh_axes`` here is only the batch-sharding world of
    stage 0. Per-stage programs are what buy per-stage elastic
    recovery: a stage failure recompiles/reloads only that stage.
    """
    return Strategy(
        name="mpmd",
        mesh_axes={"data": -1},
        rules=[["batch", "data"]],
        extra={
            "mpmd": True,
            "zero1": True,
            "pipeline_stages": pipeline_size,
            "pipeline_microbatches": microbatches,
            "pipeline_interleave": 1,
        },
    )


def mixed(pipeline_size: int = 2, tensor_size: int = 2,
          data_size: int = -1, microbatches: int = 0,
          remat: str = "none", interleave: int = 1) -> Strategy:
    """3D: GPipe pipeline × Megatron-style tensor × data parallel.

    Reference analog: MixedParallelOptimization's TP+PP+DP combination
    (atorch/atorch/auto/opt_lib/mixed_parallel_optimization.py:32) — here
    it is just the union of the pipeline and tensor rule tables over one
    mesh; XLA derives the collectives for both axes from the shardings.
    """
    return Strategy(
        name="mixed",
        mesh_axes={
            "data": data_size,
            "pipeline": pipeline_size,
            "tensor": tensor_size,
        },
        rules=[
            ["batch", ["data", "fsdp"]],
            ["layers", "pipeline"],
            ["stages", "pipeline"],
        ] + [list(r) for r in _TP_RULES],
        remat=remat,
        extra={
            "pipeline_stages": pipeline_size,
            "pipeline_microbatches": microbatches,
            "pipeline_interleave": interleave,
        },
    )


def moe(expert_size: int = 2, data_size: int = -1) -> Strategy:
    """Expert parallel: experts split over the expert axis."""
    return Strategy(
        name="moe",
        mesh_axes={"data": data_size, "expert": expert_size},
        rules=[["batch", ["data", "fsdp"]]] + [list(r) for r in _EP_RULES],
    )


PRESETS = {
    "dp": dp,
    "zero1": zero1,
    "zero2": zero2,
    "fsdp": fsdp,
    "tp": tp,
    "fsdp_tp": fsdp_tp,
    "long_context": long_context,
    "ulysses": ulysses,
    "sliding_window": sliding_window,
    "pipeline": pipeline,
    "mpmd": mpmd,
    "mixed": mixed,
    "moe": moe,
}
