"""Decoder-only transformer, TPU-first.

Covers the model families the reference accelerates (ATorch's model-zoo TP
ports and HF integrations, atorch/atorch/modules/distributed_modules/
transformer.py:45-1742) as one configurable implementation:

- ``variant="llama"``: RMSNorm, RoPE, SwiGLU, no biases (Llama/GLM class)
- ``variant="gpt2"``: LayerNorm, learned positions, GELU (GPT-2 class)

Design choices for the MXU/XLA:
- per-layer weights are stacked along a leading ``layers`` dim and the block
  runs under ``lax.scan`` — one compiled layer body regardless of depth
- params live in fp32; compute casts to bf16 so matmuls hit the MXU at full
  rate while the loss/softmax reductions stay fp32
- every weight carries *logical* axis names (see parallel/partition.py);
  DP/FSDP/TP/SP are rule-table choices, not model edits
- attention is a pluggable callable so the ring/flash implementations
  (ops/ring_attention.py) drop in for long-context strategies
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec

Params = Any


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 8         # < n_heads -> grouped-query attention
    d_ff: int = 1408
    max_seq_len: int = 2048
    variant: str = "llama"      # "llama" | "gpt2"
    rope_theta: float = 10000.0
    dtype: str = "bfloat16"     # compute dtype
    remat_scan: bool = False    # checkpoint each scanned layer
    # per-layer remat policy: "nothing" recomputes the whole layer in
    # backward; "save_attn" keeps the (cheap, bf16) attention outputs so
    # the backward skips re-running attention to rebuild FFN inputs
    remat_policy: str = "nothing"
    # lax.scan unroll factor for the layer stack: >1 lets XLA overlap
    # weight prefetch/scheduling across adjacent layers at the cost of
    # program size (still one remat boundary per layer)
    scan_unroll: int = 1
    # interleaved remat: scan groups of k layers where only the first
    # k-1 are rematted and the k-th keeps its activations, so the
    # backward recomputes (k-1)/k of a forward instead of all of it.
    # Live memory grows by one full layer's activations per group —
    # the middle ground the reference reaches with selective
    # activation checkpointing (atorch checkpoint_optimization.py).
    # 1 = remat every layer (classic); requires n_layers % k == 0.
    remat_interval: int = 1
    # "dense" | "flash" | "splash" | "ring" | "ulysses"
    attention: str = "dense"
    # splash only: sliding-window size (0 = full causal); the sparse
    # kernel skips fully-masked blocks, so long seqs pay O(S * window)
    attention_window: int = 0
    # muP (parallel/mup.py): base d_model tuned on; 0 disables. Applies
    # the readout multiplier and 1/d_head attention scaling here; pair
    # with mup_optimizer for the per-leaf LR table.
    mup_base_width: int = 0
    # int8 MXU path (ops/quantization.py): layer-stack projections
    # (QKV/out/FFN) run as quantized int8 matmuls — v5e executes int8 at
    # ~1.5-1.6x bf16 throughput. Embedding/LM-head stay bf16 (vocab
    # logits are quantization-sensitive). The fp8/TE-optimization
    # analog, TPU-first.
    int8_matmuls: bool = False
    # MoE (ops/moe.py): experts replace the FFN when > 0; shard them over
    # the "expert" mesh axis via the moe strategy preset
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_aux_weight: float = 1e-2
    moe_capacity_factor: float = 1.25
    # pipeline parallelism (parallel/pipeline.py): >1 splits the layer
    # stack into that many GPipe stages over the "pipeline" mesh axis.
    # Microbatches default to the stage count. Set via the "pipeline"
    # strategy preset rather than by hand.
    pipeline_stages: int = 0
    pipeline_microbatches: int = 0
    # >1: interleaved (circular) schedule — each stage holds this many
    # layer chunks; bubble shrinks ~interleave-fold (1F1B-class win)
    pipeline_interleave: int = 1
    # False -> bidirectional attention (BERT-class encoders); the rest of
    # the block (norms, FFN, sharding rules) is shared with decoders
    causal: bool = True
    # GLM-class prefix LM (prefix_lm_attention): the batch carries a
    # per-row "prefix_len" — bidirectional attention inside the prefix,
    # causal beyond, loss on the generated span. Training-path feature
    # (dense attention); kernel attention configs are rejected.
    prefix_lm: bool = False
    # blockwise cross-entropy: compute the vocab logits in this many
    # token chunks under remat instead of materializing the full
    # [B, S, vocab] f32 logits (+ gradient) in HBM — the reference's
    # fused cross-entropy (atorch modules/transformer/cross_entropy.py)
    # done the XLA way. 0 = single full-logits pass.
    ce_chunks: int = 0
    # --- what a block is made of, as KINDS. The defaults are what
    # `variant` / `moe_experts` say. models/latent.py runs the one other
    # combination there is (the forward pass only: scoring and serving)
    # and refuses the rest by name: attn_kind "latent" (queries and
    # keys/values through low-rank latents, the cache ONE stack of
    # kv_lora_rank + qk_rope_head_dim numbers a token a layer) with
    # norm_kind "sandwich" (a norm after attention and after the
    # feed-forward too) and ffn_kind "sigmoid_experts": the first
    # `first_k_dense` layers one SwiGLU of `d_ff`, the rest a
    # sigmoid-routed expert layer (ops/moe.py RoutedConfig: top
    # `moe_top_k` of `n_routed_experts`, SwiGLU experts of `moe_d_ff`)
    # beside `n_shared_experts` shared ones.
    # THIS file's block runs two kinds more, served only (`make_layer_fn`
    # refuses them by name where a path cannot run them): attn_kind
    # "heads_qk_norm" (an RMSNorm over `head_dim` on every query and key
    # head before the rotary embedding, scales `ln_q`, `ln_k`) and
    # ffn_kind "softmax_experts" (every layer `n_routed_experts` SwiGLU
    # experts of `moe_d_ff`, `moe_top_k` a token by a softmax router
    # renormalised over the chosen, no capacity and no drops:
    # ops/moe.py `softmax_topk_route` + `held_expert_ffn`). It also runs
    # norm_kind "post" (NO norm on a sublayer's input: `ln1` / `ln2` norm
    # the attention's and the feed-forward's OUTPUT before the residual
    # add) and ffn_kind "sigmoid_experts" with its `first_k_dense` dense
    # layers (their weights under `dense_layers`, the expert layers' under
    # `layers`, with a score bias `b_router`): the expert half is
    # ops/moe.py `sigmoid_expert_half`, which models/latent.py's block
    # calls too.
    attn_kind: str = "heads"
    norm_kind: str = "pre"
    ffn_kind: str = ""
    # the RMSNorm eps of every kind but the defaults (a llama-variant
    # block of default kinds keeps the 1e-6 it always had)
    norm_eps: float = 1e-5
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    first_k_dense: int = 0
    n_routed_experts: int = 0
    moe_d_ff: int = 0
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    # the share held here of a deployment that divides each layer over
    # several chips: `experts_held` of the routed experts from
    # `expert_first` on (0: all); `n_layers` and `vocab_size` are then
    # the layers and vocabulary rows HELD, whatever the publication has
    experts_held: int = 0
    expert_first: int = 0
    # the dtype the weights rest in ("bfloat16": as published, no
    # per-call conversion)
    param_dtype: str = "float32"
    # a head's width where it is not d_model / n_heads (0: that). Set
    # at construction: `dataclasses.replace(cfg, d_model=..)` of a
    # config that left it 0 must pass `head_dim=0` again
    head_dim: int = 0
    # rotary pairing: "interleaved" pairs components (2i, 2i + 1),
    # "half" pairs i with i + head_dim / 2 (the Hugging Face layout)
    rope_pairing: str = "interleaved"
    # --- how the model GENERATES (serving/engine.py compiles one decode
    # program per (block_length, denoising_steps); no environment key).
    # "block_diffusion": positions in blocks of `block_length` by
    # absolute position, attention block-causal in every program (a
    # query sees every key up to the END of its own block), a block
    # generated by `denoising_steps` passes over `mask_token_id`
    # placeholders (each pass unmasks the block_length /
    # denoising_steps still-masked positions of highest confidence)
    # and one storing pass; logits at a position predict THAT position
    generation: str = "autoregressive"
    block_length: int = 0
    denoising_steps: int = 0
    mask_token_id: int = -1
    # --- attn_kind "mixers" (served only; models/hybrid.py owns the two
    # mixers and the cache, THIS file's block runs them): a stack that is
    # not homogeneous. `mixer_types` names each layer's mixer, "sparse"
    # (block-sparse attention over `sparse_kv_heads` key/value heads, no
    # rotary embedding: a query past `sparse_dense_len` keys attends to
    # the `sparse_topk` blocks of `sparse_block` keys that its scores over
    # COMPRESSED keys, means of `sparse_kernel` keys every `sparse_stride`,
    # rank highest, the first `sparse_init_blocks` and the
    # `sparse_window` / `sparse_block` blocks ending with its own among
    # them) or "lightning" (linear attention: per head a decayed
    # float32 state [head_dim, head_dim] in place of rows; `n_kv_heads`
    # heads, rotary embedding, a norm over the concatenated output).
    # Both take q/k norms and an output gate. `n_layers` is
    # len(mixer_types)
    mixer_types: tuple = ()
    sparse_kv_heads: int = 0
    sparse_kernel: int = 32
    sparse_stride: int = 16
    sparse_block: int = 64
    sparse_topk: int = 64
    sparse_init_blocks: int = 1
    sparse_window: int = 2048
    sparse_dense_len: int = 8192
    # the multipliers of a model parametrised for width transfer that are
    # not `mup_base_width`'s: on the embedding, on every residual branch,
    # on the head's input
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_scale: float = 1.0
    # --- attn_kind "mixers", the second family (nemotron_h; served only):
    # a stack whose layers are ONE pre-normed residual branch each,
    # `x + f(norm(x))`. `mixer_types` then names each layer "mamba2" (a
    # Mamba-2 state-space mixer: `ssm_heads` heads of `ssm_head_dim`, a
    # float32 state [ssm_head_dim, ssm_state] a head, B and C in
    # `ssm_groups` groups, a causal depthwise convolution over the last
    # `ssm_conv` inputs, the chunked scan over chunks of `ssm_chunk`),
    # "attention" (`n_heads` on `n_kv_heads`, NO rotary embedding, no q/k
    # norm, no gate) or "latent_experts" (`moe_top_k` of `n_routed_experts`
    # sigmoid-routed squared-ReLU experts of `moe_d_ff` at a LATENT width
    # `moe_latent` behind a shared down-projection and in front of a shared
    # up-projection, chosen by score + bias, beside one squared-ReLU shared
    # expert of `moe_shared_d_ff` at the full width; `experts_held` /
    # `expert_first` as above). The two families do not mix in one stack
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128
    moe_latent: int = 0
    moe_shared_d_ff: int = 0
    # --- per-layer kinds of THIS file's block (served only; the stack is
    # then run as scanned runs of equal layers, `stack_runs`), one entry a
    # layer beside `n_layers`: `layer_windows[l]` > 0 makes layer l's
    # attention WINDOWED (query i sees key j iff 0 <= i - j < window) and
    # its cache a RING of that many rows (models/decode.py), 0 leaves it
    # full; `layer_rope[l]` False leaves layer l's queries and keys
    # without the rotary embedding. Empty: every layer full / rotary as
    # the variant says
    layer_windows: tuple = ()
    layer_rope: tuple = ()
    # ffn_kind "softmax_experts": where the router reads, "ffn" (the
    # feed-forward half's normed input) or "attention" (the ATTENTION
    # half's normed input: the choice is made before attention runs); and
    # an expert's form, "swiglu" or "reglu" (relu where swiglu has silu)
    router_input: str = "ffn"
    expert_form: str = "swiglu"

    def __post_init__(self):
        if not self.head_dim:
            object.__setattr__(self, "head_dim",
                               self.d_model // self.n_heads)
        for name in ("layer_windows", "layer_rope"):
            kinds = tuple(getattr(self, name))
            object.__setattr__(self, name, kinds)
            if kinds and len(kinds) != self.n_layers:
                raise ValueError(
                    f"{name} has {len(kinds)} entries for n_layers "
                    f"{self.n_layers}: one a layer, or none")
        if len({w for w in self.layer_windows if w}) > 1:
            raise ValueError(
                f"layer_windows {self.layer_windows}: the windowed layers "
                "share one window (one ring length, models/decode.py)")
        if (self.router_input not in ("ffn", "attention")
                or self.expert_form not in ("swiglu", "reglu")):
            raise ValueError(
                f"router_input {self.router_input!r} / expert_form "
                f"{self.expert_form!r}: 'ffn' or 'attention', 'swiglu' or "
                "'reglu'")
        if self.generation == "block_diffusion":
            if (self.block_length < 1 or self.denoising_steps < 1
                    or self.block_length % self.denoising_steps
                    or not 0 <= self.mask_token_id < self.vocab_size):
                raise ValueError(
                    "block_diffusion needs block_length >= 1, "
                    "denoising_steps dividing it and a mask_token_id "
                    f"inside the vocabulary: got {self.block_length}, "
                    f"{self.denoising_steps}, {self.mask_token_id}")
        elif self.generation != "autoregressive":
            raise ValueError(f"unknown generation {self.generation!r}")
        if self.attn_kind == "mixers" and set(self.mixer_types) & set(
                SINGLE_MIXERS):
            c = self
            if (len(c.mixer_types) != c.n_layers
                    or not set(c.mixer_types) <= set(SINGLE_MIXERS)
                    or ("mamba2" in c.mixer_types and (
                        min(c.ssm_heads, c.ssm_head_dim, c.ssm_state,
                            c.ssm_groups, c.ssm_chunk) < 1 or c.ssm_conv < 2
                        or c.ssm_heads % c.ssm_groups))
                    or ("attention" in c.mixer_types
                        and c.n_heads % c.n_kv_heads)
                    or ("latent_experts" in c.mixer_types and min(
                        c.moe_latent, c.moe_d_ff, c.moe_shared_d_ff,
                        c.n_routed_experts, c.moe_top_k) < 1)):
                raise ValueError(
                    "attn_kind 'mixers' with single-sublayer layers needs "
                    "mixer_types of n_layers entries among "
                    f"{SINGLE_MIXERS} (none of 'sparse' / 'lightning' beside "
                    "them), ssm_groups dividing ssm_heads and every ssm_* "
                    "size set, n_kv_heads dividing n_heads, and moe_latent, "
                    "moe_d_ff, moe_shared_d_ff, n_routed_experts, moe_top_k "
                    f"set: got {c}")
        elif self.attn_kind == "mixers":
            c = self
            forced = c.sparse_init_blocks + c.sparse_window // max(
                1, c.sparse_block)
            if (len(c.mixer_types) != c.n_layers
                    or set(c.mixer_types) != {"sparse", "lightning"}
                    or c.sparse_kv_heads < 1
                    or c.n_heads % c.sparse_kv_heads
                    or c.sparse_kernel != 2 * c.sparse_stride
                    or c.sparse_block % c.sparse_stride
                    or c.sparse_window % c.sparse_block
                    or forced > c.sparse_topk
                    or c.sparse_dense_len < c.sparse_topk * c.sparse_block):
                raise ValueError(
                    "attn_kind 'mixers' needs mixer_types of n_layers "
                    "'sparse' / 'lightning' entries (both present), "
                    "sparse_kv_heads dividing n_heads, sparse_kernel == 2 * "
                    "sparse_stride, sparse_block a multiple of the stride, "
                    "sparse_window of the block, the forced blocks within "
                    "sparse_topk and sparse_dense_len >= sparse_topk * "
                    f"sparse_block: got {c}")

    @property
    def mixers(self) -> bool:
        """True where the stack is a list of mixers (models/hybrid.py)."""
        return self.attn_kind == "mixers"

    @property
    def held_experts(self) -> bool:
        """True where this file's block runs a no-drop expert layer."""
        return self.attn_kind != "latent" and self.ffn_kind in (
            "softmax_experts", "sigmoid_experts")

    @property
    def dense_layers(self) -> int:
        """The leading layers of this file's block whose feed-forward half
        is one SwiGLU of `d_ff` (their weights under `dense_layers`)."""
        return (min(self.first_k_dense, self.n_layers)
                if self.ffn_kind == "sigmoid_experts" else 0)

    @property
    def layer_kinds(self) -> bool:
        """True where the layers are not all of one kind (`stack_runs`)."""
        return bool(self.layer_windows or self.layer_rope)

    @property
    def default_kinds(self) -> bool:
        return (self.attn_kind, self.norm_kind, self.ffn_kind) == (
            "heads", "pre", "") and not self.layer_kinds

    @property
    def param_count(self) -> int:
        """The parameters HELD (a deployment's share), from the shapes."""
        return sum(math.prod(s) for s in jax.tree.leaves(
            family(self).param_shapes(self),
            is_leaf=lambda s: isinstance(s, tuple)))

    def train_flops_per_token(self, seq: int) -> float:
        """Model FLOPs of one token's forward and backward pass in a
        sequence of ``seq``, the way model-FLOPs utilization counts
        them: the matrix multiplications the mathematics needs (2 per
        multiply-add; a token passes ``moe_top_k`` experts), the causal
        half of attention when the model is causal, the backward twice
        the forward, recomputed operations NOT counted."""
        c = self
        if not c.default_kinds:
            raise NotImplementedError(
                f"train_flops_per_token: attn_kind {c.attn_kind!r} / "
                f"norm_kind {c.norm_kind!r} / ffn_kind {c.ffn_kind!r} are "
                "served, not trained (forward_flops_per_token)")
        attn = c.d_model * c.head_dim * (c.n_heads * 2 + c.n_kv_heads * 2)
        if c.moe_experts:
            ffn = (c.d_model * c.moe_experts
                   + c.moe_top_k * 2 * c.d_model * c.d_ff)
        else:
            ffn = (3 if c.variant == "llama" else 2) * c.d_model * c.d_ff
        matmul = c.n_layers * (attn + ffn) + c.d_model * c.vocab_size
        keys = (seq + 1) / 2 if c.causal else seq
        scores = c.n_layers * 2 * 2 * c.n_heads * c.head_dim * keys
        return 3.0 * (2.0 * matmul + scores)

    def forward_flops_per_token(self, keys: float) -> float:
        """Model FLOPs of one token's forward pass with ``keys`` keys in
        its sight, from the shapes (2 per multiply-add; a token passes
        ``moe_top_k`` of the routed experts): what this file's served
        kinds count where training's kinds count
        :meth:`train_flops_per_token`."""
        c = self
        counted_by = family(c).counted_by
        if counted_by:
            raise NotImplementedError(
                f"forward_flops_per_token: {counted_by} counts this "
                "family's forward")
        layer = param_shapes(c)["layers"]
        matmul = 0
        for name, shape in layer.items():
            if name.startswith("ln") or name in ("b_ff", "b_out"):
                continue
            n = math.prod(shape[1:])
            if name in ("we_gate", "we_up", "we_down"):
                n = n // c.n_routed_experts * c.moe_top_k
            elif name in ("w_in", "w_out"):
                n = n // c.moe_experts * c.moe_top_k
            matmul += n
        scores = 2 * 2 * c.n_heads * c.head_dim * keys
        return c.n_layers * (2.0 * matmul + scores) + (
            2.0 * c.d_model * c.vocab_size)


# the mixers of a stack whose layers are one sublayer each (nemotron_h)
SINGLE_MIXERS = ("mamba2", "attention", "latent_experts")


def single_mixers(pattern: str) -> tuple:
    """``mixer_types`` of a published ``hybrid_override_pattern``."""
    names = dict(zip("M*E", SINGLE_MIXERS))
    return tuple(names[letter] for letter in pattern)


# Per-layer remat policies for remat_scan (distinct from the step-level
# Strategy.remat table in parallel/strategy.py): "full" is an alias of
# "nothing" to match that table's vocabulary for full recompute.
LAYER_REMAT_POLICIES = {
    "nothing": jax.checkpoint_policies.nothing_saveable,
    "full": jax.checkpoint_policies.nothing_saveable,
    "save_attn":
        jax.checkpoint_policies.save_only_these_names("attn_out"),
    # save matmul outputs whose shape has no batch dim (weight-gradient
    # inputs); measured slightly ahead of save_attn on gpt2-small
    "dots_no_batch":
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    # save EVERY matmul output: minimal recompute (the backward re-runs
    # only elementwise ops), highest residual memory — the MFU pick when
    # the model still fits HBM with it
    "dots": jax.checkpoint_policies.dots_saveable,
    # save the two most expensive recomputes (attention output and the
    # gelu'd FFN hidden) by name: most of "dots"' recompute savings at a
    # fraction of its residual memory
    # host-offload variant of save_attn_ffn: the two biggest per-layer
    # activations move to pinned host memory instead of HBM, and the
    # backward fetches them back — activation memory bought with PCIe/
    # host bandwidth instead of recompute FLOPs. The atorch
    # SelectiveOffloadingCheckpoint analog
    # (atorch/auto/opt_lib/selective_offloading_checkpoint.py), native
    # to XLA's memory-space machinery rather than CUDA streams.
    "offload_attn_ffn": jax.checkpoint_policies.
    save_and_offload_only_these_names(
        names_which_can_be_saved=[],
        names_which_can_be_offloaded=["attn_out", "ffn_hidden"],
        offload_src="device", offload_dst="pinned_host",
    ),
    "save_attn_ffn": jax.checkpoint_policies.save_only_these_names(
        "attn_out", "ffn_hidden"
    ),
}


# Named configs, smallest to flagship. Sizes follow public model families
# (the reference's benchmark models: GPT-2 1.5B, Llama-2 7B — BASELINE.md).
CONFIGS = {
    "tiny": TransformerConfig(
        vocab_size=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=176, max_seq_len=128),
    "tiny-moe": TransformerConfig(
        vocab_size=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=128, max_seq_len=128, moe_experts=4),
    "gpt2-small": TransformerConfig(
        vocab_size=50257, d_model=768, n_layers=12, n_heads=12, n_kv_heads=12,
        d_ff=3072, max_seq_len=1024, variant="gpt2"),
    "gpt2-medium": TransformerConfig(
        vocab_size=50257, d_model=1024, n_layers=24, n_heads=16, n_kv_heads=16,
        d_ff=4096, max_seq_len=1024, variant="gpt2"),
    "gpt2-large": TransformerConfig(
        vocab_size=50257, d_model=1280, n_layers=36, n_heads=20, n_kv_heads=20,
        d_ff=5120, max_seq_len=1024, variant="gpt2"),
    "gpt2-xl": TransformerConfig(
        vocab_size=50257, d_model=1600, n_layers=48, n_heads=25, n_kv_heads=25,
        d_ff=6400, max_seq_len=1024, variant="gpt2"),
    "llama2-7b": TransformerConfig(
        vocab_size=32000, d_model=4096, n_layers=32, n_heads=32, n_kv_heads=32,
        d_ff=11008, max_seq_len=4096, variant="llama"),
    "llama3-8b": TransformerConfig(
        vocab_size=128256, d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        d_ff=14336, max_seq_len=8192, variant="llama", rope_theta=500000.0),
    # the kinds of the entry below at a size for CPU tests
    "tiny-latent-moe": TransformerConfig(
        vocab_size=256, d_model=64, n_layers=3, n_heads=4, n_kv_heads=4,
        d_ff=160, max_seq_len=256, rope_theta=10000.0,
        attn_kind="latent", norm_kind="sandwich",
        ffn_kind="sigmoid_experts", q_lora_rank=32, kv_lora_rank=24,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        first_k_dense=1, n_routed_experts=16, moe_top_k=4, moe_d_ff=32,
        n_shared_experts=1, routed_scaling_factor=2.5, dtype="float32"),
    # openPangu-Ultra-MoE-718B as published (config.json, model_type
    # pangu_ultra_moe); a deployment sets the share it holds with
    # dataclasses.replace (n_layers, first_k_dense, experts_held,
    # expert_first, vocab_size). Its multi-token-prediction module is
    # not modelled.
    "openpangu-ultra-moe-718b": TransformerConfig(
        vocab_size=153600, d_model=7680, n_layers=61, n_heads=128,
        n_kv_heads=128, d_ff=18432, max_seq_len=131072,
        rope_theta=25600000.0, attn_kind="latent", norm_kind="sandwich",
        ffn_kind="sigmoid_experts", norm_eps=1e-5, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, first_k_dense=3, n_routed_experts=256,
        moe_top_k=8, moe_d_ff=2048, n_shared_experts=1,
        routed_scaling_factor=2.5, norm_topk_prob=True,
        param_dtype="bfloat16"),
    # the kinds of the entry below at a size for CPU tests (head_dim is
    # NOT d_model / n_heads there either)
    "tiny-sdar-moe": TransformerConfig(
        vocab_size=256, d_model=64, n_layers=3, n_heads=4, n_kv_heads=2,
        head_dim=24, d_ff=96, max_seq_len=256, rope_theta=10000.0,
        rope_pairing="half", norm_eps=1e-6, attn_kind="heads_qk_norm",
        ffn_kind="softmax_experts", n_routed_experts=16, moe_top_k=4,
        moe_d_ff=32, norm_topk_prob=True, dtype="float32",
        generation="block_diffusion", block_length=4, denoising_steps=4,
        mask_token_id=255),
    # SDAR-30B-A3B-Chat as published (config.json, model_type sdar_moe:
    # Qwen3-MoE's block); block length, passes and the mask id are the
    # family's released generation defaults (not in config.json). A
    # deployment sets the layers it holds with dataclasses.replace.
    "sdar-30b-a3b-chat": TransformerConfig(
        vocab_size=151936, d_model=2048, n_layers=48, n_heads=32,
        n_kv_heads=4, head_dim=128, d_ff=6144, max_seq_len=32768,
        rope_theta=1000000.0, rope_pairing="half", norm_eps=1e-6,
        attn_kind="heads_qk_norm", ffn_kind="softmax_experts",
        n_routed_experts=128, moe_top_k=8, moe_d_ff=768,
        norm_topk_prob=True, param_dtype="bfloat16",
        generation="block_diffusion", block_length=4, denoising_steps=4,
        mask_token_id=151669),
    # the kinds of the entry below at a size for CPU tests: sparse sizes
    # small enough that the selection binds inside 100 tokens
    "tiny-sala": TransformerConfig(
        vocab_size=256, d_model=64, n_layers=4, n_heads=4, n_kv_heads=4,
        head_dim=16, d_ff=160, max_seq_len=256, rope_theta=10000.0,
        norm_eps=1e-6, attn_kind="mixers",
        mixer_types=("sparse", "lightning", "lightning", "sparse"),
        sparse_kv_heads=2, sparse_kernel=8, sparse_stride=4,
        sparse_block=16, sparse_topk=4, sparse_init_blocks=1,
        sparse_window=32, sparse_dense_len=64, embed_scale=12.0,
        residual_scale=1.4 / math.sqrt(4), logit_scale=16 / 64,
        dtype="float32"),
    # MiniCPM-SALA as published (config.json, model_type minicpm_sala):
    # `minicpm4` layers are "sparse" (InfLLM-v2; the sparse sizes are
    # MiniCPM4's published sparse_config, which this config.json lacks),
    # `lightning-attn` layers "lightning"; scale_emb 12, scale_depth 1.4
    # over sqrt(32 layers), hidden 4096 / dim_model_base 256 at the head.
    # A deployment sets the layers it holds with dataclasses.replace
    # (mixer_types, n_layers); residual_scale stays the published stack's.
    "minicpm-sala": TransformerConfig(
        vocab_size=73448, d_model=4096, n_layers=32, n_heads=32,
        n_kv_heads=32, head_dim=128, d_ff=16384, max_seq_len=524288,
        rope_theta=10000.0, norm_eps=1e-6, attn_kind="mixers",
        mixer_types=(
            ("sparse",) + ("lightning",) * 8 + ("sparse",)
            + ("lightning",) * 6 + ("sparse",) * 2 + ("lightning",) * 4
            + ("sparse",) + ("lightning",) * 6 + ("sparse",) * 3),
        sparse_kv_heads=2, sparse_kernel=32, sparse_stride=16,
        sparse_block=64, sparse_topk=64, sparse_init_blocks=1,
        sparse_window=2048, sparse_dense_len=8192, embed_scale=12.0,
        residual_scale=1.4 / math.sqrt(32), logit_scale=256 / 4096,
        param_dtype="bfloat16"),
    # the kinds of the entry below at a size for CPU tests: every kind
    # present, a scan chunk of 8 so that a call of a few dozen tokens
    # crosses chunks; all 8 experts held (a test holds a share)
    "tiny-nemotron-h": TransformerConfig(
        vocab_size=256, d_model=64, n_layers=7, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=24, max_seq_len=256, norm_eps=1e-5,
        attn_kind="mixers", mixer_types=single_mixers("MEM*EME"),
        ssm_heads=4, ssm_head_dim=16, ssm_state=8, ssm_groups=2, ssm_conv=4,
        ssm_chunk=8, n_routed_experts=8, moe_top_k=3, moe_d_ff=24,
        moe_latent=16, moe_shared_d_ff=48, n_shared_experts=1,
        routed_scaling_factor=2.5, norm_topk_prob=True, dtype="float32"),
    # NVIDIA-Nemotron-3-Super-120B-A12B as published (config.json,
    # model_type nemotron_h): every layer ONE sublayer, by
    # hybrid_override_pattern (M Mamba-2, E LatentMoE, * attention with no
    # rotary embedding: rope_theta is inert). A deployment sets the share
    # it holds with dataclasses.replace (n_layers, mixer_types,
    # experts_held, expert_first, vocab_size). Its multi-token-prediction
    # module is not modelled.
    "nemotron-3-super-120b-a12b": TransformerConfig(
        vocab_size=131072, d_model=4096, n_layers=88, n_heads=32,
        n_kv_heads=2, head_dim=128, d_ff=2688, max_seq_len=262144,
        rope_theta=10000.0, norm_eps=1e-5, attn_kind="mixers",
        mixer_types=single_mixers(
            "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
            "EMEMEMEMEM*EMEMEMEM*EMEMEMEME"),
        ssm_heads=128, ssm_head_dim=64, ssm_state=128, ssm_groups=8,
        ssm_conv=4, ssm_chunk=128, n_routed_experts=512, moe_top_k=22,
        moe_d_ff=2688, moe_latent=1024, moe_shared_d_ff=5376,
        n_shared_experts=1, routed_scaling_factor=5.0, norm_topk_prob=True,
        param_dtype="bfloat16"),
    # the kinds of the entry below at a size for CPU tests: two periods of
    # [full without rotary, windowed x 3], a window of 16
    "tiny-smallthinker": TransformerConfig(
        vocab_size=256, d_model=64, n_layers=8, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=32, max_seq_len=256, rope_theta=10000.0,
        rope_pairing="half", norm_eps=1e-6, ffn_kind="softmax_experts",
        n_routed_experts=8, moe_top_k=3, moe_d_ff=32, norm_topk_prob=True,
        router_input="attention", expert_form="reglu",
        layer_windows=(0, 16, 16, 16) * 2,
        layer_rope=(False, True, True, True) * 2, dtype="float32"),
    # SmallThinker-21BA3B-Instruct as published (config.json, model_name
    # smallthinker_21b_instruct): rope_layout and sliding_window_layout
    # [0, 1, 1, 1] x 13 (a full layer WITHOUT rotary embedding, then three
    # windowed layers with one), 64 primary ReGLU experts 6 a token, the
    # router on the attention half's normed input; it has no dense width
    # (`d_ff` is inert). A deployment sets the layers it holds with
    # dataclasses.replace (n_layers, layer_windows, layer_rope).
    "smallthinker-21b-a3b-instruct": TransformerConfig(
        vocab_size=151936, d_model=2560, n_layers=52, n_heads=28,
        n_kv_heads=4, head_dim=128, d_ff=768, max_seq_len=16384,
        rope_theta=1500000.0, rope_pairing="half", norm_eps=1e-6,
        ffn_kind="softmax_experts", n_routed_experts=64, moe_top_k=6,
        moe_d_ff=768, norm_topk_prob=True, router_input="attention",
        expert_form="reglu", layer_windows=(0, 4096, 4096, 4096) * 13,
        layer_rope=(False, True, True, True) * 13,
        param_dtype="bfloat16"),
    # the kinds of the entry below at a size for CPU tests: the dense layer
    # and four expert layers, L L L G L, a window of 4, all 16 experts held
    # (a test holds each eighth)
    "tiny-k-exaone": TransformerConfig(
        vocab_size=256, d_model=64, n_layers=5, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=96, max_seq_len=256, rope_theta=10000.0,
        rope_pairing="half", norm_eps=1e-5, attn_kind="heads_qk_norm",
        norm_kind="post", ffn_kind="sigmoid_experts", first_k_dense=1,
        n_routed_experts=16, moe_top_k=4, moe_d_ff=32, n_shared_experts=1,
        routed_scaling_factor=2.5, norm_topk_prob=True,
        layer_windows=(4, 4, 4, 0, 4),
        layer_rope=(True, True, True, False, True), dtype="float32"),
    # K-EXAONE-236B-A23B as published (config.json, model_type exaone_moe):
    # layer_types LLLG x 12 (three windowed layers of 128 with a rotary
    # embedding, then a full layer WITHOUT one), norms on the sublayers'
    # OUTPUTS, q/k norms, layer 0 dense and 47 layers of 128 sigmoid-routed
    # experts 8 a token (score + bias chosen, x 2.5) beside one shared
    # expert. A deployment sets the share it holds with dataclasses.replace
    # (n_layers, layer_windows, layer_rope, first_k_dense, experts_held,
    # expert_first, vocab_size). Its multi-token-prediction module is not
    # modelled.
    "k-exaone-236b-a23b": TransformerConfig(
        vocab_size=153600, d_model=6144, n_layers=48, n_heads=64,
        n_kv_heads=8, head_dim=128, d_ff=18432, max_seq_len=262144,
        rope_theta=1000000.0, rope_pairing="half", norm_eps=1e-5,
        attn_kind="heads_qk_norm", norm_kind="post",
        ffn_kind="sigmoid_experts", first_k_dense=1, n_routed_experts=128,
        moe_top_k=8, moe_d_ff=2048, n_shared_experts=1,
        routed_scaling_factor=2.5, norm_topk_prob=True,
        layer_windows=(128, 128, 128, 0) * 12,
        layer_rope=(True, True, True, False) * 12, param_dtype="bfloat16"),
}


# ------------------------------------------------------------------- init


EXPERT_STACKS = ("we_gate", "we_up", "we_down")


def routed_config(cfg: TransformerConfig):
    """``ops/moe.RoutedConfig`` of the 'softmax_experts' layer (SwiGLU or
    ReGLU experts, no scaling), of a 'sigmoid_experts' one (SwiGLU,
    scaled) or of a 'latent_experts' one (squared-ReLU experts at
    ``moe_latent``, scaled)."""
    from dlrover_tpu.ops.moe import RoutedConfig

    return RoutedConfig(
        n_experts=cfg.n_routed_experts, top_k=cfg.moe_top_k,
        norm_topk=cfg.norm_topk_prob, first=cfg.expert_first,
        held=cfg.experts_held,
        scaling=(1.0 if cfg.ffn_kind == "softmax_experts"
                 else cfg.routed_scaling_factor),
        form="relu2" if cfg.moe_latent else cfg.expert_form)


class Family(NamedTuple):
    """What the rest of ``models/`` asks of an architecture
    (:func:`family`); DESIGN.md §23.7 says how to add one."""
    # cfg -> one label a layer, ``(kind, params key, *what else tells two
    # layers apart)``: what :func:`stack_runs` cuts into runs
    labels: Callable
    param_shapes: Callable      # cfg -> the parameter tree as shapes
    init_cache: Callable        # (cfg, batch, max_len) -> the cache tree
    # (params, tokens, cache, cfg, real) -> (logits, cache)
    forward_cached: Callable
    # (params, tokens, cfg, return_hidden) -> logits or hidden, of a
    # family that is served only; None: `forward_with_aux`'s own path
    forward: Callable | None
    counted_by: str = ""    # the file that counts its forward's FLOPs


def family(cfg: TransformerConfig) -> Family:
    """THE choice of family: the one place that reads a config's kinds to
    decide whose code runs it, and the one place this file imports the
    modules above it."""
    c = cfg
    if c.attn_kind == "latent":
        from dlrover_tpu.models import latent

        return Family(latent.labels, latent.param_shapes, latent.init_cache,
                      latent.forward_cached, latent.forward_uncached,
                      "benchmark/counts/mla_moe.py")
    if c.mixers:
        from dlrover_tpu.models import hybrid

        return Family(hybrid.labels, hybrid.param_shapes, hybrid.init_cache,
                      hybrid.forward_cached, hybrid.forward_uncached,
                      "benchmark/counts/sala.py, ssm_moe.py")
    from dlrover_tpu.models import decode

    # two stacked trees are counted where the family's cell counts them
    counted_by = ("benchmark/counts/swa_shared_moe.py"
                  if c.ffn_kind == "sigmoid_experts" else "")
    if c.layer_kinds:
        return Family(_labels, _block_shapes, decode.init_ring_cache,
                      decode.forward_rings, _forward_served, counted_by)
    served = (c.held_experts or c.generation == "block_diffusion"
              or c.norm_kind != "pre")
    return Family(_labels, _block_shapes, decode.init_row_cache,
                  decode.forward_rows, _forward_served if served else None,
                  counted_by)


class Run(NamedTuple):
    """A run of equal layers of a stack: one scan."""
    # whose cache its layers share: a mixer's name, "window" (rings) or
    # "full" (rows), "latent"
    kind: str
    key: str            # ``params[key]``: the stacked weights they index
    first: int          # its first layer
    # ... among the layers of its kind: where its rows start in that
    # kind's carried stacks
    first_of_kind: int
    n: int              # how many layers


def stack_runs(cfg: TransformerConfig) -> list[Run]:
    """Every family's stack as runs of equal layers: a layer joins the
    run before it when its whole label (``Family.labels``) is the same."""
    runs, seen, first = [], {}, 0
    for (kind, key, *_), group in itertools.groupby(family(cfg).labels(cfg)):
        n = len(list(group))
        runs.append(Run(kind, key, first, seen.get(kind, 0), n))
        seen[kind] = seen.get(kind, 0) + n
        first += n
    return runs


def layer_kind(cfg: TransformerConfig, layer: int) -> tuple[int, bool]:
    """`make_layer_fn`'s ``kind`` of one layer: ``(window, rope)``."""
    return (cfg.layer_windows[layer] if cfg.layer_windows else 0,
            bool(cfg.layer_rope[layer]) if cfg.layer_rope else True)


def _labels(cfg: TransformerConfig) -> list[tuple]:
    """This file's block: a ring or rows a layer, the tree its weights
    lie in (a 'sigmoid_experts' stack's leading dense layers have one of
    their own) and the kind beside."""
    return [("window" if w else "full",
             "dense_layers" if l < cfg.dense_layers else "layers", w, r)
            for l in range(cfg.n_layers) for w, r in [layer_kind(cfg, l)]]


def _check_kinds(cfg: TransformerConfig) -> None:
    """This file's block runs these kinds and names what it does not."""
    c = cfg
    if (c.attn_kind, c.norm_kind, c.ffn_kind) == (
            "latent", "sandwich", "sigmoid_experts"):
        return          # models/latent.py's block, whole
    sigmoid = c.ffn_kind == "sigmoid_experts"
    if (c.attn_kind not in ("heads", "heads_qk_norm", "mixers")
            or c.norm_kind not in ("pre", "post")
            or c.ffn_kind not in ("", "softmax_experts", "sigmoid_experts")
            or (c.ffn_kind and c.moe_experts)
            or (c.mixers and (c.ffn_kind or c.moe_experts
                              or c.norm_kind != "pre"))
            or (not c.default_kinds and c.variant != "llama")
            or c.rope_pairing not in ("interleaved", "half")
            or ((c.router_input != "ffn" or c.expert_form != "swiglu")
                and c.ffn_kind != "softmax_experts")
            or (sigmoid and (min(c.n_routed_experts, c.moe_top_k, c.moe_d_ff,
                                 c.n_shared_experts) < 1
                             or not 0 <= c.first_k_dense <= c.n_layers))
            or (c.layer_kinds and (c.mixers or c.moe_experts
                                   or c.generation != "autoregressive"))):
        raise NotImplementedError(
            f"attn_kind / norm_kind / ffn_kind ({c.attn_kind!r}, "
            f"{c.norm_kind!r}, {c.ffn_kind!r}) with variant {c.variant!r}"
            f", moe_experts {c.moe_experts}, rope_pairing "
            f"{c.rope_pairing!r}: models/transformer.py runs 'heads' or "
            "'heads_qk_norm' attention under 'pre' or 'post' norms with the "
            "variant's FFN, `moe_experts`, 'softmax_experts' (llama "
            "variant; `router_input` and `expert_form` are that kind's) or "
            "'sigmoid_experts' (llama variant; `n_routed_experts`, "
            "`moe_top_k`, `moe_d_ff` and a shared expert set, "
            "`first_k_dense` within the stack), 'mixers' (models/hybrid.py) "
            "under 'pre' norms with the llama FFN, `layer_windows` / "
            "`layer_rope` for autoregressive 'heads' kinds without "
            "`moe_experts` or 'mixers', and models/latent.py runs "
            "('latent', 'sandwich', 'sigmoid_experts')")


def param_shapes(cfg: TransformerConfig) -> dict:
    """The parameter tree as shapes (a tuple a leaf): what
    :func:`init_params` makes and the counts count."""
    _check_kinds(cfg)
    return family(cfg).param_shapes(cfg)


def _block_shapes(cfg: TransformerConfig) -> dict:
    """:func:`param_shapes` of this file's block."""
    c = cfg
    e, hd, n = c.d_model, c.head_dim, c.n_layers
    layers = {
        "wq": (e, c.n_heads, hd), "wk": (e, c.n_kv_heads, hd),
        "wv": (e, c.n_kv_heads, hd), "wo": (c.n_heads, hd, e),
        "ln1": (e,), "ln2": (e,),
    }
    if c.attn_kind == "heads_qk_norm":
        layers.update(ln_q=(hd,), ln_k=(hd,))
    dense = {"w_gate": (e, c.d_ff), "w_down": (c.d_ff, e)}
    if c.variant == "llama":
        dense["w_up"] = (e, c.d_ff)
    else:
        dense.update(b_ff=(c.d_ff,), b_out=(e,), ln1_b=(e,), ln2_b=(e,))
    attention = dict(layers)
    if c.held_experts:
        held, f = routed_config(c).n_held, c.moe_d_ff
        layers.update(w_router=(e, c.n_routed_experts),
                      we_gate=(held, e, f), we_up=(held, e, f),
                      we_down=(held, f, e))
        if c.ffn_kind == "sigmoid_experts":
            fs = c.n_shared_experts * f
            layers.update(b_router=(c.n_routed_experts,), ws_gate=(e, fs),
                          ws_up=(e, fs), ws_down=(fs, e))
    elif c.moe_experts:
        layers.update(w_router=(e, c.moe_experts),
                      w_in=(c.moe_experts, e, c.d_ff),
                      w_out=(c.moe_experts, c.d_ff, e))
    else:
        layers.update(dense)
    tree = {"embed": (c.vocab_size, e)}
    # a stacked tree for each of `_labels`' keys that holds a layer
    for key, shapes, held in (
            ("dense_layers", {**attention, **dense}, c.dense_layers),
            ("layers", layers, n - c.dense_layers)):
        if held:
            tree[key] = {k: (held, *v) for k, v in shapes.items()}
    tree.update(ln_f=(e,), lm_head=(e, c.vocab_size))
    if c.variant == "gpt2":
        tree.update(pos_embed=(c.max_seq_len, e), ln_f_b=(e,))
    return tree


def _log_uniform(key, shape, low: float, high: float) -> jax.Array:
    return jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                      math.log(low), math.log(high)))


# a state-space mixer's and a biased router's small leaves, which are no
# matrices: the decay rates' logarithms `log(uniform[1, 16])`, a time-step
# bias whose softplus is log-uniform in [1e-3, 1e-1], a skip of one, and
# biases drawn small and non-zero (so that leaving one out moves a logit)
_SMALL_LEAVES = {
    "a_log": lambda k, s: jnp.log(jax.random.uniform(
        k, s, jnp.float32, 1.0, 16.0)),
    "dt_bias": lambda k, s: jnp.log(jnp.expm1(_log_uniform(k, s, 1e-3, 1e-1))),
    "d_skip": lambda k, s: jnp.ones(s, jnp.float32),
    "conv_b": lambda k, s: 0.1 * jax.random.normal(k, s, jnp.float32),
    "b_router": lambda k, s: 0.1 * jax.random.normal(k, s, jnp.float32),
}


def init_from_shapes(shapes: dict, key: jax.Array, dtype) -> Params:
    """Seeded weights for a tree of shapes (a tuple a leaf; a nested
    dict's leaves are stacked along a leading layer dim), in ``dtype``:
    matrices normal / sqrt(fan_in) (the contracted dims: an expert
    stack's second, an output projection's first two, else the first),
    norm scales (``ln*``) one. The served kinds' init."""
    dt = jnp.dtype(dtype)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        if name.startswith("ln"):
            leaves.append(jnp.ones(shape, dt))
            continue
        if name in _SMALL_LEAVES:
            leaves.append(_SMALL_LEAVES[name](
                jax.random.fold_in(key, i), shape).astype(dt))
            continue
        core = shape[1:] if len(path) > 1 else shape
        if name in EXPERT_STACKS or name == "conv_w":
            fan_in = core[1]
        elif name in ("wo", "w_o"):
            fan_in = core[0] * core[1]
        else:
            fan_in = core[0]
        leaves.append((jax.random.normal(jax.random.fold_in(key, i), shape,
                                         jnp.float32)
                       / math.sqrt(fan_in)).astype(dt))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def init_params(cfg: TransformerConfig, key: jax.Array) -> Params:
    """Initialize an fp32 parameter pytree (layer-stacked)."""
    c = cfg
    if not c.default_kinds:     # the served kinds: seeded from their shapes
        return init_from_shapes(param_shapes(c), key, c.param_dtype)
    _check_kinds(c)
    k_embed, k_layers, k_out, k_pos = jax.random.split(key, 4)
    hd = c.head_dim

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / math.sqrt(fan_in))

    ks = jax.random.split(k_layers, 8)

    def stack(key, shape, fan_in):
        return dense(key, (c.n_layers, *shape), fan_in)

    layers = {
        "wq": stack(ks[0], (c.d_model, c.n_heads, hd), c.d_model),
        "wk": stack(ks[1], (c.d_model, c.n_kv_heads, hd), c.d_model),
        "wv": stack(ks[2], (c.d_model, c.n_kv_heads, hd), c.d_model),
        "wo": stack(ks[3], (c.n_heads, hd, c.d_model), c.d_model),
        "ln1": jnp.ones((c.n_layers, c.d_model), jnp.float32),
        "ln2": jnp.ones((c.n_layers, c.d_model), jnp.float32),
    }
    if c.moe_experts:
        # one source of truth for expert init: ops/moe.py, stacked per
        # layer via vmap
        from dlrover_tpu.ops.moe import MoeConfig, init_moe_params

        moe = jax.vmap(
            lambda k: init_moe_params(
                k, c.d_model, c.d_ff,
                MoeConfig(n_experts=c.moe_experts),
            )
        )(jax.random.split(ks[4], c.n_layers))
        layers.update(moe)
    else:
        layers["w_gate"] = stack(ks[4], (c.d_model, c.d_ff), c.d_model)
        layers["w_down"] = stack(ks[5], (c.d_ff, c.d_model), c.d_ff)
        if c.variant == "llama":
            layers["w_up"] = stack(ks[6], (c.d_model, c.d_ff), c.d_model)
        else:
            layers["b_ff"] = jnp.zeros((c.n_layers, c.d_ff), jnp.float32)
            layers["b_out"] = jnp.zeros(
                (c.n_layers, c.d_model), jnp.float32
            )
            layers["ln1_b"] = jnp.zeros(
                (c.n_layers, c.d_model), jnp.float32
            )
            layers["ln2_b"] = jnp.zeros(
                (c.n_layers, c.d_model), jnp.float32
            )
    params = {
        "embed": dense(k_embed, (c.vocab_size, c.d_model), c.d_model),
        "layers": layers,
        "ln_f": jnp.ones((c.d_model,), jnp.float32),
        "lm_head": dense(k_out, (c.d_model, c.vocab_size), c.d_model),
    }
    if c.variant == "gpt2":
        params["pos_embed"] = 0.01 * jax.random.normal(
            k_pos, (c.max_seq_len, c.d_model), jnp.float32
        )
        params["ln_f_b"] = jnp.zeros((c.d_model,), jnp.float32)
    return params


def logical_axes(cfg: TransformerConfig) -> Params:
    """Same-structure tree of logical axis names for every weight.

    Vocabulary: layers (scan dim, never sharded), vocab, embed (the big
    model dim — FSDP shards it), heads/kv_heads (TP), mlp (TP).
    """
    c = cfg
    if not c.default_kinds:
        raise NotImplementedError(
            f"logical_axes: attn_kind {c.attn_kind!r} / norm_kind "
            f"{c.norm_kind!r} / ffn_kind {c.ffn_kind!r} are served on one "
            "device; no rule table names their weights (ln_q, ln_k, the "
            "held experts' stacks, the latent projections) yet")
    layers = {
        "wq": ("layers", "embed", "heads", None),
        "wk": ("layers", "embed", "kv_heads", None),
        "wv": ("layers", "embed", "kv_heads", None),
        "wo": ("layers", "heads", None, "embed"),
        "ln1": ("layers", None),
        "ln2": ("layers", None),
    }
    if c.moe_experts:
        from dlrover_tpu.ops.moe import moe_logical_axes

        layers.update({
            name: ("layers", *axes)
            for name, axes in moe_logical_axes().items()
        })
    else:
        layers["w_gate"] = ("layers", "embed", "mlp")
        layers["w_down"] = ("layers", "mlp", "embed")
        if c.variant == "llama":
            layers["w_up"] = ("layers", "embed", "mlp")
        else:
            layers["b_ff"] = ("layers", "mlp")
            layers["b_out"] = ("layers", None)
            layers["ln1_b"] = ("layers", None)
            layers["ln2_b"] = ("layers", None)
    tree = {
        "embed": ("vocab", "embed"),
        "layers": layers,
        "ln_f": (None,),
        "lm_head": ("embed", "vocab"),
    }
    if c.variant == "gpt2":
        tree["pos_embed"] = (None, "embed")
        tree["ln_f_b"] = (None,)
    return tree


# ---------------------------------------------------------------- forward


def rms_norm(x, scale, eps: float):
    x32 = x.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * inv).astype(x.dtype) * scale.astype(x.dtype)


def _norm(x, scale, bias, variant: str, eps: float = 1e-6):
    if variant == "llama":  # `eps` is cfg.norm_eps where the block's
        # kinds set it (`_norm_eps`), else the 1e-6 it always was
        return rms_norm(x, scale, eps)
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mean) * lax.rsqrt(var + 1e-5)
    out = out.astype(x.dtype) * scale.astype(x.dtype)
    return out + bias.astype(x.dtype) if bias is not None else out


def _norm_eps(cfg: TransformerConfig) -> float:
    """The RMSNorm eps of a block: ``cfg.norm_eps`` where its kinds set
    it, the 1e-6 a llama-variant block of default kinds always had."""
    return 1e-6 if cfg.default_kinds else cfg.norm_eps


def _rope(x: jax.Array, positions: jax.Array, theta: float,
          pairing: str = "interleaved") -> jax.Array:
    """Rotary embedding over the last dim. x: [B, S, H, D]. ``pairing``
    "interleaved" rotates components (2i, 2i + 1) together, "half"
    component i with i + D / 2."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions[:, :, None, None].astype(jnp.float32) * freqs  # B,S,1,d/2
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if pairing == "half":
        x1, x2 = x[..., : d // 2], x[..., d // 2:]
    else:
        x1, x2 = x[..., 0::2], x[..., 1::2]
    cos = cos.astype(x.dtype)
    sin = sin.astype(x.dtype)
    if pairing == "half":
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape)


def dense_attention(q, k, v, *, causal: bool = True) -> jax.Array:
    """Reference attention: [B,S,H,D] einsum softmax. fp32 softmax."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        s_q, s_k = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((s_q, s_k), bool))
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _seen_attention(q, k, v, seen, causal: bool = True) -> jax.Array:
    """Attention over a whole sequence, [B,S,H,D], under a mask of
    positions: query ``i`` sees key ``j`` iff ``seen(i [S, 1], j [1, K])``.
    fp32 softmax. ``causal`` is accepted for the call's form and has to be
    true."""
    assert causal
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    mask = seen(jnp.arange(q.shape[1])[:, None], jnp.arange(k.shape[1])[None])
    probs = jax.nn.softmax(jnp.where(mask, logits, -1e30),
                           axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def block_causal_attention(q, k, v, *, block: int,
                           causal: bool = True) -> jax.Array:
    """Attention of a block-diffusion model: positions in blocks of
    ``block``, a query sees every key up to the END of its own block."""
    return _seen_attention(
        q, k, v, lambda i, j: j < (i // block + 1) * block, causal)


def windowed_attention(q, k, v, *, window: int,
                       causal: bool = True) -> jax.Array:
    """Causal attention of a WINDOWED layer: query ``i`` sees key ``j``
    iff ``0 <= i - j < window``."""
    return _seen_attention(
        q, k, v, lambda i, j: (i - j >= 0) & (i - j < window), causal)


def prefix_lm_attention(q, k, v, prefix_len: jax.Array, *,
                        causal: bool = True) -> jax.Array:
    """GLM-class prefix-LM mask: bidirectional inside the per-row
    prefix, causal beyond it.

    Reference analog: the GLM blocks of atorch's model zoo
    (atorch/atorch/modules/distributed_modules/modules_registry.py and
    transformer.py GLM attention/MLP ports) — GLM's objective attends
    bidirectionally over the conditioning prefix and autoregressively
    over the generated span. ``allowed(b, q, k) = k <= q  OR
    k < prefix_len[b]``; ``prefix_len`` is [B] int32. ``causal=False``
    degenerates to full bidirectional (the mask is a no-op then).
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        s_q, s_k = q.shape[1], k.shape[1]
        pos_q = jnp.arange(s_q)
        pos_k = jnp.arange(s_k)
        causal_m = pos_q[:, None] >= pos_k[None, :]          # [q, k]
        prefix_m = (pos_k[None, :]
                    < prefix_len.astype(jnp.int32)[:, None])  # [B, k]
        allowed = causal_m[None] | prefix_m[:, None, :]       # [B, q, k]
        logits = jnp.where(allowed[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


AttentionFn = Callable[..., jax.Array]

# The leaves the block, the embedding and the head multiply or add in
# ``cfg.dtype``: `_leaf` takes a leaf by one of these names and no
# other. The norm scales and biases (`_norm` rounds them itself) and
# the capacity-routed experts (`moe_ffn` reads them as they are) are not
# among them. ``models/decode.weights_at_rest`` keeps exactly these in
# ``cfg.dtype`` for a holder that runs the block many times.
PRODUCT_LEAVES = frozenset({
    "embed", "pos_embed", "wq", "wk", "wv", "wo", "w_og", "w_gate", "w_up",
    "w_down", "b_ff", "b_out", "lm_head", *EXPERT_STACKS, "w_ssm_in",
    "w_ssm_out", "w_lat_down", "w_lat_up", "ws_gate", "ws_up", "ws_down"})


def _leaf(tree, name: str, dt) -> jax.Array:
    """A leaf the products read, in their dtype. Nothing at all where it
    rests there (``weights_at_rest``: the serving engine's tree); on a
    float32 tree (training, ``generate``, the RL rollouts) a conversion
    on every call, in a scope of its own so that a trace prices it."""
    assert name in PRODUCT_LEAVES, name
    with jax.named_scope("weight_cast"):
        return tree[name].astype(dt)


def token_positions(pos, batch: int, seq: int) -> jax.Array:
    """``[batch, seq]`` positions of a call's tokens. ``pos`` is where
    the call starts: None (every row at 0: the uncached forward), a
    scalar (rows in lockstep) or ``[batch]`` (rows at positions of their
    own: the serving engine's slots)."""
    steps = jnp.arange(seq)
    if pos is None:
        return jnp.broadcast_to(steps, (batch, seq))
    if jnp.ndim(pos) == 0:
        return pos + jnp.broadcast_to(steps, (batch, seq))
    return pos[:, None] + steps[None]


def make_layer_fn(
    cfg: TransformerConfig,
    attention_fn: AttentionFn | None = None,
    constrain: Callable[[jax.Array, tuple], jax.Array] | None = None,
    mask: jax.Array | None = None,
    attend: Callable | None = None,
    positions: jax.Array | None = None,
    experts: dict | None = None,
    mixer: str = "",
    kind: tuple | None = None,
    dense: bool = False,
) -> Callable[..., tuple[jax.Array, jax.Array, Any]]:
    """One transformer block as a reusable ``(x, w, state=None,
    index=None) -> (x, aux, state)``: THE definition of what a dense
    layer computes.

    This is the scan body of :func:`forward_with_aux`, of the MPMD
    runtime's per-stage programs (``parallel/mpmd.py``: any divergence
    would break the cross-schedule loss-equivalence bound
    ``RTOL_CROSS_LAYOUT``) and of the cached forward
    (``models/decode.forward_cached``: prefill chunk, decode step,
    verify block). ``w`` is one layer's weight dict (a single slice of
    the stacked ``params["layers"]``); ``aux`` is the MoE load-balancing
    increment (0 for dense FFNs).

    What a cached caller hands over: ``attend(q, k, v, state) -> (o,
    state)``, which owns everything between the projections and the
    output product (it writes K and V into the layer's cache ``state``
    and attends over it), and the ``positions [B, S]`` of the call's
    tokens. Without them the block attends over the call's own tokens
    through ``attention_fn``, positions from 0, and ``state`` passes
    through untouched.

    ``ffn_kind='softmax_experts'`` (served only): ``experts`` holds the
    routed experts' stacks ``we_gate``, ``we_up``, ``we_down`` of ALL
    layers in ``cfg.dtype``, closed over and indexed in place by the
    grouped product's tile loop (``w`` then holds the layer's other
    leaves, and ``index`` says which layer this is: a per-layer slice
    handed to the loop would be copied whole); ``aux`` is then the
    layer's ``loads [held]`` int32, the assignments each held expert
    took, which a cached caller adds to its counters.

    ``attn_kind='mixers'`` (served only): ``mixer`` says which of the
    stack's mixers this block runs ("sparse" or "lightning";
    ``models/hybrid.py`` builds one block a run of equal layers and hands
    over the ``attend`` that owns the rows or the state). Both take q/k
    norms and an output gate ``o * sigmoid(h W_og)``; "sparse" takes no
    rotary embedding; "lightning" norms the concatenated heads' output
    (``ln_o``) before the gate. ``cfg.residual_scale`` multiplies both
    residual branches of every kind (1.0: nothing).

    ``SINGLE_MIXERS`` (served only, the same path): the block is ONE half
    alone, ``x + f(norm(x))``. "attention" is the attention half as it
    stands with no rotary embedding, q/k norm or gate. "mamba2" is the
    mixer half around ``attend(xBC, dt, w, state) -> (y, state)``: the
    block owns the in-projection ``[z | xBC | dt]``, the gated norm
    ``RMSNorm_groups(y * silu(z))`` and the out-projection; the hook owns
    the convolution's window and the state (it reads the layer's small
    leaves from ``w``). "latent_experts" is the feed-forward half:
    sigmoid scores, the choice by score + bias, squared-ReLU experts of
    ``experts`` (``we_up``, ``we_down``, closed over as above) at the
    latent width between ``w_lat_down`` and ``w_lat_up``, beside a
    squared-ReLU shared expert at the full width; ``aux`` is ``loads``;
    ``mask [B, S]`` (None: all) says which tokens are real, and the rest
    reach no routed expert.

    ``cfg.layer_windows`` / ``cfg.layer_rope`` (served only): the layers
    are not all of one kind, so the caller runs the stack as
    :func:`stack_runs` and builds one block a run, ``kind=(window,
    rope)``: ``rope`` False leaves the rotary embedding out; ``window``
    > 0 is the mask of the block's own attention (a cached caller's
    ``attend`` owns its mask, and its ring). ``cfg.router_input``
    "attention": the expert layer's router reads the ATTENTION half's
    normed input, so the choice is made before attention runs.
    """
    c = cfg
    _check_kinds(c)
    dt = jnp.dtype(c.dtype)
    eps = _norm_eps(c)
    pin = constrain or (lambda x, a: x)
    if c.layer_kinds and (kind is None or constrain is not None
                          or mask is not None or attention_fn is not None
                          or c.int8_matmuls or c.mixers):
        raise NotImplementedError(
            "layer_windows / layer_rope (layers of several kinds) are the "
            "forward pass on one device (forward, forward_cached): the "
            "caller runs the stack as `stack_runs` and says which kind "
            "each block is; there is neither a kernel attention, a token "
            "mask, a sharding rule, an int8 path nor a gradient for it "
            "(training, parallel/pipeline.py, parallel/mpmd.py)")
    window, rope = kind or (0, True)
    if c.mixers and (mixer not in ("sparse", "lightning", *SINGLE_MIXERS)
                     or (attend is None) != (mixer == "latent_experts")
                     or (experts is None) == (mixer == "latent_experts")
                     or (mask is not None and mixer != "latent_experts")
                     or constrain is not None or c.int8_matmuls):
        raise NotImplementedError(
            "attn_kind 'mixers' is the forward pass on one device through "
            "models/hybrid.py, which names each run's mixer and owns its "
            "cache (or, for 'latent_experts', closes the experts' stacks "
            "over the block); there is neither a token mask, a sharding "
            "rule, an int8 path nor a gradient for it (training, parallel/"
            "pipeline.py, parallel/mpmd.py)")
    qk_norm = (c.attn_kind == "heads_qk_norm"
               or mixer in ("sparse", "lightning"))
    rotary = (c.variant == "llama" and rope
              and mixer not in ("sparse", "attention"))
    res = c.residual_scale
    if mixer == "latent_experts":
        from dlrover_tpu.ops import moe as _moe

        rcfg = routed_config(c)
    post = c.norm_kind == "post"
    if post and (constrain is not None or mask is not None
                 or c.int8_matmuls):
        raise NotImplementedError(
            "norm_kind 'post' is the forward pass on one device (forward, "
            "forward_cached): there is neither a token mask, a sharding "
            "rule, an int8 path nor a gradient for it (training, parallel/"
            "pipeline.py, parallel/mpmd.py)")
    if dense and c.ffn_kind != "sigmoid_experts":
        raise ValueError("a `dense` block is one of the `first_k_dense` "
                         "layers of a 'sigmoid_experts' stack")
    routed = c.held_experts and not dense
    if c.held_experts:
        if ((experts is None) == routed or mask is not None
                or constrain is not None):
            raise NotImplementedError(
                f"ffn_kind {c.ffn_kind!r} is the forward pass on one "
                "device (forward, forward_cached): the caller closes the "
                "experts' stacks over the block (none over a `dense` one), "
                "and there is neither a token mask, a sharding rule nor a "
                "gradient for it (training, parallel/pipeline.py, "
                "parallel/mpmd.py)")
        if c.int8_matmuls:
            raise NotImplementedError(
                f"int8_matmuls with ffn_kind {c.ffn_kind!r}: the held "
                "experts' grouped product has no int8 path")
        from dlrover_tpu.ops import moe as _moe

        rcfg = routed_config(c)
    router_early = routed and c.router_input == "attention"
    if c.generation == "block_diffusion" and attend is None:
        if attention_fn is not None:
            raise NotImplementedError(
                "generation 'block_diffusion' attends block-causally "
                "(block_causal_attention); the kernel attention kinds "
                "have no such mask")
        attention_fn = partial(block_causal_attention,
                               block=c.block_length)
    if window and attend is None:
        attention_fn = partial(windowed_attention, window=window)
    if attend is None:
        attn = attention_fn or dense_attention
        n_rep = c.n_heads // c.n_kv_heads

        def attend(q, k, v, state):
            if n_rep > 1 and not getattr(attn, "supports_gqa", False):
                # GQA-native impls (splash) read the shared KV directly —
                # repeating here would multiply KV memory traffic by n_rep
                k = jnp.repeat(k, n_rep, axis=2)
                v = jnp.repeat(v, n_rep, axis=2)
            return attn(q, k, v, causal=c.causal), state

    # muP: attention logits scale 1/d_head instead of 1/sqrt(d_head) —
    # pre-scaling q composes with the attention impl's 1/sqrt(d)
    mup_q_scale = (
        1.0 / math.sqrt(c.head_dim) if c.mup_base_width else 1.0
    )

    if c.moe_experts:
        from dlrover_tpu.ops.moe import MoeConfig, moe_ffn

        # Capacity is per call: a cached decode step routes B tokens
        # against a fresh capacity pool, so drop patterns can differ
        # from the training forward when experts overflow; cached and
        # uncached agree exactly in the no-drop regime.
        moe_cfg = MoeConfig(
            n_experts=c.moe_experts, top_k=c.moe_top_k,
            capacity_factor=c.moe_capacity_factor,
        )

    if c.int8_matmuls:
        from dlrover_tpu.ops.quantization import int8_matmul

    def proj(x, wt, expr, n_contract=1):
        """Layer projection: einsum normally, int8 MXU path when enabled.

        ``n_contract`` leading dims of ``wt`` are contracted against the
        trailing dims of ``x`` (the einsum exprs here all have that form).
        """
        if not c.int8_matmuls:
            return jnp.einsum(expr, x, wt)
        k = math.prod(wt.shape[:n_contract])
        xf = x.reshape(*x.shape[:x.ndim - n_contract], k)
        y = int8_matmul(xf, wt.reshape(k, -1))
        return y.reshape(*x.shape[:x.ndim - n_contract],
                         *wt.shape[n_contract:])

    def ssm_half(x, w, state):
        """The Mamba-2 mixer half alone: ``(x + f(norm(x)), state)``."""
        di = c.ssm_heads * c.ssm_head_dim
        bc = 2 * c.ssm_groups * c.ssm_state
        with jax.named_scope("ssm"):
            h = _norm(x, w["ln1"], None, "llama", eps)
            with jax.named_scope("ssm_in_proj"):
                zxd = proj(h, _leaf(w, "w_ssm_in", dt), "bse,ef->bsf")
            z, xbc, dt_raw = (zxd[..., :di], zxd[..., di:2 * di + bc],
                              zxd[..., 2 * di + bc:])
            y, state = attend(xbc, dt_raw, w, state)     # [B, S, H, P]
            with jax.named_scope("ssm_gate_norm"):
                # the norm AFTER the gate, over each group's channels
                y = (y.reshape(z.shape).astype(jnp.float32)
                     * jax.nn.silu(z.astype(jnp.float32)))
                grouped = (*z.shape[:2], c.ssm_groups, di // c.ssm_groups)
                y = _norm(y.reshape(grouped), w["ln_y"].reshape(grouped[2:]),
                          None, "llama", eps).reshape(z.shape).astype(dt)
            with jax.named_scope("ssm_out_proj"):
                o = proj(y, _leaf(w, "w_ssm_out", dt), "bsf,fe->bse")
        return x + o, state

    def latent_expert_half(x, w, index):
        with jax.named_scope("mlp"):
            h = _norm(x, w["ln2"], None, "llama", eps)
            ht = h.reshape(-1, h.shape[-1])
            with jax.named_scope("moe_router"):
                idx, gate = _moe.sigmoid_topk_route(
                    ht, w["w_router"], rcfg, bias=w["b_router"])
                if mask is not None:   # a token that is not real reaches
                    # no expert: nothing is read or counted for it
                    idx = jnp.where(mask.reshape(-1, 1), idx, -1)
            with jax.named_scope("moe_latent"):
                lat = proj(ht, _leaf(w, "w_lat_down", dt), "te,ef->tf")
            with jax.named_scope("moe_experts"):
                routed, loads = _moe.held_expert_ffn(
                    lat, idx, gate, experts, index, rcfg)
            with jax.named_scope("moe_latent"):
                ff = proj(routed.astype(dt), _leaf(w, "w_lat_up", dt),
                          "tf,fe->te")
            with jax.named_scope("moe_shared"):
                ff = ff + _moe.relu2(ht, _leaf(w, "ws_up", dt),
                                     _leaf(w, "ws_down", dt))
        return x + ff.reshape(x.shape), loads

    def layer(x, w, state=None, index=None):
        """One block: activations [B', S, E] -> ([B', S, E], aux_inc,
        state).

        B' is the full batch under scan, a microbatch under the pipeline —
        positions not handed in derive from the input shape so both work.
        """
        aux = jnp.zeros((), jnp.float32)
        if mixer == "mamba2":
            x, state = ssm_half(x, w, state)
            return x, aux, state
        if mixer == "latent_experts":
            return latent_expert_half(x, w, index) + (state,)
        at = (positions if positions is not None
              else token_positions(None, *x.shape[:2]))
        with jax.named_scope("attn"):
            h = x if post else _norm(x, w["ln1"], w.get("ln1_b"), c.variant,
                                     eps)
            if router_early:
                with jax.named_scope("moe_router"):
                    idx, gate = _moe.softmax_topk_route(
                        h.reshape(-1, h.shape[-1]), w["w_router"], rcfg)
            q = proj(h, _leaf(w, "wq", dt), "bse,ehd->bshd")
            if c.mup_base_width:
                q = q * mup_q_scale
            k = proj(h, _leaf(w, "wk", dt), "bse,ehd->bshd")
            v = proj(h, _leaf(w, "wv", dt), "bse,ehd->bshd")
            if qk_norm:
                with jax.named_scope("qk_norm"):
                    q = _norm(q, w["ln_q"], None, "llama", eps)
                    k = _norm(k, w["ln_k"], None, "llama", eps)
            if rotary:
                q = _rope(q, at, c.rope_theta, c.rope_pairing)
                k = _rope(k, at, c.rope_theta, c.rope_pairing)
            o, state = attend(q, k, v, state)
            if mixer in ("sparse", "lightning"):
                with jax.named_scope("out_gate"):
                    if mixer == "lightning":
                        o = _norm(o.reshape(*o.shape[:2], -1), w["ln_o"],
                                  None, "llama", eps).reshape(o.shape)
                    gate = proj(h, _leaf(w, "w_og", dt), "bse,ehd->bshd")
                    o = o * jax.nn.sigmoid(
                        gate.astype(jnp.float32)).astype(dt)
            o = proj(o, _leaf(w, "wo", dt), "bshd,hde->bse", n_contract=2)
            o = checkpoint_name(o, "attn_out")  # inert without a names policy
            if post:
                with jax.named_scope("post_norm"):
                    o = _norm(o, w["ln1"], None, "llama", eps)
            if res != 1.0:
                o = o * res
            x = pin(x + o, ("batch", "sequence", "embed"))
        if mixer == "attention":
            return x, aux, state

        with jax.named_scope("mlp"):
            h = x if post else _norm(x, w["ln2"], w.get("ln2_b"), c.variant,
                                     eps)
            if routed and c.ffn_kind == "sigmoid_experts":
                ff, aux = _moe.sigmoid_expert_half(h, w, experts, index,
                                                   rcfg, dt)
            elif routed:
                ht = h.reshape(-1, h.shape[-1])
                if not router_early:
                    with jax.named_scope("moe_router"):
                        idx, gate = _moe.softmax_topk_route(
                            ht, w["w_router"], rcfg)
                with jax.named_scope("moe_experts"):
                    ff, aux = _moe.held_expert_ffn(
                        ht, idx, gate, experts, index, rcfg)
                ff = ff.reshape(h.shape).astype(dt)
            elif c.moe_experts:
                ff, aux = moe_ffn(
                    {"w_router": w["w_router"], "w_in": w["w_in"],
                     "w_out": w["w_out"]},
                    h, moe_cfg, constrain=pin, token_mask=mask,
                )
            elif c.variant == "llama":
                gate = jax.nn.silu(proj(h, _leaf(w, "w_gate", dt),
                                        "bse,ef->bsf"))
                up = proj(h, _leaf(w, "w_up", dt), "bse,ef->bsf")
                ff = proj(gate * up, _leaf(w, "w_down", dt), "bsf,fe->bse")
            else:
                hidden = jax.nn.gelu(
                    proj(h, _leaf(w, "w_gate", dt), "bse,ef->bsf")
                    + _leaf(w, "b_ff", dt)
                )
                hidden = checkpoint_name(hidden, "ffn_hidden")
                ff = (proj(hidden, _leaf(w, "w_down", dt), "bsf,fe->bse")
                      + _leaf(w, "b_out", dt))
            if post:
                with jax.named_scope("post_norm"):
                    ff = _norm(ff, w["ln2"], None, "llama", eps)
            if res != 1.0:
                ff = ff * res
            x = pin(x + ff, ("batch", "sequence", "embed"))
        return x, aux, state

    return layer


def split_experts(layers: dict, cfg: TransformerConfig):
    """``(experts, indexed)`` of a layer stack: the routed experts'
    stacks it holds, in ``cfg.dtype`` (nothing at all where they rest
    there), which a caller closes over its block (`make_layer_fn`), and
    the leaves its layer loop indexes a layer at a time. ``(None,
    layers)`` for a stack without held experts."""
    dt = jnp.dtype(cfg.dtype)
    experts = {k: _leaf(layers, k, dt) for k in EXPERT_STACKS if k in layers}
    return experts or None, {k: v for k, v in layers.items()
                             if k not in experts}


def scan_runs(runs: list[Run], x: jax.Array, held: dict,
              layer_of: Callable) -> tuple[jax.Array, dict, list]:
    """THE loop of a served stack, whatever the family: one ``lax.scan``
    a run (:func:`stack_runs`). ``layer_of(run)``, asked before the run's
    scan, gives ``(weights, layer)``: the stacked leaves its layers read
    a layer at a time (``params[run.key]`` less what a block closes over:
    `split_experts`) and ``layer(x, carried, w, i) -> (x, carried, out)``,
    ``w`` being ``weights`` at ``i``, the layer's index there. ``carried``
    is ``held[run.kind]``: what the layers of its kind keep (rows, rings,
    state; None without a cache). It rides the CARRY beside the
    activations, and the weights are indexed inside the body: a scanned
    input or output of a layer's shape would be sliced out and copied
    back whole, per layer (models/decode.py). Returns ``(x, held, each
    run's stacked outs)``."""
    at, outs = {}, []
    for run in runs:
        start = at.get(run.key, 0)
        at[run.key] = start + run.n
        weights, layer = layer_of(run)

        def body(carry, i):    # traced here, before the next run rebinds
            w = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, i, keepdims=False),
                weights)
            x, carried, out = layer(*carry, w, i)
            return (x, carried), out

        (x, held[run.kind]), out = lax.scan(
            body, (x, held.get(run.kind)),
            jnp.arange(start, start + run.n, dtype=jnp.int32))
        outs.append(out)
    return x, held, outs


def embed_tokens(
    params: Params,
    tokens: jax.Array,
    cfg: TransformerConfig,
    constrain: Callable[[jax.Array, tuple], jax.Array] | None = None,
    pos: jax.Array | None = None,
) -> jax.Array:
    """Token ids [B, S] -> embedded activations [B, S, E] (the model's
    front end, shared by :func:`forward_with_aux`, the MPMD stage-0
    program and the cached forward). ``pos`` is where the call's tokens
    start, as :func:`token_positions` takes it."""
    c = cfg
    dt = jnp.dtype(c.dtype)
    pin = constrain or (lambda x, a: x)
    # pin the gather result BEFORE the position add: with the table
    # sharded (vocab x embed) and tokens (batch x sequence), the
    # partitioner otherwise leaves the gather's layout ambiguous and
    # falls back to involuntary full rematerialization of the embedding
    # (seen in the r02 4D dryrun tail)
    with jax.named_scope("embed"):
        x = pin(_leaf(params, "embed", dt)[tokens],
                ("batch", "sequence", "embed"))
        if c.variant == "gpt2":
            table = _leaf(params, "pos_embed", dt)
            seq = tokens.shape[1]
            if pos is None:
                pe = table[:seq][None]
            elif jnp.ndim(pos) == 0:
                pe = lax.dynamic_slice_in_dim(table, pos, seq, axis=0)[None]
            else:
                # gather (not slice): per-row positions; clamp keeps the
                # lookup in-table for padded/inactive rows
                pe = table[jnp.clip(token_positions(pos, *tokens.shape),
                                    0, c.max_seq_len - 1)]
            x = pin(x + pe, ("batch", "sequence", "embed"))
        if c.embed_scale != 1.0:
            x = x * c.embed_scale
    return x


def final_norm(params: Params, x: jax.Array,
               cfg: TransformerConfig) -> jax.Array:
    """The post-stack norm (``ln_f``): the model's tail starts here."""
    return _norm(x, params["ln_f"], params.get("ln_f_b"), cfg.variant,
                 _norm_eps(cfg))


def lm_logits(params: Params, hidden: jax.Array,
              cfg: TransformerConfig) -> jax.Array:
    """Final-normed hidden [B, S, E] -> fp32 logits [B, S, vocab]."""
    dt = jnp.dtype(cfg.dtype)
    if cfg.logit_scale != 1.0:
        hidden = hidden * cfg.logit_scale
    logits = jnp.einsum("bse,ev->bsv", hidden, _leaf(params, "lm_head", dt))
    if cfg.mup_base_width:
        # muP readout multiplier keeps logit scale width-invariant
        logits = logits * (cfg.mup_base_width / cfg.d_model)
    return logits.astype(jnp.float32)


def token_ce(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean next-token cross entropy (the unmasked branch of
    :func:`loss_fn`, shared with the MPMD last-stage program — a mean
    over equal-size microbatches composes to the full-batch mean)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


def _forward_served(params, tokens, cfg, return_hidden=False,
                    attention_fn=None, inputs_embeds=None):
    """`forward_with_aux` for the served-only kinds of this file's block
    (held experts, block diffusion, layers of several kinds): the runs
    with no cache. ``attention_fn`` None: the block picks its own
    (block-causal, windowed) and refuses a kernel by name."""
    c = cfg
    x = (inputs_embeds.astype(jnp.dtype(c.dtype))
         if inputs_embeds is not None else embed_tokens(params, tokens, c))
    runs = stack_runs(c)
    split = {key: split_experts(params[key], c)
             for key in {run.key for run in runs}}

    def layer_of(run):
        experts, layers = split[run.key]
        block = make_layer_fn(c, attention_fn=attention_fn, experts=experts,
                              kind=layer_kind(c, run.first),
                              dense=run.key == "dense_layers")
        return layers, lambda x, _, w, i: (block(x, w, None, i)[0], None, None)

    x, _, _ = scan_runs(runs, x, {}, layer_of)
    x = final_norm(params, x, c)
    return x if return_hidden else lm_logits(params, x, c)


def forward(
    params: Params,
    tokens: jax.Array,
    cfg: TransformerConfig,
    attention_fn: AttentionFn | None = None,
    constrain: Callable[[jax.Array, tuple], jax.Array] | None = None,
    prefix_len: jax.Array | None = None,
) -> jax.Array:
    """Token ids [B, S] -> logits [B, S, vocab]."""
    return forward_with_aux(
        params, tokens, cfg, attention_fn=attention_fn,
        constrain=constrain, prefix_len=prefix_len,
    )[0]


def forward_with_aux(
    params: Params,
    tokens: jax.Array,
    cfg: TransformerConfig,
    attention_fn: AttentionFn | None = None,
    constrain: Callable[[jax.Array, tuple], jax.Array] | None = None,
    mask: jax.Array | None = None,
    return_hidden: bool = False,
    inputs_embeds: jax.Array | None = None,
    prefix_len: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """(logits, aux_loss). aux is the MoE load-balancing term (0 when
    the model has no experts). ``return_hidden`` yields the final normed
    hidden states instead of logits (value heads, probes).

    ``constrain(x, logical_axes)`` optionally pins activation shardings
    (supplied by the strategy layer); identity when absent.

    ``inputs_embeds`` [B, S, d_model] bypasses the token embedding (and
    the gpt2 position add) — the caller owns the front end. This is how
    non-token modalities (ViT patches, models/vision.py) reuse the block
    stack with every strategy unchanged.
    """
    c = cfg
    served = family(c).forward
    if served is not None:
        if (c.prefix_lm or c.remat_scan or c.pipeline_stages > 1
                or mask is not None or constrain is not None):
            raise NotImplementedError(
                f"attn_kind {c.attn_kind!r} / norm_kind {c.norm_kind!r} / "
                f"ffn_kind {c.ffn_kind!r} / generation {c.generation!r} / "
                "layer_windows, layer_rope: "
                "the forward pass on one device, and nothing of prefix_lm, "
                "remat_scan, pipeline stages (parallel/pipeline.py), a "
                "token mask or a sharding rule")
        if served is _forward_served:
            served = partial(served, attention_fn=attention_fn,
                             inputs_embeds=inputs_embeds)
        elif attention_fn is not None or inputs_embeds is not None:
            raise NotImplementedError(
                f"attn_kind {c.attn_kind!r} takes tokens and nothing else "
                "(models/latent.py, models/hybrid.py)")
        # served, not trained: no balancing loss, so the aux term is zero
        return (served(params, tokens, c, return_hidden),
                jnp.zeros((), jnp.float32))
    dt = jnp.dtype(c.dtype)
    pin = constrain or (lambda x, a: x)
    if c.prefix_lm:
        if attention_fn is not None and attention_fn is not dense_attention:
            raise NotImplementedError(
                "prefix_lm needs the dense attention path (the sparse "
                "kernels have no per-row prefix mask); leave "
                "cfg.attention='dense'"
            )
        if c.pipeline_stages > 1:
            raise NotImplementedError(
                "prefix_lm + pipeline: the per-row prefix mask is "
                "closed over at full-batch shape, but pipeline stages "
                "see microbatches — the shapes cannot line up"
            )
        if prefix_len is None:
            raise ValueError(
                "cfg.prefix_lm=True but the batch carries no "
                "'prefix_len' [B] array"
            )
        attn = partial(prefix_lm_attention, prefix_len=prefix_len)
    else:
        attn = attention_fn or dense_attention

    if inputs_embeds is not None:
        x = pin(inputs_embeds.astype(dt), ("batch", "sequence", "embed"))
    else:
        x = embed_tokens(params, tokens, cfg, constrain=constrain)

    layer = make_layer_fn(cfg, attention_fn=attn, constrain=constrain,
                          mask=mask)

    if c.remat_interval > 1 and (not c.remat_scan or c.pipeline_stages > 1):
        # would be silently ignored below — reject so sweeps can't
        # attribute numbers to an interleaving that never ran
        raise ValueError(
            "remat_interval > 1 requires remat_scan=True and no pipeline"
        )
    body = layer
    if c.remat_scan:
        if c.remat_policy not in LAYER_REMAT_POLICIES:
            raise ValueError(
                f"unknown remat_policy {c.remat_policy!r}; "
                f"known: {sorted(LAYER_REMAT_POLICIES)}"
            )
        body = jax.checkpoint(
            layer, policy=LAYER_REMAT_POLICIES[c.remat_policy]
        )

    if c.pipeline_stages > 1:
        if c.moe_experts:
            raise NotImplementedError(
                "pipeline + MoE: the GPipe drain steps would pollute the "
                "load-balancing aux loss; use the moe/expert strategies"
            )
        from dlrover_tpu.parallel.pipeline import pipeline_apply

        x = pipeline_apply(
            lambda h, w: body(h, w)[0],
            params["layers"],
            x,
            num_stages=c.pipeline_stages,
            num_microbatches=c.pipeline_microbatches,
            interleave=c.pipeline_interleave,
            constrain=pin,
        )
        aux = jnp.zeros((), jnp.float32)
    elif c.remat_scan and c.remat_interval > 1:
        k = c.remat_interval
        if c.n_layers % k:
            raise ValueError(
                f"remat_interval {k} must divide n_layers {c.n_layers}"
            )
        grouped = jax.tree_util.tree_map(
            lambda a: a.reshape(c.n_layers // k, k, *a.shape[1:]),
            params["layers"],
        )

        def scan_group(carry, wg):
            x, aux = carry
            for i in range(k - 1):
                wi = jax.tree_util.tree_map(lambda a: a[i], wg)
                x, inc, _ = body(x, wi)
                aux = aux + inc
            # last layer of the group runs unrematted: its activations
            # become scan residuals, bought back as skipped recompute
            wl = jax.tree_util.tree_map(lambda a: a[k - 1], wg)
            x, inc, _ = layer(x, wl)
            return (x, aux + inc), None

        (x, aux), _ = lax.scan(
            scan_group, (x, jnp.zeros((), jnp.float32)), grouped,
            unroll=max(1, min(c.scan_unroll, c.n_layers // k)),
        )
    else:
        def scan_body(carry, w):
            x, aux = carry
            x, inc, _ = body(x, w)
            return (x, aux + inc), None

        (x, aux), _ = lax.scan(
            scan_body, (x, jnp.zeros((), jnp.float32)), params["layers"],
            unroll=max(1, c.scan_unroll),
        )

    x = final_norm(params, x, c)
    if return_hidden:
        return x, aux
    return lm_logits(params, x, c), aux


def resolve_config(cfg: TransformerConfig, strategy) -> TransformerConfig:
    """Merge the strategy's model-affecting extras into the config.

    The strategy presets carry attention kind/window and pipeline shape
    in ``strategy.extra`` (e.g. sliding_window, long_context, pipeline);
    training consumes them through make_loss_fn. Anything that reads the
    config OUTSIDE that path — cached decode/serving, parameter counts —
    must use the RESOLVED config or its masks/shapes silently diverge
    from what was trained.
    """
    extra = getattr(strategy, "extra", {}) or {}
    updates: dict = {}
    if extra.get("attention"):
        updates["attention"] = extra["attention"]
    if "attention_window" in extra:
        updates["attention_window"] = int(extra["attention_window"])
    if extra.get("int8_matmuls"):
        updates["int8_matmuls"] = True
    # model-level remat knobs (the measured search, parallel/search.py,
    # expresses its remat cross-product through these)
    if "remat_scan" in extra:
        updates["remat_scan"] = bool(extra["remat_scan"])
    if extra.get("remat_policy"):
        updates["remat_policy"] = extra["remat_policy"]
    if int(extra.get("remat_interval", 0)) > 1:
        updates["remat_interval"] = int(extra["remat_interval"])
    pp = int(extra.get("pipeline_stages", 0))
    if pp > 1:
        # the strategy wins when it pipelines; its microbatch count only
        # overrides the config when actually set (0 = "stage count")
        updates["pipeline_stages"] = pp
        mb = int(extra.get("pipeline_microbatches", 0))
        if mb:
            updates["pipeline_microbatches"] = mb
        il = int(extra.get("pipeline_interleave", 0))
        if il > 1:
            updates["pipeline_interleave"] = il
    return dataclasses.replace(cfg, **updates) if updates else cfg


def make_loss_fn(cfg: TransformerConfig, strategy, mesh) -> Callable:
    """Bind loss_fn to a strategy: activation constraints + attention impl.

    Consumes ``strategy.extra["attention"]`` (or ``cfg.attention``):
    "ring" (long_context preset) and "ulysses" run sequence-parallel
    attention over the mesh's "sequence" axis (ops/ring_attention.py /
    ops/ulysses.py), degrading to dense when the mesh has no sequence
    axis; "flash"/"splash" pick per-device Pallas kernels.
    """
    from dlrover_tpu.parallel.partition import constrain as _constrain

    cfg = resolve_config(cfg, strategy)
    extra = getattr(strategy, "extra", {}) or {}

    pin = partial(_constrain, rules=strategy.rule_table(), mesh=mesh)
    attn: AttentionFn | None = None
    if cfg.attention == "ring":
        from dlrover_tpu.ops.ring_attention import make_ring_attention

        attn = make_ring_attention(mesh)
    elif cfg.attention == "ulysses":
        from dlrover_tpu.ops.ulysses import make_ulysses_attention

        attn = make_ulysses_attention(mesh)
    elif cfg.attention == "flash":
        from dlrover_tpu.ops.flash_attention import flash_attention

        attn = _per_device(flash_attention, mesh)
    elif cfg.attention == "splash":
        from dlrover_tpu.ops.splash_attention import make_splash_attention

        attn = _per_device(make_splash_attention(
            cfg.attention_window,
            native_gqa=bool(extra.get("native_gqa", False)),
        ), mesh)
    return partial(loss_fn, cfg=cfg, attention_fn=attn, constrain=pin)


def _per_device(attn: AttentionFn, mesh) -> AttentionFn:
    """Run a Pallas attention kernel per device under ``shard_map``.

    XLA cannot partition a Mosaic kernel: in a program that spans
    several devices the TPU compiler refuses it ("Mosaic kernels cannot
    be automatically partitioned"). Attention is independent per batch
    row and per head, so each device runs the kernel on its own block —
    batch over the data axes, heads over the tensor axis — and no
    collective is added. A one-device mesh needs no wrapping.
    """
    if mesh.size == 1:
        return attn
    from dlrover_tpu.ops.collectives import shard_map_nocheck
    from dlrover_tpu.parallel.mesh import batch_axes

    batch = batch_axes(mesh)
    heads = "tensor" if "tensor" in mesh.axis_names else None
    spec = PartitionSpec(batch or None, None, heads, None)

    def sharded(q, k, v, causal: bool = True):
        return shard_map_nocheck(
            partial(attn, causal=causal), mesh=mesh,
            in_specs=(spec, spec, spec), out_specs=spec,
        )(q, k, v)

    sharded.supports_gqa = getattr(attn, "supports_gqa", False)
    return sharded


def _blockwise_ce(
    hidden: jax.Array, params: Params, targets: jax.Array,
    mask: jax.Array | None, cfg: TransformerConfig,
) -> jax.Array:
    """Cross entropy over token chunks: logits for one chunk at a time,
    rematerialized in backward, so the [B, S, vocab] f32 logits tensor
    (3.3 GB for gpt2-small at batch 16 / seq 1024 — plus its gradient)
    never lands in HBM. ``hidden`` is the final normed states [B, S, E].
    """
    B, S, D = hidden.shape
    T = B * S
    n = max(1, min(cfg.ce_chunks, T))
    while T % n:  # largest divisor of T not above the requested count
        n -= 1
    xt = hidden.reshape(n, T // n, D)
    tt = targets.reshape(n, T // n)
    mt = (
        jnp.ones((n, T // n), jnp.float32) if mask is None
        else mask.reshape(n, T // n).astype(jnp.float32)
    )
    lm = params["lm_head"]
    mup_scale = (
        cfg.mup_base_width / cfg.d_model if cfg.mup_base_width else 1.0
    )

    def chunk(carry, inp):
        xc, tc, mc = inp
        logits = jnp.einsum(
            "td,dv->tv", xc, lm.astype(xc.dtype)
        ).astype(jnp.float32) * mup_scale
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        return carry + ((lse - gold) * mc).sum(), None

    nll_sum, _ = lax.scan(
        jax.checkpoint(chunk), jnp.zeros((), jnp.float32), (xt, tt, mt)
    )
    return nll_sum / jnp.maximum(mt.sum(), 1.0)


def loss_fn(
    params: Params,
    batch: dict[str, jax.Array],
    cfg: TransformerConfig,
    attention_fn: AttentionFn | None = None,
    constrain=None,
) -> jax.Array:
    """Next-token cross entropy (+ MoE aux). batch: tokens [B, S].

    Under ``cfg.prefix_lm`` the batch carries ``prefix_len`` [B]; when no
    explicit loss mask is given, one is derived so only the generated
    span (positions >= prefix_len) is scored — GLM's objective shape.
    """
    if (cfg.held_experts or cfg.generation != "autoregressive"
            or cfg.norm_kind == "post"):
        raise NotImplementedError(
            f"loss_fn: ffn_kind {cfg.ffn_kind!r} / generation "
            f"{cfg.generation!r} / norm_kind {cfg.norm_kind!r} are served, "
            "not trained: the held experts' tile loop has a data-dependent "
            "trip count (no gradient), a block-diffusion model's objective "
            "is a masked-token loss this file does not have, and no "
            "training path was ever run under 'post' norms")
    tokens = batch["tokens"]
    in_mask = batch.get("mask")
    prefix_len = batch.get("prefix_len") if cfg.prefix_lm else None
    # loss_mask scores only the generated span under prefix_lm; it is
    # NOT fed into forward (there `mask` means token padding and also
    # weights MoE gating stats — prefix tokens are real tokens). A
    # padding mask COMBINES with the span mask rather than replacing
    # it: otherwise a variable-length batch would silently score the
    # prefix and the objective would degrade to full-sequence LM.
    loss_mask = in_mask
    if cfg.prefix_lm and prefix_len is not None:
        positions = jnp.arange(tokens.shape[1])
        span = (positions[None, :]
                >= prefix_len.astype(jnp.int32)[:, None]
                ).astype(jnp.float32)
        loss_mask = span if in_mask is None else (
            in_mask.astype(jnp.float32) * span
        )
    mask_in = in_mask[:, :-1] if in_mask is not None else None
    targets = tokens[:, 1:]
    if cfg.ce_chunks:
        hidden, aux = forward_with_aux(
            params, tokens[:, :-1], cfg,
            attention_fn=attention_fn, constrain=constrain,
            mask=mask_in, return_hidden=True, prefix_len=prefix_len,
        )
        with jax.named_scope("ce_loss"):
            ce = _blockwise_ce(
                hidden, params, targets,
                loss_mask[:, 1:] if loss_mask is not None else None, cfg,
            )
    else:
        logits, aux = forward_with_aux(
            params, tokens[:, :-1], cfg,
            attention_fn=attention_fn, constrain=constrain,
            mask=mask_in, prefix_len=prefix_len,
        )
        with jax.named_scope("ce_loss"):
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(
                logp, targets[..., None], axis=-1
            )[..., 0]
            if loss_mask is not None:
                m = loss_mask[:, 1:].astype(nll.dtype)
                ce = (nll * m).sum() / jnp.maximum(m.sum(), 1.0)
            else:
                ce = nll.mean()
    if cfg.moe_experts:
        ce = ce + cfg.moe_aux_weight * aux
    return ce
