"""The serving child for a STATE-SPACE / LATENT-EXPERT configuration
(``model_type`` ``nemotron_h``: Mamba-2 layers whose cache is a float32 state
and a convolution window, latent squared-ReLU experts, a few attention
layers, every layer one sublayer; ``benchmark/reference/nemotron_h.py``).
Started by the ``serve_gateway_ssm`` driver with a spec file; writes its
answer as JSON, in the form ``serve_child`` writes. ``README.ssm.md`` beside
this file.

The process IS ``serve_child_hybrid``'s (its traffic loop, warm-up, sample,
`correct` with its five numbers, the builder's ``CONTROL=sound,a,b`` list):
:func:`main` puts this family's parts in the places of that child's own and
runs its ``main``. What is this family's: every published key of the file
against the program's preset (or the run stops), the program's stacks built
from the reference's leaves a kind at a time, and the POSITIONS the engine's
own logits are taken at, which add the first tokens after the last chunk
boundary: a convolution window carried across a boundary, or resumed from
the prefix cache, shows in the ``conv_kernel - 1`` tokens behind it and
nowhere else.
"""

from __future__ import annotations

from benchmark import serve_child_hybrid, serve_child_ref
from benchmark.serve_child_ref import published

# program field -> the published key it must equal
PUBLISHED = {
    "d_model": "hidden_size", "n_heads": "num_attention_heads",
    "n_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
    "d_ff": "intermediate_size", "vocab_size": "vocab_size",
    "max_seq_len": "max_position_embeddings", "rope_theta": "rope_theta",
    "norm_eps": "layer_norm_epsilon", "n_layers": "num_hidden_layers",
    "ssm_heads": "mamba_num_heads", "ssm_head_dim": "mamba_head_dim",
    "ssm_state": "ssm_state_size", "ssm_groups": "n_groups",
    "ssm_conv": "conv_kernel", "ssm_chunk": "chunk_size",
    "n_routed_experts": "n_routed_experts",
    "moe_top_k": "num_experts_per_tok", "moe_d_ff": "moe_intermediate_size",
    "moe_latent": "moe_latent_size",
    "moe_shared_d_ff": "moe_shared_expert_intermediate_size",
    "n_shared_experts": "n_shared_experts",
    "routed_scaling_factor": "routed_scaling_factor",
    "norm_topk_prob": "norm_topk_prob",
}
# published keys that say which kinds the program must run
KINDS = {"model_type": "nemotron_h", "mamba_hidden_act": "silu",
         "mlp_hidden_act": "relu2", "attention_bias": False,
         "mamba_proj_bias": False, "mlp_bias": False, "use_bias": False,
         "use_conv_bias": True, "tie_word_embeddings": False, "n_group": 1,
         "topk_group": 1, "sliding_window": None,
         "num_nextn_predict_layers": 0}


EXPERTS_A_TIME = 16


def program_config(cfgf: dict):
    """The program's ``TransformerConfig`` for a configuration file: the
    preset it names must hold every published value, then the share the
    file states is applied to it."""
    import dataclasses

    from dlrover_tpu.models import transformer as tfm

    if cfgf["program_model"] not in tfm.CONFIGS:
        raise SystemExit(f"the program has no preset "
                         f"{cfgf['program_model']!r}: it cannot run this "
                         "configuration")
    base = tfm.CONFIGS[cfgf["program_model"]]

    def same(what, mine, theirs):
        if mine != theirs:
            raise SystemExit(f"config file {what}={theirs!r} but the "
                             f"program's {cfgf['program_model']} has "
                             f"{mine!r}")

    for field, key in PUBLISHED.items():
        same(key, getattr(base, field), published(cfgf, key))
    for key, value in KINDS.items():
        same(key, value, cfgf[key])
    same("hybrid_override_pattern", base.mixer_types, tfm.single_mixers(
        published(cfgf, "hybrid_override_pattern")))
    same("expand x hidden_size", base.ssm_heads * base.ssm_head_dim,
         cfgf["expand"] * cfgf["hidden_size"])
    held = cfgf["hybrid_override_pattern"]
    same("num_hidden_layers", len(held), cfgf["num_hidden_layers"])
    dtype = cfgf["assumed"]["torch_dtype"]
    return dataclasses.replace(
        base, n_layers=len(held), mixer_types=tfm.single_mixers(held),
        experts_held=cfgf["n_routed_experts"],
        expert_first=cfgf["deployment"]["expert_first"],
        vocab_size=cfgf["vocab_size"], dtype=dtype, param_dtype=dtype)


def program_params(ref, cfgf: dict, seed: int, pcfg):
    """The program's parameter tree, made of the reference's numbers under
    the program's own leaf names: each kind's stack is filled in place, a
    layer's leaf at a time, so that at most one float32 leaf exists beside
    what is kept."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from dlrover_tpu.models import hybrid

    dt = jnp.dtype(pcfg.param_dtype)
    put = jax.jit(lambda stack, leaf, i: lax.dynamic_update_index_in_dim(
        stack, leaf.astype(stack.dtype), i, 0), donate_argnums=0)
    put_experts = jax.jit(
        lambda stack, some, i, lo: lax.dynamic_update_slice(
            stack, some.astype(stack.dtype)[None], (i, lo, 0, 0)),
        donate_argnums=0)
    shapes = hybrid.param_shapes(pcfg)
    params = {name: ref.weight(cfgf, seed, ref.TOP, name).astype(dt)
              for name in ("embed", "ln_f", "lm_head")}
    for kind in hybrid.kinds_of(pcfg):
        layers = [i for i, m in enumerate(pcfg.mixer_types) if m == kind]
        params[f"{kind}_layers"] = {}
        for name, shape in shapes[f"{kind}_layers"].items():
            stack = jnp.zeros(shape, dt)
            for at, layer in enumerate(layers):
                if name in ref.EXPERT_STACKS:
                    # a layer's experts a few at a time: a whole float32
                    # stack (1.4 GB at the published sizes) beside what is
                    # kept would be set-up's peak, not the engine's
                    for lo in range(0, shape[1], EXPERTS_A_TIME):
                        hi = min(lo + EXPERTS_A_TIME, shape[1])
                        stack = jax.block_until_ready(put_experts(
                            stack, ref.weight(cfgf, seed, layer, name,
                                              (lo, hi)), at, lo))
                else:
                    stack = put(stack, ref.weight(cfgf, seed, layer, name),
                                at)
                # the host does not run ahead of the device: a leaf made
                # but not yet put away is a buffer beside what is kept
                jax.block_until_ready(stack)
            params[f"{kind}_layers"][name] = stack
    got = jax.tree.map(lambda a: tuple(a.shape), params)
    if got != shapes:
        raise SystemExit(f"the reference's leaves {got} are not the "
                         f"program's {shapes}")
    return jax.block_until_ready(params)


_seeded_positions = serve_child_ref._positions


def positions(spec, sample) -> tuple[list, list]:
    """``serve_child_ref._positions`` (the last tokens of every sampled
    prompt; seeded positions of the last chunk of prompt plus answer), the
    tail joined by the first ``limits.boundary_positions`` tokens behind
    the last chunk boundary: what resumes from a stored window."""
    ends, tail = _seeded_positions(spec, sample)
    chunk = spec["serving"]["prefill_len"]
    for i, rec in enumerate(sample):
        n_all = len(rec["prompt"]) + len(rec["result"].tokens)
        boundary = (n_all - 1) // chunk * chunk
        if boundary:
            tail += [(i, n) for n in range(boundary + 1, min(
                n_all, boundary + spec["limits"]["boundary_positions"]) + 1)
                if (i, n) not in tail]
    return ends, sorted(tail)


def main(argv=None) -> int:
    serve_child_hybrid.program_config = program_config
    serve_child_hybrid.program_params = program_params
    serve_child_hybrid._positions = serve_child_ref._positions = positions
    return serve_child_hybrid.main(argv)


if __name__ == "__main__":
    raise SystemExit(main())
