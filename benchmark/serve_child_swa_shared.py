"""The serving child for a WINDOWED-AND-FULL configuration with POST-NORMS and
a SIGMOID router beside a SHARED expert (``model_type`` ``exaone_moe``:
windowed layers whose cache row is a ring SHORTER than a prefill chunk beside
full layers without a rotary embedding, norms on the sublayers' outputs, q/k
norms, a leading dense layer, one chip's share of the routed experts;
``benchmark/reference/exaone_moe.py``). Started by the
``serve_gateway_swa_shared`` driver with a spec file; writes its answer as
JSON, in the form ``serve_child`` writes. ``README.swa-shared.md`` beside
this file.

The process IS ``serve_child_hybrid``'s (``serve_child``'s traffic loop,
warm-up and sample, ``serve_child_ref``'s positions and engine logits, the
five numbers of `correct`, the builder's ``CONTROL=sound,a,b`` list):
:func:`main` puts this family's parts in the places of that child's own and
runs its ``main``. What is this family's: every published key of the file
against the program's preset (or the run stops), the program's TWO layer
stacks (the dense layers', the expert layers') built from the reference's
leaves, and the SAMPLE rule: a sample must hold a request whose PROMPT passed
the window by more than a chunk (its prompt then took several chunks, each
wider than the ring, and its last chunk resumed from rings that had wrapped),
and a builder's faults are read on the shortest request of all beside such a
one with the longest pad tail, not on the two shortest.
"""

from __future__ import annotations

from benchmark import serve_child, serve_child_hybrid
from benchmark.serve_child_ref import published
from benchmark.serve_child_swa import _context

# program field -> the published key it must equal
PUBLISHED = {
    "d_model": "hidden_size", "n_heads": "num_attention_heads",
    "n_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
    "d_ff": "intermediate_size", "vocab_size": "vocab_size",
    "max_seq_len": "max_position_embeddings", "norm_eps": "rms_norm_eps",
    "n_layers": "num_hidden_layers", "first_k_dense": "first_k_dense_replace",
    "n_routed_experts": "num_experts", "moe_top_k": "num_experts_per_tok",
    "moe_d_ff": "moe_intermediate_size",
    "n_shared_experts": "num_shared_experts",
    "routed_scaling_factor": "routed_scaling_factor",
    "norm_topk_prob": "norm_topk_prob",
}
# published keys that say which kinds the program must run
KINDS = {"model_type": "exaone_moe", "hidden_act": "silu",
         "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
         "norm_topk_prob": True, "tie_word_embeddings": False,
         "sliding_window_pattern": "LLLG", "num_nextn_predict_layers": 0}
# the program's kinds of this family
PROGRAM_KINDS = {"attn_kind": "heads_qk_norm", "norm_kind": "post",
                 "ffn_kind": "sigmoid_experts", "router_input": "ffn",
                 "expert_form": "swiglu", "rope_pairing": "half",
                 "variant": "llama", "generation": "autoregressive"}

EXPERTS_A_TIME = 2


def layer_kinds(layouts: dict) -> tuple:
    """``(layer_windows, layer_rope, dense layers)`` of the program for a
    file's (or the publication's) three per-layer lists: a windowed layer
    takes the rotary embedding, a full one does not."""
    return (tuple(layouts["sliding_windows"]),
            tuple(t == "sliding_attention" for t in layouts["layer_types"]),
            sum(t == "dense" for t in layouts["mlp_layer_types"]))


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
    same("rope_parameters.rope_theta", base.rope_theta,
         float(cfgf["rope_parameters"]["rope_theta"]))
    same("rope_parameters.rope_type", "default",
         cfgf["rope_parameters"]["rope_type"])
    for key, value in KINDS.items():
        same(key, value, cfgf[key])
    for field, value in PROGRAM_KINDS.items():
        same(f"(the program's {field})", getattr(base, field), value)
    lists = ("sliding_windows", "layer_types", "mlp_layer_types")

    def kinds_of(layouts, n_layers):
        """`layer_kinds` of three lists that agree with each other."""
        same("len(sliding_windows), len(layer_types), len(mlp_layer_types)",
             tuple(len(layouts[k]) for k in lists), (n_layers,) * 3)
        windows, rope, dense = layer_kinds(layouts)
        same("sliding_windows against layer_types x sliding_window", windows,
             tuple(cfgf["sliding_window"] * r for r in rope))
        same("mlp_layer_types (the dense layers lead)",
             tuple(layouts["mlp_layer_types"]),
             ("dense",) * dense + ("sparse",) * (n_layers - dense))
        return windows, rope, dense

    as_published = kinds_of({k: published(cfgf, k) for k in lists},
                            published(cfgf, "num_hidden_layers"))
    same("sliding_windows", base.layer_windows, as_published[0])
    same("layer_types", base.layer_rope, as_published[1])
    same("mlp_layer_types / first_k_dense_replace", base.first_k_dense,
         as_published[2])
    windows, rope, dense = kinds_of(cfgf, cfgf["num_hidden_layers"])
    same("deployment.dense_layers_held", dense,
         cfgf["deployment"]["dense_layers_held"])
    if cfgf["n_routed_experts"] != cfgf["num_experts"]:
        raise SystemExit(
            f"config file num_experts={cfgf['num_experts']!r} (the experts "
            f"held) but n_routed_experts={cfgf['n_routed_experts']!r} beside "
            "it: the accepted expert-layer readers know the second")
    dtype = cfgf["assumed"]["torch_dtype"]
    return dataclasses.replace(
        base, n_layers=cfgf["num_hidden_layers"], layer_windows=windows,
        layer_rope=rope, first_k_dense=dense,
        experts_held=cfgf["num_experts"],
        expert_first=cfgf["deployment"]["expert_first"],
        vocab_size=cfgf["vocab_size"], dtype=dtype, param_dtype=dtype)


def program_params(ref, cfgf: dict, seed: int, pcfg):
    """The program's parameter tree, made of the reference's numbers under
    the program's own leaf names: each of the two layer stacks is filled in
    place, a layer's leaf at a time (an expert stack a few experts at a
    time), so that at most one small float32 leaf exists beside what is
    kept."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from dlrover_tpu.models import transformer as tfm

    dt = jnp.dtype(pcfg.param_dtype)
    put = jax.jit(lambda stack, leaf, i: lax.dynamic_update_index_in_dim(
        stack, leaf.astype(stack.dtype), i, 0), donate_argnums=0)
    put_experts = jax.jit(
        lambda stack, some, i, lo: lax.dynamic_update_slice(
            stack, some.astype(stack.dtype)[None], (i, lo, 0, 0)),
        donate_argnums=0)
    shapes = tfm.param_shapes(pcfg)
    params = {name: ref.weight(cfgf, seed, ref.TOP, name).astype(dt)
              for name in ("embed", "ln_f", "lm_head")}
    dense = cfgf["deployment"]["dense_layers_held"]
    for tree, layers in (("dense_layers", range(dense)),
                         ("layers", range(dense, pcfg.n_layers))):
        if not layers:
            continue
        params[tree] = {}
        for name, shape in shapes[tree].items():
            stack = jnp.zeros(shape, dt)
            for at, layer in enumerate(layers):
                if name in ref.EXPERT_STACKS:
                    for lo in range(0, shape[1], EXPERTS_A_TIME):
                        hi = min(lo + EXPERTS_A_TIME, shape[1])
                        # the host does not run ahead of the device: a
                        # leaf made but not yet put away is a buffer
                        # beside what is kept
                        stack = jax.block_until_ready(put_experts(
                            stack, ref.weight(cfgf, seed, layer, name,
                                              (lo, hi)), at, lo))
                else:
                    stack = jax.block_until_ready(
                        put(stack, ref.weight(cfgf, seed, layer, name), at))
            params[tree][name] = stack
    got = jax.tree.map(lambda a: tuple(a.shape), params)
    if got != shapes:
        raise SystemExit(f"the reference's leaves {got} are not the "
                         f"program's {shapes}")
    return jax.block_until_ready(params)


def wrapped(spec, sample) -> list:
    """The sampled requests whose PROMPT passed the window by more than a
    chunk."""
    edge = spec["config"]["sliding_window"] + spec["serving"]["prefill_len"]
    return [rec for rec in sample if len(rec["prompt"]) > edge]


def sample_and_prefill(spec, engine, window, control: str):
    """``serve_child.sample_and_prefill`` (the longest finished request and
    a seeded choice of the others), held to this family's rule: a sample
    that holds no request whose prompt passed ``sliding_window +
    prefill_len`` compares nothing of a chunk that resumed from a wrapped
    ring, and stops the run."""
    prefill, sample = serve_child.sample_and_prefill(spec, engine, window,
                                                     control)
    if not wrapped(spec, sample):
        raise SystemExit(
            "the sample holds no request whose prompt passed sliding_window "
            "+ prefill_len: nothing of it has been through a chunk behind a "
            f"ring that wrapped ({[len(r['prompt']) for r in sample]})")
    return prefill, sample


_hybrid_checks = serve_child_hybrid.reference_checks
_sample: list = []


def reference_checks(spec, ref, sample, control: str, logits: dict,
                     memo: dict | None = None):
    """``serve_child_hybrid.reference_checks``. The first call (`correct`
    itself, ``memo`` None) is the whole sample's; a builder's further
    faults, which that child reads on the sample's two shortest, are read
    here on the shortest of all beside the request, of those whose prompt
    passed the window by more than a chunk, whose prompt's final chunk has
    the longest pad tail (the shortest such): a fault of the ring under a
    wide chunk shows in no request of a single chunk, and one of the pad
    tail in proportion to the tail."""
    if memo is None:
        _sample[:] = sample
    else:
        chunk = spec["serving"]["prefill_len"]
        by_length = sorted(_sample, key=_context)
        few = [by_length[0], min(
            wrapped(spec, by_length),
            key=lambda rec: (len(rec["prompt"]) % chunk or chunk,
                             _context(rec)))]
        sample = few if few[0] is not few[1] else few[:1]
    return _hybrid_checks(spec, ref, sample, control, logits, memo)


def main(argv=None) -> int:
    serve_child_hybrid.program_config = program_config
    serve_child_hybrid.program_params = program_params
    serve_child_hybrid.sample_and_prefill = sample_and_prefill
    serve_child_hybrid.reference_checks = reference_checks
    return serve_child_hybrid.main(argv)


if __name__ == "__main__":
    raise SystemExit(main())
