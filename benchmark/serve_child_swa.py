"""The serving child for a WINDOWED-AND-FULL, routed-expert configuration
(``model_name`` ``smallthinker_21b_instruct``: windowed layers whose cache
row is a ring beside full layers without a rotary embedding, a router that
reads the attention's input, ReGLU experts; ``benchmark/reference/
smallthinker.py``). Started by the ``serve_gateway_swa`` driver with a spec
file; writes its answer as JSON, in the form ``serve_child`` writes.
``README.swa.md`` beside this file.

The process IS ``serve_child_hybrid``'s (``serve_child``'s traffic loop,
warm-up and sample, ``serve_child_ref``'s positions and engine logits, the
five numbers of `correct`, the builder's ``CONTROL=sound,a,b`` list):
:func:`main` puts this family's parts in the places of that child's own and
runs its ``main``. What is this family's: every published key of the file
against the program's preset (or the run stops), the program's one layer
stack built from the reference's leaves, and the SAMPLE rule: a sample must
hold a request whose context passed the window by more than a chunk (its
tail positions then lie behind the wrap, a chunk of it straddled the
window's edge, and its tail resumed from a prefix-cache entry whose rings
had wrapped), and a builder's faults are read on the shortest request of all
beside the wrapped one with the longest pad tail, not on the two shortest.
"""

from __future__ import annotations

from benchmark import serve_child, serve_child_hybrid
from benchmark.serve_child_ref import published

# program field -> the published key it must equal
PUBLISHED = {
    "d_model": "hidden_size", "n_heads": "num_attention_heads",
    "n_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
    "vocab_size": "vocab_size", "max_seq_len": "max_position_embeddings",
    "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
    "n_layers": "num_hidden_layers",
    "n_routed_experts": "moe_num_primary_experts",
    "moe_top_k": "moe_num_active_primary_experts",
    "moe_d_ff": "moe_ffn_hidden_size", "norm_topk_prob": "norm_topk_prob",
}
# published keys that say which kinds the program must run
KINDS = {"model_name": "smallthinker_21b_instruct",
         "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
         "rope_scaling": None, "tie_word_embeddings": False}
# the program's kinds of this family
PROGRAM_KINDS = {"attn_kind": "heads", "norm_kind": "pre",
                 "ffn_kind": "softmax_experts", "router_input": "attention",
                 "expert_form": "reglu", "rope_pairing": "half",
                 "variant": "llama", "generation": "autoregressive"}

EXPERTS_A_TIME = 16


def layer_kinds(cfgf: dict, layouts: dict) -> tuple:
    """``(layer_windows, layer_rope)`` of the program for a file's window
    size and a pair of layouts."""
    return (tuple(cfgf["sliding_window_size"] * int(bool(w))
                  for w in layouts["sliding_window_layout"]),
            tuple(bool(r) for r in layouts["rope_layout"]))


def program_config(cfgf: dict):
    """The program's ``TransformerConfig`` for a configuration file: the
    preset it names must hold every published value, then the depth the
    file holds is applied to it."""
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
    for field, value in PROGRAM_KINDS.items():
        same(f"(the program's {field})", getattr(base, field), value)
    windows, rope = layer_kinds(cfgf, {
        k: published(cfgf, k)
        for k in ("sliding_window_layout", "rope_layout")})
    same("sliding_window_layout x sliding_window_size", base.layer_windows,
         windows)
    same("rope_layout", base.layer_rope, rope)
    same("n_routed_experts", cfgf["n_routed_experts"],
         cfgf["moe_num_primary_experts"])
    held = cfgf["num_hidden_layers"]
    same("len(sliding_window_layout), len(rope_layout)",
         (len(cfgf["sliding_window_layout"]), len(cfgf["rope_layout"])),
         (held, held))
    windows, rope = layer_kinds(cfgf, cfgf)
    dtype = cfgf["assumed"]["torch_dtype"]
    return dataclasses.replace(
        base, n_layers=held, layer_windows=windows, layer_rope=rope,
        dtype=dtype, param_dtype=dtype)


def program_params(ref, cfgf: dict, seed: int, pcfg):
    """The program's parameter tree, made of the reference's numbers under
    the program's own leaf names: the layer stack is filled in place, a
    layer's leaf at a time (an expert stack a few experts at a time), so
    that at most one small float32 leaf exists beside what is kept."""
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
    params["layers"] = {}
    for name, shape in shapes["layers"].items():
        stack = jnp.zeros(shape, dt)
        for layer in range(shape[0]):
            if name in ref.EXPERT_STACKS:
                for lo in range(0, shape[1], EXPERTS_A_TIME):
                    hi = min(lo + EXPERTS_A_TIME, shape[1])
                    stack = put_experts(
                        stack, ref.weight(cfgf, seed, layer, name, (lo, hi)),
                        layer, lo)
                    # the host does not run ahead of the device: a leaf
                    # made but not yet put away is a buffer beside what
                    # is kept
                    jax.block_until_ready(stack)
            else:
                stack = jax.block_until_ready(
                    put(stack, ref.weight(cfgf, seed, layer, name), layer))
        params["layers"][name] = stack
    got = jax.tree.map(lambda a: tuple(a.shape), params)
    if got != shapes:
        raise SystemExit(f"the reference's leaves {got} are not the "
                         f"program's {shapes}")
    return jax.block_until_ready(params)


def _context(rec) -> int:
    return len(rec["prompt"]) + len(rec["result"].tokens)


def wrapped(spec, sample) -> list:
    """The sampled requests whose context passed the window by more than a
    chunk."""
    edge = (spec["config"]["sliding_window_size"]
            + spec["serving"]["prefill_len"])
    return [rec for rec in sample if _context(rec) > edge]


def sample_and_prefill(spec, engine, window, control: str):
    """``serve_child.sample_and_prefill`` (the longest finished request and
    a seeded choice of the others), held to this family's rule: a sample
    that holds no wrapped request compares nothing of a ring, and stops
    the run."""
    prefill, sample = serve_child.sample_and_prefill(spec, engine, window,
                                                     control)
    if not wrapped(spec, sample):
        raise SystemExit(
            "the sample holds no request whose context passed "
            "sliding_window_size + prefill_len: nothing of it has been "
            f"through a ring that wrapped ({[_context(r) for r in sample]})")
    return prefill, sample


_hybrid_checks = serve_child_hybrid.reference_checks
_sample: list = []


def reference_checks(spec, ref, sample, control: str, logits: dict,
                     memo: dict | None = None):
    """``serve_child_hybrid.reference_checks``. The first call (`correct`
    itself, ``memo`` None) is the whole sample's; a builder's further
    faults, which that child reads on the sample's two shortest, are read
    here on the shortest of all beside the WRAPPED request whose prompt's
    final chunk has the longest pad tail (the shortest such): a fault of
    the ring shows in no request that never reached the window, and one of
    the pad tail in proportion to the tail."""
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
