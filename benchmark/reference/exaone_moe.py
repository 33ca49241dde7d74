"""K-EXAONE (``model_type`` ``exaone_moe``: windowed layers of 128 beside full
layers without a rotary embedding, norms on the sublayers' OUTPUTS, q/k norms,
a sigmoid router with a score bias beside a shared expert, a leading dense
layer) in plain ``jax.numpy``: the yardstick for `correct` of a configuration
that names this module as its ``reference``.

Written from the published ``config.json`` and, for what a ``config.json``
never holds, the family's public modeling code (``exaone4`` in Hugging Face
transformers, which ``exaone_moe`` extends; the file's ``assumed``); it
imports nothing of the program. float32, every product at
``Precision.HIGHEST``; no cache, no ring, no chunks, no batching of requests:
one whole sequence at a time, the window an explicit mask over ALL its
positions, the expert layer a loop over the experts held. ``x [S, E]``;
``rms(u; g) = u / sqrt(mean(u^2) + rms_norm_eps) * g``; layer ``l``:

  q, k, v = x Wq [H, hd], x Wk [G, hd], x Wv [G, hd]    no bias, NO norm on x
  q, k = rms(q; ln_q), rms(k; ln_k)     over the hd components of each head
  layer_types[l] == "sliding_attention":
         q, k = rope(q, k; rope_theta, absolute positions), all hd
         components, component i paired with i + hd / 2; allowed iff
         0 <= i - j < sliding_window
  "full_attention":  NO rotary embedding; allowed iff j <= i
  a[i, j] = q_i . k_j / sqrt(hd)   (H / G query heads share a key/value head)
  x   = x + rms(softmax(a) v Wo; ln1)            (post_attention_layernorm)
  mlp_layer_types[l] == "dense":   f = Wd (silu(Wg x) * Wu x)
  "sparse":  s = sigmoid(x Wr)  [S, 128]    over ALL published experts
         C = the num_experts_per_tok largest of s + b_router
         w = s[C] / sum(s[C]) * routed_scaling_factor        (norm_topk_prob)
         f = sum_{e in C, e held} w_e SwiGLU_e(x) + SwiGLU_shared(x)
  x   = x + rms(f; ln2)                          (post_feedforward_layernorm)
  logits = rms(x; ln_f) lm_head                                     (untied)

**Departures from the publication.** Layers are the file's (published layers
0 .. ``num_hidden_layers`` - 1, its ``layer_types``, ``mlp_layer_types`` and
``sliding_windows`` cut to match); the experts held are ``num_experts`` from
``deployment.expert_first`` on (the router keeps ``published.num_experts``
outputs, and what the experts held elsewhere would add is left out); the
vocabulary is the file's slice; the multi-token-prediction module is not
modelled (``num_nextn_predict_layers`` 0). Weights come from the seed ONE
LEAF AT A TIME (:func:`weight`), float32 holding bfloat16's numbers, under
the program's leaf names, normal / sqrt(fan_in) but for ``embed``, drawn at
UNIT variance (``SPREAD``): with no norm on a layer's input the first
layer's products read the embedding as it is, and every sublayer adds a
unit-RMS vector (its output's norm, scales one), so an embedding drawn
plainly (0.013) would be drowned by the first attention's output and every
token of a neighbourhood would route alike. ``b_router`` is drawn at 0.01 x
normal, an expert's leaves by its PUBLISHED index.

``control`` swaps in a fault that `correct` must reject: ``fp8`` (both
operands of every product rounded to e4m3), ``pre_norm_in_place_of_post``
(``ln1`` / ``ln2`` norm the sublayers' inputs and nothing norms their
outputs), ``no_qk_norm``, ``rope_on_full_layers``, ``no_rope_on_windowed``,
``window_127`` (the window one short), ``full_in_place_of_window``,
``softmax_in_place_of_sigmoid`` (the router's scores), ``no_router_bias``,
``no_shared_expert``, ``scaling_1_in_place_of_2.5``, ``one_expert_left_out``
(the first held expert adds nothing), and three of a cache that is a RING of
``W = sliding_window`` slots, position ``p`` in slot ``p % W``, under prefill
chunks of ``serving.prefill_len`` tokens, WIDER than the ring:
``stale_ring`` (the mask trusts the slot's index, not the position it holds),
``pads_in_ring`` (the final chunk of a prompt wrote the last ``W`` of ITS
positions, pads among them: for the queries behind the prompt, a prompt key
whose slot a pad took is the pad's: token 0's embedding through ``Wk`` /
``Wv``, at the pad's position) and ``chunk_keeps_ring_head`` (a chunk left
its FIRST ``W`` tokens in the ring, not its last: a later call finds, where
it believes key ``j``, the key that chunk left in ``j``'s slot).
``bf16`` is a READING, not a fault: every product's operands rounded to
bfloat16, what the stated precision alone moves.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

CONTROLS = ("", "fp8", "pre_norm_in_place_of_post", "no_qk_norm",
            "rope_on_full_layers", "no_rope_on_windowed", "window_127",
            "full_in_place_of_window", "softmax_in_place_of_sigmoid",
            "no_router_bias", "no_shared_expert", "scaling_1_in_place_of_2.5",
            "one_expert_left_out", "stale_ring", "pads_in_ring",
            "chunk_keeps_ring_head")
READINGS = ("bf16",)
Q_BLOCK = 256
TOP = -1          # the "layer" of embed, ln_f and lm_head
EXPERT_STACKS = ("we_gate", "we_up", "we_down")
# leaves drawn at this many times normal / sqrt(fan_in): see the docstring
SPREAD = {"embed": "unit"}


def key_for(seed: int) -> jax.Array:
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    lo, hi = int(seed) & 0x7FFFFFFF, int(seed) >> 31
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def router_width(cfg: dict) -> int:
    """The router's outputs: the PUBLISHED number of experts."""
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def expert_first(cfg: dict) -> int:
    return cfg.get("deployment", {}).get("expert_first", 0)


def leaf_shapes(cfg: dict, layer: int) -> dict:
    """``{name: (shape, fan_in)}`` of one layer, or of the top (``TOP``);
    fan_in 0 marks a leaf that is no matrix (a norm's scale, the bias)."""
    e, h, g = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["num_key_value_heads"])
    hd = cfg["head_dim"]
    if layer == TOP:
        vocab = cfg["vocab_size"]
        return {"embed": ((vocab, e), e), "ln_f": ((e,), 0),
                "lm_head": ((e, vocab), e)}
    shapes = {
        "wq": ((e, h, hd), e), "wk": ((e, g, hd), e), "wv": ((e, g, hd), e),
        "wo": ((h, hd, e), h * hd), "ln_q": ((hd,), 0), "ln_k": ((hd,), 0),
        "ln1": ((e,), 0), "ln2": ((e,), 0),
    }
    if cfg["mlp_layer_types"][layer] == "dense":
        f = cfg["intermediate_size"]
        shapes.update(w_gate=((e, f), e), w_up=((e, f), e),
                      w_down=((f, e), f))
        return shapes
    n, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    fs = cfg["num_shared_experts"] * f
    shapes.update(
        w_router=((e, router_width(cfg)), e),
        b_router=((router_width(cfg),), 0),
        we_gate=((n, e, f), e), we_up=((n, e, f), e), we_down=((n, f, e), f),
        ws_gate=((e, fs), e), ws_up=((e, fs), e), ws_down=((fs, e), fs))
    return shapes


@partial(jax.jit, static_argnums=(0, 1))
def _normal(shape: tuple, scale: float, key: jax.Array) -> jax.Array:
    w = jax.random.normal(key, shape, jnp.float32) * scale
    return w.astype(jnp.bfloat16).astype(jnp.float32)


def weight(cfg: dict, seed: int, layer: int, name: str,
           experts: tuple | None = None) -> jax.Array:
    """One leaf, float32 holding bfloat16's numbers, from the seed.
    ``experts`` (lo, hi): those of the held experts of an expert stack
    alone; an expert's numbers follow its PUBLISHED index (so that a share
    holds the whole's numbers)."""
    shapes = leaf_shapes(cfg, layer)
    shape, fan_in = shapes[name]
    key = jax.random.fold_in(
        jax.random.fold_in(key_for(seed), layer + 1),
        sorted(shapes).index(name))
    if name == "b_router":
        return _normal(shape, 0.01, key)
    if not fan_in:
        return jnp.ones(shape, jnp.float32)
    scale = (1.0 if SPREAD.get(name) == "unit" else 1.0 / math.sqrt(fan_in))
    if name not in EXPERT_STACKS:
        return _normal(shape, scale, key)
    lo, hi = experts or (0, shape[0])
    first = expert_first(cfg)
    return jnp.stack([
        _normal(shape[1:], scale, jax.random.fold_in(key, first + e))
        for e in range(lo, hi)])


def layer_weights(cfg: dict, seed: int, layer: int) -> dict:
    return {name: weight(cfg, seed, layer, name)
            for name in leaf_shapes(cfg, layer)}


def _product(expr: str, a, b, control: str):
    if control == "fp8":   # e4m3 has no infinity: saturate, as a cast on
        # the chip would
        a = jnp.clip(a, -448.0, 448.0).astype(jnp.float8_e4m3fn).astype(
            jnp.float32)
        b = jnp.clip(b, -448.0, 448.0).astype(jnp.float8_e4m3fn).astype(
            jnp.float32)
    if control == "bf16":
        a = a.astype(jnp.bfloat16).astype(jnp.float32)
        b = b.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.einsum(expr, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, positions, theta):
    """``x [S, heads, D]`` at ``positions [S]``: component i is rotated
    with component i + D / 2."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(angles) - x2 * jnp.sin(angles),
                            x1 * jnp.sin(angles) + x2 * jnp.cos(angles)], -1)


def layer_kind(cfg: dict, layer: int, control: str) -> tuple[int, bool, bool]:
    """``(window, rope, dense)`` of a layer, with the control's fault:
    window 0 is a full layer, which the publication leaves without a
    rotary embedding."""
    windowed = cfg["layer_types"][layer] == "sliding_attention"
    window = cfg["sliding_windows"][layer]
    if windowed != bool(window) or (windowed
                                    and window != cfg["sliding_window"]):
        raise ValueError(f"layer {layer}: layer_types says "
                         f"{cfg['layer_types'][layer]!r}, sliding_windows "
                         f"{window}, sliding_window {cfg['sliding_window']}")
    rope = windowed
    if control == "full_in_place_of_window":
        window = 0
    if control == "window_127" and window:
        window -= 1
    if control == "rope_on_full_layers":
        rope = True
    if control == "no_rope_on_windowed":
        rope = False
    return window, rope, cfg["mlp_layer_types"][layer] == "dense"


def _seen(at, keys, window: int, ring: int, control: str):
    """Which of ``keys [K]`` the queries ``at [Q]`` see: ``[Q, K]``."""
    back = at[:, None] - keys[None]
    seen = back >= 0
    if window:
        seen &= back < window
        if control == "stale_ring":
            slot = (keys % ring)[None]
            seen &= (slot <= at[:, None]) & (at[:, None] - slot < ring)
    return seen


def attention(cfg: dict, h, w, window: int, rope: bool, control: str,
              n_prompt, pad_x):
    """``softmax(mask(q k^T / sqrt(hd))) v Wo`` of one whole sequence ``h
    [S, E]``, in blocks of queries so that heads x S x S never exist at
    once. ``n_prompt`` and ``pad_x`` (token 0's embedding) are read by the
    ring's faults alone."""
    mm = partial(_product, control=control)
    g, eps = cfg["num_key_value_heads"], cfg["rms_norm_eps"]
    theta = float(cfg["rope_theta"])
    ring, chunk = cfg["sliding_window"], cfg["prefill_len"]
    s = h.shape[0]
    keys = jnp.arange(s)

    def keys_of(rows, positions):
        """Keys of ``rows [S or 1, E]`` as they lie at ``positions [S]``."""
        k = mm("se,ehd->shd", rows, w["wk"])
        if control != "no_qk_norm":
            k = _rms(k, w["ln_k"], eps)
        k = jnp.broadcast_to(k, (s, *k.shape[1:]))
        return _rope(k, positions, theta) if rope else k

    q = mm("se,ehd->shd", h, w["wq"])
    if control != "no_qk_norm":
        q = _rms(q, w["ln_q"], eps)
    if rope:
        q = _rope(q, keys, theta)
    k = keys_of(h, keys)
    v = mm("se,ehd->shd", h, w["wv"])
    scale = 1.0 / math.sqrt(q.shape[-1])
    qg = q.reshape(s, g, q.shape[1] // g, q.shape[2])
    step = min(Q_BLOCK, s)
    if s % step:
        raise ValueError(f"{s} positions do not split into blocks of {step}")

    # the ring's faults put other keys where a query believes key j. Each
    # is a second set of keys and values and the (query, key) pairs that
    # read it: [(k, v, pairs(at) -> [Q, K] bool)]
    swapped = []
    last = (n_prompt - 1) // chunk * chunk     # the final chunk's start
    end = last + chunk
    if window and control == "pads_in_ring":
        # the last write into key j's slot by the final chunk: a pad's,
        # where that position lies behind the prompt
        pad_at = end - 1 - (end - 1 - keys) % ring
        under = (keys < n_prompt) & (pad_at >= n_prompt)
        swapped.append((
            jnp.where(under[:, None, None], keys_of(pad_x[None], pad_at), k),
            jnp.where(under[:, None, None],
                      mm("se,ehd->shd", pad_x[None], w["wv"]), v),
            lambda at: (at >= n_prompt)[:, None] & under[None]))
    if window and control == "chunk_keeps_ring_head" and chunk > ring:
        # a query of a later chunk believes the ring holds the `ring` keys
        # before its chunk; it holds the FIRST `ring` of the chunk before
        back = jnp.maximum(keys - (chunk - ring), 0)
        swapped.append((k[back], v[back], lambda at: (
            (at < n_prompt)[:, None]
            & (keys[None] < (at // chunk * chunk)[:, None]))))
        # a query behind the prompt finds, in the slot of a prompt key,
        # what the final chunk's first `ring` real tokens left there, or
        # (a slot they did not reach) the chunk before's
        mine = last + keys % ring
        src = jnp.where(mine < n_prompt, mine, jnp.where(
            last >= chunk, mine - chunk, keys))
        swapped.append((k[src], v[src], lambda at: (
            (at >= n_prompt)[:, None] & (keys < n_prompt)[None])))

    def queries(lo):
        ql = jax.lax.dynamic_slice_in_dim(qg, lo, step, 0)
        at = lo + jnp.arange(step)
        seen = _seen(at, keys, window, ring, control)
        scores = mm("qgrd,kgd->grqk", ql, k) * scale
        reads = []
        for k2, _, pairs in swapped:
            reads.append(pairs(at))
            scores = jnp.where(reads[-1], mm("qgrd,kgd->grqk", ql, k2)
                               * scale, scores)
        scores = jnp.where(seen, scores, -jnp.inf)
        # a query that sees nothing (a fault's) gets nothing
        probs = jnp.where(seen.any(-1, keepdims=True),
                          jax.nn.softmax(scores, axis=-1), 0.0)
        plain = jnp.ones_like(seen)
        for mine in reads:
            plain &= ~mine
        o = mm("grqk,kgd->qgrd", jnp.where(plain, probs, 0.0), v)
        for (_, v2, _), mine in zip(swapped, reads):
            o = o + mm("grqk,kgd->qgrd", jnp.where(mine, probs, 0.0), v2)
        return o

    o = jax.lax.map(queries, jnp.arange(0, s, step))
    return mm("shd,hde->se", o.reshape(q.shape), w["wo"])


def swiglu(x, w_gate, w_up, w_down, control: str = ""):
    mm = partial(_product, control=control)
    return mm("sf,fe->se", jax.nn.silu(mm("se,ef->sf", x, w_gate))
              * mm("se,ef->sf", x, w_up), w_down)


def routing(cfg: dict, x, w, control: str = ""):
    """(expert ids ``[S, k]``, gates ``[S, k]``) over ALL published experts:
    the k largest of score + bias, their gates from the scores."""
    r = _product("se,en->sn", x, w["w_router"], control)
    score = (jax.nn.softmax(r, axis=-1)
             if control == "softmax_in_place_of_sigmoid"
             else jax.nn.sigmoid(r))
    ranked = score if control == "no_router_bias" else score + w["b_router"]
    idx = jax.lax.top_k(ranked, cfg["num_experts_per_tok"])[1]
    top = jnp.take_along_axis(score, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        top = top / (top.sum(-1, keepdims=True) + 1e-20)
    scaling = (1.0 if control == "scaling_1_in_place_of_2.5"
               else cfg["routed_scaling_factor"])
    return idx, top * scaling


def expert_layer(cfg: dict, x, w, control: str = "", held=None):
    """``f(x)`` of a sparse layer on rows ``x [S, E]``: the routed sum over
    the experts HELD (a loop over them; the router over all published
    ones) plus the shared expert. ``held`` (first, count) overrides the
    file's share (the test that adds the shares up); ``w['we_*']`` hold
    ``count`` experts."""
    first, count = held or (expert_first(cfg), cfg["num_experts"])
    idx, gate = routing(cfg, x, w, control)

    def one(total, e):
        g = jnp.where(idx == first + e, gate, 0.0).sum(-1)
        out = swiglu(x, w["we_gate"][e], w["we_up"][e], w["we_down"][e],
                     control)
        return total + g[:, None] * out, None

    skipped = 1 if control == "one_expert_left_out" else 0
    routed, _ = jax.lax.scan(one, jnp.zeros_like(x),
                             jnp.arange(skipped, count))
    if control == "no_shared_expert":
        return routed
    return routed + swiglu(x, w["ws_gate"], w["ws_up"], w["ws_down"], control)


def _hashable(cfg: dict) -> tuple:
    """The keys the mathematics reads, as a static jit argument."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rms_norm_eps", "intermediate_size",
            "moe_intermediate_size", "num_experts", "num_experts_per_tok",
            "num_shared_experts", "norm_topk_prob", "routed_scaling_factor",
            "sliding_window")
    if (cfg["scoring_func"], cfg["n_group"], cfg["topk_group"],
            cfg["hidden_act"]) != ("sigmoid", 1, 1, "silu"):
        raise ValueError("this reference has the sigmoid router without a "
                         "group limit and SwiGLU experts, and no other")
    return tuple((k, cfg[k]) for k in keys) + (
        ("rope_theta", float(cfg["rope_parameters"]["rope_theta"])),
        ("prefill_len", cfg["serving"]["prefill_len"]),
        ("expert_first", expert_first(cfg)),
        ("router_width", router_width(cfg)))


@partial(jax.jit, static_argnums=(0, 1, 2))
def block(cfg_key: tuple, kind: tuple, control: str, x, w, n_prompt):
    """One layer of ``kind = (window, rope, dense)`` on one whole sequence
    ``x [S, E]`` (the first ``n_prompt`` positions are the prompt)."""
    cfg = dict(cfg_key)
    cfg["deployment"] = {"expert_first": cfg.pop("expert_first")}
    cfg["published"] = {"num_experts": cfg.pop("router_width")}
    window, rope, dense = kind
    eps = cfg["rms_norm_eps"]
    pre = control == "pre_norm_in_place_of_post"

    def half(x, f, ln):
        if pre:
            return x + f(_rms(x, ln, eps))
        return x + _rms(f(x), ln, eps)      # the norm on the OUTPUT

    x = half(x, lambda h: attention(cfg, h, w, window, rope, control,
                                    n_prompt, w["pad_x"]), w["ln1"])
    if dense:
        return half(x, lambda h: swiglu(h, w["w_gate"], w["w_up"],
                                        w["w_down"], control), w["ln2"])
    return half(x, lambda h: expert_layer(cfg, h, w, control), w["ln2"])


def logits_many(cfg: dict, seed: int, sequences, control: str = "",
                positions=None, prompt_lens=None):
    """For each sequence (1-D id arrays of one length, a multiple of
    ``Q_BLOCK`` where longer): float32 logits at ``positions[i]`` (every
    position when None), ``[len(positions[i]), V]``. ``prompt_lens[i]``
    says where the sequence's prompt ends (all of it when None); only the
    ring's faults read it. The weights are made once a layer and used for
    all the sequences.

    The pads that ``pads_in_ring`` lets in are, in the program, what the
    final chunk computed for token 0 at those positions: a pad's hidden
    state entering a layer is taken as the embedding of token 0 (what lower
    layers add to a pad is left out), which is fault enough.
    """
    if control not in CONTROLS + READINGS:
        raise ValueError(f"unknown control {control!r}")
    key = _hashable(cfg)
    prompt_lens = (list(prompt_lens) if prompt_lens is not None
                   else [len(s) for s in sequences])
    with jax.default_matmul_precision("highest"):
        embed = weight(cfg, seed, TOP, "embed")
        xs = [embed[jnp.asarray(s)] for s in sequences]
        pad_x = embed[0]
        del embed
        for layer in range(cfg["num_hidden_layers"]):
            w = layer_weights(cfg, seed, layer)
            w["pad_x"] = pad_x
            kind = layer_kind(cfg, layer, control)
            for i in range(len(xs)):
                xs[i] = jax.block_until_ready(block(
                    key, kind, control, xs[i], w,
                    jnp.asarray(prompt_lens[i], jnp.int32)))
            del w
        ln_f, head = (weight(cfg, seed, TOP, "ln_f"),
                      weight(cfg, seed, TOP, "lm_head"))
        out = []
        for i, x in enumerate(xs):
            rows = x if positions is None else x[jnp.asarray(positions[i])]
            out.append(_product("se,ev->sv", _rms(rows, ln_f,
                                                  cfg["rms_norm_eps"]),
                                head, control))
        return out


def logits(cfg: dict, seed: int, tokens, control: str = ""):
    """float32 logits ``[S, V]`` of one sequence."""
    return logits_many(cfg, seed, [tokens], control)[0]
