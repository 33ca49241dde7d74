"""SmallThinker (windowed layers beside full layers without a rotary
embedding, a router in front of attention, ReGLU experts) in plain
``jax.numpy``: the yardstick for `correct` of a configuration that names this
module as its ``reference``.

Written from the published ``config.json`` (``model_name``
``smallthinker_21b_instruct``), the catalog's description and the model's
published ``modeling_smallthinker.py``; it imports nothing of the program.
float32, every product at ``Precision.HIGHEST``; no cache, no ring, no
chunks, no batching of requests: one whole sequence at a time, the window an
explicit mask over ALL its positions, the expert layer a loop over all the
experts. ``x [S, E]``; ``rms(u; g) = u / sqrt(mean(u^2) + rms_norm_eps) * g``;
layer ``l``:

  h   = rms(x; ln1)
  r   = h W_r                  [S, 64]   the router reads the ATTENTION's
                                         input ("router placed before
                                         attention"; `assumed.router_input`)
  q, k, v = h Wq [H, hd], h Wk [G, hd], h Wv [G, hd]   no bias, no q/k norm
  rope_layout[l]:  q, k = rope(q, k; rope_theta, absolute positions), all hd
         components, component i paired with i + hd / 2;  else none (NoPE)
  a[i, j] = q_i . k_j / sqrt(hd), allowed iff j <= i and
            (not sliding_window_layout[l] or i - j < sliding_window_size)
            (H / G query heads share a key/value head)
  x   = x + softmax(a) v Wo
  h2  = rms(x; ln2)
  S   = the moe_num_active_primary_experts largest of r
  w   = softmax(r[S])      (moe_primary_router_apply_softmax; equal to the
                            softmax over all 64 renormalised over S)
  x   = x + sum_{e in S} w_e Down_e(relu(Gate_e h2) * Up_e h2)      (ReGLU)
  logits = rms(x; ln_f) lm_head                                     (untied)

**Departures from the publication.** The publication speaks of "secondary
experts"; ``config.json`` has primary experts only, and so does this. Layers
are the file's (published layers 0 .. ``num_hidden_layers`` - 1, its
``rope_layout`` and ``sliding_window_layout`` cut to match). Weights come
from the seed ONE LEAF AT A TIME (:func:`weight`), float32 holding
bfloat16's numbers, under the program's leaf names. Two leaves are drawn
wider than normal / sqrt(fan_in) (``SPREAD``), each for a reason a trained
model does not have. ``wo`` at TWICE the spread: a softmax over thousands of
keys with unit-variance scores averages ~1500 of them, so attention would add
~2% to the stream a layer and a fault of a mask would lie inside rounding.
``embed`` at FIFTY times (a row's numbers then have unit variance, as the
stream has after any layer; drawn plainly they are 0.02 and the first layer's
output drowns the token): with random weights an attention layer's output is
nearly the SAME for every query of a sequence (a running mean of the values),
and where it outweighs what is the token's own, all tokens of a chunk route
alike and the deeper layers read half their experts (seen on the chip with
``wo`` at four times and ``embed`` plain: PERF.md section 6, PR 41).
(Sharpening the softmax instead, ``wq`` and ``wk`` at twice the spread, was
tried first and makes the function chaotic: the reference's own bfloat16
reading then lies 3.5 standard deviations of the logits from it.)

``control`` swaps in a fault that `correct` must reject:
``fp8`` (both operands of every product rounded to e4m3),
``full_in_place_of_window`` (no layer is windowed), ``window_4095`` (the
window one short), ``rope_on_full_layers``, ``no_rope_on_windowed``,
``router_after_attention`` (the router reads ``h2``),
``silu_in_place_of_relu``, ``one_expert_left_out`` (expert 0 adds nothing),
and three of a cache that is a RING of ``W = sliding_window_size`` slots,
position ``p`` in slot ``p % W``:
``stale_ring`` (the mask trusts the slot's index, not the position it holds:
a key in slot ``s`` counts as position ``s``, so past the wrap a query sees
only the slots ``s > i - W`` and none of what overwrote the others),
``ring_reset_at_chunk`` (a windowed layer's keys from before the chunk of
``serving.prefill_len`` that holds the query are lost) and ``pads_in_ring``
(the pad tail of the prompt's final chunk was written into the ring: for the
queries behind the prompt, the keys ``W`` before each pad are the pad's:
token 0's embedding through ``ln1``, ``Wk`` / ``Wv``, at the pad's position).
``bf16`` is a READING, not a fault: every product's operands rounded to
bfloat16, what the stated precision alone moves.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

CONTROLS = ("", "fp8", "full_in_place_of_window", "window_4095",
            "rope_on_full_layers", "no_rope_on_windowed",
            "router_after_attention", "silu_in_place_of_relu", "stale_ring",
            "ring_reset_at_chunk", "pads_in_ring", "one_expert_left_out")
READINGS = ("bf16",)
Q_BLOCK = 256
TOP = -1          # the "layer" of embed, ln_f and lm_head
EXPERT_STACKS = ("we_gate", "we_up", "we_down")
# leaves drawn at this many times normal / sqrt(fan_in)
SPREAD = {"embed": 50.0, "wo": 2.0}


def key_for(seed: int) -> jax.Array:
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    lo, hi = int(seed) & 0x7FFFFFFF, int(seed) >> 31
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def leaf_shapes(cfg: dict, layer: int) -> dict:
    """``{name: (shape, fan_in)}`` of one layer, or of the top (``TOP``);
    fan_in 0 marks a norm's scale (ones)."""
    e, h, g = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["num_key_value_heads"])
    hd, n, f = (cfg["head_dim"], cfg["moe_num_primary_experts"],
                cfg["moe_ffn_hidden_size"])
    if layer == TOP:
        vocab = cfg["vocab_size"]
        return {"embed": ((vocab, e), e), "ln_f": ((e,), 0),
                "lm_head": ((e, vocab), e)}
    return {
        "ln1": ((e,), 0), "wq": ((e, h, hd), e), "wk": ((e, g, hd), e),
        "wv": ((e, g, hd), e), "wo": ((h, hd, e), h * hd), "ln2": ((e,), 0),
        "w_router": ((e, n), e), "we_gate": ((n, e, f), e),
        "we_up": ((n, e, f), e), "we_down": ((n, f, e), f),
    }


@partial(jax.jit, static_argnums=(0, 1))
def _normal(shape: tuple, scale: float, key: jax.Array) -> jax.Array:
    w = jax.random.normal(key, shape, jnp.float32) * scale
    return w.astype(jnp.bfloat16).astype(jnp.float32)


def weight(cfg: dict, seed: int, layer: int, name: str,
           experts: tuple | None = None) -> jax.Array:
    """One leaf, float32 holding bfloat16's numbers, from the seed.
    ``experts`` (lo, hi): those experts of an expert stack alone, each
    keyed by its own index (so that a part holds the whole's numbers)."""
    shapes = leaf_shapes(cfg, layer)
    shape, fan_in = shapes[name]
    if not fan_in:
        return jnp.ones(shape, jnp.float32)
    key = jax.random.fold_in(
        jax.random.fold_in(key_for(seed), layer + 1),
        sorted(shapes).index(name))
    scale = SPREAD.get(name, 1.0) / math.sqrt(fan_in)
    if name not in EXPERT_STACKS:
        return _normal(shape, scale, key)
    lo, hi = experts or (0, shape[0])
    return jnp.stack([_normal(shape[1:], scale, jax.random.fold_in(key, e))
                      for e in range(lo, hi)])


def layer_weights(cfg: dict, seed: int, layer: int) -> dict:
    return {name: weight(cfg, seed, layer, name)
            for name in leaf_shapes(cfg, layer)}


def _product(expr: str, a, b, control: str):
    for name, low in (("fp8", jnp.float8_e4m3fn), ("bf16", jnp.bfloat16)):
        if control == name:
            a = a.astype(low).astype(jnp.float32)
            b = b.astype(low).astype(jnp.float32)
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


def layer_kind(cfg: dict, layer: int, control: str) -> tuple[int, bool]:
    """``(window, rope)`` of a layer, with the control's fault: window 0
    is a full layer."""
    window = (cfg["sliding_window_size"]
              if cfg["sliding_window_layout"][layer] else 0)
    rope = bool(cfg["rope_layout"][layer])
    if control == "full_in_place_of_window":
        window = 0
    if control == "window_4095" and window:
        window -= 1
    if control == "rope_on_full_layers":
        rope = True
    if control == "no_rope_on_windowed":
        rope = False
    return window, rope


def _seen(at, keys, window: int, ring: int, chunk: int, control: str):
    """Which of ``keys [K]`` the queries ``at [Q]`` see: ``[Q, K]``."""
    back = at[:, None] - keys[None]
    seen = back >= 0
    if window:
        seen &= back < window
        if control == "stale_ring":
            slot = (keys % ring)[None]
            seen &= (slot <= at[:, None]) & (at[:, None] - slot < ring)
        if control == "ring_reset_at_chunk":
            seen &= keys[None] >= (at // chunk * chunk)[:, None]
    return seen


def attention(cfg: dict, h, w, window: int, rope: bool, control: str,
              n_prompt, pad_h):
    """``softmax(mask(q k^T / sqrt(hd))) v Wo`` of one whole sequence ``h
    [S, E]`` (already normed), in blocks of queries so that heads x S x S
    never exist at once."""
    mm = partial(_product, control=control)
    g = cfg["num_key_value_heads"]
    theta = float(cfg["rope_theta"])
    ring, chunk = cfg["sliding_window_size"], cfg["prefill_len"]
    s = h.shape[0]
    keys = jnp.arange(s)
    q = mm("se,ehd->shd", h, w["wq"])
    k = mm("se,ehd->shd", h, w["wk"])
    v = mm("se,ehd->shd", h, w["wv"])
    if rope:
        q, k = _rope(q, keys, theta), _rope(k, keys, theta)
    scale = 1.0 / math.sqrt(q.shape[-1])
    qg = q.reshape(s, g, q.shape[1] // g, q.shape[2])
    step = min(Q_BLOCK, s)
    if s % step:
        raise ValueError(f"{s} positions do not split into blocks of {step}")
    faulted = window and control == "pads_in_ring"
    if faulted:
        # the keys a pad of the prompt's final chunk lies over: `ring`
        # before each pad, in the pad's content at the pad's position
        end = -(-n_prompt // chunk) * chunk
        under = (keys + ring >= n_prompt) & (keys + ring < end)
        pad_k = jnp.broadcast_to(mm("e,ehd->hd", pad_h, w["wk"]), k.shape)
        if rope:
            pad_k = _rope(pad_k, keys + ring, theta)
        k2 = jnp.where(under[:, None, None], pad_k, k)
        v2 = jnp.where(under[:, None, None],
                       mm("e,ehd->hd", pad_h, w["wv"])[None], v)

    def queries(lo):
        ql = jax.lax.dynamic_slice_in_dim(qg, lo, step, 0)
        at = lo + jnp.arange(step)
        seen = _seen(at, keys, window, ring, chunk, control)

        def over(k, v):
            scores = mm("qgrd,kgd->grqk", ql, k) * scale
            scores = jnp.where(seen, scores, -jnp.inf)
            # a query that sees nothing (a fault's) gets nothing
            probs = jnp.where(seen.any(-1, keepdims=True),
                              jax.nn.softmax(scores, axis=-1), 0.0)
            return mm("grqk,kgd->qgrd", probs, v)

        o = over(k, v)
        if faulted:
            o = jnp.where((at >= n_prompt)[:, None, None, None],
                          over(k2, v2), o)
        return o

    o = jax.lax.map(queries, jnp.arange(0, s, step))
    return mm("shd,hde->se", o.reshape(q.shape), w["wo"])


def routing(cfg: dict, r):
    """Router logits ``r [S, n]`` -> (chosen experts ``[S, k]``, their
    weights: the softmax over the chosen, float32)."""
    top, idx = jax.lax.top_k(r, cfg["moe_num_active_primary_experts"])
    if not (cfg["moe_primary_router_apply_softmax"]
            and cfg["norm_topk_prob"]):
        raise ValueError("this reference has the softmax router "
                         "renormalised over the chosen, and no other")
    return idx, jax.nn.softmax(top, axis=-1)


def choice_margin(cfg: dict, r):
    """What each row's last chosen expert's router logit lies above the
    first one passed over ``[S]``: under bfloat16 activations a margin of
    a few hundredths is decided by rounding, and the other choice is
    another function of that row."""
    k = cfg["moe_num_active_primary_experts"]
    ranked = jax.lax.top_k(r, k + 1)[0]
    return ranked[:, k - 1] - ranked[:, k]


def expert_layer(cfg: dict, h2, r, w, control: str = ""):
    """The routed sum over ALL experts of rows ``h2 [S, E]`` whose router
    logits are ``r [S, n]``: a loop over the experts."""
    mm = partial(_product, control=control)
    idx, gate = routing(cfg, r)
    act = jax.nn.silu if control == "silu_in_place_of_relu" else jax.nn.relu
    first = 1 if control == "one_expert_left_out" else 0

    def one(y, inputs):
        e, gate_w, up_w, down_w = inputs
        g = jnp.where(idx == e, gate, 0.0).sum(-1)
        out = mm("sf,fe->se", act(mm("se,ef->sf", h2, gate_w))
                 * mm("se,ef->sf", h2, up_w), down_w)
        return y + g[:, None] * out, None

    n = cfg["moe_num_primary_experts"]
    y, _ = jax.lax.scan(one, jnp.zeros_like(h2), (
        jnp.arange(first, n), w["we_gate"][first:], w["we_up"][first:],
        w["we_down"][first:]))
    return y


def _hashable(cfg: dict) -> tuple:
    """The keys the mathematics reads, as a static jit argument."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rope_theta", "rms_norm_eps", "moe_ffn_hidden_size",
            "moe_num_primary_experts", "moe_num_active_primary_experts",
            "moe_primary_router_apply_softmax", "norm_topk_prob",
            "sliding_window_size")
    return tuple((k, cfg[k]) for k in keys) + (
        ("prefill_len", cfg["serving"]["prefill_len"]),)


@partial(jax.jit, static_argnums=(0, 1, 2, 3))
def block(cfg_key: tuple, window: int, rope: bool, control: str, x, w,
          n_prompt):
    """One layer on one whole sequence ``x [S, E]`` (the first ``n_prompt``
    positions are the prompt) -> (x, each position's choice margin)."""
    cfg = dict(cfg_key)
    eps = cfg["rms_norm_eps"]
    mm = partial(_product, control=control)
    h = _rms(x, w["ln1"], eps)
    x = x + attention(cfg, h, w, window, rope, control, n_prompt,
                      _rms(w["pad_x"], w["ln1"], eps))
    h2 = _rms(x, w["ln2"], eps)
    r = mm("se,en->sn", h2 if control == "router_after_attention" else h,
           w["w_router"])
    return x + expert_layer(cfg, h2, r, w, control), choice_margin(cfg, r)


def logits_many(cfg: dict, seed: int, sequences, control: str = "",
                positions=None, prompt_lens=None, margins: bool = False):
    """For each sequence (1-D id arrays of one length, a multiple of
    ``Q_BLOCK`` where longer): float32 logits at ``positions[i]`` (every
    position when None), ``[len(positions[i]), V]``. ``prompt_lens[i]``
    says where the sequence's prompt ends (all of it when None); only
    ``pads_in_ring`` reads it. ``margins``: also each position's smallest
    :func:`choice_margin` over the layers. The weights are made once a
    layer and used for all the sequences.

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
        least = [jnp.full(x.shape[:1], jnp.inf) for x in xs]
        pad_x = embed[0]
        del embed
        for layer in range(cfg["num_hidden_layers"]):
            w = layer_weights(cfg, seed, layer)
            w["pad_x"] = pad_x
            window, rope = layer_kind(cfg, layer, control)
            for i in range(len(xs)):
                xs[i], m = jax.block_until_ready(block(
                    key, window, rope, control, xs[i], w,
                    jnp.asarray(prompt_lens[i], jnp.int32)))
                least[i] = jnp.minimum(least[i], m)
            del w
        ln_f, head = (weight(cfg, seed, TOP, "ln_f"),
                      weight(cfg, seed, TOP, "lm_head"))
        out = []
        for i, x in enumerate(xs):
            rows = x if positions is None else x[jnp.asarray(positions[i])]
            out.append(_product("se,ev->sv", _rms(rows, ln_f,
                                                  cfg["rms_norm_eps"]),
                                head, control))
        return (out, least) if margins else out


def logits(cfg: dict, seed: int, tokens, control: str = ""):
    """float32 logits ``[S, V]`` of one sequence."""
    return logits_many(cfg, seed, [tokens], control)[0]
