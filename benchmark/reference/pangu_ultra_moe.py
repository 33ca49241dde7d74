"""openPangu-Ultra-MoE in plain ``jax.numpy``: the yardstick for `correct`
of a configuration that names this module as its ``reference``.

Written from the published ``config.json`` (model_type ``pangu_ultra_moe``;
its keys are DeepSeek-V3's) and the family's description; it imports
nothing of the program. float32, every product at ``Precision.HIGHEST``
(``jax.default_matmul_precision("highest")`` around the entry points too);
no kernel, no cache, no batching: one sequence at a time, the equations in
their EXPANDED form, a loop over the experts held. RMSNorm ``N`` with
``rms_norm_eps``; ``h`` is a layer's normed input:

  sandwich norm   x = x + N_post_attn(Attn(N_in(x)));
                  x = x + N_post_mlp(FFN(N_pre_mlp(x)))
  latent attention  c_q = N_q(h W_qa); [q_nope | q_rope]_h = c_q W_qb;
                  [c_kv | k_r] = h W_kva; c_kv = N_kv(c_kv); RoPE on q_rope
                  and on k_r (one k_rope for all heads);
                  [k_nope | v]_h = c_kv W_kvb;
                  score_h = (q_nope_h . k_nope_h + q_rope_h . k_rope)
                            / sqrt(qk_nope_head_dim + qk_rope_head_dim),
                  causal softmax, out = concat_h(sum p v_h) W_o; no biases
  expert layer    s = sigmoid(h W_r) over ALL routed experts; the
                  num_experts_per_tok largest; g = s_sel / (sum s_sel +
                  1e-20) * routed_scaling_factor;
                  y = sum_e g_e Down_e(silu(Gate_e h) * Up_e h) + Shared(h)
  dense layer     (the first first_k_dense_replace) one SwiGLU of
                  intermediate_size

Departures from the publication, each because the system under test serves
this and the reference must compute the same function:
  * **the share.** The configuration file holds ONE chip's share of a
    deployment that divides each layer over several chips: ``n_routed_experts``
    experts are held (from ``deployment.expert_first`` on) of
    ``published.n_routed_experts``. The router scores all published experts
    and picks among all; the sum runs over the held ones only, and what the
    absent experts would add is left out. The shared expert and attention
    are whole. ``vocab_size`` is the rows of the embedding and of the head
    held (a smaller vocabulary: ids and logits are over the slice).
  * **the depth.** ``num_hidden_layers`` layers are held, the first
    ``deployment.dense_layers_held`` of them dense (the publication: 61 and 3).
  * **no multi-token-prediction module** (``num_nextn_predict_layers`` 0).
  * assumed, where the config is silent (the file's ``assumed``): the router
    is a plain sigmoid with neither group limit nor correction bias; RoPE
    pairs components ``(2i, 2i + 1)`` with no scaling; weights are seeded
    normal / sqrt(fan_in), **rounded to bfloat16 and widened again**, so that
    a program whose weights rest in bfloat16 holds the same numbers.

Weights come from the seed ONE LEAF AT A TIME (:func:`weight`), keyed by
layer and name, so that a float32 layer (4.0 GB at the published widths)
fits beside the activations and the program can build its stacks from the
same generator. Attention runs in blocks of ``Q_BLOCK`` queries.

``control`` swaps in a fault that `correct` must reject: ``"fp8"`` rounds
both operands of every product to e4m3 (the precision below the bfloat16
the configuration states); ``"drop_expert"`` leaves the first held expert
out; ``"no_scaling"`` leaves ``routed_scaling_factor`` out;
``"no_post_norms"`` leaves the two post-norms out.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

CONTROLS = ("", "fp8", "drop_expert", "no_scaling", "no_post_norms")
Q_BLOCK = 256
TOP = -1          # the "layer" of embed, ln_f and lm_head


def key_for(seed: int) -> jax.Array:
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    lo, hi = int(seed) & 0x7FFFFFFF, int(seed) >> 31
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def is_expert_layer(cfg: dict, layer: int) -> bool:
    return layer >= cfg["deployment"]["dense_layers_held"]


def leaf_shapes(cfg: dict, layer: int) -> dict:
    """``{name: (shape, fan_in)}`` of one layer, or of the top (``TOP``);
    fan_in 0 marks a norm's scale (ones)."""
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    if layer == TOP:
        vocab = cfg["vocab_size"]
        return {"embed": ((vocab, e), e), "ln_f": ((e,), 0),
                "lm_head": ((e, vocab), e)}
    out = {
        "ln_in": ((e,), 0), "w_qa": ((e, qr), e), "ln_q": ((qr,), 0),
        "w_qb": ((qr, h, nope + rope), qr),
        "w_kva": ((e, kr + rope), e), "ln_kv": ((kr,), 0),
        "w_kvb": ((kr, h, nope + v), kr), "w_o": ((h, v, e), h * v),
        "ln_post_attn": ((e,), 0), "ln_pre_mlp": ((e,), 0),
        "ln_post_mlp": ((e,), 0),
    }
    if is_expert_layer(cfg, layer):
        held, fe = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
        fs = cfg["n_shared_experts"] * fe
        out.update({
            "w_router": ((e, cfg["published"]["n_routed_experts"]), e),
            "we_gate": ((held, e, fe), e), "we_up": ((held, e, fe), e),
            "we_down": ((held, fe, e), fe),
            "ws_gate": ((e, fs), e), "ws_up": ((e, fs), e),
            "ws_down": ((fs, e), fs)})
    else:
        f = cfg["intermediate_size"]
        out.update({"w_gate": ((e, f), e), "w_up": ((e, f), e),
                    "w_down": ((f, e), f)})
    return out


@partial(jax.jit, static_argnums=(0, 1))
def _normal(shape: tuple, fan_in: int, key: jax.Array) -> jax.Array:
    w = jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
    return w.astype(jnp.bfloat16).astype(jnp.float32)


def weight(cfg: dict, seed: int, layer: int, name: str) -> jax.Array:
    """One leaf, float32 holding bfloat16's numbers, from the seed."""
    shapes = leaf_shapes(cfg, layer)
    shape, fan_in = shapes[name]
    if not fan_in:
        return jnp.ones(shape, jnp.float32)
    key = jax.random.fold_in(
        jax.random.fold_in(key_for(seed), layer + 1),
        sorted(shapes).index(name))
    return _normal(shape, fan_in, key)


def layer_weights(cfg: dict, seed: int, layer: int) -> dict:
    return {name: weight(cfg, seed, layer, name)
            for name in leaf_shapes(cfg, layer)}


def _product(expr: str, a, b, control: str):
    if control == "fp8":
        a = a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        b = b.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return jnp.einsum(expr, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """``x [S, ..., D]`` at positions 0..S-1, pairs ``(2i, 2i + 1)``."""
    s, d = x.shape[0], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(s, dtype=jnp.float32).reshape(
        (s,) + (1,) * (x.ndim - 1)) * freqs
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * jnp.cos(angles) - x2 * jnp.sin(angles),
                      x1 * jnp.sin(angles) + x2 * jnp.cos(angles)],
                     axis=-1).reshape(x.shape)


def _swiglu(h, w_gate, w_up, w_down, mm):
    return mm("sf,fe->se", jax.nn.silu(mm("se,ef->sf", h, w_gate))
              * mm("se,ef->sf", h, w_up), w_down)


def attention(cfg: dict, h, w, control: str):
    """Latent attention of one sequence ``h [S, E]``, expanded, in blocks
    of queries so that heads x S x S never exist at once."""
    mm = partial(_product, control=control)
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    nope, rank = cfg["qk_nope_head_dim"], cfg["kv_lora_rank"]
    s = h.shape[0]
    q = mm("sr,rhd->shd", _rms(mm("se,er->sr", h, w["w_qa"]), w["ln_q"], eps),
           w["w_qb"])
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], theta)
    kva = mm("se,er->sr", h, w["w_kva"])
    c_kv, k_rope = _rms(kva[:, :rank], w["ln_kv"], eps), _rope(kva[:, rank:],
                                                               theta)
    kv = mm("sr,rhd->shd", c_kv, w["w_kvb"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = 1.0 / math.sqrt(nope + cfg["qk_rope_head_dim"])
    block = min(Q_BLOCK, s)
    if s % block:
        raise ValueError(f"{s} positions do not split into blocks of {block}")
    keys = jnp.arange(s)

    def queries(lo):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, lo, block, 0)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, lo, block, 0)
        scores = (mm("qhd,khd->hqk", qn, k_nope)
                  + mm("qhd,kd->hqk", qr, k_rope)) * scale
        seen = (lo + jnp.arange(block))[:, None] >= keys[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return mm("hqk,khd->qhd", probs, v)

    o = jax.lax.map(queries, jnp.arange(0, s, block))
    return mm("shd,hde->se", o.reshape((s,) + o.shape[2:]), w["w_o"])


def routing(cfg: dict, h, w_router, control: str = ""):
    """(expert ids ``[S, k]``, gates ``[S, k]``, router logits ``[S, N]``)
    over all published experts."""
    logit = _product("se,en->sn", h, w_router, control)
    top, idx = jax.lax.top_k(jax.nn.sigmoid(logit),
                             cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / (top.sum(-1, keepdims=True) + 1e-20)
    if control != "no_scaling":
        top = top * cfg["routed_scaling_factor"]
    return idx, top, logit


def choice_margin(cfg: dict, logit, first: int, count: int):
    """How far each token's choice is from changing what is computed HERE:
    the least, over the experts held, of what a chosen one lies above the
    first expert passed over (below that it leaves) and of what one passed
    over lies below the last chosen (above that it enters). Two experts
    that are both absent may swap: that changes nothing on this chip.
    Under bfloat16 activations a margin of a few hundredths is decided by
    rounding. (Looking only at the last chosen and the first passed over
    misses a held expert third in a close row: on the chip that left 0.2%
    of positions far off the reference with no margin to show for it.)"""
    k = cfg["num_experts_per_tok"]
    top = jax.lax.top_k(logit, k + 1)[0]
    last_in, first_out = top[:, k - 1:k], top[:, k:]
    mine = logit[:, first:first + count]
    return jnp.where(mine >= last_in, mine - first_out,
                     last_in - mine).min(-1)


def expert_layer(cfg: dict, h, w, control: str = "", held=None):
    """(the routed sum over the experts held plus the shared expert, each
    token's :func:`choice_margin`). ``held`` (first, count) overrides the
    file's share (the test that adds the shares up)."""
    mm = partial(_product, control=control)
    first, count = held or (cfg["deployment"]["expert_first"],
                            cfg["n_routed_experts"])
    idx, gate, logit = routing(cfg, h, w["w_router"], control)
    y = _swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"], mm)
    for e in range(1 if control == "drop_expert" else 0, count):
        g = jnp.where(idx == first + e, gate, 0.0).sum(-1)
        y = y + g[:, None] * _swiglu(h, w["we_gate"][e], w["we_up"][e],
                                     w["we_down"][e], mm)
    return y, choice_margin(cfg, logit, first, count)


@partial(jax.jit, static_argnums=(0, 3, 4))
def block(cfg_key: tuple, x, w, expert: bool, control: str):
    """One layer on one sequence ``x [S, E]`` -> (x, choice margins
    ``[S]``, infinite for a dense layer)."""
    cfg = dict(cfg_key)
    cfg["deployment"] = dict(cfg["deployment"])
    eps = cfg["rms_norm_eps"]
    post = cfg["sandwich_norm"] and control != "no_post_norms"
    o = attention(cfg, _rms(x, w["ln_in"], eps), w, control)
    x = x + (_rms(o, w["ln_post_attn"], eps) if post else o)
    h = _rms(x, w["ln_pre_mlp"], eps)
    if expert:
        ff, margin = expert_layer(cfg, h, w, control)
    else:
        ff = _swiglu(h, w["w_gate"], w["w_up"], w["w_down"],
                     partial(_product, control=control))
        margin = jnp.full(x.shape[:1], jnp.inf)
    return x + (_rms(ff, w["ln_post_mlp"], eps) if post else ff), margin


def _hashable(cfg: dict) -> tuple:
    """The keys the mathematics reads, as a static jit argument."""
    keys = ("hidden_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rope_theta", "rms_norm_eps", "intermediate_size",
            "moe_intermediate_size", "n_shared_experts", "n_routed_experts",
            "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
            "sandwich_norm", "vocab_size", "num_hidden_layers")
    dep = tuple(sorted((k, v) for k, v in cfg["deployment"].items()
                       if isinstance(v, int)))
    return tuple((k, cfg[k]) for k in keys) + (("deployment", dep),)


def logits_many(cfg: dict, seed: int, sequences, control: str = ""):
    """For each sequence (1-D id arrays of one length): float32 logits
    ``[S, V]`` and each position's smallest :func:`choice_margin` over the
    expert layers ``[S]``. The weights are made once a layer and used for
    all the sequences."""
    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    key = _hashable(cfg)
    with jax.default_matmul_precision("highest"):
        embed = weight(cfg, seed, TOP, "embed")
        xs = [embed[jnp.asarray(s)] for s in sequences]
        margins = [jnp.full(x.shape[:1], jnp.inf) for x in xs]
        del embed
        for layer in range(cfg["num_hidden_layers"]):
            w = layer_weights(cfg, seed, layer)
            expert = is_expert_layer(cfg, layer)
            for i, x in enumerate(xs):
                xs[i], m = jax.block_until_ready(
                    block(key, x, w, expert, control))
                margins[i] = jnp.minimum(margins[i], m)
            del w
        ln_f, head = (weight(cfg, seed, TOP, "ln_f"),
                      weight(cfg, seed, TOP, "lm_head"))
        return [_product("se,ev->sv", _rms(x, ln_f, cfg["rms_norm_eps"]),
                         head, control) for x in xs], margins


def logits(cfg: dict, seed: int, tokens, control: str = ""):
    """float32 logits ``[S, V]`` of one sequence."""
    return logits_many(cfg, seed, [tokens], control)[0][0]
