"""The block kinds that ``variant`` cannot name: latent attention, sandwich
norms, a sigmoid-routed expert layer beside a shared expert, a stack
whose first layers are dense (the DeepSeek-V3 / openPangu-Ultra-MoE
family). The forward pass only: scoring and serving.

ONE definition of the block, :func:`forward_cached`: without a cache it
is the uncached forward (:func:`forward_uncached`, ``forward_with_aux``'s
through ``transformer.family``), with one it is ``decode.forward_cached``
(prefill chunk, decode step, verify block). The two differ in one thing,
which rows the queries attend over: the call's own latent rows, or the
cache's after the call's rows were written into it.

RMSNorm ``N`` (eps ``cfg.norm_eps``); ``h`` is a layer's normed input:

  sandwich   x = x + N_post_attn(Attn(N_in(x)));
             x = x + N_post_mlp(FFN(N_pre_mlp(x)))
  latent     c_q = N_q(h W_qa); per head [q_nope | q_rope] = c_q W_qb,
             q_rope = RoPE(q_rope); [c_kv | k_r] = h W_kva,
             c_kv = N_kv(c_kv), k_rope = RoPE(k_r) (one for all heads);
             [k_nope_h | v_h] = c_kv W_kvb;
             score_h = (q_nope_h . k_nope_h + q_rope_h . k_rope)
                       / sqrt(nope + rope); causal softmax in float32;
             out = concat_h(sum p v_h) W_o
  experts    ops/moe.py: sigmoid_expert_half (the one this kind's layers
             share with models/transformer.py's block), swiglu

**The cache is ``c_kv`` and ``k_rope``**: one stack ``latent [L, B,
max_len, kv_lora_rank + qk_rope_head_dim]`` (576 numbers a token a layer
at the published sizes), carried through both runs' scans
(``transformer.scan_runs``), written in place (``cache.write_rows``). A
call of at most :data:`ABSORB_UPTO` new tokens a row reads it as it
lies: ``W_kvb`` is absorbed into the
query (``q~_h = q_nope_h W_kvb,k,h^T``, ``score_h = [q~_h | q_rope_h] .
[c_kv | k_rope]``) and into the output (``o_h = (sum p c_kv) W_kvb,v,h``):
the same function. A wider call (a prefill chunk) expands keys and
values, a group of heads at a time, over the row only as far as its
last query reaches (``cache.key_reaches``: the first of a few lengths that
holds it, chosen on the device from the call's positions; the keys past
it were masked to exactly 0.0 before). The cache's ``counters`` say how
far such a call read (``keys_read``).

Parameters: ``embed [V, E]``, ``ln_f [E]``, ``lm_head [E, V]``,
``dense_layers`` (the first ``first_k_dense``) and ``layers`` (the
rest), each stacked along a leading layer dim; see :func:`param_shapes`.
The routed experts' stacks ``we_*`` hold ``experts_held`` experts from
``expert_first`` on; the router keeps its full width.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from dlrover_tpu.models import transformer as tfm
from dlrover_tpu.models.cache import reach_of, write_rows
from dlrover_tpu.ops import moe

Params = Any
# heads whose keys and values the expanded path holds at once
HEAD_GROUP = 16
# a cached call of at most this many new tokens a row (a decode step, a
# verify block) reads the latent rows as they lie, W_kvb absorbed into
# the query and the output; a wider one (a prefill chunk) expands keys
# and values. Chosen from the call's width, which the code sees
ABSORB_UPTO = 64
KINDS = ("latent", "sandwich", "sigmoid_experts")


def _only_kinds(cfg) -> None:
    kinds = (cfg.attn_kind, cfg.norm_kind, cfg.ffn_kind)
    if kinds != KINDS:
        # 'pre' norms or a plain 'swiglu' under latent attention: no
        # configuration asks for them yet
        raise NotImplementedError(
            f"attn_kind / norm_kind / ffn_kind {kinds}: models/latent.py "
            f"runs {KINDS} together, and `variant` names the rest")


def labels(cfg) -> list[tuple]:
    """One cache, the latent stack, under every layer; the first
    ``first_k_dense`` layers' weights under ``dense_layers``, the expert
    layers' under ``layers``: each a whole run (``transformer.stack_runs``)."""
    _only_kinds(cfg)
    return [("latent", "dense_layers" if l < cfg.first_k_dense else "layers")
            for l in range(cfg.n_layers)]


def segments(cfg) -> list[tuple[str, bool, int]]:
    """The stack as ``(params key, expert layer?, layers)`` runs."""
    _only_kinds(cfg)
    return [(r.key, r.key == "layers", r.n) for r in tfm.stack_runs(cfg)]


def param_shapes(cfg) -> dict:
    """The parameter tree as shapes (a tuple a leaf)."""
    c = cfg
    e, h = c.d_model, c.n_heads
    attn = {
        "ln_in": (e,), "w_qa": (e, c.q_lora_rank), "ln_q": (c.q_lora_rank,),
        "w_qb": (c.q_lora_rank, h, c.qk_nope_head_dim + c.qk_rope_head_dim),
        "w_kva": (e, c.kv_lora_rank + c.qk_rope_head_dim),
        "ln_kv": (c.kv_lora_rank,),
        "w_kvb": (c.kv_lora_rank, h, c.qk_nope_head_dim + c.v_head_dim),
        "w_o": (h, c.v_head_dim, e), "ln_post_attn": (e,),
        "ln_pre_mlp": (e,), "ln_post_mlp": (e,),
    }
    held, fe = tfm.routed_config(c).n_held, c.moe_d_ff
    fs = c.n_shared_experts * c.moe_d_ff
    ffn = {
        False: {"w_gate": (e, c.d_ff), "w_up": (e, c.d_ff),
                "w_down": (c.d_ff, e)},
        True: {"w_router": (e, c.n_routed_experts),
               "we_gate": (held, e, fe), "we_up": (held, e, fe),
               "we_down": (held, fe, e),
               "ws_gate": (e, fs), "ws_up": (e, fs), "ws_down": (fs, e)},
    }
    tree = {"embed": (c.vocab_size, e), "ln_f": (e,),
            "lm_head": (e, c.vocab_size)}
    for key, experts, n in segments(c):
        tree[key] = {name: (n, *shape)
                     for name, shape in {**attn, **ffn[experts]}.items()}
    return tree


def init_cache(cfg, batch: int, max_len: int) -> dict:
    """The cache tree: the latent stack, the position, and the counters
    every cached call adds to: ``keys_read`` (the length of the row a
    call's expanded attention read, ``cache.key_reaches``; an absorbed
    call adds none) beside the expert layers' (``ops/moe.held_counters``)."""
    c = cfg
    cache = {
        "latent": jnp.zeros(
            (c.n_layers, batch, max_len,
             c.kv_lora_rank + c.qk_rope_head_dim), jnp.dtype(c.dtype)),
        "pos": jnp.zeros((), jnp.int32),
        "counters": {"keys_read": jnp.zeros((), jnp.int32)},
    }
    n_expert = sum(n for _, experts, n in segments(c) if experts)
    if n_expert:
        cache["counters"].update(
            moe.held_counters(n_expert, tfm.routed_config(c).n_held))
    return cache


def _rope(x, positions, theta):
    """Rotary embedding of ``x [B, S, H, D]`` at ``positions [B, S]``,
    pairing components ``(2i, 2i + 1)``; angles in float32. NOT
    ``transformer``'s: this one rotates in float32 and rounds once, that
    one rotates in the input's dtype, so swapping them moves the logits
    (ROADMAP D22)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions[:, :, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _masked_softmax(scores, q_pos, dt):
    """``scores [B, H, S, K]`` float32; query ``s`` of row ``b`` sits at
    ``q_pos[b, s]`` and sees keys at positions up to its own."""
    k_pos = jnp.arange(scores.shape[-1])
    mask = q_pos[:, None, :, None] >= k_pos[None, None, None, :]
    return jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1).astype(dt)


def attend(q_nope, q_rope, rows, w_kvb, q_pos, cfg, absorbed: bool):
    """``q_nope [B, S, H, nope]``, ``q_rope [B, S, H, rope]`` over the
    latent ``rows [B, K, rank + rope]`` -> ``[B, S, H, v]``. The
    expanded path reads ``rows[:, :keys]``, ``keys`` from
    ``cache.reach_of``."""
    c = cfg
    dt = q_nope.dtype
    rank, nope = c.kv_lora_rank, c.qk_nope_head_dim
    scale = 1.0 / math.sqrt(nope + c.qk_rope_head_dim)
    if absorbed:
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_kvb[..., :nope])
        scores = jnp.einsum(
            "bshc,bkc->bhsk", jnp.concatenate([q_lat, q_rope], -1), rows
        ).astype(jnp.float32) * scale
        probs = _masked_softmax(scores, q_pos, dt)
        o_lat = jnp.einsum("bhsk,bkr->bshr", probs, rows[..., :rank])
        return jnp.einsum("bshr,rhv->bshv", o_lat, w_kvb[..., nope:])

    B, S, H, _ = q_nope.shape
    hg = min(H, HEAD_GROUP)
    if H % hg:
        raise ValueError(f"{H} heads do not split into groups of {hg}")

    def split(a, axis):                        # heads -> [groups, ..., hg]
        shape = a.shape[:axis] + (H // hg, hg) + a.shape[axis + 1:]
        return jnp.moveaxis(a.reshape(shape), axis, 0)

    def expanded(keys: int):
        c_kv, k_rope = rows[:, :keys, :rank], rows[:, :keys, rank:]

        def group(_, inputs):
            qn, qr, w = inputs                 # [B,S,hg,*], [rank,hg,*]
            kv = jnp.einsum("bkr,rhn->bkhn", c_kv, w)
            scores = (jnp.einsum("bshn,bkhn->bhsk", qn, kv[..., :nope])
                      + jnp.einsum("bshn,bkn->bhsk", qr, k_rope)
                      ).astype(jnp.float32) * scale
            probs = _masked_softmax(scores, q_pos, dt)
            return None, jnp.einsum("bhsk,bkhv->bshv", probs,
                                    kv[..., nope:])

        _, out = lax.scan(group, None, (split(q_nope, 2), split(q_rope, 2),
                                        split(w_kvb, 1)))
        return jnp.moveaxis(out, 0, 2).reshape(B, S, H, -1)

    reach, which = reach_of(q_pos, S, rows.shape[1])
    if len(reach) == 1:
        return expanded(reach[0])
    return lax.switch(which, [partial(expanded, keys) for keys in reach])


def forward_cached(params: Params, tokens: jax.Array, cache: dict | None,
                   cfg, real=None, return_hidden: bool = False):
    """``tokens [B, S]`` -> ``(float32 logits [B, S, V], cache)``.

    ``cache`` None: the uncached forward, every row from position 0.
    Else the call's tokens start at ``cache['pos']`` (a scalar: rows in
    lockstep; ``[B]``: rows at positions of their own), their latent
    rows are written into the stack and the queries attend over it.
    ``real`` is not read: the tree is rows alone.
    """
    c = cfg
    dt = jnp.dtype(c.dtype)
    rms = partial(tfm.rms_norm, eps=c.norm_eps)
    B, S = tokens.shape
    rcfg = tfm.routed_config(c)
    pos = cache["pos"] if cache is not None else jnp.zeros((), jnp.int32)
    positions = tfm.token_positions(pos, B, S)
    absorbed = cache is not None and S <= ABSORB_UPTO
    rank = c.kv_lora_rank
    counters = None if cache is None else cache["counters"]
    if cache is not None and not absorbed:
        reach, which = reach_of(positions, S, cache["latent"].shape[2])
        counters = {**counters, "keys_read": counters["keys_read"]
                    + jnp.asarray(reach, jnp.int32)[which]}

    def block(x, stack, w, experts, layer, global_layer):
        """One layer on ``x [B, S, E]``; ``stack`` is the cache's latent
        stack or None. Returns (x, stack, loads of this layer or None)."""
        h = rms(x, w["ln_in"])
        with jax.named_scope("mla_q"):
            c_q = rms(jnp.einsum("bse,er->bsr", h, w["w_qa"].astype(dt)),
                      w["ln_q"])
            q = jnp.einsum("bsr,rhd->bshd", c_q, w["w_qb"].astype(dt))
            q_nope = q[..., :c.qk_nope_head_dim]
            q_rope = _rope(q[..., c.qk_nope_head_dim:], positions,
                           c.rope_theta)
        with jax.named_scope("latent_write"):
            kva = jnp.einsum("bse,er->bsr", h, w["w_kva"].astype(dt))
            rows = jnp.concatenate([
                rms(kva[..., :rank], w["ln_kv"]),
                _rope(kva[..., None, rank:], positions,
                      c.rope_theta)[:, :, 0]], -1)
            if stack is not None:
                stack = write_rows(stack, rows, global_layer, pos)
                rows = lax.dynamic_index_in_dim(stack, global_layer,
                                                keepdims=False)
        with jax.named_scope("mla_attend"):
            o = attend(q_nope, q_rope, rows, w["w_kvb"].astype(dt),
                       positions, c, absorbed)
            o = jnp.einsum("bshv,hve->bse", o, w["w_o"].astype(dt))
        x = x + rms(o, w["ln_post_attn"])

        h = rms(x, w["ln_pre_mlp"])
        loads = None
        if experts is None:
            with jax.named_scope("mlp"):
                ff = moe.swiglu(h, w["w_gate"].astype(dt),
                                w["w_up"].astype(dt), w["w_down"].astype(dt))
        else:
            ff, loads = moe.sigmoid_expert_half(h, w, experts, layer, rcfg,
                                                dt)
        x = x + rms(ff, w["ln_post_mlp"])
        return x, stack, loads

    def layer_of(run):
        # the routed experts' stacks are closed over and indexed in
        # place by the tile loop; everything else is read a layer at a
        # time. A run is a whole subtree: its first layer has index 0
        # there and `first_of_kind` in the latent stack
        experts, weights = tfm.split_experts(params[run.key], c)
        return weights, lambda x, stack, w, i: block(
            x, stack, w, experts, i, run.first_of_kind + i)

    x, held, loads = tfm.scan_runs(
        tfm.stack_runs(c), tfm.embed_tokens(params, tokens, c),
        {"latent": None if cache is None else cache["latent"]}, layer_of)
    for mine in loads:
        if mine is not None and counters is not None:
            counters = {**counters, **moe.count_loads(counters, mine)}
    with jax.named_scope("lm_head"):
        x = tfm.final_norm(params, x, c)
        out = x if return_hidden else tfm.lm_logits(params, x, c)
    if cache is None:
        return out, None
    return out, {"latent": held["latent"], "pos": pos + S,
                 "counters": counters}


def forward_uncached(params: Params, tokens: jax.Array, cfg,
                     return_hidden: bool = False):
    """``forward_with_aux``'s answer for these kinds (no balancing loss:
    the router is served, not trained)."""
    return forward_cached(params, tokens, None, cfg,
                          return_hidden=return_hidden)[0]
