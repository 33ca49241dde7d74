"""MiniCPM-SALA (block-sparse InfLLM-v2 attention beside lightning linear
attention) in plain ``jax.numpy``: the yardstick for `correct` of a
configuration that names this module as its ``reference``.

Written from the published ``config.json`` (``model_type`` ``minicpm_sala``)
and the sizes the configuration file lists under ``assumed``; it imports
nothing of the program. float32, every product at ``Precision.HIGHEST``; no
kernel, no cache, no batching: one sequence at a time, a layer at a time.
``E`` hidden, ``H`` heads of ``D``; ``N(.)`` an RMSNorm with a learned scale;
``r = scale_depth / sqrt(published num_hidden_layers)``:

  stack      x = scale_emb * embed[token]
             x = x + r * Mixer(N_in(x));  x = x + r * W_down(silu(W_gate h) * W_up h),
             h = N_ffn(x);  logits = (N_f(x) / (E / dim_model_base)) W_head
  lightning-attn   q, k, v = h W_q, h W_k, h W_v [H, D]; q = N_q(q), k = N_k(k)
             over D; rotary embedding on q and k (pairs (2i, 2i + 1));
             lam_h = exp(-2^(-8 (h + 1) / H)); per head, TOKEN BY TOKEN:
             S_t = lam_h S_(t-1) + k_t^T v_t,  o_t = q_t S_t / sqrt(D);
             o = N_o(concat_h o_t) * sigmoid(h W_g);  out = o W_o
  minicpm4   q [H, D], k, v [G, D]; q = N_q(q), k = N_k(k); NO rotary
             embedding. K1_j = mean(k[stride j : stride j + kernel]) for
             every j all of whose positions exist. The query at position t
             (it sees t + 1 keys): t + 1 <= dense_len: plain causal
             attention. Else p_h = softmax_j(q_h . K1_j / sqrt(D)) over the
             j with stride j + kernel <= t + 1; P_g = sum of p_h over the
             group's heads; B_g,b = max of P_g,j over the windows that touch
             block b (j = m b - 1 .. m b + m - 1, m = block / stride); block
             0..init_blocks-1 and the window / block blocks ending with the
             query's own are forced; the topk highest B_g,b (ties to the
             lower index) are the selected blocks;
             o_t,h = sum over s <= t in them of softmax_s(q_h . k_s / sqrt(D)) v_s;
             o = o * sigmoid(h W_g');  out = o W_o

The configuration file holds the layers HELD (``num_hidden_layers``,
``mixer_types``: a cut in depth), the published counts under ``published``.

Weights come from the seed ONE LEAF AT A TIME (:func:`weight`), float32
holding bfloat16's numbers, under the program's leaf names: matrices normal
/ sqrt(fan_in), norm scales one, EXCEPT ``ln_q`` and ``ln_k``, drawn
uniformly from [1.5, 2) (``assumed.weights`` of the file): with unit scales
random weights give attention scores of unit spread over thousands of keys,
a nearly flat softmax whose output is a hundredth of the stream, and no
fault in the selection would move a logit.

``control`` swaps in a fault that `correct` must reject (``CONTROLS``):
``fp8`` (both operands of every product rounded to e4m3),
``dense_in_place_of_sparse``, ``forced_blocks_only`` (the scored blocks left
out), ``rope_on_sparse``, ``state_reset_at_chunk`` (a prefill chunk starts
from an empty state), ``pads_in_state`` (the pad tail of the prompt's final
chunk, token 0, is folded into the state before the answer), ``no_decay``,
``no_output_gate``, ``no_residual_scale``. The two that speak of chunks read
``serving.prefill_len`` and each sequence's prompt length.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

CONTROLS = ("", "fp8", "dense_in_place_of_sparse", "forced_blocks_only",
            "rope_on_sparse", "state_reset_at_chunk", "pads_in_state",
            "no_decay", "no_output_gate", "no_residual_scale")
# not a fault: both operands of every product rounded to bfloat16, the
# precision the configuration states. What a builder's run reads beside the
# sound numbers to see how much of them rounding alone explains; `correct`
# never decides by it
READINGS = ("bf16",)
Q_BLOCK = 128
ROW_BLOCK = 1024
TOP = -1          # the "layer" of embed, ln_f and lm_head
KINDS = {"minicpm4": "sparse", "lightning-attn": "lightning"}


def key_for(seed: int) -> jax.Array:
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    lo, hi = int(seed) & 0x7FFFFFFF, int(seed) >> 31
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def kind_of(cfg: dict, layer: int) -> str:
    return KINDS[cfg["mixer_types"][layer]]


def sparse_sizes(cfg: dict) -> dict:
    return cfg["assumed"]["sparse_config"]


def leaf_shapes(cfg: dict, layer: int) -> dict:
    """``{name: (shape, fan_in)}`` of one layer, or of the top (``TOP``);
    fan_in 0 marks a norm's scale."""
    e, f = cfg["hidden_size"], cfg["intermediate_size"]
    if layer == TOP:
        vocab = cfg["vocab_size"]
        return {"embed": ((vocab, e), e), "ln_f": ((e,), 0),
                "lm_head": ((e, vocab), e)}
    if kind_of(cfg, layer) == "sparse":
        h, g, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
        extra = {}
    else:
        h, g, d = (cfg["lightning_nh"], cfg["lightning_nkv"],
                   cfg["lightning_head_dim"])
        extra = {"ln_o": ((h * d,), 0)}
    return {"ln1": ((e,), 0), "ln2": ((e,), 0), "ln_q": ((d,), 0),
            "ln_k": ((d,), 0), "wq": ((e, h, d), e), "wk": ((e, g, d), e),
            "wv": ((e, g, d), e), "wo": ((h, d, e), h * d),
            "w_og": ((e, h, d), e), "w_gate": ((e, f), e),
            "w_up": ((e, f), e), "w_down": ((f, e), f), **extra}


@partial(jax.jit, static_argnums=(0, 1))
def _normal(shape: tuple, fan_in: int, key: jax.Array) -> jax.Array:
    w = jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
    return w.astype(jnp.bfloat16).astype(jnp.float32)


def weight(cfg: dict, seed: int, layer: int, name: str) -> jax.Array:
    """One leaf, float32 holding bfloat16's numbers, from the seed."""
    shapes = leaf_shapes(cfg, layer)
    shape, fan_in = shapes[name]
    key = jax.random.fold_in(
        jax.random.fold_in(key_for(seed), layer + 1),
        sorted(shapes).index(name))
    if name in ("ln_q", "ln_k"):
        scale = jax.random.uniform(key, shape, jnp.float32, 1.5, 2.0)
        return scale.astype(jnp.bfloat16).astype(jnp.float32)
    if not fan_in:
        return jnp.ones(shape, jnp.float32)
    return _normal(shape, fan_in, key)


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
    """``x [S, H, D]`` at ``positions [S]``, pairs ``(2i, 2i + 1)``."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * jnp.cos(angles) - x2 * jnp.sin(angles),
                      x1 * jnp.sin(angles) + x2 * jnp.cos(angles)],
                     axis=-1).reshape(x.shape)


def _by_rows(fn, x):
    """``fn`` over blocks of rows of ``x [S, ...]`` (S a multiple of the
    block, or under it): wide intermediates never exist for all rows."""
    s = x.shape[0]
    block = min(ROW_BLOCK, s)
    if s % block:
        return fn(x)
    out = jax.lax.map(fn, x.reshape((s // block, block) + x.shape[1:]))
    return out.reshape((s,) + out.shape[2:])


# ---------------------------------------------------------------- sparse


def compressed_keys(k, sizes: dict):
    """``K1 [Nc, G, D]`` of ``k [S, G, D]`` with ``Nc = S // stride``, and
    which of them exist at all (every position of the window inside S)."""
    st, kern = sizes["kernel_stride"], sizes["kernel_size"]
    s = k.shape[0]
    j = np.arange(s // st)
    at = st * j[:, None] + np.arange(kern)[None]
    k1 = k[np.minimum(at, s - 1)].mean(axis=1)
    return k1, jnp.asarray(st * j + kern <= s)


def selected_blocks(q, k1, t, sizes: dict, control: str = ""):
    """The blocks the queries ``q [Q, H, D]`` at positions ``t [Q]`` select,
    ``[G, Q, topk]`` block ids in rank order (forced blocks first, by
    index); with ``forced_blocks_only`` the rest are -1."""
    st, kern, blk = (sizes["kernel_stride"], sizes["kernel_size"],
                     sizes["block_size"])
    topk = sizes["topk"]
    n_comp, g, d = k1.shape
    qn, h = q.shape[:2]
    n_blocks = n_comp * st // blk
    m = blk // st
    scores = _product("qgrd,jgd->grqj", q.reshape(qn, g, h // g, d), k1,
                      control) / math.sqrt(d)
    seen = st * jnp.arange(n_comp)[None, :] + kern <= t[:, None] + 1
    probs = jnp.where(
        seen, jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1), 0.0)
    p_group = probs.sum(axis=1)                                  # [G, Q, Nc]
    touching = m * np.arange(n_blocks)[:, None] + np.arange(-1, m)[None]
    real = (touching >= 0) & (touching < n_comp)
    per_window = jnp.where(
        real, p_group[..., np.clip(touching, 0, n_comp - 1)], 0.0)
    score = per_window.max(axis=-1)                              # [G, Q, Nb]
    b = jnp.arange(n_blocks)[None, :]
    own = (t // blk)[:, None]
    forced = (b < sizes["init_blocks"]) | (
        (b <= own) & (b > own - sizes["window_size"] // blk))
    score = jnp.where(b > own, -jnp.inf, jnp.where(forced, jnp.inf, score))
    order = jnp.argsort(-score, axis=-1, stable=True)[..., :topk]
    if control == "forced_blocks_only":
        order = jnp.where(jnp.take_along_axis(
            jnp.broadcast_to(forced, score.shape), order, -1), order, -1)
    return order


def sparse_attention(cfg: dict, q, k, v, control: str):
    """``q [S, H, D]``, ``k, v [S, G, D]`` at positions 0..S-1 -> ``[S, H,
    D]``, in blocks of queries."""
    sizes = sparse_sizes(cfg)
    blk, dense_len = sizes["block_size"], sizes["dense_len"]
    s, h, d = q.shape
    g = k.shape[1]
    n_blocks = s // blk
    mm = partial(_product, control=control)
    k1, _ = compressed_keys(k, sizes)
    if s % blk:
        raise ValueError(f"{s} positions do not split into blocks of {blk}")
    block = next(b for b in range(min(Q_BLOCK, s), 0, -1) if s % b == 0)
    keys = jnp.arange(s)

    def queries(lo):
        t = lo + jnp.arange(block)
        qb = jax.lax.dynamic_slice_in_dim(q, lo, block, 0)
        causal = keys[None, :] <= t[:, None]                        # [Q, S]
        if control == "dense_in_place_of_sparse":
            see = jnp.broadcast_to(causal, (g, block, s))
        else:
            chosen = selected_blocks(qb, k1, t, sizes, control)  # [G,Q,topk]
            open_blocks = (chosen[..., None]
                           == jnp.arange(n_blocks)).any(axis=-2)  # [G,Q,Nb]
            see = jnp.where((t + 1 <= dense_len)[None, :, None], causal,
                            causal & jnp.repeat(open_blocks, blk, axis=-1))
        scores = mm("qgrd,kgd->grqk", qb.reshape(block, g, h // g, d),
                    k) / math.sqrt(d)
        probs = jax.nn.softmax(
            jnp.where(see[:, None], scores, -jnp.inf), axis=-1)
        return mm("grqk,kgd->qgrd", probs, v).reshape(block, h, d)

    o = jax.lax.map(queries, jnp.arange(0, s, block))
    return o.reshape(s, h, d)


# ------------------------------------------------------------- lightning


def decays(heads: int, control: str = ""):
    """``lam_h = exp(-2^(-8 (h + 1) / H))``, h = 0..H-1."""
    if control == "no_decay":
        return jnp.ones((heads,), jnp.float32)
    return jnp.exp(-(2.0 ** (-8.0 * (jnp.arange(heads) + 1.0) / heads)))


def lightning_recurrence(q, k, v, state, control: str, reset=None,
                         folded=None):
    """The recurrence, token by token: ``q, k, v [S, H, D]`` from ``state
    [H, D, D]`` -> (``o [S, H, D]``, the state after the last token).
    Two faults: ``reset [S]``, the state is emptied BEFORE those tokens;
    ``folded = (where [S], shrink [H], added [H, D, D])``, before those
    tokens the state becomes ``shrink * state + added``."""
    s, h, d = q.shape
    lam = decays(h, control)
    if reset is None:
        reset = jnp.zeros((s,), bool)
    where, shrink, added = folded or (
        jnp.zeros((s,), bool), jnp.ones((h,)), jnp.zeros_like(state))

    def step(state, inputs):
        qt, kt, vt, fresh, fold = inputs
        state = jnp.where(fresh, 0.0, state)
        state = jnp.where(fold, shrink[:, None, None] * state + added, state)
        state = lam[:, None, None] * state + _product(
            "hd,he->hde", kt, vt, control)
        return state, _product("hd,hde->he", qt, state, control) / math.sqrt(d)

    state, o = jax.lax.scan(step, state, (q, k, v, reset, where))
    return o, state


# ----------------------------------------------------------------- layer


@partial(jax.jit, static_argnums=(0, 1, 3))
def block(cfg_key: tuple, kind: str, x, control: str, w, n_prompt):
    """One layer on one sequence ``x [S, E]`` (positions 0..S-1; the first
    ``n_prompt`` are the prompt, the rest what was generated)."""
    cfg = {k: (dict(v) if isinstance(v, tuple) else v) for k, v in cfg_key}
    cfg["assumed"] = {"sparse_config": cfg.pop("sparse_config")}
    mm = partial(_product, control=control)
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    r = 1.0 if control == "no_residual_scale" else (
        cfg["scale_depth"] / math.sqrt(cfg["published_layers"]))
    s = x.shape[0]
    positions = jnp.arange(s)

    def project(h):
        q = _rms(mm("se,ehd->shd", h, w["wq"]), w["ln_q"], eps)
        k = _rms(mm("se,ehd->shd", h, w["wk"]), w["ln_k"], eps)
        return q, k, mm("se,ehd->shd", h, w["wv"])

    h = _rms(x, w["ln1"], eps)
    q, k, v = project(h)
    if kind == "sparse":
        if control == "rope_on_sparse":
            q, k = _rope(q, positions, theta), _rope(k, positions, theta)
        o = sparse_attention(cfg, q, k, v, control)
    else:
        q, k = _rope(q, positions, theta), _rope(k, positions, theta)
        chunk = cfg["prefill_len"]
        reset = folded = None
        if control == "state_reset_at_chunk":
            reset = (positions % chunk == 0) & (positions < n_prompt)
        if control == "pads_in_state":
            # the pad tail (token 0) of the prompt's final chunk, as hidden
            # states of THIS layer, folded in before the first token that
            # follows the prompt (in closed form: it is a fault's stand-in)
            n_pad = -n_prompt % chunk
            j = jnp.arange(chunk)
            hp = _rms(jnp.broadcast_to(w["pad_x"], (chunk, x.shape[1])),
                      w["ln1"], eps)
            _, kp, vp = project(hp)
            kp = _rope(kp, n_prompt + j, theta)
            lam = decays(kp.shape[1], control)
            left = jnp.where(
                (j < n_pad)[:, None],
                lam[None, :] ** jnp.maximum(n_pad - 1 - j, 0)[:, None], 0.0)
            folded = ((positions == n_prompt) & (n_pad > 0), lam ** n_pad,
                      mm("jhd,jhe->hde", kp * left[..., None], vp))
        empty = jnp.zeros((q.shape[1], q.shape[2], q.shape[2]), jnp.float32)
        o, _ = lightning_recurrence(q, k, v, empty, control, reset, folded)
        o = _rms(o.reshape(s, -1), w["ln_o"], eps).reshape(o.shape)
    if control != "no_output_gate":
        o = o * jax.nn.sigmoid(mm("se,ehd->shd", h, w["w_og"]))
    x = x + r * mm("shd,hde->se", o, w["wo"])

    def ffn(rows):
        hh = _rms(rows, w["ln2"], eps)
        return rows + r * mm(
            "sf,fe->se", jax.nn.silu(mm("se,ef->sf", hh, w["w_gate"]))
            * mm("se,ef->sf", hh, w["w_up"]), w["w_down"])

    return _by_rows(ffn, x)


def _hashable(cfg: dict) -> tuple:
    """The keys the mathematics reads, as a static jit argument."""
    keys = ("hidden_size", "intermediate_size", "rope_theta", "rms_norm_eps",
            "scale_depth")
    return tuple((k, cfg[k]) for k in keys) + (
        ("published_layers", cfg.get("published", {}).get(
            "num_hidden_layers", cfg["num_hidden_layers"])),
        ("prefill_len", cfg["serving"]["prefill_len"]),
        ("sparse_config", tuple(sorted(sparse_sizes(cfg).items()))))


def logits_many(cfg: dict, seed: int, sequences, control: str = "",
                positions=None, prompt_lens=None):
    """For each sequence (1-D id arrays of one length, a multiple of the
    sparse block): float32 logits at ``positions[i]`` (every position when
    None), ``[len(positions[i]), V]``. ``prompt_lens[i]`` says where the
    sequence's prompt ends (all of it when None); only the two controls
    that speak of chunks read it. The weights are made once a layer and
    used for all the sequences.

    The pad tail that ``pads_in_state`` folds in is, in the program, what
    the final chunk computed for token 0 at those positions: the pads'
    hidden states entering a layer are taken as the embedding of token 0
    (what lower layers add to a pad is left out), which is fault enough.
    """
    if control not in CONTROLS + READINGS:
        raise ValueError(f"unknown control {control!r}")
    key = _hashable(cfg)
    n = len(sequences)
    prompt_lens = (list(prompt_lens) if prompt_lens is not None
                   else [len(s) for s in sequences])
    with jax.default_matmul_precision("highest"):
        embed = weight(cfg, seed, TOP, "embed")
        xs = [cfg["scale_emb"] * embed[jnp.asarray(s)] for s in sequences]
        pad_x = cfg["scale_emb"] * embed[0]
        del embed
        for layer in range(cfg["num_hidden_layers"]):
            w = layer_weights(cfg, seed, layer)
            w["pad_x"] = pad_x
            kind = kind_of(cfg, layer)
            for i in range(n):
                xs[i] = jax.block_until_ready(block(
                    key, kind, xs[i], control, w,
                    jnp.asarray(prompt_lens[i], jnp.int32)))
            del w
        ln_f, head = (weight(cfg, seed, TOP, "ln_f"),
                      weight(cfg, seed, TOP, "lm_head"))
        shrink = cfg["hidden_size"] / cfg["dim_model_base"]
        out = []
        for i, x in enumerate(xs):
            rows = x if positions is None else x[jnp.asarray(positions[i])]
            out.append(_product(
                "se,ev->sv", _rms(rows, ln_f, cfg["rms_norm_eps"]) / shrink,
                head, control))
        return out


def logits(cfg: dict, seed: int, tokens, control: str = ""):
    """float32 logits ``[S, V]`` of one sequence."""
    return logits_many(cfg, seed, [tokens], control)[0]
