"""NVIDIA-Nemotron-3-Super (``model_type`` ``nemotron_h``: Mamba-2 state-space
layers, LatentMoE expert layers and a few attention layers, every layer ONE
sublayer) in plain ``jax.numpy``: the yardstick for `correct` of a
configuration that names this module as its ``reference``.

Written from the published ``config.json`` and what the configuration file
lists under ``assumed``; it imports nothing of the program. float32, every
product at ``Precision.HIGHEST``; no kernel, no cache, no batching: one
sequence at a time, a layer at a time. ``E`` hidden; ``N(.)`` an RMSNorm with
a learned scale and ``layer_norm_epsilon``; every layer is
``x <- x + f(N(x))`` with ``f`` by ``hybrid_override_pattern``:

  M  Mamba-2   ``[z | xBC | dt] = u W_in`` (widths ``H P | H P + 2 G N | H``);
         ``xBC_t <- silu(sum_j w_conv[:, j] xBC_(t-K+1+j) + b_conv)``, an
         EXPLICIT sum over the ``K`` inputs, zeros before the first token;
         ``xBC -> x [H, P], B [G, N], C [G, N]``; head ``h`` uses group
         ``h // (H / G)``; ``dt = softplus(dt + dt_bias)``, ``a = exp(dt A)``,
         ``A = -exp(A_log)``; the recurrence TOKEN BY TOKEN (never a chunked
         scan): ``S_t = a_t S_(t-1) + dt_t x_t (outer) B_t``, ``y_t = S_t C_t +
         D x_t``; ``y <- N_groups(y * silu(z))`` (the norm AFTER the gate, over
         each of the ``G`` groups of ``H P / G`` channels); ``f = y W_out``
  E  LatentMoE  ``s = sigmoid(u W_r)`` over ALL published experts; the
         ``num_experts_per_tok`` largest of ``s + b``; gates ``scaling * s_e /
         sum_chosen s``; ``l = u W_lat_down``; ``r = sum_e gate_e
         relu(l W_up_e)^2 W_dn_e`` over the experts HELD (a loop);
         ``f = r W_lat_up + relu(u W_su)^2 W_sd``
  *  attention  ``H_q`` query heads on ``G_kv`` key/value heads of ``D``, no
         bias, no q/k norm, causal softmax at ``1 / sqrt(D)``, NO rotary
         embedding
  logits = N_f(x) W_head

The configuration file holds the layers HELD (``num_hidden_layers``,
``hybrid_override_pattern``), the experts held (``n_routed_experts`` from
``deployment.expert_first`` on; the router keeps ``published.n_routed_experts``
outputs) and the vocabulary rows held. What the absent experts would add is
left out; the latent projections and the shared expert are whole.

Weights come from the seed ONE LEAF AT A TIME (:func:`weight`), float32
holding bfloat16's numbers, under the program's leaf names: matrices normal
/ sqrt(fan_in), norm scales one, and (``assumed.weights`` of the file)
``a_log = log(uniform[1, 16])``, ``dt_bias`` the inverse softplus of a
log-uniform draw in ``[time_step_min, time_step_max]`` floored at
``time_step_floor``, ``d_skip`` one, ``conv_b`` and the score bias
``b_router`` small and non-zero, the attention layer's ``wq`` and ``wk`` at
TWICE the usual spread (scores of spread 4: with unit spread a softmax over a
thousand keys is nearly flat and no fault in that layer moves a logit) and
its ``wo`` at twice too (the ONE attention layer of eleven then adds twice
what another layer adds: at the usual spread a fault in it moved the logits
by little more than the experts that rounding flips do, README.ssm.md). An
expert's leaves are keyed by the expert's PUBLISHED index, so every share of
a layer holds the same numbers for the same expert.

``control`` swaps in a fault that `correct` must reject (``CONTROLS``); the
three that speak of chunks read ``serving.prefill_len``: ``fp8`` (both
operands of every product rounded to e4m3), ``one_held_expert_left_out``,
``latent_projection_left_out`` (the routed sum never leaves the latent:
``f`` is the shared expert's alone), ``relu_in_place_of_relu2``,
``no_score_bias``, ``state_reset_at_chunk`` and ``conv_window_reset_at_chunk``
(a chunk of ``prefill_len`` tokens starts from an empty state / from a window
of zeros), ``pads_in_state`` (the pad tail of the prompt's final chunk, token
0, passes the window and the state before the answer), ``no_D_skip``,
``no_gate_before_norm`` (``N(y) * silu(z)``), ``rope_on_attention``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

CONTROLS = ("", "fp8", "one_held_expert_left_out",
            "latent_projection_left_out", "relu_in_place_of_relu2",
            "no_score_bias", "state_reset_at_chunk",
            "conv_window_reset_at_chunk", "pads_in_state", "no_D_skip",
            "no_gate_before_norm", "rope_on_attention")
# not a fault: both operands of every product rounded to bfloat16, the
# precision the configuration states; `correct` never decides by it
READINGS = ("bf16",)
Q_BLOCK = 256
ROW_BLOCK = 1024
TOP = -1          # the "layer" of embed, ln_f and lm_head
KINDS = {"M": "mamba2", "E": "latent_experts", "*": "attention"}


def key_for(seed: int) -> jax.Array:
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    lo, hi = int(seed) & 0x7FFFFFFF, int(seed) >> 31
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def kind_of(cfg: dict, layer: int) -> str:
    return KINDS[cfg["hybrid_override_pattern"][layer]]


def ssm_sizes(cfg: dict) -> tuple:
    """``(H, P, G, N, K, inner width H P, convolved channels)``."""
    h, p, g, n = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                  cfg["n_groups"], cfg["ssm_state_size"])
    return h, p, g, n, cfg["conv_kernel"], h * p, h * p + 2 * g * n


def leaf_shapes(cfg: dict, layer: int) -> dict:
    """``{name: (shape, fan_in)}`` of one layer, or of the top (``TOP``);
    fan_in 0 marks a leaf that is no matrix (:func:`weight` says what it
    is)."""
    e = cfg["hidden_size"]
    if layer == TOP:
        vocab = cfg["vocab_size"]
        return {"embed": ((vocab, e), e), "ln_f": ((e,), 0),
                "lm_head": ((e, vocab), e)}
    kind = kind_of(cfg, layer)
    if kind == "mamba2":
        h, _, _, _, k, inner, conv = ssm_sizes(cfg)
        return {"ln1": ((e,), 0), "w_ssm_in": ((e, inner + conv + h), e),
                "conv_w": ((conv, k), k), "conv_b": ((conv,), 0),
                "a_log": ((h,), 0), "d_skip": ((h,), 0),
                "dt_bias": ((h,), 0), "ln_y": ((inner,), 0),
                "w_ssm_out": ((inner, e), inner)}
    if kind == "attention":
        h, g, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
        return {"ln1": ((e,), 0), "wq": ((e, h, d), e / 4),
                "wk": ((e, g, d), e / 4), "wv": ((e, g, d), e),
                "wo": ((h, d, e), h * d / 4)}
    held, lat, f, fs = (cfg["n_routed_experts"], cfg["moe_latent_size"],
                        cfg["moe_intermediate_size"],
                        cfg["moe_shared_expert_intermediate_size"])
    n_all = cfg.get("published", {}).get("n_routed_experts", held)
    return {"ln2": ((e,), 0), "w_router": ((e, n_all), e),
            "b_router": ((n_all,), 0), "w_lat_down": ((e, lat), e),
            "w_lat_up": ((lat, e), lat), "we_up": ((held, lat, f), lat),
            "we_down": ((held, f, lat), f), "ws_up": ((e, fs), e),
            "ws_down": ((fs, e), fs)}


@partial(jax.jit, static_argnums=(0, 1))
def _normal(shape: tuple, fan_in: float, key: jax.Array) -> jax.Array:
    w = jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
    return w.astype(jnp.bfloat16).astype(jnp.float32)


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


EXPERT_STACKS = ("we_up", "we_down")


def weight(cfg: dict, seed: int, layer: int, name: str,
           experts: tuple | None = None) -> jax.Array:
    """One leaf, float32 holding bfloat16's numbers, from the seed.
    ``experts = (lo, hi)`` (an expert stack only): the held experts
    ``lo..hi-1`` alone, for a holder that fills its stack a few at a
    time."""
    shapes = leaf_shapes(cfg, layer)
    shape, fan_in = shapes[name]
    key = jax.random.fold_in(
        jax.random.fold_in(key_for(seed), layer + 1),
        sorted(shapes).index(name))
    if name in EXPERT_STACKS:
        # an expert's numbers follow its PUBLISHED index
        first = cfg.get("deployment", {}).get("expert_first", 0)
        return jnp.stack([
            _normal(shape[1:], fan_in, jax.random.fold_in(key, first + e))
            for e in range(*(experts or (0, shape[0])))])
    if fan_in:
        return _normal(shape, fan_in, key)
    if name.startswith("ln") or name == "d_skip":
        return jnp.ones(shape, jnp.float32)
    if name == "a_log":
        return _bf16(jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                                1.0, 16.0)))
    if name == "dt_bias":
        lo, hi = math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"])
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, lo, hi)), cfg["time_step_floor"])
        return _bf16(jnp.log(jnp.expm1(dt)))
    spread = {"conv_b": 0.1, "b_router": 0.01}[name]
    return _bf16(spread * jax.random.normal(key, shape, jnp.float32))


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
        a, b = _bf16(a), _bf16(b)
    return jnp.einsum(expr, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, positions, theta):
    """``x [S, H, D]`` at ``positions [S]``, pairs ``(2i, 2i + 1)``: what the
    attention layer does NOT take (``rope_on_attention`` applies it)."""
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


# ---------------------------------------------------------------- mamba2


def mamba2_mixer(cfg: dict, u, w, control: str = "", n_prompt=None,
                 pad_u=None):
    """``f(u)`` of an ``M`` layer for one sequence ``u [S, E]`` (normed
    input) from an empty state: the in-projection for all tokens, then
    convolution and recurrence a token at a time, then the gated norm and
    the out-projection. ``n_prompt`` and ``pad_u [E]`` (a pad's normed
    input) serve the faults that speak of chunks."""
    mm = partial(_product, control=control)
    heads, p, g, n, k, inner, conv = ssm_sizes(cfg)
    eps, chunk = cfg["layer_norm_epsilon"], cfg["serving"]["prefill_len"]
    s = u.shape[0]
    n_prompt = s if n_prompt is None else n_prompt
    rate = -jnp.exp(w["a_log"])
    taps = w["conv_w"]

    def project(rows):
        zxd = mm("se,ef->sf", rows, w["w_ssm_in"])
        return (zxd[:, :inner], zxd[:, inner:inner + conv],
                zxd[:, inner + conv:])

    def step(carry, inputs):
        """One token: the window takes its input, the state its update."""
        state, window = carry                      # [H,P,N], [K-1, C]
        xbc, dt_raw = inputs
        full = jnp.concatenate([window, xbc[None]])            # [K, C]
        # the explicit sum over the K inputs
        mixed = w["conv_b"] + sum(taps[:, j] * full[j] for j in range(k))
        mixed = jax.nn.silu(mixed)
        x = mixed[:inner].reshape(heads, p)
        b = jnp.repeat(mixed[inner:inner + g * n].reshape(g, n),
                       heads // g, axis=0)
        c = jnp.repeat(mixed[inner + g * n:].reshape(g, n), heads // g,
                       axis=0)
        dt = jax.nn.softplus(dt_raw + w["dt_bias"])
        state = (jnp.exp(dt * rate)[:, None, None] * state
                 + mm("hp,hn->hpn", dt[:, None] * x, b))
        y = mm("hpn,hn->hp", state, c)
        if control != "no_D_skip":
            y = y + w["d_skip"][:, None] * x
        return (state, full[1:]), y.reshape(inner)

    z, xbc_all, dt_all = project_all(project, u, inner, conv)
    at = jnp.arange(s)
    boundary = (at % chunk == 0) & (at > 0)
    pad_xbc = pad_dt = None
    if control == "pads_in_state":
        _, pad_xbc, pad_dt = project(pad_u[None])
        n_pad = -n_prompt % chunk

    def token(carry, inputs):
        xbc, dt_raw, cut, first_answer = inputs
        state, window = carry
        if control == "state_reset_at_chunk":
            state = jnp.where(cut, 0.0, state)
        if control == "conv_window_reset_at_chunk":
            window = jnp.where(cut, 0.0, window)
        if control == "pads_in_state":
            state, window = jax.lax.cond(
                first_answer,
                lambda sw: jax.lax.fori_loop(
                    0, n_pad, lambda _, c: step(
                        c, (pad_xbc[0], pad_dt[0]))[0], sw),
                lambda sw: sw, (state, window))
        return step((state, window), (xbc, dt_raw))

    empty = (jnp.zeros((heads, p, n), jnp.float32),
             jnp.zeros((k - 1, conv), jnp.float32))
    _, y = jax.lax.scan(token, empty,
                        (xbc_all, dt_all, boundary, at == n_prompt))
    gate = jax.nn.silu(z)
    grouped = (s, g, inner // g)
    scale = w["ln_y"].reshape(g, inner // g)
    if control == "no_gate_before_norm":
        y = (_rms(y.reshape(grouped), scale, eps).reshape(s, inner) * gate)
    else:
        y = _rms((y * gate).reshape(grouped), scale, eps).reshape(s, inner)
    return mm("sf,fe->se", y, w["w_ssm_out"])


def project_all(project, u, inner: int, conv: int):
    """The in-projection of every token, in blocks of rows."""
    zxd = _by_rows(lambda r: jnp.concatenate(project(r), axis=-1), u)
    return zxd[:, :inner], zxd[:, inner:inner + conv], zxd[:, inner + conv:]


# ------------------------------------------------------------- attention


def attention(cfg: dict, u, w, control: str = ""):
    """``f(u)`` of a ``*`` layer for one sequence ``u [S, E]``: causal
    grouped-query attention with no rotary embedding, in blocks of
    queries."""
    mm = partial(_product, control=control)
    h, g, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    s = u.shape[0]
    q = mm("se,ehd->shd", u, w["wq"])
    k = mm("se,ehd->shd", u, w["wk"])
    v = mm("se,ehd->shd", u, w["wv"])
    if control == "rope_on_attention":
        at = jnp.arange(s)
        q = _rope(q, at, float(cfg["rope_theta"]))
        k = _rope(k, at, float(cfg["rope_theta"]))
    block = next(b for b in range(min(Q_BLOCK, s), 0, -1) if s % b == 0)
    keys = jnp.arange(s)

    def queries(lo):
        t = lo + jnp.arange(block)
        qb = jax.lax.dynamic_slice_in_dim(q, lo, block, 0)
        scores = mm("qgrd,kgd->grqk", qb.reshape(block, g, h // g, d),
                    k) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(
            keys[None, :] <= t[:, None], scores, -jnp.inf), axis=-1)
        return mm("grqk,kgd->qgrd", probs, v).reshape(block, h, d)

    o = jax.lax.map(queries, jnp.arange(0, s, block)).reshape(s, h, d)
    return mm("shd,hde->se", o, w["wo"])


# ---------------------------------------------------------- expert layer


def routing(cfg: dict, u, w, control: str = ""):
    """(expert ids ``[S, k]``, gates ``[S, k]``, what the choice ranks
    ``[S, N]``: score + bias) over all published experts."""
    score = jax.nn.sigmoid(_product("se,en->sn", u, w["w_router"], control))
    ranked = score if control == "no_score_bias" else score + w["b_router"]
    idx = jax.lax.top_k(ranked, cfg["num_experts_per_tok"])[1]
    top = jnp.take_along_axis(score, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        top = top / (top.sum(-1, keepdims=True) + 1e-20)
    return idx, top * cfg["routed_scaling_factor"], ranked


def choice_margin(cfg: dict, ranked, first: int, count: int):
    """How far each token's choice is from changing what is computed HERE,
    in units of what the choice ranks (score + bias): the least, over the
    experts held, of what a chosen one lies above the first expert passed
    over and of what one passed over lies below the last chosen. With a
    quarter of 512 experts held it is under bfloat16's resolution at most
    positions (``README.ssm.md``), so `correct` does not lean on it."""
    k = cfg["num_experts_per_tok"]
    top = jax.lax.top_k(ranked, k + 1)[0]
    last_in, first_out = top[:, k - 1:k], top[:, k:]
    mine = ranked[:, first:first + count]
    return jnp.where(mine >= last_in, mine - first_out,
                     last_in - mine).min(-1)


def expert_layer(cfg: dict, u, w, control: str = "", held=None):
    """``f(u)`` of an ``E`` layer on rows ``u [S, E]``: the routed sum over
    the experts held, through the latent, plus the shared expert. ``held``
    (first, count) overrides the file's share (the test that adds the
    shares up)."""
    mm = partial(_product, control=control)
    first, count = held or (cfg.get("deployment", {}).get("expert_first", 0),
                            cfg["n_routed_experts"])

    def act(rows):
        rows = jax.nn.relu(rows)
        return rows if control == "relu_in_place_of_relu2" else rows * rows

    idx, gate, _ = routing(cfg, u, w, control)
    latent = mm("se,el->sl", u, w["w_lat_down"])

    def one(total, e):
        g = jnp.where(idx == first + e, gate, 0.0).sum(-1)
        out = mm("sf,fl->sl", act(mm("sl,lf->sf", latent, w["we_up"][e])),
                 w["we_down"][e])
        return total + g[:, None] * out, None

    skipped = 1 if control == "one_held_expert_left_out" else 0
    routed, _ = jax.lax.scan(one, jnp.zeros_like(latent),
                             jnp.arange(skipped, count))
    shared = mm("sf,fe->se", act(mm("se,ef->sf", u, w["ws_up"])),
                w["ws_down"])
    if control == "latent_projection_left_out":
        return shared
    return mm("sl,le->se", routed, w["w_lat_up"]) + shared


# ----------------------------------------------------------------- layer


@partial(jax.jit, static_argnums=(0, 1, 3))
def block(cfg_key: tuple, kind: str, x, control: str, w, n_prompt):
    """One layer on one sequence ``x [S, E]`` (positions 0..S-1; the first
    ``n_prompt`` are the prompt, the rest what was generated)."""
    cfg = dict(cfg_key)
    cfg["serving"] = {"prefill_len": cfg.pop("prefill_len")}
    cfg["deployment"] = {"expert_first": cfg.pop("expert_first")}
    cfg["published"] = {"n_routed_experts": cfg.pop("router_width")}
    eps = cfg["layer_norm_epsilon"]
    if kind == "mamba2":
        pad_u = _rms(w["pad_x"], w["ln1"], eps)
        return x + mamba2_mixer(cfg, _rms(x, w["ln1"], eps), w, control,
                                n_prompt, pad_u)
    if kind == "attention":
        return x + attention(cfg, _rms(x, w["ln1"], eps), w, control)
    return _by_rows(lambda rows: rows + expert_layer(
        cfg, _rms(rows, w["ln2"], eps), w, control), x)


def _hashable(cfg: dict) -> tuple:
    """The keys the mathematics reads, as a static jit argument."""
    keys = ("hidden_size", "mamba_num_heads", "mamba_head_dim", "n_groups",
            "ssm_state_size", "conv_kernel", "num_attention_heads",
            "num_key_value_heads", "head_dim", "rope_theta",
            "layer_norm_epsilon", "n_routed_experts", "num_experts_per_tok",
            "norm_topk_prob", "routed_scaling_factor", "moe_latent_size",
            "moe_intermediate_size", "moe_shared_expert_intermediate_size")
    return tuple((k, cfg[k]) for k in keys) + (
        ("prefill_len", cfg["serving"]["prefill_len"]),
        ("expert_first", cfg.get("deployment", {}).get("expert_first", 0)),
        ("router_width", cfg.get("published", {}).get(
            "n_routed_experts", cfg["n_routed_experts"])))


def logits_many(cfg: dict, seed: int, sequences, control: str = "",
                positions=None, prompt_lens=None):
    """For each sequence (1-D id arrays of one length): float32 logits at
    ``positions[i]`` (every position when None), ``[len(positions[i]), V]``,
    the head in blocks of rows. ``prompt_lens[i]`` says where the sequence's
    prompt ends (all of it when None); only ``pads_in_state`` reads it. The
    weights are made once a layer and used for all the sequences.

    The pads that ``pads_in_state`` lets in are, in the program, what the
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
            kind = kind_of(cfg, layer)
            for i in range(len(xs)):
                xs[i] = jax.block_until_ready(block(
                    key, kind, xs[i], control, w,
                    jnp.asarray(prompt_lens[i], jnp.int32)))
            del w
        ln_f, head = (weight(cfg, seed, TOP, "ln_f"),
                      weight(cfg, seed, TOP, "lm_head"))
        eps = cfg["layer_norm_epsilon"]
        out = []
        for i, x in enumerate(xs):
            rows = x if positions is None else x[jnp.asarray(positions[i])]
            out.append(_product("se,ev->sv", _rms(rows, ln_f, eps), head,
                                control))
        return out


def logits(cfg: dict, seed: int, tokens, control: str = ""):
    """float32 logits ``[S, V]`` of one sequence."""
    return logits_many(cfg, seed, [tokens], control)[0]
