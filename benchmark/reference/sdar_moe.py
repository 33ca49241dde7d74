"""SDAR-MoE (block diffusion over a Qwen3-MoE block) in plain ``jax.numpy``:
the yardstick for `correct` of a configuration that names this module as its
``reference``.

Written from the published ``config.json`` (``model_type`` ``sdar_moe``) and
the family's released block-diffusion generation; it imports nothing of the
program. float32, every product at ``Precision.HIGHEST``; no kernel, no
cache, no batching of requests: one sequence at a time, a loop over all the
experts. ``x [S, E]``; ``rms(u; g) = u / sqrt(mean(u^2) + rms_norm_eps) * g``:

  h  = rms(x; ln1)
  q  = rms_hd(h Wq -> [S, H, hd]; ln_q)     ln_q [hd] shared by the heads
  k  = rms_hd(h Wk -> [S, G, hd]; ln_k);  v = h Wv -> [S, G, hd]
  q, k = rope(q, k; rope_theta, absolute positions), component i paired
         with i + hd / 2 (the published layout)
  a[i, j] = q_i . k_j / sqrt(hd), allowed iff j < (floor(i / B) + 1) * B
            (H / G query heads share a key/value head)
  x  = x + softmax(a) v Wo
  h2 = rms(x; ln2);  p = softmax(h2 W_router);  E = the num_experts_per_tok
       largest of p;  w_e = p_e / sum_E p  (norm_topk_prob)
  x  = x + sum_{e in E} w_e Down_e(silu(Gate_e h2) * Up_e h2)
  logits = rms(x_last; ln_f) lm_head        (untied; NOT shifted: the logits
                                             at a position predict THAT token)

**Generation** (``assumed`` in the configuration file: not in
``config.json``). Positions are grouped in blocks of ``B = block_length`` by
absolute position. The prompt's whole blocks are context; the block being
generated holds the prompt's last ``P mod B`` tokens and ``mask_token_id`` in
the rest. For ``T = denoising_steps`` passes: the block's ``B`` positions
attend to all earlier blocks in their FINAL state and to each other; at
every still-masked position the candidate is the argmax and its confidence
the softmax probability of the candidate; the ``B / T`` still-masked
positions of highest confidence are unmasked (ties to the lower position;
fewer if fewer are left). :func:`generate` does exactly this, recomputing
everything each pass. :func:`denoise_logits_many` REPLAYS a served trajectory
(which position was unmasked in which pass; never the program's choice of
token or expert) and gives, for every pass of every block, the logits and
confidences the mathematics has there. A last block that the budget cut
(its tail was never served) cannot be replayed and is left out.

Weights come from the seed ONE LEAF AT A TIME (:func:`weight`), float32
holding bfloat16's numbers, under the program's leaf names.

``control`` swaps in a fault that `correct` must reject: ``causal_in_block``
(the plain causal mask), ``stale_rows`` (later blocks see a generated
block's rows as its LAST DENOISING pass left them, not the final tokens':
what a program without the storing pass would cache), ``no_qk_norm``,
``expert_left_out`` (expert 0 adds nothing), ``fp8`` (both operands of every
product rounded to e4m3), ``least_confident`` (unmasks the position of
LOWEST confidence).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

CONTROLS = ("", "causal_in_block", "stale_rows", "no_qk_norm",
            "expert_left_out", "fp8", "least_confident")
Q_BLOCK = 256
TOP = -1          # the "layer" of embed, ln_f and lm_head


def key_for(seed: int) -> jax.Array:
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    lo, hi = int(seed) & 0x7FFFFFFF, int(seed) >> 31
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def gen(cfg: dict) -> tuple[int, int, int]:
    """(block length, denoising passes, mask token id)."""
    a = cfg["assumed"]
    return a["block_length"], a["denoising_steps"], a["mask_token_id"]


def leaf_shapes(cfg: dict, layer: int) -> dict:
    """``{name: (shape, fan_in)}`` of one layer, or of the top (``TOP``);
    fan_in 0 marks a norm's scale (ones)."""
    e, h, g = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["num_key_value_heads"])
    hd, n, f = cfg["head_dim"], cfg["num_experts"], cfg["moe_intermediate_size"]
    if layer == TOP:
        vocab = cfg["vocab_size"]
        return {"embed": ((vocab, e), e), "ln_f": ((e,), 0),
                "lm_head": ((e, vocab), e)}
    return {
        "ln1": ((e,), 0), "wq": ((e, h, hd), e), "wk": ((e, g, hd), e),
        "wv": ((e, g, hd), e), "ln_q": ((hd,), 0), "ln_k": ((hd,), 0),
        "wo": ((h, hd, e), h * hd), "ln2": ((e,), 0),
        "w_router": ((e, n), e), "we_gate": ((n, e, f), e),
        "we_up": ((n, e, f), e), "we_down": ((n, f, e), f),
    }


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


def _rope(x, positions, theta):
    """``x [..., S, heads, D]`` at ``positions [..., S]``: component i is
    rotated with component i + D / 2."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[..., None, None] * freqs
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(angles) - x2 * jnp.sin(angles),
                            x1 * jnp.sin(angles) + x2 * jnp.cos(angles)], -1)


def _qkv(cfg: dict, x, w, positions, control: str):
    """Normed, rotated queries and keys and the values of rows ``x [..., S,
    E]`` at ``positions [..., S]``."""
    mm = partial(_product, control=control)
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    h = _rms(x, w["ln1"], eps)
    q = mm("...se,ehd->...shd", h, w["wq"])
    k = mm("...se,ehd->...shd", h, w["wk"])
    v = mm("...se,ehd->...shd", h, w["wv"])
    if control != "no_qk_norm":
        q, k = _rms(q, w["ln_q"], eps), _rms(k, w["ln_k"], eps)
    return _rope(q, positions, theta), _rope(k, positions, theta), v


def _heads(cfg: dict, q):
    """``[..., H, D] -> [..., G, H / G, D]``: the query heads of a key head."""
    g = cfg["num_key_value_heads"]
    return q.reshape(q.shape[:-2] + (g, q.shape[-2] // g, q.shape[-1]))


def _final_attention(cfg: dict, q, k, v, control: str):
    """Block-causal attention of one whole sequence (queries ``[S, H, D]``
    over its own keys), in blocks of queries so that heads x S x S never
    exist at once."""
    mm = partial(_product, control=control)
    b = gen(cfg)[0]
    s, scale = q.shape[0], 1.0 / math.sqrt(q.shape[-1])
    step = min(Q_BLOCK, s)
    if s % step:
        raise ValueError(f"{s} positions do not split into blocks of {step}")
    keys = jnp.arange(s)
    qg = _heads(cfg, q)

    def queries(lo):
        ql = jax.lax.dynamic_slice_in_dim(qg, lo, step, 0)
        scores = mm("qgrd,kgd->grqk", ql, k) * scale
        at = lo + jnp.arange(step)
        seen = (at[:, None] >= keys[None] if control == "causal_in_block"
                else keys[None] < ((at // b + 1) * b)[:, None])
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return mm("grqk,kgd->qgrd", probs, v)

    o = jax.lax.map(queries, jnp.arange(0, s, step))
    return o.reshape((s,) + q.shape[1:])


def _pass_attention(cfg: dict, q, k, v, k_ctx, v_ctx, starts, control: str):
    """The blocks in their pass states: queries ``q [N, B, H, D]`` of ``N``
    (block, pass) pairs over the context rows ``k_ctx [S, G, D]`` before
    their block (``starts [N]``) and over their own block's ``k [N, B, G,
    D]``."""
    mm = partial(_product, control=control)
    scale = 1.0 / math.sqrt(q.shape[-1])
    qg = _heads(cfg, q)
    ctx = mm("nbgrd,kgd->ngrbk", qg, k_ctx) * scale
    before = jnp.arange(k_ctx.shape[0])[None] < starts[:, None]   # [N, S]
    ctx = jnp.where(before[:, None, None, None], ctx, -jnp.inf)
    own = mm("nbgrd,ncgd->ngrbc", qg, k) * scale
    if control == "causal_in_block":
        n_b = q.shape[1]
        own = jnp.where(jnp.arange(n_b)[:, None] >= jnp.arange(n_b)[None],
                        own, -jnp.inf)
    probs = jax.nn.softmax(jnp.concatenate([ctx, own], -1), axis=-1)
    n_ctx = k_ctx.shape[0]
    o = (mm("ngrbk,kgd->nbgrd", probs[..., :n_ctx], v_ctx)
         + mm("ngrbc,ncgd->nbgrd", probs[..., n_ctx:], v))
    return o.reshape(q.shape)


def expert_layer(cfg: dict, h, w, control: str = ""):
    """``h [R, E]`` -> (the routed sum over ALL experts, each row's
    ``choice_margin``: what its last chosen expert's router logit lies above
    the first one passed over; under bfloat16 activations a margin of a few
    hundredths is decided by rounding, and the other choice is another
    function of that row)."""
    mm = partial(_product, control=control)
    k = cfg["num_experts_per_tok"]
    logit = mm("re,en->rn", h, w["w_router"])
    top, idx = jax.lax.top_k(jax.nn.softmax(logit, axis=-1), k)
    if cfg["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    ranked = jax.lax.top_k(logit, k + 1)[0]
    first = 1 if control == "expert_left_out" else 0

    def one(y, inputs):
        e, gate_w, up_w, down_w = inputs
        g = jnp.where(idx == e, top, 0.0).sum(-1)
        out = mm("rf,fe->re", jax.nn.silu(mm("re,ef->rf", h, gate_w))
                 * mm("re,ef->rf", h, up_w), down_w)
        return y + g[:, None] * out, None

    n = cfg["num_experts"]
    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        jnp.arange(first, n), w["we_gate"][first:], w["we_up"][first:],
        w["we_down"][first:]))
    return y, ranked[:, k - 1] - ranked[:, k]


def _hashable(cfg: dict) -> tuple:
    """The keys the mathematics reads, as a static jit argument."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rope_theta", "rms_norm_eps", "moe_intermediate_size",
            "num_experts", "num_experts_per_tok", "norm_topk_prob",
            "vocab_size")
    return tuple((k, cfg[k]) for k in keys) + (("assumed", tuple(
        (k, cfg["assumed"][k]) for k in (
            "block_length", "denoising_steps", "mask_token_id"))),)


def _unhash(cfg_key: tuple) -> dict:
    cfg = dict(cfg_key)
    cfg["assumed"] = dict(cfg["assumed"])
    return cfg


@partial(jax.jit, static_argnums=(0, 3))
def final_block(cfg_key: tuple, x, w, control: str):
    """One layer on one whole sequence ``x [S, E]`` -> (x, margins [S])."""
    cfg = _unhash(cfg_key)
    mm = partial(_product, control=control)
    q, k, v = _qkv(cfg, x, w, jnp.arange(x.shape[0]), control)
    x = x + mm("shd,hde->se", _final_attention(cfg, q, k, v, control),
               w["wo"])
    ff, margin = expert_layer(cfg, _rms(x, w["ln2"], cfg["rms_norm_eps"]), w,
                              control)
    return x + ff, margin


@partial(jax.jit, static_argnums=(0, 6, 7))
def replay_block(cfg_key: tuple, x, xp, w, starts, stale_from,
                 n_passes: int, control: str):
    """One layer on a sequence in its final state ``x [S, E]`` AND on its
    generated blocks in their pass states ``xp [N, B, E]`` (pair ``n`` is
    block ``n // n_passes`` of the generated ones, before its pass ``n %
    n_passes``; its block starts at ``starts[n]``). The pass states see the
    FINAL rows of everything before their block (under ``stale_rows``: a
    generated block's rows, from ``stale_from`` on, as its last denoising
    pass left them). -> (x, xp, margins [S], margins [N, B])."""
    cfg = _unhash(cfg_key)
    mm = partial(_product, control=control)
    eps = cfg["rms_norm_eps"]
    n, b = xp.shape[:2]
    q, k, v = _qkv(cfg, x, w, jnp.arange(x.shape[0]), control)
    at = starts[:, None] + jnp.arange(b)[None]
    qp, kp, vp = _qkv(cfg, xp, w, at, control)
    k_ctx, v_ctx = k, v
    if control == "stale_rows":
        # the rows a program WITHOUT the storing pass would keep: those
        # its last denoising pass wrote
        last = jnp.arange(n_passes - 1, n, n_passes)
        rows = (starts[last][:, None] + jnp.arange(b)[None]).reshape(-1)
        stale = rows >= stale_from
        rows = jnp.where(stale, rows, x.shape[0])        # dropped
        k_ctx = k.at[rows].set(kp[last].reshape((-1,) + k.shape[1:]),
                               mode="drop")
        v_ctx = v.at[rows].set(vp[last].reshape((-1,) + v.shape[1:]),
                               mode="drop")
    x = x + mm("shd,hde->se", _final_attention(cfg, q, k, v, control),
               w["wo"])
    xp = xp + mm("nbhd,hde->nbe", _pass_attention(
        cfg, qp, kp, vp, k_ctx, v_ctx, starts, control), w["wo"])
    rows_all = jnp.concatenate([x, xp.reshape(n * b, -1)])
    ff, margin = expert_layer(cfg, _rms(rows_all, w["ln2"], eps), w, control)
    rows_all = rows_all + ff
    s = x.shape[0]
    return (rows_all[:s], rows_all[s:].reshape(xp.shape), margin[:s],
            margin[s:].reshape(n, b))


def _head(cfg: dict, x, ln_f, lm_head, control: str):
    return _product("...e,ev->...v", _rms(x, ln_f, cfg["rms_norm_eps"]),
                    lm_head, control)


def logits_many(cfg: dict, seed: int, sequences, control: str = "",
                positions=None):
    """For each sequence (1-D id arrays; all tokens FINAL, the block-causal
    forward of the whole): float32 logits ``[S, V]``, or ``[len(p), V]`` at
    ``positions[i]`` where given (the vocabulary is wide), and each
    position's smallest ``choice_margin`` over the layers ``[S]``. The
    weights are made once a layer and used for all the sequences."""
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
            for i, x in enumerate(xs):
                xs[i], m = jax.block_until_ready(
                    final_block(key, x, w, control))
                margins[i] = jnp.minimum(margins[i], m)
            del w
        ln_f, head = (weight(cfg, seed, TOP, "ln_f"),
                      weight(cfg, seed, TOP, "lm_head"))
        if positions is not None:
            xs = [x[jnp.asarray(p, jnp.int32)] for x, p in zip(xs, positions)]
        return [_head(cfg, x, ln_f, head, control) for x in xs], margins


def logits(cfg: dict, seed: int, tokens, control: str = ""):
    """float32 logits ``[S, V]`` of one sequence."""
    return logits_many(cfg, seed, [tokens], control)[0][0]


def _trajectory(cfg: dict, prompt, answer, unmask_steps, width: int,
                n_pairs: int):
    """What a served trajectory fixes, as arrays padded to ``width``
    positions and ``n_pairs`` (block, pass) pairs: the final tokens, each
    pair's tokens, masked state and block start, and where the replay holds
    the served positions."""
    b, t, mask_id = gen(cfg)
    prompt, answer = list(prompt), list(answer)
    steps = np.asarray(unmask_steps, np.int64)
    if len(steps) != len(answer):
        raise ValueError("one unmask step a served token")
    n_whole = (len(prompt) + len(answer)) // b      # blocks known in full
    first = len(prompt) // b                        # the first generated
    blocks = max(0, n_whole - first)
    if blocks * t > n_pairs or n_whole * b > width:
        raise ValueError("the trajectory does not fit the padding")
    final = np.zeros((width,), np.int32)
    known = (prompt + answer)[: n_whole * b]
    final[: len(known)] = known
    tokens = np.full((n_pairs, b), mask_id, np.int32)
    masked = np.zeros((n_pairs, b), bool)
    # a padding pair sits on the last block of the width, where nothing
    # real looks
    starts = np.full((n_pairs,), width - b, np.int32)
    where = []           # (answer index, pair, position in block)
    for g in range(blocks):
        for s in range(t):
            n = g * t + s
            starts[n] = (first + g) * b
            for j in range(b):
                p = (first + g) * b + j
                a = p - len(prompt)
                if a < 0:
                    tokens[n, j] = prompt[p]
                elif steps[a] < s:
                    tokens[n, j] = answer[a]
                else:
                    masked[n, j] = True
                    if steps[a] == s:
                        where.append((a, n, j))
    return {"final": final, "tokens": tokens, "masked": masked,
            "starts": starts, "where": sorted(where), "blocks": blocks,
            "stale_from": len(prompt) // b * b}


def denoise_logits_many(cfg: dict, seed: int, requests, control: str = "",
                        width: int | None = None, blocks: int | None = None):
    """Replays served trajectories: ``requests`` is a list of ``(prompt,
    answer, unmask_steps)``. For each, a dict:

      ``index``       the answer indices replayed (a last block that the
                      budget cut is left out), in order
      ``logits``      ``[len(index), V]``: at each such position, in the
                      pass that unmasked it, the block in the state the
                      trajectory says it had (final token where
                      ``unmask_steps < s``, the mask id elsewhere) behind
                      all earlier blocks in their FINAL state
      ``margin``      ``[len(index)]``: that row's smallest
                      ``choice_margin`` over the layers (``margin_pairs``
                      ``[pairs, B]``: every row's)
      ``log_conf``    ``[pairs, B]``: for every (block, pass) the log of the
                      softmax probability of the reference's own candidate
                      (its argmax) at each position; ``masked`` ``[pairs,
                      B]`` says which positions were still masked before
                      the pass, ``chosen`` which of them the trajectory
                      unmasked in it
      ``would``       ``[pairs, B]`` bool: what THIS reference (with its
                      ``control``) unmasks in that pass: the ``B / T``
                      masked positions of highest confidence, ties to the
                      lower (``least_confident``: of lowest)

    ``width`` and ``blocks`` pad every request alike (one compilation)."""
    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    b, t, _ = gen(cfg)
    key = _hashable(cfg)
    need_w = max((len(p) + len(a)) // b * b for p, a, _ in requests)
    need_b = max((len(p) + len(a)) // b - len(p) // b for p, a, _ in requests)
    width = max(width or 0, -(-need_w // Q_BLOCK) * Q_BLOCK
                if need_w > Q_BLOCK else need_w)
    n_pairs = max(1, max(blocks or 0, need_b) * t)
    trajs = [_trajectory(cfg, p, a, u, width, n_pairs)
             for p, a, u in requests]
    with jax.default_matmul_precision("highest"):
        embed = weight(cfg, seed, TOP, "embed")
        xs = [embed[jnp.asarray(tr["final"])] for tr in trajs]
        xps = [embed[jnp.asarray(tr["tokens"])] for tr in trajs]
        margins = [jnp.full((n_pairs, b), jnp.inf) for _ in trajs]
        del embed
        for layer in range(cfg["num_hidden_layers"]):
            w = layer_weights(cfg, seed, layer)
            for i, tr in enumerate(trajs):
                xs[i], xps[i], _, m = jax.block_until_ready(replay_block(
                    key, xs[i], xps[i], w, jnp.asarray(tr["starts"]),
                    jnp.asarray(tr["stale_from"], jnp.int32), t, control))
                margins[i] = jnp.minimum(margins[i], m)
            del w
        ln_f, head = (weight(cfg, seed, TOP, "ln_f"),
                      weight(cfg, seed, TOP, "lm_head"))
        out = []
        for tr, xp, margin in zip(trajs, xps, margins):
            lg = _head(cfg, xp, ln_f, head, control)          # [pairs, B, V]
            log_conf = np.asarray(lg.max(-1)
                                  - jax.nn.logsumexp(lg, axis=-1))
            idx = np.asarray([a for a, _, _ in tr["where"]], np.int64)
            pair = np.asarray([n for _, n, _ in tr["where"]], np.int64)
            pos = np.asarray([j for _, _, j in tr["where"]], np.int64)
            chosen = np.zeros(tr["masked"].shape, bool)
            chosen[pair, pos] = True
            out.append({
                "index": idx,
                "logits": np.asarray(lg[pair, pos]) if len(idx) else
                np.zeros((0, lg.shape[-1]), np.float32),
                "margin": np.asarray(margin)[pair, pos],
                "margin_pairs": np.asarray(margin),
                "log_conf": log_conf, "masked": tr["masked"],
                "chosen": chosen, "pairs": tr["blocks"] * t,
                "would": _would_unmask(log_conf, tr["masked"], b // t,
                                       control == "least_confident"),
            })
            del lg
        return out


def _would_unmask(log_conf, masked, per_pass: int, least: bool):
    """The ``per_pass`` masked positions of highest (``least``: lowest)
    confidence in each pair, ties to the lower position."""
    score = np.where(masked, -log_conf if least else log_conf, -np.inf)
    out = np.zeros(masked.shape, bool)
    left = masked.copy()
    for _ in range(per_pass):
        pick = np.where(left, score, -np.inf).argmax(axis=1)
        ok = left[np.arange(len(pick)), pick]
        out[np.arange(len(pick))[ok], pick[ok]] = True
        left[np.arange(len(pick)), pick] = False
    return out


def denoise_logits(cfg: dict, seed: int, prompt, answer, unmask_steps,
                   control: str = ""):
    """:func:`denoise_logits_many` for one request."""
    return denoise_logits_many(cfg, seed, [(prompt, answer, unmask_steps)],
                               control)[0]


def generate(cfg: dict, seed: int, prompt, max_new: int, control: str = ""):
    """The reference's own greedy generation, everything recomputed in every
    pass (a tiny configuration's: the tests'). -> (tokens, unmask_steps),
    ``max_new`` of each; the whole of a last block is denoised and only the
    tokens inside the budget are returned."""
    b, t, mask_id = gen(cfg)
    prompt = list(prompt)
    whole = len(prompt) // b * b
    done, opening = prompt[:whole], prompt[whole:]
    answer, steps = [], []
    width = -(-(len(prompt) + max_new) // b) * b
    while len(answer) < max_new:
        tokens = opening + [mask_id] * (b - len(opening))
        masked = [False] * len(opening) + [True] * (b - len(opening))
        step_of = [-1] * b
        for s in range(t):
            seq = np.zeros((width,), np.int32)
            seq[: len(done) + b] = done + tokens
            lg = np.asarray(logits_many(
                cfg, seed, [seq], control,
                positions=[np.arange(len(done), len(done) + b)])[0][0])
            log_conf = lg.max(-1) - np.asarray(
                jax.nn.logsumexp(jnp.asarray(lg), axis=-1))
            would = _would_unmask(log_conf[None], np.asarray(masked)[None],
                                  b // t, control == "least_confident")[0]
            for j in np.flatnonzero(would):
                tokens[j], masked[j], step_of[j] = int(lg[j].argmax()), \
                    False, s
        for j in range(len(opening), b):
            answer.append(tokens[j])
            steps.append(step_of[j])
        done, opening = done + tokens, []
    return answer[:max_new], steps[:max_new]
