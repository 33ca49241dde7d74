"""A stack that is a LIST of mixers (``attn_kind='mixers'``): block-sparse
attention over a compressed-key cache beside lightning linear attention,
whose cache is a state that no token position addresses. The forward pass
only: scoring and serving.

The block is ``models/transformer.py``'s ONE block (``make_layer_fn`` with
``mixer=``: norms, projections, q/k norms, the rotary embedding where the
mixer takes one, the output gate, the residual scale, the SwiGLU). What
lives here is what that block's ``attend`` hook owns, the two mixers, and
the cache tree they keep (DESIGN.md §23.5):

  rows    ``k``, ``v`` ``[L_sparse * G, B, max_len, D]`` (a sparse layer's
          ``G`` key/value heads lie side by side on the first axis, so
          that a block of one head's rows is contiguous) and the
          COMPRESSED keys ``kc`` ``[L_sparse * G, B, max_len / stride, D]``:
          ``kc[j] = mean(k[stride j : stride j + kernel])``, written once
          all ``kernel`` positions exist (``kernel == 2 * stride``)
  state   ``s`` ``[L_lightning, B, H, D, D]`` float32: no position axis

``sparse`` (InfLLM-v2), the query at position ``t`` (it sees ``t + 1`` keys):
  t + 1 <= dense_len   plain causal softmax attention
  else   p_h = softmax_j(q_h . kc_j / sqrt(D)) over the j all of whose
         positions it sees; P_g = sum of p_h over the group's heads; a
         block's score the max of P_g over the compressed windows that
         touch it; the first ``init_blocks`` and the ``window / block``
         blocks ending with the query's own are forced; the ``topk``
         highest (ties to the lower index) are attended to, causally
``lightning``, per head a decay ``lam_h = exp(-2^(-8 (h + 1) / H))``:
  S_t = lam_h S_(t-1) + k_t^T v_t,  o_t = q_t S_t / sqrt(D)
  run for a call of C tokens in its chunk form (the same function):
  o_i = (lam^(i+1) q_i S_prev + sum_(j<=i) lam^(i-j) (q_i . k_j) v_j) / sqrt(D)
  S_new = lam^n S_prev + sum_(j<n) lam^(n-1-j) k_j^T v_j

**Which of a call's tokens are real** (``real [B]``, all of them when
None): the first ``real[b]`` of row ``b``'s tokens. The rest (a final
chunk's pad tail, a row that a decode step does not advance) enter no
state and complete no compressed key; their ``k``/``v`` rows are written
as ever and lie beyond the row's position, where the next real token
overwrites them.

A decode step (one new token a row) GATHERS the selected blocks and reads
nothing else of a row; a wider call (a prefill chunk, the uncached
forward) computes its attention densely, a few heads at a time, and masks
it to each query's selection. The model's ``counters`` say how many keys
each scored and how many the selection chose.

**The second family** (``transformer.SINGLE_MIXERS``: nemotron_h), layers of
ONE sublayer each, through the same ``labels`` / ``init_cache`` /
``forward_cached``:

  rows    ``k``, ``v`` ``[L_attention, B, max_len, G, D]`` (read through
          ``cache.layer_attend``: no rotary embedding reaches it)
  state   ``ssm`` ``[L_mamba2, B, H, P, N]`` float32 and ``conv``
          ``[L_mamba2, B, K - 1, C]``: the convolution's WINDOW, the last
          ``K - 1`` inputs of its ``C = H P + 2 G N`` channels

``mamba2``, per head ``h`` (group ``g = h // (H / G)``), ``dt`` after its
softplus, ``a_t = exp(dt_t A_h)``:
  S_t = a_t S_(t-1) + dt_t x_t (outer) B_t,g;   y_t = S_t C_t,g + D_h x_t
  a call of one token runs that; a wider one the chunked form over chunks of
  ``ssm_chunk`` (the same function): inside a chunk ``y_l = sum_(s<=l)
  (C_l . B_s) exp(cum_l - cum_s) dt_s x_s + exp(cum_l) C_l S_in`` with
  ``cum`` the running sum of ``dt A``, and the chunk hands on
  ``S_out = exp(cum_end) S_in + sum_s exp(cum_end - cum_s) dt_s x_s (outer) B_s``.
  A token that is not real gets ``dt = 0`` (it decays nothing and adds
  nothing), and the window kept is the ``K - 1`` inputs ending with the
  row's last REAL token: unlike a decayed sum, a window depends on WHICH
  tokens came last.
``latent_experts`` keeps no cache; its loads ride the counters
(``ops/moe.held_counters``) beside ``ssm_row_steps`` (real tokens x mamba2
layers that entered a state), ``context_tokens`` (as above),
``expert_steps`` (calls counted: each passes
every expert layer once) and ``experts_hit_share`` (``experts_hit`` over held
experts x expert layers x ``expert_steps``).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from dlrover_tpu.models import transformer as tfm
from dlrover_tpu.models.cache import layer_attend, write_rows
from dlrover_tpu.ops import moe

Params = Any
KINDS = ("sparse", "lightning")
# with the second family's (a stack holds one family's kinds: `kinds_of`)
EVERY_KIND = (*KINDS, *tfm.SINGLE_MIXERS)
# bytes of float32 scores a wide call's attention holds at once
SCORE_BYTES = 300e6
# a wide call reads a row up to one of this many lengths (a compiled
# branch each): the least that holds the call's last query. Quarters of
# the row, block-aligned: not `cache.key_reaches`' rule (ROADMAP D19)
KEY_REACHES = 4


def labels(cfg) -> list[tuple]:
    """A layer's mixer names its cache and its weights' subtree
    (``transformer.stack_runs``: each run one scan over its kind's
    stacked weights, from its first layer OF ITS KIND on)."""
    return [(kind, f"{kind}_layers") for kind in cfg.mixer_types]


def kinds_of(cfg) -> tuple:
    """The kinds the stack holds, in ``EVERY_KIND``'s order."""
    return tuple(k for k in EVERY_KIND if k in cfg.mixer_types)


def _single(cfg) -> bool:
    return cfg.mixer_types[0] in tfm.SINGLE_MIXERS


def _ssm_sizes(cfg) -> tuple:
    """``(inner width H P, B and C's width 2 G N, convolved channels)``."""
    inner = cfg.ssm_heads * cfg.ssm_head_dim
    bc = 2 * cfg.ssm_groups * cfg.ssm_state
    return inner, bc, inner + bc


def _single_layer_shapes(cfg) -> dict:
    """One layer's leaves of each single-sublayer kind."""
    c = cfg
    e, h, g, d = c.d_model, c.n_heads, c.n_kv_heads, c.head_dim
    inner, bc, conv = _ssm_sizes(c)
    lat, f = c.moe_latent, c.moe_d_ff
    held = tfm.routed_config(c).n_held
    return {
        "mamba2": {
            "ln1": (e,), "w_ssm_in": (e, 2 * inner + bc + c.ssm_heads),
            "conv_w": (conv, c.ssm_conv), "conv_b": (conv,),
            "a_log": (c.ssm_heads,), "d_skip": (c.ssm_heads,),
            "dt_bias": (c.ssm_heads,), "ln_y": (inner,),
            "w_ssm_out": (inner, e)},
        "attention": {"ln1": (e,), "wq": (e, h, d), "wk": (e, g, d),
                      "wv": (e, g, d), "wo": (h, d, e)},
        "latent_experts": {
            "ln2": (e,), "w_router": (e, c.n_routed_experts),
            "b_router": (c.n_routed_experts,), "w_lat_down": (e, lat),
            "w_lat_up": (lat, e), "we_up": (held, lat, f),
            "we_down": (held, f, lat), "ws_up": (e, c.moe_shared_d_ff),
            "ws_down": (c.moe_shared_d_ff, e)},
    }


def param_shapes(cfg) -> dict:
    """The parameter tree as shapes (a tuple a leaf): ``<kind>_layers`` for
    each kind the stack holds (``sparse_layers`` and ``lightning_layers``,
    or the single-sublayer kinds'), each stacked over the layers of its
    kind in their order in the stack."""
    c, n_of = cfg, cfg.mixer_types.count
    tree = {"embed": (c.vocab_size, c.d_model), "ln_f": (c.d_model,),
            "lm_head": (c.d_model, c.vocab_size)}
    if _single(c):
        for kind in kinds_of(c):
            tree[f"{kind}_layers"] = {
                name: (n_of(kind), *shape)
                for name, shape in _single_layer_shapes(c)[kind].items()}
        return tree
    e, h, d, f = c.d_model, c.n_heads, c.head_dim, c.d_ff
    common = {"wq": (e, h, d), "wo": (h, d, e), "w_og": (e, h, d),
              "ln1": (e,), "ln2": (e,), "ln_q": (d,), "ln_k": (d,),
              "w_gate": (e, f), "w_up": (e, f), "w_down": (f, e)}
    kv = {"sparse": c.sparse_kv_heads, "lightning": c.n_kv_heads}
    for kind in kinds_of(c):
        layer = {**common, "wk": (e, kv[kind], d), "wv": (e, kv[kind], d)}
        if kind == "lightning":
            layer["ln_o"] = (h * d,)
        tree[f"{kind}_layers"] = {
            name: (n_of(kind), *shape) for name, shape in layer.items()}
    return tree


def init_cache(cfg, batch: int, max_len: int) -> dict:
    """The cache tree: rows, ``state``, the position and the counters."""
    c, n_of = cfg, cfg.mixer_types.count
    if _single(c):
        dt = jnp.dtype(c.dtype)
        rows = (n_of("attention"), batch, max_len, c.n_kv_heads,
                c.head_dim)
        layers = n_of("mamba2")
        return {
            "k": jnp.zeros(rows, dt), "v": jnp.zeros(rows, dt),
            "pos": jnp.zeros((), jnp.int32),
            "state": {
                "ssm": jnp.zeros((layers, batch, c.ssm_heads, c.ssm_head_dim,
                                  c.ssm_state), jnp.float32),
                "conv": jnp.zeros((layers, batch, c.ssm_conv - 1,
                                   _ssm_sizes(c)[2]), dt)},
            "counters": {
                **moe.held_counters(n_of("latent_experts"),
                                    tfm.routed_config(c).n_held),
                "ssm_row_steps": jnp.zeros((), jnp.int32),
                "context_tokens": jnp.zeros((), jnp.int32),
                "expert_steps": jnp.zeros((), jnp.int32),
                "experts_hit_share": jnp.zeros((), jnp.float32)},
        }
    if max_len % c.sparse_block:
        raise ValueError(
            f"max_len {max_len} is not a multiple of the sparse block "
            f"{c.sparse_block}: the selection reads a row as whole blocks")
    dt = jnp.dtype(c.dtype)
    rows = (n_of("sparse") * c.sparse_kv_heads, batch, max_len,
            c.head_dim)
    comp = rows[:2] + (max_len // c.sparse_stride, c.head_dim)
    return {
        "k": jnp.zeros(rows, dt), "v": jnp.zeros(rows, dt),
        "kc": jnp.zeros(comp, dt),
        "pos": jnp.zeros((), jnp.int32),
        "state": {"s": jnp.zeros(
            (n_of("lightning"), batch, c.n_heads, c.head_dim, c.head_dim),
            jnp.float32)},
        "counters": {
            "sparse_keys_selected": jnp.zeros((), jnp.int32),
            "sparse_keys_scored": jnp.zeros((), jnp.int32),
            "sparse_queries": jnp.zeros((), jnp.int32),
            "context_tokens": jnp.zeros((), jnp.int32),
            "sparse_keys_share": jnp.zeros((), jnp.float32)},
    }


# ------------------------------------------------------------- lightning


def _lightning_attend(q, k, v, state, *, real_b):
    """``q, k, v [B, C, H, D]`` against the layer's state: the chunk form
    of the recurrence (module docstring). The state takes in the first
    ``real_b[b]`` tokens of row ``b`` and no other."""
    s_stack, layer = state
    dt = q.dtype
    B, C, H, D = q.shape
    f32 = jnp.float32
    s_prev = lax.dynamic_index_in_dim(s_stack, layer, keepdims=False)
    log_lam = -(2.0 ** (-8.0 * (jnp.arange(H, dtype=f32) + 1.0) / H))
    at = jnp.arange(C)
    with jax.named_scope("lightning_intra"):
        qk = jnp.einsum("bihd,bjhd->bhij", q, k, preferred_element_type=f32)
        ago = at[:, None] - at[None, :]
        decay = jnp.where(
            ago >= 0,
            jnp.exp(log_lam[:, None, None] * jnp.maximum(ago, 0)), 0.0)
        intra = jnp.einsum("bhij,bjhd->bihd", (qk * decay).astype(dt), v,
                           preferred_element_type=f32)
    with jax.named_scope("lightning_state"):
        since = jnp.exp(log_lam[None, :] * (at[:, None] + 1.0))     # [C, H]
        inter = jnp.einsum("bihd,bhde->bihe",
                           q.astype(f32) * since[None, :, :, None], s_prev)
        o = (intra + inter) * (1.0 / math.sqrt(D))
        left = real_b[:, None] - 1 - at[None, :]                    # [B, C]
        weight = jnp.where(
            left[..., None] >= 0,
            jnp.exp(log_lam * jnp.maximum(left, 0)[..., None]), 0.0)
        kd = (k.astype(f32) * weight[..., None]).astype(dt)
        s_new = (jnp.exp(log_lam[None, :] * real_b[:, None].astype(f32))
                 [..., None, None] * s_prev
                 + jnp.einsum("bjhd,bjhe->bhde", kd, v,
                              preferred_element_type=f32))
        s_stack = lax.dynamic_update_index_in_dim(s_stack, s_new, layer, 0)
    return o.astype(dt), (s_stack, layer)


# ---------------------------------------------------------------- mamba2


def _ssm_step(x, b, c, dt, a_log_rate, s_prev):
    """The recurrence for one token a row: ``x [B, H, P]``, ``b, c [B, G,
    N]``, ``dt [B, H]`` (0 where the token is not real), ``a_log_rate =
    A [H]`` -> ``(y [B, H, P], S [B, H, P, N])``, all float32 and
    elementwise: the state is read once and written once."""
    rep = x.shape[1] // b.shape[1]
    bh, ch = jnp.repeat(b, rep, axis=1), jnp.repeat(c, rep, axis=1)
    s_new = (jnp.exp(dt * a_log_rate)[..., None, None] * s_prev
             + (dt[..., None] * x)[..., None] * bh[:, :, None, :])
    return (s_new * ch[:, :, None, :]).sum(axis=-1), s_new


def _ssm_chunks(x, b, c, dt, rate, s_prev, chunk: int, dtype):
    """The chunked (state-space-dual) form of the same recurrence for a
    call of ``S`` tokens: ``x [B, S, H, P]``, ``b, c [B, S, G, N]`` float32,
    ``dt [B, S, H]`` (0 where a token is not real) -> ``(y [B, S, H, P],
    S_out)``. Products over tokens run in ``dtype`` with float32
    accumulation; the running sums, the decays and what touches the state
    are float32."""
    f32, hi = jnp.float32, lax.Precision.HIGHEST
    B, S, H, P = x.shape
    G, N = b.shape[2:]
    R = H // G
    pad = -S % chunk
    if pad:      # dt = 0: the padding decays nothing and adds nothing
        x, b, c, dt = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                       for a in (x, b, c, dt))
    nc = (S + pad) // chunk
    x, b, c, dt = (a.reshape(B, nc, chunk, *a.shape[2:])
                   for a in (x, b, c, dt))
    cum = jnp.cumsum(dt * rate, axis=2).transpose(0, 1, 3, 2)   # [B,nc,H,L]
    xdt = x * dt[..., None]                                   # [B,nc,L,H,P]
    # inside a chunk: (C_l . B_s) exp(cum_l - cum_s) for s <= l
    at = jnp.arange(chunk)
    since = jnp.where(at[:, None] >= at[None, :],
                      cum[..., :, None] - cum[..., None, :], -jnp.inf)
    cb = jnp.einsum("bclgn,bcsgn->bcgls", c.astype(dtype), b.astype(dtype),
                    preferred_element_type=f32)
    mix = (jnp.repeat(cb, R, axis=2) * jnp.exp(since)).astype(dtype)
    y = jnp.einsum("bchls,bcshp->bclhp", mix, xdt.astype(dtype),
                   preferred_element_type=f32)
    # what a chunk adds to the state, and the states entering each chunk
    to_end = jnp.exp(cum[..., -1:] - cum).transpose(0, 1, 3, 2)  # [B,nc,L,H]
    added = jnp.einsum(
        "bcsgrp,bcsgn->bcgrpn",
        (xdt * to_end[..., None]).astype(dtype).reshape(B, nc, chunk, G, R, P),
        b.astype(dtype), preferred_element_type=f32
    ).reshape(B, nc, H, P, N)

    def hand_on(state, inputs):
        mine, decay = inputs
        return decay[..., None, None] * state + mine, state

    s_out, s_in = lax.scan(
        hand_on, s_prev, (jnp.moveaxis(added, 1, 0),
                          jnp.moveaxis(jnp.exp(cum[..., -1]), 1, 0)))
    from_state = jnp.einsum(
        "bclgn,cbgrpn->bclgrp", c, s_in.reshape(nc, B, G, R, P, N),
        precision=hi).reshape(B, nc, chunk, H, P)
    y = y + from_state * jnp.exp(cum).transpose(0, 1, 3, 2)[..., None]
    return y.reshape(B, nc * chunk, H, P)[:, :S], s_out


def _mamba2_attend(xbc, dt_raw, w, state, *, cfg, real_b):
    """The Mamba-2 mixer's ``attend`` (``make_layer_fn``): ``xbc [B, S, C]``
    and ``dt_raw [B, S, H]`` from the in-projection, the layer's small
    leaves in ``w`` -> ``y [B, S, H, P]``. The window and the state take in
    the first ``real_b[b]`` tokens of row ``b`` and no other."""
    ssm_stack, conv_stack, steps, layer = state
    c = cfg
    f32 = jnp.float32
    dtype = xbc.dtype
    B, S, _ = xbc.shape
    H, P, G, N, K = (c.ssm_heads, c.ssm_head_dim, c.ssm_groups, c.ssm_state,
                     c.ssm_conv)
    inner = H * P
    with jax.named_scope("ssm_conv"):
        window = lax.dynamic_index_in_dim(conv_stack, layer, keepdims=False)
        full = jnp.concatenate([window, xbc], axis=1)        # [B, K-1+S, C]
        taps = w["conv_w"].astype(f32)
        conv = w["conv_b"].astype(f32) + sum(
            full[:, j:j + S].astype(f32) * taps[:, j] for j in range(K))
        conv = jax.nn.silu(conv)
        # the K - 1 inputs that end with the row's last real token
        if S == 1:
            window = jnp.where((real_b > 0)[:, None, None], full[:, 1:],
                               window)
        else:
            window = jnp.take_along_axis(
                full, (real_b[:, None] + jnp.arange(K - 1))[..., None],
                axis=1)
        conv_stack = lax.dynamic_update_index_in_dim(
            conv_stack, window, layer, 0)
    with jax.named_scope("ssm_scan"):
        x = conv[..., :inner].reshape(B, S, H, P)
        b = conv[..., inner:inner + G * N].reshape(B, S, G, N)
        cm = conv[..., inner + G * N:].reshape(B, S, G, N)
        if dtype != f32:      # the convolution's output rests in `dtype`
            x, b, cm = (a.astype(dtype).astype(f32) for a in (x, b, cm))
        live = jnp.arange(S)[None] < real_b[:, None]
        dt = jnp.where(live[..., None], jax.nn.softplus(
            dt_raw.astype(f32) + w["dt_bias"].astype(f32)), 0.0)
        rate = -jnp.exp(w["a_log"].astype(f32))
        s_prev = lax.dynamic_index_in_dim(ssm_stack, layer, keepdims=False)
        if S == 1:
            y, s_new = _ssm_step(x[:, 0], b[:, 0], cm[:, 0], dt[:, 0], rate,
                                 s_prev)
            y = y[:, None]
        else:
            y, s_new = _ssm_chunks(x, b, cm, dt, rate, s_prev, c.ssm_chunk,
                                   dtype)
        y = y + w["d_skip"].astype(f32)[:, None] * x
        ssm_stack = lax.dynamic_update_index_in_dim(ssm_stack, s_new, layer, 0)
    return y.astype(dtype), (ssm_stack, conv_stack,
                             steps + jnp.sum(real_b), layer)


def _attention_attend(q, k, v, state, *, cfg, pos):
    """The "attention" kind's ``attend``: the call's rows into ``k``/``v``
    (positions before heads, ``[L, B, T, G, D]``), then
    ``cache.layer_attend`` over the layer's rows."""
    k_stack, v_stack, layer = state
    dt = q.dtype
    with jax.named_scope("kv_write"):
        k_stack = write_rows(k_stack, k.astype(dt), layer, pos)
        v_stack = write_rows(v_stack, v.astype(dt), layer, pos)
    o = layer_attend(
        q, lax.dynamic_index_in_dim(k_stack, layer, keepdims=False),
        lax.dynamic_index_in_dim(v_stack, layer, keepdims=False),
        pos, cfg.n_heads // cfg.n_kv_heads, dt)
    return o, (k_stack, v_stack, layer)


# ---------------------------------------------------------------- sparse


def _heads(layer, cfg):
    """Where a sparse layer's key/value heads lie on a row stack's first
    axis: ``layer * G + g``."""
    return layer * cfg.sparse_kv_heads + jnp.arange(cfg.sparse_kv_heads)


def _compress(kc_stack, k_stack, layer, pos_b, real_b, width: int, cfg):
    """Write the compressed keys whose LAST position one of the call's
    real tokens filled: window ``j`` covers positions ``[stride j,
    stride j + kernel)`` of the layer's ``k`` rows (the call's rows already
    in them), so a window that straddles the call's start is completed
    from rows an earlier call stored."""
    c = cfg
    st, kern, G = c.sparse_stride, c.sparse_kernel, c.sparse_kv_heads
    B, max_len, D = k_stack.shape[1:]
    n_comp = kc_stack.shape[2]
    # windows that can end inside the call, from the first that does (held
    # inside the stack: a window before it is not `mine` and stays)
    n_win = min(width // st + 1, n_comp)
    first = jnp.clip(-(-(pos_b - (kern - 1)) // st), 0, n_comp - n_win)
    at = st * first[:, None] + jnp.arange(st * (n_win + 1))[None]
    heads = _heads(layer, c)[:, None, None]
    rows_b = jnp.arange(B)[None, :, None]
    rows = k_stack[heads, rows_b, jnp.minimum(at, max_len - 1)[None]]
    halves = rows.astype(jnp.float32).reshape(
        G, B, n_win + 1, st, D).sum(axis=3)
    new = ((halves[:, :, :-1] + halves[:, :, 1:]) / kern).astype(
        kc_stack.dtype)
    j = first[:, None] + jnp.arange(n_win)[None]                     # [B, W]
    last = st * j + kern - 1
    mine = (last >= pos_b[:, None]) & (last < (pos_b + real_b)[:, None])
    old = kc_stack[heads, rows_b, j[None]]
    new = jnp.where(mine[None, ..., None], new, old)
    for g in range(G):
        kc_stack = write_rows(kc_stack, new[g], layer * G + g, first)
    return kc_stack


def _block_scores(p_group, t, cfg):
    """``p_group [..., Nc]`` (a key/value group's summed probabilities over
    compressed keys, zero where a window is not seen) -> ``[..., Nb]``
    block scores for the query at ``t [...]``: forced blocks +inf, blocks
    past the query's own -inf."""
    c = cfg
    m = c.sparse_block // c.sparse_stride
    n_blocks = p_group.shape[-1] // m
    lead = p_group.shape[:-1]
    # block b is touched by windows m b - 1 .. m b + m - 1: one before its
    # first, and its own m
    padded = jnp.concatenate(
        [jnp.zeros(lead + (1,), p_group.dtype), p_group], axis=-1)
    own_windows = padded[..., : m * n_blocks].reshape(
        lead + (n_blocks, m)).max(axis=-1)
    score = jnp.maximum(own_windows, padded[..., m::m])
    b = jnp.arange(n_blocks)
    own = (t // c.sparse_block)[..., None]
    forced = (b < c.sparse_init_blocks) | (
        (b <= own) & (b > own - c.sparse_window // c.sparse_block))
    score = jnp.where(forced, jnp.inf, score)
    return jnp.where(b > own, -jnp.inf, score)


def _select(qg, kc_rows, t, cfg, as_mask: bool = False):
    """The blocks each query and key/value group selects, from the layer's
    compressed keys ``kc_rows [G, B, Nc, D]``: block ids ``[..., topk]`` (a
    decode step gathers them) or, ``as_mask``, ``[..., Nb]`` booleans (a
    wide call masks with them). ``qg [G, B, (S,) R, D]`` at positions ``t
    [B(, S)]``: group and row lead, as they do in the stacks (an operand
    whose batch dims lie otherwise is copied whole to suit the product).
    Scores, softmax and block scores are float32."""
    c = cfg
    scale = 1.0 / math.sqrt(c.head_dim)
    n_comp = kc_rows.shape[2]
    seen = (c.sparse_stride * jnp.arange(n_comp) + c.sparse_kernel
            <= t[..., None] + 1)[None, :, None]          # [1,B,1(,S),Nc]
    if qg.ndim == 4:       # a decode step
        scores = jnp.einsum("gbrd,gbjd->gbrj", qg, kc_rows,
                            preferred_element_type=jnp.float32) * scale
    else:
        scores = jnp.einsum("gbsrd,gbjd->gbrsj", qg, kc_rows,
                            preferred_element_type=jnp.float32) * scale
    probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1) * seen
    score = _block_scores(probs.sum(axis=2), t[None], c)
    if not as_mask:
        return lax.top_k(score, c.sparse_topk)[1]
    # a block's rank: the blocks that score higher, and those that score
    # the same at a lower index (a sort of 528 scores a query costs the
    # chip 2.5 ms a chunk a layer; the comparisons fuse into one pass)
    before = jnp.arange(score.shape[-1])
    ahead = (score[..., None, :] > score[..., :, None]) | (
        (score[..., None, :] == score[..., :, None])
        & (before[None, :] < before[:, None]))
    return ahead.sum(axis=-1) < c.sparse_topk


def _sparse_step(q, k_stack, v_stack, kc_rows, layer, t, live, cfg):
    """One new token a row: ``q [B, 1, H, D]`` at position ``t [B]``. The
    selected blocks are gathered; a row still under ``dense_len`` (and
    ``live``) attends over the first ``dense_len`` keys instead."""
    c = cfg
    dt = q.dtype
    B, _, H, D = q.shape
    G, blk, topk = c.sparse_kv_heads, c.sparse_block, c.sparse_topk
    scale = 1.0 / math.sqrt(D)
    qg = q[:, 0].reshape(B, G, H // G, D).transpose(1, 0, 2, 3)
    with jax.named_scope("sparse_select"):
        idx = _select(qg, kc_rows, t, c)                         # [G,B,topk]
    with jax.named_scope("sparse_attend"):
        max_len = k_stack.shape[2]
        heads = _heads(layer, c)[:, None, None]
        rows_b = jnp.arange(B)[None, :, None]

        def gather(stack):
            # a block of one head's rows is contiguous: [blk, D]
            blocks = stack.reshape(-1, B, max_len // blk, blk, D)
            return blocks[heads, rows_b, idx].reshape(G, B, topk * blk, D)

        key_pos = (idx[..., None] * blk + jnp.arange(blk)).reshape(
            G, B, topk * blk)
        seen = key_pos <= t[None, :, None]
        scores = jnp.einsum("gbrd,gbkd->gbrk", qg, gather(k_stack),
                            preferred_element_type=jnp.float32) * scale
        probs = jax.nn.softmax(
            jnp.where(seen[:, :, None], scores, -1e30), axis=-1).astype(dt)
        o = jnp.einsum("gbrk,gbkd->gbrd", probs, gather(v_stack))

        dense_len = min(c.sparse_dense_len, max_len)
        is_dense = t + 1 <= c.sparse_dense_len

        def head_of(stack):
            return lax.dynamic_slice(stack, (layer * G, 0, 0, 0),
                                     (G, B, dense_len, D))

        def dense(_):
            s = jnp.einsum("gbrd,gbkd->gbrk", qg, head_of(k_stack),
                           preferred_element_type=jnp.float32) * scale
            see = jnp.arange(dense_len)[None] <= t[:, None]
            p = jax.nn.softmax(jnp.where(see[None, :, None], s, -1e30),
                               axis=-1).astype(dt)
            return jnp.einsum("gbrk,gbkd->gbrd", p, head_of(v_stack))

        o_dense = lax.cond(jnp.any(is_dense & live), dense,
                           lambda _: jnp.zeros_like(o), None)
        o = jnp.where(is_dense[None, :, None, None], o_dense, o)
    selected = jnp.where(is_dense[None], (t + 1)[None],
                         seen.sum(axis=-1))                      # [G, B]
    scored = jnp.where(is_dense, dense_len, topk * blk)          # [B]
    tally = jnp.stack([
        jnp.sum(jnp.where(live[None], selected, 0)),
        jnp.sum(jnp.where(live, scored, 0)) * G,
        jnp.sum(live) * G]).astype(jnp.int32)
    return o.transpose(1, 0, 2, 3).reshape(B, 1, H, D), tally


def _sparse_chunk(q, k_stack, v_stack, kc_rows, layer, t, live, cfg):
    """A call of ``S`` tokens a row: ``q [B, S, H, D]`` at positions
    ``t [B, S]``. Attention over the row as far as the call's last query
    reaches, a few heads at a time, masked to each query's selection (or
    to the causal mask under ``dense_len``)."""
    c = cfg
    dt = q.dtype
    B, S, H, D = q.shape
    G, blk = c.sparse_kv_heads, c.sparse_block
    R = H // G
    n_keys = k_stack.shape[2]
    n_blocks = n_keys // blk
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, S, G, R, D)
    with jax.named_scope("sparse_select"):
        chosen = _select(qg.transpose(2, 0, 1, 3, 4), kc_rows, t, c,
                         as_mask=True)
        is_dense = t + 1 <= c.sparse_dense_len                 # [B, S]
        open_blocks = chosen | is_dense[None, ..., None]       # [G,B,S,Nb]
    with jax.named_scope("sparse_attend"):
        # the keys a call can see end at its last query: the row is read
        # up to the first of a few fixed lengths that holds them (chosen
        # from the call's position, which the code sees)
        reach = [min(n_keys, -(-n_keys * i // (KEY_REACHES * blk)) * blk)
                 for i in range(1, KEY_REACHES + 1)]
        reach = sorted(set(reach))
        which = jnp.sum(jnp.asarray(reach) < jnp.max(t) + 1)

        def attend(keys: int):
            heads = max(1, int(SCORE_BYTES // (4 * B * S * keys)))
            while R % heads:
                heads -= 1
            causal = jnp.arange(keys)[None, None] <= t[..., None]  # [B,S,K]
            q_sub = jnp.moveaxis(
                qg.reshape(B, S, G * R // heads, heads, D), 2, 0)

            def sub(_, inputs):
                qs, n = inputs                             # [B,S,heads,D]
                g = n // (R // heads)
                head = layer * G + g
                kg = lax.dynamic_index_in_dim(
                    k_stack, head, 0, keepdims=False)[:, :keys]
                vg = lax.dynamic_index_in_dim(
                    v_stack, head, 0, keepdims=False)[:, :keys]
                see = causal & jnp.repeat(lax.dynamic_index_in_dim(
                    open_blocks, g, 0, keepdims=False)[..., : keys // blk],
                    blk, axis=-1)
                s = jnp.einsum("bshd,bkd->bhsk", qs, kg,
                               preferred_element_type=jnp.float32) * scale
                p = jax.nn.softmax(jnp.where(see[:, None], s, -1e30),
                                   axis=-1).astype(dt)
                return None, jnp.einsum("bhsk,bkd->bshd", p, vg)

            _, out = lax.scan(sub, None,
                              (q_sub, jnp.arange(G * R // heads)))
            return jnp.moveaxis(out, 0, 2).reshape(B, S, H, D)

        o = lax.switch(which, [partial(attend, keys) for keys in reach])
        scored = jnp.asarray(reach)[which]
    own = (t // blk)[None, ..., None]
    before = (chosen & (jnp.arange(n_blocks) < own)).sum(axis=-1) * blk
    selected = jnp.where(is_dense[None], (t + 1)[None],
                         before + (t % blk + 1)[None])           # [G,B,S]
    n_live = jnp.sum(live)
    tally = jnp.stack([
        jnp.sum(jnp.where(live[None], selected, 0)),
        n_live * G * scored, n_live * G]).astype(jnp.int32)
    return o, tally


def _sparse_attend(q, k, v, state, *, cfg, pos, pos_b, real_b):
    """The sparse mixer's ``attend`` (``make_layer_fn``): the call's rows
    into ``k``/``v``, the compressed keys they complete into ``kc``, then
    the attention of a decode step or of a wider call."""
    k_stack, v_stack, kc_stack, tally, layer = state
    dt = q.dtype
    B, S = q.shape[:2]
    G = cfg.sparse_kv_heads
    with jax.named_scope("kv_write"):
        for g in range(G):
            k_stack = write_rows(k_stack, k[:, :, g].astype(dt),
                                  layer * G + g, pos)
            v_stack = write_rows(v_stack, v[:, :, g].astype(dt),
                                  layer * G + g, pos)
    with jax.named_scope("sparse_compress"):
        kc_stack = _compress(kc_stack, k_stack, layer, pos_b, real_b, S, cfg)
    kc_rows = lax.dynamic_slice_in_dim(kc_stack, layer * G, G, axis=0)
    if S == 1:
        o, mine = _sparse_step(q, k_stack, v_stack, kc_rows, layer, pos_b,
                               real_b > 0, cfg)
    else:
        t = pos_b[:, None] + jnp.arange(S)[None]
        o, mine = _sparse_chunk(q, k_stack, v_stack, kc_rows, layer, t,
                                jnp.arange(S)[None] < real_b[:, None], cfg)
    return o, (k_stack, v_stack, kc_stack, tally + mine, layer)


# --------------------------------------------------------------- forward


def forward_cached(params: Params, tokens: jax.Array, cache: dict, cfg,
                   real=None, return_hidden: bool = False):
    """``tokens [B, S]`` from ``cache['pos']`` on (a scalar: rows in
    lockstep; ``[B]``: rows at positions of their own) -> ``(float32
    logits [B, S, V], cache)``. ``real`` (a scalar or ``[B]``; None: all):
    how many of each row's tokens are real (module docstring). The
    position advances by ``S`` whatever ``real`` says: a caller that holds
    a row back puts its position back, as it does for any model."""
    c = cfg
    B, S = tokens.shape
    pos = cache["pos"]
    pos_b = jnp.broadcast_to(pos, (B,)).astype(jnp.int32)
    real_b = jnp.full((B,), S, jnp.int32) if real is None else jnp.clip(
        jnp.broadcast_to(jnp.asarray(real).astype(jnp.int32), (B,)), 0, S)
    positions = tfm.token_positions(pos, B, S)
    x = tfm.embed_tokens(params, tokens, c, pos=pos)
    state = cache["state"]
    # what each kind's layers carry through its runs, and the `attend`
    # that owns it (none for a kind that keeps no cache)
    held = {
        "sparse": lambda: (cache["k"], cache["v"], cache["kc"],
                           jnp.zeros((3,), jnp.int32)),
        "lightning": lambda: (state["s"],),
        "mamba2": lambda: (state["ssm"], state["conv"],
                           jnp.zeros((), jnp.int32)),
        "attention": lambda: (cache["k"], cache["v"]),
        "latent_experts": lambda: (
            jnp.zeros_like(cache["counters"]["loads"]),),
    }
    held = {kind: held[kind]() for kind in kinds_of(c)}
    attends = {
        "sparse": partial(_sparse_attend, cfg=c, pos=pos, pos_b=pos_b,
                          real_b=real_b),
        "lightning": partial(_lightning_attend, real_b=real_b),
        "mamba2": partial(_mamba2_attend, cfg=c, real_b=real_b),
        "attention": partial(_attention_attend, cfg=c, pos=pos)}

    def layer_of(run):
        # a kind's held experts are closed over the block and indexed in
        # place by its tile loop (`make_layer_fn`); everything else is
        # read a layer at a time
        experts, weights = tfm.split_experts(params[run.key], c)
        block = tfm.make_layer_fn(
            c, attend=attends.get(run.kind), positions=positions,
            mixer=run.kind, experts=experts,
            mask=jnp.arange(S)[None] < real_b[:, None] if experts else None)

        def layer(x, mine, w, i):
            if run.kind == "latent_experts":
                x, loads, _ = block(x, w, None, i)
                return x, (lax.dynamic_update_index_in_dim(
                    mine[0], loads, i, 0),), None
            x, _, (*mine, _) = block(x, w, (*mine, i), i)
            return x, tuple(mine), None

        return weights, layer

    # the rows and the state ride the CARRY (models/decode.py)
    x, held, _ = tfm.scan_runs(tfm.stack_runs(c), x, held, layer_of)
    with jax.named_scope("lm_head"):
        x = tfm.final_norm(params, x, c)
        out = x if return_hidden else tfm.lm_logits(params, x, c)
    old = cache["counters"]
    if _single(c):
        k, v = held["attention"]
        ssm, conv, row_steps = held["mamba2"]
        counters = moe.count_loads(old, held["latent_experts"][0])
        counters["ssm_row_steps"] = old["ssm_row_steps"] + row_steps
        steps = jnp.arange(S)[None]
        counters["context_tokens"] = old["context_tokens"] + jnp.sum(
            jnp.where(steps < real_b[:, None], pos_b[:, None] + steps, 0))
        # calls counted: each passes every expert layer once
        counters["expert_steps"] = old["expert_steps"] + 1
        counters["experts_hit_share"] = (
            counters["experts_hit"].astype(jnp.float32)
            / (counters["expert_steps"] * max(1, counters["loads"].size)))
        return out, {"k": k, "v": v, "pos": pos + S,
                     "state": {"ssm": ssm, "conv": conv},
                     "counters": counters}
    k, v, kc, tally = held["sparse"]
    steps = jnp.arange(S)[None]
    counters = {
        "sparse_keys_selected": old["sparse_keys_selected"] + tally[0],
        "sparse_keys_scored": old["sparse_keys_scored"] + tally[1],
        "sparse_queries": old["sparse_queries"] + tally[2],
        "context_tokens": old["context_tokens"] + jnp.sum(jnp.where(
            steps < real_b[:, None], pos_b[:, None] + steps, 0)),
    }
    counters["sparse_keys_share"] = (
        counters["sparse_keys_selected"].astype(jnp.float32)
        / jnp.maximum(counters["sparse_keys_scored"], 1))
    return out, {"k": k, "v": v, "kc": kc, "pos": pos + S,
                 "state": {"s": held["lightning"][0]},
                 "counters": counters}


def forward_uncached(params: Params, tokens: jax.Array, cfg,
                     return_hidden: bool = False):
    """``forward_with_aux``'s answer for these kinds: the cached forward
    from an empty cache just long enough (the same function, and the one
    definition of it)."""
    B, S = tokens.shape
    blk = 1 if _single(cfg) else cfg.sparse_block
    return forward_cached(params, tokens,
                          init_cache(cfg, B, -(-S // blk) * blk), cfg,
                          return_hidden=return_hidden)[0]
