"""KV-cached autoregressive decoding for the bundled transformer.

Reference analog: the reference leans on vLLM for RLHF inference
(atorch/atorch/rl/inference_backend/vllm_backend.py); the TPU-native
equivalent is a cache-carrying decode step under jit — static shapes
(cache pre-allocated to max length, position masking) so XLA compiles one
step program, O(S) per generated token instead of the O(S^2) recompute of
calling the full forward per step.

The cache is the model's TREE (DESIGN.md §23.5): a position, ROWS
(stacks laid out ``[L, B, len, ...]``, a position axis third: one per K
and V, ``[L, B, max_len, H_kv * D]``, for per-head attention: a key's
heads side by side, so the default layout is row-major with full
128-lane tiles and a head of 64 is not padded; one latent
stack for ``attn_kind='latent'``, models/latent.py; ``k``, ``v`` and a
shorter stack of compressed keys for ``attn_kind='mixers'``,
models/hybrid.py) and, under ``state``, stacks ``[L, B, ...]`` that no
token position addresses (a linear-attention layer's matrix). Stacks
may differ in their layer count and rows in their length. On a TPU a
decode call of the plain per-head tree (rows at positions of their own,
few queries a row) reads its rows through ONE kernel that is given each
query's key limit and fetches a row's key blocks IN PLACE only as far as
the row reaches (``ops/cached_attention.py``); every other call takes
the einsum :func:`_layer_attend`, which is also the kernel's oracle. A WINDOWED
layer's rows (``cfg.layer_windows``) are a RING of ``window`` slots,
``k_win`` / ``v_win`` ``[L_win, B, H_kv, window, D]``: position ``p``
lives in slot ``p % window``, and the ring is STATE to everyone outside
this file (it is kept under ``state``): no one else may index its third
axis by position. A stack is
never copied whole (§23.1): the layer loop CARRIES it, layer ``l`` writes its
``S_new`` new rows into it in place (the update is the new rows alone)
and attends over ``stack[l]`` read out of the carry. Nothing of a
layer's shape is scanned in or out: a scanned input is sliced out
whole and a scanned output copied back whole, per layer. A caller that
wants the update in place donates the stack to the jitted program that
calls ``forward_cached`` and keeps no other reference to it
(``serving/engine.py`` does).

The block, the embedding and the head are training's own
(``models/transformer.py``: ``make_layer_fn``, ``embed_tokens``,
``final_norm``, ``lm_logits``). What lives here is what a cached caller
hands that block: the cache tree, the attention that writes a layer's
new rows and reads its cache, and the positions. The equivalence test
(tests/test_decode.py) pins those: prefill+cached-decode logits must
match ``forward`` on the same tokens to tolerance.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from dlrover_tpu.models import transformer as tfm
from dlrover_tpu.models.transformer import PRODUCT_LEAVES, TransformerConfig

Params = Any


def init_cache(cfg: TransformerConfig, batch: int, max_len: int) -> dict:
    """The model's cache TREE (DESIGN.md §23.5): ``pos``, rows laid
    out ``[L, B, len, ...]`` under names of the model's choosing
    (``k`` and ``v`` ``[L, B, max_len, H_kv * D]`` here, a key's heads
    side by side on the last axis; one ``latent`` stack for
    ``attn_kind='latent'``), optionally ``state`` (stacks ``[L, B, ...]``
    with no position axis) and ``counters`` (the plain tree's
    `READ_COUNTERS`, the expert layers', the rings'). Callers carry
    it whole and name none of its stacks: :func:`cache_stacks`,
    :func:`cache_state`. Only this file and ``ops/cached_attention.py``
    read a row's trailing dims as heads.

    ``cfg.layer_windows`` / ``cfg.layer_rope``: the full layers' rows are
    ``k``, ``v`` ``[L_full, B, H_kv, max_len, D]``; the windowed layers'
    are RINGS under ``state``, ``k_win``, ``v_win`` ``[L_win, B, H_kv,
    window, D]``, whose length follows from the window and never from
    ``max_len``. The key/value heads lie BEFORE the positions in both:
    the layout the grouped-head products read a row in (with positions
    first the TPU compiler copies all the stacks into this layout at the
    start of every decode call and back at its end: 2.9 GB of temporaries
    for 24 slots of 16384, PERF.md section 6, PR 41); such a tree has
    state, so nothing outside this file addresses a position of it. A
    ring holds the last ``window`` keys of its row, position ``p`` in
    slot ``p % window``; which position a slot holds
    follows from the row's ``pos`` alone (:func:`_ring_positions`), so a
    ring is valid only together with the ``pos`` it was left at: a
    holder that copies a row copies its rings whole, as it does state."""
    c = cfg
    if c.mixers:
        from dlrover_tpu.models import hybrid

        return hybrid.init_cache(c, batch, max_len)
    if c.new_kinds:
        from dlrover_tpu.models import latent

        return latent.init_cache(c, batch, max_len)
    n_win = sum(1 for w in c.layer_windows if w)
    # a key's heads side by side (above)
    shape = (c.n_layers, batch, max_len, c.n_kv_heads * c.head_dim)
    if c.layer_kinds:
        # heads before positions (below)
        shape = (c.n_layers - n_win, batch, c.n_kv_heads, max_len,
                 c.head_dim)
    cache = {
        "k": jnp.zeros(shape, jnp.dtype(c.dtype)),
        "v": jnp.zeros(shape, jnp.dtype(c.dtype)),
        "pos": jnp.zeros((), jnp.int32),
    }
    if not c.layer_kinds:
        cache["counters"] = {
            name: jnp.zeros((), jnp.int32) for name in READ_COUNTERS}
    if c.held_experts:
        from dlrover_tpu.ops.moe import held_counters

        cache["counters"] = {
            **cache.get("counters", {}),
            **held_counters(c.n_layers, tfm.routed_config(c).n_held)}
    if n_win:
        ring = (n_win, batch, c.n_kv_heads, max(c.layer_windows), c.head_dim)
        cache["state"] = {"k_win": jnp.zeros(ring, jnp.dtype(c.dtype)),
                          "v_win": jnp.zeros(ring, jnp.dtype(c.dtype))}
        cache["counters"] = {
            **cache.get("counters", {}),
            **{name: jnp.zeros((), jnp.int32) for name in WINDOW_COUNTERS}}
    return cache


# what the plain tree counts of each row of each call (a rows-only model
# is not told which rows are frozen or idle, so every row counts, as
# `expert_tokens` does): the keys attention fetched of the row (the live
# key blocks' widths through `ops/cached_attention.py`, `max_len` through
# the einsum) and the row's key limit, what the floor would read
READ_COUNTERS = ("attn_keys_read", "context_tokens")

# what a tree with rings counts of its live row-steps (a row-step: one
# real token of one row in one call): how many there were, their
# positions summed (`context_tokens`, as models/hybrid.py counts it: what
# a full layer reads of a row), `min(position, window)` summed (what a
# windowed layer reads of it) and how many lay past the window (the ring
# had wrapped)
WINDOW_COUNTERS = ("row_steps", "context_tokens", "window_keys",
                   "ring_wrapped_row_steps")


def cache_stacks(cache: dict) -> dict:
    """The ROWS of a cache tree, ``[L, B, len, ...]`` each (token
    positions along the third axis; ``len`` is the cache's length or a
    fixed fraction of it): all but the position, the state and the
    counters."""
    return {k: v for k, v in cache.items()
            if k not in ("pos", "counters", "state")}


def cache_state(cache: dict) -> dict:
    """The STATE of a cache tree: stacks ``[L, B, ...]`` that no token
    position addresses (empty for a model that keeps rows alone). What a
    caller may do with both kinds alike is index the SECOND axis by row;
    what assumes a position axis (pages, bundles, a draft's rejected
    tail put back by its position) holds for rows only."""
    return cache.get("state", {})


def cache_counter_fields(cache: dict) -> dict:
    """What a span says of a model's counters: their scalars, under the
    names the model gave them (none for a model that counts nothing)."""
    return {name: value for name, value in cache.get("counters", {}).items()
            if jnp.ndim(value) == 0}


def zero_counters(cache: dict) -> dict:
    """``cache`` with its counters at zero: a program that reports them
    a call at a time starts from here."""
    if "counters" not in cache:
        return cache
    return {**cache, "counters": jax.tree.map(jnp.zeros_like,
                                              cache["counters"])}


def _layer_attend(q, k_cache, v_cache, pos, n_rep, dt, window=0, block=0):
    """q: [B, S_new, H, D] against cache [B, max_len, H_kv, D].

    GQA reads the cache UNEXPANDED via a grouped-head einsum — repeating
    it to H heads would multiply per-token decode memory traffic by
    ``n_rep`` on the hot path. ``window > 0`` applies the sliding-window
    mask so decode matches a model trained with local attention.
    ``pos`` scalar: all rows in lockstep (one [S, K] mask). [B] vector:
    independent per-row positions (continuous batching,
    serving/engine.py) with a [B, S, K] mask. ``block > 0`` is a
    block-diffusion model's mask in place of the causal one: a query
    sees every key up to the END of its own block of ``block`` absolute
    positions (``k < (q // block + 1) * block``), in every program: a
    prefill chunk, a denoising pass, a storing pass.
    """
    B, S_new, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    G = k_cache.shape[2]  # kv heads
    qg = q.reshape(B, S_new, G, n_rep, D)
    with jax.named_scope("kv_read"):
        logits = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k_cache).astype(
            jnp.float32
        ) * scale
    max_len = k_cache.shape[1]
    k_pos = jnp.arange(max_len)
    if jnp.ndim(pos) == 0:
        # causal over absolute positions: query i sits at pos + i
        q_pos = pos + jnp.arange(S_new)
        if block > 0:
            mask = k_pos[None, :] < ((q_pos // block + 1) * block)[:, None]
        else:
            mask = q_pos[:, None] >= k_pos[None, :]        # [S, K]
        if window > 0:
            mask &= q_pos[:, None] - k_pos[None, :] < window
        mask = mask[None, None, None]
    else:
        # row b's query i sits at pos[b] + i
        q_pos = pos[:, None] + jnp.arange(S_new)[None]     # [B, S_new]
        if block > 0:
            mask = (k_pos[None, None, :]
                    < ((q_pos // block + 1) * block)[:, :, None])
        else:
            mask = q_pos[:, :, None] >= k_pos[None, None, :]  # [B, S, K]
        if window > 0:
            mask &= q_pos[:, :, None] - k_pos[None, None, :] < window
        mask = mask[:, None, None]
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(dt)
    with jax.named_scope("kv_read"):
        o = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v_cache)
    return o.reshape(B, S_new, H, D)


def _write_rows(stack, new, layer, pos):
    """Write ``new`` [B, S_new, ...] into ``stack`` [L, B, max_len, ...]
    (per-head rows ``[H_kv * D]`` or one latent row: any trailing dims)
    at ``[layer, b, pos[b] : pos[b] + S_new]``, in place where
    the stack is a loop's carry: the update is the new rows alone,
    never a layer. Rows in lockstep (scalar ``pos``) take one
    ``dynamic_update_slice``; rows at positions of their own take one
    each, unrolled: as ONE scatter the TPU compiler runs a loop over
    the rows that costs 2.8 us a row (a block of 8 decode steps at 16
    slots on a v5e: 97.2 ms against 81.6; PERF.md §6, PR 26). A start past
    ``max_len - S_new`` is clamped so that the rows fit. A RING is not
    written here: its write wraps and leaves pads out (:func:`_write_ring`),
    and only this file may address a slot of it by position."""
    rest = (0,) * (stack.ndim - 3)
    if jnp.ndim(pos) == 0:
        return lax.dynamic_update_slice(
            stack, new[None], (layer, 0, pos, *rest))
    for b in range(new.shape[0]):
        stack = lax.dynamic_update_slice(
            stack, new[None, b:b + 1], (layer, b, pos[b], *rest))
    return stack


def _heads_attend(q, k_rows, v_rows, mask, n_rep, dt):
    """``q [B, S, H, D]`` over ``k_rows``, ``v_rows`` ``[B, H_kv, K, D]``
    (key/value heads BEFORE the keys: the layout of a tree with rings,
    ``init_cache``) under ``mask`` (broadcast to ``[B, G, n_rep, S, K]``):
    :func:`_layer_attend`'s grouped-head softmax attention, the cache read
    unexpanded."""
    B, S_new, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, S_new, k_rows.shape[1], n_rep, D)
    with jax.named_scope("kv_read"):
        logits = jnp.einsum("bqgrd,bgkd->bgrqk", qg, k_rows).astype(
            jnp.float32) * scale
    probs = jax.nn.softmax(jnp.where(mask, logits, -1e30), axis=-1).astype(dt)
    with jax.named_scope("kv_read"):
        o = jnp.einsum("bgrqk,bgkd->bqgrd", probs, v_rows)
    return o.reshape(B, S_new, H, D)


def _ring_positions(last, ring: int):
    """The absolute position each slot of a ring holds once its row has
    been written up to position ``last`` (``[B]``; -1: nothing yet):
    ``[B, ring]``, the largest ``p <= last`` with ``p % ring == slot``;
    negative where the row has not reached the slot. The mask of a ring
    is made of THESE, never of the slot index."""
    last = last[:, None]
    return last - (last - jnp.arange(ring)[None]) % ring


def _write_heads_major(stack, new, layer, at):
    """Write ``new`` [B, S_new, H_kv, D] into ``stack`` [L, B, H_kv, len,
    D] at ``[layer, b, :, at[b] : at[b] + S_new]`` (``at`` a scalar: rows
    in lockstep, one update; ``[B]``: one a row, unrolled, as
    :func:`_write_rows` and for its reason)."""
    new = jnp.swapaxes(new, 1, 2)[None]
    if jnp.ndim(at) == 0:
        return lax.dynamic_update_slice(stack, new, (layer, 0, 0, at, 0))
    for b in range(new.shape[1]):
        stack = lax.dynamic_update_slice(
            stack, new[:, b:b + 1], (layer, b, 0, at[b], 0))
    return stack


def _write_ring(stack, new, layer, pos_b, real_b):
    """Write the REAL ones of ``new`` [B, S_new, H_kv, D] into the ring
    ``stack`` [L, B, H_kv, ring, D]: token ``t`` of row ``b`` into slot
    ``(pos_b[b] + t) % ring``, wrapping. One token a row is one
    ``dynamic_update_slice`` a row, real or not: a token that is not
    real lands on the slot of position ``pos - ring``, which neither the
    row's next query (at ``pos``) nor a later one sees, and the next real
    token overwrites it. A wider call (a prefill chunk: few rows) rewrites
    each row's ring whole, a slot taking the real token that maps to it
    (of a call wider than the ring, the last such) and keeping its key
    where none does: a pad is never written, for it would lie over a key
    that the row's next queries still see."""
    ring, S = stack.shape[3], new.shape[1]
    if S == 1:
        return _write_heads_major(stack, new, layer, pos_b % ring)
    # the window of the call that a ring can hold: all of it, padded to
    # the ring's length, or its last `ring` real tokens
    slots = jnp.arange(ring)
    for b in range(new.shape[0]):
        if S <= ring:
            start = 0
            mine = jnp.pad(new[b], ((0, ring - S), (0, 0), (0, 0)))
        else:
            start = jnp.clip(real_b[b] - ring, 0, S - ring)
            mine = lax.dynamic_slice_in_dim(new[b], start, ring, axis=0)
        # slot j takes token `t`: rolled, not gathered
        shift = (pos_b[b] + start) % ring
        t = start + (slots - shift) % ring
        old = lax.dynamic_slice(
            stack, (layer, b, 0, 0, 0), (1, 1, *stack.shape[2:]))
        mine = jnp.swapaxes(jnp.roll(mine, shift, axis=0), 0, 1)
        stack = lax.dynamic_update_slice(
            stack, jnp.where((t >= real_b[b])[None, None, None, :, None],
                             old, mine[None, None]),
            (layer, b, 0, 0, 0))
    return stack


def _ring_attend(q, k, v, k_stack, v_stack, layer, pos_b, real_b, window,
                 n_rep, dt):
    """A WINDOWED layer's attention and the write of its new rows into
    the layer's rings: query ``i`` (absolute position) sees key ``j`` iff
    ``0 <= i - j < window``. One new token a row (a decode step) is
    written first and attends over the ring, which then holds exactly
    the ``window`` keys it may see. A wider call's first query still
    needs the ``window - 1`` keys before it while its last keys would
    overwrite them, so it attends over the ring AS IT STOOD beside its
    own new keys, and writes after. Either way the mask is made of each
    slot's absolute position (:func:`_ring_positions`)."""
    S = q.shape[1]
    ring = k_stack.shape[3]
    q_pos = pos_b[:, None] + jnp.arange(S)[None]            # [B, S]

    def seen(k_pos):
        back = q_pos[:, :, None] - k_pos[:, None, :]
        return ((k_pos >= 0)[:, None, :] & (back >= 0)
                & (back < window))[:, None, None]

    def rows(stack):
        return lax.dynamic_index_in_dim(stack, layer, keepdims=False)

    def write(k_stack, v_stack):
        with jax.named_scope("kv_write"):
            return (_write_ring(k_stack, k.astype(dt), layer, pos_b, real_b),
                    _write_ring(v_stack, v.astype(dt), layer, pos_b, real_b))

    if S == 1:
        k_stack, v_stack = write(k_stack, v_stack)
        o = _heads_attend(q, rows(k_stack), rows(v_stack),
                          seen(_ring_positions(pos_b, ring)), n_rep, dt)
        return o, k_stack, v_stack
    mask = jnp.concatenate(
        [seen(_ring_positions(pos_b - 1, ring)), seen(q_pos)], axis=-1)
    o = _heads_attend(
        q, jnp.concatenate(
            [rows(k_stack), jnp.swapaxes(k.astype(dt), 1, 2)], axis=2),
        jnp.concatenate(
            [rows(v_stack), jnp.swapaxes(v.astype(dt), 1, 2)], axis=2),
        mask, n_rep, dt)
    return (o, *write(k_stack, v_stack))


def weights_at_rest(params: Params, cfg: TransformerConfig) -> Params:
    """``params`` as a holder that calls `forward_cached` many times
    keeps them (``serving/engine.py``): the `PRODUCT_LEAVES`, wherever
    they sit in the tree, in ``cfg.dtype``, so that no call converts
    them again; every other leaf as it is. The same function, to the
    bit: rounding a weight once gives what rounding it a call gives.

    What decides is the leaf's dtype, which the code can see. A leaf
    already in ``cfg.dtype`` comes back as the SAME array, so a tree
    that rests there (``param_dtype``) is not held twice; the others
    are converted one at a time, outside any ``jit`` (which would hand
    back a copy of what it only passes through), and the tree returned
    holds no float32 leaf of the list: what was handed in lives as long
    as its owner keeps it."""
    dt = jnp.dtype(cfg.dtype)

    def rest(path, leaf):
        name = getattr(path[-1], "key", None)
        if name not in PRODUCT_LEAVES or leaf.dtype == dt:
            return leaf
        return jnp.asarray(leaf, dt)

    return jax.tree_util.tree_map_with_path(rest, params)


def forward_cached(
    params: Params, tokens: jax.Array, cache: dict,
    cfg: TransformerConfig, real: jax.Array | None = None,
) -> tuple[jax.Array, dict]:
    """Run S_new tokens starting at cache['pos'].

    tokens: [B, S_new] -> (logits [B, S_new, vocab], updated cache).
    Used with S_new=P for prefill and S_new=1 for decode steps; both
    compile once each (static shapes). ``cache['pos']`` may be a scalar
    (all rows in lockstep — generate()) or a [B] vector (independent
    per-row positions — the continuous-batching serving engine).

    A block-diffusion model (``cfg.generation``) attends block-causally
    (:func:`_layer_attend`), so a call's rows see each other inside a
    block. Its DENOISING pass is this call with ``pos`` put back by the
    caller: the block's rows are written (the call's queries read them)
    and the next pass, and last the storing pass from the final tokens,
    write them again; only the storing pass keeps the advance.

    ``real`` (a scalar, or ``[B]``; None: all of them): how many of each
    row's ``S_new`` tokens are real, the first ones. A caller that holds
    a row back by putting its ``pos`` back says so here too: rows lie
    under the next write at that position, but a model that keeps STATE
    (:func:`cache_state`) has folded in whatever it was fed. Such a model
    takes in the real tokens alone; one that keeps rows alone does not
    read ``real`` at all.
    """
    c = cfg
    if c.mixers:
        from dlrover_tpu.models import hybrid

        return hybrid.forward(params, tokens, c, cache, real=real)
    if c.new_kinds:
        # one definition of those kinds' block, cached or not
        from dlrover_tpu.models import latent

        return latent.forward(params, tokens, c, cache)
    if c.int8_matmuls:
        # the cached products are plain whatever training ran
        # (ROADMAP D12)
        c = dataclasses.replace(c, int8_matmuls=False)
    if c.layer_kinds:
        return _forward_runs(params, tokens, cache, c, real)
    dt = jnp.dtype(c.dtype)
    B, S_new = tokens.shape
    pos = cache["pos"]
    n_rep = c.n_heads // c.n_kv_heads
    # the window only binds when training actually used it (the splash
    # kind): other attention kinds ignore attention_window in training,
    # so decode must too or the masks diverge
    window = c.attention_window if c.attention == "splash" else 0
    block = c.block_length if c.generation == "block_diffusion" else 0

    max_len = cache["k"].shape[2]
    # one behind the last key each query sees (`_layer_attend`'s masks)
    q_pos = (jnp.broadcast_to(pos, (B,)).astype(jnp.int32)[:, None]
             + jnp.arange(S_new, dtype=jnp.int32)[None])
    limits = (q_pos // block + 1) * block if block else q_pos + 1
    # the kernel takes a decode call of rows at positions of their own,
    # on a TPU, where the shapes allow it (`cached_attention.takes`: a
    # prefill chunk is too wide); everything else takes the einsum
    walk = None
    if jax.default_backend() == "tpu" and jnp.ndim(pos) == 1 and not window:
        # imported where it runs: Pallas costs a process 1.4 s to import
        from dlrover_tpu.ops import cached_attention

        if cached_attention.takes((B, S_new, c.n_heads, c.head_dim),
                                  cache["k"].shape, n_rep, dt.itemsize):
            # which key blocks of which rows: the same for every layer
            walk = cached_attention.walk(
                limits, cache["k"].shape, dt.itemsize, c.n_kv_heads, n_rep)

    def rows_of(new):
        return new.astype(dt).reshape(B, S_new, -1)

    def heads_of(stack, l):
        return lax.dynamic_index_in_dim(stack, l, keepdims=False).reshape(
            B, max_len, c.n_kv_heads, c.head_dim)

    def attend(q, k, v, state):
        k_stack, v_stack, l = state
        with jax.named_scope("kv_write"):
            k_stack = _write_rows(k_stack, rows_of(k), l, pos)
            v_stack = _write_rows(v_stack, rows_of(v), l, pos)
        if walk is not None:
            with jax.named_scope("kv_read"):
                o = cached_attention.cached_attention(
                    q, k_stack, v_stack, l, walk, n_rep=n_rep)
        else:
            o = _layer_attend(q, heads_of(k_stack, l), heads_of(v_stack, l),
                              pos, n_rep, dt, window=window, block=block)
        return o, (k_stack, v_stack, l)

    # the held experts' stacks are closed over the block and indexed in
    # place by its tile loop; everything else is scanned in
    experts, scanned = tfm.split_experts(params["layers"], c)
    run_layer = tfm.make_layer_fn(
        c, attend=attend,
        positions=tfm.token_positions(pos, B, S_new), experts=experts)

    def layer(carry, inputs):
        x, k_stack, v_stack = carry
        w, l = inputs
        x, aux, (k_stack, v_stack, _) = run_layer(
            x, w, (k_stack, v_stack, l), l)
        return (x, k_stack, v_stack), (aux if c.held_experts else None)

    # the stack rides the CARRY: a scanned input or output of the
    # per-layer shape would be sliced out and copied back whole, per
    # layer, for the sake of S_new new rows
    (x, k_new, v_new), loads = lax.scan(
        layer, (tfm.embed_tokens(params, tokens, c, pos=pos),
                cache["k"], cache["v"]),
        (scanned, jnp.arange(c.n_layers, dtype=jnp.int32)),
    )
    with jax.named_scope("lm_head"):
        logits = tfm.lm_logits(params, tfm.final_norm(params, x, c), c)
    new = {"k": k_new, "v": v_new, "pos": pos + S_new}
    if "counters" not in cache:     # a tree made by hand counts nothing
        return logits, new
    old = cache["counters"]
    reach = jnp.minimum(jnp.max(limits, axis=1), max_len)
    read = jnp.full((B,), max_len) if walk is None else walk.keys_read
    new["counters"] = {
        "attn_keys_read": old["attn_keys_read"]
        + jnp.sum(read).astype(jnp.int32),
        "context_tokens": old["context_tokens"] + jnp.sum(reach)}
    if c.held_experts:
        from dlrover_tpu.ops.moe import count_loads

        new["counters"].update(count_loads(old, loads))
    return logits, new


def _forward_runs(params, tokens, cache, cfg, real):
    """:func:`forward_cached` for a stack whose layers are of several
    kinds (``cfg.layer_windows`` / ``cfg.layer_rope``): one scan a run of
    equal layers (``transformer.layer_runs``), the full layers' rows and
    the windowed layers' rings each riding the carry of their own runs.
    A full layer attends over its row as ever (a wide call only as far
    as its last query reaches, ``latent.key_reaches``'s lengths; a decode
    step reads what its mask leaves), a windowed one
    through :func:`_ring_attend`. ``real``: the rings take in the real
    tokens alone (:func:`_write_ring`), and the counters count them."""
    c = cfg
    dt = jnp.dtype(c.dtype)
    B, S = tokens.shape
    pos = cache["pos"]
    pos_b = jnp.broadcast_to(pos, (B,)).astype(jnp.int32)
    real_b = jnp.full((B,), S, jnp.int32) if real is None else jnp.clip(
        jnp.broadcast_to(jnp.asarray(real).astype(jnp.int32), (B,)), 0, S)
    n_rep = c.n_heads // c.n_kv_heads
    window = max(c.layer_windows, default=0)
    from dlrover_tpu.models.latent import key_reaches

    keys = cache["k"].shape[3]
    reaches = [keys] if S == 1 else key_reaches(S, keys)
    reach = jnp.sum(jnp.asarray(reaches) < jnp.max(pos_b) + S)
    q_pos = pos_b[:, None] + jnp.arange(S)[None]            # [B, S]

    def attend_full(q, k, v, state):
        k_stack, v_stack, l = state
        with jax.named_scope("attn_full"):
            with jax.named_scope("kv_write"):
                k_stack = _write_heads_major(k_stack, k.astype(dt), l, pos)
                v_stack = _write_heads_major(v_stack, v.astype(dt), l, pos)
            k_rows = lax.dynamic_index_in_dim(k_stack, l, keepdims=False)
            v_rows = lax.dynamic_index_in_dim(v_stack, l, keepdims=False)
            o = lax.switch(reach, [
                lambda q, k_rows, v_rows, n=n: _heads_attend(
                    q, k_rows[:, :, :n], v_rows[:, :, :n],
                    (q_pos[:, :, None] >= jnp.arange(n)[None, None]
                     )[:, None, None], n_rep, dt)
                for n in reaches], q, k_rows, v_rows)
        return o, (k_stack, v_stack, l)

    def attend_window(q, k, v, state):
        k_stack, v_stack, l = state
        with jax.named_scope("attn_window"):
            o, k_stack, v_stack = _ring_attend(
                q, k, v, k_stack, v_stack, l, pos_b, real_b, window,
                n_rep, dt)
        return o, (k_stack, v_stack, l)

    experts, layers = tfm.split_experts(params["layers"], c)
    positions = tfm.token_positions(pos, B, S)
    rings = cache.get("state", {})
    held = {False: (cache["k"], cache["v"]),
            True: (rings.get("k_win"), rings.get("v_win"))}
    x = tfm.embed_tokens(params, tokens, c, pos=pos)
    loads = []
    for win, rope, first, first_of_kind, n in tfm.layer_runs(c):
        run_layer = tfm.make_layer_fn(
            c, attend=attend_window if win else attend_full,
            positions=positions, experts=experts, kind=(win, rope))

        def layer(carry, i, run_layer=run_layer,
                  offset=first_of_kind - first):
            x, k_stack, v_stack = carry
            w = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, i, keepdims=False),
                layers)
            x, aux, (k_stack, v_stack, _) = run_layer(
                x, w, (k_stack, v_stack, i + offset), i)
            return (x, k_stack, v_stack), (aux if c.held_experts else None)

        # rows and rings ride the CARRY (module docstring)
        (x, *held[win > 0]), aux = lax.scan(
            layer, (x, *held[win > 0]),
            jnp.arange(first, first + n, dtype=jnp.int32))
        loads.append(aux)
    with jax.named_scope("lm_head"):
        logits = tfm.lm_logits(params, tfm.final_norm(params, x, c), c)
    new = {"k": held[False][0], "v": held[False][1], "pos": pos + S}
    old, counters = cache.get("counters", {}), {}
    if c.held_experts:
        from dlrover_tpu.ops.moe import count_loads

        counters = count_loads(old, jnp.concatenate(loads))
    if not window:
        return logits, {**new, **({"counters": counters} if counters else {})}
    live = jnp.arange(S)[None] < real_b[:, None]
    for name, what in (
            ("row_steps", live), ("context_tokens", q_pos),
            ("window_keys", jnp.minimum(q_pos, window)),
            ("ring_wrapped_row_steps", q_pos >= window)):
        counters[name] = old[name] + jnp.sum(
            jnp.where(live, what, 0).astype(jnp.int32))
    return logits, {**new, "counters": counters, "state": {
        "k_win": held[True][0], "v_win": held[True][1]}}


@jax.named_scope("sample")
def sample_logits(
    logits: jax.Array, key: jax.Array,
    temperature: float | jax.Array = 1.0,
    top_k: int | jax.Array = 0,
    top_p: float | jax.Array = 1.0,
) -> jax.Array:
    """One sampling step over [B, V] logits: temperature, top-k, nucleus.

    The serving-side sampler surface (reference analog: the vLLM
    SamplingParams the RLHF backend passes through,
    atorch/atorch/rl/inference_backend/vllm_backend.py) as pure lax ops:
    static shapes, no data-dependent control flow, usable inside scan.

    Each parameter may be a python scalar (whole batch, generate()) or a
    [B] array (per-row, the continuous-batching engine) — one
    implementation for both, so the nucleus/greedy semantics can't
    drift between serving and rollout paths. Per-row temperature <= 0
    means greedy for that row.

    ``key`` may be one PRNG key (whole batch) or a [B, key_size] stack
    of per-row keys — per-request determinism: a row's draw then
    depends only on its own key, never on batch composition.
    """
    B, V = logits.shape
    static = all(isinstance(p, (int, float))
                 for p in (temperature, top_k, top_p))
    if static and temperature <= 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    temp = jnp.broadcast_to(
        jnp.asarray(temperature, jnp.float32), (B,))
    k_vec = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (B,))
    p_vec = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (B,))

    need_sort = (not static) or (0 < top_k < V) or top_p < 1.0
    if need_sort:
        sorted_l = jnp.sort(logits, axis=-1)[:, ::-1]
        # top-k: survival threshold is the value at rank k-1; k <= 0 or
        # k >= V disables the filter for that row
        k_idx = jnp.clip(k_vec - 1, 0, V - 1)
        kth = jnp.take_along_axis(sorted_l, k_idx[:, None], axis=-1)
        k_on = ((k_vec > 0) & (k_vec < V))[:, None]
        logits = jnp.where(k_on & (logits < kth), -jnp.inf, logits)
        # nucleus: keep the smallest prefix of the (top-k-filtered)
        # distribution whose mass reaches top_p; the top-1 always
        # survives (cum - prob = 0 < top_p). Masking below-kth entries
        # preserves descending order, so the filtered sorted view
        # derives from the first sort instead of a second O(V log V)
        # pass (this runs inside the serving decode scan's hot path).
        sorted_m = jnp.where(k_on & (sorted_l < kth), -jnp.inf,
                             sorted_l)
        probs = jax.nn.softmax(sorted_m, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = cum - probs < p_vec[:, None]
        cutoff = jnp.min(
            jnp.where(keep, sorted_m, jnp.inf), axis=-1, keepdims=True,
        )
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)

    scaled = logits / jnp.maximum(temp, 1e-6)[:, None]
    if key.ndim == 2:  # per-row keys
        sampled = jax.vmap(
            lambda k, row: jax.random.categorical(k, row)
        )(key, scaled)
    else:
        sampled = jax.random.categorical(key, scaled, axis=-1)
    greedy = jnp.argmax(logits, axis=-1)
    return jnp.where(temp <= 0, greedy, sampled).astype(jnp.int32)


def generate(
    params: Params, prompts: jax.Array, cfg: TransformerConfig,
    gen_len: int, key: jax.Array, temperature: float = 1.0,
    max_len: int | None = None, top_k: int = 0, top_p: float = 1.0,
    eos_id: int | None = None,
) -> jax.Array:
    """Sample continuations with a KV cache: [B, P] -> [B, P+gen_len].

    O(P + gen_len) attention reads per generated token instead of the
    O((P+gen_len)^2) full-forward recompute. ``eos_id`` pads a finished
    row with eos for the rest of the (static-shape) scan.
    """
    B, P = prompts.shape
    total = P + gen_len
    if cfg.variant == "gpt2" and total > cfg.max_seq_len:
        # learned positions end at max_seq_len; the dynamic slice would
        # silently clamp and reuse the last embedding row
        raise ValueError(
            f"prompt {P} + gen_len {gen_len} exceeds the gpt2 model's "
            f"max_seq_len {cfg.max_seq_len}"
        )
    max_len = max_len or total
    if max_len < total:
        # an undersized cache would clamp dynamic_update_slice and
        # silently decode against overwritten rows
        raise ValueError(
            f"max_len {max_len} < prompt {P} + gen_len {gen_len}"
        )
    cache = init_cache(cfg, B, max_len)
    logits, cache = forward_cached(params, prompts, cache, cfg)
    last = logits[:, -1]
    done0 = jnp.zeros((B,), bool)

    def step(carry, key):
        cache, last, done = carry
        nxt = sample_logits(last, key, temperature, top_k, top_p)
        if eos_id is not None:
            nxt = jnp.where(done, jnp.int32(eos_id), nxt)
            done = done | (nxt == eos_id)
        logits, cache = forward_cached(
            params, nxt[:, None], cache, cfg
        )
        return (cache, logits[:, -1], done), nxt

    keys = jax.random.split(key, gen_len)
    (_, _, _), toks = lax.scan(step, (cache, last, done0), keys)
    return jnp.concatenate(
        [prompts, jnp.moveaxis(toks, 0, 1)], axis=1
    )
