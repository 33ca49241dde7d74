"""What a cache tree is made of, below every family (DESIGN.md §23.5):
a caller's views of a tree, the writes of a call's new rows into a carried
stack in place, the reads of a layer's rows in each layout, a windowed
layer's rings, the lengths a wide call reads a row to. Nothing here knows
a family or a block: their ``attend`` hooks are built of these.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

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


def layer_attend(q, k_cache, v_cache, pos, n_rep, dt, window=0, block=0):
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


def write_rows(stack, new, layer, pos):
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
    written here: its write wraps and leaves pads out (:func:`write_ring`),
    and only this file may address a slot of it by position."""
    rest = (0,) * (stack.ndim - 3)
    if jnp.ndim(pos) == 0:
        return lax.dynamic_update_slice(
            stack, new[None], (layer, 0, pos, *rest))
    for b in range(new.shape[0]):
        stack = lax.dynamic_update_slice(
            stack, new[None, b:b + 1], (layer, b, pos[b], *rest))
    return stack


def attend_heads_major(q, k_rows, v_rows, mask, n_rep, dt):
    """``q [B, S, H, D]`` over ``k_rows``, ``v_rows`` ``[B, H_kv, K, D]``
    (key/value heads BEFORE the keys: the layout of a tree with rings,
    ``decode.init_ring_cache``) under ``mask`` (broadcast to ``[B, G, n_rep,
    S, K]``):
    :func:`layer_attend`'s grouped-head softmax attention, the cache read
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


def ring_positions(last, ring: int):
    """The absolute position each slot of a ring holds once its row has
    been written up to position ``last`` (``[B]``; -1: nothing yet):
    ``[B, ring]``, the largest ``p <= last`` with ``p % ring == slot``;
    negative where the row has not reached the slot. The mask of a ring
    is made of THESE, never of the slot index."""
    last = last[:, None]
    return last - (last - jnp.arange(ring)[None]) % ring


def write_heads_major(stack, new, layer, at):
    """Write ``new`` [B, S_new, H_kv, D] into ``stack`` [L, B, H_kv, len,
    D] at ``[layer, b, :, at[b] : at[b] + S_new]`` (``at`` a scalar: rows
    in lockstep, one update; ``[B]``: one a row, unrolled, as
    :func:`write_rows` and for its reason)."""
    new = jnp.swapaxes(new, 1, 2)[None]
    if jnp.ndim(at) == 0:
        return lax.dynamic_update_slice(stack, new, (layer, 0, 0, at, 0))
    for b in range(new.shape[1]):
        stack = lax.dynamic_update_slice(
            stack, new[:, b:b + 1], (layer, b, 0, at[b], 0))
    return stack


def write_ring(stack, new, layer, pos_b, real_b):
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
        return write_heads_major(stack, new, layer, pos_b % ring)
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


def ring_attend(q, k, v, k_stack, v_stack, layer, pos_b, real_b, window,
                 n_rep, dt):
    """A WINDOWED layer's attention and the write of its new rows into
    the layer's rings: query ``i`` (absolute position) sees key ``j`` iff
    ``0 <= i - j < window``. One new token a row (a decode step) is
    written first and attends over the ring, which then holds exactly
    the ``window`` keys it may see. A wider call's first query still
    needs the ``window - 1`` keys before it while its last keys would
    overwrite them, so it attends over the ring AS IT STOOD beside its
    own new keys, and writes after. Either way the mask is made of each
    slot's absolute position (:func:`ring_positions`)."""
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
            return (write_ring(k_stack, k.astype(dt), layer, pos_b, real_b),
                    write_ring(v_stack, v.astype(dt), layer, pos_b, real_b))

    if S == 1:
        k_stack, v_stack = write(k_stack, v_stack)
        o = attend_heads_major(q, rows(k_stack), rows(v_stack),
                          seen(ring_positions(pos_b, ring)), n_rep, dt)
        return o, k_stack, v_stack
    mask = jnp.concatenate(
        [seen(ring_positions(pos_b - 1, ring)), seen(q_pos)], axis=-1)
    o = attend_heads_major(
        q, jnp.concatenate(
            [rows(k_stack), jnp.swapaxes(k.astype(dt), 1, 2)], axis=2),
        jnp.concatenate(
            [rows(v_stack), jnp.swapaxes(v.astype(dt), 1, 2)], axis=2),
        mask, n_rep, dt)
    return (o, *write(k_stack, v_stack))


def key_reaches(tokens: int, keys: int) -> list[int]:
    """The lengths a wide call of ``tokens`` new tokens a row may read a
    row of ``keys`` to: its own width doubled up to the row's length,
    which ends the list. One length where the row is the call (the
    uncached forward)."""
    reach = []
    while tokens < keys:
        reach.append(tokens)
        tokens *= 2
    return reach + [keys]


def reach_of(q_pos, tokens: int, keys: int):
    """``(lengths, which)``: :func:`key_reaches` and the index of the
    first that holds the last query of ``q_pos [B, S]`` (the keys a
    query sees end at its own position)."""
    reach = key_reaches(tokens, keys)
    return reach, jnp.sum(jnp.asarray(reach) < jnp.max(q_pos) + 1)
