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
the einsum ``cache.layer_attend``, which is also the kernel's oracle. A
WINDOWED layer's rows (``cfg.layer_windows``) are a RING of ``window``
slots (:func:`init_ring_cache`), STATE to everyone outside
``models/cache.py``: no one else may index it by position. A stack is never
copied whole (§23.1): the layer loop CARRIES it, layer ``l`` writes its
``S_new`` new rows into it in place (the update is the new rows alone)
and attends over ``stack[l]`` read out of the carry. Nothing of a
layer's shape is scanned in or out: a scanned input is sliced out
whole and a scanned output copied back whole, per layer. A caller that
wants the update in place donates the stack to the jitted program that
calls ``forward_cached`` and keeps no other reference to it
(``serving/engine.py`` does).

The block, the embedding and the head are training's own
(``models/transformer.py``: ``make_layer_fn``, ``embed_tokens``,
``final_norm``, ``lm_logits``), the loop over the stack's runs every
family's (``transformer.scan_runs``). This file is the ENTRY, which asks
``transformer.family`` once, and the family of training's block (rows, and
rings beside rows): its cache tree and the ``attend`` hooks that write a
layer's new rows and read its cache. The equivalence test
(tests/test_decode.py) pins those: prefill+cached-decode logits must
match ``forward`` on the same tokens to tolerance.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from dlrover_tpu.models import transformer as tfm
from dlrover_tpu.models.cache import (  # noqa: F401  (the views are callers')
    attend_heads_major, cache_counter_fields, cache_stacks, cache_state,
    key_reaches, layer_attend, ring_attend, write_heads_major, write_rows,
    zero_counters)
from dlrover_tpu.models.transformer import PRODUCT_LEAVES, TransformerConfig
from dlrover_tpu.ops.moe import count_loads, held_counters

Params = Any


def init_cache(cfg: TransformerConfig, batch: int, max_len: int) -> dict:
    """The model's cache TREE (DESIGN.md §23.5): ``pos``, rows laid
    out ``[L, B, len, ...]`` under names of the family's choosing,
    optionally ``state`` (stacks ``[L, B, ...]`` with no position axis)
    and ``counters``. Callers carry it whole and name none of its
    stacks: :func:`cache_stacks`, :func:`cache_state`."""
    return tfm.family(cfg).init_cache(cfg, batch, max_len)


def _kv_cache(cfg, shape: tuple, counted: tuple) -> dict:
    """``k`` and ``v`` of ``shape``, the position, the counters ``counted``
    beside the held experts' (none: a tree that counts nothing)."""
    c = cfg
    counters = {name: jnp.zeros((), jnp.int32) for name in counted}
    if c.held_experts:
        counters = {**counters, **held_counters(
            c.n_layers - c.dense_layers, tfm.routed_config(c).n_held)}
    cache = {"k": jnp.zeros(shape, jnp.dtype(c.dtype)),
             "v": jnp.zeros(shape, jnp.dtype(c.dtype)),
             "pos": jnp.zeros((), jnp.int32)}
    return {**cache, "counters": counters} if counters else cache


def init_row_cache(cfg: TransformerConfig, batch: int, max_len: int) -> dict:
    """Layers all of one kind: ``k`` and ``v`` ``[L, B, max_len, H_kv *
    D]``, a key's heads side by side on the last axis (module docstring),
    and `READ_COUNTERS` beside the expert layers'. Only :func:`forward_rows`
    and ``ops/cached_attention.py`` read a row's trailing dim as heads."""
    c = cfg
    rows = (c.n_layers, batch, max_len, c.n_kv_heads * c.head_dim)
    return _kv_cache(c, rows, READ_COUNTERS)


def init_ring_cache(cfg: TransformerConfig, batch: int, max_len: int) -> dict:
    """``cfg.layer_windows`` / ``cfg.layer_rope``: the full layers' rows are
    ``k``, ``v`` ``[L_full, B, H_kv, max_len, D]``; the windowed layers'
    are RINGS under ``state``, ``k_win``, ``v_win`` ``[L_win, B, H_kv,
    window, D]``, whose length follows from the window and never from
    ``max_len``. The key/value heads lie BEFORE the positions in both:
    the layout the grouped-head products read a row in (with positions
    first the TPU compiler copies all the stacks into this layout at the
    start of every decode call and back at its end: 2.9 GB of temporaries
    for 24 slots of 16384, PERF.md section 6, PR 41); such a tree has
    state, so nothing outside ``models/`` addresses a position of it. A
    ring holds the last ``window`` keys of its row, position ``p`` in
    slot ``p % window``; which position a slot holds
    follows from the row's ``pos`` alone (``cache.ring_positions``), so a
    ring is valid only together with the ``pos`` it was left at: a
    holder that copies a row copies its rings whole, as it does state."""
    c = cfg
    n_win = sum(1 for w in c.layer_windows if w)
    cache = _kv_cache(
        c, (c.n_layers - n_win, batch, c.n_kv_heads, max_len, c.head_dim),
        WINDOW_COUNTERS if n_win else ())
    if n_win:
        ring = (n_win, batch, c.n_kv_heads, max(c.layer_windows), c.head_dim)
        cache["state"] = {"k_win": jnp.zeros(ring, jnp.dtype(c.dtype)),
                          "v_win": jnp.zeros(ring, jnp.dtype(c.dtype))}
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
# windowed layer reads of it), how many lay past the window (the ring
# had wrapped), and how many no ring slot keeps because their call was
# wider than the ring (`cache.write_ring` keeps a wide call's last
# `window` real tokens: 0 wherever a chunk fits the ring)
WINDOW_COUNTERS = ("row_steps", "context_tokens", "window_keys",
                   "ring_wrapped_row_steps", "ring_chunk_tokens_dropped")


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
    (``cache.layer_attend``), so a call's rows see each other inside a
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
    return tfm.family(cfg).forward_cached(params, tokens, cache, cfg, real)


def _through_blocks(params, tokens, cache, cfg, attends: dict, held: dict):
    """Both of this file's cached forwards: the embedding, the runs
    through training's block (``attends[kind]`` a run's hook, ``held[kind]``
    the ``(k, v)`` stacks its kind's layers carry) and the head: ``(logits,
    held, the expert layers' loads [expert layers, held] or None)``."""
    c = cfg
    if c.int8_matmuls:
        # the cached products are plain whatever training ran (D12)
        c = dataclasses.replace(c, int8_matmuls=False)
    B, S = tokens.shape
    pos = cache["pos"]
    positions = tfm.token_positions(pos, B, S)
    runs = tfm.stack_runs(c)
    # the held experts' stacks are closed over the block and indexed in
    # place by its tile loop; everything else is read a layer at a time,
    # out of the tree the run's layers lie in
    split = {key: tfm.split_experts(params[key], c)
             for key in {run.key for run in runs}}

    def layer_of(run):
        experts, layers = split[run.key]
        block = tfm.make_layer_fn(
            c, attend=attends[run.kind], positions=positions,
            experts=experts, kind=tfm.layer_kind(c, run.first),
            dense=run.key == "dense_layers")
        # `i` counts the layers of the run's tree; a layer's rows lie at
        # its index among the layers of its kind
        in_tree = sum(r.n for r in runs[:runs.index(run)] if r.key == run.key)

        def layer(x, rows, w, i):
            x, aux, (*rows, _) = block(
                x, w, (*rows, i + (run.first_of_kind - in_tree)), i)
            return x, tuple(rows), (aux if experts is not None else None)

        return layers, layer

    x, held, loads = tfm.scan_runs(
        runs, tfm.embed_tokens(params, tokens, c, pos=pos), held, layer_of)
    with jax.named_scope("lm_head"):
        logits = tfm.lm_logits(params, tfm.final_norm(params, x, c), c)
    loads = [mine for mine in loads if mine is not None]
    return logits, held, jnp.concatenate(loads) if loads else None


def forward_rows(params, tokens, cache, cfg, real=None):
    """:func:`forward_cached` for layers all of one kind
    (:func:`init_row_cache`), through the kernel (a decode call on a TPU)
    or the einsum. ``real`` is not read: the tree is rows alone."""
    c = cfg
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
    # one behind the last key each query sees (`layer_attend`'s masks)
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
            k_stack = write_rows(k_stack, rows_of(k), l, pos)
            v_stack = write_rows(v_stack, rows_of(v), l, pos)
        if walk is not None:
            with jax.named_scope("kv_read"):
                o = cached_attention.cached_attention(
                    q, k_stack, v_stack, l, walk, n_rep=n_rep)
        else:
            o = layer_attend(q, heads_of(k_stack, l), heads_of(v_stack, l),
                              pos, n_rep, dt, window=window, block=block)
        return o, (k_stack, v_stack, l)


    logits, held, loads = _through_blocks(
        params, tokens, cache, c, {"full": attend},
        {"full": (cache["k"], cache["v"])})
    k_new, v_new = held["full"]
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
        new["counters"].update(count_loads(old, loads))
    return logits, new


def forward_rings(params, tokens, cache, cfg, real=None):
    """:func:`forward_cached` for a stack whose layers are of several
    kinds (:func:`init_ring_cache`): the full layers' rows and the
    windowed layers' rings each ride the carry of their own runs.
    A full layer attends over its row as ever (a wide call only as far
    as its last query reaches, ``cache.key_reaches``'s lengths; a decode
    step reads what its mask leaves), a windowed one
    through ``cache.ring_attend``. ``real``: the rings take in the real
    tokens alone (``cache.write_ring``), and the counters count them."""
    c = cfg
    dt = jnp.dtype(c.dtype)
    B, S = tokens.shape
    pos = cache["pos"]
    pos_b = jnp.broadcast_to(pos, (B,)).astype(jnp.int32)
    real_b = jnp.full((B,), S, jnp.int32) if real is None else jnp.clip(
        jnp.broadcast_to(jnp.asarray(real).astype(jnp.int32), (B,)), 0, S)
    n_rep = c.n_heads // c.n_kv_heads
    window = max(c.layer_windows, default=0)
    keys = cache["k"].shape[3]
    reaches = [keys] if S == 1 else key_reaches(S, keys)
    reach = jnp.sum(jnp.asarray(reaches) < jnp.max(pos_b) + S)
    q_pos = pos_b[:, None] + jnp.arange(S)[None]            # [B, S]

    def attend_full(q, k, v, state):
        k_stack, v_stack, l = state
        with jax.named_scope("attn_full"):
            with jax.named_scope("kv_write"):
                k_stack = write_heads_major(k_stack, k.astype(dt), l, pos)
                v_stack = write_heads_major(v_stack, v.astype(dt), l, pos)
            k_rows = lax.dynamic_index_in_dim(k_stack, l, keepdims=False)
            v_rows = lax.dynamic_index_in_dim(v_stack, l, keepdims=False)
            o = lax.switch(reach, [
                lambda q, k_rows, v_rows, n=n: attend_heads_major(
                    q, k_rows[:, :, :n], v_rows[:, :, :n],
                    (q_pos[:, :, None] >= jnp.arange(n)[None, None]
                     )[:, None, None], n_rep, dt)
                for n in reaches], q, k_rows, v_rows)
        return o, (k_stack, v_stack, l)

    def attend_window(q, k, v, state):
        k_stack, v_stack, l = state
        with jax.named_scope("attn_window"):
            o, k_stack, v_stack = ring_attend(
                q, k, v, k_stack, v_stack, l, pos_b, real_b, window,
                n_rep, dt)
        return o, (k_stack, v_stack, l)

    rings = cache.get("state", {})
    logits, held, loads = _through_blocks(
        params, tokens, cache, c,
        {"full": attend_full, "window": attend_window},
        {"full": (cache["k"], cache["v"]),
         "window": (rings.get("k_win"), rings.get("v_win"))})
    new = {"k": held["full"][0], "v": held["full"][1], "pos": pos + S}
    old, counters = cache.get("counters", {}), {}
    if c.held_experts:
        counters = count_loads(old, loads)
    if not window:
        return logits, {**new, **({"counters": counters} if counters else {})}
    live = jnp.arange(S)[None] < real_b[:, None]
    for name, what in (
            ("row_steps", live), ("context_tokens", q_pos),
            ("window_keys", jnp.minimum(q_pos, window)),
            ("ring_wrapped_row_steps", q_pos >= window),
            ("ring_chunk_tokens_dropped",
             jnp.arange(S)[None] < real_b[:, None] - window)):
        counters[name] = old[name] + jnp.sum(
            jnp.where(live, what, 0).astype(jnp.int32))
    return logits, {**new, "counters": counters, "state": {
        "k_win": held["window"][0], "v_win": held["window"][1]}}


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
