"""The cached decode attention kernel (``ops/cached_attention.py``) off the
TPU, in interpret mode, against the einsum it takes the place of
(``cache.layer_attend``, which stays the path off the TPU and the
oracle here, as ``held_expert_loop`` is the grouped kernel's).

What interpret mode shows: the walk over (row, key block) pairs, the
block-diagonal query matrix and its fold, the masks, the running
softmax. What it cannot (block shapes Mosaic refuses, a stack copied
instead of read in place) is ``tests/test_tpu_compile.py``'s.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dlrover_tpu.models import transformer as tfm
from dlrover_tpu.models.cache import layer_attend
from dlrover_tpu.models.decode import forward_cached, init_cache
from dlrover_tpu.ops import cached_attention as ca

GPT2 = dict(G=16, n_rep=1, D=64)        # gpt2-medium: 16 heads of 64
SDAR = dict(G=4, n_rep=8, D=128)        # SDAR: 32 query heads on 4 of 128

# name -> heads, queries a row, the block mask's length (0: causal), each
# row's position, whether every key past a row's limit is NaN. max_len is
# 1024, so a key block is 256 (gpt2) or 512 (SDAR) keys.
CASES = {
    # a fresh row reads one block and sees one key
    "a_row_at_position_0": (GPT2, 1, 0, [0, 300], False),
    # the new key is a block's last: two whole blocks, no masked one
    "a_row_ending_on_a_block_edge": (GPT2, 1, 0, [511, 255, 256], False),
    # the row's last position: every block, nothing masked
    "a_row_at_max_len": (GPT2, 1, 0, [1023, 17], False),
    # a slot nobody holds keeps the position it was left at, which may
    # lie past the row's end: it reads like any row and harms no other
    "an_idle_row_with_a_stale_pos": (GPT2, 1, 0, [1024, 1030, 77], False),
    "gpt2_heads_unequal_rows": (GPT2, 1, 0, [40, 700, 3, 512, 999], False),
    # a block-diffusion pass: 4 queries that see to their block's end
    "sdar_heads_under_the_block_mask": (SDAR, 4, 4, [0, 508, 1020, 64],
                                        False),
    # a verify block: query i sees one key more than query i - 1, and
    # the first queries stop inside the block before the last's
    "a_verify_blocks_causal_queries": (GPT2, 5, 0, [0, 254, 509, 1019],
                                       False),
    "grouped_heads_causal_queries": (SDAR, 3, 0, [5, 511, 600], False),
    "keys_past_the_limit_are_nan": (GPT2, 1, 0, [0, 255, 300, 1023], True),
    "keys_past_a_blocks_end_are_nan": (SDAR, 4, 4, [0, 508, 700], True),
}


def _limits(pos, S, block):
    q_pos = pos[:, None] + jnp.arange(S)[None]
    return (q_pos // block + 1) * block if block else q_pos + 1


@pytest.mark.parametrize("case", CASES)
def test_kernel_reads_each_row_as_far_as_it_reaches(case):
    heads, S, block, pos, poison = CASES[case]
    G, n_rep, D = heads["G"], heads["n_rep"], heads["D"]
    B, L, max_len, layer, dt = len(pos), 2, 1024, 1, jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    q = jax.random.normal(keys[0], (B, S, G * n_rep, D)).astype(dt)
    k = jax.random.normal(keys[1], (L, B, max_len, G * D)).astype(dt)
    v = jax.random.normal(keys[2], (L, B, max_len, G * D)).astype(dt)
    pos = jnp.asarray(pos, jnp.int32)
    limits = _limits(pos, S, block)
    assert ca.takes(q.shape, k.shape, n_rep, 2)

    want = layer_attend(
        q, k[layer].reshape(B, max_len, G, D),
        v[layer].reshape(B, max_len, G, D), pos, n_rep, dt, block=block)
    if poison:
        dead = (jnp.arange(max_len)[None, :, None]
                >= jnp.max(limits, axis=1)[:, None, None])
        k = k.at[layer].set(jnp.where(dead, jnp.nan, k[layer]))
        v = v.at[layer].set(jnp.where(dead, jnp.nan, v[layer]))
        # the layers the call does not name hold nothing it may read
        k, v = k.at[0].set(jnp.nan), v.at[0].set(jnp.nan)
    rows = ca.walk(limits, k.shape, 2, G, n_rep)
    got = ca.cached_attention(q, k, v, layer, rows, n_rep=n_rep,
                              interpret=True)
    assert got.shape == want.shape and got.dtype == dt
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    # bfloat16 outputs of order 1: a few units in the last place (the
    # einsum rounds its scores to bfloat16, the kernel keeps float32)
    np.testing.assert_allclose(got, np.asarray(want, np.float32), atol=0.03)

    # what it fetched: whole blocks up to the row's limit, never more
    tk = ca.key_block(max_len, G * D, 2)
    read = np.asarray(rows.keys_read)
    reach = np.minimum(np.asarray(jnp.max(limits, axis=1)), max_len)
    assert (read >= reach).all() and (read < reach + tk).all()
    assert (read % tk == 0).all()


def test_the_grid_walks_the_live_blocks_alone():
    # blocks of 256 keys in rows of 1024: 1, 3, 1 and 2 of them live
    limits = jnp.asarray([[1], [513], [256], [300]], jnp.int32)
    rows = ca.walk(limits, (2, 4, 1024, 1024), 2, 16, 1)
    assert np.asarray(rows.blocks).tolist() == [1, 3, 1, 2]
    assert int(rows.total) == 7
    assert np.asarray(rows.row)[:7].tolist() == [0, 1, 1, 1, 2, 3, 3]
    assert np.asarray(rows.block)[:7].tolist() == [0, 0, 1, 2, 0, 0, 1]
    # the entries behind them repeat the last
    assert set(np.asarray(rows.row)[7:].tolist()) == {3}
    assert set(np.asarray(rows.block)[7:].tolist()) == {1}
    assert np.asarray(rows.keys_read).tolist() == [256, 768, 256, 512]


@pytest.mark.parametrize("shape, fits", [
    # gpt2-medium's decode step, its verify block, its prefill chunk
    (((16, 1, 16, 64), (24, 16, 1024, 1024), 1), True),
    (((16, 8, 16, 64), (24, 16, 1024, 1024), 1), True),
    (((1, 64, 16, 64), (24, 1, 1024, 1024), 1), False),
    # SDAR's pass of a block, its chunk
    (((16, 4, 32, 128), (6, 16, 2560, 512), 8), True),
    (((1, 512, 32, 128), (6, 1, 2560, 512), 8), False),
    # the tests' tiny models: lanes short of a tile, rows short of a block
    (((2, 1, 4, 16), (2, 2, 64, 64), 1), False),
    (((2, 1, 2, 64), (2, 2, 64, 128), 1), False),
])
def test_which_calls_the_kernel_takes(shape, fits):
    q_shape, stack_shape, n_rep = shape
    assert ca.takes(q_shape, stack_shape, n_rep, 2) is fits


@pytest.mark.parametrize("kind", ["gpt2", "block_diffusion"])
def test_forward_cached_through_the_kernel(monkeypatch, kind):
    """``forward_cached`` traced as on a TPU (the backend is what it
    asks) with the kernel interpreted: a decode call's logits agree with
    the einsum's, the stacks come back the same, and the counters say
    how much of the rows each path fetched."""
    from jax.experimental.pallas import tpu as pltpu

    extra = {"gpt2": dict(variant="gpt2", n_heads=2), "block_diffusion": dict(
        n_heads=4, generation="block_diffusion", block_length=4,
        denoising_steps=4, mask_token_id=255)}[kind]
    cfg = dataclasses.replace(
        tfm.CONFIGS["tiny"], n_layers=2, d_model=128, n_kv_heads=2,
        head_dim=64, max_seq_len=256, dtype="float32", **extra)
    S = 4 if kind == "block_diffusion" else 1
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    B, max_len = 3, 256
    pos = jnp.asarray([0, 124, 200], jnp.int32)
    cache = init_cache(cfg, B, max_len)
    filled = jax.random.normal(jax.random.PRNGKey(1), cache["k"].shape)
    cache = {**cache, "k": filled, "v": filled[::-1], "pos": pos}
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, 250)

    want, plain = forward_cached(params, tokens, cache, cfg)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # at these widths the rule would take a row in one block
    monkeypatch.setattr(ca, "BLOCK_BYTES", 128 * 128 * 4)
    with pltpu.force_tpu_interpret_mode():
        got, through = forward_cached(params, tokens, cache, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)
    for name in ("k", "v"):
        np.testing.assert_allclose(np.asarray(through[name]),
                                   np.asarray(plain[name]), atol=1e-5)
    reach = int((pos + S).sum())
    assert int(plain["counters"]["context_tokens"]) == reach
    assert int(through["counters"]["context_tokens"]) == reach
    assert int(plain["counters"]["attn_keys_read"]) == B * max_len
    # blocks of 128 keys: 1 + 1 + 2 of them
    assert int(through["counters"]["attn_keys_read"]) == 4 * 128
