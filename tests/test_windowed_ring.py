"""Layers of several kinds in the ONE block (``layer_windows`` /
``layer_rope``): windowed layers whose cache row is a RING beside full
layers without a rotary embedding, a router that reads the attention's
input, ReGLU experts (``models/transformer.py``, ``models/decode.py``,
``serving/engine.py``).

The oracle of the cached path is the uncached forward (``transformer.
forward``: the window an explicit mask over the whole sequence, no cache,
no ring, no chunks), float32 on both sides; the tolerance ``TOL`` is a few
float32 roundings of logits of size ~5 (the two paths sum a softmax over
other key sets in another order). The benchmark's plain reference is held
against the same forward in ``tests/benchmark_tests/test_smallthinker.py``.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dlrover_tpu.models import decode, transformer as tfm
from dlrover_tpu.models.cache import (
    layer_attend, ring_attend, ring_positions, write_ring)
from dlrover_tpu.ops import moe
from dlrover_tpu.serving.engine import InferenceEngine, SamplingParams

TOL = 5e-5
WINDOW = 8


def _cfg(window: int = WINDOW, **kw):
    base = tfm.CONFIGS["tiny-smallthinker"]
    return dataclasses.replace(
        base, layer_windows=tuple(window * bool(w)
                                  for w in base.layer_windows), **kw)


CFG = _cfg()
# jitted: run op by op, a file of these tests compiles tens of thousands of
# one-operation programs in one process
cached = jax.jit(decode.forward_cached, static_argnums=(3,))
forward = jax.jit(tfm.forward, static_argnums=(2,))


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(CFG, jax.random.PRNGKey(0))


def _tokens(seed: int, *shape):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                              CFG.vocab_size)


def greedy(n):
    return SamplingParams(temperature=0.0, max_new_tokens=n, eos_id=None)


# ------------------------------------------------------------ (a), (e)


def test_the_stack_is_runs_of_equal_layers():
    runs = tfm.stack_runs(CFG)
    assert runs == [
        ("full", "layers", 0, 0, 1), ("window", "layers", 1, 0, 3),
        ("full", "layers", 4, 1, 1), ("window", "layers", 5, 3, 3)]
    assert [tfm.layer_kind(CFG, r.first) for r in runs] == [
        (0, False), (WINDOW, True)] * 2
    sdar = tfm.CONFIGS["tiny-sdar-moe"]
    assert tfm.stack_runs(sdar) == [("full", "layers", 0, 0, 3)]
    assert tfm.layer_kind(sdar, 0) == (0, True)
    full = tfm.CONFIGS["smallthinker-21b-a3b-instruct"]
    assert len(tfm.stack_runs(full)) == 26 and (
        full.layer_windows[:4], full.layer_rope[:4]) == (
        (0, 4096, 4096, 4096), (False, True, True, True))
    with pytest.raises(ValueError, match="layer_windows"):
        dataclasses.replace(CFG, layer_windows=(0, 8))
    with pytest.raises(ValueError, match="one window"):
        dataclasses.replace(CFG, layer_windows=(0, 8, 4, 8) * 2)
    with pytest.raises(ValueError, match="router_input"):
        dataclasses.replace(CFG, router_input="x")


@pytest.mark.parametrize("seq", [30, 50])
def test_forward_is_the_layers_written_out(params, seq):
    """The uncached forward against the equations written out with plain
    einsums: a full layer sees every earlier key and takes no rotary
    embedding, a windowed one sees the last ``WINDOW`` and takes one; the
    router reads the attention's normed input; experts are ReGLU."""
    toks = _tokens(seq, 2, seq)
    got = forward(params, toks, CFG)
    c = CFG
    x = params["embed"][toks]
    at = jnp.broadcast_to(jnp.arange(seq), toks.shape)
    back = jnp.arange(seq)[:, None] - jnp.arange(seq)[None]
    for l in range(c.n_layers):
        w = jax.tree.map(lambda a: a[l], params["layers"])
        h = tfm._norm(x, w["ln1"], None, "llama", c.norm_eps)
        r = h @ w["w_router"]
        q, k, v = (jnp.einsum("bse,ehd->bshd", h, w[n])
                   for n in ("wq", "wk", "wv"))
        if c.layer_rope[l]:
            q = tfm._rope(q, at, c.rope_theta, "half")
            k = tfm._rope(k, at, c.rope_theta, "half")
        k, v = (jnp.repeat(a, c.n_heads // c.n_kv_heads, axis=2)
                for a in (k, v))
        seen = back >= 0
        if c.layer_windows[l]:
            seen &= back < c.layer_windows[l]
        a = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(c.head_dim)
        p = jax.nn.softmax(jnp.where(seen, a, -jnp.inf), axis=-1)
        x = x + jnp.einsum("bshd,hde->bse",
                           jnp.einsum("bhqk,bkhd->bqhd", p, v), w["wo"])
        h2 = tfm._norm(x, w["ln2"], None, "llama", c.norm_eps)
        top, idx = jax.lax.top_k(r, c.moe_top_k)
        gate = jax.nn.softmax(top, axis=-1)
        ff = jnp.zeros_like(x)
        for e in range(c.n_routed_experts):
            g = jnp.where(idx == e, gate, 0.0).sum(-1)
            out = (jax.nn.relu(h2 @ w["we_gate"][e])
                   * (h2 @ w["we_up"][e])) @ w["we_down"][e]
            ff = ff + g[..., None] * out
        x = x + ff
    want = tfm._norm(x, params["ln_f"], None, "llama",
                     c.norm_eps) @ params["lm_head"]
    assert float(jnp.abs(got - want).max()) < TOL


@pytest.mark.parametrize("change", [
    {"router_input": "ffn"}, {"expert_form": "swiglu"},
    {"layer_rope": (True,) * 8}, {"layer_rope": (False,) * 8},
    {"layer_windows": (0,) * 8},
    {"layer_windows": (0, 7, 7, 7) * 2}])
def test_each_kind_moves_the_logits(params, change):
    """No kind is vacuous: the router's input, the expert's form, the
    rotary embedding on a full layer or off a windowed one, the window."""
    toks = _tokens(7, 2, 40)
    other = forward(params, toks, dataclasses.replace(CFG, **change))
    assert float(jnp.abs(other - forward(params, toks, CFG)).max()) > 1e-2


def test_training_and_sharding_raise_by_name(params):
    with pytest.raises(NotImplementedError, match="served, not trained"):
        tfm.loss_fn(params, {"tokens": _tokens(1, 2, 16)}, CFG)
    with pytest.raises(NotImplementedError, match="layer_windows"):
        tfm.make_layer_fn(dataclasses.replace(CFG, ffn_kind=""))
    with pytest.raises(NotImplementedError, match="logical_axes"):
        tfm.logical_axes(CFG)
    with pytest.raises(NotImplementedError, match="layer_windows"):
        tfm.param_shapes(dataclasses.replace(CFG, moe_experts=4, ffn_kind=""))


# ----------------------------------------------------------- (b), (c)


def _run_cached(params, toks, widths, cfg=CFG, max_len=64, real=None):
    cache = decode.init_cache(cfg, toks.shape[0], max_len)
    out, at = [], 0
    for i, S in enumerate(widths):
        logits, cache = cached(
            params, toks[:, at:at + S], cache, cfg,
            real=None if real is None else real[i])
        out.append(logits)
        at += S
    return jnp.concatenate(out, axis=1), cache


# widths of the calls: a prefill in chunks, then decode a token at a time
CALLS = {
    "shorter_than_the_window": [5, 1, 1],
    "exactly_the_window": [8, 1, 1, 1],
    "a_chunk_straddles_the_wrap": [6, 6, 1, 1, 1],
    "several_wraps": [6] * 5 + [1] * 10,
    "a_call_wider_than_the_ring": [13, 1, 1, 1, 9, 1, 1],
    "one_token_at_a_time_from_nothing": [1] * 20,
}


@pytest.mark.parametrize("name", CALLS)
def test_chunks_and_steps_through_the_ring_are_the_forward(params, name):
    widths = CALLS[name]
    toks = _tokens(len(name), 2, sum(widths))
    got, cache = _run_cached(params, toks, widths)
    want = forward(params, toks, CFG)
    assert float(jnp.abs(got - want).max()) < TOL
    # the rings' length follows from the window, never from max_len
    assert cache["state"]["k_win"].shape == (6, 2, 2, WINDOW, 16)
    assert cache["k"].shape == (2, 2, 2, 64, 16)
    assert decode.init_cache(CFG, 2, 32)["state"]["k_win"].shape == \
        cache["state"]["k_win"].shape


def test_layers_without_rotary_embedding_alone_keep_rows_alone(params):
    """``layer_rope`` with no windowed layer: the same runs, no ring, no
    state."""
    cfg = dataclasses.replace(CFG, layer_windows=())
    toks = _tokens(2, 2, 20)
    got, cache = _run_cached(params, toks, [9, 1, 5, 1, 1, 1, 1, 1], cfg)
    assert float(jnp.abs(got - forward(params, toks, cfg)).max()) < TOL
    assert "state" not in cache and cache["k"].shape == (8, 2, 2, 64, 16)
    assert "row_steps" not in cache["counters"]


def test_a_padded_chunk_tail_leaves_no_pad_in_the_ring(params):
    """A final chunk of 6 with 3 real tokens, ``pos`` put back by the
    caller as the engine does: the pads would lie over keys 9-11 positions
    back, which the next queries still see."""
    toks = _tokens(3, 1, 24)
    fed = jnp.concatenate([toks[:, :15], jnp.zeros((1, 3), toks.dtype)], 1)
    cache = decode.init_cache(CFG, 1, 64)
    for lo, real in ((0, 6), (6, 6), (12, 3)):
        logits, cache = cached(
            params, fed[:, lo:lo + 6], cache, CFG, real=jnp.asarray(real))
    cache["pos"] = jnp.asarray(15, jnp.int32)
    got = [logits[:, 2]]
    for t in range(15, 23):
        logits, cache = cached(params, toks[:, t:t + 1],
                                              cache, CFG)
        got.append(logits[:, 0])
    want = forward(params, toks[:, :23], CFG)[:, 14:]
    assert float(jnp.abs(jnp.stack(got, 1) - want).max()) < TOL
    # told nothing (`real` None), the pads DO land in the ring: the
    # control is not vacuous
    cache = decode.init_cache(CFG, 1, 64)
    for lo in (0, 6, 12):
        _, cache = cached(params, fed[:, lo:lo + 6], cache,
                                         CFG)
    cache["pos"] = jnp.asarray(15, jnp.int32)
    bad, _ = cached(params, toks[:, 15:16], cache, CFG)
    assert float(jnp.abs(bad[:, 0] - want[:, 1]).max()) > 1e-2


def test_a_decode_step_of_a_row_that_wrapped_beside_one_that_did_not(params):
    """Rows at positions of their own (the engine's slots), one past the
    window and one inside it, a frozen row between them: each is the
    forward of its own sequence, and the frozen row's ring is as it was
    for its next real token."""
    toks = _tokens(11, 3, 30)
    lens = [20, 3, 11]
    cache = decode.init_cache(CFG, 3, 64)
    cache["pos"] = jnp.zeros((3,), jnp.int32)
    want = forward(params, toks, CFG)
    # prefill each row alone, installed by hand as the engine installs it
    for b, n in enumerate(lens):
        row = decode.init_cache(CFG, 1, 64)
        _, row = cached(params, toks[b:b + 1, :n], row, CFG)
        for name in ("k", "v"):
            cache[name] = cache[name].at[:, b].set(row[name][:, 0])
        for name in ("k_win", "v_win"):
            cache["state"][name] = cache["state"][name].at[:, b].set(
                row["state"][name][:, 0])
        cache["pos"] = cache["pos"].at[b].set(n)
    for step in range(6):
        at = np.asarray(lens) + step
        nxt = jnp.stack([toks[b, at[b]] for b in range(3)])[:, None]
        # row 1 is held back on the odd steps: a garbage token, not real
        run = jnp.asarray([True, step % 2 == 0, True])
        if step % 2:
            nxt = nxt.at[1, 0].set(0)
            lens[1] -= 1
        logits, new = cached(params, nxt, cache, CFG,
                                            real=run)
        new["pos"] = jnp.where(run, new["pos"], cache["pos"])
        cache = new
        for b in range(3):
            if bool(run[b]):
                assert float(jnp.abs(
                    logits[b, 0] - want[b, int(cache["pos"][b]) - 1]
                ).max()) < TOL, (step, b)
    counted = cache["counters"]
    assert int(counted["row_steps"]) == 15
    assert int(counted["ring_wrapped_row_steps"]) == 6 + 6   # rows 0 and 2


def test_the_ring_is_a_mask_over_a_full_length_row(params):
    """(c) ``ring_attend`` against ``layer_attend(window=)`` over a
    full-length row at equal inputs: a decode step behind 21 keys and a
    chunk of 5 behind 13, rows at positions of their own."""
    G, D, H, L = CFG.n_kv_heads, CFG.head_dim, CFG.n_heads, 32
    key = jax.random.PRNGKey(5)
    k_all = jax.random.normal(key, (2, L, G, D))
    v_all = jax.random.normal(jax.random.fold_in(key, 1), (2, L, G, D))
    for S, pos in ((1, (21, 5)), (5, (13, 2)), (12, (9, 20))):
        pos_b = jnp.asarray(pos, jnp.int32)
        q = jax.random.normal(jax.random.fold_in(key, S), (2, S, H, D))
        # each row's ring, written a token at a time up to its position
        rings = []
        for src in (k_all, v_all):
            rows = []
            for b in range(2):
                ring = jnp.zeros((1, 1, G, WINDOW, D))
                for p in range(pos[b]):
                    ring = write_ring(
                        ring, src[b:b + 1, p:p + 1], 0, jnp.asarray([p]),
                        jnp.ones((1,), jnp.int32))
                rows.append(ring)
            rings.append(jnp.concatenate(rows, axis=1))
        new_k = jnp.stack([jax.lax.dynamic_slice_in_dim(k_all[b], pos[b], S)
                           for b in range(2)])
        new_v = jnp.stack([jax.lax.dynamic_slice_in_dim(v_all[b], pos[b], S)
                           for b in range(2)])
        got, _, _ = ring_attend(
            q, new_k, new_v, rings[0], rings[1], 0, pos_b,
            jnp.full((2,), S, jnp.int32), WINDOW, H // G, jnp.float32)
        want = layer_attend(q, k_all, v_all, pos_b, H // G, jnp.float32,
                            window=WINDOW)
        assert float(jnp.abs(got - want).max()) < 1e-5, S


def test_ring_positions_are_the_latest_of_each_slot():
    last = jnp.asarray([-1, 0, 7, 8, 21])
    got = np.asarray(ring_positions(last, 8))
    assert (got[0] < 0).all()
    assert got[1].tolist() == [0] + [-8 + s for s in range(1, 8)]
    assert got[2].tolist() == list(range(8))
    assert got[3].tolist() == [8, 1, 2, 3, 4, 5, 6, 7]
    assert got[4].tolist() == [16, 17, 18, 19, 20, 21, 14, 15]


# ------------------------------------------------- (b) through the engine


def _served_is_the_forward(params, eng, prompts, budgets):
    ids = [eng.submit(p, greedy(n)) for p, n in zip(prompts, budgets)]
    done = {r.id: r for r in eng.run()}
    for i, p in zip(ids, prompts):
        toks = list(done[i].tokens)
        full = forward(params, jnp.asarray([p + toks]), CFG)[0]
        want = np.asarray(full[len(p) - 1:-1].argmax(-1)).tolist()
        assert toks == want, (len(p), toks, want)


def test_the_engine_serves_through_rings(params):
    """Chunked prefill (chunks of 6: wider than the window's remainder,
    final chunks with padded tails), install, decode blocks in which
    wrapped rows decode beside rows that did not, rows frozen at their
    budget inside a block, slots reused by a SHORTER request after a longer
    one (3 slots, 7 requests)."""
    eng = InferenceEngine(params, CFG, slots=3, max_len=96, prefill_len=6,
                          decode_block=4)
    assert eng.state_bytes_per_slot == 6 * 2 * 2 * WINDOW * 16 * 4
    assert eng.cache_bytes_per_token == 2 * 2 * 2 * 16 * 4
    rng = np.random.default_rng(0)
    lens = (50, 5, 41, 3, 12, 24, 7)
    prompts = [rng.integers(0, 256, n).tolist() for n in lens]
    _served_is_the_forward(params, eng, prompts, (11, 20, 14, 30, 5, 9, 6))


def test_a_prefix_cache_entry_holds_the_ring_at_its_boundary(params):
    """A resume from a boundary behind the wrap copies the ring as it stood
    THERE: the resumed prompt's logits are the forward's, and the entry is
    still good for the next resume (the working row is not written in
    place)."""
    eng = InferenceEngine(params, CFG, slots=2, max_len=96, prefill_len=6,
                          decode_block=4, prefix_cache_entries=2)
    rng = np.random.default_rng(1)
    shared = rng.integers(0, 256, 31).tolist()
    _served_is_the_forward(params, eng, [shared], (5,))
    for tail in ([1, 2, 3], [9, 8, 7, 6, 5, 4, 3, 2, 1]):
        prompt = shared[:30] + tail
        run = eng.prefill_begin(prompt)
        assert run.start == 30
        while not eng.prefill_step(run):
            pass
        want = forward(params, jnp.asarray([prompt]), CFG)[0, -1]
        assert float(jnp.abs(run.last - want).max()) < TOL
    assert eng.prefix_cache_hits == 2
    _served_is_the_forward(params, eng, [shared[:30] + [4, 4]], (7,))


# ----------------------------------------------------------------- (f)


def test_what_addresses_positions_raises_by_name_for_a_tree_with_a_ring(
        params, monkeypatch):
    # pages, park/resume and copy-on-write are the paged store
    with pytest.raises(NotImplementedError, match="ring"):
        InferenceEngine(params, CFG, slots=2, max_len=48, prefill_len=6,
                        kv_pages=8)
    monkeypatch.setenv("DLROVER_TPU_KV_COW", "1")
    with pytest.raises(NotImplementedError, match="ring"):
        InferenceEngine(params, CFG, slots=2, max_len=48, prefill_len=6,
                        kv_pages=8)
    eng = InferenceEngine(params, CFG, slots=2, max_len=48, prefill_len=6)
    run = eng.prefill_begin([1, 2, 3])
    while not eng.prefill_step(run):
        pass
    with pytest.raises(NotImplementedError, match="ring"):
        eng.make_bundle(run)
    with pytest.raises(NotImplementedError, match="ring"):
        eng.submit_prefilled([1, 2, 3], greedy(2), object())
    monkeypatch.setenv("DLROVER_TPU_SPEC_DEPTH", "4")
    with pytest.raises(NotImplementedError, match="ring"):
        InferenceEngine(params, CFG, slots=2, max_len=48, prefill_len=6)


# ----------------------------------------------------------------- (d)


def test_reglu_loop_is_the_dense_sum():
    rcfg = moe.RoutedConfig(n_experts=8, top_k=3, form="reglu")
    key = jax.random.PRNGKey(2)
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (20, 32))
    ex = {"we_gate": jax.random.normal(ks[1], (2, 8, 32, 24)) / 6,
          "we_up": jax.random.normal(ks[2], (2, 8, 32, 24)) / 6,
          "we_down": jax.random.normal(ks[3], (2, 8, 24, 32)) / 5}
    idx, gate = moe.softmax_topk_route(
        x, jax.random.normal(ks[4], (32, 8)), rcfg)
    got, loads = moe.held_expert_loop(x, idx, gate, ex, 1, rcfg)
    want = jnp.zeros_like(x)
    for e in range(8):
        g = jnp.where(idx == e, gate, 0.0).sum(-1)
        out = (jax.nn.relu(x @ ex["we_gate"][1, e])
               * (x @ ex["we_up"][1, e])) @ ex["we_down"][1, e]
        want = want + g[:, None] * out
    assert float(jnp.abs(got - want).max()) < 2e-5
    assert int(loads.sum()) == 60
    silu, _ = moe.held_expert_loop(
        x, idx, gate, ex, 1, dataclasses.replace(rcfg, form="swiglu"))
    assert float(jnp.abs(silu - want).max()) > 1e-2
