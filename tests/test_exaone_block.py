"""K-EXAONE's kinds in the ONE block (``models/transformer.py``,
``models/decode.py``, ``ops/moe.py``): norms on the sublayers' OUTPUTS, q/k
norms under ``layer_rope``, a sigmoid router with a score bias beside a shared
expert, a leading dense layer in a tree of its own, and a RING SHORTER than a
prefill chunk, against the plain reference
(``benchmark/reference/exaone_moe.py``) at a small size on the CPU: the held
stack L L L G L with layer 0 dense, a window of 4 under chunks of 12, 8 of 16
experts held from the 8th on, half the vocabulary, float32, seeded weights
that both sides hold alike.

The tolerance ``TOL`` is a few float32 roundings of logits of size ~4 (the
cached path sums a softmax over other key sets in another order than the
forward does).
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import serve_child_swa_shared as child
from benchmark.drivers.serve_gateway_swa_shared import (
    REHEARSAL_CONFIG, REHEARSAL_SERVING)
from benchmark.reference import exaone_moe as ref
from dlrover_tpu.models import decode, latent
from dlrover_tpu.models import transformer as tfm
from dlrover_tpu.ops import moe
from dlrover_tpu.serving.engine import InferenceEngine, SamplingParams

FILE = {**REHEARSAL_CONFIG, "serving": REHEARSAL_SERVING}
SEED = 2**31 + 11
TOL = 5e-5
WINDOW, CHUNK = 4, 12

cached = jax.jit(decode.forward_cached, static_argnums=(3,))
forward = jax.jit(tfm.forward, static_argnums=(2,))


@pytest.fixture(scope="module")
def model():
    cfg = child.program_config(FILE)
    return cfg, child.program_params(ref, FILE, SEED, cfg)


def tokens(seed, *shape):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, FILE["vocab_size"], shape), jnp.int32)


def greedy(n):
    return SamplingParams(temperature=0.0, max_new_tokens=n, eos_id=None)


def test_the_file_builds_the_share_of_the_published_preset(model):
    cfg, params = model
    assert (cfg.n_layers, cfg.first_k_dense, cfg.n_routed_experts,
            cfg.experts_held, cfg.expert_first, cfg.vocab_size) == (
        5, 1, 16, 8, 8, 128)
    assert (cfg.attn_kind, cfg.norm_kind, cfg.ffn_kind) == (
        "heads_qk_norm", "post", "sigmoid_experts")
    # L-dense, L, L, G, L: a dense layer beside sparse ones is a label, a
    # second stacked tree and a run more
    assert tfm.stack_runs(cfg) == [
        ("window", "dense_layers", 0, 0, 1), ("window", "layers", 1, 1, 2),
        ("full", "layers", 3, 0, 1), ("window", "layers", 4, 3, 1)]
    assert params["dense_layers"]["w_gate"].shape == (1, 64, 96)
    assert params["layers"]["we_gate"].shape == (4, 8, 64, 32)
    assert params["layers"]["w_router"].shape == (4, 64, 16)
    assert params["layers"]["b_router"].shape == (4, 16)
    assert params["layers"]["ws_gate"].shape == (4, 64, 32)
    assert "w_router" not in params["dense_layers"]
    assert cfg.param_count == sum(a.size for a in jax.tree.leaves(params))
    published = tfm.CONFIGS["k-exaone-236b-a23b"]
    assert len(tfm.stack_runs(published)) == 25
    assert (published.layer_windows[:5], published.layer_rope[:5]) == (
        (128, 128, 128, 0, 128), (True, True, True, False, True))
    assert tfm.routed_config(published).scaling == 2.5
    with pytest.raises(SystemExit, match="hidden_size"):
        child.program_config({**FILE, "hidden_size": 96})
    with pytest.raises(SystemExit, match="no preset"):
        child.program_config({**FILE, "program_model": "absent"})
    with pytest.raises(SystemExit, match="dense layers lead"):
        child.program_config({**FILE, "mlp_layer_types": [
            "sparse", "dense", "sparse", "sparse", "sparse"]})
    with pytest.raises(SystemExit, match="sliding_windows against"):
        child.program_config({**FILE, "sliding_windows": [4, 4, 4, 4, 4]})


@pytest.mark.parametrize("seed", [1, 2])
def test_uncached_forward_agrees_with_the_reference(model, seed):
    cfg, params = model
    toks = tokens(seed, 256)
    got = forward(params, toks[None], cfg)[0]
    want = ref.logits(FILE, SEED, toks)
    assert float(jnp.abs(got - want).max()) < TOL
    assert float(jnp.abs(want).std()) > 0.1


def _run_cached(params, cfg, toks, widths, max_len=96, real=None):
    cache = decode.init_cache(cfg, toks.shape[0], max_len)
    out, at = [], 0
    for i, S in enumerate(widths):
        logits, cache = cached(params, toks[:, at:at + S], cache, cfg,
                               real=None if real is None else real[i])
        out.append(logits)
        at += S
    return jnp.concatenate(out, axis=1), cache


# widths of the calls: a prefill in chunks, then decode a token at a time
CALLS = {
    "chunks_three_rings_wide_then_steps": [CHUNK] * 3 + [1] * 9,
    "a_short_call_then_a_wide_one": [3, 1, 1, CHUNK, 1, 1],
    "a_call_of_the_rings_length": [WINDOW, WINDOW, 1, 1, 1],
    "a_call_one_wider_than_the_ring": [WINDOW + 1, 1, WINDOW + 1, 1],
    "one_token_at_a_time_from_nothing": [1] * 14,
}


@pytest.mark.parametrize("name", CALLS)
def test_chunks_and_steps_through_a_ring_of_4_are_the_forward(model, name):
    cfg, params = model
    widths = CALLS[name]
    toks = tokens(len(name), 2, sum(widths))
    got, cache = _run_cached(params, cfg, toks, widths)
    assert float(jnp.abs(got - forward(params, toks, cfg)).max()) < TOL
    assert cache["state"]["k_win"].shape == (4, 2, 2, WINDOW, 16)
    assert cache["k"].shape == (1, 2, 2, 96, 16)
    # what no ring slot keeps: a call's real tokens beyond its last 4
    dropped = 2 * sum(max(S - WINDOW, 0) for S in widths)
    assert int(cache["counters"]["ring_chunk_tokens_dropped"]) == dropped
    loads = np.asarray(cache["counters"]["loads"])
    assert loads.shape == (4, 8) and 0 < loads.sum() < 2 * sum(widths) * 4 * 4


def test_the_reference_agrees_through_chunks_and_steps(model):
    """Prefill in chunks wider than the ring, then decode through rings and
    rows, against the REFERENCE's full forward: logits, not tokens."""
    cfg, params = model
    toks = tokens(5, 1, 64)
    got, _ = _run_cached(params, cfg, toks, [CHUNK] * 4 + [1] * 16)
    want = ref.logits(FILE, SEED, np.asarray(toks[0]))
    assert float(jnp.abs(got[0] - want).max()) < TOL


def test_a_padded_chunk_tail_leaves_no_pad_in_the_ring(model):
    """A final chunk of 12 with 5 real tokens, ``pos`` put back by the
    caller as the engine does: the ring must hold the last 4 REAL tokens;
    the chunk's last 4 are pads."""
    cfg, params = model
    toks = tokens(3, 1, 40)
    fed = jnp.concatenate([toks[:, :29], jnp.zeros((1, 7), toks.dtype)], 1)
    cache = decode.init_cache(cfg, 1, 96)
    for lo, real in ((0, 12), (12, 12), (24, 5)):
        logits, cache = cached(params, fed[:, lo:lo + 12], cache, cfg,
                               real=jnp.asarray(real))
    assert int(cache["counters"]["ring_chunk_tokens_dropped"]) == 8 + 8 + 1
    cache["pos"] = jnp.asarray(29, jnp.int32)
    got = [logits[:, 4]]
    for t in range(29, 39):
        logits, cache = cached(params, toks[:, t:t + 1], cache, cfg)
        got.append(logits[:, 0])
    want = forward(params, toks[:, :39], cfg)[:, 28:]
    assert float(jnp.abs(jnp.stack(got, 1) - want).max()) < TOL
    # told nothing (`real` None), the pads DO land in the ring: the
    # control is not vacuous
    cache = decode.init_cache(cfg, 1, 96)
    for lo in (0, 12, 24):
        _, cache = cached(params, fed[:, lo:lo + 12], cache, cfg)
    cache["pos"] = jnp.asarray(29, jnp.int32)
    bad, _ = cached(params, toks[:, 29:30], cache, cfg)
    assert float(jnp.abs(bad[:, 0] - want[:, 1]).max()) > 1e-2


def test_a_decode_step_of_a_row_that_wrapped_beside_one_that_did_not(model):
    """Rows at positions of their own (the engine's slots), one far past
    the window and one inside it, a frozen row between them."""
    cfg, params = model
    toks = tokens(11, 3, 30)
    lens = [20, 2, 11]
    cache = decode.init_cache(cfg, 3, 64)
    cache["pos"] = jnp.zeros((3,), jnp.int32)
    want = forward(params, toks, cfg)
    for b, n in enumerate(lens):
        row = decode.init_cache(cfg, 1, 64)
        _, row = cached(params, toks[b:b + 1, :n], row, cfg)
        for name in ("k", "v"):
            cache[name] = cache[name].at[:, b].set(row[name][:, 0])
        for name in ("k_win", "v_win"):
            cache["state"][name] = cache["state"][name].at[:, b].set(
                row["state"][name][:, 0])
        cache["pos"] = cache["pos"].at[b].set(n)
    cache = decode.zero_counters(cache)
    for step in range(6):
        at = np.asarray(lens) + step
        nxt = jnp.stack([toks[b, at[b]] for b in range(3)])[:, None]
        run = jnp.asarray([True, step % 2 == 0, True])
        if step % 2:
            nxt = nxt.at[1, 0].set(0)
            lens[1] -= 1
        logits, new = cached(params, nxt, cache, cfg, real=run)
        new["pos"] = jnp.where(run, new["pos"], cache["pos"])
        cache = new
        for b in range(3):
            if bool(run[b]):
                assert float(jnp.abs(
                    logits[b, 0] - want[b, int(cache["pos"][b]) - 1]
                ).max()) < TOL, (step, b)
    counted = cache["counters"]
    assert int(counted["row_steps"]) == 15
    # rows 0 and 2 throughout; row 1's third real step sits at position 4
    assert int(counted["ring_wrapped_row_steps"]) == 6 + 6 + 1
    assert int(counted["ring_chunk_tokens_dropped"]) == 0


def test_the_engine_serves_and_resumes_from_the_prefix_cache(model):
    """Through ``InferenceEngine``: chunked prefill (every chunk three
    rings wide, final chunks with padded tails), decode blocks of rows at
    positions of their own, then a resume from a prefix-cache entry whose
    rings had wrapped; every served token is the reference's first."""
    cfg, params = model
    eng = InferenceEngine(params, cfg, slots=3, max_len=96, prefill_len=CHUNK,
                          decode_block=4, prefix_cache_entries=2)
    assert eng.state_bytes_per_slot == 4 * 2 * 2 * WINDOW * 16 * 4
    assert eng.cache_bytes_per_token == 1 * 2 * 2 * 16 * 4
    rng = np.random.default_rng(0)
    shared = rng.integers(0, 128, 37).tolist()
    prompts = [shared[:36] + [4, 4]] + [rng.integers(0, 128, n).tolist()
                                        for n in (5, 41, 3, 26)]
    budgets = (9, 20, 14, 30, 5)

    def served_is_the_reference(prompts, budgets):
        ids = [eng.submit(p, greedy(n)) for p, n in zip(prompts, budgets)]
        done = {r.id: r for r in eng.run()}
        for i, p in zip(ids, prompts):
            toks = list(done[i].tokens)
            seq = np.zeros(128, np.int64)
            seq[: len(p) + len(toks)] = p + toks
            rows = np.asarray(ref.logits(FILE, SEED, seq))[
                len(p) - 1: len(p) + len(toks) - 1]
            gaps = rows.max(-1) - rows[np.arange(len(toks)), toks]
            assert gaps.max() < TOL, (len(p), gaps)

    served_is_the_reference([shared], (6,))
    for tail in ([1, 2, 3], [9, 8, 7, 6, 5, 4, 3, 2, 1, 0, 1, 2, 3]):
        prompt = shared[:36] + tail
        run = eng.prefill_begin(prompt)
        assert run.start == 36
        while not eng.prefill_step(run):
            pass
        want = forward(params, jnp.asarray([prompt]), cfg)[0, -1]
        assert float(jnp.abs(run.last - want).max()) < TOL
    assert eng.prefix_cache_hits == 2
    # 3 slots, 5 requests, the first resuming from the entry once more
    served_is_the_reference(prompts, budgets)
    assert eng.prefix_cache_hits == 3
    with pytest.raises(NotImplementedError, match="ring"):
        InferenceEngine(params, cfg, slots=2, max_len=48,
                        prefill_len=CHUNK, kv_pages=8)


def test_the_kinds_run_over_rows_alone_too(model):
    """Without ``layer_windows`` / ``layer_rope`` the same kinds (post
    norms, the dense layer's tree, the shared expert) ride
    ``decode.forward_rows``."""
    cfg, params = model
    rows = dataclasses.replace(cfg, layer_windows=(), layer_rope=())
    assert [r[:2] for r in tfm.stack_runs(rows)] == [
        ("full", "dense_layers"), ("full", "layers")]
    toks = tokens(7, 2, 30)
    got, cache = _run_cached(params, rows, toks, [CHUNK, CHUNK, 1, 1, 1, 1,
                                                  1, 1])
    assert float(jnp.abs(got - forward(params, toks, rows)).max()) < TOL
    assert "state" not in cache
    assert np.asarray(cache["counters"]["loads"]).shape == (4, 8)


@pytest.mark.parametrize("change", [
    {"norm_kind": "pre"}, {"routed_scaling_factor": 1.0},
    {"layer_rope": (True,) * 5}, {"layer_rope": (False,) * 5},
    {"layer_windows": (3, 3, 3, 0, 3)}, {"layer_windows": (0,) * 5},
    {"attn_kind": "heads"}, "no_bias", "no_shared_expert"])
def test_each_kind_moves_the_logits(model, change):
    """No kind is vacuous: post for pre norms, the scaling, the rotary
    embedding on the full layer or off the windowed ones, the window one
    short or gone, the q/k norms, the router's bias, the shared expert."""
    cfg, params = model
    toks = tokens(9, 2, 40)
    want = forward(params, toks, cfg)
    if isinstance(change, dict):
        got = forward(params, toks, dataclasses.replace(cfg, **change))
    else:
        layers = dict(params["layers"])
        for name in {"no_bias": ("b_router",),
                     "no_shared_expert": ("ws_down",)}[change]:
            layers[name] = jnp.zeros_like(layers[name])
        got = forward({**params, "layers": layers}, toks, cfg)
    assert float(jnp.abs(got - want).max()) > 1e-3


@pytest.mark.parametrize("control", [c for c in ref.CONTROLS if c])
def test_the_reference_rejects_each_control(control):
    """Every fault the cell's `correct` must reject moves the reference's
    logits at this size (float32: no rounding to hide in), prompt and
    served tokens alike; ``pads_in_ring`` only behind a prompt whose final
    chunk has a pad tail."""
    toks = np.asarray(tokens(13, 128))
    sound = np.asarray(ref.logits_many(FILE, SEED, [toks],
                                       prompt_lens=[41])[0])
    fault = np.asarray(ref.logits_many(FILE, SEED, [toks], control,
                                       prompt_lens=[41])[0])
    assert np.abs(fault - sound).max() > (0.02 if control == "fp8" else 1e-3)
    if control in ("pads_in_ring", "stale_ring", "chunk_keeps_ring_head"):
        # the ring's faults leave what precedes the first wrap alone
        assert np.abs(fault[:WINDOW] - sound[:WINDOW]).max() < 1e-5
    if control == "pads_in_ring":
        assert np.abs(fault[:41] - sound[:41]).max() < 1e-5
        whole = np.asarray(ref.logits_many(FILE, SEED, [toks], control,
                                           prompt_lens=[48])[0])
        assert np.abs(whole - sound).max() < 1e-5     # no pad tail, no fault


def test_the_shares_of_a_layer_add_up_to_the_uncut_reference(model):
    """The test that ties the share to the model: the routed sums of all 8
    shares of one layer (``expert_first`` 0, 2, ..., each holding its
    eighth of 16 experts), with the shared expert (what every chip computes
    alike) counted once, add up to the uncut reference's layer."""
    cfg, _ = model
    uncut = {**FILE, "num_experts": 16, "n_routed_experts": 16,
             "published": {}, "deployment": {**FILE["deployment"],
                                             "expert_first": 0}}
    w = ref.layer_weights(uncut, SEED, 2)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 64))
    want = ref.expert_layer(uncut, x.reshape(-1, 64), w).reshape(x.shape)
    shared = moe.swiglu(x, w["ws_gate"], w["ws_up"], w["ws_down"])
    total = shared
    for first in range(0, 16, 2):
        rcfg = tfm.routed_config(dataclasses.replace(
            cfg, experts_held=2, expert_first=first))
        assert (rcfg.first, rcfg.n_held, rcfg.scaling) == (first, 2, 2.5)
        experts = {k: w[k][None, first:first + 2] for k in ref.EXPERT_STACKS}
        ff, loads = moe.sigmoid_expert_half(x, w, experts, 0, rcfg,
                                            jnp.float32)
        assert loads.shape == (2,)
        # the reference's own share is the program's
        mine = ref.expert_layer(
            uncut, x.reshape(-1, 64),
            {**w, **{k: v[0] for k, v in experts.items()}},
            held=(first, 2)).reshape(x.shape)
        assert float(jnp.abs(ff - mine).max()) < TOL
        total = total + (ff - shared)
    assert float(jnp.abs(total - want).max()) < TOL
    assert float(jnp.abs(want - shared).max()) > 0.1


def test_latent_logits_are_what_they_were_before_the_expert_half_moved(
        monkeypatch):
    """``models/latent.py``'s block calls ``ops/moe.sigmoid_expert_half``;
    until PR 45 it spelled the half out. With the old spelling put back in
    the half's place the logits are the same to the BIT, cached and not."""
    cfg = tfm.CONFIGS["tiny-latent-moe"]
    cfg = dataclasses.replace(cfg, experts_held=4, expert_first=4)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    toks = tokens(1, 2, 40) % cfg.vocab_size

    def run():
        full = tfm.forward(params, toks, cfg)
        cache = decode.init_cache(cfg, 2, 64)
        first, cache = decode.forward_cached(params, toks[:, :30], cache, cfg)
        step, _ = decode.forward_cached(params, toks[:, 30:31], cache, cfg)
        return [np.asarray(a) for a in (full, first, step)]

    def spelled_out(h, w, experts, layer, rcfg, dt):
        B, S = h.shape[:2]
        ht = h.reshape(B * S, -1)
        idx, gate = moe.sigmoid_topk_route(ht, w["w_router"], rcfg)
        routed, loads = moe.held_expert_ffn(ht, idx, gate, experts, layer,
                                            rcfg)
        shared = moe.swiglu(h, w["ws_gate"].astype(dt), w["ws_up"].astype(dt),
                            w["ws_down"].astype(dt))
        return shared + routed.reshape(B, S, -1).astype(dt), loads

    now = run()
    monkeypatch.setattr(latent.moe, "sigmoid_expert_half", spelled_out)
    for a, b in zip(now, run()):
        assert np.array_equal(a, b)


def test_training_and_sharding_still_raise_by_name(model):
    cfg, params = model
    toks = tokens(2, 2, 16)
    with pytest.raises(NotImplementedError, match="served, not trained"):
        tfm.loss_fn(params, {"tokens": toks}, cfg)
    with pytest.raises(NotImplementedError, match="served, not trained"):
        tfm.loss_fn(params, {"tokens": toks}, dataclasses.replace(
            tfm.CONFIGS["tiny"], norm_kind="post"))
    with pytest.raises(NotImplementedError, match="served, not trained"):
        cfg.train_flops_per_token(16)
    with pytest.raises(NotImplementedError, match="swa_shared_moe"):
        cfg.forward_flops_per_token(16)
    with pytest.raises(NotImplementedError, match="logical_axes"):
        tfm.logical_axes(cfg)
    with pytest.raises(NotImplementedError, match="one device"):
        tfm.forward_with_aux(params, toks, cfg,
                             mask=jnp.ones(toks.shape, bool))
    with pytest.raises(NotImplementedError, match="caller closes"):
        tfm.make_layer_fn(cfg, kind=(4, True))           # no experts
    with pytest.raises(NotImplementedError, match="'post'"):
        tfm.make_layer_fn(dataclasses.replace(
            cfg, layer_windows=(), layer_rope=(), int8_matmuls=True),
            dense=True)
    # `mixers` with post norms or this expert layer, experts twice over, a
    # sigmoid layer without its sizes, sandwich norms outside latent.py
    for change in ({"attn_kind": "mixers"}, {"moe_experts": 4},
                   {"n_shared_experts": 0}, {"first_k_dense": 6},
                   {"norm_kind": "sandwich"}, {"router_input": "attention"},
                   {"variant": "gpt2"}):
        with pytest.raises((NotImplementedError, ValueError)):
            tfm.param_shapes(dataclasses.replace(cfg, **change))
