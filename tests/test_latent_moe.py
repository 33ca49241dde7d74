"""The latent-attention / sandwich-norm / sigmoid-routed-expert kinds
(models/latent.py, ops/moe.py) against the plain reference
(benchmark/reference/pangu_ultra_moe.py), at a small size on the CPU:
1 dense + 2 expert layers, 16 experts of which 4 are held, latent ranks
scaled down, float32, seeded weights that both sides hold alike."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import serve_child_ref as child
from benchmark.drivers.serve_gateway_ref import REHEARSAL_CONFIG as FILE
from benchmark.reference import pangu_ultra_moe as ref
from dlrover_tpu.common.constants import EnvKey
from dlrover_tpu.models import decode, latent
from dlrover_tpu.models import transformer as tfm
from dlrover_tpu.models.cache import key_reaches, reach_of
from dlrover_tpu.ops import moe
from dlrover_tpu.serving import InferenceEngine
from dlrover_tpu.serving.engine import SamplingParams
from dlrover_tpu.telemetry import journal as journal_mod
from dlrover_tpu.telemetry.report import load_events

SEED = 2**31 + 7
TOL = 2e-5


@pytest.fixture(scope="module")
def model():
    cfg = child.program_config(FILE)
    return cfg, child.program_params(ref, FILE, SEED, cfg)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, FILE["vocab_size"], n)


def test_the_file_builds_the_share_of_the_published_preset(model):
    cfg, params = model
    assert (cfg.n_layers, cfg.first_k_dense, cfg.n_routed_experts,
            cfg.experts_held, cfg.expert_first, cfg.vocab_size) == (
        3, 1, 16, 4, 4, 128)
    assert params["layers"]["we_gate"].shape == (2, 4, 64, 32)
    assert params["layers"]["w_router"].shape == (2, 64, 16)
    assert cfg.param_count == sum(a.size for a in jax.tree.leaves(params))
    with pytest.raises(NotImplementedError, match="served, not trained"):
        cfg.train_flops_per_token(16)
    with pytest.raises(SystemExit, match="hidden_size"):
        child.program_config({**FILE, "hidden_size": 96})
    with pytest.raises(SystemExit, match="no preset"):
        child.program_config({**FILE, "program_model": "absent"})


@pytest.mark.parametrize("seed", [1, 2])
def test_uncached_forward_agrees_with_the_reference(model, seed):
    cfg, params = model
    toks = tokens(256, seed)
    got = tfm.forward(params, jnp.asarray(toks)[None], cfg)[0]
    want = ref.logits(FILE, SEED, toks)
    assert float(jnp.abs(got - want).max()) < TOL
    assert float(jnp.abs(want).std()) > 0.1


@pytest.mark.parametrize("absorb_upto", [0, 64], ids=["expanded", "absorbed"])
def test_chunked_prefill_then_decode_agrees_at_every_position(
        model, absorb_upto, monkeypatch):
    cfg, params = model
    monkeypatch.setattr(latent, "ABSORB_UPTO", absorb_upto)
    toks = tokens(64, 3)
    want = ref.logits(FILE, SEED, np.pad(toks, (0, 192)))[:64]
    cache = decode.init_cache(cfg, 1, 64)
    step = jax.jit(lambda t, c: decode.forward_cached(params, t, c, cfg))
    outs = []
    for lo, hi in [(0, 16), (16, 32), (32, 48)] + [
            (i, i + 1) for i in range(48, 64)]:
        lg, cache = step(jnp.asarray(toks[lo:hi])[None], cache)
        outs.append(lg[0])
    assert float(jnp.abs(jnp.concatenate(outs) - want).max()) < TOL
    assert int(cache["pos"]) == 64
    # what the expert layers counted: 64 tokens x 4 choices x 2 layers,
    # the share that landed on the 4 held of 16
    loads = np.asarray(cache["counters"]["loads"])
    assert loads.shape == (2, 4) and 0 < loads.sum() < 64 * 4 * 2
    fields = decode.cache_counter_fields(cache)
    assert int(fields["expert_tokens"]) == loads.sum()
    assert int(fields["expert_load_max"]) == loads.max()


@pytest.mark.parametrize("change", [
    {"norm_kind": "pre"}, {"ffn_kind": "swiglu"}, {"attn_kind": "heads"}])
def test_kinds_that_no_configuration_asks_for_are_refused_by_name(
        model, change):
    cfg, _ = model
    with pytest.raises(NotImplementedError, match="runs .* together"):
        latent.segments(dataclasses.replace(cfg, **change))


def test_the_engine_serves_slots_at_positions_of_their_own(model):
    cfg, params = model
    eng = InferenceEngine(params, cfg, slots=4, max_len=64, prefill_len=8,
                          decode_block=4)
    prompts = [tokens(n, 10 + n).tolist() for n in (5, 19, 9, 30)]
    for p in prompts:
        eng.submit(p, SamplingParams(temperature=0.0, max_new_tokens=12))
    results = sorted(eng.run(), key=lambda r: r.id)
    for prompt, res in zip(prompts, results):
        seq = np.zeros(256, np.int64)
        seq[: len(prompt) + 12] = prompt + res.tokens
        rows = np.asarray(ref.logits(FILE, SEED, seq))[
            len(prompt) - 1: len(prompt) + 11]
        gaps = rows.max(-1) - rows[np.arange(12), res.tokens]
        assert gaps.max() < TOL, (len(prompt), gaps)


def test_the_cache_is_one_latent_stack(model):
    cfg, params = model
    cache = decode.init_cache(cfg, 4, 64)
    assert set(decode.cache_stacks(cache)) == {"latent"}
    assert cache["latent"].shape == (3, 4, 64, 24 + 8)
    eng = InferenceEngine(params, cfg, slots=2, max_len=64, prefill_len=8)
    assert eng.cache_bytes_per_token == 3 * 32 * 4          # float32 here
    # at the published sizes: 576 numbers a token a layer
    full = tfm.CONFIGS["openpangu-ultra-moe-718b"]
    row = jax.eval_shape(lambda: latent.init_cache(full, 1, 8))["latent"]
    assert row.shape == (61, 1, 8, 576) and row.dtype == jnp.bfloat16


def test_a_latent_cache_refuses_pages_by_name(model):
    cfg, params = model
    with pytest.raises(NotImplementedError, match="latent"):
        InferenceEngine(params, cfg, slots=2, max_len=64, prefill_len=8,
                        kv_pages=16)


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts that the 4 shares of 4 experts give, with the
    shared expert counted once, are the uncut reference layer."""
    whole = {**FILE, "n_routed_experts": 16,
             "deployment": {**FILE["deployment"], "expert_first": 0}}
    w = ref.layer_weights(whole, SEED, 1)
    h = jax.random.normal(jax.random.PRNGKey(5), (48, 64), jnp.float32)
    want, _ = ref.expert_layer(whole, h, w)
    shared = moe.swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"])
    total = shared
    for first in (0, 4, 8, 12):
        rcfg = moe.RoutedConfig(n_experts=16, top_k=4, scaling=2.5,
                                first=first, held=4)
        idx, gate = moe.sigmoid_topk_route(h, w["w_router"], rcfg)
        held = {k: w[k][first:first + 4] for k in tfm.EXPERT_STACKS}
        part, loads = moe.held_expert_ffn(
            h, idx, gate, {k: v[None] for k, v in held.items()}, 0, rcfg)
        mine = ref.expert_layer(whole, h, {**w, **held},
                                held=(first, 4))[0]
        assert float(jnp.abs(part + shared - mine).max()) < TOL
        total = total + part
    assert float(jnp.abs(total - want).max()) < TOL


@pytest.mark.parametrize("tokens_n", [7, 300])
def test_every_token_to_one_held_expert_and_none_is_dropped(tokens_n):
    """A router that overloads one held expert: every token's first
    choice is expert 5 (held), the rest lie outside the share. 300 tokens
    are three row tiles of that one expert."""
    rcfg = moe.RoutedConfig(n_experts=16, top_k=4, first=4, held=4)
    key = jax.random.PRNGKey(9)
    x = jax.random.normal(key, (tokens_n, 64), jnp.float32)
    experts = {
        "we_gate": jax.random.normal(key, (2, 4, 64, 32)) / 8,
        "we_up": jax.random.normal(jax.random.fold_in(key, 1),
                                   (2, 4, 64, 32)) / 8,
        "we_down": jax.random.normal(jax.random.fold_in(key, 2),
                                     (2, 4, 32, 64)) / 6}
    idx = jnp.tile(jnp.asarray([[5, 0, 1, 12]], jnp.int32), (tokens_n, 1))
    gate = jax.random.uniform(jax.random.fold_in(key, 3), (tokens_n, 4))
    got, loads = jax.jit(
        lambda *a: moe.held_expert_ffn(*a, experts, 1, rcfg))(x, idx, gate)
    want = gate[:, :1] * moe.swiglu(
        x, experts["we_gate"][1, 1], experts["we_up"][1, 1],
        experts["we_down"][1, 1])
    assert loads.tolist() == [0, tokens_n, 0, 0]
    assert float(jnp.abs(got - want).max()) < TOL


# ------------------- a wide call reads its row as far as its last query


def _first_reach(pos, S, K):
    """What the rule should pick, written out plainly: the first of
    S, 2S, 4S, ..., K that holds position ``pos + S - 1``."""
    n = S
    while n < min(pos + S, K):
        n *= 2
    return min(n, K)


@pytest.mark.parametrize("n_tokens,start", [
    *[(n, 0) for n in (15, 16, 17, 32, 33, 64, 65, 80)],
    *[(n, 32) for n in (33, 64, 65, 80)]])
def test_chunks_that_end_around_every_reach_agree_with_the_uncached_forward(
        model, n_tokens, start, monkeypatch):
    """Chunks of 16 into a row of 80 (reaches 16, 32, 64, 80) for prompts
    whose last chunk ends just under, at and just over each of them; the
    last chunk is pad-tailed as the engine's is. ``start`` 32: the row's
    first 32 positions were written by an earlier call, as a prefix-cache
    hit hands them over."""
    cfg, params = model
    monkeypatch.setattr(latent, "ABSORB_UPTO", 0)
    S, K = 16, 80
    toks = tokens(n_tokens, 40 + n_tokens)
    want = tfm.forward(params, jnp.asarray(toks)[None], cfg)[0]
    step = jax.jit(lambda t, c: decode.forward_cached(
        params, t, decode.zero_counters(c), cfg))
    cache = decode.init_cache(cfg, 1, K)
    outs = []
    if start:
        lg, cache = step(jnp.asarray(toks[:start])[None], cache)
        outs.append(lg[0])
    for lo in range(start, n_tokens, S):
        chunk = np.zeros(S, np.int64)
        real = min(S, n_tokens - lo)
        chunk[:real] = toks[lo: lo + real]
        lg, cache = step(jnp.asarray(chunk)[None], cache)
        outs.append(lg[0, :real])
        assert int(cache["counters"]["keys_read"]) == _first_reach(lo, S, K)
    assert float(jnp.abs(jnp.concatenate(outs) - want).max()) < TOL


@pytest.mark.parametrize("S,K", [(16, 80), (16, 64), (64, 1000), (128, 128),
                                 (512, 5120), (65, 66)])
def test_the_chosen_reach_holds_the_last_query_at_every_position(S, K):
    reach = key_reaches(S, K)
    assert reach[0] == S and reach[-1] == K and reach == sorted(set(reach))
    assert all(b == 2 * a for a, b in zip(reach[:-2], reach[1:-1]))
    for pos in sorted({*range(0, K - S + 1, max(1, S // 4)), K - S}):
        q_pos = pos + np.arange(S)[None]
        got, which = reach_of(jnp.asarray(q_pos), S, K)
        keys = got[int(which)]
        assert got == reach
        assert keys >= pos + S and keys == _first_reach(pos, S, K)
        # doubling: never more than twice what the call can see
        assert keys < 2 * (pos + S) or keys == S
    # rows at positions of their own: the farthest decides
    _, which = reach_of(jnp.asarray([[0, 1], [K - 2, K - 1]]), S, K)
    assert reach[int(which)] == K


def test_the_uncached_forward_holds_no_branch_and_a_chunk_program_one_switch(
        model):
    """Where the row IS the call there is one length and no switch; a
    chunk into a longer row is one program that holds every reach."""
    cfg, params = model
    toks = jnp.zeros((1, 80), jnp.int32)
    text = jax.jit(lambda p, t: tfm.forward(p, t, cfg)).lower(
        params, toks).as_text()
    assert "stablehlo.case" not in text and "stablehlo.if" not in text
    cache = decode.init_cache(cfg, 1, 400)
    text = jax.jit(lambda p, t, c: decode.forward_cached(p, t, c, cfg)).lower(
        params, toks, cache).as_text()
    # one switch a scanned segment (dense layers, expert layers), each
    # over the reaches 80, 160, 320, 400
    assert text.count("stablehlo.case") == len(latent.segments(cfg))
    # an absorbed call (a decode step) keeps none
    text = jax.jit(lambda p, t, c: decode.forward_cached(p, t, c, cfg)).lower(
        params, toks[:, :4], cache).as_text()
    assert "stablehlo.case" not in text


def test_every_prefill_chunk_span_says_how_far_its_row_was_read(
        model, tmp_path, monkeypatch):
    cfg, params = model
    monkeypatch.setenv(EnvKey.JOURNAL_DIR, str(tmp_path / "journal"))
    monkeypatch.setattr(journal_mod, "_cached", None)
    try:
        S, K = 128, 640                    # reaches 128, 256, 512, 640
        eng = InferenceEngine(params, cfg, slots=2, max_len=K, prefill_len=S,
                              decode_block=4)
        for n in (100, 300, 600):
            eng.submit(tokens(n, n).tolist(),
                       SamplingParams(temperature=0.0, max_new_tokens=4))
        eng.run()
    finally:
        journal_mod._cached = None
    events = load_events(str(tmp_path / "journal"))
    begun = {e["span"]: e for e in events if e.get("ev") == "b"}
    chunks = [{**begun[e["span"]], **e} for e in events
              if e.get("ev") == "e" and e["name"] == "prefill_chunk"]
    assert sorted(c["context"] for c in chunks) == [
        0, 0, 0, 128, 128, 256, 256, 384, 512]
    for c in chunks:
        assert c["keys_read"] == _first_reach(c["context"], S, K), c
    read = sum(c["keys_read"] for c in chunks)
    seen = sum(c["context"] + c["tokens"] for c in chunks)
    assert 1.0 <= read / seen <= 2.0
    # a decode call is absorbed: it reads the rows as they lie
    blocks = [e for e in events
              if e.get("ev") == "e" and e["name"] == "decode_block"]
    assert blocks and all(e["keys_read"] == 0 for e in blocks)
