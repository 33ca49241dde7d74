"""A stack whose layers are ONE sublayer each (``transformer.SINGLE_MIXERS``,
models/hybrid.py: Mamba-2 layers whose cache is a float32 state and a
convolution window, latent squared-ReLU experts chosen by score + bias, an
attention layer with no rotary embedding) against the plain reference
(``benchmark/reference/nemotron_h.py``, which imports nothing of the
program), and through the serving engine, whose every "this row does not
advance" has to leave window and state untouched. The rehearsal's
configuration: ``tiny-nemotron-h``, float32, the stack ``MEM*EME``, scan
chunks of 8, 4 of 8 experts held from the 3rd on, half the vocabulary."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import serve_child_ssm as child
from benchmark.drivers.serve_gateway_ssm import REHEARSAL_CONFIG
from benchmark.reference import nemotron_h as ref
from dlrover_tpu.models import decode, hybrid
from dlrover_tpu.models import transformer as tfm
from dlrover_tpu.ops import moe

FILE = {**REHEARSAL_CONFIG, "serving": {"prefill_len": 24}}
SEED = 2**31 + 11
# float32 on both sides, the reference at Precision.HIGHEST: what is left
# is the order of float32 sums (measured 5e-6 at logits of spread 1.0)
TOL = 5e-5


@pytest.fixture(scope="module", autouse=True)
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def model():
    cfg = child.program_config(FILE)
    return cfg, child.program_params(ref, FILE, SEED, cfg)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(1).integers(0, FILE["vocab_size"], 100)


@pytest.fixture(scope="module")
def want(tokens):
    return np.asarray(ref.logits(FILE, SEED, tokens))


def test_the_file_builds_the_share_of_the_published_preset(model):
    cfg, params = model
    assert (cfg.n_layers, cfg.n_routed_experts, cfg.experts_held,
            cfg.expert_first, cfg.vocab_size) == (7, 8, 4, 2, 128)
    assert cfg.mixer_types == tfm.single_mixers("MEM*EME") == (
        "mamba2", "latent_experts", "mamba2", "attention", "latent_experts",
        "mamba2", "latent_experts")
    assert [(r.kind, r.first_of_kind, r.n)
            for r in tfm.stack_runs(cfg)] == [
        ("mamba2", 0, 1), ("latent_experts", 0, 1), ("mamba2", 1, 1),
        ("attention", 0, 1), ("latent_experts", 1, 1), ("mamba2", 2, 1),
        ("latent_experts", 2, 1)]
    assert params["latent_experts_layers"]["we_up"].shape == (3, 4, 16, 24)
    assert params["latent_experts_layers"]["w_router"].shape == (3, 64, 8)
    assert params["mamba2_layers"]["w_ssm_in"].shape == (3, 64, 64 + 96 + 4)
    assert cfg.param_count == sum(a.size for a in jax.tree.leaves(params))
    with pytest.raises(NotImplementedError, match="served, not trained"):
        cfg.train_flops_per_token(16)
    with pytest.raises(SystemExit, match="hidden_size"):
        child.program_config({**FILE, "hidden_size": 96})
    with pytest.raises(SystemExit, match="mlp_hidden_act"):
        child.program_config({**FILE, "mlp_hidden_act": "silu"})
    with pytest.raises(SystemExit, match="no preset"):
        child.program_config({**FILE, "program_model": "absent"})
    # the two families do not mix, and every size has to be set
    with pytest.raises(ValueError, match="mixer_types"):
        dataclasses.replace(cfg, mixer_types=("mamba2", "sparse") + (
            "mamba2",) * 5)
    with pytest.raises(ValueError, match="ssm_"):
        dataclasses.replace(cfg, ssm_state=0)
    with pytest.raises(NotImplementedError, match="mixers"):
        tfm.make_layer_fn(cfg, mixer="latent_experts")      # no experts
    # the program's own init draws the small leaves as the file assumes
    own = tfm.init_params(cfg, jax.random.PRNGKey(0))
    assert jax.tree.map(jnp.shape, own) == jax.tree.map(jnp.shape, params)
    small = own["mamba2_layers"]
    assert float(small["a_log"].min()) >= 0.0 and float(
        jax.nn.softplus(small["dt_bias"]).max()) <= 0.1 + 1e-6
    assert float(jnp.abs(own["latent_experts_layers"]["b_router"]).min()) > 0


def _cached(cfg, params, tokens, widths, max_len=128):
    """Logits of ``tokens`` fed through ``forward_cached`` in calls of the
    given widths (a width larger than what is left is pad-tailed and told
    so), rows at positions of their own as the engine holds them."""
    fc = jax.jit(lambda p, t, c, r: decode.forward_cached(p, t, c, cfg, real=r))
    cache = decode.init_cache(cfg, 1, max_len)
    cache["pos"] = jnp.zeros((1,), jnp.int32)
    out, at = [], 0
    for width in widths:
        n = min(width, len(tokens) - at)
        fed = np.zeros((1, width), np.int32)
        fed[0, :n] = tokens[at: at + n]
        logits, cache = fc(params, jnp.asarray(fed), cache, jnp.asarray([n]))
        cache["pos"] = jnp.asarray([at + n])        # a pad tail is put back
        out.append(np.asarray(logits[0, :n]))
        at += n
    assert at == len(tokens)
    return np.concatenate(out), cache


def test_the_uncached_forward_is_the_references(model, tokens, want):
    cfg, params = model
    got = tfm.forward(params, jnp.asarray(tokens)[None], cfg)[0]
    assert np.abs(np.asarray(got) - want).max() < TOL
    assert want.std() > 0.3


@pytest.mark.parametrize("widths", [
    # chunks of 24 (three scan chunks of 8), the last pad-tailed by 20,
    # then a token at a time
    [24, 24, 24] + [1] * 28,
    # chunks of 20: boundaries that are no multiple of the scan chunk, so
    # a call pads its own last scan chunk and the next starts mid-way
    [20] * 5,
    # a one-token chunk between wide ones, and odd widths
    [30, 1, 33, 1, 35],
    [1] * 40 + [7, 53],
])
def test_chunked_prefill_then_cached_decode_is_the_references_forward(
        model, tokens, want, widths):
    cfg, params = model
    got, cache = _cached(cfg, params, tokens, widths)
    assert np.abs(got - want).max() < TOL
    counted = cache["counters"]
    # every real token entered each Mamba-2 layer's state once, and no pad
    assert int(counted["ssm_row_steps"]) == 3 * 100
    assert int(counted["context_tokens"]) == sum(range(100))
    # 3 of 8 a token, 4 held: the assignments that landed here, no pad's
    assert 0 < int(counted["expert_tokens"]) <= 3 * 3 * 100
    assert int(counted["expert_steps"]) == len(widths)
    assert 0.0 < float(counted["experts_hit_share"]) <= 1.0


def test_the_step_form_is_the_chunk_form(model, tokens):
    """One Mamba-2 layer's hook alone: 37 tokens in one call (five scan
    chunks, the last padded) against the same tokens a call each, from a
    state and a window that are not empty."""
    cfg, params = model
    w = jax.tree.map(lambda a: a[1], params["mamba2_layers"])
    rng = np.random.default_rng(5)
    xbc = jnp.asarray(rng.normal(size=(2, 37, 96)), jnp.float32)
    dt_raw = jnp.asarray(rng.normal(size=(2, 37, 4)), jnp.float32)
    cache = decode.init_cache(cfg, 2, 8)["state"]
    start = (cache["ssm"].at[1].set(jnp.asarray(
        rng.normal(size=cache["ssm"].shape[1:]), jnp.float32)),
        cache["conv"].at[1].set(jnp.asarray(
            rng.normal(size=cache["conv"].shape[1:]), jnp.float32)),
        jnp.zeros((), jnp.int32), 1)
    real = jnp.asarray([37, 30])
    wide, (ssm, conv, steps, _) = hybrid._mamba2_attend(
        xbc, dt_raw, w, start, cfg=cfg, real_b=real)
    state, outs = start, []
    for t in range(37):
        y, state = hybrid._mamba2_attend(
            xbc[:, t:t + 1], dt_raw[:, t:t + 1], w, state, cfg=cfg,
            real_b=(t < real).astype(jnp.int32))
        outs.append(y)
    narrow = jnp.concatenate(outs, axis=1)
    assert float(jnp.abs(wide[0] - narrow[0]).max()) < TOL
    assert float(jnp.abs(wide[1, :30] - narrow[1, :30]).max()) < TOL
    assert float(jnp.abs(ssm - state[0]).max()) < TOL
    # the window is the three inputs ending with the row's last REAL token
    assert np.array_equal(conv, state[1])
    assert np.array_equal(conv[1, 0], xbc[0, 34:37])
    assert np.array_equal(conv[1, 1], xbc[1, 27:30])
    assert int(steps) == int(state[2]) == 67
    assert float(jnp.abs(ssm[0]).max()) == 0.0       # another layer's


def test_a_call_that_holds_a_row_back_leaves_window_and_state(model, tokens):
    """Two rows, one told that none of its tokens is real: its state, its
    window and the counters are as before, the other row's logits are the
    reference's."""
    cfg, params = model
    fc = jax.jit(lambda p, t, c, r: decode.forward_cached(p, t, c, cfg, real=r))
    cache = decode.init_cache(cfg, 2, 64)
    cache["pos"] = jnp.zeros((2,), jnp.int32)
    first = jnp.asarray(np.stack([tokens[:20], tokens[40:60]]))
    _, cache = fc(params, first, cache, jnp.asarray([20, 20]))
    before = jax.tree.map(np.asarray, cache["state"])
    hit = int(cache["counters"]["expert_tokens"])
    for width in (1, 9):
        fed = jnp.asarray(np.stack([tokens[20:20 + width],
                                    tokens[70:70 + width]]))
        logits, after = fc(params, fed, cache, jnp.asarray([width, 0]))
        for name in ("ssm", "conv"):
            assert np.array_equal(after["state"][name][:, 1],
                                  before[name][:, 1]), (name, width)
            assert not np.array_equal(after["state"][name][:, 0],
                                      before[name][:, 0])
        want = np.asarray(ref.logits(FILE, SEED, tokens[:20 + width]))
        assert np.abs(np.asarray(logits[0]) - want[20:]).max() < TOL
        counted = after["counters"]
        assert int(counted["ssm_row_steps"]) == 3 * (40 + width)
        # the held-back row's tokens reached no expert
        assert int(counted["expert_tokens"]) - hit <= 3 * 3 * width


def test_the_cache_tree_has_two_state_leaves_beside_one_layers_rows(model):
    cfg, _ = model
    cache = decode.init_cache(cfg, 3, 64)
    rows, state = decode.cache_stacks(cache), decode.cache_state(cache)
    assert {k: v.shape for k, v in rows.items()} == {
        "k": (1, 3, 64, 2, 16), "v": (1, 3, 64, 2, 16)}
    assert {k: (v.shape, v.dtype) for k, v in state.items()} == {
        "ssm": ((3, 3, 4, 16, 8), jnp.float32),
        "conv": ((3, 3, 3, 96), jnp.float32)}
    assert set(decode.cache_counter_fields(cache)) == {
        "expert_tokens", "expert_load_max", "experts_hit",
        "expert_load_max_over_mean", "ssm_row_steps", "context_tokens",
        "expert_steps", "experts_hit_share"}
    assert cache["counters"]["loads"].shape == (3, 4)


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts that the 4 shares of 2 experts give, summed in the
    latent and projected up once, with the shared expert counted once, are
    the uncut reference layer."""
    whole = {**FILE, "n_routed_experts": 8,
             "deployment": {**FILE["deployment"], "expert_first": 0}}
    w = ref.layer_weights(whole, SEED, 1)
    h = jax.random.normal(jax.random.PRNGKey(5), (48, 64), jnp.float32)
    want = ref.expert_layer(whole, h, w)
    shared = jnp.square(jax.nn.relu(h @ w["ws_up"])) @ w["ws_down"]
    latent = h @ w["w_lat_down"]
    total = jnp.zeros_like(latent)
    for first in (0, 2, 4, 6):
        rcfg = moe.RoutedConfig(n_experts=8, top_k=3, scaling=2.5,
                                first=first, held=2, form="relu2")
        idx, gate = moe.sigmoid_topk_route(h, w["w_router"], rcfg,
                                           bias=w["b_router"])
        share = {**whole, "n_routed_experts": 2, "published": {
            "n_routed_experts": 8}, "deployment": {"expert_first": first}}
        held = {k: ref.weight(share, SEED, 1, k) for k in ("we_up", "we_down")}
        # a share's experts are the uncut layer's, by their published index
        assert np.array_equal(held["we_up"], w["we_up"][first:first + 2])
        part, loads = moe.held_expert_ffn(
            latent, idx, gate, {k: v[None] for k, v in held.items()}, 0, rcfg)
        mine = ref.expert_layer(share, h, {**w, **held})
        assert float(jnp.abs(part @ w["w_lat_up"] + shared - mine).max()) < TOL
        assert int(loads.sum()) == int(((idx >= first) & (idx < first + 2)).sum())
        total = total + part
    assert float(jnp.abs(total @ w["w_lat_up"] + shared - want).max()) < TOL


def test_the_score_bias_enters_the_choice_and_not_the_gate():
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(40, 64)), jnp.float32)
    w_r = jnp.asarray(rng.normal(size=(64, 8)) / 8, jnp.float32)
    bias = jnp.asarray([0.0, 0.9, 0, 0, 0, 0, -0.9, 0], jnp.float32)
    rcfg = moe.RoutedConfig(n_experts=8, top_k=3, scaling=2.0)
    plain_idx, plain_gate = moe.sigmoid_topk_route(h, w_r, rcfg)
    idx, gate = moe.sigmoid_topk_route(h, w_r, rcfg, bias=bias)
    assert bool((idx == 1).any(axis=-1).all()) and not bool((idx == 6).any())
    assert not np.array_equal(np.sort(idx), np.sort(plain_idx))
    scores = jax.nn.sigmoid(h @ w_r)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    assert float(jnp.abs(gate - 2.0 * chosen / chosen.sum(
        -1, keepdims=True)).max()) < 1e-6
    assert float(jnp.abs(gate.sum(-1) - 2.0).max()) < 1e-5
    # absent: the function it was
    same_idx, same_gate = moe.sigmoid_topk_route(h, w_r, rcfg, bias=None)
    assert np.array_equal(same_idx, plain_idx) and np.array_equal(
        same_gate, plain_gate)
