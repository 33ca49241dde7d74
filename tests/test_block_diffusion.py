"""A block-diffusion model through the normal path (DESIGN.md §23.8): the
``tiny-sdar-moe`` preset (3 layers, 16 softmax-routed experts of which 4 a
token, 4 query heads on 2 key/value heads of 24 = NOT 64 / 4, q/k norms,
blocks of 4) against ``benchmark/reference/sdar_moe.py`` on the reference's
seeded weights, float32: the uncached forward, the engine's served tokens AND
``unmask_steps`` against the reference's own generation, the cache a request
leaves, the prefix cache, the held experts against all experts computed
densely, and every refusal by name.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers.serve_gateway_diffusion import REHEARSAL_CONFIG as CFGF
from benchmark.reference import sdar_moe as ref
from dlrover_tpu.models import transformer as tfm
from dlrover_tpu.models.decode import forward_cached, init_cache
from dlrover_tpu.ops import moe
from dlrover_tpu.serving.engine import InferenceEngine, SamplingParams

SEED = 2**31 + 7
PCFG = tfm.CONFIGS["tiny-sdar-moe"]


def greedy(n, eos=None):
    return SamplingParams(temperature=0.0, max_new_tokens=n, eos_id=eos)


@pytest.fixture(scope="module")
def params():
    shapes = tfm.param_shapes(PCFG)
    p = {n: ref.weight(CFGF, SEED, ref.TOP, n)
         for n in ("embed", "ln_f", "lm_head")}
    p["layers"] = {
        n: jnp.stack([ref.weight(CFGF, SEED, layer, n)
                      for layer in range(PCFG.n_layers)])
        for n in shapes["layers"]}
    assert jax.tree.map(lambda a: tuple(a.shape), p) == shapes
    return p


@pytest.fixture(scope="module")
def engine(params):
    return InferenceEngine(params, PCFG, slots=3, max_len=64, prefill_len=8,
                           decode_block=4, prefix_cache_entries=2)


def prompt_of(n, salt=0):
    return np.random.default_rng(100 * n + salt).integers(0, 255, n).tolist()


# ------------------------------------------------------------- the forward


def test_the_preset_holds_a_head_dim_of_its_own_and_counts_from_shapes():
    assert PCFG.head_dim == 24 != PCFG.d_model // PCFG.n_heads
    assert tfm.CONFIGS["gpt2-medium"].head_dim == 64
    assert tfm.CONFIGS["llama3-8b"].head_dim == 128
    big = tfm.CONFIGS["sdar-30b-a3b-chat"]
    layer = (2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * 128
             + 128 * 3 * 2048 * 768 + 2 * 2048 + 2 * 128)
    assert big.param_count == 48 * layer + 2 * 151936 * 2048 + 2048
    # a token passes 8 of 128 experts: 2 FLOPs a weight it touches, the
    # scores and values products over the keys it sees
    per = (2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * 128
           + 8 * 3 * 2048 * 768)
    assert big.forward_flops_per_token(1000) == 48 * (
        2.0 * per + 4 * 32 * 128 * 1000) + 2.0 * 2048 * 151936
    with pytest.raises(NotImplementedError, match="served, not trained"):
        big.train_flops_per_token(128)


@pytest.mark.parametrize("name", ["tiny", "tiny-moe", "gpt2-small",
                                  "llama3-8b", "tiny-sdar-moe"])
def test_param_shapes_are_what_init_makes(name):
    cfg = tfm.CONFIGS[name]
    made = jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    assert jax.tree.map(lambda a: tuple(a.shape), made) == \
        tfm.param_shapes(cfg)
    assert cfg.param_count == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(made))


def test_forward_uncached_is_the_references_block_causal_forward(params):
    """1e-4: float32 on both sides; what is left is the order of sums (the
    program's grouped product adds an expert's rows through a one-hot
    product, the reference loops over the experts)."""
    tokens = np.asarray(prompt_of(32))
    got = tfm.forward(params, jnp.asarray(tokens)[None], PCFG)[0]
    want = ref.logits(CFGF, SEED, tokens)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-4
    # the mask is block-causal: a later token of the same block moves a
    # position's logits, a token of the next block does not
    other = tokens.copy()
    other[3] = (other[3] + 1) % 255
    moved = np.asarray(tfm.forward(params, jnp.asarray(other)[None],
                                   PCFG)[0])
    assert np.abs(moved[0] - np.asarray(got)[0]).max() > 1e-3
    other = tokens.copy()
    other[4] = (other[4] + 1) % 255
    moved = np.asarray(tfm.forward(params, jnp.asarray(other)[None],
                                   PCFG)[0])
    assert np.abs(moved[:4] - np.asarray(got)[:4]).max() == 0.0


def test_chunks_through_the_cache_are_the_whole_forward(params):
    tokens = jnp.asarray(prompt_of(24))[None]
    whole = tfm.forward(params, tokens, PCFG)
    cache = init_cache(PCFG, 1, 32)
    parts = []
    for lo in (0, 8, 16):
        lg, cache = forward_cached(params, tokens[:, lo:lo + 8], cache, PCFG)
        parts.append(lg)
    assert np.abs(np.asarray(jnp.concatenate(parts, 1))
                  - np.asarray(whole)).max() < 1e-5
    counted = cache["counters"]
    assert int(counted["expert_tokens"]) == 24 * 4 * 3
    assert int(counted["loads"].sum()) == 24 * 4 * 3
    assert int(cache["pos"]) == 24


def test_held_experts_under_the_softmax_router_are_all_experts_densely():
    rcfg = moe.RoutedConfig(n_experts=16, top_k=4)
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(keys[0], (20, 64))
    w_router = jax.random.normal(keys[1], (64, 16)) / 8
    experts = {"we_gate": jax.random.normal(keys[2], (2, 16, 64, 32)) / 8,
               "we_up": jax.random.normal(keys[3], (2, 16, 64, 32)) / 8,
               "we_down": jax.random.normal(keys[4], (2, 16, 32, 64)) / 6}
    idx, gate = moe.softmax_topk_route(x, w_router, rcfg)
    assert np.allclose(np.asarray(gate.sum(-1)), 1.0, atol=1e-6)
    probs = jax.nn.softmax(x @ w_router, axis=-1)
    assert (np.asarray(idx) == np.asarray(jax.lax.top_k(probs, 4)[1])).all()
    got, loads = moe.held_expert_ffn(x, idx, gate, experts, 1, rcfg)
    want = jnp.zeros_like(x)
    for e in range(16):
        g = jnp.where(idx == e, gate, 0.0).sum(-1)
        want = want + g[:, None] * moe.swiglu(
            x, experts["we_gate"][1, e], experts["we_up"][1, e],
            experts["we_down"][1, e])
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    assert loads.tolist() == np.bincount(np.asarray(idx).ravel(),
                                         minlength=16).tolist()


# -------------------------------------------------- the engine's generation


@pytest.mark.parametrize("plen,new", [
    (8, 8),      # the prompt ends on a block: every block opens all masked
    (9, 7),      # one prompt token opens the first block; budget cuts the last
    (10, 5), (11, 9),
    (3, 6),      # shorter than a block: nothing is prefilled
    (19, 10),    # two chunks of 8 prefilled, a remainder of 3
])
def test_served_tokens_and_unmask_steps_are_the_references(engine, plen, new):
    prompt = prompt_of(plen)
    engine.submit(prompt, greedy(new))
    (res,) = engine.run()
    want, steps = ref.generate(CFGF, SEED, prompt, new)
    assert (res.tokens, res.unmask_steps) == (want, steps)
    assert res.finish_reason == "length" and len(res.tokens) == new
    assert set(res.unmask_steps) <= {0, 1, 2, 3}


def test_rows_at_different_blocks_serve_what_each_serves_alone(engine):
    """Three slots, four requests of different lengths: rows join and leave
    between decode calls and sit at blocks of their own."""
    work = [(prompt_of(n, 1), new) for n, new in
            ((9, 12), (17, 5), (6, 9), (12, 4))]
    ids = [engine.submit(p, greedy(new)) for p, new in work]
    results = {r.id: r for r in engine.run()}
    for rid, (prompt, new) in zip(ids, work):
        want, steps = ref.generate(CFGF, SEED, prompt, new)
        assert (results[rid].tokens, results[rid].unmask_steps) == \
            (want, steps)


def test_two_passes_a_block_unmask_two_positions_each(params):
    cfg2 = dataclasses.replace(PCFG, denoising_steps=2)
    cfgf2 = {**CFGF, "assumed": {**CFGF["assumed"], "denoising_steps": 2}}
    eng = InferenceEngine(params, cfg2, slots=2, max_len=32, prefill_len=8,
                          decode_block=8)
    prompt = prompt_of(10, 2)
    eng.submit(prompt, greedy(11))
    (res,) = eng.run()
    want, steps = ref.generate(cfgf2, SEED, prompt, 11)
    assert (res.tokens, res.unmask_steps) == (want, steps)
    assert set(steps) == {0, 1}


def test_the_cache_after_a_request_is_the_cache_of_prefilling_it(params):
    """The storing pass leaves the rows of the FINAL tokens: what a chunk
    over prompt + answer writes."""
    eng = InferenceEngine(params, PCFG, slots=1, max_len=32, prefill_len=8,
                          decode_block=4)
    prompt = prompt_of(10, 3)
    eng.submit(prompt, greedy(14))          # 24 positions: whole blocks
    (res,) = eng.run()
    served = {k: np.asarray(v[:, 0, :24]) for k, v in eng._cache.items()
              if k in ("k", "v")}
    assert int(eng._cache["pos"][0]) == 24
    row = init_cache(PCFG, 1, 32)
    tokens = jnp.asarray(prompt + res.tokens)[None]
    for lo in (0, 8, 16):
        _, row = forward_cached(params, tokens[:, lo:lo + 8], row, PCFG)
    for name, got in served.items():
        assert np.abs(got - np.asarray(row[name][:, 0, :24])).max() < 1e-5


def test_a_prefix_cache_hit_serves_the_same_tokens(engine):
    prompt = prompt_of(21, 4)
    hits = engine.prefix_cache_hits
    engine.submit(prompt, greedy(6))
    (cold,) = engine.run()
    engine.submit(prompt, greedy(6))
    (hit,) = engine.run()
    assert engine.prefix_cache_hits == hits + 1
    assert (hit.tokens, hit.unmask_steps) == (cold.tokens, cold.unmask_steps)


def test_eos_cuts_a_row_at_its_first_occurrence_in_a_finished_block(engine):
    prompt = prompt_of(9, 5)
    engine.submit(prompt, greedy(12))
    (free,) = engine.run()
    eos = free.tokens[4]
    cut = free.tokens.index(eos) + 1
    engine.submit(prompt, greedy(12, eos=eos))
    (res,) = engine.run()
    assert res.finish_reason == "eos"
    assert res.tokens == free.tokens[:cut]
    assert res.unmask_steps == free.unmask_steps[:cut]


def test_a_sampled_row_is_its_seeds_and_leaves_the_greedy_row_alone(engine):
    prompt = prompt_of(9, 6)
    hot = SamplingParams(temperature=1.0, max_new_tokens=8, seed=11)
    engine.submit(prompt, greedy(8))
    (alone,) = engine.run()
    a = engine.submit(prompt, hot)
    b = engine.submit(prompt, greedy(8))
    both = {r.id: r for r in engine.run()}
    engine.submit(prompt, hot)
    (again,) = engine.run()
    assert both[b].tokens == alone.tokens
    assert both[a].tokens == again.tokens != alone.tokens
    assert max(both[a].tokens) < 256 and len(both[a].unmask_steps) == 8


def test_steps_ahead_answers_in_tokens(engine):
    """PR 30's rule: one unit of admission per TOKEN a live row is about to
    receive. A call of one block yields block_length tokens a row."""
    engine.submit(prompt_of(8, 7), greedy(9))
    engine.step()
    assert engine._block_size() == engine._steps_ahead() == 4
    engine.run()
    wide = InferenceEngine(engine.params, PCFG, slots=1, max_len=64,
                           prefill_len=8, decode_block=16)
    wide.submit(prompt_of(8, 7), greedy(30))
    wide.step()                       # 26 left: 7 blocks, the ladder's 4
    assert wide._steps_ahead() == 16
    wide.run()


# ------------------------------------------------------------ the refusals


def test_what_does_not_serve_block_diffusion_raises_by_name(params,
                                                            monkeypatch):
    with pytest.raises(NotImplementedError, match="kv_pages"):
        InferenceEngine(params, PCFG, slots=1, max_len=32, prefill_len=8,
                        kv_pages=4)
    with pytest.raises(ValueError, match="multiple of the model's block"):
        InferenceEngine(params, PCFG, slots=1, max_len=30, prefill_len=6)
    monkeypatch.setenv("DLROVER_TPU_SPEC_DEPTH", "4")
    with pytest.raises(NotImplementedError, match="speculation"):
        InferenceEngine(params, PCFG, slots=1, max_len=32, prefill_len=8)
    monkeypatch.delenv("DLROVER_TPU_SPEC_DEPTH")
    eng = InferenceEngine(params, PCFG, slots=1, max_len=32, prefill_len=8)
    run = eng.prefill_begin(prompt_of(9))
    while not eng.prefill_step(run):
        pass
    with pytest.raises(NotImplementedError, match="KVBundle"):
        eng.make_bundle(run)
    with pytest.raises(NotImplementedError, match="KVBundle"):
        eng.submit_prefilled(prompt_of(9), greedy(4), bundle=object())
    assert eng.warm_aot_step() is None


def test_paths_that_cannot_run_the_kinds_raise_by_name(params):
    batch = {"tokens": jnp.zeros((1, 9), jnp.int32)}
    with pytest.raises(NotImplementedError, match="served, not trained"):
        tfm.loss_fn(params, batch, PCFG)
    with pytest.raises(NotImplementedError, match="softmax_experts"):
        tfm.make_layer_fn(PCFG)               # pipeline.py, mpmd.py
    with pytest.raises(NotImplementedError, match="int8"):
        tfm.make_layer_fn(dataclasses.replace(PCFG, int8_matmuls=True),
                          experts={})
    with pytest.raises(NotImplementedError, match="pipeline"):
        tfm.forward(params, batch["tokens"],
                    dataclasses.replace(PCFG, pipeline_stages=2))
    with pytest.raises(NotImplementedError, match="block_causal"):
        tfm.forward(params, batch["tokens"], PCFG,
                    attention_fn=tfm.dense_attention)
    with pytest.raises(NotImplementedError, match="no rule table"):
        tfm.logical_axes(PCFG)
    with pytest.raises(NotImplementedError, match="runs 'heads'"):
        tfm.param_shapes(dataclasses.replace(PCFG, variant="gpt2"))
    with pytest.raises(ValueError, match="denoising_steps dividing"):
        dataclasses.replace(PCFG, denoising_steps=3)


def test_norm_eps_is_honoured_where_the_kind_sets_it(params):
    tokens = jnp.asarray(prompt_of(8))[None]
    base = tfm.forward(params, tokens, PCFG)
    loose = tfm.forward(params, tokens,
                        dataclasses.replace(PCFG, norm_eps=1e-2))
    assert np.abs(np.asarray(base) - np.asarray(loose)).max() > 1e-4
    # a llama-variant block of default kinds keeps its 1e-6
    tiny = tfm.CONFIGS["tiny"]
    p = tfm.init_params(tiny, jax.random.PRNGKey(0))
    t = jnp.zeros((1, 4), jnp.int32)
    assert np.array_equal(
        np.asarray(tfm.forward(p, t, tiny)),
        np.asarray(tfm.forward(p, t, dataclasses.replace(
            tiny, norm_eps=1e-2))))
