"""A cache tree of ROWS and STATE (DESIGN.md §23.5): ``attn_kind='mixers'``
(models/hybrid.py: block-sparse attention over a compressed-key cache beside
lightning linear-attention layers) against the plain reference
(``benchmark/reference/minicpm_sala.py``, which imports nothing of the
program), and through the serving engine, whose every "this row does not
advance" has to leave a state untouched. ``tiny-sala``: float32, a stack
``[sparse, lightning, lightning, sparse]``, blocks of 16 keys, the selection
binding from 65 keys on."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import minicpm_sala as ref
from dlrover_tpu.models import decode, hybrid
from dlrover_tpu.models import transformer as tfm
from dlrover_tpu.serving.engine import InferenceEngine, SamplingParams

CFG = tfm.CONFIGS["tiny-sala"]
SIZES = {"kernel_size": 8, "kernel_stride": 4, "block_size": 16, "topk": 4,
         "init_blocks": 1, "window_size": 32, "dense_len": 64}
# the configuration file tiny-sala would have
FILE = {
    "hidden_size": 64, "intermediate_size": 160, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "lightning_nh": 4,
    "lightning_nkv": 4, "lightning_head_dim": 16, "vocab_size": 256,
    "num_hidden_layers": 4, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn",
                    "minicpm4"],
    "scale_emb": 12, "scale_depth": 1.4, "dim_model_base": 16,
    "assumed": {"sparse_config": SIZES}, "serving": {"prefill_len": 32},
}
SEED = 3
# float32 on both sides, the reference at Precision.HIGHEST: what is left
# is the order of float32 sums (measured 7e-7 at logits of spread 0.25)
TOL = 2e-5


@pytest.fixture(scope="module", autouse=True)
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    """The program's tree, made of the reference's leaves."""
    tree = {n: ref.weight(FILE, SEED, ref.TOP, n)
            for n in ("embed", "ln_f", "lm_head")}
    for kind in hybrid.KINDS:
        layers = [i for i in range(4) if ref.kind_of(FILE, i) == kind]
        tree[f"{kind}_layers"] = {
            n: jnp.stack([ref.weight(FILE, SEED, i, n) for i in layers])
            for n in ref.leaf_shapes(FILE, layers[0])}
    assert jax.tree.map(lambda a: tuple(a.shape), tree) == \
        hybrid.param_shapes(CFG)
    return tree


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(1).integers(0, 256, 160)


@pytest.fixture(scope="module")
def want(tokens):
    return np.asarray(ref.logits(FILE, SEED, tokens))


def _cached(params, tokens, widths, max_len=192):
    """Logits of ``tokens`` fed through ``forward_cached`` in calls of the
    given widths (a width larger than what is left is pad-tailed and told
    so), rows at positions of their own as the engine holds them."""
    fc = jax.jit(lambda p, t, c, r: decode.forward_cached(p, t, c, CFG, real=r))
    cache = decode.init_cache(CFG, 1, max_len)
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


def test_the_uncached_forward_is_the_references(params, tokens, want):
    got = tfm.forward(params, jnp.asarray(tokens)[None], CFG)[0]
    assert np.abs(np.asarray(got) - want).max() < TOL
    assert want.std() > 0.1


@pytest.mark.parametrize("widths", [
    # chunks of 32 (a boundary inside compression windows: stride 4, kernel
    # 8), the last pad-tailed, then a token at a time across dense_len
    [32, 32, 32] + [1] * 64,
    # chunks of 24: boundaries that are no multiple of the block (16), a
    # pad tail of 8, the switch to the selection inside a chunk
    [24] * 7,
    # one odd chunk, so that later chunks start inside a stride
    [30, 32, 32, 32, 34],
    [1] * 80 + [40, 40],
])
def test_chunked_prefill_then_cached_decode_is_the_references_forward(
        params, tokens, want, widths):
    got, cache = _cached(params, tokens, widths)
    assert np.abs(got - want).max() < TOL
    # every real token counted once; 2 sparse layers x 2 groups a token
    assert int(cache["counters"]["sparse_queries"]) <= 4 * 160


def test_a_call_that_holds_a_row_back_leaves_state_and_compressed_keys(
        params, tokens):
    """Two rows, one told that none of its tokens is real: its state and
    its compressed keys come back to the bit, whatever it was fed."""
    _, one = _cached(params, tokens[:100], [25] * 4)
    cache = jax.tree.map(lambda a: jnp.concatenate([a, a], axis=1)
                         if a.ndim > 1 else a, {k: v for k, v in one.items()
                                                if k != "counters"})
    cache["counters"] = one["counters"]
    cache["pos"] = jnp.asarray([100, 100])
    fed = jnp.asarray(tokens[100:104])
    for width, real in ((1, [1, 0]), (4, [4, 0]), (4, [2, 0])):
        _, new = decode.forward_cached(
            params, jnp.stack([fed[:width]] * 2), cache, CFG,
            real=jnp.asarray(real))
        for held in ("kc",):
            assert np.array_equal(np.asarray(new[held][:, 1]),
                                  np.asarray(cache[held][:, 1]))
        state, was = new["state"]["s"], cache["state"]["s"]
        assert np.array_equal(np.asarray(state[:, 1]), np.asarray(was[:, 1]))
        assert not np.array_equal(np.asarray(state[:, 0]),
                                  np.asarray(was[:, 0]))


def test_the_chunk_form_of_lightning_is_the_recurrence():
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 37, 4, 16)), jnp.float32)
               for _ in range(3))
    s0 = jnp.asarray(rng.normal(size=(1, 2, 4, 16, 16)), jnp.float32)
    real = jnp.asarray([37, 20])
    o, (stack, _) = hybrid._lightning_attend(q, k, v, (s0, 0), real_b=real)
    for b, n in enumerate([37, 20]):
        want_o, want_s = ref.lightning_recurrence(
            q[b, :n], k[b, :n], v[b, :n], s0[0, b], "")
        assert np.abs(np.asarray(o[b, :n] - want_o)).max() < 1e-4
        assert np.abs(np.asarray(stack[0, b] - want_s)).max() < 1e-4
    # decay as assumed: exp(-2^(-8 (h + 1) / H))
    assert np.allclose(ref.decays(4), np.exp(-2.0 ** (-2.0 * np.arange(1, 5))))


def test_the_selection_is_the_references_blocks_exactly():
    rng = np.random.default_rng(5)
    n = 160
    k = jnp.asarray(rng.normal(size=(n, 2, 16)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(n, 4, 16)), jnp.float32) * 3.0
    k1, exists = ref.compressed_keys(k, SIZES)
    assert int(exists.sum()) == (n - 8) // 4 + 1
    t = jnp.arange(64, n)
    want = np.asarray(ref.selected_blocks(q[64:], k1, t, SIZES))  # [G,Q,4]
    got = hybrid._select(
        q[64:].reshape(1, n - 64, 2, 2, 16).transpose(2, 0, 1, 3, 4),
        jnp.asarray(k1)[:, None].transpose(2, 1, 0, 3), t[None], CFG)
    assert np.array_equal(np.sort(np.asarray(got)[:, 0], axis=-1),
                          np.sort(want, axis=-1))
    # block 0 and the two blocks ending with the query's own are forced,
    # and one block is chosen by its score
    own = np.asarray(t) // 16
    for g in range(2):
        for i in range(n - 64):
            assert {0, own[i] - 1, own[i]} <= set(want[g, i].tolist())
            assert len(set(want[g, i].tolist())) == 4
    scored = {tuple(sorted(set(want[g, i].tolist())
                           - {0, own[i] - 1, own[i]}))
              for g in range(2) for i in range(n - 64)}
    assert len(scored) > 3          # the scores do choose


def test_the_cache_tree_says_which_stacks_have_positions():
    cache = decode.init_cache(CFG, 3, 64)
    rows, state = decode.cache_stacks(cache), decode.cache_state(cache)
    assert {k: v.shape for k, v in rows.items()} == {
        "k": (4, 3, 64, 16), "v": (4, 3, 64, 16), "kc": (4, 3, 16, 16)}
    assert {k: v.shape for k, v in state.items()} == {"s": (2, 3, 4, 16, 16)}
    assert state["s"].dtype == jnp.float32
    # a model of rows alone has no state
    plain = decode.init_cache(tfm.CONFIGS["tiny"], 2, 16)
    assert decode.cache_state(plain) == {} and set(
        decode.cache_stacks(plain)) == {"k", "v"}
    with pytest.raises(ValueError, match="multiple of the sparse block"):
        decode.init_cache(CFG, 1, 40)
    with pytest.raises(ValueError, match="mixer_types"):
        dataclasses.replace(CFG, mixer_types=("sparse",) * 4)
    with pytest.raises(NotImplementedError, match="mixers"):
        tfm.make_layer_fn(CFG)


# ------------------------------------------------------------- the engine


def _engine(params, **kw):
    kw = {"slots": 4, "max_len": 192, "prefill_len": 32, "decode_block": 8,
          **kw}
    return InferenceEngine(params, CFG, **kw)


def _greedy(n, **kw):
    return SamplingParams(temperature=0.0, max_new_tokens=n, **kw)


@pytest.fixture(scope="module")
def alone(params, tokens):
    """A 103-token prompt served alone, 40 tokens: the reference's greedy
    continuation (the logits the engine decoded from are the reference's
    to TOL, so the argmax is, wherever the reference decides by more)."""
    prompt = tokens[:103].tolist()
    eng = _engine(params)
    eng.submit(prompt, _greedy(40))
    served = eng.run()[0].tokens
    seq = list(prompt)
    for _ in range(40):
        seq.append(int(np.asarray(ref.logits(
            FILE, SEED, np.asarray(seq + [0] * (-len(seq) % 16))
        ))[len(seq) - 1].argmax()))
    assert served == seq[103:]
    return prompt, served


def test_a_request_beside_rows_that_freeze_finish_idle_or_arrive(
        params, tokens, alone):
    """The same request beside a row that reaches its budget inside a
    block (frozen), one that samples its eos, an idle slot, and one
    admitted mid-way: the same tokens, and the same logits after them."""
    prompt, served = alone
    rng = np.random.default_rng(7)
    eng = _engine(params)
    first = eng.submit(prompt, _greedy(40))
    eng.submit(rng.integers(0, 256, 70).tolist(), _greedy(5))
    eos = served[10]                       # a token this model does emit
    eng.submit(prompt[:90], _greedy(30, eos_id=eos))
    for _ in range(3):
        eng.step()
    eng.submit(rng.integers(0, 256, 90).tolist(), _greedy(21))
    results = {r.id: r for r in eng.run()}
    assert results[first].tokens == served
    assert len(results) == 4
    # the logits of the last prompt token through the timed engine's chunk
    # program are the reference's
    run = eng.prefill_begin(prompt)
    while not eng.prefill_step(run):
        pass
    want = np.asarray(ref.logits(
        FILE, SEED, np.asarray(prompt + [0] * (-len(prompt) % 16))))[102]
    assert np.abs(np.asarray(run.last) - want).max() < TOL


@pytest.mark.parametrize("prefill_len", [8, 24, 64])
def test_a_final_chunk_with_a_pad_tail_is_the_unpadded_prompt(
        params, alone, prefill_len):
    prompt, served = alone               # 103 = 12 x 8 + 7 = 4 x 24 + 7
    eng = _engine(params, prefill_len=prefill_len)
    eng.submit(prompt, _greedy(40))
    assert eng.run()[0].tokens == served


def test_a_prefix_hit_resumes_with_the_boundarys_state(params, alone):
    prompt, served = alone
    eng = _engine(params, prefix_cache_entries=2)
    eng.submit(prompt, _greedy(40))
    assert eng.run()[0].tokens == served and eng.prefix_cache_hits == 0
    # the same prompt again resumes at 96 with that boundary's rows AND
    # state: one chunk runs, and the answer is the same
    before = eng._chunks_run
    eng.submit(prompt, _greedy(40))
    assert eng.run()[0].tokens == served
    assert eng.prefix_cache_hits == 1 and eng._chunks_run - before == 1
    # an entry stands for its own prefix alone: a prompt that shares only
    # 64 of its 96 tokens finds nothing (the state at 96 has folded in
    # tokens it does not have), and is served as it is alone
    other = prompt[:64] + [(t + 1) % 256 for t in prompt[64:]]
    hits = eng.prefix_cache_hits
    eng.submit(other, _greedy(12))
    got = eng.run()[0].tokens
    assert eng.prefix_cache_hits == hits
    cold = _engine(params)
    cold.submit(other, _greedy(12))
    assert cold.run()[0].tokens == got


def test_what_assumes_token_addressed_rows_raises_by_name(params, monkeypatch):
    with pytest.raises(NotImplementedError, match="carries state"):
        _engine(params, kv_pages=8, page_size=32)
    eng = _engine(params)
    run = eng.prefill_begin([1, 2, 3])
    while not eng.prefill_step(run):
        pass
    with pytest.raises(NotImplementedError, match="carries state"):
        eng.make_bundle(run)
    with pytest.raises(NotImplementedError, match="carries state"):
        eng.submit_prefilled([1, 2, 3], _greedy(2), bundle=object())
    monkeypatch.setenv("DLROVER_TPU_SPEC_DEPTH", "4")
    with pytest.raises(NotImplementedError, match="carries state"):
        _engine(params)


def test_the_engine_counts_rows_and_state_apart(params):
    eng = _engine(params)
    # rows: 2 sparse layers x (k + v: 2 heads x 16 x 4 B, and a quarter of
    # that once more for the compressed keys a stride of 4 apart)
    assert eng.cache_bytes_per_token == 2 * (2 * 2 * 16 * 4 + 2 * 16 * 4 // 4)
    # state: 2 lightning layers x 4 heads x 16 x 16 float32
    assert eng.state_bytes_per_slot == 2 * 4 * 16 * 16 * 4
    plain = InferenceEngine(
        tfm.init_params(tfm.CONFIGS["tiny"], jax.random.PRNGKey(0)),
        tfm.CONFIGS["tiny"], slots=2, max_len=32)
    assert plain.state_bytes_per_slot == 0
    eng.submit(list(range(70)), _greedy(9))
    eng.run()
    fields = decode.cache_counter_fields(eng._cache)
    assert set(fields) == {"sparse_keys_selected", "sparse_keys_scored",
                           "sparse_queries", "context_tokens",
                           "sparse_keys_share"}
