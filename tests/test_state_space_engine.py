"""``tests/test_state_space.py``'s model THROUGH THE SERVING ENGINE, whose
every "this row does not advance" (a frozen row, an idle slot, a pad tail,
an install, a prefix-cache resume) has to leave a Mamba-2 layer's window and
state as the reference's token-by-token recurrence would have them. A file
of its own: a test file runs on one worker."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import serve_child_ssm as child
from benchmark.drivers.serve_gateway_ssm import REHEARSAL_CONFIG
from benchmark.reference import nemotron_h as ref
from dlrover_tpu.models import decode
from dlrover_tpu.serving.engine import InferenceEngine, SamplingParams

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




def _engine(model, **kw):
    cfg, params = model
    kw = {"slots": 4, "max_len": 192, "prefill_len": 24, "decode_block": 8,
          **kw}
    return InferenceEngine(params, cfg, **kw)


def _greedy(n, **kw):
    return SamplingParams(temperature=0.0, max_new_tokens=n, **kw)


@pytest.fixture(scope="module")
def alone(model, tokens):
    """A 55-token prompt served alone, 30 tokens: the reference's greedy
    continuation."""
    prompt = tokens[:55].tolist()
    eng = _engine(model)
    eng.submit(prompt, _greedy(30))
    served = eng.run()[0].tokens
    seq = list(prompt)
    for _ in range(30):       # one width, so the reference compiles once
        seq.append(int(np.asarray(ref.logits(
            FILE, SEED, np.asarray(seq + [0] * (88 - len(seq)))
        ))[len(seq) - 1].argmax()))
    assert served == seq[55:]
    return prompt, served


@pytest.mark.timeout(300)
def test_a_request_beside_rows_that_freeze_finish_idle_or_arrive(
        model, tokens, alone):
    """The same request beside a row that reaches its budget inside a
    block (frozen), one that samples its eos, an idle slot, and one
    admitted mid-way: the same tokens, and the same logits after them."""
    prompt, served = alone
    rng = np.random.default_rng(7)
    eng = _engine(model)
    first = eng.submit(prompt, _greedy(30))
    eng.submit(rng.integers(0, 128, 40).tolist(), _greedy(5))
    eos = served[10]                       # a token this model does emit
    eng.submit(prompt[:50], _greedy(25, eos_id=eos))
    for _ in range(3):
        eng.step()
    eng.submit(rng.integers(0, 128, 70).tolist(), _greedy(21))
    results = {r.id: r for r in eng.run()}
    assert results[first].tokens == served
    assert len(results) == 4
    run = eng.prefill_begin(prompt)
    while not eng.prefill_step(run):
        pass
    want = np.asarray(ref.logits(FILE, SEED, np.asarray(prompt)))[54]
    assert np.abs(np.asarray(run.last) - want).max() < TOL


@pytest.mark.timeout(300)
@pytest.mark.parametrize("prefill_len", [8, 12, 64])
def test_a_final_chunk_with_a_pad_tail_is_the_unpadded_prompt(
        model, alone, prefill_len):
    prompt, served = alone              # 55 = 6 x 8 + 7 = 4 x 12 + 7
    eng = _engine(model, prefill_len=prefill_len)
    eng.submit(prompt, _greedy(30))
    assert eng.run()[0].tokens == served


@pytest.mark.timeout(300)
def test_a_prefix_hit_resumes_with_the_boundarys_window_and_state(
        model, alone):
    prompt, served = alone
    eng = _engine(model, prefix_cache_entries=2)
    eng.submit(prompt, _greedy(30))
    assert eng.run()[0].tokens == served and eng.prefix_cache_hits == 0
    # the same prompt again resumes at 48 with that boundary's rows, window
    # AND state: one chunk runs, and the answer is the same
    before = eng._chunks_run
    eng.submit(prompt, _greedy(30))
    assert eng.run()[0].tokens == served
    assert eng.prefix_cache_hits == 1 and eng._chunks_run - before == 1
    # the logits of the first tokens BEHIND the boundary, where a window
    # that did not resume would show: the reference's
    for n in (49, 50, 51):
        run = eng.prefill_begin(prompt[:n])
        while not eng.prefill_step(run):
            pass
        want = np.asarray(ref.logits(FILE, SEED, np.asarray(prompt[:n])))[-1]
        assert np.abs(np.asarray(run.last) - want).max() < TOL
    assert eng.prefix_cache_hits >= 4


def test_what_assumes_token_addressed_rows_raises_by_name(model, monkeypatch):
    with pytest.raises(NotImplementedError, match="carries state"):
        _engine(model, kv_pages=8, page_size=32)
    eng = _engine(model)
    run = eng.prefill_begin([1, 2, 3])
    while not eng.prefill_step(run):
        pass
    with pytest.raises(NotImplementedError, match="carries state"):
        eng.make_bundle(run)
    with pytest.raises(NotImplementedError, match="carries state"):
        eng.submit_prefilled([1, 2, 3], _greedy(2), bundle=object())
    monkeypatch.setenv("DLROVER_TPU_SPEC_DEPTH", "4")
    with pytest.raises(NotImplementedError, match="carries state"):
        _engine(model)


@pytest.mark.timeout(300)
def test_the_engine_counts_rows_and_both_state_leaves(model):
    eng = _engine(model)
    # rows: 1 attention layer x (k + v: 2 heads x 16 x 4 B)
    assert eng.cache_bytes_per_token == 2 * 2 * 16 * 4
    # state: 3 Mamba-2 layers x (4 heads x 16 x 8 float32 + a window of 3
    # inputs of 96 channels)
    assert eng.state_bytes_per_slot == 3 * (4 * 16 * 8 * 4 + 3 * 96 * 4)
    eng.submit(list(range(70)), _greedy(9))
    eng.run()
    fields = decode.cache_counter_fields(eng._cache)
    assert {"ssm_row_steps", "experts_hit", "experts_hit_share",
            "expert_tokens", "context_tokens"} <= set(fields)
