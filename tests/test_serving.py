"""Continuous-batching inference engine (serving/engine.py).

Reference analog: the vLLM backend the reference's RLHF stack serves
through (atorch rl/inference_backend) — here validated for the property
that matters: slot-batched decode with per-row positions produces exactly
the tokens a solo greedy ``generate`` would, while requests of different
lengths join and leave the batch mid-flight.
"""

from __future__ import annotations

import contextlib
import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dlrover_tpu.models import transformer as tfm
from dlrover_tpu.models.decode import (
    PRODUCT_LEAVES,
    generate,
    weights_at_rest,
)
from dlrover_tpu.serving import InferenceEngine, SamplingParams

CFG = tfm.CONFIGS["tiny"]


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(CFG, jax.random.PRNGKey(0))


@pytest.mark.timeout(300)
# slow tier (tier-1 envelope): among the heaviest bodies in this
# file on XLA:CPU; core behavior stays covered by the lighter
# tests in-tier. `pytest tests/` still runs it.
@pytest.mark.slow
def test_matches_solo_greedy_generate(params):
    """Slot-batched greedy == single-request generate, per request."""
    prompts = [[5, 9, 2], [7, 7, 7, 7, 1], [3]]
    eng = InferenceEngine(params, CFG, slots=2, max_len=64,
                          prefill_len=8)
    ids = {}
    for p in prompts:
        ids[eng.submit(p, SamplingParams(temperature=0.0,
                                         max_new_tokens=6))] = p
    results = {r.id: r for r in eng.run()}
    assert len(results) == 3
    for rid, prompt in ids.items():
        solo = generate(
            params, jnp.asarray([prompt], jnp.int32), CFG,
            gen_len=6, key=jax.random.PRNGKey(1), temperature=0.0,
        )
        expect = np.asarray(solo)[0, len(prompt):].tolist()
        assert results[rid].tokens == expect, (
            rid, results[rid].tokens, expect
        )
        assert results[rid].finish_reason == "length"


@pytest.mark.timeout(300)
def test_slot_reuse_and_mixed_lengths(params):
    """More requests than slots with different max_new: slots recycle."""
    eng = InferenceEngine(params, CFG, slots=2, max_len=64,
                          prefill_len=8)
    lens = [2, 9, 4, 6, 3]
    ids = [
        eng.submit([i + 1], SamplingParams(temperature=0.0,
                                           max_new_tokens=n))
        for i, n in enumerate(lens)
    ]
    results = {r.id: r for r in eng.run()}
    assert len(results) == 5
    for rid, n in zip(ids, lens):
        assert len(results[rid].tokens) == n


@pytest.mark.timeout(300)
def test_eos_retires_early(params):
    eng = InferenceEngine(params, CFG, slots=1, max_len=64,
                          prefill_len=8)
    # discover which token greedy decoding emits first, use it as eos
    probe = generate(params, jnp.asarray([[5, 9, 2]], jnp.int32), CFG,
                     gen_len=1, key=jax.random.PRNGKey(0),
                     temperature=0.0)
    eos = int(np.asarray(probe)[0, -1])
    rid = eng.submit([5, 9, 2], SamplingParams(
        temperature=0.0, max_new_tokens=20, eos_id=eos))
    res = {r.id: r for r in eng.run()}[rid]
    assert res.finish_reason == "eos"
    assert res.tokens == [eos]


@pytest.mark.timeout(300)
def test_validation_errors(params):
    eng = InferenceEngine(params, CFG, slots=1, max_len=32,
                          prefill_len=8)
    # prompt > prefill_len is fine now (chunked prefill) as long as the
    # budget fits max_len
    eng.submit(list(range(9)), SamplingParams(max_new_tokens=4))
    with pytest.raises(ValueError):
        eng.submit([1], SamplingParams(max_new_tokens=40))  # > max_len
    with pytest.raises(ValueError):
        eng.submit(list(range(30)))  # prompt + default 64 > max_len


@pytest.mark.timeout(300)
# slow tier (tier-1 envelope): among the heaviest bodies in this
# file on XLA:CPU; core behavior stays covered by the lighter
# tests in-tier. `pytest tests/` still runs it.
@pytest.mark.slow
def test_block_decode_matches_per_token(params):
    """decode_block > 1 produces the same greedy tokens as block=1."""
    out = {}
    for block in (1, 8):
        eng = InferenceEngine(params, CFG, slots=2, max_len=64,
                              prefill_len=8, decode_block=block)
        rids = [
            eng.submit([4, 2], SamplingParams(temperature=0.0,
                                              max_new_tokens=12)),
            eng.submit([9], SamplingParams(temperature=0.0,
                                           max_new_tokens=7)),
        ]
        res = {r.id: r for r in eng.run()}
        out[block] = [res[r].tokens for r in rids]
    assert out[1] == out[8]
    assert len(out[1][0]) == 12 and len(out[1][1]) == 7


@pytest.mark.timeout(300)
def test_eos_request_no_longer_serializes_batchmates(params):
    """ISSUE 12 satellite: eos is observed per-slot INSIDE the compiled
    block — one eos-bearing request must not collapse the whole batch
    to token-at-a-time decode, and its mate's tokens are unchanged."""
    eng = InferenceEngine(params, CFG, slots=2, max_len=64,
                          prefill_len=8, decode_block=8)
    # reference first, on the SAME engine (seeded per-request streams
    # are batch-independent, so engine reuse is sound and saves a
    # second 3-program compile in the tier-1 envelope)
    ref_mate = eng.submit([4, 2], SamplingParams(
        temperature=0.0, max_new_tokens=12))
    want_mate = {r.id: r for r in eng.run()}[ref_mate].tokens
    blocks = _spy_step_block(eng)
    probe = generate(params, jnp.asarray([[5, 9, 2]], jnp.int32), CFG,
                     gen_len=1, key=jax.random.PRNGKey(0),
                     temperature=0.0)
    eos = int(np.asarray(probe)[0, -1])
    r_eos = eng.submit([5, 9, 2], SamplingParams(
        temperature=0.0, max_new_tokens=20, eos_id=eos))
    r_mate = eng.submit([4, 2], SamplingParams(
        temperature=0.0, max_new_tokens=12))
    res = {r.id: r for r in eng.run()}
    # the eos request still stops AT its eos...
    assert res[r_eos].finish_reason == "eos"
    assert res[r_eos].tokens == [eos]
    # ...while whole blocks ran (pre-fix this was all 1s): the eos row
    # is frozen inside the first, and the mate's 12 tokens are a whole
    # block and ONE call of 4 for its tail
    assert [n for n, _ in blocks] == [8, 4], blocks
    # and the mate decoded exactly what a no-eos batch produces
    assert res[r_mate].tokens == want_mate


def _count_chunks(eng) -> list:
    """Spy on the engine's prefill program: one entry per chunk run."""
    calls = []
    orig = eng._prefill_chunk

    def spy(*a):
        calls.append(True)
        return orig(*a)

    eng._prefill_chunk = spy
    return calls


def _stall_count() -> int:
    from dlrover_tpu.serving import engine as engine_mod

    samp = engine_mod._decode_stall_seconds.samples()
    return samp[0]["count"] if samp else 0


@pytest.mark.timeout(300)
@pytest.mark.parametrize("decode_block", [1, 8])
def test_chunked_admission_bounds_decode_stall(params, decode_block):
    """A long prompt joining a live batch runs at most one prefill chunk
    per decode STEP of the block beside it (ISSUE 12's one chunk per
    engine step at ``decode_block`` 1, ISSUE 30's ``n_steps`` at 8): a
    decoding request waits for one chunk per token it is about to
    receive, keeps emitting on every engine step of the admission, and
    each admitting step lands ONCE in the stall histogram."""
    eng = InferenceEngine(params, CFG, slots=2, max_len=64,
                          prefill_len=4, decode_block=decode_block)
    chunk_calls = _count_chunks(eng)
    active = eng.submit([1, 2], SamplingParams(temperature=0.0,
                                               max_new_tokens=40))
    eng.step()                      # admit + first block
    assert eng._active[0] is not None
    long_prompt = list((np.arange(43) * 3 + 1) % CFG.vocab_size)
    eng.submit(long_prompt, SamplingParams(temperature=0.0,
                                           max_new_tokens=16))  # 11 chunks
    emitted_at = [len(eng._emitted[0])]
    admitting_steps = 0
    stalls_before = _stall_count()
    while not any(r is not None and r.id != active
                  for r in eng._active):
        chunks_before = len(chunk_calls)
        eng.step()
        ran = len(chunk_calls) - chunks_before
        # the block this step's admission ran beside: a whole one
        # while the live request has that much left (ISSUE 32), else
        # the smallest power of two that holds its tail
        left = 40 - emitted_at[-1]
        beside = min(decode_block, 1 << (left - 1).bit_length())
        assert 1 <= ran <= beside     # at decode_block 1: exactly one
        admitting_steps += 1
        emitted_at.append(len(eng._emitted[0]))
        assert admitting_steps < 30
    # the live request made progress on EVERY step of the admission
    assert all(b > a for a, b in zip(emitted_at, emitted_at[1:]))
    assert len(chunk_calls) == 1 + 11
    assert admitting_steps == (11 if decode_block == 1 else 2)
    # one observation per admitting step, however many chunks it ran
    assert _stall_count() - stalls_before == admitting_steps
    eng.run()


def _spy_step_block(eng) -> list:
    """Spy on the decode program: ``(n_steps, remaining of each slot)``
    per call."""
    calls = []
    orig = eng._step_block

    def spy(*a, n_steps):
        calls.append((n_steps, np.asarray(a[10]).tolist()))
        return orig(*a, n_steps=n_steps)

    eng._step_block = spy
    return calls


def _budget_requests(temperature, eos_of=None):
    """One long row that keeps every block whole, beside rows whose
    budgets end at every remainder 1-7 of a block of 8 (a row is
    installed between calls, so ``max_new_tokens`` 8k + r ends r steps
    into its last block)."""
    rng = np.random.default_rng(11)
    budgets = [72, 1, 9, 2, 10, 3, 11, 4, 12, 5, 13, 6, 14, 7, 15]
    return [
        (list(rng.integers(1, CFG.vocab_size, 2 + i % 5)),
         SamplingParams(temperature=temperature, top_p=0.9,
                        max_new_tokens=m, seed=300 + i,
                        eos_id=None if eos_of is None else eos_of(i)))
        for i, m in enumerate(budgets)]


def _streams(params, reqs, block):
    eng = InferenceEngine(params, CFG, slots=3, max_len=128,
                          prefill_len=8, decode_block=block)
    calls = _spy_step_block(eng)
    ids = [eng.submit(p, sp) for p, sp in reqs]
    got = {r.id: r for r in eng.run()}
    return [got[i] for i in ids], calls


@pytest.mark.timeout(300)
@pytest.mark.parametrize("eos", [False, True], ids=["no_eos", "eos"])
@pytest.mark.parametrize("temperature", [0.0, 0.9],
                         ids=["greedy", "sampled"])
def test_rows_frozen_at_their_budget_serve_the_same_tokens(
        params, temperature, eos):
    """ISSUE 32 (a): ``decode_block`` 1 against 8 with rows whose
    budgets end at every remainder of a block, greedy and seeded
    sampling, with and without an eos inside a block: every request
    receives the same tokens, and at 8 every call beside the long row
    is a whole block, the short rows frozen inside it."""
    reqs = _budget_requests(temperature)
    one, calls1 = _streams(params, reqs, 1)
    if eos:
        # each short row's third token (where it has more) as ITS eos:
        # it stops the row inside a block, ahead of its budget
        plain = [r.tokens for r in one]
        reqs = _budget_requests(
            temperature, lambda i: (plain[i][2]
                                    if 3 < len(plain[i]) < 72 else None))
        one, calls1 = _streams(params, reqs, 1)
    eight, calls8 = _streams(params, reqs, 8)
    assert [r.tokens for r in eight] == [r.tokens for r in one]
    assert ([r.finish_reason for r in eight]
            == [r.finish_reason for r in one])
    if eos:
        assert {r.finish_reason for r in one} == {"eos", "length"}
        assert all(r.tokens[-1] == sp.eos_id for r, (_, sp)
                   in zip(one, reqs) if r.finish_reason == "eos")
    else:
        assert [len(r.tokens) for r in one] == [
            sp.max_new_tokens for _, sp in reqs]
    assert {n for n, _ in calls1} == {1}
    whole = [(n, rem) for n, rem in calls8 if max(rem) >= 8]
    assert len(whole) >= 9 and {n for n, _ in whole} == {8}
    # rows with less than a block left rode those whole calls
    assert {r for _, rem in whole for r in rem if 0 < r < 8} >= (
        {1, 2, 3} if eos else set(range(1, 8)))


@pytest.mark.timeout(300)
@pytest.mark.parametrize("case", ["batchmate", "lone"])
def test_block_is_sized_by_the_row_with_the_most_left(params, case):
    """ISSUE 32 (b): while one row has a whole block left every call is
    a whole block, though a batchmate has 3 left; a lone row with 3
    left rides ONE call of 4 and waits for no dead steps."""
    eng = InferenceEngine(params, CFG, slots=2, max_len=64,
                          prefill_len=8, decode_block=8)
    calls = _spy_step_block(eng)
    short = eng.submit([4, 2], SamplingParams(temperature=0.0,
                                              max_new_tokens=3))
    if case == "batchmate":
        eng.submit([9, 1, 5], SamplingParams(temperature=0.0,
                                             max_new_tokens=19))
    res = {r.id: r for r in eng.run()}
    assert len(res[short].tokens) == 3
    if case == "lone":
        assert calls == [(4, [3, 0])]
        assert eng._steps_ahead() == 1    # an idle engine: one unit
    else:
        # 19 tokens: two whole blocks, then a tail of 3 in a call of 4
        assert calls == [(8, [3, 19]), (8, [0, 11]), (4, [0, 3])]


@pytest.mark.timeout(300)
@pytest.mark.parametrize("case", ["inside", "at_max_len"])
def test_frozen_row_stops_at_its_budget(params, case):
    """ISSUE 32 (c): a row whose budget ends inside a block keeps its
    ``pos`` and its draw count at prompt + budget, also when that is
    ``max_len`` itself, and the request installed next in its slot
    decodes what it decodes in a fresh engine."""
    max_len = 64
    plen = 5 if case == "inside" else max_len - 3
    prompt = list((np.arange(plen) * 7 + 3) % CFG.vocab_size)
    long_sp = SamplingParams(temperature=0.8, max_new_tokens=30, seed=5)
    nxt = ([8, 6, 4], SamplingParams(temperature=0.8, max_new_tokens=9,
                                     seed=6))

    def engine():
        return InferenceEngine(params, CFG, slots=2, max_len=max_len,
                               prefill_len=8, decode_block=8)

    fresh = engine()
    want = fresh.submit(*nxt)
    want = {r.id: r.tokens for r in fresh.run()}[want]

    eng = engine()
    eng.submit([1, 2], long_sp)
    eng.submit(prompt, SamplingParams(temperature=0.8, max_new_tokens=3,
                                      seed=7))
    calls = _spy_step_block(eng)
    eng.step()
    assert calls == [(8, [30, 3])]
    assert eng._active[1] is None            # retired after the call
    pos = np.asarray(eng._cache["pos"])
    assert pos.tolist() == [2 + 8, plen + 3]
    assert eng._sampled.tolist() == [8, 3]
    got = eng.submit(*nxt)
    res = {r.id: r for r in eng.run()}
    assert res[got].tokens == want
    assert len(res[got].tokens) == 9


@pytest.mark.timeout(300)
def test_kv_ledgers_clean_after_rows_finish_inside_blocks(params):
    """ISSUE 32 (d): with the page pool on, rows that reach their
    budget inside a block hold no page past it: every lease returns and
    the ledgers balance."""
    from dlrover_tpu.serving.engine import check_kv_ledgers

    eng = InferenceEngine(params, CFG, slots=3, max_len=64,
                          prefill_len=8, decode_block=8, kv_pages=24)
    calls = _spy_step_block(eng)
    reqs = _budget_requests(0.0)
    reqs[0] = (reqs[0][0], dataclasses.replace(reqs[0][1],
                                               max_new_tokens=40))
    ids = [eng.submit(p, sp) for p, sp in reqs]
    res = {r.id: r for r in eng.run()}
    assert [len(res[i].tokens) for i in ids] == [
        sp.max_new_tokens for _, sp in reqs]
    assert any(n == 8 and 0 < min(r for r in rem if r) < 8
               for n, rem in calls)
    ledger = eng.kv_page_ledger()
    assert ledger["ok"] and ledger["leased"] == 0
    assert eng.free_pages == 24
    assert check_kv_ledgers() == []


def _engine_step_spans(journal_dir) -> list[dict]:
    from dlrover_tpu.telemetry.report import load_events

    return [e for e in load_events(str(journal_dir / "events.jsonl"))
            if e["ev"] == "e" and e["name"] == "engine_step"]


@pytest.fixture()
def journal_dir(tmp_path, monkeypatch):
    from dlrover_tpu.common.constants import EnvKey
    from dlrover_tpu.telemetry import journal as journal_mod

    monkeypatch.setenv(EnvKey.JOURNAL_DIR, str(tmp_path / "journal"))
    monkeypatch.delenv(EnvKey.JOURNAL_MAX_MB, raising=False)
    monkeypatch.setattr(journal_mod, "_cached", None)
    yield tmp_path / "journal"
    journal_mod._cached = None


@pytest.mark.timeout(300)
def test_long_prompt_admits_in_chunks_over_block_steps(params,
                                                       journal_dir):
    """ISSUE 30 (a): beside a live batch decoding blocks of 8, a queued
    prompt of 11 chunks finishes its admission in ceil(11 / 8) engine
    steps, and the ``engine_step`` span says how many chunks each ran."""
    eng = InferenceEngine(params, CFG, slots=2, max_len=64,
                          prefill_len=4, decode_block=8)
    eng.submit([1, 2], SamplingParams(temperature=0.0,
                                      max_new_tokens=48))
    eng.step()
    long_prompt = list((np.arange(43) * 3 + 1) % CFG.vocab_size)
    eng.submit(long_prompt, SamplingParams(temperature=0.0,
                                           max_new_tokens=16))
    assert eng.step() == 1          # 8 chunks beside a block of 8
    assert eng._pending is not None and eng._pending.run.chunks == 8
    assert eng.step() == 2          # the last 3, and the install
    assert eng._pending is None
    eng.step()                      # nothing left to admit
    spans = _engine_step_spans(journal_dir)
    assert [e["prefill_chunks"] for e in spans] == [1, 8, 3, 0]
    assert [e["n_steps"] for e in spans] == [8, 8, 8, 8]
    assert [e["decoding_slots"] for e in spans] == [1, 1, 2, 2]
    eng.run()


@pytest.mark.timeout(300)
def test_decode_block_span_counts_frozen_row_steps(params, journal_dir):
    """ISSUE 32 (e): the ``decode_block`` span says how many row-steps
    of the call went to rows past their budget, the gauge their share
    of the call's rows x steps; ``slots`` stays the rows in the call."""
    from dlrover_tpu.serving import engine as engine_mod
    from dlrover_tpu.telemetry.report import load_events

    eng = InferenceEngine(params, CFG, slots=4, max_len=64,
                          prefill_len=8, decode_block=8)
    gauge = engine_mod._frozen_row_share.labels(eng.engine_id)
    for m in (20, 3, 6):
        eng.submit([1, 2, m], SamplingParams(temperature=0.0,
                                             max_new_tokens=m))
    shares = []
    while eng.outstanding:
        eng.step()
        shares.append(gauge.value)
    blocks = [e for e in load_events(str(journal_dir / "events.jsonl"))
              if e["ev"] == "b" and e["name"] == "decode_block"]
    # 20 / 3 / 6 left: (8 - 3) + (8 - 6) frozen row-steps of 3 x 8;
    # then the long row alone: a whole block, and a tail of 4 in 4
    assert [(e["slots"], e["n_steps"], e["frozen_row_steps"])
            for e in blocks] == [(3, 8, 7), (1, 8, 0), (1, 4, 0)]
    assert shares == [7 / 24, 0.0, 0.0]
    steps = _engine_step_spans(journal_dir)
    assert [e["n_steps"] for e in steps] == [8, 8, 4]
    assert [e["decoding_slots"] for e in steps] == [3, 1, 1]


@pytest.mark.timeout(300)
@pytest.mark.parametrize("decode_block", [1, 8])
def test_warm_aot_step_arms_the_block_a_live_batch_runs(
        params, decode_block, tmp_path):
    """ISSUE 32 (f): the AOT decode program is the one the engine calls
    beside a live batch, ``n_steps`` = ``decode_block``; once it is
    armed a whole block goes through it and compiles nothing."""
    from dlrover_tpu.parallel.compile_cache import CompileCacheClient

    eng = InferenceEngine(params, CFG, slots=2, max_len=64,
                          prefill_len=8, decode_block=decode_block)
    aot = eng.warm_aot_step(cache=CompileCacheClient(str(tmp_path)))
    assert aot is not None and eng._aot_step is aot.fn
    armed, real = [], eng._aot_step

    def through_aot(*a):
        armed.append(np.asarray(a[10]).tolist())
        out = real(*a)
        assert out[0].shape == (decode_block, eng.slots)   # n_steps
        return out

    eng._aot_step = through_aot
    jitted = _spy_step_block(eng)
    sp = SamplingParams(temperature=0.0, max_new_tokens=2 * decode_block)
    eng.submit([5, 9, 2], sp)
    eng.run()                     # prefill and install compile here
    compiles = []

    def on_compile(name, secs, **kw):
        if name.endswith("backend_compile_duration"):
            compiles.append(name)

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        rid = eng.submit([7, 7, 1], sp)
        res = {r.id: r for r in eng.run()}
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    assert len(res[rid].tokens) == 2 * decode_block
    assert compiles == [] and jitted == []
    assert armed == [[2 * decode_block, 0], [decode_block, 0]] * 2


@pytest.mark.timeout(300)
def test_short_prompts_fill_free_slots_in_one_engine_step(params):
    """ISSUE 30 (b): one-chunk prompts queued behind a live batch are
    all prefilled AND installed in one engine step while slots stand
    free (an install clears the pending admission, so the next unit
    starts the next prompt); the one that finds no slot is prefilled
    ahead and waits."""
    eng = InferenceEngine(params, CFG, slots=4, max_len=64,
                          prefill_len=8, decode_block=8)
    chunk_calls = _count_chunks(eng)
    sp = SamplingParams(temperature=0.0, max_new_tokens=24)
    first = eng.submit([1, 2], sp)
    eng.step()
    ids = [eng.submit([3 + i, 5, 7 + i], sp) for i in range(4)]
    stalls_before = _stall_count()
    assert eng.step() == 4
    assert [r.id for r in eng._active] == [first] + ids[:3]
    # the fourth found no slot: prefilled, pending, one chunk run
    assert len(chunk_calls) == 1 + 4
    assert eng._pending.req.id == ids[3] and eng._pending.run.done
    assert _stall_count() - stalls_before == 1
    results = {r.id: r for r in eng.run()}
    assert sorted(results) == [first] + ids
    assert all(len(r.tokens) == 24 for r in results.values())


@pytest.mark.timeout(300)
@pytest.mark.parametrize("temperature", [0.0, 0.9],
                         ids=["greedy", "sampled"])
def test_decode_block_changes_the_schedule_not_a_stream(params,
                                                        temperature):
    """ISSUE 30 (c): the same requests through ``decode_block`` 1 and 8
    (one chunk an engine step, up to eight) return the same tokens per
    request: sampling is keyed by the request's seed and draw index,
    so the order and pace of admission reach no stream."""
    rng = np.random.default_rng(7)
    reqs = [
        (list(rng.integers(1, CFG.vocab_size, int(n))),
         SamplingParams(temperature=temperature, top_p=0.9,
                        max_new_tokens=int(m), seed=100 + i))
        for i, (n, m) in enumerate(
            [(2, 20), (19, 6), (5, 12), (26, 9), (3, 17), (11, 5)])
    ]
    streams, steps = {}, {}
    for block in (1, 8):
        eng = InferenceEngine(params, CFG, slots=3, max_len=64,
                              prefill_len=4, decode_block=block)
        chunk_calls = _count_chunks(eng)
        # one live request first, so the others admit beside a batch
        ids = [eng.submit(*reqs[0])]
        eng.step()
        ids += [eng.submit(p, sp) for p, sp in reqs[1:]]
        n = 1
        while eng.outstanding:
            eng.step()
            n += 1
        got = {r.id: r.tokens for r in eng.poll_results()}
        streams[block] = [got[i] for i in ids]
        steps[block] = n
        assert len(chunk_calls) == sum(-(-len(p) // 4) for p, _ in reqs)
    assert streams[1] == streams[8]
    assert [len(t) for t in streams[8]] == [20, 6, 12, 9, 17, 5]
    assert steps[8] < steps[1]


def test_sampling_tensors_cached_between_steps(params):
    """ISSUE 12 satellite: temp/top_k/top_p/eos vectors upload once per
    active-set change, not once per step."""
    eng = InferenceEngine(params, CFG, slots=2, max_len=64,
                          prefill_len=8)
    eng.submit([1, 2], SamplingParams(temperature=0.7,
                                      max_new_tokens=6))
    eng.step()
    t1 = eng._sampling_tensors()
    eng.step()
    assert eng._sampling_tensors() is t1       # steady state: cached
    eng.submit([3], SamplingParams(temperature=0.2, max_new_tokens=2))
    eng.step()                                  # admit -> invalidated
    t2 = eng._sampling_tensors()
    assert t2 is not t1
    eng.run()
    assert eng._sampling_tensors() is not t2   # retire -> invalidated


def _shard_params(preset_name, params, cfg, **preset_kwargs):
    """Place params per a strategy preset's specs on the CPU mesh."""
    from jax.sharding import NamedSharding
    from dlrover_tpu.parallel.strategy import PRESETS

    strategy = PRESETS[preset_name](**preset_kwargs)
    mesh = strategy.build_mesh()
    specs = strategy.specs(tfm.logical_axes(cfg), mesh)
    return jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
        params, specs,
        is_leaf=lambda x: hasattr(x, "shape") and not isinstance(x, tuple),
    )


@pytest.mark.timeout(300)
# slow tier (tier-1 envelope): among the heaviest bodies in this
# file on XLA:CPU; core behavior stays covered by the lighter
# tests in-tier. `pytest tests/` still runs it.
@pytest.mark.slow
def test_serves_sharded_params_identically(params):
    """Multi-chip serving: FSDP-sharded params on the 8-device mesh
    produce exactly the tokens the unsharded engine produces (XLA
    inserts the gathers; the engine code is sharding-agnostic)."""
    import dataclasses

    # f32 compute for the comparison: at bf16, resharding reorders
    # reductions enough (~0.3 logit drift over 2 layers) that numeric
    # equality claims are meaningless — the property under test is the
    # engine's sharding-agnosticism, not bf16 determinism
    cfg32 = dataclasses.replace(CFG, dtype="float32")
    sharded_params = _shard_params("fsdp", params, cfg32)

    outs = {}
    logits = {}
    for name, ps in (("plain", params), ("sharded", sharded_params)):
        eng = InferenceEngine(ps, cfg32, slots=2, max_len=64,
                              prefill_len=8, decode_block=4)
        rid = eng.submit([3, 1, 4], SamplingParams(
            temperature=0.0, max_new_tokens=8))
        eng._admit()
        # prefill logits before any decode: the numeric comparison point
        logits[name] = np.asarray(jax.device_get(eng._last[0]))
        res = {r.id: r for r in eng.run()}
        outs[name] = res[rid].tokens
    np.testing.assert_allclose(
        logits["plain"], logits["sharded"], rtol=1e-4, atol=1e-4)
    assert outs["plain"] == outs["sharded"]


@pytest.mark.timeout(300)
# slow tier (tier-1 envelope): among the heaviest bodies in this
# file on XLA:CPU; core behavior stays covered by the lighter
# tests in-tier. `pytest tests/` still runs it.
@pytest.mark.slow
def test_serves_tensor_parallel_params_identically(params):
    """TP serving (the vLLM-backend multi-GPU layout): heads/mlp/vocab
    sharded over the tensor axis; decode output must match unsharded.
    Unlike the FSDP case (gather-then-compute), TP keeps the compute
    sharded, so this exercises partitioned attention + KV cache."""
    import dataclasses

    cfg32 = dataclasses.replace(CFG, dtype="float32")
    tp_params = _shard_params("tp", params, cfg32, tensor_size=2)
    outs = {}
    for name, ps in (("plain", params), ("tp", tp_params)):
        eng = InferenceEngine(ps, cfg32, slots=2, max_len=64,
                              prefill_len=8, decode_block=4)
        rid = eng.submit([3, 1, 4], SamplingParams(
            temperature=0.0, max_new_tokens=8))
        res = {r.id: r for r in eng.run()}
        outs[name] = res[rid].tokens
    assert outs["plain"] == outs["tp"]


@pytest.mark.timeout(300)
# slow tier (tier-1 envelope): among the heaviest bodies in this
# file on XLA:CPU; core behavior stays covered by the lighter
# tests in-tier. `pytest tests/` still runs it.
@pytest.mark.slow
def test_chunked_prefill_long_prompt_matches_solo(params):
    """A prompt longer than prefill_len loops the chunk program and the
    greedy continuation is exactly solo generate's."""
    prompt = list((np.arange(19) * 7 + 3) % CFG.vocab_size)
    eng = InferenceEngine(params, CFG, slots=2, max_len=64,
                          prefill_len=8)  # 19 tokens -> 3 chunks
    rid = eng.submit(prompt, SamplingParams(temperature=0.0,
                                            max_new_tokens=6))
    res = {r.id: r for r in eng.run()}
    solo = generate(params, jnp.asarray([prompt], jnp.int32), CFG,
                    gen_len=6, key=jax.random.PRNGKey(0),
                    temperature=0.0)
    assert res[rid].tokens == np.asarray(solo)[0, 19:].tolist()
    with pytest.raises(ValueError):
        eng.submit([])  # empty prompt


def test_prefill_divisibility_invariant(params):
    """max_len % prefill_len != 0 is rejected at construction — a
    clamped final chunk write would corrupt earlier cache rows."""
    with pytest.raises(ValueError, match="divide"):
        InferenceEngine(params, CFG, slots=1, max_len=100,
                        prefill_len=64)
    # default prefill_len adapts to the LARGEST divisor <= 64
    eng = InferenceEngine(params, CFG, slots=1, max_len=100)
    assert eng.prefill_len == 50
    eng2 = InferenceEngine(params, CFG, slots=1, max_len=96)
    assert eng2.prefill_len == 48


@pytest.mark.timeout(300)
def test_randomized_workload_completes_exactly(params):
    """Mini-fuzz (fixed seed): a mixed bag of prompt lengths, budgets
    and sampling params on one engine must complete every request with
    the promised token counts and finish reasons."""
    import random

    rng = random.Random(42)
    eng = InferenceEngine(params, CFG, slots=3, max_len=64,
                          prefill_len=8, decode_block=4)
    expected = {}
    for _ in range(10):
        plen = rng.randint(1, 20)
        max_new = rng.randint(1, 64 - plen)
        sp = SamplingParams(
            temperature=rng.choice([0.0, 0.7, 1.2]),
            top_k=rng.choice([0, 3, 20]),
            top_p=rng.choice([1.0, 0.9, 0.5]),
            max_new_tokens=max_new,
            eos_id=rng.choice([None, 7]),
        )
        prompt = [rng.randrange(CFG.vocab_size) for _ in range(plen)]
        expected[eng.submit(prompt, sp)] = (max_new, sp.eos_id)
    results = {r.id: r for r in eng.run()}
    assert set(results) == set(expected)
    for rid, (max_new, eos) in expected.items():
        r = results[rid]
        assert 1 <= len(r.tokens) <= max_new
        assert all(0 <= t < CFG.vocab_size for t in r.tokens)
        if r.finish_reason == "length":
            assert len(r.tokens) == max_new
        else:
            assert eos is not None and r.tokens[-1] == eos
        if eos is not None:
            # the stop must have been observed AT the eos token: an eos
            # anywhere before the end means the engine decoded past it
            assert eos not in r.tokens[:-1], r


@pytest.mark.timeout(300)
# slow tier (tier-1 envelope): among the heaviest bodies in this
# file on XLA:CPU; core behavior stays covered by the lighter
# tests in-tier. `pytest tests/` still runs it.
@pytest.mark.slow
def test_seeded_requests_are_batch_independent(params):
    """A seeded request's continuation depends only on (prompt, params,
    seed) — identical whether it runs alone or batched with strangers.
    f32: bf16 tiling differences across batch shapes would add ulp
    noise unrelated to the property under test."""
    import dataclasses

    cfg32 = dataclasses.replace(CFG, dtype="float32")
    sp = SamplingParams(temperature=0.9, top_p=0.95,
                        max_new_tokens=10, seed=123)

    def run_alone():
        eng = InferenceEngine(params, cfg32, slots=1, max_len=64,
                              prefill_len=8)
        rid = eng.submit([5, 9, 2], sp)
        return {r.id: r for r in eng.run()}[rid].tokens

    def run_batched():
        eng = InferenceEngine(params, cfg32, slots=3, max_len=64,
                              prefill_len=8)
        eng.submit([7, 7], SamplingParams(temperature=1.1,
                                          max_new_tokens=14))
        rid = eng.submit([5, 9, 2], sp)
        eng.submit([1, 2, 3, 4], SamplingParams(temperature=0.5,
                                                max_new_tokens=5))
        return {r.id: r for r in eng.run()}[rid].tokens

    alone = run_alone()
    assert run_batched() == alone
    assert run_alone() == alone            # and reproducible
    # a different seed (almost surely) diverges
    sp2 = dataclasses.replace(sp, seed=99)
    eng = InferenceEngine(params, cfg32, slots=1, max_len=64,
                          prefill_len=8)
    rid = eng.submit([5, 9, 2], sp2)
    assert {r.id: r for r in eng.run()}[rid].tokens != alone


@pytest.mark.timeout(300)
# slow tier (tier-1 envelope): among the heaviest bodies in this
# file on XLA:CPU; core behavior stays covered by the lighter
# tests in-tier. `pytest tests/` still runs it.
@pytest.mark.slow
def test_streaming_callback_receives_tokens_in_order(params):
    """on_token streams every accepted token in order; a raising
    consumer never kills decode; nothing streams past eos."""
    eng = InferenceEngine(params, CFG, slots=2, max_len=64,
                          prefill_len=8, decode_block=4)
    streamed = {}

    def cb(rid, tok):
        streamed.setdefault(rid, []).append(tok)

    def bad_cb(rid, tok):
        raise RuntimeError("consumer bug")

    r1 = eng.submit([5, 9, 2], SamplingParams(temperature=0.0,
                                              max_new_tokens=9),
                    on_token=cb)
    r2 = eng.submit([7, 7], SamplingParams(temperature=0.0,
                                           max_new_tokens=6),
                    on_token=bad_cb)
    results = {r.id: r for r in eng.run()}
    assert streamed[r1] == results[r1].tokens
    assert len(results[r2].tokens) == 6  # bad consumer didn't kill it

    # eos path: the eos token itself streams, nothing after it
    probe = generate(params, jnp.asarray([[5, 9, 2]], jnp.int32), CFG,
                     gen_len=1, key=jax.random.PRNGKey(0),
                     temperature=0.0)
    eos = int(np.asarray(probe)[0, -1])
    r3 = eng.submit([5, 9, 2], SamplingParams(
        temperature=0.0, max_new_tokens=20, eos_id=eos), on_token=cb)
    res3 = {r.id: r for r in eng.run()}[r3]
    assert res3.finish_reason == "eos"
    assert streamed[r3] == res3.tokens == [eos]


@pytest.mark.timeout(300)
class TestPrefixCache:
    """vLLM automatic-prefix-caching analog: chunk-aligned KV reuse."""

    SYS = list(range(40, 56))  # 16 tokens = 2 aligned chunks at P=8

    def _run(self, params, prompts, cache_entries, temperature=0.0,
             seed=None):
        eng = InferenceEngine(params, CFG, slots=2, max_len=64,
                              prefill_len=8,
                              prefix_cache_entries=cache_entries)
        ids = [
            eng.submit(p, SamplingParams(
                temperature=temperature, max_new_tokens=5, seed=seed))
            for p in prompts
        ]
        results = {r.id: r.tokens for r in eng.run()}
        return eng, [results[i] for i in ids]

    def test_hit_produces_identical_greedy_output(self, params):
        prompts = [self.SYS + [3, 1], self.SYS + [9],
                   self.SYS + [3, 1]]
        _, base = self._run(params, prompts, cache_entries=0)
        eng, cached = self._run(params, prompts, cache_entries=8)
        assert cached == base
        # prompts 2 and 3 must have resumed from the shared prefix
        assert eng.prefix_cache_hits >= 2
        assert eng.prefix_cache_queries == 3

    def test_full_prompt_hit_skips_prefill_entirely(self, params):
        prompt = self.SYS  # exactly 2 chunks: cacheable in full
        _, base = self._run(params, [prompt, prompt], cache_entries=8)
        eng, cached = self._run(params, [prompt, prompt],
                                cache_entries=8)
        assert cached[0] == cached[1] == base[0]
        # the second submit must have taken the skip-prefill path, not
        # silently cold-prefilled to the same answer
        assert eng.prefix_cache_hits >= 1

    def test_seeded_sampling_unaffected_by_cache(self, params):
        prompts = [self.SYS + [2], self.SYS + [2]]
        _, base = self._run(params, prompts, cache_entries=0,
                            temperature=0.9, seed=1234)
        eng, cached = self._run(params, prompts, cache_entries=8,
                                temperature=0.9, seed=1234)
        assert cached == base
        assert eng.prefix_cache_hits >= 1  # parity held THROUGH a hit

    def test_lru_bound_holds(self, params):
        eng = InferenceEngine(params, CFG, slots=1, max_len=64,
                              prefill_len=8, prefix_cache_entries=2)
        for base in (10, 20, 30, 40):
            eng.submit([base + i for i in range(16)],
                       SamplingParams(temperature=0.0,
                                      max_new_tokens=2))
        eng.run()
        assert len(eng._prefix_cache) <= 2

    def test_long_prompt_miss_probes_stored_lengths_only(self, params):
        """Advisor fix (engine.py _prefix_lookup): a cache miss on a
        long prompt must probe one key per DISTINCT stored length, not
        hash every aligned prefix of the prompt (O(n^2/P))."""
        eng = InferenceEngine(params, CFG, slots=1, max_len=64,
                              prefill_len=8, prefix_cache_entries=8)
        eng.submit(self.SYS, SamplingParams(temperature=0.0,
                                            max_new_tokens=2))
        eng.run()   # stores one entry (final aligned boundary, len 16)
        probes = 0
        orig_get = dict.get

        class Counting(dict):
            def get(self, *a):
                nonlocal probes
                probes += 1
                return orig_get(self, *a)

        eng._prefix_cache = Counting(eng._prefix_cache)
        # a 4096-token prompt that shares nothing: pre-fix this probed
        # 512 ever-shorter tuples (~1M hashed elements); now it probes
        # exactly the one stored length
        assert eng._prefix_lookup(list(range(100, 4196))) is None
        assert probes == 1
        # and a real hit through the capped path still resolves
        probes = 0
        hit = eng._prefix_lookup(self.SYS + [1, 2, 3])
        assert hit is not None and hit[0] == 16
        assert probes == 1

    def test_cold_long_prompts_do_not_churn_lru(self, params):
        """Advisor fix (engine.py _admit): a cold non-sharing prompt
        snapshots only its FINAL aligned boundary, so a wave of long
        unrelated prompts cannot evict a shared system prefix."""
        eng = InferenceEngine(params, CFG, slots=1, max_len=64,
                              prefill_len=8, prefix_cache_entries=4)
        sp = SamplingParams(temperature=0.0, max_new_tokens=2)
        eng.submit(self.SYS, sp)              # the shared prefix: 1 entry
        eng.run()
        for base in (200, 300):               # cold 32-token prompts
            eng.submit([base + i for i in range(32)], sp)
            eng.run()
        # each cold prompt added ONE entry (len 32), not 4 (8/16/24/32)
        assert len(eng._prefix_cache) == 3
        assert sorted(len(k) for k in eng._prefix_cache) == [16, 32, 32]
        # the shared system prefix survived the churn and still hits
        hits_before = eng.prefix_cache_hits
        eng.submit(self.SYS + [7], sp)
        eng.run()
        assert eng.prefix_cache_hits == hits_before + 1

    def test_extension_snapshots_intermediate_boundaries(self, params):
        """Extending an already-cached prefix DOES snapshot the chain:
        that is the shared-system-prompt shape the cache exists for."""
        eng = InferenceEngine(params, CFG, slots=1, max_len=64,
                              prefill_len=8, prefix_cache_entries=8)
        sp = SamplingParams(temperature=0.0, max_new_tokens=2)
        eng.submit(self.SYS, sp)              # cache len-16 prefix
        eng.run()
        eng.submit(self.SYS + list(range(60, 76)), sp)  # 32 tokens
        eng.run()
        # resumed at 16 (a hit), then snapshotted 24 AND 32
        assert eng.prefix_cache_hits >= 1
        assert sorted(len(k) for k in eng._prefix_cache) == [16, 24, 32]

    def test_weight_push_invalidates(self, params):
        eng = InferenceEngine(params, CFG, slots=1, max_len=64,
                              prefill_len=8, prefix_cache_entries=8)
        eng.submit(self.SYS, SamplingParams(temperature=0.0,
                                            max_new_tokens=2))
        eng.run()
        assert eng._prefix_cache
        eng.params = jax.tree.map(lambda a: a * 0.5, params)
        assert not eng._prefix_cache
        # and generations under the new weights match a fresh engine
        fresh = InferenceEngine(
            jax.tree.map(lambda a: a * 0.5, params), CFG, slots=1,
            max_len=64, prefill_len=8, prefix_cache_entries=8)
        rid_a = eng.submit(self.SYS + [7], SamplingParams(
            temperature=0.0, max_new_tokens=4))
        rid_b = fresh.submit(self.SYS + [7], SamplingParams(
            temperature=0.0, max_new_tokens=4))
        out_a = {r.id: r.tokens for r in eng.run()}[rid_a]
        out_b = {r.id: r.tokens for r in fresh.run()}[rid_b]
        assert out_a == out_b


# ------------------------- where the engine's weights rest (ISSUE 28)


GPT2 = dataclasses.replace(CFG, variant="gpt2")


def _serve(eng, requests):
    ids = [eng.submit(prompt, sp) for prompt, sp in requests]
    done = {r.id: r.tokens for r in eng.run()}
    return [done[i] for i in ids]


def _eqns_under(jaxpr, under=()):
    """``(enclosing equations, equation)`` for every equation of a jaxpr
    and of the jaxprs inside it (the jitted call, the step loop, the
    layer loop, branches)."""
    for eqn in jaxpr.eqns:
        yield under, eqn
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (tuple, list))
                          else (value,)):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _eqns_under(inner, under + (eqn,))


def _all_eqns(jaxpr):
    return (eqn for _, eqn in _eqns_under(jaxpr))


def _weight_conversions(fn, params, *args, **static):
    """(shape, dtype) of every `PRODUCT_LEAVES` leaf of ``params``
    that the program ``fn(params, *args)`` converts: the operands of
    its ``convert_element_type`` equations that look like such a leaf
    whole, or like one layer of a stacked one."""
    looks = set()
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        if path[-1].key in PRODUCT_LEAVES:
            shape = leaf.shape[1:] if len(path) > 1 else leaf.shape
            looks.add((tuple(shape), jnp.dtype(leaf.dtype)))
    jaxpr = fn.trace(params, *args, **static).jaxpr.jaxpr
    found = set()
    for eqn in _all_eqns(jaxpr):
        if eqn.primitive.name == "convert_element_type":
            aval = eqn.invars[0].aval
            found.add((tuple(aval.shape), jnp.dtype(aval.dtype)))
    return found & looks, looks


@pytest.mark.timeout(300)
@pytest.mark.parametrize("cfg", [CFG, GPT2], ids=["llama", "gpt2"])
@pytest.mark.parametrize("program", ["decode_block", "prefill_chunk"])
def test_no_engine_program_converts_a_weight(cfg, program):
    """The decode block and the prefill chunk, traced on the tree the
    engine holds, convert no leaf the products read; traced on the
    float32 tree it was handed (what every call did before) they
    convert each of them, which shows that the search can see it."""
    handed = tfm.init_params(cfg, jax.random.PRNGKey(0))
    eng = InferenceEngine(handed, cfg, slots=2, max_len=64,
                          prefill_len=8, decode_block=4)
    if program == "decode_block":
        fn, static = eng._step_block, {"n_steps": 4}
        args = eng._block_sample_args()[1:]
    else:
        fn, static = eng._prefill_chunk, {}
        args = (jnp.zeros((1, 8), jnp.int32),
                eng.prefill_begin([1, 2, 3]).row,
                jnp.asarray(3, jnp.int32))
    held, _ = _weight_conversions(fn, eng.params, *args, **static)
    assert held == set()
    before, looks = _weight_conversions(fn, handed, *args, **static)
    assert before == looks and len(looks) >= 6


@pytest.mark.timeout(300)
def test_float32_and_converted_weights_serve_the_same_tokens(params):
    """An engine handed training's float32 tree and one handed the
    converted tree are the same server: the same tokens, greedy and
    sampled, the same float32 logits out of a prefill; and the second
    keeps the very arrays it was handed."""
    rested = weights_at_rest(params, CFG)
    a = InferenceEngine(params, CFG, slots=2, max_len=64, prefill_len=8,
                        decode_block=4)
    b = InferenceEngine(rested, CFG, slots=2, max_len=64, prefill_len=8,
                        decode_block=4)
    assert all(x is y for x, y in zip(jax.tree.leaves(b.params),
                                      jax.tree.leaves(rested)))
    held = jax.tree_util.tree_leaves_with_path(a.params)
    assert {leaf.dtype for path, leaf in held
            if path[-1].key in PRODUCT_LEAVES} == {jnp.dtype("bfloat16")}
    assert {leaf.dtype for path, leaf in held
            if path[-1].key not in PRODUCT_LEAVES} == {
        jnp.dtype("float32")}
    requests = [
        ([5, 9, 2, 11, 4, 4, 8, 1, 3, 7], SamplingParams(
            temperature=0.0, max_new_tokens=9)),
        ([7, 7, 1], SamplingParams(
            temperature=0.8, top_k=20, top_p=0.9, seed=11,
            max_new_tokens=7)),
        ([3], SamplingParams(temperature=1.0, seed=5, max_new_tokens=5)),
    ]
    assert _serve(a, requests) == _serve(b, requests)
    logits = []
    for eng in (a, b):
        run = eng.prefill_begin(list(range(1, 12)))
        while not eng.prefill_step(run):
            pass
        assert run.last.dtype == jnp.float32
        logits.append(np.asarray(run.last))
    np.testing.assert_array_equal(*logits)


@pytest.mark.timeout(300)
def test_a_push_converts_once_and_the_next_request_reads_it(params):
    """`engine.params = float32_tree` (the RLHF weight push) keeps the
    converted tree, clears the prefix cache, and what is served next is
    what a new engine on the pushed weights serves."""
    eng = InferenceEngine(params, CFG, slots=1, max_len=64,
                          prefill_len=8, prefix_cache_entries=8)
    prompt = list(range(40, 57))
    sp = SamplingParams(temperature=0.0, max_new_tokens=6)
    [old] = _serve(eng, [(prompt, sp)])
    assert eng._prefix_cache
    pushed = jax.tree.map(
        lambda a: a + 0.05 * jax.random.normal(
            jax.random.PRNGKey(a.size % 97), a.shape, a.dtype), params)
    eng.params = pushed
    assert not eng._prefix_cache and not eng._prefix_lens
    want = weights_at_rest(pushed, CFG)
    for (path, got), ref in zip(
            jax.tree_util.tree_leaves_with_path(eng.params),
            jax.tree.leaves(want)):
        assert got.dtype == ref.dtype, path
        np.testing.assert_array_equal(
            np.asarray(got.astype(jnp.float32)),
            np.asarray(ref.astype(jnp.float32)))
    assert eng.params["layers"]["wq"].dtype == jnp.bfloat16
    assert eng.params["layers"]["ln1"] is pushed["layers"]["ln1"]
    fresh = InferenceEngine(pushed, CFG, slots=1, max_len=64,
                            prefill_len=8)
    [new] = _serve(eng, [(prompt, sp)])
    assert new == _serve(fresh, [(prompt, sp)])[0]
    assert new != old


# ------------------------------------ the stack is donated (ISSUE 26)


def _donated(lowered) -> list[str]:
    """Tensor types of the arguments a lowered program aliases to an
    output (or offers as a donor), read off its text: no chip needed."""
    [sig] = [line for line in lowered.as_text().splitlines()
             if "func.func public @main" in line]
    return [
        m.group(1)
        for m in re.finditer(
            r"%arg\d+: tensor<([^>]*)>(?: \{([^%]*?)\})?(?=, %arg|\) ->)",
            sig)
        if m.group(2) and ("tf.aliasing_output" in m.group(2)
                           or "jax.buffer_donor" in m.group(2))
    ]


@pytest.mark.timeout(300)
@pytest.mark.parametrize("program", [
    "_step_block", "_verify_block", "_install", "_resume_install"])
def test_programs_that_return_the_stack_donate_it(params, program):
    """ISSUE 26: every program that takes the stacked cache and returns
    it aliases k, v (and pos, last) input to output, so no call holds
    two stacks or rewrites one to change a row."""
    eng = InferenceEngine(params, CFG, slots=2, max_len=64,
                          prefill_len=8, kv_pages=16)
    step = eng._step_sample_args()
    stack = "x".join(map(str, eng._cache["k"].shape)) + "xbf16"
    row = eng.prefill_begin([1, 2, 3]).row
    slot = jnp.asarray(0, jnp.int32)
    table = jnp.zeros((eng.pages_per_slot,), jnp.int32)
    lowered = {
        "_step_block": lambda: eng._step_block.lower(
            *eng._block_sample_args(), n_steps=4),
        "_verify_block": lambda: eng._verify_block.lower(
            *step, jnp.full((eng.slots, 4), -1, jnp.int32)),
        "_install": lambda: eng._install.lower(
            eng._cache, eng._last, row, eng._last[0], slot, slot),
        "_resume_install": lambda: eng._resume_install.lower(
            eng._cache["k"], eng._cache["v"], eng._cache["pos"],
            eng._last, eng._kpool, eng._vpool, table, slot, slot,
            eng._last[0]),
    }[program]()
    donated = _donated(lowered)
    assert donated.count(stack) == 2, donated             # k and v
    assert f"{eng.slots}xi32" in donated                  # pos
    assert f"{eng.slots}x{CFG.vocab_size}xf32" in donated  # last
    # what a prefill holds (working rows, a row's logits) and the
    # weights are never given away; the model's counters (scalars) ride
    # the tree where a program passes them through
    assert len([d for d in donated if d != "i32"]) == 4, donated


@pytest.mark.timeout(300)
def test_prefill_chunk_donates_nothing(params):
    """The working rows of a prefill are held by the prefix cache and
    by a bundle in the making, so the chunk program keeps its inputs."""
    eng = InferenceEngine(params, CFG, slots=2, max_len=64,
                          prefill_len=8, prefix_cache_entries=2)
    run = eng.prefill_begin([1, 2, 3])
    lowered = eng._prefill_chunk.lower(
        eng.params, jnp.zeros((1, 8), jnp.int32), run.row,
        jnp.asarray(3, jnp.int32))
    assert _donated(lowered) == []


@pytest.mark.timeout(300)
@pytest.mark.parametrize("kind", ["serving_step", "serving_verify"])
def test_aot_digest_names_the_stack_contract(params, monkeypatch, kind):
    """The elastic compile cache keys on facts, not on the program's
    text: the parent's build (which keeps the stack it passes in) and
    this one (which donates it) must not load each other's serving
    executables, so the cache contract is a fact of the digest, and it
    is the digest the engine publishes under."""
    from dlrover_tpu.parallel.compile_cache import (
        abstract_signature,
        compile_fingerprint,
        verify_key,
    )

    monkeypatch.setenv("DLROVER_TPU_SERVING_OBSERVATORY", "1")
    monkeypatch.setenv("DLROVER_TPU_SPEC_DEPTH", "2")
    eng = InferenceEngine(params, CFG, slots=2, max_len=64,
                          prefill_len=8)
    if kind == "serving_step":
        aot, facts = eng.warm_aot_step(), {"n_steps": eng.decode_block}
        extra = eng._block_sample_args()[-1:]
    else:
        [aot], facts = eng.warm_aot_verify(depths=[2]), {}
        extra = (jnp.full((eng.slots, 2), -1, jnp.int32),)

    def key(strategy):
        base, _ = compile_fingerprint(
            num_nodes=1, total_devices=jax.local_device_count(),
            mesh_axes={}, model=CFG, strategy=strategy,
            args_signature=abstract_signature(
                eng._step_sample_args() + extra))
        return base if kind == "serving_step" else verify_key(
            base, depth=2)

    ours = eng._aot_strategy(kind, **facts)
    parents = {k: v for k, v in ours.items() if k != "kv_stack"}
    assert set(parents) == {"kind", "slots", "max_len", "prefill_len",
                            "numerics", *facts}
    assert aot.key == key(ours)
    assert key(parents) != key(ours)


# ------------------------------------- ISSUE 42: an all-greedy call skips
# the sampler. `_step_block` and `_verify_block` put `sample_logits` under
# `lax.cond(any(active & (temperature > 0)), ...)`: the tokens are what the
# ungated sampler gives, bit for bit.

GATED = ["step_block_1", "step_block_8", "verify_block"]
LIVE = [True, True, True, False]        # the last slot is EMPTY


class _TodaysLax:
    """``jax.lax`` with a ``cond`` that always takes its first branch:
    an engine traced under it runs ``sample_logits`` on every call and
    for every row, which is the program every engine had before."""

    def __getattr__(self, name):
        return getattr(jax.lax, name)

    @staticmethod
    def cond(pred, true_fn, false_fn, *operands):
        return true_fn(*operands)


@contextlib.contextmanager
def _ungated(monkeypatch):
    """A context in which the engine's programs are traced ungated."""
    from dlrover_tpu.serving import engine as engine_mod

    with monkeypatch.context() as patch:
        patch.setattr(engine_mod, "lax", _TodaysLax())
        yield


def _gate_args(eng, mixed: bool) -> tuple:
    """The decode programs' arguments with crafted logits: ties (at the
    maximum too), ``-inf`` entries, three live rows and an EMPTY slot
    that holds what `_sampling_tensors` gives one: ``temperature`` 1.0.
    ``mixed``: live row 1 samples (0.8, top-k 40, top-p 0.9)."""
    rng = np.random.default_rng(42)
    V = CFG.vocab_size
    last = np.round(rng.normal(size=(4, V)) * 3).astype(np.float32) / 2
    last[rng.random((4, V)) < 0.15] = -np.inf
    for row in range(3):          # the maximum twice in every live row
        last[row, rng.choice(V, 2, replace=False)] = 7.0
    temp = np.array([0.0, 0.8 if mixed else 0.0, 0.0, 1.0], np.float32)
    top_k = np.array([0, 40 if mixed else 0, 0, 0], np.int32)
    top_p = np.array([1.0, 0.9 if mixed else 1.0, 1.0, 1.0], np.float32)
    cache = jax.tree.map(jnp.copy, eng._cache)
    cache["pos"] = jnp.asarray([5, 9, 2, 0], jnp.int32)
    return (eng.params, cache, jnp.asarray(last),
            jnp.asarray([11, 22, 33, 0], jnp.int32),      # seeds
            jnp.asarray([0, 3, 7, 0], jnp.int32),         # draw indices
            jnp.asarray(temp), jnp.asarray(top_k), jnp.asarray(top_p),
            jnp.asarray(LIVE), jnp.full((4,), -1, jnp.int32))


def _run_gated_program(eng, program: str, mixed: bool):
    """``(tokens [slots, steps], the rows' next logits, pos)``."""
    args = _gate_args(eng, mixed)
    if program == "verify_block":
        guesses = np.random.default_rng(7).integers(
            0, CFG.vocab_size, (4, 4)).astype(np.int32)
        guesses[1, 0] = guesses[3, 0] = -1   # plain rows: one token
        toks, cache, last, acc, _ = eng._verify_block(
            *args, jnp.asarray(guesses))
        return np.asarray(toks), np.asarray(last), np.asarray(
            cache["pos"]), np.asarray(acc)
    n = int(program.rsplit("_", 1)[1])
    toks, cache, last, _ = eng._step_block(
        *args, jnp.asarray([8, 8, 8, 0], jnp.int32), n_steps=n)
    return np.asarray(toks).T, np.asarray(last), np.asarray(
        cache["pos"])


@pytest.mark.timeout(300)
@pytest.mark.parametrize("mixed", [False, True],
                         ids=["all_greedy", "one_row_samples"])
@pytest.mark.parametrize("program", GATED)
def test_gated_sampler_gives_the_ungated_samplers_tokens(
        params, monkeypatch, program, mixed):
    """ISSUE 42 (a): on logits with ties, ``-inf`` entries and an EMPTY
    slot at ``temperature`` 1.0, the gated programs return what the
    ungated ones do for every LIVE row (all rows when one samples: the
    call then takes the sampler whole), and their first tokens are
    ``sample_logits``' own with the same seeds and draw indices."""
    from dlrover_tpu.models.decode import sample_logits

    sizes = dict(slots=4, max_len=64, prefill_len=8, decode_block=8)
    with _ungated(monkeypatch):
        want = _run_gated_program(
            InferenceEngine(params, CFG, **sizes), program, mixed)
    eng = InferenceEngine(params, CFG, **sizes)
    got = _run_gated_program(eng, program, mixed)
    rows = slice(None) if mixed else np.flatnonzero(LIVE)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g[rows], w[rows])
    _, _, last, seeds, counts, temp, top_k, top_p, _, _ = _gate_args(
        eng, mixed)
    keys = jax.vmap(lambda s, c: jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(0), s), c))(seeds, counts)
    first = np.asarray(jax.jit(sample_logits)(last, keys, temp, top_k,
                                              top_p))
    np.testing.assert_array_equal(got[0][rows, 0], first[rows])
    if not mixed:
        # the EMPTY slot's temperature did not buy the call a sampler:
        # its row too is the plain argmax (first index of a tied maximum)
        np.testing.assert_array_equal(
            got[0][:, 0], np.argmax(np.asarray(last), axis=-1))


def _top_level_sources(jaxpr, var) -> set[int]:
    """Indices of ``jaxpr``'s inputs that ``var`` is computed from (an
    equation's outputs read all of its inputs)."""
    made_by = {out: eqn for eqn in jaxpr.eqns for out in eqn.outvars}
    inputs = {v: i for i, v in enumerate(jaxpr.invars)}
    found, todo, seen = set(), [var], set()
    while todo:
        v = todo.pop()
        if not hasattr(v, "count") or v in seen:    # a literal
            continue
        seen.add(v)
        if v in inputs:
            found.add(inputs[v])
        elif v in made_by:
            todo.extend(made_by[v].invars)
    return found


@pytest.mark.timeout(300)
@pytest.mark.parametrize("program", ["_step_block", "_verify_block"])
def test_every_sort_of_a_decode_program_sits_under_the_gate(params,
                                                            program):
    """ISSUE 42 (b): in the decode programs' jaxprs every ``sort`` is
    inside a ``cond`` branch, none outside, and the branch index is
    computed from ``active`` and ``temperature``."""
    eng = InferenceEngine(params, CFG, slots=4, max_len=64,
                          prefill_len=8, decode_block=8)
    if program == "_step_block":
        args, static = eng._block_sample_args(), {"n_steps": 8}
    else:
        args = eng._step_sample_args() + (
            jnp.full((4, 4), -1, jnp.int32),)
        static = {}
    top = getattr(eng, program).trace(*args, **static).jaxpr.jaxpr
    leaves = jax.tree_util.tree_leaves(args)
    active = next(i for i, leaf in enumerate(leaves) if leaf is args[8])
    temperature = next(i for i, leaf in enumerate(leaves)
                       if leaf is args[5])
    found = [under for under, eqn in _eqns_under(top)
             if eqn.primitive.name == "sort"]
    assert found                       # the sampler is still in there
    for under in found:
        names = [e.primitive.name for e in under]
        assert "cond" in names, names
        # the gate's index, followed out through the step loop (a
        # scan's body reads its operands in the equation's own order)
        loops = under[:names.index("cond")]
        var = under[len(loops)].invars[0]
        for loop in reversed(loops):
            [i] = _top_level_sources(loop.params["jaxpr"].jaxpr, var)
            var = loop.invars[i]
        assert {active, temperature} <= _top_level_sources(top, var)


@pytest.mark.timeout(300)
@pytest.mark.parametrize("sampled", [False, True],
                         ids=["all_greedy", "one_request_samples"])
def test_decode_block_span_counts_the_live_rows_that_sample(
        params, journal_dir, monkeypatch, sampled):
    """ISSUE 42 (c): ``sampling_rows`` on the ``decode_block`` span is
    the program's own predicate counted (same mask, same comparison): 0
    on every call of a greedy run with a free slot, 1 while a sampling
    request lives and 0 once it has retired; and every stream, the
    seeded sampled one too, is the ungated engine's."""
    from dlrover_tpu.telemetry.report import load_events

    reqs = [([5, 9, 2], SamplingParams(temperature=0.0,
                                       max_new_tokens=30)),
            ([7, 7, 1], SamplingParams(
                temperature=0.8 if sampled else 0.0, top_k=40, top_p=0.9,
                max_new_tokens=10, seed=1234)),
            ([3], SamplingParams(temperature=0.0, max_new_tokens=20))]
    sizes = dict(slots=4, max_len=64, prefill_len=8, decode_block=8)
    with _ungated(monkeypatch):
        want = _serve(InferenceEngine(params, CFG, **sizes), reqs)
    eng = InferenceEngine(params, CFG, **sizes)
    program_saw, orig = [], eng._step_block

    def spy(*a, n_steps):
        # the predicate's terms as the program receives them
        temperature, active = np.asarray(a[5]), np.asarray(a[8])
        assert temperature[~active].tolist() == [1.0] * int(
            (~active).sum())
        program_saw.append(int(np.sum(active & (temperature > 0))))
        return orig(*a, n_steps=n_steps)

    eng._step_block = spy
    assert _serve(eng, reqs) == want
    blocks = [e for e in load_events(str(journal_dir / "events.jsonl"))
              if e["ev"] == "b" and e["name"] == "decode_block"]
    rows = [e["sampling_rows"] for e in blocks[-len(program_saw):]]
    assert rows == program_saw
    # 10 tokens in blocks of 8: two calls hold the sampling row
    assert rows == ([1, 1] if sampled else [0, 0]) + [0] * (len(rows) - 2)
    assert len(rows) >= 4 and all(e["slots"] < 4 for e in blocks)
