"""COW KV pages + speculative decoding (ISSUE 20 tentpole, DESIGN.md §31).

Both §29 shadow instruments are promoted to live mechanisms here, and
both live under one contract: the greedy token stream is BIT-IDENTICAL
with the mechanism on or off. Everything else — admitted-capacity
gains, draft acceptance, verify-step speedup — is only worth shipping
if that pin holds, so these tests are identity-first:

- COW on/off identity under a paged trace with parks, resumes, shared
  prefixes and retires; spec on/off identity on self-drafting cyclic
  streams, including the deep ladder depths whose wide-verify KV
  writes once diverged from the block scan by one bf16 ulp (the
  canonical-numerics regression pin);
- identity survives the disagg prefill→decode handoff and a
  mid-decode replica kill with orphan resubmission;
- the page pool is a conserved ledger: every physical page is exactly
  one of free or leased-with-positive-refcount, a negative refcount
  raises instead of limping, and a forced copy-on-write break re-homes
  the page without perturbing the stream;
- acceptance collapse drops a hopeless request to k=1 for good, and
  the per-slot digest store feeds the observatory sample the same
  numbers the token-rehashing path would have computed.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

import jax

from dlrover_tpu.gateway import Gateway
from dlrover_tpu.models import transformer as tfm
from dlrover_tpu.serving import (
    InferenceEngine,
    PrefillEngine,
    SamplingParams,
)
from dlrover_tpu.serving.engine import check_kv_ledgers
from dlrover_tpu.serving.observatory import (
    digest_share_stats,
    page_share_stats,
)

CFG = tfm.CONFIGS["tiny"]

# short cyclic prompts: each pattern twice, so the order-k n-gram shadow
# finds its repeats in the prompt itself and greedy rows start drafting
# within a few tokens — whatever this machine's XLA:CPU makes the
# random-init model continue with (PR 22: with the patterns given once,
# drafting hung on the continuation happening to repeat, and on this
# host it does not)
_CYCLIC = [2 * p for p in (
    [454, 126, 12, 214, 262, 346],
    [229, 389, 164, 351],
    [485, 180, 384, 142, 241, 56],
    [4, 47, 391, 116],
    [21, 485, 24],
    [443, 88, 403],
)]

# one full KV page (page_size == prefill_len == 8 throughout) shared
# verbatim across requests, so the sharing index has something to dedup
_SYS8 = [11, 12, 13, 14, 15, 16, 17, 18]


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(CFG, jax.random.PRNGKey(0))


def _serving_env(monkeypatch, *, spec=0, cow=True):
    monkeypatch.setenv("DLROVER_TPU_SERVING_OBSERVATORY", "1")
    monkeypatch.setenv("DLROVER_TPU_OBSERVATORY_SAMPLE_EVERY", "8")
    monkeypatch.setenv("DLROVER_TPU_SPEC_DEPTH", str(spec))
    monkeypatch.setenv("DLROVER_TPU_KV_COW", "1" if cow else "0")


def _drain(eng, reqs):
    ids = [eng.submit(p, sp) for p, sp in reqs]
    out = {r.id: r.tokens for r in eng.run()}
    return [out[i] for i in ids]


def _spec_reqs(max_new=40):
    prompts = _CYCLIC + _CYCLIC[:2]
    return [
        (p, SamplingParams(temperature=0.0, max_new_tokens=max_new,
                           seed=900 + i))
        for i, p in enumerate(prompts)
    ]


def _shared_prefix_reqs(n=6, max_new=23):
    """Six requests sharing one full prompt-prefix page, mixed greedy
    and seeded-sampled, each spanning several decode pages so parking
    victims exist."""
    reqs = []
    for i in range(n):
        temp = 0.0 if i % 2 == 0 else 0.8
        reqs.append((
            _SYS8 + [30 + i],
            SamplingParams(temperature=temp, max_new_tokens=max_new,
                           seed=700 + i),
        ))
    return reqs


# ------------------------------------------------ token-identity pins


@pytest.mark.timeout(600)
def test_spec_on_off_token_identity(params, monkeypatch):
    """ISSUE 20 acceptance: a seeded paged trace (parks, resumes and
    retires included) emits bit-identical streams with speculative
    decoding at depth 4 and with it off — and the spec leg actually
    speculated rather than vacuously matching."""
    def leg(depth):
        _serving_env(monkeypatch, spec=depth)
        eng = InferenceEngine(params, CFG, slots=2, max_len=64,
                              prefill_len=8, kv_pages=48)
        toks = _drain(eng, _spec_reqs())
        return toks, eng

    plain, eng0 = leg(0)
    spec, eng4 = leg(4)
    assert spec == plain
    assert eng0.spec_steps_total == 0
    assert eng4.spec_steps_total > 0
    assert eng4.spec_extra_tokens_total > 0
    assert eng4.spec_accept_rate > 0.0
    # the trace exercised parking on both legs, not just admission
    assert eng0.kv_parked_total > 0 and eng4.kv_parked_total > 0


@pytest.mark.timeout(600)
@pytest.mark.parametrize("depth", [8, 16])
def test_spec_identity_deep_ladder(params, monkeypatch, depth):
    """Canonical-numerics regression pin: the wide verify program and
    the narrow block scan are different XLA programs, and with excess
    precision allowed their bf16 KV writes disagreed by one ulp —
    flipping greedy argmaxes ~150 tokens downstream. Long generations
    at the deep ladder depths are exactly where that surfaced."""
    reqs = [
        (p, SamplingParams(temperature=0.0, max_new_tokens=110,
                           seed=40 + i))
        for i, p in enumerate(_CYCLIC[:2])
    ]

    def leg(d):
        _serving_env(monkeypatch, spec=d)
        eng = InferenceEngine(params, CFG, slots=2, max_len=128,
                              prefill_len=8, decode_block=4)
        toks = _drain(eng, reqs)
        return toks, eng

    plain, _ = leg(0)
    spec, eng = leg(depth)
    assert spec == plain
    assert eng.spec_steps_total > 0


@pytest.mark.timeout(600)
def test_cow_on_off_token_identity(params, monkeypatch):
    """Shared-prefix paged trace with parks and retires: COW dedups
    real pages (shared counter moves) yet the streams match the
    COW-off run bit for bit."""
    def leg(cow):
        _serving_env(monkeypatch, cow=cow)
        eng = InferenceEngine(params, CFG, slots=2, max_len=32,
                              prefill_len=8, kv_pages=24)
        toks = _drain(eng, _shared_prefix_reqs())
        return toks, eng

    off, eng_off = leg(False)
    on, eng_on = leg(True)
    assert on == off
    assert eng_off.cow_pages_shared_total == 0
    assert eng_on.cow_pages_shared_total > 0
    assert eng_on.cow_breaks_total == 0   # full-prefix shares never break


@pytest.mark.timeout(600)
def test_spec_identity_across_disagg_handoff(params, monkeypatch):
    """The §31 pin composes with ISSUE 12's: prefill on one engine,
    decode WITH speculation on another, versus the unified spec-off
    path — same seed, same tokens."""
    prompt = _CYCLIC[0]
    sp = SamplingParams(temperature=0.0, max_new_tokens=48, seed=11)

    _serving_env(monkeypatch, spec=0)
    uni = InferenceEngine(params, CFG, slots=2, max_len=64,
                          prefill_len=8)
    [want] = _drain(uni, [(prompt, sp)])

    pe = PrefillEngine(InferenceEngine(params, CFG, slots=2,
                                       max_len=64, prefill_len=8))
    pe.submit(prompt)
    while pe.step():
        pass
    [res] = pe.poll_results()

    _serving_env(monkeypatch, spec=4)
    dec = InferenceEngine(params, CFG, slots=2, max_len=64,
                          prefill_len=8)
    rid = dec.submit_prefilled(prompt, sp, bundle=res.bundle)
    out = {r.id: r.tokens for r in dec.run()}
    assert out[rid] == want
    assert dec.spec_steps_total > 0


@pytest.mark.timeout(600)
def test_spec_identity_across_replica_kill(params, monkeypatch):
    """Mid-decode replica kill with orphan resubmission, speculating:
    the survivor regenerates the orphans from scratch and still lands
    on the quiet spec-off gateway's exact tokens."""
    sp = [SamplingParams(temperature=0.0, max_new_tokens=24,
                         seed=1000 + i) for i in range(8)]
    prompts = _CYCLIC + _CYCLIC[:2]

    def factory():
        return InferenceEngine(params, CFG, slots=2, max_len=64,
                               prefill_len=8)

    def wait(cond, timeout=90.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if cond():
                return True
            time.sleep(0.02)
        return False

    _serving_env(monkeypatch, spec=0)
    quiet = Gateway(factory, replicas=1, prefill_len=8)
    assert wait(lambda: len(quiet.pool.ready_replicas()) == 1)
    want = [quiet.generate(p, s, timeout=120).tokens
            for p, s in zip(prompts, sp)]
    quiet.stop()

    _serving_env(monkeypatch, spec=4)
    gw = Gateway(factory, replicas=2, prefill_len=8,
                 health_interval_s=0.1)
    assert wait(lambda: len(gw.pool.ready_replicas()) == 2)
    try:
        futs = [gw.submit(p, s) for p, s in zip(prompts, sp)]
        victim = gw.pool.ready_replicas()[0].id
        gw.pool.kill_replica(victim)
        got = [f.result(timeout=120).tokens for f in futs]
        assert got == want
    finally:
        gw.stop()


# ---------------------------------------------- pool ledger + capacity


@pytest.mark.timeout(600)
def test_cow_admits_more_at_fixed_pages(params, monkeypatch):
    """The admitted-capacity gain is real, not just a counter: at a
    fixed kv_pages budget the COW run keeps strictly more requests
    resident at peak, because admission charges only UNIQUE pages."""
    sys16 = _SYS8 + [21, 22, 23, 24, 25, 26, 27, 28]
    reqs = [
        (sys16 + [40 + i],
         SamplingParams(temperature=0.0, max_new_tokens=15,
                        seed=300 + i))
        for i in range(6)
    ]

    def leg(cow):
        _serving_env(monkeypatch, cow=cow)
        # 4 pages/request, 2 of them the shared system prefix: off
        # fits 2 requests in 8 pages, on fits 1 + 2 more at 2 fresh
        # pages each
        eng = InferenceEngine(params, CFG, slots=4, max_len=32,
                              prefill_len=8, kv_pages=8)
        ids = [eng.submit(p, sp) for p, sp in reqs]
        peak, out = 0, {}
        while eng.outstanding:
            eng.step()
            holders = (sum(p is not None for p in eng._slot_pages)
                       + len(eng._parked)
                       + (1 if eng._pending is not None else 0))
            peak = max(peak, holders)
            out.update({r.id: r.tokens for r in eng.poll_results()})
        return peak, [out[i] for i in ids]

    peak_off, toks_off = leg(False)
    peak_on, toks_on = leg(True)
    assert toks_on == toks_off
    assert peak_off == 2          # 8 pages / 4 unique pages per request
    assert peak_on > peak_off


@pytest.mark.timeout(600)
def test_page_ledger_conserves_and_refcounts_guard(params, monkeypatch):
    """Conservation at every step of a shared-prefix trace, full
    recovery of the pool at drain, and the corruption guard: a second
    release of the same page raises instead of going negative."""
    _serving_env(monkeypatch)
    eng = InferenceEngine(params, CFG, slots=2, max_len=32,
                          prefill_len=8, kv_pages=24)
    for p, sp in _shared_prefix_reqs():
        eng.submit(p, sp)
    while eng.outstanding:
        eng.step()
        ledger = eng.kv_page_ledger()
        assert ledger["ok"], ledger
    eng.poll_results()
    ledger = eng.kv_page_ledger()
    assert ledger["ok"]
    assert ledger["free"] == eng.kv_pages and ledger["leased"] == 0
    assert not eng._share_index and not eng._page_digest
    assert check_kv_ledgers() == []

    pid = eng._lease_page()
    eng._release_ref(pid)
    with pytest.raises(AssertionError, match="negative refcount"):
        eng._release_ref(pid)
    assert eng.kv_page_ledger()["ok"]


@pytest.mark.timeout(600)
def test_forced_cow_break_repoints_without_stream_change(
        params, monkeypatch):
    """`_cow_break` is unreachable under the share policy (only full
    prompt-prefix pages are shared and decode never writes below the
    prompt), so force it: register a DECODE-span page in the sharing
    index by hand, park the slot, and require a fresh private page, a
    clean ledger, and an unperturbed stream after resume. The slot's
    genuinely-registered prompt page 0 must NOT break."""
    prompt, sp = list(_SYS8), SamplingParams(
        temperature=0.0, max_new_tokens=17, seed=5)

    _serving_env(monkeypatch)
    ref = InferenceEngine(params, CFG, slots=2, max_len=32,
                          prefill_len=8, kv_pages=8)
    [want] = _drain(ref, [(prompt, sp)])

    eng = InferenceEngine(params, CFG, slots=2, max_len=32,
                          prefill_len=8, kv_pages=8)
    rid = eng.submit(prompt, sp)
    while len(eng._emitted[0]) < 2:
        eng.step()
    pid = eng._slot_pages[0][1]            # decode page, spans [8, 16)
    eng._share_index[b"forced"] = pid
    eng._page_digest[pid] = b"forced"
    eng._park_slot(0)
    assert eng.cow_breaks_total == 1
    assert pid in eng._free_pages          # old page freed at refcount 0
    assert b"forced" not in eng._share_index
    assert eng._slot_pages[0] is None and len(eng._parked) == 1
    assert eng.kv_page_ledger()["ok"]
    out = {r.id: r.tokens for r in eng.run()}
    assert out[rid] == want


@pytest.mark.timeout(900)
def test_every_path_crossed_after_donated_steps(params, monkeypatch):
    """ISSUE 26: every program that returns the stacked cache donates
    it, so the engine must never read a stack (or pos, or last) it
    handed to an earlier call. One engine crosses every path after
    donated steps — admission + install, prefix-cache hits, COW shares,
    parks and resumes through the pages, a forced COW break,
    speculative verifies, a weight push through the ``params`` setter —
    and emits, request for request, what a one-slot engine with no
    pages, no prefix cache and no speculation emits for that request
    alone; each watched call has deleted the buffers it was given."""
    def alone(weights, reqs):
        _serving_env(monkeypatch, spec=0, cow=False)
        eng = InferenceEngine(weights, CFG, slots=1, max_len=64,
                              prefill_len=8)
        return [_drain(eng, [r])[0] for r in reqs]

    wave = _spec_reqs(max_new=24) + _shared_prefix_reqs()
    forced = (list(_SYS8), SamplingParams(
        temperature=0.0, max_new_tokens=17, seed=5))
    pushed = tfm.init_params(CFG, jax.random.PRNGKey(7))
    want = alone(params, wave + [forced])
    want_pushed = alone(pushed, wave)

    _serving_env(monkeypatch, spec=4, cow=True)
    eng = InferenceEngine(params, CFG, slots=2, max_len=64,
                          prefill_len=8, kv_pages=40,
                          prefix_cache_entries=4)
    gave_away = {}

    def watch(name, first, n):
        real = getattr(eng, name)

        def call(*args, **kw):
            out = real(*args, **kw)
            # the cache tree and last (the paged twin: k, v, pos, last);
            # not the model's counters: a program that starts them at
            # zero never reads the scalars it was given
            stale = jax.tree.leaves([
                {k: v for k, v in a.items() if k != "counters"}
                if isinstance(a, dict) else a
                for a in args[first:first + n]])
            assert all(a.is_deleted() for a in stale), name
            gave_away[name] = gave_away.get(name, 0) + 1
            return out

        setattr(eng, name, call)

    for name, first, n in (("_install", 0, 2), ("_resume_install", 0, 4),
                           ("_step_block", 1, 2), ("_verify_block", 1, 2)):
        watch(name, first, n)

    assert _drain(eng, wave) == want[:-1]
    assert eng.kv_parked_total > 0 and eng.spec_steps_total > 0
    assert eng.prefix_cache_hits > 0 and eng.cow_pages_shared_total > 0

    # a forced copy-on-write break (see the test above), then a resume
    rid = eng.submit(*forced)
    while len(eng._emitted[0]) < 2:
        eng.step()
    pid = eng._slot_pages[0][1]
    eng._share_index[b"forced"] = pid
    eng._page_digest[pid] = b"forced"
    eng._park_slot(0)
    assert eng.cow_breaks_total == 1
    assert {r.id: r.tokens for r in eng.run()}[rid] == want[-1]

    # a weight push between waves: the stack outlives the weights
    eng.params = pushed
    assert not eng._prefix_cache
    assert _drain(eng, wave) == want_pushed
    assert set(gave_away) == {"_install", "_resume_install",
                              "_step_block", "_verify_block"}
    ledger = eng.kv_page_ledger()
    assert ledger["ok"] and ledger["leased"] == 0
    assert check_kv_ledgers() == []


# ------------------------------------- depth policy + digest satellite


@pytest.mark.timeout(600)
def test_acceptance_collapse_drops_to_k1(params, monkeypatch):
    """Once a request's live acceptance sinks below the collapse rate
    with enough drafts scored, `_spec_plan` excludes it for good —
    adaptive fallback to k=1 — and the collapse is counted exactly
    once."""
    _serving_env(monkeypatch, spec=4)
    eng = InferenceEngine(params, CFG, slots=2, max_len=128,
                          prefill_len=8)
    rid = eng.submit(_CYCLIC[0], SamplingParams(
        temperature=0.0, max_new_tokens=64, seed=3))
    for _ in range(30):
        if eng._spec_plan() is not None:
            break
        eng.step()
    plan = eng._spec_plan()
    assert plan is not None and plan[0] >= 2

    # replay pure misses into the live accounting: first fed guess
    # matches (so the row is scored at all), every later one misses
    eng._spec_acc[rid] = [0, 0, 0]
    guesses = np.full((eng.slots, 4), -1, np.int32)
    guesses[0] = [5, 7, 9, 11]
    toks_sn = np.zeros((eng.slots, 4), np.int64)
    toks_sn[0] = [5, 1, 2, 3]
    for _ in range(16):
        eng._spec_score(guesses, toks_sn, 4)
    assert eng._spec_acc[rid][2] == 1
    assert eng.spec_collapsed_total == 1
    assert eng._spec_plan() is None        # collapsed row never drafts
    eng._spec_score(guesses, toks_sn, 4)   # idempotent once collapsed
    assert eng.spec_collapsed_total == 1
    out = {r.id: r.tokens for r in eng.run()}
    assert len(out[rid]) == 64             # k=1 path finishes the run


@pytest.mark.timeout(600)
def test_digest_store_matches_token_rehash(params, monkeypatch):
    """§31 dedup satellite: the incremental per-slot digest store must
    report, at every step, exactly the share stats the O(tokens)
    rehashing path computes from the raw streams — that equivalence is
    what makes the O(1) observatory sample trustworthy."""
    _serving_env(monkeypatch)
    eng = InferenceEngine(params, CFG, slots=2, max_len=32,
                          prefill_len=8, kv_pages=24)
    for p, sp in _shared_prefix_reqs():
        eng.submit(p, sp)
    saw_shareable = False
    while eng.outstanding:
        eng.step()
        streams, rids = [], []
        for s, req in enumerate(eng._active):
            if req is not None:
                streams.append(list(req.prompt) + eng._emitted[s])
                rids.append(req.id)
        for parked in eng._parked:
            streams.append(list(parked.req.prompt) + parked.emitted)
            rids.append(parked.req.id)
        if eng._pending is not None:
            streams.append(list(eng._pending.req.prompt))
            rids.append(eng._pending.req.id)
        want = page_share_stats(streams, eng.page_size)
        got = digest_share_stats(
            [eng._digest_store.pages(r) for r in rids])
        assert got == want
        saw_shareable = saw_shareable or want["shareable_frac"] > 0
    assert saw_shareable
    eng.poll_results()


@pytest.mark.timeout(600)
def test_warm_aot_verify_populates_ladder_and_preserves_identity(
        params, monkeypatch):
    """`warm_aot_verify` fills the per-depth executable map through
    `verify_key`-derived cache keys, and the AOT programs emit the
    same tokens the jit ladder does."""
    def leg(warm):
        _serving_env(monkeypatch, spec=8)
        eng = InferenceEngine(params, CFG, slots=2, max_len=64,
                              prefill_len=8)
        if warm:
            eng.warm_aot_verify()
            assert sorted(eng._aot_verify) == [2, 4, 8]
            for depth, aot in eng.aot_verify_info.items():
                assert f"/sv{depth}_" in aot.key
        toks = _drain(eng, _spec_reqs(max_new=24))
        return toks, eng

    jit_toks, _ = leg(False)
    aot_toks, eng = leg(True)
    assert aot_toks == jit_toks
    assert eng.spec_steps_total > 0
