"""Serving memory observatory (ISSUE 18 tentpole, DESIGN.md §29).

The properties that make a measure-only instrument trustworthy:

- the measure-only pin: a seeded engine trace produces bit-identical
  token streams with the observatory on vs off (mirroring the
  disagg==unified identity test) — measurement must never steer;
- shareable-page hashing counts only full, whole-prefix-matching
  pages (overlap / no-overlap / partial-page cases);
- the n-gram shadow predictor is deterministic: same stream, same
  acceptance, no RNG anywhere;
- a prompt enters the predictor in slices (ISSUE 34: under its own
  prefill chunks, the rest at the install) and the predictor is the one
  built whole, table for table, answer for answer;
- `bench.py --compare` gates by category, so the committed r06/r07
  pair (whose stage configs legitimately diverged) runs green while a
  genuine quality drop still fails.
"""

from __future__ import annotations

import json
import random
from pathlib import Path

import pytest

import jax

import bench
from dlrover_tpu.common.constants import EnvKey
from dlrover_tpu.models import transformer as tfm
from dlrover_tpu.serving import (
    InferenceEngine,
    PrefillEngine,
    SamplingParams,
)
from dlrover_tpu.serving.observatory import (
    SHADOW_ORDER,
    ShadowPredictor,
    page_share_stats,
)
from dlrover_tpu.telemetry import journal as journal_mod
from dlrover_tpu.telemetry.report import load_events

CFG = tfm.CONFIGS["tiny"]
REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(CFG, jax.random.PRNGKey(0))


# ------------------------------------------------- measure-only pin


@pytest.mark.timeout(300)
def test_observatory_on_off_token_identity(params, monkeypatch):
    """ISSUE 18 acceptance: the same seeded open-loop-shaped trace on
    a paged engine (parks and resumes included) emits bit-identical
    streams with the observatory enabled and disabled."""
    rng = random.Random(7)
    reqs = []
    for i in range(8):
        plen = rng.randint(1, 12)
        reqs.append((
            [rng.randrange(CFG.vocab_size) for _ in range(plen)],
            SamplingParams(
                temperature=rng.choice([0.0, 0.8]),
                max_new_tokens=rng.randint(2, 20),
                seed=2000 + i),
        ))

    def run(enabled):
        monkeypatch.setenv("DLROVER_TPU_SERVING_OBSERVATORY",
                           "1" if enabled else "0")
        monkeypatch.setenv("DLROVER_TPU_OBSERVATORY_SAMPLE_EVERY", "4")
        eng = InferenceEngine(params, CFG, slots=2, max_len=64,
                              prefill_len=8, kv_pages=24)
        ids = [eng.submit(p, sp) for p, sp in reqs]
        out = {r.id: r.tokens for r in eng.run()}
        return eng, [out[i] for i in ids]

    eng_on, on = run(True)
    eng_off, off = run(False)
    assert on == off                       # the measure-only pin
    assert eng_on.kv_parked_total >= 1     # parks actually happened
    # and the instrument measured while not steering
    snap = eng_on.observatory_snapshot()
    assert snap is not None
    assert snap["total"] == 24
    assert snap["scored"] > 0
    assert 0.0 <= snap["accept_rate"] <= 1.0
    assert snap["high_water"] > 0
    assert eng_off.observatory_snapshot() is None


# ------------------------------------------- shareable-page hashing


class TestPageShareStats:
    def test_full_overlap_two_slots(self):
        # two slots share 2 aligned pages, then diverge on page 3
        shared = list(range(100, 108))          # 2 pages of 4
        a = shared + [1, 2, 3, 4]
        b = shared + [5, 6, 7, 8]
        s = page_share_stats([a, b], 4)
        assert s["total_pages"] == 6
        assert s["shareable_pages"] == 4        # both copies of both
        assert s["shareable_frac"] == pytest.approx(4 / 6)
        assert s["unique_pages"] == 4           # 2 shared + 2 distinct
        assert s["cow_multiplier"] == pytest.approx(6 / 4)
        assert s["families"] == 1
        assert s["largest_family"] == 2

    def test_no_overlap(self):
        s = page_share_stats([[1, 2, 3, 4], [5, 6, 7, 8]], 4)
        assert s["shareable_pages"] == 0
        assert s["shareable_frac"] == 0.0
        assert s["cow_multiplier"] == 1.0
        assert s["families"] == 2

    def test_partial_page_never_shareable(self):
        # shared prefix shorter than one page: no FULL page matches
        s = page_share_stats([[9, 9, 9], [9, 9, 9]], 4)
        assert s["total_pages"] == 0
        assert s["shareable_frac"] == 0.0
        # ... and a full first page + partial tail counts only the page
        s = page_share_stats([[9] * 6, [9] * 6], 4)
        assert s["total_pages"] == 2
        assert s["shareable_pages"] == 2

    def test_equal_content_different_prefix_not_shareable(self):
        # page 2's TOKENS match across slots but the prefixes differ;
        # KV content depends on the whole prefix, so the chain hash
        # must refuse the share
        a = [1, 2, 3, 4] + [7, 7, 7, 7]
        b = [5, 6, 7, 8] + [7, 7, 7, 7]
        s = page_share_stats([a, b], 4)
        assert s["shareable_pages"] == 0


# ------------------------------------------- shadow-draft determinism


class TestShadowPredictor:
    def test_deterministic_under_fixed_seed(self):
        rng = random.Random(123)
        prompt = [rng.randrange(64) for _ in range(12)]
        stream = [rng.randrange(64) for _ in range(200)]

        def score():
            sp = ShadowPredictor(3, prompt)
            hits = [sp.observe(t) for t in stream]
            return sp.accepted, sp.scored, hits

        assert score() == score()

    def test_repetition_is_predictable(self):
        period = [3, 1, 4, 1, 5]
        sp = ShadowPredictor(3, period * 2)
        accepts = sum(sp.observe(t) for t in period * 10)
        # a periodic stream is exactly what an n-gram nails
        assert accepts / (len(period) * 10) > 0.9
        assert sp.scored == len(period) * 10

    def test_cold_context_scores_misses(self):
        sp = ShadowPredictor(2, [1])
        assert sp.observe(2) is False   # no evidence -> miss, scored
        assert sp.scored == 1 and sp.accepted == 0

    @pytest.mark.parametrize("order", [1, 2, 3, 5])
    @pytest.mark.parametrize("widths", [(1,), (64,), (512,),
                                        (7, 130, 1, 300, 64)],
                             ids=["1", "64", "512", "uneven"])
    @pytest.mark.parametrize("start", [0, 96])
    def test_fed_in_slices_equals_built_whole(self, order, widths, start):
        """A prompt indexed slice by slice from ``start`` on (a prefill's
        chunks; ``start`` > 0: past a prefix-cache hit's head), then
        finished, is the predictor built whole: equal tables and context,
        equal ``predict()`` / ``draft(k)`` / ``observe()`` over a stream
        with repeats."""
        rng = random.Random(1000 * order + sum(widths) + start)
        motif = [rng.randrange(40) for _ in range(9)]
        prompt = []
        while len(prompt) < 700:
            prompt += (motif if rng.random() < 0.4
                       else [rng.randrange(40) for _ in range(5)])
        whole = ShadowPredictor(order, prompt)
        sliced = ShadowPredictor(order, prompt, whole=False)
        lo, i, under_slices = start, 0, 0
        while lo < len(prompt) - 30:    # the tail is left to finish()
            hi = min(lo + widths[i % len(widths)], len(prompt) - 30)
            under_slices += sliced.index(lo, hi)
            lo, i = hi, i + 1
        assert under_slices == len(prompt) - 30 - start
        assert sliced.finish() == start + 30
        assert sliced.finish() == 0     # nothing is counted twice
        assert sliced.index(0, len(prompt)) == 0
        assert sliced._tables == whole._tables
        assert sliced._ctx == whole._ctx == prompt
        stream = [rng.choice(motif) if rng.random() < 0.6
                  else rng.randrange(40) for _ in range(120)]
        for t in stream:
            assert sliced.predict() == whole.predict()
            assert sliced.draft(4) == whole.draft(4)
            assert sliced.draft(3, min_order=1) == whole.draft(
                3, min_order=1)
            assert sliced.observe(t) == whole.observe(t)
        assert (sliced.accepted, sliced.scored) == (
            whole.accepted, whole.scored)
        assert whole.accepted > 0       # the stream did repeat
        assert sliced._tables == whole._tables

    def test_a_slice_that_does_not_extend_is_left_to_finish(self):
        prompt = [1, 2, 3, 1, 2, 3, 1, 2, 4, 1, 2]
        sp = ShadowPredictor(3, prompt, whole=False)
        assert sp.index(4, 7) == 3
        assert sp.index(0, 4) == 0      # behind the counted interval
        assert sp.index(9, 11) == 0     # a hole before it
        assert sp.index(7, 99) == 4     # cut at the prompt's end
        assert sp.finish() == 4
        assert sp._tables == ShadowPredictor(3, prompt)._tables

    def test_tables_count_what_the_counter_tables_counted(self):
        """The tables' values are plain counts (ISSUE 34); they hold
        what the ``Counter`` tables held, built a token at a time."""
        from collections import Counter

        rng = random.Random(5)
        toks = [rng.randrange(12) for _ in range(400)]
        tables = [{} for _ in range(3)]
        ctx = []
        for t in toks:
            for j in range(1, 4):
                if len(ctx) >= j:
                    tables[j - 1].setdefault(
                        tuple(ctx[-j:]), Counter())[t] += 1
            ctx.append(t)
        sp = ShadowPredictor(3, toks[:250])
        for t in toks[250:]:
            sp.observe(t)
        assert sp._tables == tables


# ----------------------------- a prompt indexed under its own prefill


@pytest.fixture()
def journal_dir(tmp_path, monkeypatch):
    monkeypatch.setenv(EnvKey.JOURNAL_DIR, str(tmp_path / "journal"))
    monkeypatch.setattr(journal_mod, "_cached", None)
    yield str(tmp_path / "journal")
    journal_mod._cached = None


def _indexed_by_span(journal_dir: str, rid: int) -> dict[str, list[int]]:
    """``indexed_tokens`` of a request's ``prefill_chunk`` and
    ``kv_install`` spans, in time order."""
    events = load_events(journal_dir)
    mine = {e["span"] for e in events
            if e.get("ev") == "b" and e.get("request") == rid}
    out = {"prefill_chunk": [], "kv_install": []}
    for e in events:
        if e.get("ev") == "e" and e["span"] in mine and e["name"] in out:
            out[e["name"]].append(e["indexed_tokens"])
    return out


_SP = SamplingParams(temperature=0.0, max_new_tokens=6, seed=3)


def _prompt(n, salt=0):
    rng = random.Random(100 * n + salt)
    return [rng.randrange(CFG.vocab_size) for _ in range(n)]


def _cold(params):
    eng = InferenceEngine(params, CFG, slots=2, max_len=64, prefill_len=8)
    prompt = _prompt(21)
    # chunks of 8, 8 and 5; the install has nothing left
    return eng, eng.submit(prompt, _SP), prompt, [8, 8, 5], 0


def _short(params):
    eng = InferenceEngine(params, CFG, slots=2, max_len=64, prefill_len=8)
    prompt = _prompt(3)
    return eng, eng.submit(prompt, _SP), prompt, [3], 0


def _hit(params):
    eng = InferenceEngine(params, CFG, slots=2, max_len=64, prefill_len=8,
                          prefix_cache_entries=4)
    head = _prompt(16)
    eng.submit(head + [1, 2, 3], _SP)
    eng.run()
    prompt = head + [4, 5, 6, 7, 8]
    rid = eng.submit(prompt, _SP)
    # the run resumes at 16: one chunk of 5, the head is the install's
    return eng, rid, prompt, [5], 16


def _handoff(params):
    pe = PrefillEngine(InferenceEngine(params, CFG, slots=2, max_len=64,
                                       prefill_len=8))
    prompt = _prompt(19)
    pe.submit(prompt)
    while pe.step():
        pass
    [res] = pe.poll_results()
    # the prefill pool's runs belong to no request: nothing is indexed
    assert pe.engine._obs._shadow == {}
    eng = InferenceEngine(params, CFG, slots=2, max_len=64, prefill_len=8)
    rid = eng.submit_prefilled(prompt, _SP, bundle=res.bundle)
    return eng, rid, prompt, [], 19


def _diffusion(_params):
    cfg = tfm.CONFIGS["tiny-sdar-moe"]
    eng = InferenceEngine(tfm.init_params(cfg, jax.random.PRNGKey(1)), cfg,
                          slots=2, max_len=64, prefill_len=8, decode_block=4)
    prompt = [t % cfg.vocab_size for t in _prompt(14)]
    # whole blocks of 4 are prefilled (12: chunks of 8 and 4); the
    # remainder opens the first generated block
    return eng, eng.submit(prompt, _SP), prompt, [8, 4], 2


def _diffusion_short(_params):
    cfg = tfm.CONFIGS["tiny-sdar-moe"]
    eng = InferenceEngine(tfm.init_params(cfg, jax.random.PRNGKey(1)), cfg,
                          slots=2, max_len=64, prefill_len=8, decode_block=4)
    prompt = [5, 6, 7]                   # under one block: zero chunks
    return eng, eng.submit(prompt, _SP), prompt, [], 3


@pytest.mark.timeout(300)
@pytest.mark.parametrize("case", [_cold, _short, _hit, _handoff,
                                  _diffusion, _diffusion_short],
                         ids=lambda f: f.__name__.strip("_"))
def test_shadow_after_install_is_the_one_built_whole(
        case, params, journal_dir, monkeypatch):
    """ISSUE 34: a prompt's tokens enter the request's shadow under the
    prefill chunks that carry them, the install indexes what no chunk
    carried, and after it the shadow is ``ShadowPredictor(3, prompt)``;
    the spans say how many tokens each absorbed."""
    monkeypatch.setenv("DLROVER_TPU_SERVING_OBSERVATORY", "1")
    eng, rid, prompt, want_chunks, want_install = case(params)
    eng._admit()                         # chunks and install, no decode
    shadow = eng._obs._shadow[rid]
    whole = ShadowPredictor(SHADOW_ORDER, prompt)
    assert shadow._indexed is None
    assert shadow._tables == whole._tables
    assert shadow._ctx == whole._ctx
    assert shadow.draft(4) == whole.draft(4)
    got = _indexed_by_span(journal_dir, rid)
    assert got == {"prefill_chunk": want_chunks,
                   "kv_install": [want_install]}
    assert sum(want_chunks) + want_install == len(prompt)
    # ... and the request decodes to its end with the shadow scoring
    [result] = [r for r in eng.run() if r.id == rid]
    assert eng._obs.scored >= len(result.tokens) > 0
    assert rid not in eng._obs._shadow   # retired with the request


@pytest.mark.timeout(300)
def test_observatory_off_indexes_nothing(params, journal_dir, monkeypatch):
    monkeypatch.setenv("DLROVER_TPU_SERVING_OBSERVATORY", "0")
    eng, rid, prompt, _, _ = _cold(params)
    eng._admit()
    assert eng._obs is None
    assert _indexed_by_span(journal_dir, rid) == {
        "prefill_chunk": [0, 0, 0], "kv_install": [0]}


# --------------------------------------------------- bench --compare


class TestBenchCompare:
    def test_committed_r06_r07_green(self):
        """ISSUE 18 acceptance: the committed trajectory files diff
        clean — config-driven latency/throughput swings are
        informational, not gated."""
        rc = bench.main([
            "--compare",
            str(REPO / "BENCH_r06.json"),
            str(REPO / "BENCH_r07.json"),
        ])
        assert rc == 0

    def test_quality_regression_gates(self, tmp_path, capsys):
        old = tmp_path / "old.json"
        new = tmp_path / "new.json"
        old.write_text(json.dumps(
            {"headline": {"goodput": 0.95, "step_ms": 100}}))
        new.write_text(json.dumps(
            {"headline": {"goodput": 0.50, "step_ms": 300}}))
        rc = bench.main(["--compare", str(old), str(new)])
        assert rc == 1
        out = capsys.readouterr().out
        assert "goodput" in out and "REGRESSION" in out
        # the raw-latency swing reports but does not gate
        assert "step_ms" in out

    def test_failure_count_increase_gates(self, tmp_path):
        old = tmp_path / "old.json"
        new = tmp_path / "new.json"
        old.write_text(json.dumps(
            {"headline": {"gateway_failed": 0, "n_errors": 0}}))
        new.write_text(json.dumps(
            {"headline": {"gateway_failed": 2, "n_errors": 1}}))
        assert bench.main(["--compare", str(old), str(new)]) == 1

    def test_boolean_flip_gates(self, tmp_path):
        old = tmp_path / "old.json"
        new = tmp_path / "new.json"
        old.write_text(json.dumps(
            {"headline": {"cp_rack_p99_within_2x_1k": True}}))
        new.write_text(json.dumps(
            {"headline": {"cp_rack_p99_within_2x_1k": False}}))
        assert bench.main(["--compare", str(old), str(new)]) == 1

    def test_wrapper_and_raw_formats_load(self, tmp_path):
        raw = tmp_path / "raw.txt"
        raw.write_text(
            'noise\n{"metric": "x", "headline": {"mfu": 0.4}}\n')
        assert bench._load_headline(str(raw)) == {"mfu": 0.4}
        wrapped = tmp_path / "wrapped.json"
        wrapped.write_text(json.dumps(
            {"n": 1, "rc": 0,
             "tail": 'cut{"bad\n{"headline": {"mfu": 0.5}}\n'}))
        assert bench._load_headline(str(wrapped)) == {"mfu": 0.5}
        with pytest.raises(ValueError):
            empty = tmp_path / "empty.json"
            empty.write_text("{}")
            bench._load_headline(str(empty))

    def test_new_headline_keys_registered(self):
        for key in ("gateway_kv_occupancy_p95",
                    "gateway_pages_shareable_frac",
                    "gateway_draft_accept_rate",
                    "gateway_accept_run_p50",
                    "gateway_accept_run_p95"):
            assert key in bench.HEADLINE_KEYS


# ------------------------------------------- gateway-level aggregation


@pytest.mark.timeout(300)
def test_gateway_stats_expose_observatory(params, monkeypatch):
    """The health tick rolls replica samples into the pool aggregate
    and stats()/healthz carry the §29 payload + prefix hit rate."""
    from dlrover_tpu.gateway import Gateway

    monkeypatch.setenv("DLROVER_TPU_SERVING_OBSERVATORY", "1")
    monkeypatch.setenv("DLROVER_TPU_OBSERVATORY_SAMPLE_EVERY", "2")

    def factory():
        return InferenceEngine(
            params, CFG, slots=2, max_len=64, prefill_len=8,
            prefix_cache_entries=4, kv_pages=16,
        )

    gw = Gateway(factory, replicas=1, prefill_len=8, seed=11,
                 health_interval_s=0.05)
    try:
        import time

        deadline = time.monotonic() + 90
        while (len(gw.pool.ready_replicas()) < 1
               and time.monotonic() < deadline):
            time.sleep(0.02)
        shared = list(range(40, 48))            # one aligned page
        for extra_tok in (1, 2, 3):
            gw.generate(shared + [extra_tok], SamplingParams(
                temperature=0.0, max_new_tokens=4), timeout=120)
        deadline = time.monotonic() + 30
        # wait for a sample taken AFTER all 3 generates: an early
        # health tick can snapshot the pool mid-traffic and stats()
        # would then serve a 2-query observatory
        while ((not gw.pool.observatory.get("replicas_sampled")
                or gw.pool.observatory.get("prefix_cache_queries", 0)
                < 3)
               and time.monotonic() < deadline):
            time.sleep(0.05)
        stats = gw.stats()
        obs = stats["serving_observatory"]
        assert obs["replicas_sampled"] == 1
        assert obs["kv_pages_total"] == 16
        assert obs["draft_tokens_scored"] > 0
        assert 0.0 <= obs["draft_accept_rate"] <= 1.0
        # shared one-page prefix across the 3 prompts: the LRU hit
        assert stats["prefix_cache_hit_rate"] > 0.0
        assert obs["prefix_cache_queries"] >= 3
    finally:
        gw.stop()
