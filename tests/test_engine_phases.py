"""Where a request waits inside the engine, and what the host does around
every device call (ISSUE 36, DESIGN.md §32).

- ``prefill_chunk`` carries the host's seconds around the chunk program's
  call in the order they ran (``build_s``, ``dispatch_s``, then what runs
  under the chunk's device time, which is in no field, ``wait_s``,
  ``after_s``); ``decode_block`` at its three sites (the plain block, the
  verify block, the block-diffusion call) carries its ``wait_s``;
  ``engine_step`` the host's seconds for its decode call.
- ``kv_install`` carries the request's time inside the engine: the queue
  wait, the seconds of the ``_start_admission`` call that took it up (a
  page-blocked head's earlier tries lie in its queue wait), the admission's
  wall, and the chunks' own numbers, which are the ``engine_admit``
  point's. The queue wait is also the one histogram.
- Every field has a reader (PERF.md §3): a span carries these and no other
  seconds.
- With no journal and no capture nothing is written and ``step()`` returns
  what it returned.
"""

from __future__ import annotations

import os
import time

import pytest

import jax

from dlrover_tpu.common.constants import EnvKey
from dlrover_tpu.models import transformer as tfm
from dlrover_tpu.serving import engine as E
from dlrover_tpu.telemetry import journal as journal_mod
from dlrover_tpu.telemetry.report import load_events

REQUEST_FIELDS = ["queue_wait_s", "start_s", "admit_wall_s", "chunk_work_s",
                  "chunks"]
# a span's fields that are seconds, in the order they are written: each has
# a metric or the runbook for a reader, and there are no others
SECONDS = {
    "prefill_chunk": ["build_s", "dispatch_s", "wait_s", "after_s"],
    "kv_install": REQUEST_FIELDS[:-1],
    "decode_block": ["wait_s"],
    "engine_step": ["decode_host_s"],
}
SITES = ("plain", "verify", "block_diffusion")
# drafts repeat, so the shadow predictor has something to propose
CYCLIC = [[5, 9, 2, 5, 9, 2, 5, 9, 2, 5, 9], [7, 3, 7, 3, 7, 3, 7, 3, 7]]


@pytest.fixture()
def journal_dir(tmp_path, monkeypatch):
    monkeypatch.setenv(EnvKey.JOURNAL_DIR, str(tmp_path / "journal"))
    monkeypatch.delenv(EnvKey.JOURNAL_MAX_MB, raising=False)
    monkeypatch.setattr(journal_mod, "_cached", None)
    yield str(tmp_path / "journal")
    journal_mod._cached = None


def events_of(journal_dir: str) -> list[dict]:
    return load_events(os.path.join(journal_dir, "events.jsonl"))


def build(site: str, monkeypatch, **kw) -> E.InferenceEngine:
    """An engine whose decode calls are the named site's."""
    spec = site == "verify"
    monkeypatch.setenv("DLROVER_TPU_SERVING_OBSERVATORY", "1")
    monkeypatch.setenv("DLROVER_TPU_SPEC_DEPTH", "4" if spec else "0")
    cfg = tfm.CONFIGS["tiny-sdar-moe" if site == "block_diffusion"
                      else "tiny"]
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    sizes = dict(slots=2, max_len=64, prefill_len=8,
                 decode_block=1 if spec else 4)
    sizes.update(kw)
    return E.InferenceEngine(params, cfg, **sizes)


def serve(eng: E.InferenceEngine, max_new: int = 12) -> tuple[dict, list]:
    """Three requests on two slots (the third queues behind a live batch).
    Per request: when it was submitted and when its first token came; and
    what every ``step()`` returned."""
    greedy = E.SamplingParams(temperature=0.0, max_new_tokens=max_new)
    seen: dict[int, dict] = {}

    def first_token(rid, _tok):
        seen[rid].setdefault("first", time.monotonic())

    for prompt in CYCLIC + [CYCLIC[0][:5]]:
        before = time.monotonic()
        rid = eng.submit(prompt, greedy, on_token=first_token)
        seen[rid] = {"submitted": before}
    returned = []
    while eng.outstanding:
        returned.append(eng.step())
    return seen, returned


def ended(events: list[dict], name: str) -> list[dict]:
    """A span's end events with the fields of its begin folded in."""
    begun = {e["span"]: e for e in events
             if e["name"] == name and e["ev"] == "b"}
    return [{**begun[e["span"]], **e} for e in events
            if e["name"] == name and e["ev"] == "e"]


@pytest.fixture(params=SITES)
def served(request, journal_dir, monkeypatch):
    eng = build(request.param, monkeypatch)
    seen, _ = serve(eng)
    return request.param, eng, seen, events_of(journal_dir)


# ------------------------------------------- the host around a device call


@pytest.mark.timeout(300)
def test_every_device_call_span_says_where_the_host_stood(served):
    site, eng, _, events = served
    if site == "verify":
        assert eng.spec_steps_total > 0          # the verify block did run
    for name, order in SECONDS.items():
        spans = ended(events, name)
        assert spans, name
        for e in spans:
            mine = [k for k in e if k.endswith("_s")]
            assert mine == order, (name, mine)
            assert all(e[k] >= 0 for k in order), e
    for e in ended(events, "prefill_chunk"):
        # in the order they ran; what ran under the chunk's device time
        # (between the dispatch and the wait) is the span's remainder
        assert sum(e[k] for k in SECONDS["prefill_chunk"]) <= e["dur"] + 1e-3
    blocks = ended(events, "decode_block")
    assert all(e["wait_s"] <= e["dur"] + 1e-3 for e in blocks)
    if site == "block_diffusion":
        assert all("denoise_passes" in e for e in blocks)
    if site == "verify":
        # both the verify block and the plain block wrote it
        assert {"frozen_row_steps" in e for e in blocks} == {True, False}
    assert not [e for e in events if e["name"] == "admission_start"]


@pytest.mark.timeout(300)
def test_engine_step_times_the_decode_calls_host_seconds(served):
    _, _, _, events = served
    steps = ended(events, "engine_step")
    assert steps
    children: dict[str, list] = {}
    for name in ("prefill_chunk", "kv_install", "decode_block",
                 "engine_emit"):
        for e in ended(events, name):
            children.setdefault(e["parent"], []).append(e)
    for step in steps:
        mine = children.get(step["span"], [])
        block = [e for e in mine if e["name"] == "decode_block"]
        if not step["n_steps"]:
            assert not block and step["decode_host_s"] == 0.0
            continue
        block, = block
        emit, = [e for e in mine if e["name"] == "engine_emit"]
        # the uploads before the span opened, then the span less its wait
        assert step["decode_host_s"] >= (block["dur"] - block["wait_s"]
                                         - 1e-3)
        # admission's spans, the call's host time, its wait and the
        # hand-out lie end to end inside the step
        admission = sum(e["dur"] for e in mine
                        if e["name"] in ("prefill_chunk", "kv_install"))
        assert (admission + step["decode_host_s"] + block["wait_s"]
                + emit["dur"]) <= step["dur"] + 1e-3


# ------------------------------------------- a request's time in the engine


@pytest.mark.timeout(300)
def test_a_requests_queue_wait_and_admission_on_its_install(served):
    _, eng, seen, events = served
    installs = {e["request"]: e for e in ended(events, "kv_install")}
    admits = {e["request"]: e for e in events if e["name"] == "engine_admit"}
    assert set(installs) == set(admits) == {0, 1, 2}
    for rid, e in installs.items():
        assert all(e[k] >= 0 for k in REQUEST_FIELDS), e
        # in the queue, then in admission, then the install and a block
        assert e["queue_wait_s"] + e["admit_wall_s"] <= (
            seen[rid]["first"] - seen[rid]["submitted"])
        assert e["start_s"] + e["chunk_work_s"] <= e["admit_wall_s"] + 1e-3
        assert e["chunks"] == admits[rid]["chunks"]
        assert e["chunk_work_s"] == admits[rid]["dur"]
    # two slots: the third request waited for a turn the first two did not
    assert installs[2]["queue_wait_s"] > installs[0]["queue_wait_s"]
    waits = E._queue_wait_seconds.labels(eng.engine_id)
    assert waits.count == 3
    assert waits.sum == pytest.approx(
        sum(e["queue_wait_s"] for e in installs.values()))


@pytest.mark.timeout(300)
def test_a_page_blocked_heads_tries_lie_in_its_queue_wait(
        journal_dir, monkeypatch):
    # 4 pages of 8: each request needs 3 (11 + 12 tokens), so the second
    # waits at the queue's head until the first retires
    eng = build("plain", monkeypatch, kv_pages=4)
    blocked = []
    monkeypatch.setattr(eng._obs, "note_page_blocked",
                        lambda: blocked.append(time.monotonic()))
    _, returned = serve(eng)
    installs = {e["request"]: e
                for e in ended(events_of(journal_dir), "kv_install")}
    assert len(blocked) > 1                              # tried again
    for e in installs.values():
        # the seconds of the call that TOOK the request, so a part of the
        # admission's wall like the chunks: what is left is the time the
        # admission stood, never under nothing
        assert e["start_s"] + e["chunk_work_s"] <= e["admit_wall_s"] + 1e-3
    # the blocked tries lie in the queue wait: the head was not taken yet
    assert installs[1]["queue_wait_s"] >= installs[0]["admit_wall_s"]
    assert installs[1]["queue_wait_s"] >= blocked[-1] - blocked[0]
    assert eng.kv_page_ledger()["ok"] and len(returned) > 3


# ------------------------------------------- which calls ran the sampler


@pytest.mark.timeout(300)
@pytest.mark.parametrize("site", SITES)
def test_decode_block_counts_the_live_rows_that_sample(
        site, journal_dir, monkeypatch):
    """ISSUE 42: ``decode_block`` at its three sites carries
    ``sampling_rows``, the live rows with ``temperature`` > 0: 0 on every
    call of a greedy run (a free slot's default temperature is 1.0 and
    does not count), 1 while one sampling request lives, 0 once it has
    retired."""
    eng = build(site, monkeypatch, slots=4)
    serve(eng)                     # three requests: a slot stays free
    greedy_run = ended(events_of(journal_dir), "decode_block")
    assert greedy_run and all(e["slots"] < 4 for e in greedy_run)
    assert [e["sampling_rows"] for e in greedy_run] == [0] * len(greedy_run)
    sampling = eng.submit(CYCLIC[1], E.SamplingParams(
        temperature=0.7, max_new_tokens=4, seed=5))
    eng.submit(CYCLIC[0], E.SamplingParams(temperature=0.0,
                                           max_new_tokens=24))
    lived = []
    while eng.outstanding:
        eng.step()
        lived.append(any(r is not None and r.id == sampling
                         for r in eng._active))
    rows = [e["sampling_rows"] for e in ended(
        events_of(journal_dir), "decode_block")[len(greedy_run):]]
    assert rows == sorted(rows, reverse=True)
    assert set(rows) == {1, 0} and rows.count(1) <= 1 + sum(lived)
    if site == "verify":
        assert eng.spec_steps_total > 0


# ------------------------------------------------- nothing on, nothing written


@pytest.mark.timeout(300)
@pytest.mark.parametrize("site", SITES)
def test_no_journal_and_no_capture_writes_nothing_and_steps_as_before(
        site, tmp_path, monkeypatch):
    monkeypatch.delenv(EnvKey.JOURNAL_DIR, raising=False)
    monkeypatch.setattr(journal_mod, "_cached", None)
    monkeypatch.chdir(tmp_path)
    eng = build(site, monkeypatch)
    _, returned = serve(eng)
    assert os.listdir(tmp_path) == []
    # step() returns the slots still active: never more than there are,
    # and none once everything is served
    assert all(isinstance(n, int) and 0 <= n <= eng.slots for n in returned)
    assert max(returned) == eng.slots and returned[-1] == 0
    assert sorted(r.id for r in eng.poll_results()) == [0, 1, 2]
    journal_mod._cached = None
