"""The seven metrics that read the engine's queue wait and the host's
seconds around its device calls (ISSUE 36): CPU, one file.

Each reader on a hand-made slice with the arithmetic done by hand, on a
program that writes no such fields (nothing, and no raise), the entries at
the end of ``BENCHMARK.json`` each with its file, and one rehearsed TRACED
run of the cell that lists all seven: the capture's host plane carries the
fields as numbers, so the six metrics that need no device time show in
``would_print``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, span_reduce  # noqa: E402

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SERVING = [w["name"] for w in BENCH["workloads"] if ".serve-" in w["name"]]
# the one cell whose engine queue can fill: more clients than admission
# keeps up with (where clients equal slots a request arrives to a free slot)
QUEUES = "minicpm-sala.serve-closed-16k"
# name -> (unit, the dummy slice's reading)
NEW = {
    # installs waited 40 ms, 0.5 s and 9 s: the median, and their count
    "engine_queue_ms": ("ms", 500.0),
    # taken up 6 ms, 0.8 s and 1.6 s before their installs began, of 2, 1
    # and 10 chunks: the slice's sums, so one long prompt reads as many short
    "admission_ms": ("ms/chunk", 2406.0 / 13),
    # 2.406 s of admission; 0.071 s of starts and 1.7035 s of chunks in it
    "admission_decode_share": ("%", 100 * (2.406 - 0.071 - 1.7035) / 2.406),
    # starts of 1, 20 and 50 ms over the same 13 chunks
    "admission_start_ms": ("ms/chunk", 71.0 / 13),
    # build + dispatch + after of the three chunks: 0.4, 0.7 and 1.5 ms
    "chunk_exposed_host_ms": ("ms", 0.7),
    # the steps that decoded: 1.2 and 3.0 ms (the one that did not: left out)
    "decode_call_host_ms": ("ms", 2.1),
    # one wait of 97.5 ms over 30 ms of device work; the block's 60 over 20
    # stays under 50 ms; the device's window runs 0.4 -> 142 ms
    "stall_idle_share": ("%", 100 * 0.0675 / 0.1416),
}


def reader(name: str):
    return harness.load_named("layer_metrics", name).read


@pytest.fixture(scope="module")
def reduced() -> dict:
    rec = harness.load_json(os.path.join(DATA, "engine_phase_events.json"))
    spans = [tuple(s) for s in rec["spans"]]
    devices = {k: [tuple(e) for e in v] for k, v in rec["devices"].items()}
    return json.loads(json.dumps(span_reduce.reduce(spans, devices)))


def test_the_reducer_keeps_every_new_field_as_a_number(reduced):
    assert reduced["window_s"] == pytest.approx(0.1416)
    install = reduced["spans"]["kv_install"]["events"][0]["fields"]
    assert install == {"request": 7, "slot": 1, "tokens": 128,
                       "queue_wait_s": 0.04, "start_s": 0.001,
                       "admit_wall_s": 0.006, "chunk_work_s": 0.0035,
                       "chunks": 2}
    chunk = reduced["spans"]["prefill_chunk"]["events"][2]
    assert chunk["device_busy_s"] == pytest.approx(0.030)
    assert chunk["fields"]["wait_s"] == 0.0975
    step = reduced["spans"]["engine_step"]["events"][0]["fields"]
    assert step["decode_host_s"] == 0.0012
    block = reduced["spans"]["decode_block"]["events"][0]["fields"]
    assert block == {"slots": 2, "n_steps": 4, "wait_s": 0.0033}


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_on_the_dummy_slice_by_hand(name, reduced, monkeypatch):
    monkeypatch.setattr(span_reduce, "for_run", lambda run: reduced)
    run = {"trace": {"busy_s": 1.0}}
    assert reader(name)(run) == pytest.approx(NEW[name][1])
    # a slice may hold two installs or thirty: the median says how many
    notes = run.get("notes", [])
    assert notes == ([{"engine_queue_ms_installs": 3}]
                     if name == "engine_queue_ms" else [])


def test_stall_share_needs_the_device_plane(reduced, monkeypatch):
    """A CPU capture has host spans and no device plane: no busy time
    inside a span, so no stall can be told from a long step."""
    hostonly = json.loads(json.dumps(reduced))
    for name in ("prefill_chunk", "decode_block"):
        for e in hostonly["spans"][name]["events"]:
            del e["device_busy_s"]
    monkeypatch.setattr(span_reduce, "for_run", lambda run: hostonly)
    assert reader("stall_idle_share")({"trace": {}}) is None
    assert reader("chunk_exposed_host_ms")({"trace": {}}) == pytest.approx(0.7)
    # no stall in the slice reads 0, not nothing
    calm = json.loads(json.dumps(reduced))
    calm["spans"]["prefill_chunk"]["events"][2]["fields"]["wait_s"] = 0.031
    monkeypatch.setattr(span_reduce, "for_run", lambda run: calm)
    assert reader("stall_idle_share")({"trace": {}}) == 0.0


@pytest.mark.parametrize("name", ["admission_ms", "admission_start_ms"])
def test_an_admissions_reading_does_not_follow_the_prompt_drawn(
        name, reduced, monkeypatch):
    """The 16k cell's slice holds ONE install: of a prompt twice as long
    (twice the chunks, twice the seconds) it reads the same, where a median
    of the installs' seconds would double. Installs that ran no chunk
    (bundles handed over) read nothing."""
    readings = []
    for times in (1, 2):
        one = json.loads(json.dumps(reduced))
        event = one["spans"]["kv_install"]["events"][2]
        for k in ("start_s", "admit_wall_s", "chunk_work_s", "chunks"):
            event["fields"][k] *= times
        one["spans"]["kv_install"]["events"] = [event]
        monkeypatch.setattr(span_reduce, "for_run", lambda run, one=one: one)
        readings.append(reader(name)({"trace": {}}))
    assert readings[0] == pytest.approx(readings[1])
    assert readings[0] == pytest.approx(
        {"admission_ms": 160.0, "admission_start_ms": 5.0}[name])
    event["fields"]["chunks"] = 0
    assert reader(name)({"trace": {}}) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_gives_nothing_on_a_program_without_the_fields(
        name, monkeypatch):
    """The parent commit writes the spans without these fields: each reader
    returns nothing there and does not raise, nor where there is no trace."""
    rec = harness.load_json(os.path.join(DATA, "span_events.json"))
    parent = span_reduce.reduce(
        [tuple(s) for s in rec["spans"]],
        {k: [tuple(e) for e in v] for k, v in rec["devices"].items()})
    assert parent["spans"]["prefill_chunk"]["count"] == 1
    monkeypatch.setattr(span_reduce, "for_run", lambda run: parent)
    run = {"trace": {"busy_s": 1.0}}
    assert reader(name)(run) is None and "notes" not in run
    bare = span_reduce.reduce([], {})
    monkeypatch.setattr(span_reduce, "for_run", lambda run: bare)
    assert reader(name)(run) is None
    monkeypatch.undo()
    assert reader(name)({"trace": None}) is None


def test_seven_entries_close_the_list_each_with_its_file():
    tail = BENCH["per_layer"][-len(NEW):]
    assert [m["name"] for m in tail] == list(NEW)
    assert len(SERVING) == 4
    assert QUEUES in SERVING
    for m in tail:
        assert m == {"name": m["name"], "unit": NEW[m["name"]][0],
                     "better": "lower", "source": "program_span",
                     "layer": "serving engine",
                     "moves": "serve_tokens_per_s",
                     "workloads": ([QUEUES] if m["name"] == "engine_queue_ms"
                                   else SERVING)}
        assert callable(reader(m["name"]))
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.timeout(420)
def test_a_rehearsed_traced_run_prints_the_six_that_need_no_device_time():
    """``--rehearse --trace 1`` captures on the CPU: host plane only. The
    program's fields reach the line through the capture, the reducer and the
    readers; ``stall_idle_share`` needs the device plane and stays out."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("DLROVER_TPU_JOURNAL_DIR", None)
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         QUEUES, "--seed", str(2**31 + 36), "--seconds",
         "8", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=400)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(x) for x in out.stdout.strip().splitlines()]
    metrics = lines[-1]["would_print"]["metrics"]
    assert set(NEW) - set(metrics) == {"stall_idle_share"}
    for name in set(NEW) & set(metrics):
        assert metrics[name]["unit"] == NEW[name][0]
        assert metrics[name]["value"] >= 0
    assert metrics["admission_decode_share"]["value"] <= 100
    # the timed reading of the host around a decode call lies inside what
    # the span's self time charges to the step
    assert metrics["decode_call_host_ms"]["value"] > 0
    counts = [n["note"]["engine_queue_ms_installs"] for n in lines
              if "engine_queue_ms_installs" in n.get("note", {})]
    assert len(counts) == 1 and counts[0] > 0
