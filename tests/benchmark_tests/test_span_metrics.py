"""The benchmark's reading of the program's own spans (PR 25): CPU, one file.

``span_reduce`` on a small hand-made trace (self time, device time inside a
span, gap attribution with an ``unattributed`` gap, scopes), the decode
counts against hand values, every new reader on a dummy run, the readers on
a program that has no such spans (they give nothing and do not raise), and
that the entries this PR adds to ``BENCHMARK.json`` stand behind the ones
it found, each with its file.
"""

from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, span_reduce  # noqa: E402
from benchmark import journal_reduce as jr  # noqa: E402
from benchmark.counts import decode, flops, peaks  # noqa: E402

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRAIN, SERVE = "gpt2-medium.train-steady", "gpt2-medium.serve-closed"
# what the accepted benchmark had (PR 24), in its order
ACCEPTED = ["snapshot_stall_ms", "step_ms", "step_mfu", "splash_roofline",
            "queue_ms.closed", "slot_occupancy", "itl_p95_ms"]
NEW = {
    "input_wait_ms": TRAIN, "dispatch_ms": TRAIN, "snapshot_fetch_s": TRAIN,
    "snapshot_stall_host_ms": TRAIN, "snapshot_stall_device_ms": TRAIN,
    "snapshot_stall_unphased_ms": TRAIN,
    "device_step_ms": TRAIN, "host_gap_attributed.train": TRAIN,
    "decoding_slots": SERVE, "decode_step_ms": SERVE,
    "prefill_chunk_ms": SERVE, "engine_host_ms": SERVE,
    "decode_roofline": SERVE, "prefill_roofline": SERVE,
    "host_gap_attributed.serve": SERVE,
}
CFG = harness.load_json(os.path.join(ROOT, "benchmark", "configs",
                                     "gpt2-medium.json"))


def reader(name: str):
    return harness.load_named("layer_metrics", name).read


@pytest.fixture(scope="module")
def reduced() -> dict:
    rec = harness.load_json(os.path.join(DATA, "span_events.json"))
    spans = [tuple(s) for s in rec["spans"]]
    devices = {k: [tuple(e) for e in v] for k, v in rec["devices"].items()}
    return json.loads(json.dumps(span_reduce.reduce(spans, devices)))


# ------------------------------------------------------------ the reducer


def test_self_time_is_a_span_less_its_children_on_the_same_thread(reduced):
    spans = reduced["spans"]
    assert spans["train_step"]["count"] == 2
    first, second = spans["train_step"]["events"]
    # 1000 us less h2d 100, dispatch 300, block 500; the writer thread's
    # snapshot_fetch overlaps it and takes nothing from it
    assert first["self_s"] == pytest.approx(100e-6)
    assert second["self_s"] == pytest.approx(800e-6)
    assert spans["snapshot_fetch"]["self_s"] == pytest.approx(2000e-6)
    step, = spans["engine_step"]["events"]
    assert step["dur_s"] == pytest.approx(3000e-6)
    assert step["self_s"] == pytest.approx(300e-6)
    assert step["fields"] == {"queued": 2, "decoding_slots": 3,
                              "n_steps": 2}
    assert spans["dispatch"]["wall_s"] == pytest.approx(500e-6)


def test_device_time_inside_spans_and_between_step_starts(reduced):
    block, = reduced["spans"]["decode_block"]["events"]
    assert block["device_busy_s"] == pytest.approx(1200e-6)
    chunk, = reduced["spans"]["prefill_chunk"]["events"]
    assert chunk["device_busy_s"] == pytest.approx(600e-6)
    assert "device_busy_s" not in reduced["spans"]["engine_step"]["events"][0]
    assert reduced["steps"] == [{"step": 5,
                                 "interval_s": pytest.approx(1200e-6),
                                 "device_busy_s": pytest.approx(700e-6)}]
    assert reduced["busy_s"] == pytest.approx(3000e-6)
    assert reduced["window_s"] == pytest.approx(5300e-6)


def test_each_instant_of_a_gap_goes_to_the_innermost_span_over_it(reduced):
    gaps = {round(g["gap_s"] * 1e6): g for g in reduced["gaps"]}
    assert sorted(gaps) == [600, 700, 1000]
    # 900-1600 us: the first step 100, then only the writer's fetch 200,
    # the second step 100 + 100 around its dispatch 200: train_step 300
    assert gaps[700]["span"] == "train_step"
    assert (gaps[700]["after"], gaps[700]["before"]) == ("fusion.1",
                                                         "fusion.2")
    # 2100-3100 us: step 100, fetch 300, nothing 500, prefill_chunk 100
    assert gaps[1000]["span"] == "unattributed"
    # 3700-4300 us: prefill_chunk 100, the step's own 200, decode_block 300
    assert gaps[600]["span"] == "decode_block"
    idle = reduced["idle"]
    assert idle["total_s"] == pytest.approx(2300e-6)
    assert idle["attributed_s"] == pytest.approx(1800e-6)
    assert {k: round(v * 1e6) for k, v in idle["by_span"].items()} == {
        "snapshot_fetch": 500, "unattributed": 500, "train_step": 400,
        "decode_block": 300, "dispatch": 200, "prefill_chunk": 200,
        "engine_step": 200}
    table = span_reduce.gap_table(reduced)
    assert "`unattributed` | 1 | 0.0005" in table
    assert "fusion.1 -> fusion.2" in table
    # the command line shows the scopes too: nothing else reads them
    assert "| `attn` | 0.0007 | 23.3 |" in table
    assert "no operation carries a scope" not in table


def test_scopes_take_the_innermost_name_and_a_while_only_its_own_time(
        reduced):
    us = {k: round(v * 1e6) for k, v in reduced["scopes"].items()}
    assert us == {"attn": 700, "weight_cast": 600, "lm_head": 600,
                  "mlp": 500, "kv_read": 500, "unscoped": 100}
    assert span_reduce.scope_of("jit(f)/attn/kv_write/scatter") == "kv_write"
    assert span_reduce.scope_of("") == "unscoped"
    assert span_reduce.scope_of("state.params['lm_head']:") == "unscoped"
    # a transform wraps the scope it differentiates
    assert span_reduce.scope_of(
        "jit(_step)/transpose(jvp(ce_loss))/while/body/reduce_sum:"
    ) == "ce_loss"
    assert span_reduce.scope_of(
        "jit(_step)/jvp()/while/body/checkpoint/rematted_computation/mlp/"
        "bse,ef->bsf/dot_general:") == "mlp"


def _pb(no: int, value) -> bytes:
    """One protobuf field, by hand: a varint or a length-delimited one."""
    def varint(n: int) -> bytes:
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    if isinstance(value, int):
        return varint(no << 3) + varint(value)
    return varint(no << 3 | 2) + varint(len(value)) + value


def test_name_stacks_are_read_from_the_files_event_metadata():
    """``ProfileData`` hands out no event metadata; the reducer reads the
    ``tf_op`` stat from the file's own fields. A two-operation plane made
    by hand: one stack as a string, one as a reference to a stat name."""
    stat_names = {1: b"tf_op", 2: b"flops", 300: b"jit(f)/mlp/dot_general:"}
    plane = _pb(2, b"/device:TPU:0") + _pb(3, b"\x08\x01" * 9)  # a line
    for key, name in stat_names.items():
        plane += _pb(5, _pb(1, key) + _pb(2, _pb(1, key) + _pb(2, name)))
    ops = {7: (b"%fusion.1 = f32[8] fusion(...)",
               _pb(1, 2) + _pb(4, 99), _pb(1, 1) + _pb(5, b"jit(f)/attn/exp:")),
           8: (b"%fusion.2 = f32[8] fusion(...)", _pb(1, 1) + _pb(7, 300)),
           9: (b"%copy.3 = f32[8] copy(...)", _pb(1, 2) + _pb(4, 5))}
    for key, (name, *stats) in ops.items():
        meta = _pb(1, key) + _pb(2, name) + b"".join(_pb(5, st)
                                                      for st in stats)
        plane += _pb(4, _pb(1, key) + _pb(2, meta))
    host = _pb(2, b"/host:CPU") + _pb(4, _pb(1, 1) + _pb(2, _pb(2, b"x")))
    blob = _pb(1, plane) + _pb(1, host) + _pb(4, b"hostname")
    assert span_reduce.name_stacks(blob) == {"/device:TPU:0": {
        "%fusion.1 = f32[8] fusion(...)": "jit(f)/attn/exp:",
        "%fusion.2 = f32[8] fusion(...)": "jit(f)/mlp/dot_general:"}}


def test_reducing_a_trace_without_device_planes_or_spans():
    out = span_reduce.reduce([], {})
    assert out["spans"] == {} and out["gaps"] == [] and out["steps"] == []
    assert out["busy_s"] == 0 and out["idle"]["total_s"] == 0
    only_device = span_reduce.reduce([], {"/device:TPU:0": [
        ("a", 0, 10, ""), ("b", 2_000_000, 10, "")]})
    assert only_device["idle"]["by_span"] == {
        "unattributed": pytest.approx(0.00199999)}
    assert only_device["gaps"][0]["span"] == "unattributed"
    assert "no operation carries a scope" in span_reduce.gap_table(
        only_device)


# -------------------------------------------------------------- the counts


def test_decode_counts_by_hand():
    weights = 24 * 12582912 + 51463168          # as counts/flops has it
    assert flops.matmul_params(CFG) == weights
    row = 24 * 2 * 1024 * 2                     # one token's k and v, bf16
    step = decode.decode_step(CFG, slots=4, context=300)
    assert step["bytes"] == weights * 2 + 4 * 301 * row
    assert step["flops"] == 4 * (2 * weights + 24 * 4 * 1024 * 300)
    peak = peaks.peaks("TPU v5 lite")
    # memory bound: 0.71e9 weight bytes and 0.12e9 cache bytes at 819 GB/s
    assert decode.least_seconds(step, peak) == pytest.approx(
        step["bytes"] / 819e9)
    assert 0.9e-3 < decode.least_seconds(step, peak) < 1.2e-3
    chunk = decode.prefill_chunk(CFG, tokens=64, context=128)
    body = weights - 51463168
    keys = 64 * 128 + 64 * 65 / 2
    assert chunk["flops"] == 2 * (body * 64 + 51463168) + 24 * 4 * 1024 * keys
    assert chunk["bytes"] == weights * 2 + 192 * row
    # slots that hold no request need nothing but the weights
    assert decode.decode_step(CFG, 0, 0)["flops"] == 0


# ------------------------------------------------------------ the readers


def serve_run(reduced: dict) -> dict:
    return {"trace": {"busy_s": 1.0}, "config": CFG,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            "rows": [{"prompt_tokens": 200, "output_tokens": 100},
                     {"prompt_tokens": 300, "output_tokens": 100}]}


def test_serving_readers_on_a_dummy_run(reduced, monkeypatch):
    monkeypatch.setattr(span_reduce, "for_run", lambda run: reduced)
    run = serve_run(reduced)
    assert reader("decoding_slots")(run) == 3.0
    assert reader("decode_step_ms")(run) == pytest.approx(0.6)
    assert reader("prefill_chunk_ms")(run) == pytest.approx(0.8)
    assert reader("engine_host_ms")(run) == pytest.approx(0.3)
    assert reader("host_gap_attributed.serve")(run) == pytest.approx(
        100 * 1800 / 2300)
    peak = peaks.peaks("TPU v5 lite")
    least = 2 * decode.least_seconds(decode.decode_step(CFG, 3, 300), peak)
    assert reader("decode_roofline")(run) == pytest.approx(
        100 * least / 1200e-6)
    least = decode.least_seconds(decode.prefill_chunk(CFG, 64, 0), peak)
    assert reader("prefill_roofline")(run) == pytest.approx(
        100 * least / 600e-6)
    # a CPU run's times are never written under a device metric's name
    run["device"]["platform"] = "cpu"
    assert reader("decode_roofline")(run) is None
    assert reader("prefill_roofline")(run) is None


def train_journal(tmp_path) -> dict:
    """80 steps of 100 ms (2 ms the host's own; every tenth is logged and
    waits in ``on_step``, not in ``block``), the window's two cycles of 40
    behind sync point 20; the first snapshot costs the host 5 x 100 ms and
    the wait 5 x 60 ms, the second 400 and 200 ms in one logged step."""
    journal = tmp_path / "journal"
    journal.mkdir()
    extra_host = {s: 0.1 for s in range(41, 46)} | {81: 0.4}
    extra_wait = {s: 0.06 for s in range(46, 51)} | {81: 0.2}
    t = 1000.0
    with open(journal / "events.jsonl", "w") as f:
        for step in range(2, 121):
            host = 0.002 + extra_host.get(step, 0.0)
            wait = 0.098 + extra_wait.get(step, 0.0)
            t += host + wait
            block = 0.0 if step % 10 == 1 else wait - 0.001
            f.write(json.dumps({
                "t": t, "span": f"s{step}", "name": "train_step", "ev": "p",
                "step": step, "dur": host + wait, "data_wait_s": 0.0005,
                "h2d_s": 0.0005, "dispatch_s": host - 0.001,
                "block_s": block, "ckpt_s": 0.0}) + "\n")
        for i, (at, dur) in enumerate(((1005.0, 4.0), (1009.5, 5.0),
                                       (2000.0, 9.0))):
            for ev, when in (("b", at - dur), ("e", at)):
                f.write(json.dumps({
                    "t": when, "span": f"f{i}", "name": "snapshot_fetch",
                    "ev": ev, "step": 40 * (i + 1), "bytes": 4.87e9,
                    **({"dur": dur} if ev == "e" else {})}) + "\n")
    return {"files": {"journal": str(journal)}, "kill_t": None,
            "first_sync": 20, "steps": 80, "cycle": 40, "log_interval": 10,
            "window": (1000.0, 1012.0), "trace": None}


def test_training_readers_on_a_dummy_run(tmp_path):
    run = train_journal(tmp_path)
    assert sorted(jr.window_steps(run)) == list(range(21, 101))
    assert reader("input_wait_ms")(run) == pytest.approx(0.5)
    assert reader("dispatch_ms")(run) == pytest.approx(1.5)
    # cycles 21-60 and 61-100: 500 and 400 ms of host work; 300 ms more in
    # `block` in the first; in the second the 200 ms fall on a logged step,
    # whose wait is un-phased; the medians of the two cycles
    assert reader("snapshot_stall_host_ms")(run) == pytest.approx(450.0)
    assert reader("snapshot_stall_device_ms")(run) == pytest.approx(150.0)
    assert reader("snapshot_stall_unphased_ms")(run) == pytest.approx(100.0)
    # the two fetches that ended inside the window, not the third
    assert reader("snapshot_fetch_s")(run) == pytest.approx(4.5)
    # a logged step's wait moves from block to the loss fetch: a step is
    # held against the clean steps at its own place in the logging interval,
    # so the split does not mistake that for a faster device
    points = jr.window_steps(run)
    assert points[31]["block_s"] == 0.0
    assert jr.unphased_s(points[31]) == pytest.approx(
        jr.block_s(points[32]) + jr.unphased_s(points[32]))
    del run["log_interval"]                      # an older driver's run
    assert reader("snapshot_stall_host_ms")(run) == pytest.approx(450.0)


def test_device_step_and_gap_share_on_a_dummy_training_run(reduced,
                                                           monkeypatch):
    monkeypatch.setattr(span_reduce, "for_run", lambda run: reduced)
    run = {"trace": {"busy_s": 1.0}}
    assert reader("device_step_ms")(run) == pytest.approx(0.7)
    assert reader("host_gap_attributed.train")(run) == pytest.approx(
        100 * 1800 / 2300)
    no_gap = dict(reduced, idle={"total_s": 0.0, "attributed_s": 0.0,
                                 "by_span": {}})
    monkeypatch.setattr(span_reduce, "for_run", lambda run: no_gap)
    assert reader("host_gap_attributed.train")(run) == 100.0


def test_for_run_reduces_a_bundle_once_in_a_child(tmp_path):
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.telemetry.journal import annotate

    journal = tmp_path / "work" / "journal"
    bundle = journal / "bundles" / "node0_profile_1"
    bundle.mkdir(parents=True)
    jax.profiler.start_trace(str(bundle / "profile"))
    try:
        for step in (1, 2, 3):
            with annotate("train_step", step_num=step):
                with annotate("dispatch"):
                    jnp.ones((4, 4)).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    run = {"trace": {"busy_s": 0.0}, "files": {"journal": str(journal)}}
    out = span_reduce.for_run(run)
    assert out["spans"]["train_step"]["count"] == 3
    assert [s["step"] for s in out["steps"]] == [1, 2]
    kept = tmp_path / "work" / span_reduce.OUT_NAME
    stamp = kept.stat().st_mtime_ns
    assert span_reduce.for_run(run) is out       # kept with the run
    again = dict(run)
    del again["_span_reduce"]
    assert span_reduce.for_run(again) == out     # read back, not made again
    assert kept.stat().st_mtime_ns == stamp
    # no device plane on the CPU: the device readers give nothing
    assert reader("device_step_ms")(run) is None
    assert reader("host_gap_attributed.train")(run) is None
    assert span_reduce.for_run({"trace": None}) is None


def test_a_reduction_that_fails_runs_once_and_leaves_a_note(tmp_path):
    """Eight readers share one trace: a broken one must not cost eight
    children, and the run's lines say why the metrics are missing."""
    journal = tmp_path / "work" / "journal"
    bundle = journal / "bundles" / "node0_profile_1"
    bundle.mkdir(parents=True)
    (bundle / "torn.xplane.pb").write_bytes(b"\x0a\xff\xff")
    run = {"trace": {"busy_s": 1.0}, "files": {"journal": str(journal)}}
    assert span_reduce.for_run(run) is None
    note, = run["notes"]
    assert note["span_reduce_failed"] == 1 and "Error" in note["log"]
    log = tmp_path / "work" / "span_reduce.log"
    stamp = log.stat().st_mtime_ns
    assert reader("device_step_ms")(run) is None
    assert reader("host_gap_attributed.train")(run) is None
    assert log.stat().st_mtime_ns == stamp and len(run["notes"]) == 1


# ------------------- a program without the spans; entries added, none edited


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_gives_nothing_on_a_program_without_the_spans(
        name, tmp_path, monkeypatch):
    """The parent commit writes no such span or field: each reader returns
    nothing there and does not raise, and the line leaves the metric out."""
    bare = span_reduce.reduce([], {"/device:TPU:0": [
        ("fusion.1", 0, 1000, ""), ("fusion.2", 3_000_000, 1000, "")]})
    monkeypatch.setattr(span_reduce, "for_run", lambda run: bare)
    journal = tmp_path / "journal"
    journal.mkdir()
    with open(journal / "events.jsonl", "w") as f:   # the old point
        f.write(json.dumps({"t": 1.0, "span": "a", "name": "train_step",
                            "ev": "p", "step": 30, "dur": 0.01}) + "\n")
    run = dict(serve_run(bare), files={"journal": str(journal)},
               kill_t=None, first_sync=20, steps=80, cycle=40,
               window=(0.0, 10.0))
    assert reader(name)(run) is None
    assert reader(name)({"trace": None, "device": {"platform": "tpu"},
                         "kill_t": 1.0}) is None


def test_new_entries_stand_behind_the_accepted_ones_each_with_its_file():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[:len(ACCEPTED)] == ACCEPTED
    assert set(names[len(ACCEPTED):]) == set(NEW)
    cells = {w["name"] for w in BENCH["workloads"]}
    assert cells == {TRAIN, SERVE}               # no cell added
    moved = {TRAIN: "train_tokens_per_s", SERVE: "serve_tokens_per_s"}
    for m in BENCH["per_layer"][len(ACCEPTED):]:
        assert m["workloads"] == [NEW[m["name"]]]
        assert m["moves"] == moved[NEW[m["name"]]]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("program_span", "program_counter",
                               "device_trace")
        assert callable(reader(m["name"]))
    layers = {m["layer"] for m in BENCH["per_layer"][:len(ACCEPTED)]}
    assert {m["layer"] for m in BENCH["per_layer"]} - layers == {"device"}
    assert len(json.dumps(BENCH)) < 64 * 1024
