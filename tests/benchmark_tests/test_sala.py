"""The benchmark's pieces for a HYBRID-CACHE configuration (MiniCPM-SALA:
``drivers/serve_gateway_hybrid.py``, ``serve_child_hybrid.py``,
``reference/minicpm_sala.py``, ``counts/sala.py``): the cell's traffic, the
configuration file against the catalog's publication and the program's
preset, the counts by hand, a rehearsed run and its controls.
"""

import json
import os
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, traffic  # noqa: E402
from benchmark.counts import peaks, sala  # noqa: E402

CELL = "minicpm-sala.serve-closed-16k"
CONFIG = harness.load_json(os.path.join(
    ROOT, "benchmark", "configs", "minicpm-sala.json"))
WORKLOAD = harness.load_json(os.path.join(
    ROOT, "benchmark", "workloads", f"{CELL}.json"))
BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
NEW_METRICS = ["sala_decode_roofline", "sala_prefill_roofline",
               "sparse_keys_scored_over_selected", "decode_context_tokens"]
SERVING_METRICS = ["queue_ms.closed", "slot_occupancy", "itl_p95_ms",
                   "decoding_slots", "decode_step_ms", "prefill_chunk_ms",
                   "engine_host_ms", "host_gap_attributed.serve"]


# ----------------------------------------------- the cell, its file, its sizes


def test_traffic_stays_inside_the_cells_lengths():
    mix, serving = WORKLOAD["traffic_mix"], CONFIG["serving"]
    big = 2**31 + 12345
    a, b = (traffic.requests(mix, big, 80.0) for _ in range(2))
    c = traffic.requests(mix, big + 1, 80.0)
    assert a == b and a != c and len(a) == mix["pool"] == 8
    sparse = CONFIG["assumed"]["sparse_config"]
    for r in a:
        # every prompt passes dense_len: every decode step selects
        assert sparse["dense_len"] <= 8192 <= r.prompt_tokens <= 32768
        assert 64 <= r.max_new_tokens <= 768
        assert r.prompt_tokens + r.max_new_tokens <= serving["max_len"]
    assert sorted((r.prompt_tokens, r.max_new_tokens) for r in a) == \
        sorted((r.prompt_tokens, r.max_new_tokens) for r in c)
    assert mix["arrivals"] == {"kind": "closed", "clients_per_slot": 1}
    assert mix["ramp_s"] == 30
    assert serving == {"slots": 16, "max_len": 33792, "prefill_len": 512,
                       "decode_block": 8, "prefix_cache_entries": 2,
                       "kv_pages": 0, "admission_deadline_s": 120.0}
    # the selection reads a row as whole blocks, a chunk as whole strides
    assert serving["max_len"] % sparse["block_size"] == 0
    assert serving["prefill_len"] % sparse["kernel_stride"] == 0


def test_the_file_is_the_publication_less_the_stated_depth():
    entry = next(c for c in BENCH["configs"] if c["name"] == "minicpm-sala")
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "mixer_types"]
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmark/configs/minicpm-sala.json"
    pub = CONFIG["published"]
    assert pub["num_hidden_layers"] == 32 == len(pub["mixer_types"])
    # a cut in DEPTH alone: the published layers 9-16 (the issue's
    # fallback), 2 sparse and 6 lightning (the published 1 : 3), every
    # width as published
    assert CONFIG["mixer_types"] == pub["mixer_types"][9:17] == (
        ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"])
    assert CONFIG["num_hidden_layers"] == 8 >= 4
    assert CONFIG["mixer_types"].count("minicpm4") == 2
    assert pub["mixer_types"].count("minicpm4") == 8
    assert (CONFIG["hidden_size"], CONFIG["intermediate_size"],
            CONFIG["vocab_size"], CONFIG["head_dim"],
            CONFIG["num_key_value_heads"], CONFIG["lightning_nkv"]) == (
        4096, 16384, 73448, 128, 2, 32)
    assert CONFIG["deployment"]["chips_per_layer"] == 1
    for item in ("sparse_config", "lightning_decay", "lightning_output_norm",
                 "rope", "mup", "weights", "torch_dtype"):
        assert item in CONFIG["assumed"]
    # every published value is the program's preset's (or the run stops)
    from benchmark import serve_child_hybrid as child

    cfg = child.program_config(CONFIG)
    assert (cfg.n_layers, cfg.mixer_types.count("sparse"), cfg.param_dtype,
            cfg.dtype) == (8, 2, "bfloat16", "bfloat16")
    assert cfg.mixer_types == ("sparse",) + ("lightning",) * 6 + ("sparse",)
    assert cfg.param_count == CONFIG["sizes"]["parameters"] == 2820569088
    assert CONFIG["sizes"]["weight_bytes"] == 2 * cfg.param_count
    # the counts hold the matrices; the program also has its norms' scales
    norms = 8 * (2 * 4096 + 2 * 128) + 6 * 4096 + 4096
    assert sala.held_parameters(CONFIG) == cfg.param_count - norms
    bad = {**CONFIG, "scale_emb": 11}
    with pytest.raises(SystemExit, match="scale_emb"):
        child.program_config(bad)
    bad = {**CONFIG, "assumed": {**CONFIG["assumed"], "sparse_config": {
        **CONFIG["assumed"]["sparse_config"], "topk": 32}}}
    with pytest.raises(SystemExit, match="topk"):
        child.program_config(bad)


def test_the_new_entries_each_have_their_file():
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert cells[CELL] == {**cells[CELL], "config": "minicpm-sala",
                           "traffic": "serve-closed-16k", "chips": 1}
    assert WORKLOAD["driver"] == "serve_gateway_hybrid"
    assert callable(harness.load_named("drivers", WORKLOAD["driver"]).run)
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    assert list(per_layer)[-4:] == NEW_METRICS
    for name in NEW_METRICS:
        m = per_layer[name]
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"
        assert callable(harness.load_named("layer_metrics", name).read)
    for name in SERVING_METRICS:
        assert per_layer[name]["workloads"][-1] == CELL
    tokens = next(m for m in BENCH["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert tokens["workloads"][-1] == CELL
    assert len(BENCH["workloads"]) == 5
    assert not any(w["chips"] == 4 for w in BENCH["workloads"])
    assert len(json.dumps(BENCH)) < 64 * 1024
    for key in ("decode_logit_gap", "decode_logit_gap_mean",
                "prefill_logit_gap", "tail_logit_gap_3rd",
                "tail_logit_gap_median", "prompt_positions",
                "tail_positions"):
        assert key in WORKLOAD["limits"]


def test_counts_by_hand():
    s = sala.sizes(CONFIG)
    assert s["ffn"] == 3 * 4096 * 16384 == 201326592
    # W_q, W_o, the gate: 4096 x 4096 each; W_k, W_v: 4096 x 2 x 128
    assert s["sparse_mixer"] == 3 * 16777216 + 2 * 1048576 == 52428800
    assert s["lightning_mixer"] == 5 * 16777216 == 83886080
    assert s["head"] == 4096 * 73448
    assert (s["sparse_layers"], s["lightning_layers"], s["state"],
            s["heads_a_group"], s["row"]) == (2, 6, 32 * 128 * 128, 16, 256)
    body = 2 * (52428800 + 201326592) + 6 * (83886080 + 201326592)
    assert body == 2218786816
    assert sala.held_parameters(CONFIG) == body + 2 * 300843008 == 2820472832

    # a block of 8 steps, 16 slots, 10 row-steps frozen: 118 live. Their
    # positions sum to 118 x 16000; each selected 63 whole blocks and 30
    # keys of its own (a sparse layer and group: 4 of them a row-step)
    live, context = 118, 118 * 16000
    selected = live * 4 * (63 * 64 + 30)
    call = sala.decode_block(CONFIG, 16, 8, 10, context, selected)
    seen = context / 16 * 2 * 2
    state = live * 6 * 524288
    assert call["flops"] == (
        live * 2.0 * (body + 300843008)
        + 4.0 * 16 * 128 * selected + 2.0 * 16 * 128 * seen + 4.0 * state)
    assert call["bytes"] == (
        2 * (8 * (body + 300843008) + live * 4096 + live * 2 * 2 * 256)
        + 2 * 128 * (2 * selected + seen) + 2 * 4 * state)
    peak = peaks.peaks("TPU v5 lite")
    assert sala.least_seconds(call, peak) == call["bytes"] / 819e9
    # weights 40.31e9, state 2.97e9, selected rows 0.98e9 of 44.3e9 bytes
    assert 54e-3 < sala.least_seconds(call, peak) < 55e-3

    # a whole chunk behind 16384 cached tokens: every query past dense_len
    assert sala.compressed_seen(s, 512, 16384) == sum(
        (t + 1 - 32) // 16 + 1 for t in range(16384, 16896))
    assert sala.compressed_seen(s, 512, 0) == 0
    assert sala.compressed_seen(s, 512, 7936) == sum(
        (t + 1 - 32) // 16 + 1 for t in range(8192, 8448))
    selected = 512 * 4 * (63 * 64 + 32)
    call = sala.prefill_chunk(CONFIG, 512, 16384, selected)
    seen = sala.compressed_seen(s, 512, 16384) * 4
    assert call["flops"] == (
        2.0 * (512 * body + 300843008) + 4.0 * 2048 * selected
        + 2.0 * 2048 * seen + 4.0 * 512 * 6 * 524288)
    assert call["bytes"] == (
        2 * (body + 300843008 + 512 * 4096 + 2 * 16896 * (512 + 16))
        + 2 * 4 * 6 * 524288)
    assert sala.least_seconds(call, peak) == call["flops"] / 197e12
    assert 11e-3 < sala.least_seconds(call, peak) < 13e-3


def test_the_new_metrics_read_nothing_from_a_run_without_their_fields():
    """What the parent's program gives: spans without the counters."""
    run = {"device": {"platform": "tpu", "kind": "TPU v5 lite"},
           "rows": [{"prompt_tokens": 10, "output_tokens": 4}],
           "config": {"n_embd": 8},
           "_span_reduce": {"spans": {
               "decode_block": {"events": [{
                   "device_busy_s": 0.1,
                   "fields": {"slots": 4, "n_steps": 8}}]},
               "prefill_chunk": {"events": [{
                   "device_busy_s": 0.1,
                   "fields": {"tokens": 64, "context": 0}}]}}}}
    for name in NEW_METRICS:
        assert harness.load_named("layer_metrics", name).read(run) is None


def test_the_new_metrics_read_the_spans_fields():
    counted = {"sparse_keys_selected": 118 * 4 * 4062,
               "sparse_keys_scored": 118 * 4 * 4096,
               "sparse_queries": 118 * 4, "context_tokens": 118 * 16000}
    chunk = {"sparse_keys_selected": 512 * 4 * 4064,
             "sparse_keys_scored": 512 * 4 * 16896, "sparse_queries": 2048,
             "context_tokens": 512 * 16640}
    run = {"device": {"platform": "tpu", "kind": "TPU v5 lite"},
           "rows": [], "config": CONFIG,
           "_span_reduce": {"spans": {
               "decode_block": {"events": [{
                   "device_busy_s": 0.1,
                   "fields": {"slots": 16, "n_steps": 8,
                              "frozen_row_steps": 10, **counted}}]},
               "prefill_chunk": {"events": [{
                   "device_busy_s": 0.05,
                   "fields": {"tokens": 512, "context": 16384, **chunk}}]}}}}

    def read(name):
        return harness.load_named("layer_metrics", name).read(run)

    assert read("decode_context_tokens") == 16000.0
    assert read("sparse_keys_scored_over_selected") == pytest.approx(
        (118 * 4 * 4096 + 512 * 4 * 16896)
        / (118 * 4 * 4062 + 512 * 4 * 4064))
    peak = peaks.peaks("TPU v5 lite")
    assert read("sala_decode_roofline") == pytest.approx(
        100 * sala.least_seconds(sala.decode_block(
            CONFIG, 16, 8, 10, 118 * 16000, 118 * 4 * 4062), peak) / 0.1)
    assert 52 < read("sala_decode_roofline") < 56
    assert read("sala_prefill_roofline") == pytest.approx(
        100 * sala.least_seconds(sala.prefill_chunk(
            CONFIG, 512, 16384, 512 * 4 * 4064), peak) / 0.05)


# ------------------------------------------- a run with the chip look skipped


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One window at the rehearsal's configuration through the child's own
    set-up, traffic loop and sample."""
    from benchmark import serve_child_hybrid as child
    from benchmark.drivers import serve_gateway_hybrid as drv
    from benchmark.reference import minicpm_sala as ref

    spec = {"seed": 2**31 + 9, "seconds": 3.0, "trace": False,
            "rehearse": True, "chips": 1,
            "config": {**drv.REHEARSAL_CONFIG,
                       "serving": drv.REHEARSAL_SERVING},
            "serving": drv.REHEARSAL_SERVING,
            "traffic": {**WORKLOAD["traffic_mix"], **drv.REHEARSAL_LENGTHS,
                        "ramp_s": 0},
            "limits": drv.REHEARSAL_LIMITS, "sample": WORKLOAD["sample"],
            "control": "",
            "trace_dir": str(tmp_path_factory.mktemp("trace")),
            "t_start": 0.0, "trace_after_s": 1, "trace_seconds": 1}
    device, pcfg, gateway = child.build(spec, ref)
    try:
        replica = gateway.pool.ready_replicas()[0]
        child.warm_up(gateway, spec, pcfg.vocab_size)
        now = time.monotonic()
        window = child.drive(gateway, spec, pcfg.vocab_size, now, now)
        summary = child.summarize(window, now, spec["seconds"])
        _, sample = child.sample_and_prefill(spec, replica.engine, window,
                                             "sample")
        logits = child.engine_logits(spec, replica.engine, sample, "")
        # a hit resumed every position after a request's first: the tail
        # reads stored rows AND stored state
        assert replica.engine.prefix_cache_hits >= len(logits) - 2 * len(sample)
    finally:
        gateway.stop()
    assert summary["failed"] == 0 and summary["serve_tokens_per_s"] > 0
    # every sampled request passed dense_len (64 here): its decode selected
    assert all(len(rec["prompt"]) >= 70 for rec in sample)
    assert len(logits) > 6 + len(sample)
    return spec, ref, sample, logits


CHECKS = ["decode_logit_gap", "decode_logit_gap_mean", "prefill_logit_gap",
          "tail_logit_gap_3rd", "tail_logit_gap_median"]


@pytest.mark.parametrize("control", [
    "", "fp8", "dense_in_place_of_sparse", "forced_blocks_only",
    "rope_on_sparse", "state_reset_at_chunk", "pads_in_state", "no_decay",
    "no_output_gate", "no_residual_scale"])
def test_a_sound_run_is_correct_and_every_control_is_not(served, control):
    from benchmark import serve_child_hybrid as child

    spec, ref, sample, logits = served
    checks = child.reference_checks(spec, ref, sample, control,
                                    {} if control else logits)
    assert [c["name"] for c in checks] == CHECKS
    correct = all(c["value"] <= c["limit"] for c in checks)
    assert correct is (control == ""), (control, checks)


def test_a_request_sampled_twice_is_read_once(served):
    from benchmark import serve_child_hybrid as child

    spec, ref, sample, logits = served
    once = child.compare(spec, ref, sample, "", logits)
    again = child.compare(spec, ref, sample + [sample[0]], "", logits)
    assert again == once and len(once["decode"]) > 20


@pytest.mark.parametrize("lost", ["prefill", "tail"])
def test_a_comparison_that_went_missing_is_not_correct(served, lost):
    """No logits from the engine at the prompts' ends, or none in the
    tails: those checks read NOTHING_COMPARED and fail, the others pass."""
    from benchmark import serve_child_hybrid as child

    spec, ref, sample, logits = served
    prompts = set(child._positions(spec, sample)[0])
    logits = {k: v for k, v in logits.items()
              if (k in prompts) is (lost == "tail")}
    checks = {c["name"]: c for c in child.reference_checks(
        spec, ref, sample, "", logits)}
    failed = {n for n, c in checks.items() if not c["value"] <= c["limit"]}
    assert failed == {"prefill": {"prefill_logit_gap"},
                      "tail": {"tail_logit_gap_3rd",
                               "tail_logit_gap_median"}}[lost]
    for name in failed:
        assert checks[name]["value"] == child.NOTHING_COMPARED


@pytest.mark.parametrize("control", ["", "state_reset_at_chunk"])
def test_the_drivers_rehearsal(control):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("CONTROL", None)
    if control:
        env["CONTROL"] = control
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--seed", str(2**31 + 5), "--seconds", "3", "--trace", "0",
         "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.stdout.strip(), out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["rehearsal_passed"] is (control == ""), out.stdout[-2000:]
    assert out.returncode == (0 if control == "" else 1)
    line = last["would_print"]
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
