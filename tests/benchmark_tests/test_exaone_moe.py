"""The benchmark's pieces for a WINDOWED-AND-FULL configuration with
post-norms, a sigmoid router, a shared expert and a leading dense layer
(K-EXAONE-236B-A23B: ``drivers/serve_gateway_swa_shared.py``,
``serve_child_swa_shared.py``, ``reference/exaone_moe.py``,
``counts/swa_shared_moe.py``): the cell's traffic, the configuration file
against the catalog's publication and the program's preset, the counts by
hand, the readers on a recorded reduction and on an empty one, a window at the
rehearsal's size and every control. Entries are found BY NAME: a later cell or
metric fails nothing here. (The rehearsal through ``benchmark.run`` has a file
of its own.)
"""

import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, traffic  # noqa: E402
from benchmark.counts import peaks, swa_shared_moe  # noqa: E402

NAME = "k-exaone-236b-a23b"
CELL = f"{NAME}.serve-closed-reasoning"
CONFIG = harness.load_json(os.path.join(
    ROOT, "benchmark", "configs", f"{NAME}.json"))
WORKLOAD = harness.load_json(os.path.join(
    ROOT, "benchmark", "workloads", f"{CELL}.json"))
BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
NEW_METRICS = ["swa_shared_moe_decode_roofline",
               "swa_shared_moe_prefill_roofline"]
# what every serving cell reports, the two of the routed ones and the three
# that read a windowed-and-full tree's counters
SHARED_METRICS = [
    "queue_ms.closed", "slot_occupancy", "decoding_slots", "decode_step_ms",
    "prefill_chunk_ms", "engine_host_ms", "host_gap_attributed.serve",
    "admission_ms", "admission_decode_share", "admission_start_ms",
    "chunk_exposed_host_ms", "decode_call_host_ms", "stall_idle_share",
    "expert_tokens_per_step", "expert_load_max_over_mean",
    "decode_context_tokens", "window_keys_over_context", "ring_wrapped_share"]
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types",
           "sliding_windows", "num_experts", "vocab_size",
           "num_nextn_predict_layers", "mtp_layer_types",
           "mtp_sliding_windows"]
L, G = "sliding_attention", "full_attention"


# ----------------------------------------------- the cell, its file, its sizes


def test_traffic_stays_inside_the_cells_lengths():
    mix, serving = WORKLOAD["traffic_mix"], CONFIG["serving"]
    big = 2**31 + 12345
    a, b = (traffic.requests(mix, big, 50.0) for _ in range(2))
    c = traffic.requests(mix, big + 1, 50.0)
    assert a == b and a != c and len(a) == mix["pool"] == 32
    for r in a:
        assert 256 <= r.prompt_tokens <= 4096
        assert 512 <= r.max_new_tokens <= 3072
        assert r.prompt_tokens + r.max_new_tokens <= 7168 < serving["max_len"]
    # every seed offers the same set, from another place in the cycle
    assert sorted((r.prompt_tokens, r.max_new_tokens) for r in a) == \
        sorted((r.prompt_tokens, r.max_new_tokens) for r in c)
    # contexts are built BY DECODING: most of a row's context is generated
    assert sum(r.max_new_tokens for r in a) > sum(r.prompt_tokens for r in a)
    # every chunk is wider than the ring, most prompts take several, and
    # every request wraps its rings many times under decode steps
    assert serving["prefill_len"] == 4 * CONFIG["sliding_window"]
    passed = [r for r in a if r.prompt_tokens
              > CONFIG["sliding_window"] + serving["prefill_len"]]
    assert len(passed) > len(a) // 2
    assert min(r.max_new_tokens for r in a) >= 4 * CONFIG["sliding_window"]
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 1024,
                                    "sigma": 0.6, "min": 256, "max": 4096}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 1536,
                                    "sigma": 0.5, "min": 512, "max": 3072}
    assert mix["arrivals"] == {"kind": "closed", "clients_per_slot": 1}
    assert (mix["ramp_s"], mix["mix_seed"]) == (20, 0)
    assert {k: serving[k] for k in (
        "max_len", "prefill_len", "decode_block", "prefix_cache_entries",
        "kv_pages")} == {"max_len": 8192, "prefill_len": 512,
                         "decode_block": 8, "prefix_cache_entries": 2,
                         "kv_pages": 0}
    assert serving["slots"] in (32, 48)


def test_the_file_is_the_publication_less_the_stated_share():
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["reduced"] == CONFIG["reduced"] == REDUCED
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    pub = CONFIG["published"]
    assert sorted(pub) == sorted(REDUCED)
    assert (pub["num_hidden_layers"], pub["num_experts"], pub["vocab_size"],
            pub["num_nextn_predict_layers"]) == (48, 128, 153600, 1)
    assert pub["layer_types"] == [L, L, L, G] * 12
    assert pub["sliding_windows"] == [128, 128, 128, 0] * 12
    assert pub["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    # published layers 0-4: the first whole period, whose first layer is the
    # dense one, and the layer after it: four expert layers behind the dense
    assert CONFIG["num_hidden_layers"] == 5
    assert CONFIG["layer_types"] == pub["layer_types"][:5] == [L, L, L, G, L]
    assert CONFIG["sliding_windows"] == [128, 128, 128, 0, 128]
    assert CONFIG["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    # one chip's share of 8 a layer: an eighth of the experts and of the
    # vocabulary (both at the guide's floors); the router keeps its width
    dep = CONFIG["deployment"]
    assert (dep["chips_per_layer"], dep["expert_parallel"],
            dep["expert_first"], dep["dense_layers_held"]) == (8, 8, 0, 1)
    assert CONFIG["num_experts"] * 8 == pub["num_experts"]
    assert CONFIG["num_experts"] == CONFIG["n_routed_experts"] == 16 >= 8
    assert CONFIG["vocab_size"] * 8 == pub["vocab_size"]
    assert (CONFIG["num_nextn_predict_layers"], CONFIG["mtp_layer_types"],
            CONFIG["mtp_sliding_windows"]) == (0, [], [])
    # every width as published
    assert {k: CONFIG[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "sliding_window", "intermediate_size",
        "moe_intermediate_size", "num_experts_per_tok", "num_shared_experts",
        "routed_scaling_factor", "rms_norm_eps", "first_k_dense_replace",
        "max_position_embeddings")} == {
        "hidden_size": 6144, "num_attention_heads": 64,
        "num_key_value_heads": 8, "head_dim": 128, "sliding_window": 128,
        "intermediate_size": 18432, "moe_intermediate_size": 2048,
        "num_experts_per_tok": 8, "num_shared_experts": 1,
        "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-05,
        "first_k_dense_replace": 1, "max_position_embeddings": 262144}
    assert CONFIG["rope_parameters"] == {"rope_theta": 1000000,
                                         "rope_type": "default"}
    for item in ("norms", "qk_norm", "rope", "window", "router", "experts",
                 "weights", "compute", "torch_dtype"):
        assert item in CONFIG["assumed"]
    # the expert-layer metric that is there counts expert layers from this
    assert CONFIG["num_hidden_layers"] - dep["dense_layers_held"] == 4
    # every published value is the program's preset's (or the run stops)
    from benchmark import serve_child_swa_shared as child

    cfg = child.program_config(CONFIG)
    assert (cfg.n_layers, cfg.first_k_dense, cfg.experts_held,
            cfg.expert_first, cfg.n_routed_experts, cfg.vocab_size,
            cfg.param_dtype, cfg.dtype) == (
        5, 1, 16, 0, 128, 19200, "bfloat16", "bfloat16")
    assert (cfg.layer_windows, cfg.layer_rope) == (
        (128, 128, 128, 0, 128), (True, True, True, False, True))
    assert cfg.param_count == CONFIG["sizes"]["parameters"] == 3712028416
    assert CONFIG["sizes"]["weight_bytes"] == 2 * cfg.param_count
    assert CONFIG["sizes"]["memory_peak_bytes"] >= 0.25 * 15.75e9
    for key, bad in (("sliding_window", 256), ("moe_intermediate_size", 1024),
                     ("scoring_func", "softmax"), ("routed_scaling_factor", 1),
                     ("num_nextn_predict_layers", 1), ("num_experts", 8)):
        with pytest.raises(SystemExit, match=key):
            child.program_config({**CONFIG, key: bad})


def test_the_catalogs_numbers_are_the_files():
    """Every key of the catalog's row under its own name, but the nine
    reduced (which the file keeps under ``published``)."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == CONFIG["source"])
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value, key
            assert CONFIG[key] != value, key
        else:
            assert CONFIG[key] == value, key


def test_the_new_entries_each_have_their_file():
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert cells[CELL] == {**cells[CELL], "config": NAME,
                           "traffic": "serve-closed-reasoning", "chips": 1}
    assert len(cells[CELL]["why"]) <= 200
    assert WORKLOAD["driver"] == "serve_gateway_swa_shared"
    assert callable(harness.load_named("drivers", WORKLOAD["driver"]).run)
    assert os.path.isfile(os.path.join(
        ROOT, "benchmark", "reference", f"{CONFIG['reference']}.py"))
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW_METRICS:
        m = per_layer[name]
        assert m["workloads"] == [CELL]
        assert (m["moves"], m["layer"], m["source"], m["unit"]) == (
            "serve_tokens_per_s", "kernels", "device_trace", "%")
        assert callable(harness.load_named("layer_metrics", name).read)
    for name in SHARED_METRICS:
        assert CELL in per_layer[name]["workloads"], name
    # not the share that reads `moe_latent_size`, nor another family's
    for name in ("experts_hit_share", "swa_moe_decode_roofline",
                 "swa_moe_prefill_roofline", "itl_p95_ms"):
        assert CELL not in per_layer[name]["workloads"], name
    tokens = next(m for m in BENCH["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert CELL in tokens["workloads"]
    assert len(json.dumps(BENCH, indent=1)) < 64 * 1024
    for key in ("decode_logit_gap", "decode_logit_gap_mean",
                "prefill_logit_gap", "tail_logit_gap_3rd",
                "tail_logit_gap_median", "prompt_positions",
                "tail_positions"):
        assert key in WORKLOAD["limits"]
    assert WORKLOAD["limits_from"].startswith("PERF.md section 6")
    assert os.path.isfile(os.path.join(ROOT, "benchmark",
                                       "README.swa-shared.md"))


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "exaone_moe.py")) as f:
        source = f.read()
    lines = [ln for ln in source.splitlines()
             if ln.startswith(("import ", "from "))]
    assert not any("dlrover_tpu" in ln or "benchmark" in ln for ln in lines)
    assert 'default_matmul_precision("highest")' in source
    assert "Precision.HIGHEST" in source


def test_counts_by_hand():
    s = swa_shared_moe.sizes(CONFIG)
    attention = 2 * 6144 * 64 * 128 + 2 * 6144 * 8 * 128
    router, expert = 6144 * 128, 3 * 6144 * 2048
    dense_ffn, head = 3 * 6144 * 18432, 6144 * 19200
    assert (s["attention"], s["router"], s["expert"], s["shared"],
            s["dense_ffn"], s["head"]) == (
        attention, router, expert, expert, dense_ffn, head) == (
        113246208, 786432, 37748736, 37748736, 339738624, 117964800)
    assert (s["layers"], s["dense"], s["sparse"], s["windowed"], s["full"],
            s["window"], s["row"], s["scores"]) == (
        5, 1, 4, 4, 1, 128, 2048, 32768)
    body = 5 * attention + dense_ffn + 4 * (router + expert)
    norms = 5 * (2 * 6144 + 2 * 128) + 4 * 128 + 6144
    assert swa_shared_moe.held_parameters(CONFIG) == (
        body + 4 * 16 * expert + 2 * head) == \
        CONFIG["sizes"]["parameters"] - norms
    held = swa_shared_moe.cache_bytes(CONFIG, 48, 8192)
    assert held == {"full_rows": 48 * 8192 * 4096,
                    "rings": 4 * 48 * 128 * 4096,
                    "rows_in_place_of_rings": 4 * 48 * 8192 * 4096}
    assert CONFIG["sizes"]["cache_bytes_per_token"] == 4096
    assert CONFIG["sizes"]["state_bytes_per_slot"] == 4 * 128 * 4096
    # a block of 8 steps, 48 live rows at position ~2000 (all past the
    # window), 15 of 16 experts hit a layer a step by 48 assignments
    live, hit, landed = 8 * 48, 8 * 4 * 15, 8 * 4 * 48
    context, windowed = live * 2000, live * 128
    call = swa_shared_moe.decode_block(
        CONFIG, 8, live, hit, landed, context, windowed, live)
    keys = (context + live) + 4 * (windowed + live - live)
    assert call["bytes"] == 2 * (
        8 * (body + head) + hit * expert + live * 6144
        + 2048 * (keys + live * 5))
    assert call["flops"] == (live * 2.0 * (body + head)
                             + landed * 2.0 * expert + 32768 * keys)
    peak = peaks.peaks("TPU v5 lite")
    # bound by bytes: ~8.8 ms a step, of which the routed experts 5.5
    assert swa_shared_moe.least_seconds(call, peak) == call["bytes"] / 819e9
    assert 0.0080 < swa_shared_moe.least_seconds(call, peak) / 8 < 0.0095
    # rows inside the window read what they have: fewer windowed keys
    young = swa_shared_moe.decode_block(
        CONFIG, 8, live, hit, landed, live * 50, live * 50, 0)
    assert call["bytes"] - young["bytes"] == 2 * 2048 * (
        live * 1950 + 4 * (live * 78 - live))
    # a 512-token chunk behind 512: every held expert of every layer read,
    # a windowed layer's queries see 128 keys each, the full layer's all
    chunk = swa_shared_moe.prefill_chunk(CONFIG, 512, 512, 64, 4 * 512)
    assert chunk["bytes"] == 2 * (
        body + head + 64 * expert + 512 * 6144
        + 2048 * (512 + 4 * 127 + 512 * 5))
    assert chunk["flops"] == (
        2.0 * (512 * body + head) + 4 * 512 * 2.0 * expert
        + 32768 * (512 * (512 + 256.5) + 4 * 512 * 128))
    assert swa_shared_moe.least_seconds(chunk, peak) == \
        chunk["bytes"] / 819e9
    assert 0.008 < swa_shared_moe.least_seconds(chunk, peak) < 0.010
    # a first chunk's windowed queries see what there is: 1, 2, ..., 128
    first = swa_shared_moe.prefill_chunk(CONFIG, 512, 0, 64, 4 * 512)
    assert chunk["flops"] - first["flops"] == 32768 * (
        512 * 512 + 4 * (127 * 128 - 127 * 128 // 2))


def _run(fields_decode: dict, fields_chunk: dict, config=None) -> dict:
    return {"device": {"platform": "tpu", "kind": "TPU v5 lite"},
            "rows": [], "config": config or CONFIG,
            "_span_reduce": {"spans": {
                "decode_block": {"events": [{
                    "device_busy_s": 0.12, "fields": fields_decode}]},
                "prefill_chunk": {"events": [{
                    "device_busy_s": 0.03, "fields": fields_chunk}]}}}}


def test_the_new_metrics_read_nothing_from_a_run_without_their_fields():
    """What the parent's program gives, or another family's: spans without
    the counters, a configuration without the keys (SmallThinker's spans
    have every field; its file is not this family's)."""
    plain = _run({"slots": 4, "n_steps": 8}, {"tokens": 64, "context": 0},
                 config={"n_embd": 8})
    routed = _run({"slots": 4, "n_steps": 8, "experts_hit": 50,
                   "expert_tokens": 90}, {"tokens": 64, "context": 0})
    windowed = {"row_steps": 30, "experts_hit": 50, "expert_tokens": 90,
                "context_tokens": 900, "window_keys": 400,
                "ring_wrapped_row_steps": 3}
    other = _run({"slots": 4, "n_steps": 8, **windowed},
                 {"tokens": 64, "context": 0, **windowed},
                 config={"sliding_window_layout": [0, 1],
                         "moe_num_primary_experts": 64})
    empty = {**plain, "_span_reduce": {"spans": {}}}
    for run in (plain, routed, other, empty):
        for name in NEW_METRICS:
            assert harness.load_named("layer_metrics", name).read(run) is None


def test_the_new_metrics_read_the_spans_fields():
    counted = {"row_steps": 8 * 46, "experts_hit": 8 * 4 * 15,
               "expert_tokens": 8 * 4 * 46, "context_tokens": 368 * 1800,
               "window_keys": 368 * 128, "ring_wrapped_row_steps": 368,
               "ring_chunk_tokens_dropped": 0, "expert_load_max": 40}
    chunk = {"row_steps": 400, "experts_hit": 64, "expert_tokens": 4 * 512,
             "context_tokens": 400 * 700, "window_keys": 400 * 128,
             "ring_wrapped_row_steps": 400, "ring_chunk_tokens_dropped": 272}
    run = _run({"slots": 48, "n_steps": 8, "frozen_row_steps": 16, **counted},
               {"tokens": 400, "context": 512, **chunk})

    def read(name):
        return harness.load_named("layer_metrics", name).read(run)

    peak = peaks.peaks("TPU v5 lite")
    assert read("swa_shared_moe_decode_roofline") == pytest.approx(
        100 * swa_shared_moe.least_seconds(swa_shared_moe.decode_block(
            CONFIG, 8, 368, 480, 1472, 662400, 47104, 368), peak) / 0.12)
    assert 50 < read("swa_shared_moe_decode_roofline") < 65
    assert read("swa_shared_moe_prefill_roofline") == pytest.approx(
        100 * swa_shared_moe.least_seconds(swa_shared_moe.prefill_chunk(
            CONFIG, 400, 512, 64, 2048), peak) / 0.03)
    assert 0 < read("swa_shared_moe_prefill_roofline") < 100
    # the accepted readers of the expert layer's and the windowed tree's
    # counters read this family's spans too
    assert read("expert_tokens_per_step") == 4 * 46
    assert read("expert_load_max_over_mean") == pytest.approx(
        40 * 64 / (8 * 4 * 46))
    assert read("decode_context_tokens") == pytest.approx(
        662400 / (48 * 8 - 16))
    assert read("window_keys_over_context") == pytest.approx(128 / 1800)
    assert read("ring_wrapped_share") == 1.0
    assert harness.load_named("layer_metrics", "experts_hit_share").read(
        run) is None


# ------------------------------------------- a run with the chip look skipped


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One window at the rehearsal's configuration through the child's own
    set-up, traffic loop and sample, with this family's parts in the places
    the child's ``main`` puts them."""
    from benchmark import serve_child_hybrid as base
    from benchmark import serve_child_swa_shared as child
    from benchmark.drivers import serve_gateway_swa_shared as drv
    from benchmark.reference import exaone_moe as ref

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
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(base, "program_config", child.program_config)
        patch.setattr(base, "program_params", child.program_params)
        device, pcfg, gateway = base.build(spec, ref)
        try:
            replica = gateway.pool.ready_replicas()[0]
            base.warm_up(gateway, spec, pcfg.vocab_size)
            now = time.monotonic()
            window = base.drive(gateway, spec, pcfg.vocab_size, now, now)
            summary = base.summarize(window, now, spec["seconds"])
            _, sample = child.sample_and_prefill(spec, replica.engine,
                                                 window, "sample")
            logits = base.engine_logits(spec, replica.engine, sample, "")
            # a hit resumed every position after a request's first: the
            # tail reads stored rows AND stored rings
            assert replica.engine.prefix_cache_hits >= \
                len(logits) - 2 * len(sample)
        finally:
            gateway.stop()
        assert summary["failed"] == 0 and summary["serve_tokens_per_s"] > 0
        assert len(logits) > 6 + len(sample)
        yield spec, ref, sample, logits


CHECKS = ["decode_logit_gap", "decode_logit_gap_mean", "prefill_logit_gap",
          "tail_logit_gap_3rd", "tail_logit_gap_median"]


@pytest.mark.timeout(600)
@pytest.mark.parametrize("control", [
    "", "fp8", "pre_norm_in_place_of_post", "no_qk_norm",
    "rope_on_full_layers", "no_rope_on_windowed", "window_127",
    "full_in_place_of_window", "softmax_in_place_of_sigmoid",
    "no_router_bias", "no_shared_expert", "scaling_1_in_place_of_2.5",
    "one_expert_left_out", "stale_ring", "pads_in_ring",
    "chunk_keeps_ring_head"])
def test_a_sound_run_is_correct_and_every_control_is_not(served, control):
    from benchmark import serve_child_swa_shared as child

    spec, ref, sample, logits = served
    assert control in ref.CONTROLS
    checks = child.reference_checks(spec, ref, sample, control,
                                    {} if control else logits)
    assert [c["name"] for c in checks] == CHECKS
    correct = all(c["value"] <= c["limit"] for c in checks)
    assert correct is (control == ""), (control, checks)


def test_every_control_of_the_issue_is_the_references():
    from benchmark.reference import exaone_moe as ref

    assert set(ref.CONTROLS) == {
        "", "fp8", "pre_norm_in_place_of_post", "no_qk_norm",
        "rope_on_full_layers", "no_rope_on_windowed", "window_127",
        "full_in_place_of_window", "softmax_in_place_of_sigmoid",
        "no_router_bias", "no_shared_expert", "scaling_1_in_place_of_2.5",
        "one_expert_left_out", "stale_ring", "pads_in_ring",
        "chunk_keeps_ring_head"}
    assert ref.READINGS == ("bf16",)
    for control in ref.CONTROLS[1:]:
        assert control in WORKLOAD["limits_from"], control


def test_the_sample_holds_a_prompt_past_window_and_chunk(served):
    """The family's sample rule, and what a builder's further faults are
    read on: the shortest request beside the one, of those whose PROMPT
    passed ``sliding_window + prefill_len``, with the longest pad tail."""
    from benchmark import serve_child_swa_shared as child

    spec, ref, sample, logits = served
    edge = spec["config"]["sliding_window"] + spec["serving"]["prefill_len"]
    passed = child.wrapped(spec, sample)
    assert passed and all(len(r["prompt"]) > edge for r in passed)
    longest = max(sample, key=child._context)
    assert sample[0] is longest
    seen = []
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(child, "_hybrid_checks",
                      lambda spec, ref, few, *a: seen.append(few) or [])
        child.reference_checks(spec, ref, sample, "", logits)
        child.reference_checks(spec, ref, sample, "fp8", {}, {})
    assert seen[0] is sample and 1 <= len(seen[1]) <= 2
    assert seen[1][0] is min(sample, key=child._context)
    assert seen[1][-1] in passed or len(seen[1]) == 1
    short = [r for r in sample if len(r["prompt"]) <= edge]
    with pytest.raises(SystemExit, match="passed sliding_window"):
        with pytest.MonkeyPatch.context() as patch:
            from benchmark import serve_child

            patch.setattr(serve_child, "sample_and_prefill",
                          lambda *a: ({}, short))
            child.sample_and_prefill(spec, None, None, "sample")


@pytest.mark.parametrize("lost", ["prefill", "tail"])
def test_a_comparison_that_went_missing_is_not_correct(served, lost):
    """No logits from the engine at the prompts' ends, or none in the
    tails: those checks read NOTHING_COMPARED and fail, the others pass."""
    from benchmark import serve_child_hybrid as base
    from benchmark import serve_child_swa_shared as child

    spec, ref, sample, logits = served
    prompts = set(base._positions(spec, sample)[0])
    logits = {k: v for k, v in logits.items()
              if (k in prompts) is (lost == "tail")}
    checks = {c["name"]: c for c in child.reference_checks(
        spec, ref, sample, "", logits)}
    failed = {n for n, c in checks.items() if not c["value"] <= c["limit"]}
    assert failed == {"prefill": {"prefill_logit_gap"},
                      "tail": {"tail_logit_gap_3rd",
                               "tail_logit_gap_median"}}[lost]
    for name in failed:
        assert checks[name]["value"] == base.NOTHING_COMPARED
