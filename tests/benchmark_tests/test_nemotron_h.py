"""The benchmark's pieces for a STATE-SPACE / LATENT-EXPERT configuration
(NVIDIA-Nemotron-3-Super-120B-A12B: ``drivers/serve_gateway_ssm.py``,
``serve_child_ssm.py``, ``reference/nemotron_h.py``, ``counts/ssm_moe.py``):
the cell's traffic, the configuration file against the catalog's publication
and the program's preset, the counts by hand, the readers on a recorded
reduction and on an empty one, a window at the rehearsal's size and every
control. Entries are found BY NAME: a later cell or metric fails nothing
here. (The rehearsal through ``benchmark.run`` has a file of its own.)
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
from benchmark.counts import peaks, ssm_moe  # noqa: E402

NAME = "nemotron-3-super-120b-a12b"
CELL = f"{NAME}.serve-closed-chat"
CONFIG = harness.load_json(os.path.join(
    ROOT, "benchmark", "configs", f"{NAME}.json"))
WORKLOAD = harness.load_json(os.path.join(
    ROOT, "benchmark", "workloads", f"{CELL}.json"))
BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
NEW_METRICS = ["ssm_moe_decode_roofline", "ssm_moe_prefill_roofline",
               "experts_hit_share"]
# what every serving cell reports, and the two of the routed ones
SHARED_METRICS = [
    "queue_ms.closed", "slot_occupancy", "decoding_slots", "decode_step_ms",
    "prefill_chunk_ms", "engine_host_ms", "host_gap_attributed.serve",
    "admission_ms", "admission_decode_share", "admission_start_ms",
    "chunk_exposed_host_ms", "decode_call_host_ms", "stall_idle_share",
    "expert_tokens_per_step", "expert_load_max_over_mean"]
PUBLISHED_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                     "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


# ----------------------------------------------- the cell, its file, its sizes


def test_traffic_stays_inside_the_cells_lengths():
    mix, serving = WORKLOAD["traffic_mix"], CONFIG["serving"]
    big = 2**31 + 12345
    a, b = (traffic.requests(mix, big, 50.0) for _ in range(2))
    c = traffic.requests(mix, big + 1, 50.0)
    assert a == b and a != c and len(a) == mix["pool"] == 16
    for r in a:
        assert 64 <= r.prompt_tokens <= 2048
        assert 64 <= r.max_new_tokens <= 768
        assert r.prompt_tokens + r.max_new_tokens <= 2816 < serving["max_len"]
    # every seed offers the same set, from another place in the cycle
    assert sorted((r.prompt_tokens, r.max_new_tokens) for r in a) == \
        sorted((r.prompt_tokens, r.max_new_tokens) for r in c)
    # prompts of one chunk and of several: window and state cross boundaries
    chunks = [-(-r.prompt_tokens // serving["prefill_len"]) for r in a]
    assert min(chunks) == 1 and max(chunks) >= 3
    assert mix["arrivals"] == {"kind": "closed", "clients_per_slot": 1}
    assert mix["ramp_s"] == 20
    assert serving == {"slots": 32, "max_len": 3072, "prefill_len": 512,
                       "decode_block": 8, "prefix_cache_entries": 2,
                       "kv_pages": 0, "admission_deadline_s": 30.0}
    # a chunk is whole scan chunks
    assert serving["prefill_len"] % CONFIG["chunk_size"] == 0


def test_the_file_is_the_publication_less_the_stated_share():
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    pub = CONFIG["published"]
    assert pub == {"num_hidden_layers": 88,
                   "hybrid_override_pattern": PUBLISHED_PATTERN,
                   "n_routed_experts": 512, "vocab_size": 131072,
                   "num_nextn_predict_layers": 1}
    assert len(PUBLISHED_PATTERN) == 88 and [
        PUBLISHED_PATTERN.count(k) for k in "ME*"] == [40, 40, 8]
    # one whole period as published: layers 27-37, 5 : 5 : 1
    assert CONFIG["hybrid_override_pattern"] == PUBLISHED_PATTERN[27:38] == \
        "MEMEMEMEM*E"
    assert CONFIG["num_hidden_layers"] == 11
    # one chip's share of 4 a layer: a quarter of the experts and of the
    # vocabulary; the router keeps its width and its 22 a token
    dep = CONFIG["deployment"]
    assert (dep["chips_per_layer"], dep["expert_first"]) == (4, 0)
    assert CONFIG["n_routed_experts"] * 4 == pub["n_routed_experts"]
    assert CONFIG["vocab_size"] * 4 == pub["vocab_size"]
    assert CONFIG["n_routed_experts"] >= 8 and CONFIG["vocab_size"] * 8 >= \
        pub["vocab_size"]
    assert CONFIG["num_nextn_predict_layers"] == 0
    # every width as published
    assert {k: CONFIG[k] for k in (
        "hidden_size", "mamba_num_heads", "mamba_head_dim", "ssm_state_size",
        "n_groups", "conv_kernel", "chunk_size", "expand",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "num_experts_per_tok", "moe_latent_size", "moe_intermediate_size",
        "moe_shared_expert_intermediate_size", "routed_scaling_factor",
        "intermediate_size")} == {
        "hidden_size": 4096, "mamba_num_heads": 128, "mamba_head_dim": 64,
        "ssm_state_size": 128, "n_groups": 8, "conv_kernel": 4,
        "chunk_size": 128, "expand": 2, "num_attention_heads": 32,
        "num_key_value_heads": 2, "head_dim": 128, "num_experts_per_tok": 22,
        "moe_latent_size": 1024, "moe_intermediate_size": 2688,
        "moe_shared_expert_intermediate_size": 5376,
        "routed_scaling_factor": 5, "intermediate_size": 2688}
    for item in ("latent_moe", "router", "attention", "mamba2", "norm",
                 "weights", "compute", "torch_dtype"):
        assert item in CONFIG["assumed"]
    # the expert-layer metric that is there counts expert layers from this
    assert CONFIG["num_hidden_layers"] - dep["dense_layers_held"] == 5
    # every published value is the program's preset's (or the run stops)
    from benchmark import serve_child_ssm as child

    cfg = child.program_config(CONFIG)
    assert (cfg.n_layers, cfg.experts_held, cfg.expert_first,
            cfg.n_routed_experts, cfg.vocab_size, cfg.param_dtype,
            cfg.dtype) == (11, 128, 0, 512, 32768, "bfloat16", "bfloat16")
    assert [cfg.mixer_types.count(k) for k in (
        "mamba2", "latent_experts", "attention")] == [5, 5, 1]
    assert cfg.param_count == CONFIG["sizes"]["parameters"] == 4648163712
    assert CONFIG["sizes"]["weight_bytes"] == 2 * cfg.param_count
    for key, bad in (("moe_latent_size", 512), ("mamba_head_dim", 32),
                     ("mlp_hidden_act", "silu"),
                     ("num_nextn_predict_layers", 1)):
        with pytest.raises(SystemExit, match=key):
            child.program_config({**CONFIG, key: bad})


def test_the_catalogs_numbers_are_the_files():
    """Every number of the catalog's row under its own key, but the five
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
        else:
            assert CONFIG[key] == value, key


def test_the_new_entries_each_have_their_file():
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert cells[CELL] == {**cells[CELL], "config": NAME,
                           "traffic": "serve-closed-chat", "chips": 1}
    assert len(cells[CELL]["why"]) <= 200
    assert WORKLOAD["driver"] == "serve_gateway_ssm"
    assert callable(harness.load_named("drivers", WORKLOAD["driver"]).run)
    assert os.path.isfile(os.path.join(
        ROOT, "benchmark", "reference", f"{CONFIG['reference']}.py"))
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW_METRICS:
        m = per_layer[name]
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"
        assert callable(harness.load_named("layer_metrics", name).read)
    assert per_layer["experts_hit_share"]["layer"] == \
        per_layer["expert_tokens_per_step"]["layer"] == "expert layer"
    assert per_layer["ssm_moe_decode_roofline"]["layer"] == "kernels"
    for name in SHARED_METRICS:
        assert CELL in per_layer[name]["workloads"], name
    # its gaps do not mean what they mean elsewhere: not itl_p95_ms's
    assert CELL not in per_layer["itl_p95_ms"]["workloads"]
    tokens = next(m for m in BENCH["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert CELL in tokens["workloads"]
    assert not any(w["chips"] == 4 for w in BENCH["workloads"])
    assert len(json.dumps(BENCH, indent=1)) < 64 * 1024
    for key in ("decode_logit_gap", "decode_logit_gap_mean",
                "prefill_logit_gap", "tail_logit_gap_3rd",
                "tail_logit_gap_median", "prompt_positions",
                "tail_positions", "boundary_positions"):
        assert key in WORKLOAD["limits"]
    assert WORKLOAD["limits_from"].startswith("PERF.md section 2")


def test_counts_by_hand():
    s = ssm_moe.sizes(CONFIG)
    mamba = 4096 * (8192 + 10240 + 128) + 8192 * 4096 + 10240 * (4 + 1)
    attention = 2 * 4096 * 32 * 128 + 2 * 4096 * 2 * 128
    outside = 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
    expert = 2 * 1024 * 2688
    head = 4096 * 32768
    assert (s["mamba"], s["attention"], s["expert_layer"], s["expert"],
            s["head"]) == (mamba, attention, outside, expert, head) == (
        109627392, 35651584, 54525952, 5505024, 134217728)
    assert (s["mamba_layers"], s["expert_layers"], s["attention_layers"],
            s["experts_held"]) == (5, 5, 1, 128)
    body = 5 * mamba + attention + 5 * outside
    norms = 5 * (4096 + 8192 + 3 * 128) + 4096 + 5 * (4096 + 512) + 4096
    assert ssm_moe.held_parameters(CONFIG) == (
        body + 5 * 128 * expert + 2 * head) == \
        CONFIG["sizes"]["parameters"] - norms
    state, window = 128 * 64 * 128, 3 * 10240
    assert ssm_moe.state_bytes_per_slot(CONFIG) == 5 * (
        4 * state + 2 * window) == CONFIG["sizes"]["state_bytes_per_slot"] \
        == 21278720
    assert CONFIG["sizes"]["cache_bytes_per_token"] == 2 * 2 * 128 * 2
    # a block of 8 steps, 32 live rows at position ~700, ~97 of 128 experts
    # hit a layer a step by 176 assignments
    live, hit, landed, context = 8 * 32, 8 * 5 * 97, 8 * 5 * 176, 256 * 700
    call = ssm_moe.decode_block(CONFIG, 8, live * 5, hit, landed, context)
    assert call["bytes"] == (
        2 * (8 * (body + head) + hit * expert + live * 4096
             + 2 * 256 * (context + live))
        + 2 * live * 5 * (4 * state + 2 * window))
    assert call["flops"] == (
        live * 2.0 * (body + head) + landed * 2.0 * expert
        + 4.0 * live * 5 * state + 4.0 * 4096 * context)
    peak = peaks.peaks("TPU v5 lite")
    # bound by bytes: ~10 ms a step
    assert ssm_moe.least_seconds(call, peak) == call["bytes"] / 819e9
    assert 0.0095 < ssm_moe.least_seconds(call, peak) / 8 < 0.0115
    # frozen rows are not live: half the rows, half the state's traffic
    half = ssm_moe.decode_block(CONFIG, 8, live * 5 // 2, hit, landed, context)
    assert call["bytes"] - half["bytes"] == (
        live * 5 // 2 * 2 * (4 * state + 2 * window) + 2 * (
            live // 2 * 4096 + 2 * 256 * live // 2))
    # a 512-token chunk behind 512: every held expert of every layer read
    chunk = ssm_moe.prefill_chunk(CONFIG, 512, 512, 640, 5 * 512 * 22 // 4)
    assert chunk["bytes"] == (
        2 * (body + head + 640 * expert + 512 * 4096 + 2 * 256 * 1024)
        + 2 * 5 * (4 * state + 2 * window))
    assert chunk["flops"] == (
        2.0 * (512 * body + head) + 5 * 512 * 22 // 4 * 2.0 * expert
        + 4.0 * 512 * 5 * state + 4.0 * 4096 * 512 * (512 + 256.5))
    assert ssm_moe.least_seconds(chunk, peak) == chunk["bytes"] / 819e9
    assert 0.010 < ssm_moe.least_seconds(chunk, peak) < 0.012


def _run(fields_decode: dict, fields_chunk: dict, config=None) -> dict:
    return {"device": {"platform": "tpu", "kind": "TPU v5 lite"},
            "rows": [], "config": config or CONFIG,
            "_span_reduce": {"spans": {
                "decode_block": {"events": [{
                    "device_busy_s": 0.16, "fields": fields_decode}]},
                "prefill_chunk": {"events": [{
                    "device_busy_s": 0.03, "fields": fields_chunk}]}}}}


def test_the_new_metrics_read_nothing_from_a_run_without_their_fields():
    """What the parent's program gives, or another family's: spans without
    the counters, a configuration without the keys."""
    plain = _run({"slots": 4, "n_steps": 8}, {"tokens": 64, "context": 0},
                 config={"n_embd": 8})
    routed = _run({"slots": 4, "n_steps": 8, "experts_hit": 50,
                   "expert_tokens": 90}, {"tokens": 64, "context": 0},
                  config={"n_routed_experts": 16, "deployment": {}})
    empty = {**plain, "_span_reduce": {"spans": {}}}
    for run in (plain, routed, empty):
        for name in NEW_METRICS:
            assert harness.load_named("layer_metrics", name).read(run) is None


def test_the_new_metrics_read_the_spans_fields():
    counted = {"ssm_row_steps": 8 * 30 * 5, "experts_hit": 8 * 5 * 96,
               "expert_tokens": 8 * 5 * 170, "context_tokens": 240 * 650,
               "expert_load_max": 40}
    chunk = {"ssm_row_steps": 400 * 5, "experts_hit": 635,
             "expert_tokens": 5 * 400 * 22 // 4, "context_tokens": 400 * 700}
    run = _run({"slots": 32, "n_steps": 8, "frozen_row_steps": 16, **counted},
               {"tokens": 400, "context": 512, **chunk})

    def read(name):
        return harness.load_named("layer_metrics", name).read(run)

    assert read("experts_hit_share") == pytest.approx(96 / 128)
    peak = peaks.peaks("TPU v5 lite")
    assert read("ssm_moe_decode_roofline") == pytest.approx(
        100 * ssm_moe.least_seconds(ssm_moe.decode_block(
            CONFIG, 8, 1200, 3840, 6800, 156000), peak) / 0.16)
    assert 45 < read("ssm_moe_decode_roofline") < 60
    assert read("ssm_moe_prefill_roofline") == pytest.approx(
        100 * ssm_moe.least_seconds(ssm_moe.prefill_chunk(
            CONFIG, 400, 512, 635, 11000), peak) / 0.03)
    assert 0 < read("ssm_moe_prefill_roofline") < 100
    # the live rows the model counted are the span's: slots x steps - frozen
    assert counted["ssm_row_steps"] == 5 * (32 * 8 - 16)
    # the accepted expert-layer readers read this family's spans too
    assert read("expert_tokens_per_step") == 5 * 170
    assert read("expert_load_max_over_mean") == pytest.approx(
        40 * 640 / (8 * 5 * 170))


# ------------------------------------------- a run with the chip look skipped


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One window at the rehearsal's configuration through the child's own
    set-up, traffic loop and sample, with this family's positions in the
    places the child's ``main`` puts them."""
    from benchmark import serve_child_hybrid as base
    from benchmark import serve_child_ref, serve_child_ssm as child
    from benchmark.drivers import serve_gateway_ssm as drv
    from benchmark.reference import nemotron_h as ref

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
        patch.setattr(base, "_positions", child.positions)
        patch.setattr(serve_child_ref, "_positions", child.positions)
        device, pcfg, gateway = base.build(spec, ref)
        try:
            replica = gateway.pool.ready_replicas()[0]
            base.warm_up(gateway, spec, pcfg.vocab_size)
            now = time.monotonic()
            window = base.drive(gateway, spec, pcfg.vocab_size, now, now)
            summary = base.summarize(window, now, spec["seconds"])
            _, sample = base.sample_and_prefill(spec, replica.engine, window,
                                                "sample")
            logits = base.engine_logits(spec, replica.engine, sample, "")
            # a hit resumed every position after a request's first: the
            # tail reads stored rows, a stored window AND a stored state
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
    "", "fp8", "one_held_expert_left_out", "latent_projection_left_out",
    "relu_in_place_of_relu2", "no_score_bias", "state_reset_at_chunk",
    "conv_window_reset_at_chunk", "pads_in_state", "no_D_skip",
    "no_gate_before_norm", "rope_on_attention"])
def test_a_sound_run_is_correct_and_every_control_is_not(served, control):
    from benchmark import serve_child_hybrid as base

    spec, ref, sample, logits = served
    assert control in ref.CONTROLS
    checks = base.reference_checks(spec, ref, sample, control,
                                   {} if control else logits)
    assert [c["name"] for c in checks] == CHECKS
    correct = all(c["value"] <= c["limit"] for c in checks)
    assert correct is (control == ""), (control, checks)


def test_the_first_tokens_behind_the_last_boundary_are_read(served):
    from benchmark import serve_child_ssm as child

    spec, _, sample, logits = served
    chunk = spec["serving"]["prefill_len"]
    ends, tail = child.positions(spec, sample)
    seeded = child._seeded_positions(spec, sample)
    assert ends == seeded[0] and set(seeded[1]) <= set(tail)
    crossed = 0
    for i, rec in enumerate(sample):
        n_all = len(rec["prompt"]) + len(rec["result"].tokens)
        boundary = (n_all - 1) // chunk * chunk
        if boundary:
            crossed += 1
            for n in range(boundary + 1, min(n_all, boundary + 3) + 1):
                assert (i, n) in tail and (i, n) in logits
    assert crossed >= 1 and len(tail) == len(set(tail))


@pytest.mark.parametrize("lost", ["prefill", "tail"])
def test_a_comparison_that_went_missing_is_not_correct(served, lost):
    """No logits from the engine at the prompts' ends, or none in the
    tails: those checks read NOTHING_COMPARED and fail, the others pass."""
    from benchmark import serve_child_hybrid as base

    spec, ref, sample, logits = served
    prompts = set(base._positions(spec, sample)[0])
    logits = {k: v for k, v in logits.items()
              if (k in prompts) is (lost == "tail")}
    checks = {c["name"]: c for c in base.reference_checks(
        spec, ref, sample, "", logits)}
    failed = {n for n, c in checks.items() if not c["value"] <= c["limit"]}
    assert failed == {"prefill": {"prefill_logit_gap"},
                      "tail": {"tail_logit_gap_3rd",
                               "tail_logit_gap_median"}}[lost]
    for name in failed:
        assert checks[name]["value"] == base.NOTHING_COMPARED


def test_the_choice_margin_cannot_carry_this_family():
    """Why `correct` reads central statistics and not openPangu's margins:
    at the published router (512 outputs, 22 a token, a quarter held) most
    tokens have a held expert within bfloat16's reach of the 22nd score."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import nemotron_h as ref

    cfg = {"num_experts_per_tok": 22}
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    h = jax.random.normal(keys[0], (2048, 256), jnp.float32)
    w = jax.random.normal(keys[1], (256, 512), jnp.float32) / 16
    ranked = jax.nn.sigmoid(h @ w) + 0.05 * jax.random.normal(keys[2], (512,))
    margin = ref.choice_margin(cfg, ranked, 0, 128)
    assert margin.shape == (2048,) and float(margin.min()) >= 0.0
    # one layer: a third of the tokens within 0.002 of a flip (bfloat16
    # resolves ~0.004 at a score of 0.85); five layers leave few decided
    near = float((margin < 0.002).mean())
    assert 0.15 < near < 0.6
    assert (1 - near) ** 5 < 0.45
    # with one expert held it would carry
    assert float((ref.choice_margin(cfg, ranked, 0, 1) < 0.002).mean()) < 0.02
