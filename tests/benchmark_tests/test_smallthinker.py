"""The benchmark's pieces for a WINDOWED-AND-FULL, routed-expert
configuration (SmallThinker-21BA3B-Instruct: ``drivers/serve_gateway_swa.py``,
``serve_child_swa.py``, ``reference/smallthinker.py``, ``counts/swa_moe.py``):
the cell's traffic, the configuration file against the catalog's publication
and the program's preset, the counts by hand, the readers on a recorded
reduction and on an empty one, the reference against the program at the
rehearsal's size and every control. Entries are found BY NAME: a later cell
or metric fails nothing here. (The rehearsal through ``benchmark.run`` has a
file of its own.)
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, traffic  # noqa: E402
from benchmark.counts import peaks, swa_moe  # noqa: E402

NAME = "smallthinker-21b-a3b-instruct"
CELL = f"{NAME}.serve-closed-mixed"
CONFIG = harness.load_json(os.path.join(
    ROOT, "benchmark", "configs", f"{NAME}.json"))
WORKLOAD = harness.load_json(os.path.join(
    ROOT, "benchmark", "workloads", f"{CELL}.json"))
BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
NEW_METRICS = ["swa_moe_decode_roofline", "swa_moe_prefill_roofline",
               "window_keys_over_context", "ring_wrapped_share"]
SHARED_METRICS = [
    "queue_ms.closed", "slot_occupancy", "decoding_slots", "decode_step_ms",
    "prefill_chunk_ms", "engine_host_ms", "host_gap_attributed.serve",
    "admission_ms", "admission_decode_share", "admission_start_ms",
    "chunk_exposed_host_ms", "decode_call_host_ms", "stall_idle_share",
    "expert_tokens_per_step", "expert_load_max_over_mean",
    "decode_context_tokens"]


# ----------------------------------------------- the cell, its file, its sizes


def test_traffic_is_the_issues_letter_for_letter():
    mix, serving = WORKLOAD["traffic_mix"], CONFIG["serving"]
    assert mix == {
        "prompt_tokens": {"dist": "lognormal", "median": 6144, "sigma": 0.6,
                          "min": 1024, "max": 14336},
        "output_tokens": {"dist": "lognormal", "median": 256, "sigma": 0.6,
                          "min": 64, "max": 768},
        "arrivals": {"kind": "closed", "clients_per_slot": 1},
        "pool": 32, "mix_seed": 0, "ramp_s": 20}
    big = 2**31 + 12345
    a, b = (traffic.requests(mix, big, 50.0) for _ in range(2))
    c = traffic.requests(mix, big + 1, 50.0)
    assert a == b and a != c and len(a) == 32
    for r in a:
        assert 1024 <= r.prompt_tokens <= 14336
        assert 64 <= r.max_new_tokens <= 768
        assert r.prompt_tokens + r.max_new_tokens <= 15104 < \
            serving["max_len"] == CONFIG["max_position_embeddings"]
    assert sorted((r.prompt_tokens, r.max_new_tokens) for r in a) == \
        sorted((r.prompt_tokens, r.max_new_tokens) for r in c)
    # short and long in ONE queue: some prompts never reach the window,
    # most pass it by more than a chunk (their rings wrap)
    edge = CONFIG["sliding_window_size"] + serving["prefill_len"]
    passed = sum(r.prompt_tokens > edge for r in a)
    assert 4 <= sum(r.prompt_tokens < 4096 for r in a) and 16 <= passed < 32
    assert serving["slots"] == 24 and serving["kv_pages"] == 0


def test_the_file_is_the_publication_cut_in_depth_only():
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "rope_layout", "sliding_window_layout"]
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    pub = CONFIG["published"]
    assert pub == {"num_hidden_layers": 52, "rope_layout": [0, 1, 1, 1] * 13,
                   "sliding_window_layout": [0, 1, 1, 1] * 13}
    # two whole periods as published: layers 0-7, 2 full + 6 windowed
    assert CONFIG["num_hidden_layers"] == 8
    assert CONFIG["rope_layout"] == CONFIG["sliding_window_layout"] == \
        pub["rope_layout"][:8]
    # every width as published
    assert {k: CONFIG[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "moe_ffn_hidden_size", "moe_num_primary_experts",
        "moe_num_active_primary_experts", "vocab_size",
        "sliding_window_size", "max_position_embeddings")} == {
        "hidden_size": 2560, "num_attention_heads": 28,
        "num_key_value_heads": 4, "head_dim": 128,
        "moe_ffn_hidden_size": 768, "moe_num_primary_experts": 64,
        "moe_num_active_primary_experts": 6, "vocab_size": 151936,
        "sliding_window_size": 4096, "max_position_embeddings": 16384}
    for item in ("router_input", "experts", "rope", "window", "weights",
                 "compute", "torch_dtype"):
        assert item in CONFIG["assumed"]
    dep = CONFIG["deployment"]
    assert (dep["chips_per_layer"], dep["dense_layers_held"]) == (1, 0)
    assert CONFIG["n_routed_experts"] == 64
    # every published value is the program's preset's (or the run stops)
    from benchmark import serve_child_swa as child

    cfg = child.program_config(CONFIG)
    assert (cfg.n_layers, cfg.n_routed_experts, cfg.moe_top_k, cfg.vocab_size,
            cfg.param_dtype, cfg.dtype) == (
        8, 64, 6, 151936, "bfloat16", "bfloat16")
    assert cfg.layer_windows == (0, 4096, 4096, 4096) * 2
    assert cfg.layer_rope == (False, True, True, True) * 2
    assert (cfg.router_input, cfg.expert_form) == ("attention", "reglu")
    norms = 8 * 2 * 2560 + 2560
    assert cfg.param_count == CONFIG["sizes"]["parameters"] + norms
    assert CONFIG["sizes"]["parameters"] == swa_moe.held_parameters(CONFIG) \
        == 3966894080
    assert CONFIG["sizes"]["weight_bytes"] == 2 * 3966894080
    for key, bad in (("moe_ffn_hidden_size", 512), ("head_dim", 64),
                     ("sliding_window_size", 2048),
                     ("moe_primary_router_apply_softmax", False),
                     ("moe_num_active_primary_experts", 8)):
        with pytest.raises(SystemExit, match=key):
            child.program_config({**CONFIG, key: bad})
    with pytest.raises(SystemExit, match="rope_layout"):
        child.program_config({**CONFIG, "published": {
            **pub, "rope_layout": [1, 1, 1, 1] * 13}})


def test_the_catalogs_numbers_are_the_files():
    """Every number of the catalog's row under its own key, but the three
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
                           "traffic": "serve-closed-mixed", "chips": 1}
    assert len(cells[CELL]["why"]) <= 200
    assert WORKLOAD["driver"] == "serve_gateway_swa"
    assert callable(harness.load_named("drivers", WORKLOAD["driver"]).run)
    assert os.path.isfile(os.path.join(
        ROOT, "benchmark", "reference", f"{CONFIG['reference']}.py"))
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW_METRICS:
        m = per_layer[name]
        assert CELL in m["workloads"]
        assert m["moves"] == "serve_tokens_per_s"
        assert callable(harness.load_named("layer_metrics", name).read)
    assert per_layer["swa_moe_decode_roofline"]["layer"] == \
        per_layer["swa_moe_prefill_roofline"]["layer"] == "kernels"
    assert per_layer["window_keys_over_context"]["layer"] == \
        per_layer["ring_wrapped_share"]["layer"] == "windowed and full layers"
    for name in SHARED_METRICS:
        assert CELL in per_layer[name]["workloads"], name
    for name in ("itl_p95_ms", "experts_hit_share"):
        assert CELL not in per_layer[name]["workloads"]
    tokens = next(m for m in BENCH["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert CELL in tokens["workloads"]
    assert len(json.dumps(BENCH, indent=1)) < 64 * 1024
    for key in ("decode_logit_gap", "decode_logit_gap_mean",
                "prefill_logit_gap", "tail_logit_gap_3rd",
                "tail_logit_gap_median", "prompt_positions",
                "tail_positions"):
        assert key in WORKLOAD["limits"]


def test_counts_by_hand():
    s = swa_moe.sizes(CONFIG)
    attention = 2 * 2560 * 28 * 128 + 2 * 2560 * 4 * 128
    router, expert, head = 2560 * 64, 3 * 2560 * 768, 2560 * 151936
    assert (s["attention"], s["router"], s["expert"], s["head"]) == (
        attention, router, expert, head) == (
        20971520, 163840, 5898240, 388956160)
    assert (s["layers"], s["full"], s["windowed"], s["window"], s["row"]) == (
        8, 2, 6, 4096, 1024)
    body = 8 * (attention + router)
    assert swa_moe.held_parameters(CONFIG) == body + 8 * 64 * expert + 2 * head
    held = swa_moe.cache_bytes(CONFIG, 24, 16384)
    assert held == {"full_rows": 2 * 24 * 16384 * 2048,
                    "rings": 6 * 24 * 4096 * 2048,
                    "rows_in_place_of_rings": 6 * 24 * 16384 * 2048}
    assert round(held["full_rows"] / 1e9, 2) == 1.61 and round(
        held["rings"] / 1e9, 2) == 1.21 and round(
        held["rows_in_place_of_rings"] / 1e9, 2) == 4.83
    # a block of 8 steps, 24 live rows: 18 at position 9000 (wrapped), 6 at
    # 2000; ~57 of 64 experts hit a layer a step by 144 assignments
    live, wrapped = 8 * 24, 8 * 18
    context = 8 * (18 * 9000 + 6 * 2000)
    window_keys = 8 * (18 * 4096 + 6 * 2000)
    hit, landed = 8 * 8 * 57, 8 * 8 * 144
    call = swa_moe.decode_block(CONFIG, 8, live, hit, landed, context,
                                window_keys, wrapped)
    keys = 2 * (context + live) + 6 * (window_keys + live - wrapped)
    assert call["bytes"] == 2 * (
        8 * (body + head) + hit * expert + live * 2560
        + 1024 * (keys + live * 8))
    assert call["flops"] == (live * 2.0 * (body + head)
                             + landed * 2.0 * expert
                             + 4 * 28 * 128 * keys)
    peak = peaks.peaks("TPU v5 lite")
    # bound by bytes: ~9.6 ms a step
    assert swa_moe.least_seconds(call, peak) == call["bytes"] / 819e9
    assert 0.0085 < swa_moe.least_seconds(call, peak) / 8 < 0.0105
    # with full-length rows read in the rings' place a step would need more
    rows = swa_moe.decode_block(CONFIG, 8, live, hit, landed, context,
                                context, 0)
    assert rows["bytes"] - call["bytes"] == 2 * 1024 * 6 * (
        context - window_keys + wrapped)
    # a 512-token chunk behind 8192: every expert of every layer read; a
    # windowed layer's query sees 4096 keys, a full one's 8193 to 8704
    chunk = swa_moe.prefill_chunk(CONFIG, 512, 8192, 512, 8 * 512 * 6)
    assert chunk["bytes"] == 2 * (
        body + head + 512 * expert + 512 * 2560
        + 1024 * (2 * 8192 + 6 * 4095 + 512 * 8))
    assert chunk["flops"] == (
        2.0 * (512 * body + head) + 8 * 512 * 6 * 2.0 * expert
        + 4 * 28 * 128 * (2 * (512 * 8192 + 512 * 513 / 2)
                          + 6 * 512 * 4096))
    assert swa_moe.least_seconds(chunk, peak) == chunk["bytes"] / 819e9
    assert 0.0075 < swa_moe.least_seconds(chunk, peak) < 0.0095
    # the first chunk of a prompt: a windowed layer sees what a full one does
    first = swa_moe.prefill_chunk(CONFIG, 512, 0, 512, 8 * 512 * 6)
    assert first["flops"] == (
        2.0 * (512 * body + head) + 8 * 512 * 6 * 2.0 * expert
        + 4 * 28 * 128 * 8 * 512 * 513 / 2)


def _run(fields_decode: dict, fields_chunk: dict, config=None) -> dict:
    return {"device": {"platform": "tpu", "kind": "TPU v5 lite"},
            "rows": [], "config": config or CONFIG,
            "_span_reduce": {"spans": {
                "decode_block": {"events": [{
                    "device_busy_s": 0.12, "fields": fields_decode}]},
                "prefill_chunk": {"events": [{
                    "device_busy_s": 0.016, "fields": fields_chunk}]}}}}


def test_the_new_metrics_read_nothing_from_a_run_without_their_fields():
    """What the parent's program gives, or another family's: spans without
    the counters."""
    plain = _run({"slots": 4, "n_steps": 8}, {"tokens": 64, "context": 0},
                 config={"n_embd": 8})
    routed = _run({"slots": 4, "n_steps": 8, "experts_hit": 50,
                   "expert_tokens": 90, "context_tokens": 700},
                  {"tokens": 64, "context": 0},
                  config={"n_routed_experts": 16, "deployment": {}})
    empty = {**plain, "_span_reduce": {"spans": {}}}
    for run in (plain, routed, empty):
        for name in NEW_METRICS:
            assert harness.load_named("layer_metrics", name).read(run) is None


def test_the_new_metrics_read_the_spans_fields():
    live, wrapped = 8 * 24 - 10, 8 * 18
    counted = {"row_steps": live, "ring_wrapped_row_steps": wrapped,
               "context_tokens": 8 * (18 * 9000 + 6 * 2000),
               "window_keys": 8 * (18 * 4096 + 6 * 2000),
               "experts_hit": 8 * 8 * 57, "expert_tokens": 8 * 8 * 144,
               "expert_load_max": 40}
    chunk = {"row_steps": 400, "ring_wrapped_row_steps": 400,
             "context_tokens": 400 * 8400, "window_keys": 400 * 4096,
             "experts_hit": 512, "expert_tokens": 8 * 400 * 6}
    run = _run({"slots": 24, "n_steps": 8, "frozen_row_steps": 10, **counted},
               {"tokens": 400, "context": 8192, **chunk})

    def read(name):
        return harness.load_named("layer_metrics", name).read(run)

    assert read("ring_wrapped_share") == pytest.approx(wrapped / live)
    assert read("window_keys_over_context") == pytest.approx(
        (18 * 4096 + 6 * 2000) / (18 * 9000 + 6 * 2000))
    assert 0.45 < read("window_keys_over_context") < 0.55
    peak = peaks.peaks("TPU v5 lite")
    assert read("swa_moe_decode_roofline") == pytest.approx(
        100 * swa_moe.least_seconds(swa_moe.decode_block(
            CONFIG, 8, live, 8 * 8 * 57, 8 * 8 * 144,
            counted["context_tokens"], counted["window_keys"], wrapped),
            peak) / 0.12)
    assert 55 < read("swa_moe_decode_roofline") < 75
    assert read("swa_moe_prefill_roofline") == pytest.approx(
        100 * swa_moe.least_seconds(swa_moe.prefill_chunk(
            CONFIG, 400, 8192, 512, 19200), peak) / 0.016)
    assert 0 < read("swa_moe_prefill_roofline") < 100
    # the live rows the model counted are the span's: slots x steps - frozen
    assert live == 24 * 8 - 10
    # the accepted readers read this family's spans too
    assert read("expert_tokens_per_step") == 8 * 144
    assert read("decode_context_tokens") == pytest.approx(
        counted["context_tokens"] / live)


# ------------------------------------------ the reference against the program


@pytest.fixture(scope="module")
def tiny():
    from benchmark.drivers import serve_gateway_swa as driver

    return {**driver.REHEARSAL_CONFIG, "serving": driver.REHEARSAL_SERVING}


@pytest.fixture(scope="module")
def program(tiny):
    """The program's forward at the rehearsal's size on the reference's
    own leaves, and the reference's logits, over two sequences of 48 (three
    windows) whose prompts end at 29 and 40."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark import serve_child_swa as child
    from benchmark.reference import smallthinker as ref
    from dlrover_tpu.models import transformer as tfm

    pcfg = child.program_config(tiny)
    params = child.program_params(ref, tiny, 7, pcfg)
    rng = np.random.default_rng(3)
    seqs = [rng.integers(0, 256, 48).astype(np.int32) for _ in range(2)]
    got = tfm.forward(params, jnp.asarray(np.stack(seqs)), pcfg)
    return ref, seqs, np.asarray(got)


def _logits(ref, tiny, seqs, control=""):
    import numpy as np

    return np.stack([np.asarray(rows) for rows in ref.logits_many(
        tiny, 7, seqs, control, prompt_lens=[29, 40])])


def test_the_reference_is_the_programs_forward(program, tiny):
    """(a) float32 on both sides: the plain reference (the window a mask
    over the whole sequence, a loop over all the experts) and the program's
    forward differ by float32's rounding of logits of size ~3."""
    import numpy as np

    ref, seqs, got = program
    want = _logits(ref, tiny, seqs)
    assert np.abs(got - want).max() < 1e-4
    at = [[3, 47], [0, 20, 21]]
    some = ref.logits_many(tiny, 7, seqs, "", at)
    for rows, mine, whole in zip(some, at, want):
        assert np.abs(np.asarray(rows) - whole[mine]).max() < 1e-6
    _, margins = ref.logits_many(tiny, 7, seqs, margins=True)
    assert margins[0].shape == (48,) and float(margins[0].min()) >= 0
    # a part of an expert stack holds the whole's numbers
    whole = ref.weight(tiny, 7, 2, "we_up")
    part = ref.weight(tiny, 7, 2, "we_up", (3, 5))
    assert np.array_equal(np.asarray(whole[3:5]), np.asarray(part))
    assert ref.weight(tiny, 2**31 + 9, 0, "wq").shape == (64, 4, 16)


def test_every_control_moves_the_logits(program, tiny):
    """Each fault is another function: no control is vacuous at the
    rehearsal's size. The faults of the ring leave the positions before the
    window as they are, and ``pads_in_ring`` those of the prompt."""
    import numpy as np

    ref, seqs, _ = program
    sound = _logits(ref, tiny, seqs)
    assert set(ref.CONTROLS) == {
        "", "fp8", "full_in_place_of_window", "window_4095",
        "rope_on_full_layers", "no_rope_on_windowed",
        "router_after_attention", "silu_in_place_of_relu", "stale_ring",
        "ring_reset_at_chunk", "pads_in_ring", "one_expert_left_out"}
    for control in ref.CONTROLS[1:]:
        moved = np.abs(_logits(ref, tiny, seqs, control) - sound).max(-1)
        assert moved.max() > 1e-2, control
        if control in ("full_in_place_of_window", "stale_ring"):
            assert moved[:, :16].max() == 0 and moved[:, 16:].max() > 1e-2
        if control == "window_4095":
            assert moved[:, :15].max() == 0
        if control == "ring_reset_at_chunk":
            assert moved[:, :12].max() == 0
        if control == "pads_in_ring":
            # the keys 16 before the pads of the final chunk (prompts of 29
            # and 40: pads at 29-35 and 40-47) are lost to the queries
            # behind the prompt, and to no other
            assert moved[0, :29].max() == 0 and moved[0, 29:36].max() > 1e-3
            assert moved[1, :40].max() == 0 and moved[1, 40:].max() > 1e-3
    bf16 = np.abs(_logits(ref, tiny, seqs, "bf16") - sound).max()
    assert 1e-4 < bf16 < np.abs(_logits(ref, tiny, seqs, "fp8") - sound).max()
    with pytest.raises(ValueError, match="unknown control"):
        ref.logits_many(tiny, 7, seqs, "no_such_fault")


def test_the_choice_margin_cannot_carry_this_family():
    """Why `correct` is the five numbers of ``serve_child_hybrid`` and not
    ``serve_child_ref``'s margins: at the published router's sizes (6 of 64,
    unit-variance logits, 8 layers) nearly every position has, in some
    layer, a choice that lies within bfloat16's reach of the first expert
    passed over, and would be left out as undecided."""
    import numpy as np

    rng = np.random.default_rng(0)
    logits = rng.standard_normal((8, 4096, 64)).astype(np.float32)
    ranked = -np.sort(-logits, axis=-1)
    margin = (ranked[..., 5] - ranked[..., 6]).min(0)       # over layers
    assert (margin < 0.04).mean() > 0.9


def test_the_sample_rule(tiny):
    """A sample without a wrapped request stops the run; a builder's
    faults are read on the shortest request beside the wrapped one whose
    prompt's final chunk has the longest pad tail."""
    from types import SimpleNamespace

    from benchmark import serve_child_swa as child

    spec = {"config": {"sliding_window_size": 16},
            "serving": {"prefill_len": 12}}

    def rec(n):
        return {"prompt": [0] * n, "result": SimpleNamespace(tokens=[1, 2])}

    # (pad tails of 8, 2, 6 and 9 in chunks of 12)
    sample = [rec(40), rec(10), rec(30), rec(27)]
    assert child.wrapped(spec, sample) == [sample[0], sample[2], sample[3]]
    assert child.wrapped(spec, [rec(10), rec(26)]) == []
    seen = []
    child._hybrid_checks = lambda spec, ref, sample, *a: seen.append(sample)
    try:
        child.reference_checks(spec, None, sample, "", {})
        child.reference_checks(spec, None, sample[:2], "fp8", {}, {})
    finally:
        from benchmark import serve_child_hybrid

        child._hybrid_checks = serve_child_hybrid.reference_checks
    assert seen == [sample, [sample[1], sample[3]]]
