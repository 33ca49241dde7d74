"""The benchmark's pieces for a BLOCK-DIFFUSION configuration that names its
reference (SDAR-30B-A3B-Chat: ``drivers/serve_gateway_diffusion.py``,
``serve_child_diffusion.py``, ``reference/sdar_moe.py``,
``counts/gqa_moe.py``): the cell's traffic, the configuration file against
the catalog's keys and the program's preset, the counts by hand, the replay
of a served trajectory, a rehearsed run and its controls.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, traffic  # noqa: E402
from benchmark.counts import gqa_moe, peaks  # noqa: E402

CELL = "sdar-30b-a3b-chat.serve-closed-fixed"
CONFIG = harness.load_json(os.path.join(
    ROOT, "benchmark", "configs", "sdar-30b-a3b-chat.json"))
WORKLOAD = harness.load_json(os.path.join(
    ROOT, "benchmark", "workloads", f"{CELL}.json"))
# the catalog's `config` of SDAR-30B-A3B-Chat (model-configs guide), copied:
# the file holds every key of it under the same name
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


# ----------------------------------------------- the cell, its file, its sizes


def test_traffic_is_the_issues_and_ids_lie_below_the_mask_token():
    mix, serving = WORKLOAD["traffic_mix"], CONFIG["serving"]
    assert mix == {
        "prompt_tokens": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                          "min": 64, "max": 2048},
        "output_tokens": {"dist": "fixed", "value": 256},
        "arrivals": {"kind": "closed", "clients_per_slot": 1},
        "pool": 16, "mix_seed": 0, "ramp_s": 20}
    assert serving == {"slots": 16, "max_len": 2560, "prefill_len": 512,
                       "decode_block": 4, "prefix_cache_entries": 2,
                       "kv_pages": 0}
    big = 2**31 + 12345
    a, b = (traffic.requests(mix, big, 70.0) for _ in range(2))
    c = traffic.requests(mix, big + 1, 70.0)
    assert a == b and a != c and len(a) == 16
    mask = CONFIG["assumed"]["mask_token_id"]
    for r in a:
        assert 64 <= r.prompt_tokens <= 2048 and r.max_new_tokens == 256
        assert r.prompt_tokens + r.max_new_tokens <= serving["max_len"]
        ids = traffic.prompt_ids(r, mask)
        assert 0 <= min(ids) and max(ids) < mask == 151669
    assert sorted(r.prompt_tokens for r in a) == \
        sorted(r.prompt_tokens for r in c)
    # every remainder of a prompt over the block length shows in the pool
    assert {r.prompt_tokens % 4 for r in a} == {0, 1, 2, 3}


def test_the_file_is_the_publication_less_its_depth():
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "sdar-30b-a3b-chat")
    assert entry["reduced"] == CONFIG["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == CONFIG["source"]
    assert CONFIG["published"] == {"num_hidden_layers": 48}
    for key, value in PUBLISHED.items():
        assert CONFIG[key] == (6 if key == "num_hidden_layers" else value)
    # the guide's floors: more than four layers, every expert, the whole
    # vocabulary
    assert CONFIG["num_hidden_layers"] > 4
    assert CONFIG["n_routed_experts"] == CONFIG["num_experts"] == 128
    assert CONFIG["deployment"]["chips_per_layer"] == 1
    assumed = CONFIG["assumed"]
    assert (assumed["block_length"], assumed["denoising_steps"],
            assumed["remasking"], assumed["mask_token_id"]) == (
                4, 4, "low_confidence_static", 151669)
    for key in ("torch_dtype", "logits", "prompt_remainder", "rope",
                "token_ids", "weights", "compute", "generation"):
        assert assumed[key]
    # every published width is the program's preset's (or the run stops)
    from benchmark import serve_child_diffusion as child

    cfg = child.program_config(CONFIG)
    assert (cfg.n_layers, cfg.head_dim, cfg.n_routed_experts, cfg.moe_top_k,
            cfg.vocab_size, cfg.param_dtype, cfg.block_length) == (
                6, 128, 128, 8, 151936, "bfloat16", 4)
    assert cfg.param_count == CONFIG["sizes"]["parameters"]
    norms = 6 * (2 * 2048 + 2 * 128) + 2048
    assert gqa_moe.held_parameters(CONFIG) == cfg.param_count - norms
    assert CONFIG["sizes"]["weight_bytes"] == 2 * cfg.param_count
    with pytest.raises(SystemExit, match="head_dim=64"):
        child.program_config({**CONFIG, "head_dim": 64})
    with pytest.raises(SystemExit, match="assumes block_length"):
        child.program_config({**CONFIG, "assumed": {**assumed,
                                                    "block_length": 8}})


def test_counts_by_hand():
    s = gqa_moe.sizes(CONFIG)
    assert s["attention"] == 2 * 2048 * 4096 + 2 * 2048 * 512 == 18874368
    assert s["router"] == 2048 * 128 and s["expert"] == 3 * 2048 * 768
    assert s["head"] == 2048 * 151936 and s["row"] == 1024
    assert (s["layers"], s["block"], s["scores"]) == (6, 4, 4 * 32 * 128)
    body = 6 * (18874368 + 262144)
    assert gqa_moe.held_parameters(CONFIG) == (
        body + 6 * 128 * 4718592 + 2 * 311164928) == 4361027584

    # one block for 16 slots at 900 live tokens: 4 denoising passes and a
    # storing pass of 64 rows, 5 x 64 x 8 x 6 assignments that reached 3700
    # (layer, pass, expert) cells
    call = gqa_moe.denoise_call(CONFIG, 16, 4, 1, 900, 15360, 3700)
    attend = 6 * 4 * 32 * 128 * 904
    assert call["flops"] == (5 * 64 * (2.0 * body + attend)
                             + 4 * 64 * 2.0 * 311164928
                             + 2.0 * 15360 * 4718592)
    assert call["bytes"] == 2 * (5 * body + 4 * 311164928 + 3700 * 4718592
                                 + 5 * 16 * 904 * 6 * 1024)
    peak = peaks.peaks("TPU v5 lite")
    assert gqa_moe.least_seconds(call, peak) == call["bytes"] / 819e9
    # a pass of this call: 7.9 GB, 9.6 ms at the chip's 819 GB/s
    assert 0.048 < gqa_moe.least_seconds(call, peak) < 0.0485

    # a whole chunk behind 1024 cached tokens, every expert hit in each layer
    call = gqa_moe.prefill_chunk(CONFIG, 512, 1024, 24576, 768)
    pairs = 512 * 1024 + 512 * 516 / 2
    assert call["flops"] == (2.0 * (512 * body + 311164928)
                             + 6 * 4 * 32 * 128 * pairs
                             + 2.0 * 24576 * 4718592)
    assert call["bytes"] == 2 * (body + 311164928 + 768 * 4718592
                                 + 1536 * 6 * 1024)
    assert gqa_moe.least_seconds(call, peak) == call["bytes"] / 819e9


def test_the_new_metrics_read_nothing_from_a_run_without_their_fields():
    """What the parent's program gives (spans without the passes), and what
    another family's cell gives (counters, no block diffusion)."""
    span = {"name": "decode_block", "device_busy_s": 0.01, "dur_s": 0.01,
            "self_s": 0.0, "fields": {"slots": 2, "n_steps": 8,
                                      "expert_tokens": 5, "experts_hit": 3}}
    chunk = {"name": "prefill_chunk", "device_busy_s": 0.01, "dur_s": 0.01,
             "self_s": 0.0, "fields": {"tokens": 8, "context": 0,
                                       "expert_tokens": 5, "experts_hit": 3}}
    run = {"device": {"platform": "tpu", "kind": "TPU v5 lite"},
           "rows": [{"prompt_tokens": 10, "output_tokens": 4}],
           "config": {"n_embd": 8}, "spans": {"events": [span, chunk]}}
    for name in ("gqa_moe_denoise_roofline", "gqa_moe_prefill_roofline"):
        assert harness.load_named("layer_metrics", name).read(run) is None
    assert harness.load_named(
        "layer_metrics", "gqa_moe_prefill_roofline").read(
            {**run, "spans": {"events": []}}) is None


# ------------------------------------------- a run with the chip look skipped


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One window at the rehearsal's configuration through the child's own
    set-up, traffic loop and sample."""
    from benchmark import serve_child_diffusion as child
    from benchmark.drivers import serve_gateway_diffusion as drv
    from benchmark.reference import sdar_moe as ref

    spec = {"seed": 2**31 + 9, "seconds": 2.0, "trace": False,
            "rehearse": True, "chips": 1, "config": drv.REHEARSAL_CONFIG,
            "serving": drv.REHEARSAL_SERVING,
            "traffic": {**WORKLOAD["traffic_mix"], **drv.REHEARSAL_LENGTHS},
            "limits": drv.REHEARSAL_LIMITS, "sample": WORKLOAD["sample"],
            "control": "",
            "trace_dir": str(tmp_path_factory.mktemp("trace")),
            "t_start": 0.0, "trace_after_s": 1, "trace_seconds": 1}
    vocab = spec["config"]["assumed"]["mask_token_id"]
    device, pcfg, gateway = child.build(spec, ref)
    try:
        replica = gateway.pool.ready_replicas()[0]
        child.warm_up(gateway, spec, vocab)
        now = time.monotonic()
        window = child.drive(gateway, spec, vocab, now, now)
        summary = child.summarize(window, now, spec["seconds"])
        _, sample = child.sample_and_prefill(spec, replica.engine, window,
                                             "sample")
        logits = child.engine_logits(spec, replica.engine, sample, "")
    finally:
        gateway.stop()
    assert summary["failed"] == 0 and summary["serve_tokens_per_s"] > 0
    for rec in window["records"]:
        assert max(rec["prompt"]) < vocab == 255
        res = rec.get("result")
        if res is not None:
            # the gateway hands the passes on; a block's tokens arrive
            # together: most gaps between stamps are the callback's own
            assert len(res.unmask_steps) == len(res.tokens)
            gaps = np.diff(res.token_times)
            assert (gaps < 1e-3).sum() >= len(gaps) // 2
    assert all(n % 4 == 0 for _, n in logits) and len(logits) > len(sample)
    return spec, ref, sample, logits


CHECKS = ["denoise_logit_gap", "denoise_logit_gap_mean",
          "unmask_order_gap_median", "chunk_logit_gap",
          "tail_logit_gap_median"]


@pytest.mark.parametrize("control", [
    "", "causal_in_block", "stale_rows", "no_qk_norm", "expert_left_out",
    "fp8", "least_confident"])
def test_a_sound_run_is_correct_and_every_control_is_not(served, control):
    from benchmark import serve_child_diffusion as child

    spec, ref, sample, logits = served
    checks = child.reference_checks(spec, ref, sample, control,
                                    {} if control else logits)
    assert [c["name"] for c in checks] == CHECKS
    failed = {c["name"] for c in checks if not c["value"] <= c["limit"]}
    assert bool(failed) is (control != ""), (control, checks)
    # a fault that lives in generation alone leaves the chunk program's
    # logits alone, and fails by what replays the served trajectory
    if control == "least_confident":
        assert failed == {"unmask_order_gap_median"}
    if control == "stale_rows":
        assert {"denoise_logit_gap", "denoise_logit_gap_mean"} <= failed
        assert not failed & {"chunk_logit_gap", "tail_logit_gap_median"}


def test_the_replay_is_the_references_own_generation(served):
    """Replaying what the reference itself generated gives its own tokens as
    the best at every position, and its own choices as the ones it would
    make; a last block that the budget cut is left out."""
    spec, ref, sample, _ = served
    cfgf = spec["config"]
    prompt = sample[0]["prompt"][:10]
    answer, steps = ref.generate(cfgf, spec["seed"], prompt, 9)
    out = ref.denoise_logits(cfgf, spec["seed"], prompt, answer, steps)
    assert out["index"].tolist() == [0, 1, 2, 3, 4, 5]     # 10 + 9 = 16 + 3
    assert out["logits"].argmax(-1).tolist() == answer[:6]
    pairs = out["pairs"]
    assert pairs == 8
    assert (out["would"][:pairs] == out["chosen"][:pairs]).all()
    assert out["masked"][0].tolist() == [False, False, True, True]


def test_a_request_sampled_twice_is_read_once(served):
    from benchmark import serve_child_diffusion as child

    spec, ref, sample, logits = served
    once = child.compare(spec, ref, sample, "", logits)
    again = child.compare(spec, ref, sample + [sample[0]], "", logits)
    assert again == once and len(once["denoise"]) > 30
    assert len(once["order"]) > 10


@pytest.mark.parametrize("lost", ["tail", "ends"])
def test_a_comparison_that_went_missing_is_not_correct(served, lost):
    """No logits from the engine in the tails, or none at all: those checks
    read NOTHING_COMPARED and fail alone."""
    from benchmark import serve_child_diffusion as child

    spec, ref, sample, logits = served
    prompts = set(child._positions(spec, sample)[0])
    logits = ({k: v for k, v in logits.items() if k in prompts}
              if lost == "tail" else {})
    checks = {c["name"]: c for c in child.reference_checks(
        spec, ref, sample, "", logits)}
    failed = {n for n, c in checks.items() if not c["value"] <= c["limit"]}
    assert failed == ({"tail_logit_gap_median"} if lost == "tail" else
                      {"tail_logit_gap_median", "chunk_logit_gap"})
    assert checks["tail_logit_gap_median"]["value"] == child.NOTHING_COMPARED


def test_the_drivers_rehearsal():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("CONTROL", None)
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--seed", str(2**31 + 5), "--seconds", "2", "--trace", "0",
         "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["rehearsal_passed"] is True, out.stdout[-2000:]
    line = last["would_print"]
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
