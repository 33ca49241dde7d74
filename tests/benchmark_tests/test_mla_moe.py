"""The benchmark's pieces for a configuration that names its reference
(openPangu-Ultra-MoE: ``drivers/serve_gateway_ref.py``,
``serve_child_ref.py``, ``reference/pangu_ultra_moe.py``,
``counts/mla_moe.py``): the cell's traffic, the configuration file against
the program's preset, the counts by hand, a rehearsed run and its controls.
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
from benchmark.counts import mla_moe, peaks  # noqa: E402

CELL = "openpangu-ultra-moe-718b.serve-closed-long"
CONFIG = harness.load_json(os.path.join(
    ROOT, "benchmark", "configs", "openpangu-ultra-moe-718b.json"))
WORKLOAD = harness.load_json(os.path.join(
    ROOT, "benchmark", "workloads", f"{CELL}.json"))


# ----------------------------------------------- the cell, its file, its sizes


def test_traffic_stays_inside_the_cells_lengths_and_the_held_vocabulary():
    mix, serving = WORKLOAD["traffic_mix"], CONFIG["serving"]
    big = 2**31 + 12345
    a, b = (traffic.requests(mix, big, 70.0) for _ in range(2))
    c = traffic.requests(mix, big + 1, 70.0)
    assert a == b and a != c and len(a) == mix["pool"] == 16
    for r in a:
        assert 256 <= r.prompt_tokens <= 4096
        assert 64 <= r.max_new_tokens <= 768
        assert r.prompt_tokens + r.max_new_tokens <= serving["max_len"]
        ids = traffic.prompt_ids(r, CONFIG["vocab_size"])
        assert 0 <= min(ids) and max(ids) < CONFIG["vocab_size"] == 19200
    # every seed offers the same set of sizes, in another order
    assert sorted((r.prompt_tokens, r.max_new_tokens) for r in a) == \
        sorted((r.prompt_tokens, r.max_new_tokens) for r in c)
    assert mix["arrivals"] == {"kind": "closed", "clients_per_slot": 1}
    assert serving == {"slots": 16, "max_len": 5120, "prefill_len": 512,
                       "decode_block": 8, "prefix_cache_entries": 2,
                       "kv_pages": 0}


def test_the_file_is_the_publication_less_the_stated_share():
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "openpangu-ultra-moe-718b")
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers"]
    assert entry["source"] == CONFIG["source"]
    assert CONFIG["published"] == {
        "num_hidden_layers": 61, "n_routed_experts": 256,
        "vocab_size": 153600, "num_nextn_predict_layers": 1}
    # the guide's floors: a dense layer and four expert layers, 8 experts
    # or more, an eighth of the vocabulary or more
    dep = CONFIG["deployment"]
    assert CONFIG["num_hidden_layers"] - dep["dense_layers_held"] >= 4
    assert CONFIG["n_routed_experts"] >= 8
    assert 8 * CONFIG["vocab_size"] >= CONFIG["published"]["vocab_size"]
    assert dep["chips_per_layer"] == dep["expert_parallel"] == 16
    assert 16 * CONFIG["n_routed_experts"] == 256
    # every published width is the program's preset's (or the run stops)
    from benchmark import serve_child_ref as child

    cfg = child.program_config(CONFIG)
    assert (cfg.n_layers, cfg.first_k_dense, cfg.experts_held,
            cfg.vocab_size, cfg.param_dtype) == (5, 1, 16, 19200, "bfloat16")
    assert cfg.param_count == CONFIG["sizes"]["parameters"]
    # the counts hold the matrices; the program also has its norms' scales
    norms = 5 * (4 * 7680 + 1536 + 512) + 7680
    assert mla_moe.held_parameters(CONFIG) == cfg.param_count - norms
    assert CONFIG["sizes"]["weight_bytes"] == 2 * cfg.param_count


def test_counts_by_hand():
    s = mla_moe.sizes(CONFIG)
    # W_qa + W_qb + W_kva + W_kvb + W_o
    assert s["attention"] == (7680 * 1536 + 1536 * 128 * 192 + 7680 * 576
                              + 512 * 128 * 256 + 128 * 128 * 7680)
    assert s["attention"] == 196575232 and s["w_kvb"] == 16777216
    assert s["dense_ffn"] == 3 * 7680 * 18432 == 424673280
    assert s["shared"] == s["expert"] == 3 * 7680 * 2048 == 47185920
    assert s["router"] == 7680 * 256 and s["head"] == 7680 * 19200
    assert (s["layers"], s["dense_layers"], s["expert_layers"],
            s["row"]) == (5, 1, 4, 576)
    body = 5 * 196575232 + 424673280 + 4 * (47185920 + 1966080)
    assert mla_moe.held_parameters(CONFIG) == (
        body + 4 * 16 * 47185920 + 2 * 147456000) == 4918968320

    # a block of 8 steps, 16 slots at 1500 live tokens, 256 assignments
    # that reached 200 (layer, step, expert) cells
    call = mla_moe.decode_block(CONFIG, 16, 8, 1500, 256, 200)
    attend = 5 * 2 * 128 * 1500 * (576 + 512)
    assert call["flops"] == (128 * (2.0 * (body + 147456000) + attend)
                             + 2.0 * 256 * 47185920)
    assert call["bytes"] == 2 * (8 * (body + 147456000) + 200 * 47185920
                                 + 128 * 1501 * 5 * 576)
    peak = peaks.peaks("TPU v5 lite")
    assert mla_moe.least_seconds(call, peak) == call["bytes"] / 819e9

    # a whole chunk behind 1024 cached tokens
    call = mla_moe.prefill_chunk(CONFIG, 512, 1024, 256, 16)
    pairs = 512 * 1024 + 512 * 513 / 2
    attend = 5 * (2.0 * 128 * 320 * pairs + 2.0 * 1536 * 16777216)
    assert call["flops"] == (
        2.0 * (512 * (body - 5 * 16777216) + 147456000) + attend
        + 2.0 * 256 * 47185920)
    assert call["bytes"] == 2 * (body + 147456000 + 16 * 47185920
                                 + 1536 * 5 * 576)
    assert mla_moe.least_seconds(call, peak) == call["flops"] / 197e12


def test_the_choice_margin_is_the_nearest_change_to_a_held_expert():
    """Two of six chosen, experts 4 and 5 held: a held expert third in a
    close row (the absent expert between may swap with either), one passed
    over by half a logit, and a held one that is chosen."""
    import jax.numpy as jnp

    from benchmark.reference import pangu_ultra_moe as ref

    logit = jnp.asarray([[3.0, 2.0, 1.99, 0.0, 1.98, -5.0],
                         [3.0, 2.0, 1.0, 0.0, -1.0, 1.5],
                         [3.0, 1.0, 0.9, 0.0, 2.0, -5.0]])
    margin = ref.choice_margin({"num_experts_per_tok": 2}, logit, 4, 2)
    assert margin.tolist() == pytest.approx([0.02, 0.5, 1.0], abs=1e-6)


def test_the_new_metrics_read_nothing_from_a_run_without_their_fields():
    """What the parent's program gives: spans without the counters."""
    run = {"device": {"platform": "tpu", "kind": "TPU v5 lite"},
           "rows": [{"prompt_tokens": 10, "output_tokens": 4}],
           "config": {"n_embd": 8}}
    for name in ("mla_moe_decode_roofline", "mla_moe_prefill_roofline",
                 "expert_tokens_per_step", "expert_load_max_over_mean"):
        assert harness.load_named("layer_metrics", name).read(run) is None


# ------------------------------------------- a run with the chip look skipped


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One window at the rehearsal's configuration through the child's own
    set-up, traffic loop and sample."""
    from benchmark import serve_child_ref as child
    from benchmark.drivers import serve_gateway_ref as drv
    from benchmark.reference import pangu_ultra_moe as ref

    spec = {"seed": 2**31 + 9, "seconds": 3.0, "trace": False,
            "rehearse": True, "chips": 1, "config": drv.REHEARSAL_CONFIG,
            "serving": drv.REHEARSAL_SERVING,
            "traffic": {**WORKLOAD["traffic_mix"], **drv.REHEARSAL_LENGTHS},
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
        _, sample = child.sample_and_prefill(spec, replica.engine, window, "")
        logits = child.engine_logits(spec, replica.engine, sample, "")
    finally:
        gateway.stop()
    assert summary["failed"] == 0 and summary["serve_tokens_per_s"] > 0
    for rec in window["records"]:
        assert max(rec["prompt"]) < pcfg.vocab_size == 128
    # the end of every sampled prompt (two positions here), and its tail
    assert {(i, len(rec["prompt"]) - j) for i, rec in enumerate(sample)
            for j in (0, 1)} <= set(logits)
    assert len(logits) > 6 + len(sample)
    return spec, ref, sample, logits


CHECKS = ["decode_logit_gap", "decode_logit_gap_3rd", "undecided_share",
          "prefill_logit_gap", "tail_logit_gap_3rd"]


@pytest.mark.parametrize(
    "control", ["", "fp8", "drop_expert", "no_scaling", "no_post_norms"])
def test_a_sound_run_is_correct_and_every_control_is_not(served, control):
    from benchmark import serve_child_ref as child

    spec, ref, sample, logits = served
    checks = child.reference_checks(spec, ref, sample, control,
                                    {} if control else logits)
    assert [c["name"] for c in checks] == CHECKS
    correct = all(c["value"] <= c["limit"] for c in checks)
    assert correct is (control == ""), (control, checks)


def test_a_request_sampled_twice_is_read_once(served):
    """A closed loop goes round its pool: the same request served the same
    answer again adds no reading (one flipped choice stays one)."""
    from benchmark import serve_child_ref as child

    spec, ref, sample, logits = served
    once = child.compare(spec, ref, sample, "", logits)
    again = child.compare(spec, ref, sample + [sample[0], sample[0]], "",
                          logits)
    assert again == once and len(once["decode"]) > 30


@pytest.mark.parametrize("lost", ["prefill", "tail", "decided"])
def test_a_comparison_that_went_missing_is_not_correct(served, lost):
    """No logits from the engine at the prompts' ends, or none in the tails:
    that check reads NOTHING_COMPARED and fails alone. A margin under which
    most positions count as undecided: the share left out fails."""
    from benchmark import serve_child_ref as child

    spec, ref, sample, logits = served
    prompts = set(child._positions(spec, sample)[0])
    if lost == "decided":
        spec = {**spec, "limits": {**spec["limits"], "choice_margin": 50.0}}
    else:
        logits = {k: v for k, v in logits.items()
                  if (k in prompts) is (lost == "tail")}
    checks = {c["name"]: c for c in child.reference_checks(
        spec, ref, sample, "", logits)}
    failed = {n for n, c in checks.items() if not c["value"] <= c["limit"]}
    if lost == "decided":
        assert "undecided_share" in failed
        return
    name = {"prefill": "prefill_logit_gap", "tail": "tail_logit_gap_3rd"}[lost]
    assert failed == {name}
    assert checks[name]["positions"] == 0
    assert checks[name]["value"] == child.NOTHING_COMPARED


@pytest.mark.parametrize("control", ["", "no_scaling"])
def test_the_drivers_rehearsal(control):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("CONTROL", None)
    if control:
        env["CONTROL"] = control
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--seed", str(2**31 + 5), "--seconds", "3", "--trace", "0",
         "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["rehearsal_passed"] is (control == ""), out.stdout[-2000:]
    assert out.returncode == (0 if control == "" else 1)
    line = last["would_print"]
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
