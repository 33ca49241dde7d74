"""Driver ``serve_gateway_swa_shared``: ``serve_gateway`` for a
WINDOWED-AND-FULL configuration with post-norms and a sigmoid router beside a
shared expert (``model_type`` ``exaone_moe``;
``benchmark/serve_child_swa_shared.py``, ``README.swa-shared.md``). The
program's ``Gateway`` with one engine replica in a child that holds the chip;
requests go through ``Gateway.submit``. The spec, the percentile and the
result's form are ``serve_gateway``'s; the run itself is
``serve_gateway_swa``'s with this family's child and rehearsal.

The rehearsal (``--rehearse``, CPU) runs a tiny configuration of the same
kinds, written here as the configuration file it would be: the held stack
L L L G L with a leading dense layer, a window of 4 under chunks of 12 (every
chunk three rings wide, as the cell's 512 are four of 128), 8 of 16 experts
held from the 8th on (4 a token, x 2.5, one shared expert), half the
vocabulary, float32; prompts of 30-120 tokens, so that every prompt passes
window + chunk and every ring wraps many times. ``CONTROL`` in the environment
of a rehearsal or of a builder's run (``benchmark.run`` takes no such option)
goes into the spec as ``control``; a list ``CONTROL=sound,<fault>,...``
decides `correct` by the program itself and notes every fault's numbers.
"""

from __future__ import annotations

import json
import os
import statistics

from benchmark import harness
from benchmark.drivers.serve_gateway import build_spec, percentile
from benchmark.harness import check

_TYPES = ["sliding_attention"] * 3 + ["full_attention", "sliding_attention"]
REHEARSAL_CONFIG = {
    "model_type": "exaone_moe", "hidden_act": "silu",
    "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "tie_word_embeddings": False,
    "sliding_window_pattern": "LLLG", "num_nextn_predict_layers": 0,
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 96, "max_position_embeddings": 256,
    "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 10000.0, "rope_type": "default"},
    "first_k_dense_replace": 1, "moe_intermediate_size": 32,
    "num_experts": 8, "num_experts_per_tok": 4, "num_shared_experts": 1,
    "routed_scaling_factor": 2.5, "num_hidden_layers": 5,
    "layer_types": _TYPES, "sliding_window": 4,
    "sliding_windows": [4, 4, 4, 0, 4],
    "mlp_layer_types": ["dense"] + ["sparse"] * 4, "vocab_size": 128,
    "n_routed_experts": 8, "reduced": ["num_experts", "vocab_size"],
    "published": {"num_experts": 16, "vocab_size": 256},
    "deployment": {"chips_per_layer": 2, "expert_parallel": 2,
                   "expert_first": 8, "dense_layers_held": 1},
    "assumed": {"torch_dtype": "float32"},
    "reference": "exaone_moe", "program_model": "tiny-k-exaone",
}
REHEARSAL_SERVING = {"slots": 4, "max_len": 192, "prefill_len": 12,
                     "decode_block": 8, "prefix_cache_entries": 2,
                     "kv_pages": 0, "admission_deadline_s": 120.0}
REHEARSAL_LENGTHS = {"prompt_tokens": {"dist": "lognormal", "median": 60,
                                       "sigma": 0.4, "min": 30, "max": 120},
                     "output_tokens": {"dist": "lognormal", "median": 20,
                                       "sigma": 0.5, "min": 8, "max": 48},
                     "ramp_s": 4}
# float32 on both sides
REHEARSAL_LIMITS = {"decode_logit_gap": 1e-3, "decode_logit_gap_mean": 1e-4,
                    "prefill_logit_gap": 1e-3, "tail_logit_gap_3rd": 1e-3,
                    "tail_logit_gap_median": 1e-3, "tail_positions": 6,
                    "prompt_positions": 2}


def spec_for(r: harness.Run) -> dict:
    """``serve_gateway.build_spec``, with this driver's rehearsal."""
    spec = build_spec(r)
    spec["control"] = os.environ.get("CONTROL", "")
    if r.rehearse:
        spec["config"] = {**REHEARSAL_CONFIG, "serving": REHEARSAL_SERVING}
        spec["serving"] = REHEARSAL_SERVING
        spec["traffic"] = {**r.workload["traffic_mix"], **REHEARSAL_LENGTHS}
        spec["limits"] = REHEARSAL_LIMITS
    return spec


def run(r: harness.Run) -> dict:
    spec = spec_for(r)
    with open(r.path("spec.json"), "w") as f:
        json.dump(spec, f)
    cmd = [harness.PY, "-m", "benchmark.serve_child_swa_shared", "--spec",
           r.path("spec.json"), "--out", r.path("serve.json")]
    out = r.child_json(cmd, r.path("serve.log"), r.path("serve.json"), 2400)
    device = out["device"]
    check(device["platform"] == ("cpu" if r.rehearse else "tpu"),
          f"the serving child ran on {device['platform']!r}")
    rows = out["rows"]
    check(len(rows) > 0, "the window finished no request")
    out["e2e"]["ttft_p95_ms"] = percentile([x["ttft_ms"] for x in rows], 95)
    out["config"], out["traffic"] = spec["config"], spec["traffic"]
    if r.trace:
        out["trace"] = r.child_json(
            [harness.PY, "-m", "benchmark.trace_reduce", r.path("trace"),
             r.path("trace.json")], r.path("trace_reduce.log"),
            r.path("trace.json"), 300, JAX_PLATFORMS="cpu")
        device["busy_s"] = out["trace"]["busy_s"]
        device["window_s"] = out["trace"]["window_s"]
    for c in out["checks"]:
        c["ok"] = bool(c["value"] <= c["limit"])
    out["correct"] = all(c["ok"] for c in out["checks"]) \
        and out["failed"] == 0
    out["notes"][0]["ttft_p50_ms"] = statistics.median(
        x["ttft_ms"] for x in rows)
    if spec["control"]:
        out["notes"][0]["control"] = spec["control"]
    return out
