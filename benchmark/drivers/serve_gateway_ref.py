"""Driver ``serve_gateway_ref``: ``serve_gateway`` for a configuration that
names its reference (the configuration file's ``reference`` and
``program_model``, ``benchmark/serve_child_ref.py``). The program's
``Gateway`` with one engine replica in a child that holds the chip;
requests go through ``Gateway.submit``. The spec, the percentile and the
result's form are ``serve_gateway``'s; the child builds the program from
the file's published keys and compares with the reference the file names.

The rehearsal (``--rehearse``, CPU) runs a tiny configuration of the same
kinds, written here as the configuration file it would be: 1 dense + 2
expert layers, 4 of 16 experts held (from the 4th on), half the vocabulary,
float32. ``CONTROL`` in the environment of a rehearsal or of a builder's
run (``benchmark.run`` takes no such option) goes into the spec as
``control`` and puts a faulted reference in the program's place
(``serve_child_ref``): such a run must come out ``correct: false``.
"""

from __future__ import annotations

import json
import os
import statistics

from benchmark import harness
from benchmark.drivers.serve_gateway import (
    REHEARSAL_LENGTHS,
    REHEARSAL_SERVING,
    build_spec,
    percentile,
)
from benchmark.harness import check

REHEARSAL_CONFIG = {
    "model_type": "pangu_ultra_moe", "hidden_act": "silu",
    "attention_bias": False, "tie_word_embeddings": False,
    "sandwich_norm": True, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 4, "intermediate_size": 160,
    "max_position_embeddings": 256, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-5, "q_lora_rank": 32, "kv_lora_rank": 24,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "num_experts_per_tok": 4, "moe_intermediate_size": 32,
    "n_shared_experts": 1, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "first_k_dense_replace": 1,
    "num_hidden_layers": 3, "n_routed_experts": 4, "vocab_size": 128,
    "num_nextn_predict_layers": 0,
    "reduced": ["n_routed_experts", "vocab_size"],
    "published": {"n_routed_experts": 16, "vocab_size": 256},
    "deployment": {"chips_per_layer": 4, "expert_first": 4,
                   "dense_layers_held": 1},
    "assumed": {"torch_dtype": "float32"},
    "reference": "pangu_ultra_moe", "program_model": "tiny-latent-moe",
}
# float32 on both sides, choices decided by less than 1e-4 left out
REHEARSAL_LIMITS = {"decode_logit_gap": 1e-3, "decode_logit_gap_3rd": 1e-3,
                    "prefill_logit_gap": 1e-3,
                    "tail_logit_gap_3rd": 1e-3, "tail_positions": 6,
                    "prompt_positions": 2,
                    "choice_margin": 1e-4, "undecided_share": 0.05}


def spec_for(r: harness.Run) -> dict:
    """``serve_gateway.build_spec``, with this driver's rehearsal."""
    spec = build_spec(r)
    spec["control"] = os.environ.get("CONTROL", "")
    if r.rehearse:
        spec["config"] = REHEARSAL_CONFIG
        spec["serving"] = REHEARSAL_SERVING
        spec["traffic"] = {**r.workload["traffic_mix"], **REHEARSAL_LENGTHS}
        spec["limits"] = REHEARSAL_LIMITS
    return spec


def run(r: harness.Run) -> dict:
    spec = spec_for(r)
    with open(r.path("spec.json"), "w") as f:
        json.dump(spec, f)
    cmd = [harness.PY, "-m", "benchmark.serve_child_ref", "--spec",
           r.path("spec.json"), "--out", r.path("serve.json")]
    out = r.child_json(cmd, r.path("serve.log"), r.path("serve.json"), 1500)
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
