"""Driver ``serve_gateway_ssm``: ``serve_gateway`` for a STATE-SPACE /
LATENT-EXPERT configuration (``model_type`` ``nemotron_h``; ``benchmark/
serve_child_ssm.py``, ``README.ssm.md``). The program's ``Gateway`` with one
engine replica in a child that holds the chip; requests go through
``Gateway.submit``. The spec, the percentile and the result's form are
``serve_gateway``'s; the run itself is ``serve_gateway_hybrid``'s with this
family's child and rehearsal.

The rehearsal (``--rehearse``, CPU) runs a tiny configuration of the same
kinds, written here as the configuration file it would be: the stack
``MEM*EME``, 4 state heads of 16 with a state of 8 in 2 groups, scan chunks
of 8, 4 of 8 experts held from the 3rd on (3 a token, a latent of 16), half
the vocabulary, float32. ``CONTROL`` in the environment of a rehearsal or of
a builder's run (``benchmark.run`` takes no such option) goes into the spec
as ``control``; a list ``CONTROL=sound,<fault>,...`` decides `correct` by the
program itself and notes every fault's numbers.
"""

from __future__ import annotations

import json
import os
import statistics

from benchmark import harness
from benchmark.drivers.serve_gateway import build_spec, percentile
from benchmark.harness import check

REHEARSAL_CONFIG = {
    "model_type": "nemotron_h", "mamba_hidden_act": "silu",
    "mlp_hidden_act": "relu2", "attention_bias": False,
    "mamba_proj_bias": False, "mlp_bias": False, "use_bias": False,
    "use_conv_bias": True, "tie_word_embeddings": False, "n_group": 1,
    "topk_group": 1, "sliding_window": None, "num_nextn_predict_layers": 0,
    "hidden_size": 64, "expand": 1, "num_hidden_layers": 7,
    "hybrid_override_pattern": "MEM*EME", "mamba_num_heads": 4,
    "mamba_head_dim": 16, "ssm_state_size": 8, "n_groups": 2,
    "conv_kernel": 4, "chunk_size": 8, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 24,
    "max_position_embeddings": 256, "rope_theta": 10000.0,
    "layer_norm_epsilon": 1e-5, "n_routed_experts": 4,
    "num_experts_per_tok": 3, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "moe_latent_size": 16,
    "moe_intermediate_size": 24, "moe_shared_expert_intermediate_size": 48,
    "n_shared_experts": 1, "vocab_size": 128, "time_step_min": 0.001,
    "time_step_max": 0.1, "time_step_floor": 0.0001,
    "reduced": ["n_routed_experts", "vocab_size"],
    "published": {"n_routed_experts": 8, "vocab_size": 256},
    "deployment": {"chips_per_layer": 2, "expert_first": 2,
                   "dense_layers_held": 4},
    "assumed": {"torch_dtype": "float32"},
    "reference": "nemotron_h", "program_model": "tiny-nemotron-h",
}
# prompts span chunks of 24 (no multiple of the scan chunk's 8 behind a
# pad tail), so that window and state cross boundaries
REHEARSAL_SERVING = {"slots": 4, "max_len": 192, "prefill_len": 24,
                     "decode_block": 8, "prefix_cache_entries": 2,
                     "kv_pages": 0, "admission_deadline_s": 120.0}
REHEARSAL_LENGTHS = {"prompt_tokens": {"dist": "lognormal", "median": 60,
                                       "sigma": 0.4, "min": 30, "max": 120},
                     "output_tokens": {"dist": "lognormal", "median": 16,
                                       "sigma": 0.5, "min": 6, "max": 36},
                     "ramp_s": 4}
# float32 on both sides
REHEARSAL_LIMITS = {"decode_logit_gap": 1e-3, "decode_logit_gap_mean": 1e-4,
                    "prefill_logit_gap": 1e-3, "tail_logit_gap_3rd": 1e-3,
                    "tail_logit_gap_median": 1e-3, "tail_positions": 6,
                    "prompt_positions": 2, "boundary_positions": 3}


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
    cmd = [harness.PY, "-m", "benchmark.serve_child_ssm", "--spec",
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
