"""Driver ``serve_gateway_diffusion``: ``serve_gateway_ref`` for a
BLOCK-DIFFUSION configuration that names its reference
(``benchmark/README.diffusion.md``, ``benchmark/serve_child_diffusion.py``).
The program's ``Gateway`` with one engine replica in a child that holds the
chip; requests go through ``Gateway.submit``. The spec, the percentile and
the result's form are ``serve_gateway``'s; the child builds the program from
the file's published keys and replays what it served through the reference
the file names.

The rehearsal (``--rehearse``, CPU) runs a tiny configuration of the same
kinds, written here as the configuration file it would be: 3 layers of 16
experts (4 a token), 4 query heads on 2 key/value heads of 24 (NOT 64 / 4),
blocks of 4 in 4 passes, float32. ``CONTROL`` in the environment of a
rehearsal or of a builder's run (``benchmark.run`` takes no such option) goes
into the spec as ``control`` (``serve_child_diffusion``): one of the
reference's faults in the program's place, which must come out ``correct:
false``; or a builder's list ``sound,<fault>,...``, which decides `correct`
by the program itself and notes every fault's numbers on the same sample.
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
    "model_type": "sdar_moe", "hidden_act": "silu", "attention_bias": False,
    "tie_word_embeddings": False, "decoder_sparse_step": 1,
    "mlp_only_layers": [], "rope_scaling": None,
    "use_sliding_window": False, "sliding_window": None,
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 24, "intermediate_size": 96, "max_position_embeddings": 256,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "num_experts": 16,
    "num_experts_per_tok": 4, "moe_intermediate_size": 32,
    "norm_topk_prob": True, "num_hidden_layers": 3, "vocab_size": 256,
    "n_routed_experts": 16, "reduced": [], "published": {},
    "deployment": {"chips_per_layer": 1, "dense_layers_held": 0},
    "assumed": {"torch_dtype": "float32", "block_length": 4,
                "denoising_steps": 4, "remasking": "low_confidence_static",
                "mask_token_id": 255},
    "reference": "sdar_moe", "program_model": "tiny-sdar-moe",
}
REHEARSAL_SERVING = {**REHEARSAL_SERVING, "decode_block": 4}
# float32 on both sides: what is left is the order of sums
REHEARSAL_LIMITS = {"denoise_logit_gap": 1e-3, "denoise_logit_gap_mean": 1e-3,
                    "unmask_order_gap_median": 1e-3, "chunk_logit_gap": 1e-3,
                    "tail_logit_gap_median": 1e-3, "tail_positions": 3,
                    "prompt_positions": 2}


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
    cmd = [harness.PY, "-m", "benchmark.serve_child_diffusion", "--spec",
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
