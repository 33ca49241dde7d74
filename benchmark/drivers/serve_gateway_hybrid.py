"""Driver ``serve_gateway_hybrid``: ``serve_gateway`` for a HYBRID-CACHE
configuration (block-sparse attention over a compressed-key cache beside
linear-attention layers whose cache is a state; ``benchmark/
serve_child_hybrid.py``, ``README.hybrid.md``). The program's ``Gateway``
with one engine replica in a child that holds the chip; requests go through
``Gateway.submit``. The spec, the percentile and the result's form are
``serve_gateway``'s.

The rehearsal (``--rehearse``, CPU) runs a tiny configuration of the same
kinds, written here as the configuration file it would be: a stack
``[sparse, lightning, lightning, sparse]``, 4 query heads of 16 on 2
key/value heads, sparse sizes small enough that the selection binds inside
100 tokens, float32. ``CONTROL`` in the environment of a rehearsal or of a
builder's run (``benchmark.run`` takes no such option) goes into the spec as
``control``; a list ``CONTROL=sound,<fault>,...`` decides `correct` by the
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
    "model_type": "minicpm_sala", "hidden_act": "silu",
    "attention_bias": False, "tie_word_embeddings": False,
    "attn_use_rope": False, "lightning_use_rope": True,
    "lightning_scale": "1/sqrt(d)", "qk_norm": True,
    "use_output_gate": True, "use_output_norm": True,
    "attn_use_output_gate": True,
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "lightning_nh": 4, "lightning_nkv": 4,
    "lightning_head_dim": 16, "intermediate_size": 160, "vocab_size": 256,
    "max_position_embeddings": 256, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "num_hidden_layers": 4,
    "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn",
                    "minicpm4"],
    "scale_emb": 12, "scale_depth": 1.4, "dim_model_base": 16,
    "mup_denominator": 32, "reduced": [],
    "assumed": {"torch_dtype": "float32",
                "sparse_config": {"kernel_size": 8, "kernel_stride": 4,
                                  "block_size": 16, "topk": 4,
                                  "init_blocks": 1, "window_size": 32,
                                  "dense_len": 64}},
    "reference": "minicpm_sala", "program_model": "tiny-sala",
}
# long enough that the selection binds (dense_len 64) and a prompt spans
# chunks that cut compression windows
REHEARSAL_SERVING = {"slots": 4, "max_len": 192, "prefill_len": 24,
                     "decode_block": 8, "prefix_cache_entries": 2,
                     "kv_pages": 0, "admission_deadline_s": 120.0}
REHEARSAL_LENGTHS = {"prompt_tokens": {"dist": "lognormal", "median": 100,
                                       "sigma": 0.3, "min": 70, "max": 150},
                     "output_tokens": {"dist": "lognormal", "median": 16,
                                       "sigma": 0.5, "min": 6, "max": 36},
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
    cmd = [harness.PY, "-m", "benchmark.serve_child_hybrid", "--spec",
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
