"""Driver ``serve_gateway``: the program's ``Gateway`` with one engine
replica, in a child that holds the chip (``benchmark/serve_child.py``);
requests go through ``Gateway.submit``, no HTTP. Cells differ only in data:
``traffic.arrivals.kind`` is ``closed`` (clients that wait for a reply) or an
open-loop arrival process at ``rate_per_s``.
"""

from __future__ import annotations

import json
import statistics

from benchmark import harness
from benchmark.harness import check

REHEARSAL_SERVING = {"slots": 4, "max_len": 128, "prefill_len": 16,
                     "decode_block": 8, "prefix_cache_entries": 2,
                     "kv_pages": 0}
REHEARSAL_LENGTHS = {"prompt_tokens": {"dist": "lognormal", "median": 24,
                                       "sigma": 0.8, "min": 4, "max": 90},
                     "output_tokens": {"dist": "lognormal", "median": 12,
                                       "sigma": 0.6, "min": 4, "max": 32}}


def percentile(values: list[float], q: float) -> float:
    """The q-th percentile (0-100), nearest rank."""
    ordered = sorted(values)
    return ordered[min(len(ordered) - 1, int(q / 100.0 * len(ordered)))]


def build_spec(r: harness.Run) -> dict:
    """What the serving child is told: sizes, traffic, seed, limits."""
    traffic = dict(r.workload["traffic_mix"])
    serving, config = r.config["serving"], r.config
    if r.rehearse:
        from benchmark.program import tiny_config

        serving, config = REHEARSAL_SERVING, tiny_config()
        traffic.update(REHEARSAL_LENGTHS)
    spec = {
        "seed": r.seed, "seconds": r.seconds, "trace": r.trace,
        "rehearse": r.rehearse, "chips": r.cell["chips"],
        "config": config, "serving": serving, "traffic": traffic,
        "limits": r.workload["limits"], "sample": r.workload["sample"],
        "trace_dir": r.path("trace"), "t_start": r.t_start,
        "trace_after_s": r.workload["trace_after_s"],
        "trace_seconds": r.workload["trace_seconds"],
    }
    return spec


def run(r: harness.Run) -> dict:
    spec = build_spec(r)
    config, traffic = spec["config"], spec["traffic"]
    with open(r.path("spec.json"), "w") as f:
        json.dump(spec, f)
    out = r.child_json(
        [harness.PY, "-m", "benchmark.serve_child", "--spec",
         r.path("spec.json"), "--out", r.path("serve.json")],
        r.path("serve.log"), r.path("serve.json"), 1500)
    device = out["device"]
    check(device["platform"] == ("cpu" if r.rehearse else "tpu"),
          f"the serving child ran on {device['platform']!r}")
    rows = out["rows"]
    check(len(rows) > 0, "the window finished no request")
    out["e2e"]["ttft_p95_ms"] = percentile([x["ttft_ms"] for x in rows], 95)
    out["config"], out["traffic"] = config, traffic
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
    return out
