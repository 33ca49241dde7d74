"""Step program: model FLOP/s utilization. Model FLOPs per token (forward
plus backward from the shapes, recompute not counted, ``counts/flops.py``)
times the window's tokens per second, over the chips' bf16 peak. In %."""

from benchmark.counts import flops, peaks


def read(run: dict):
    rate = run.get("e2e", {}).get("train_tokens_per_s")
    if rate is None or run["device"]["platform"] != "tpu":
        return None
    peak = peaks.peaks(run["device"]["kind"])["bf16_flops"]
    per_token = flops.train_flops_per_token(run["config"], run["job"]["seq"])
    return 100.0 * per_token * rate / (run["device"]["count"] * peak)
