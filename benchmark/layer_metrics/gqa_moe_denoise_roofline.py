"""Kernels: the block-diffusion decode call's share of its roofline for the
grouped-query, routed-expert family. The least time the chip could take for
the decode calls SEEN IN THE TRACE (``counts/gqa_moe.py``: the weights outside
the routed experts once a pass, the head once a denoising pass, a routed
expert once for each layer and pass in which a row reached it (span field
``experts_hit``), the live key/value rows, 2 FLOPs a weight a row with
``expert_tokens`` for the routed part) over the device's busy time inside
their ``decode_block`` spans. A slot's live rows are taken as the mean over
the window's requests of prompt plus half the answer. Nothing to read where
the program writes no ``denoise_passes`` field (a program without block
diffusion). In %."""

from benchmark import span_reduce
from benchmark.counts import gqa_moe, peaks


def read(run: dict):
    if run["device"]["platform"] != "tpu" or not run.get("rows"):
        return None
    calls = [e for e in span_reduce.events_of(run, "decode_block")
             if e.get("device_busy_s") and e["fields"].get("denoise_passes")
             and "experts_hit" in e["fields"]]
    if not calls:
        return None
    rows = run["rows"]
    context = sum(r["prompt_tokens"] + r["output_tokens"] / 2
                  for r in rows) / len(rows)
    peak = peaks.peaks(run["device"]["kind"])
    least = sum(gqa_moe.least_seconds(gqa_moe.denoise_call(
        run["config"], e["fields"]["slots"], e["fields"]["denoise_passes"],
        e["fields"]["store_passes"], context, e["fields"]["expert_tokens"],
        e["fields"]["experts_hit"]), peak) for e in calls)
    return 100.0 * least / sum(e["device_busy_s"] for e in calls)
