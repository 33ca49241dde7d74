"""Kernels: the decode step's share of its roofline. The least time the chip
could take for the decode steps SEEN IN THE TRACE (``counts/decode.py``: the
weights once in bf16, the live cache rows of the slots that decoded, 2 x
weights FLOPs a token; the larger of operations over peak FLOP/s and bytes
over peak bytes/s) over the device's busy time inside their ``decode_block``
spans. A slot's live rows are taken as the mean over the window's requests of
prompt plus half the answer. In %."""

from benchmark import span_reduce
from benchmark.counts import decode, peaks


def read(run: dict):
    if run["device"]["platform"] != "tpu" or not run.get("rows"):
        return None
    blocks = [e for e in span_reduce.events_of(run, "decode_block")
              if e.get("device_busy_s") and e["fields"].get("n_steps")]
    if not blocks:
        return None
    rows = run["rows"]
    context = sum(r["prompt_tokens"] + r["output_tokens"] / 2
                  for r in rows) / len(rows)
    peak = peaks.peaks(run["device"]["kind"])
    least = sum(e["fields"]["n_steps"] * decode.least_seconds(
        decode.decode_step(run["config"], e["fields"]["slots"], context),
        peak) for e in blocks)
    return 100.0 * least / sum(e["device_busy_s"] for e in blocks)
