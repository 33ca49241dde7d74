"""Kernels: the prefill chunk's share of its roofline for the grouped-query,
routed-expert, block-diffusion family. The least time the chip could take
for the chunks SEEN IN THE TRACE (``counts/gqa_moe.py``: each chunk's
``tokens`` behind the ``context`` its prompt already had, block-causal
attention, ``expert_tokens`` and ``experts_hit`` from the span) over the
device's busy time inside their ``prefill_chunk`` spans. Nothing to read
where the program writes no such fields, or the configuration is not of this
family. In %."""

from benchmark import span_reduce
from benchmark.counts import gqa_moe, peaks


def read(run: dict):
    if (run["device"]["platform"] != "tpu"
            or "block_length" not in run["config"].get("assumed", {})):
        return None
    chunks = [e for e in span_reduce.events_of(run, "prefill_chunk")
              if e.get("device_busy_s") and e["fields"].get("tokens")
              and "experts_hit" in e["fields"]]
    if not chunks:
        return None
    peak = peaks.peaks(run["device"]["kind"])
    least = sum(gqa_moe.least_seconds(gqa_moe.prefill_chunk(
        run["config"], e["fields"]["tokens"], e["fields"]["context"],
        e["fields"]["expert_tokens"], e["fields"]["experts_hit"]), peak)
        for e in chunks)
    return 100.0 * least / sum(e["device_busy_s"] for e in chunks)
