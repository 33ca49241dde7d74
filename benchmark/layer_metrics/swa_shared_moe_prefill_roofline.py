"""Kernels: the prefill chunk's share of its roofline for the
windowed-and-full family with a shared expert and a leading dense layer. The
least time the chip could take for the chunks SEEN IN THE TRACE
(``counts/swa_shared_moe.py``: each chunk's real tokens (``row_steps``) behind
the ``context`` its prompt already had, a windowed layer attending no further
back than its window, ``expert_tokens`` and ``experts_hit`` from the span)
over the device's busy time inside their ``prefill_chunk`` spans. Nothing to
read where the program writes no such fields, or for a configuration file
that is not this family's. In %."""

from benchmark import span_reduce
from benchmark.counts import peaks, swa_shared_moe


def read(run: dict):
    if (run["device"]["platform"] != "tpu"
            or "mlp_layer_types" not in run["config"]):
        return None
    chunks = [e for e in span_reduce.events_of(run, "prefill_chunk")
              if e.get("device_busy_s") and e["fields"].get("row_steps")
              and "window_keys" in e["fields"]
              and "experts_hit" in e["fields"]]
    if not chunks:
        return None
    peak = peaks.peaks(run["device"]["kind"])
    least = sum(swa_shared_moe.least_seconds(swa_shared_moe.prefill_chunk(
        run["config"], f["row_steps"], f["context"], f["experts_hit"],
        f["expert_tokens"]), peak) for f in (e["fields"] for e in chunks))
    return 100.0 * least / sum(e["device_busy_s"] for e in chunks)
