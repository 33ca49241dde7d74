"""Kernels: the prefill chunk's share of its roofline for the state-space /
latent-expert family. The least time the chip could take for the chunks SEEN
IN THE TRACE (``counts/ssm_moe.py``: each chunk's ``tokens`` behind the
``context`` its prompt already had, a routed expert once for each layer in
which a real token reached it (``experts_hit``), ``expert_tokens``
assignments through one expert each, the scan as the recurrence, state and
window read and written once) over the device's busy time inside their
``prefill_chunk`` spans. Nothing to read where the program writes no such
fields. In %."""

from benchmark import span_reduce
from benchmark.counts import peaks, ssm_moe


def read(run: dict):
    if run["device"]["platform"] != "tpu":
        return None
    chunks = [e for e in span_reduce.events_of(run, "prefill_chunk")
              if e.get("device_busy_s") and e["fields"].get("tokens")
              and "ssm_row_steps" in e["fields"]]
    if not chunks:
        return None
    peak = peaks.peaks(run["device"]["kind"])
    least = sum(ssm_moe.least_seconds(ssm_moe.prefill_chunk(
        run["config"], f["tokens"], f["context"], f["experts_hit"],
        f["expert_tokens"]), peak)
        for f in (e["fields"] for e in chunks))
    return 100.0 * least / sum(e["device_busy_s"] for e in chunks)
