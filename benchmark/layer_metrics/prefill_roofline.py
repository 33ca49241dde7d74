"""Kernels: the prefill chunk's share of its roofline. The least time the
chip could take for the chunks SEEN IN THE TRACE (``counts/decode.py``: each
chunk's ``tokens`` behind the ``context`` its prompt already had) over the
device's busy time inside their ``prefill_chunk`` spans. In %."""

from benchmark import span_reduce
from benchmark.counts import decode, peaks


def read(run: dict):
    if run["device"]["platform"] != "tpu":
        return None
    chunks = [e for e in span_reduce.events_of(run, "prefill_chunk")
              if e.get("device_busy_s") and e["fields"].get("tokens")]
    if not chunks:
        return None
    peak = peaks.peaks(run["device"]["kind"])
    least = sum(decode.least_seconds(decode.prefill_chunk(
        run["config"], e["fields"]["tokens"], e["fields"]["context"]),
        peak) for e in chunks)
    return 100.0 * least / sum(e["device_busy_s"] for e in chunks)
