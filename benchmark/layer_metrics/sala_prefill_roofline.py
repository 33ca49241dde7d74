"""Kernels: the prefill chunk's share of its roofline for the hybrid family.
The least time the chip could take for the chunks SEEN IN THE TRACE
(``counts/sala.py``: each chunk's ``tokens`` behind the ``context`` its
prompt already had, attention products over the keys the selection chose
(``sparse_keys_selected``) and no others, the lightning mixer as the
recurrence, the state read and written once) over the device's busy time
inside their ``prefill_chunk`` spans. Nothing to read where the program
writes no such fields. In %."""

from benchmark import span_reduce
from benchmark.counts import peaks, sala


def read(run: dict):
    if run["device"]["platform"] != "tpu":
        return None
    chunks = [e for e in span_reduce.events_of(run, "prefill_chunk")
              if e.get("device_busy_s") and e["fields"].get("tokens")
              and "sparse_keys_selected" in e["fields"]]
    if not chunks:
        return None
    peak = peaks.peaks(run["device"]["kind"])
    least = sum(sala.least_seconds(sala.prefill_chunk(
        run["config"], f["tokens"], f["context"],
        f["sparse_keys_selected"]), peak)
        for f in (e["fields"] for e in chunks))
    return 100.0 * least / sum(e["device_busy_s"] for e in chunks)
