"""Kernels: the decode step's share of its roofline for the windowed-and-full,
routed-expert family. The least time the chip could take for the decode
blocks SEEN IN THE TRACE (``counts/swa_moe.py``: weights outside the routed
experts once a step, a routed expert once for each layer and step in which a
real token reached it (``experts_hit``), a full layer's rows up to each live
row's position (``context_tokens``), a windowed layer's up to ``min(position,
window)`` (``window_keys``)) over the device's busy time inside their
``decode_block`` spans. Nothing to read where the program writes no such
fields. In %."""

from benchmark import span_reduce
from benchmark.counts import peaks, swa_moe


def read(run: dict):
    if run["device"]["platform"] != "tpu":
        return None
    blocks = [e for e in span_reduce.events_of(run, "decode_block")
              if e.get("device_busy_s") and e["fields"].get("n_steps")
              and "window_keys" in e["fields"]]
    if not blocks:
        return None
    peak = peaks.peaks(run["device"]["kind"])
    least = sum(swa_moe.least_seconds(swa_moe.decode_block(
        run["config"], f["n_steps"], f["row_steps"], f["experts_hit"],
        f["expert_tokens"], f["context_tokens"], f["window_keys"],
        f["ring_wrapped_row_steps"]), peak)
        for f in (e["fields"] for e in blocks))
    return 100.0 * least / sum(e["device_busy_s"] for e in blocks)
