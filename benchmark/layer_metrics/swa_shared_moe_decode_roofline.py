"""Kernels: the decode step's share of its roofline for the windowed-and-full
family with a shared expert and a leading dense layer. The least time the
chip could take for the decode blocks SEEN IN THE TRACE
(``counts/swa_shared_moe.py``: weights outside the routed experts once a
step, the dense layer's FFN and the shared expert among them; a routed expert
once for each layer and step in which a real token reached it
(``experts_hit``); the full layer's rows up to each live row's position
(``context_tokens``); a windowed layer's up to ``min(position, window)``
(``window_keys``)) over the device's busy time inside their ``decode_block``
spans. Nothing to read where the program writes no such fields, or for a
configuration file that is not this family's. In %."""

from benchmark import span_reduce
from benchmark.counts import peaks, swa_shared_moe


def read(run: dict):
    if (run["device"]["platform"] != "tpu"
            or "mlp_layer_types" not in run["config"]):
        return None
    blocks = [e for e in span_reduce.events_of(run, "decode_block")
              if e.get("device_busy_s") and e["fields"].get("n_steps")
              and "window_keys" in e["fields"]
              and "experts_hit" in e["fields"]]
    if not blocks:
        return None
    peak = peaks.peaks(run["device"]["kind"])
    least = sum(swa_shared_moe.least_seconds(swa_shared_moe.decode_block(
        run["config"], f["n_steps"], f["row_steps"], f["experts_hit"],
        f["expert_tokens"], f["context_tokens"], f["window_keys"],
        f["ring_wrapped_row_steps"]), peak)
        for f in (e["fields"] for e in blocks))
    return 100.0 * least / sum(e["device_busy_s"] for e in blocks)
