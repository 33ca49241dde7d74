"""Kernels: the decode step's share of its roofline for the state-space /
latent-expert family. The least time the chip could take for the decode
blocks SEEN IN THE TRACE (``counts/ssm_moe.py``: held weights outside the
routed experts once a step, a routed expert once for each layer and step in
which a real token reached it (``experts_hit``), state and window read and
written once for each live row-step (``ssm_row_steps``), the attention
layer's rows a live row sees (``context_tokens``)) over the device's busy
time inside their ``decode_block`` spans. Nothing to read where the program
writes no such fields. In %."""

from benchmark import span_reduce
from benchmark.counts import peaks, ssm_moe


def read(run: dict):
    if run["device"]["platform"] != "tpu":
        return None
    blocks = [e for e in span_reduce.events_of(run, "decode_block")
              if e.get("device_busy_s") and e["fields"].get("n_steps")
              and "ssm_row_steps" in e["fields"]]
    if not blocks:
        return None
    peak = peaks.peaks(run["device"]["kind"])
    least = sum(ssm_moe.least_seconds(ssm_moe.decode_block(
        run["config"], f["n_steps"], f["ssm_row_steps"], f["experts_hit"],
        f["expert_tokens"], f["context_tokens"]), peak)
        for f in (e["fields"] for e in blocks))
    return 100.0 * least / sum(e["device_busy_s"] for e in blocks)
