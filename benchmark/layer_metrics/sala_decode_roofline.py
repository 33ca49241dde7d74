"""Kernels: the decode step's share of its roofline for the hybrid family
(block-sparse attention beside lightning layers). The least time the chip
could take for the decode blocks SEEN IN THE TRACE (``counts/sala.py``: the
held weights once a step, the lightning state read and written once for each
live row of a step, the key and value rows the selection chose
(``sparse_keys_selected``) and the compressed keys a live row sees
(``context_tokens``), attention products over the selected keys alone) over
the device's busy time inside their ``decode_block`` spans. Live rows are the
span's ``slots x n_steps - frozen_row_steps``. Nothing to read where the
program writes no such fields. In %."""

from benchmark import span_reduce
from benchmark.counts import peaks, sala


def read(run: dict):
    if run["device"]["platform"] != "tpu":
        return None
    blocks = [e for e in span_reduce.events_of(run, "decode_block")
              if e.get("device_busy_s") and e["fields"].get("n_steps")
              and "sparse_keys_selected" in e["fields"]]
    if not blocks:
        return None
    peak = peaks.peaks(run["device"]["kind"])
    least = sum(sala.least_seconds(sala.decode_block(
        run["config"], f["slots"], f["n_steps"],
        f.get("frozen_row_steps", 0), f["context_tokens"],
        f["sparse_keys_selected"]), peak)
        for f in (e["fields"] for e in blocks))
    return 100.0 * least / sum(e["device_busy_s"] for e in blocks)
