"""Kernels: the decode step's share of its roofline for the latent-attention,
routed-expert family. The least time the chip could take for the decode
blocks SEEN IN THE TRACE (``counts/mla_moe.py``: the held weights outside the
routed experts once a step, a routed expert once for each layer and step in
which a token reached it (span field ``experts_hit``), the live latent rows,
2 FLOPs a weight a token with ``expert_tokens`` for the routed part, the
absorbed attention products) over the device's busy time inside their
``decode_block`` spans. A slot's live rows are taken as the mean over the
window's requests of prompt plus half the answer. Nothing to read where the
program writes no such fields. In %."""

from benchmark import span_reduce
from benchmark.counts import mla_moe, peaks


def read(run: dict):
    if run["device"]["platform"] != "tpu" or not run.get("rows"):
        return None
    blocks = [e for e in span_reduce.events_of(run, "decode_block")
              if e.get("device_busy_s") and e["fields"].get("n_steps")
              and "experts_hit" in e["fields"]]
    if not blocks:
        return None
    rows = run["rows"]
    context = sum(r["prompt_tokens"] + r["output_tokens"] / 2
                  for r in rows) / len(rows)
    peak = peaks.peaks(run["device"]["kind"])
    least = sum(mla_moe.least_seconds(mla_moe.decode_block(
        run["config"], e["fields"]["slots"], e["fields"]["n_steps"], context,
        e["fields"]["expert_tokens"], e["fields"]["experts_hit"]), peak)
        for e in blocks)
    return 100.0 * least / sum(e["device_busy_s"] for e in blocks)
