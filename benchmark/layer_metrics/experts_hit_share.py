"""Expert layer: the share of the held experts a decode step reads: held
experts that took at least one real token's assignment (``experts_hit``,
summed over a block's expert layers and steps) over held experts x expert
layers x ``n_steps``, over the traced ``decode_block`` spans. Each hit is one
expert's weights read, so lower is fewer bytes a step; 1.0 is every expert
of every layer in every step. Nothing to read where the program writes no
such field or the configuration has no latent experts."""

from benchmark import span_reduce
from benchmark.counts import ssm_moe


def read(run: dict):
    if "moe_latent_size" not in run["config"]:
        return None
    fields = [e["fields"] for e in span_reduce.events_of(run, "decode_block")
              if e["fields"].get("n_steps") and "experts_hit" in e["fields"]]
    if not fields:
        return None
    s = ssm_moe.sizes(run["config"])
    return (sum(f["experts_hit"] for f in fields)
            / (s["experts_held"] * s["expert_layers"]
               * sum(f["n_steps"] for f in fields)))
