"""Expert layer: how uneven the routing is over the experts held: the most
assignments any one held expert of any layer took in a decode block
(``expert_load_max``) against the mean over all held experts of all expert
layers (``expert_tokens`` over their number); the median over the traced
``decode_block`` spans. 1.0 is even. Nothing to read where the program
writes no such fields."""

import statistics

from benchmark import span_reduce


def read(run: dict):
    cfg = run["config"]
    if "deployment" not in cfg:
        return None
    held = ((cfg["num_hidden_layers"]
             - cfg["deployment"]["dense_layers_held"])
            * cfg["n_routed_experts"])
    ratios = [f["expert_load_max"] * held / f["expert_tokens"]
              for f in (e["fields"] for e in
                        span_reduce.events_of(run, "decode_block"))
              if f.get("expert_tokens")]
    return statistics.median(ratios) if ratios else None
