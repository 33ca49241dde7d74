"""Expert layer: token-expert assignments that landed on held experts in one
decode step, summed over the expert layers: the ``expert_tokens`` field of the
traced ``decode_block`` spans over their ``n_steps``, the mean over the
blocks. Every row of the slot batch routes, idle slots too: it is what the
device ran. Nothing to read where the program writes no such field."""

from benchmark import span_reduce


def read(run: dict):
    blocks = [e["fields"] for e in span_reduce.events_of(run, "decode_block")
              if e["fields"].get("n_steps") and "expert_tokens" in e["fields"]]
    if not blocks:
        return None
    return (sum(f["expert_tokens"] for f in blocks)
            / sum(f["n_steps"] for f in blocks))
