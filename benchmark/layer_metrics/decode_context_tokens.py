"""Serving engine: mean position of the rows a decode call advanced: the
model's counter ``context_tokens`` (the sum of the live rows' positions over
a block's steps) over the live row-steps (``slots x n_steps -
frozen_row_steps``) of the traced ``decode_block`` spans. What a decode step
reads of a row grows with it for a dense cache and stays flat for a selected
or a state one. Nothing to read where the program writes no such counter."""

from benchmark import span_reduce


def read(run: dict):
    fields = [e["fields"] for e in span_reduce.events_of(run, "decode_block")
              if e["fields"].get("n_steps") and "context_tokens" in e["fields"]]
    live = sum(f["slots"] * f["n_steps"] - f.get("frozen_row_steps", 0)
               for f in fields)
    if not live:
        return None
    return sum(f["context_tokens"] for f in fields) / live
