"""Windowed and full layers: what a windowed layer reads of a row against
what a full one does, ``sum(window_keys) / sum(context_tokens)`` over the
traced ``decode_block`` spans (the model's counters: the live rows'
``min(position, window)`` and positions, summed over a block's steps). 1.0
says the traffic never passed the window and the cell shows nothing of it.
Nothing to read where the program writes no such counters."""

from benchmark import span_reduce


def read(run: dict):
    fields = [e["fields"] for e in span_reduce.events_of(run, "decode_block")
              if "window_keys" in e["fields"]]
    context = sum(f["context_tokens"] for f in fields)
    if not context:
        return None
    return sum(f["window_keys"] for f in fields) / context
