"""Windowed and full layers: the share of live decode row-steps whose row had
passed the window (its rings had wrapped), ``sum(ring_wrapped_row_steps) /
sum(row_steps)`` over the traced ``decode_block`` spans (the model's
counters). Nothing to read where the program writes no such counters."""

from benchmark import span_reduce


def read(run: dict):
    fields = [e["fields"] for e in span_reduce.events_of(run, "decode_block")
              if "ring_wrapped_row_steps" in e["fields"]]
    live = sum(f["row_steps"] for f in fields)
    if not live:
        return None
    return sum(f["ring_wrapped_row_steps"] for f in fields) / live
