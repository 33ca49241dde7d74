"""Serving engine: mean number of slots in an engine step's decode call,
over the traced steps that made one (``slot_occupancy`` counts requests a
replica holds; this counts what decodes). Source: the ``decoding_slots``
field of the ``engine_step`` spans in the serving child's capture."""

from benchmark import span_reduce


def read(run: dict):
    slots = [e["fields"]["decoding_slots"]
             for e in span_reduce.events_of(run, "engine_step")
             if e["fields"].get("n_steps", 0) > 0]
    return sum(slots) / len(slots) if slots else None
