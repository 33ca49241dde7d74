"""Serving engine: median milliseconds of one prefill chunk, from the call
of the chunk program to its result on the host's clock. Source:
``prefill_chunk`` spans in the serving child's capture."""

from benchmark import span_reduce


def read(run: dict):
    s = span_reduce.median_of(run, "prefill_chunk", lambda e: e["dur_s"])
    return None if s is None else 1e3 * s
