"""Serving engine: median milliseconds of a prefill chunk's span during which
the device holds nothing of the chunk: building its arguments, the call of
the chunk program until it returns, and what follows the wait (the counters'
fetch, the span's own write). What dispatching the next chunk ahead can hide.
Source: ``build_s + dispatch_s + after_s`` of the ``prefill_chunk`` spans in
the serving child's capture. Nothing to read where the program writes no such
fields."""

from benchmark import span_reduce

PHASES = ("build_s", "dispatch_s", "after_s")


def exposed_s(event: dict):
    fields = event["fields"]
    if not all(p in fields for p in PHASES):
        return None
    return sum(fields[p] for p in PHASES)


def read(run: dict):
    s = span_reduce.median_of(run, "prefill_chunk", exposed_s)
    return None if s is None else 1e3 * s
