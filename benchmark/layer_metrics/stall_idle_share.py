"""Serving engine: the share of the traced slice in which the host waited for
the device far longer than the device worked: over the ``prefill_chunk`` and
``decode_block`` spans, the sum of ``wait_s`` less the device's busy time
inside the span where that exceeds 50 ms, over the slice. Such a stall is the
machine's (every thread of the process stops), not the program's: what to take
from one line's idle share before comparing it with another's. Source: the
spans' ``wait_s`` and the device plane of the serving child's capture.
Nothing to read where the program writes no such field or the capture holds
no device plane."""

from benchmark import span_reduce

STALL_S = 0.05


def read(run: dict):
    window = (span_reduce.for_run(run) or {}).get("window_s")
    events = [e for name in ("prefill_chunk", "decode_block")
              for e in span_reduce.events_of(run, name)
              if "wait_s" in e["fields"] and "device_busy_s" in e]
    if not window or not events:
        return None
    excess = (e["fields"]["wait_s"] - e["device_busy_s"] for e in events)
    return 100.0 * sum(x for x in excess if x > STALL_S) / window
