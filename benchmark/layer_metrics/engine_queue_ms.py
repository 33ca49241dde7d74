"""Serving engine: median milliseconds a request waited INSIDE the engine,
from ``submit`` to the admission pipeline taking it up: what ``queue_ms.closed``
(the gateway's queue, which ends at the hand-over to the engine) leaves out.
Source: the ``queue_wait_s`` field of the ``kv_install`` spans in the serving
child's capture; a slice may hold few installs, so their count goes on a
note line. Nothing to read where the program writes no such field."""

import statistics

from benchmark import span_reduce


def read(run: dict):
    waits = [e["fields"]["queue_wait_s"]
             for e in span_reduce.events_of(run, "kv_install")
             if "queue_wait_s" in e["fields"]]
    if not waits:
        return None
    run.setdefault("notes", []).append(
        {"engine_queue_ms_installs": len(waits)})
    return 1e3 * statistics.median(waits)
