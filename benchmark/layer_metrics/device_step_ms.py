"""Step program: median milliseconds the device was busy per training step:
from one ``train_step`` annotation's start to the next one's, the union of
the device's operation intervals. Source: the trainer's profile bundle, host
and device planes on one clock (``benchmark/span_reduce.py``)."""

import statistics

from benchmark import span_reduce


def read(run: dict):
    steps = (span_reduce.for_run(run) or {}).get("steps") or []
    busy = [s["device_busy_s"] for s in steps if s["device_busy_s"] > 0]
    return 1e3 * statistics.median(busy) if busy else None
