"""Serving engine: median milliseconds of device time one decode step takes:
the device's busy time inside a ``decode_block`` span over the block's
steps. Source: the serving child's capture (``benchmark/span_reduce.py``)."""

from benchmark import span_reduce


def per_step_s(event: dict):
    busy, steps = event.get("device_busy_s"), event["fields"].get("n_steps")
    return busy / steps if busy and steps else None


def read(run: dict):
    s = span_reduce.median_of(run, "decode_block", per_step_s)
    return None if s is None else 1e3 * s
