"""Serving engine: median milliseconds of an engine step that are the host's
own: the ``engine_step`` span less its children (``prefill_chunk``,
``kv_install``, ``decode_block``, ``engine_emit``). Source: the serving
child's capture (``benchmark/span_reduce.py``)."""

from benchmark import span_reduce


def read(run: dict):
    s = span_reduce.median_of(run, "engine_step", lambda e: e["self_s"])
    return None if s is None else 1e3 * s
