"""Serving engine: median milliseconds of a request's chunked prefill.
Source: ``GatewayResult.prefill_s``."""

import statistics


def read(run: dict):
    rows = run.get("rows")
    return statistics.median(r["prefill_ms"] for r in rows) if rows else None
