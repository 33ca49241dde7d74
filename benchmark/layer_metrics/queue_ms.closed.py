"""Gateway: median milliseconds a request waited between admission and the
engine taking it up. Source: ``GatewayResult.queue_s``. One name per cell
kind, because the cells' end-to-end metrics differ."""

import statistics


def read(run: dict):
    rows = run.get("rows")
    return statistics.median(r["queue_ms"] for r in rows) if rows else None
