"""Checkpoint: median seconds the writer thread took to fetch one snapshot's
device copy to the host. Source: journal ``snapshot_fetch`` spans that ended
inside the window."""

import statistics

from benchmark import journal_reduce as jr


def read(run: dict):
    durs = jr.span_seconds(run, "snapshot_fetch")
    return statistics.median(durs) if durs else None
