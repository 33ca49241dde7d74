"""Checkpoint: what one async snapshot costs the training loop. Per whole
snapshot cycle of the window, the cycle's time between its two sync points
less as many clean steps (``step_ms``); the median over the cycles, in ms.
Source: goodput log."""

import statistics

from benchmark import goodput_reduce as gr
from benchmark import harness


def read(run: dict):
    clean = harness.load_named("layer_metrics", "step_ms").clean_step_s(run)
    if clean is None:
        return None
    steps = gr.incarnations(run["goodput"])[0]["steps"]
    syncs = gr.sync_points(steps, run["log_interval"])
    cycle, first = run["cycle"], run["first_sync"]
    stalls = []
    for k in range(run["steps"] // cycle):
        a, b = first + k * cycle, first + (k + 1) * cycle
        stalls.append(syncs[b] - syncs[a] - cycle * clean)
    return 1e3 * statistics.median(stalls) if stalls else None
