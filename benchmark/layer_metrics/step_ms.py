"""Trainer loop: median milliseconds per step over the sync-to-sync
intervals of the window in which no snapshot was being written.
Source: goodput log (host clock at sync points) and the snapshot lines."""

from benchmark import goodput_reduce as gr


def clean_step_s(run: dict):
    if run.get("kill_t") is not None or "window" not in run:
        return None
    incs = gr.incarnations(run["goodput"])
    steps = incs[0]["steps"]
    syncs = gr.sync_points(steps, run["log_interval"])
    return gr.clean_step_seconds(
        steps, syncs, run["log_interval"], gr.snapshots(run["log_text"]),
        *run["window"])


def read(run: dict):
    s = clean_step_s(run)
    return None if s is None else 1e3 * s
