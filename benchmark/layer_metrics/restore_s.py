"""Checkpoint: seconds the new incarnation took to restore its state
(from shared memory, after a kill). Source: journal ``ckpt_restore``."""

from benchmark import harness


def read(run: dict):
    if run.get("kill_t") is None:
        return None
    durs = [e["dur"] for e in harness.journal_events(
        run["files"]["journal"], ("ckpt_restore",))
        if e["t"] > run["kill_t"]]
    return max(durs) if durs else None
