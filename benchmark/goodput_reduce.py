"""From the trainer's goodput log to times the host clock can stand behind.

The log (``--goodput-log``) has one ``start`` line per incarnation and one
``step`` line per optimizer step, stamped with ``time.time()`` as the host
DISPATCHES the step; on a TPU dispatch runs ahead of the device. Only at a
logged step (every ``--log-interval``) does the trainer fetch the loss,
which waits for the device, and the very next step's line is written right
after. So that next line's stamp is the time the logged step had COMPLETED:
a sync point. Every time here is taken between sync points.
"""

from __future__ import annotations

import re
import statistics

SNAPSHOT_RE = re.compile(r"step (\d+) snapshotted to shm in ([\d.]+)s")
LOSS_RE = re.compile(r"\[trainer\] step (\d+) loss ([-\d.naninf]+)")


def incarnations(events: list[dict]) -> list[dict]:
    """The log split at its ``start`` lines: ``{"start_t", "restart",
    "steps": {step: stamp}}`` in time order."""
    out: list[dict] = []
    for ev in events:
        if ev.get("ev") == "start":
            out.append({"start_t": ev["t"], "restart": ev.get("restart", 0),
                        "steps": {}})
        elif ev.get("ev") == "step" and out:
            out[-1]["steps"][int(ev["step"])] = ev["t"]
    return out


def sync_points(steps: dict[int, float], log_interval: int) -> dict[int, float]:
    """``{logged step: time it had completed}``: the stamp of the line
    after each logged step."""
    return {s: steps[s + 1] for s in sorted(steps)
            if s % log_interval == 0 and s + 1 in steps}


def window_steps(syncs: dict[int, float], first: int,
                 t_end: float) -> tuple[int, float]:
    """All the steps from sync point ``first`` to the last sync point at or
    before ``t_end``: (steps, seconds they took)."""
    last = max((s for s, t in syncs.items() if s >= first and t <= t_end),
               default=first)
    return last - first, syncs[last] - syncs[first]


def snapshots(log_text: str) -> list[tuple[int, float]]:
    """(step, seconds the writer took) of every snapshot that landed."""
    return [(int(s), float(d)) for s, d in SNAPSHOT_RE.findall(log_text)]


def clean_step_seconds(steps: dict[int, float], syncs: dict[int, float],
                       log_interval: int, snaps: list[tuple[int, float]],
                       t_from: float, t_to: float) -> float | None:
    """Median seconds per step over the sync-to-sync intervals inside
    [t_from, t_to] during which no snapshot was being written (a write
    runs from its step's dispatch for as long as the writer reports)."""
    busy = [(steps[s], steps[s] + d) for s, d in snaps if s in steps]
    per_step = []
    for s, t1 in syncs.items():
        t0 = syncs.get(s - log_interval)
        if t0 is None or t0 < t_from or t1 > t_to:
            continue
        if any(a < t1 and b > t0 for a, b in busy):
            continue
        per_step.append((t1 - t0) / log_interval)
    return statistics.median(per_step) if per_step else None


def logged_losses(log_text: str) -> list[tuple[int, float]]:
    return [(int(s), float(v)) for s, v in LOSS_RE.findall(log_text)]
