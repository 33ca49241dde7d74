"""From the trainer's journal to the host's share of each step.

Every step after an incarnation's first writes one ``train_step`` point
(``dlrover_tpu/trainer/elastic_trainer.py``): ``dur`` is the step's cadence,
the end of the step before to the end of this one, and ``data_wait_s``,
``h2d_s``, ``dispatch_s``, ``block_s``, ``ckpt_s`` say where the loop spent
it; what they leave of ``dur`` is un-phased. The snapshot path writes
``snapshot_request`` on the main thread and ``snapshot_fetch`` /
``snapshot_arena_write`` on the writer's (``checkpoint/engine.py``,
``checkpoint/shm_handler.py``). Readers take the
steps of a steady window, ``run["first_sync"]`` exclusive to
``run["first_sync"] + run["steps"]`` inclusive: the steps the goodput log's
two sync points enclose.
"""

from __future__ import annotations

import statistics

from benchmark import harness

HOST_PHASES = ("data_wait_s", "h2d_s", "dispatch_s", "ckpt_s")


def window_steps(run: dict) -> dict[int, dict]:
    """``{step: its train_step point}`` for the steps of the window;
    empty where the run has no steady window or the program no such
    points."""
    if run.get("kill_t") is not None or "first_sync" not in run:
        return {}
    lo, hi = run["first_sync"], run["first_sync"] + run["steps"]
    return {e["step"]: e for e in harness.journal_events(
        run["files"]["journal"], ("train_step",))
        if "data_wait_s" in e and lo < e.get("step", -1) <= hi}


def host_s(point: dict) -> float:
    """Seconds of a step the host spent on its own phases."""
    return sum(point.get(k, 0.0) for k in HOST_PHASES)


def block_s(point: dict) -> float:
    """Seconds the loop waited, one step in flight, for the step before
    (``block_until_ready``, and taking the interpreter back after it)."""
    return point.get("block_s", 0.0)


def unphased_s(point: dict) -> float:
    """The rest of the step's cadence, which no phase times: the caller's
    ``on_step`` (on a logged step its loss fetch, which waits for the
    device in ``block``'s place), the journal and goodput lines, and any
    time the loop's thread stood without the interpreter."""
    return point["dur"] - host_s(point) - block_s(point)


def median_ms(run: dict, what) -> float | None:
    points = window_steps(run)
    return (1e3 * statistics.median(what(p) for p in points.values())
            if points else None)


def stall_ms(run: dict, what) -> float | None:
    """What one snapshot adds to ``what`` (a function of a step's point):
    per whole snapshot cycle of the window, the sum over its steps of
    ``what`` less its clean value; the median over the cycles, in ms. The
    clean value of a step is the window's median over the steps at the
    same place in the logging interval, because a logged step spends in
    ``on_step`` what another spends in ``block``. The twin, from inside,
    of ``snapshot_stall_ms``."""
    points = window_steps(run)
    cycle, first = run.get("cycle"), run.get("first_sync")
    if not points or not cycle:
        return None
    every = int(run.get("log_interval") or 1)
    clean = {k: statistics.median(what(p) for s, p in points.items()
                                  if s % every == k)
             for k in {s % every for s in points}}
    stalls = []
    for k in range(run["steps"] // cycle):
        steps = range(first + k * cycle + 1, first + (k + 1) * cycle + 1)
        if all(s in points for s in steps):
            stalls.append(sum(what(points[s]) - clean[s % every]
                              for s in steps))
    return 1e3 * statistics.median(stalls) if stalls else None


def span_seconds(run: dict, name: str) -> list[float]:
    """Durations of the journal spans ``name`` that ended inside the
    window."""
    if "window" not in run or "files" not in run:
        return []
    t0, t1 = run["window"]
    return [e["dur"] for e in harness.journal_events(
        run["files"]["journal"], (name,))
        if e.get("ev") == "e" and "dur" in e and t0 <= e["t"] <= t1]
