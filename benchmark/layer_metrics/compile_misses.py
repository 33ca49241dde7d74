"""Compile caches: programs compiled after the kill instead of loaded
(must be 0: nothing compiles inside a window). Source: journal
``compile_cache`` events with ``hit`` false."""

from benchmark import harness


def read(run: dict):
    if run.get("kill_t") is None:
        return None
    return float(sum(1 for e in harness.journal_events(
        run["files"]["journal"], ("compile_cache",))
        if not e.get("hit") and e["t"] > run["kill_t"]))
