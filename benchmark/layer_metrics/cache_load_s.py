"""Compile caches: seconds the new incarnation took to load its train
step from the compile cache. Source: journal ``compile_cache`` (hit)."""

from benchmark import harness


def read(run: dict):
    if run.get("kill_t") is None:
        return None
    hits = [e["dur"] for e in harness.journal_events(
        run["files"]["journal"], ("compile_cache",))
        if e.get("hit") and e["t"] > run["kill_t"]]
    return max(hits) if hits else None
