"""Launcher / agent / master: seconds from the SIGKILL to the ``start``
line of the next incarnation (detection, persist, re-rendezvous, standby
promotion, reaching the chip). Source: goodput log."""

from benchmark import goodput_reduce as gr


def read(run: dict):
    if run.get("kill_t") is None:
        return None
    later = [i["start_t"] for i in gr.incarnations(run["goodput"])
             if i["start_t"] > run["kill_t"]]
    return min(later) - run["kill_t"] if later else None
