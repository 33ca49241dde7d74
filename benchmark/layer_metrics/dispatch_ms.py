"""Trainer loop: median milliseconds a step spent staging its batch onto the
device and dispatching the step program. Source: ``h2d_s + dispatch_s`` of
the window's journal ``train_step`` points."""

from benchmark import journal_reduce as jr


def read(run: dict):
    return jr.median_ms(run, lambda p: p["h2d_s"] + p["dispatch_s"])
