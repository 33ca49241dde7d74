"""Trainer loop: median milliseconds a step waited for its batch.
Source: ``data_wait_s`` of the window's journal ``train_step`` points."""

from benchmark import journal_reduce as jr


def read(run: dict):
    return jr.median_ms(run, lambda p: p["data_wait_s"])
