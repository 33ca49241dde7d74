"""Checkpoint: the part of one async snapshot's stall that the HOST spends
on its own phases: per whole snapshot cycle, ``data_wait + h2d + dispatch +
ckpt`` summed over its steps less as many clean values; the median over the
window's cycles, in ms. ``snapshot_stall_device_ms`` (``block``) and
``snapshot_stall_unphased_ms`` (the rest of the cadence) are the other two
parts; ``snapshot_stall_ms`` measures the whole from outside. Source: the
window's journal ``train_step`` points."""

from benchmark import journal_reduce as jr


def read(run: dict):
    return jr.stall_ms(run, jr.host_s)
