"""Checkpoint: the part of one async snapshot's stall that no phase of the
loop times: per whole snapshot cycle, each step's cadence less its five
phases (``on_step`` with a logged step's loss fetch, the journal and goodput
lines, time the loop's thread stood without the interpreter while the writer
fetched) summed over its steps less as many clean values; the median over
the window's cycles, in ms. With ``snapshot_stall_host_ms`` and
``snapshot_stall_device_ms`` it makes up the stall as the steps' cadences
show it. Source: the window's journal ``train_step`` points."""

from benchmark import journal_reduce as jr


def read(run: dict):
    return jr.stall_ms(run, jr.unphased_s)
