"""Checkpoint: the part of one async snapshot's stall that the loop spends
in ``block``, waiting with one step in flight for the step before: per whole
snapshot cycle, ``block_s`` summed over its steps less as many clean values;
the median over the window's cycles, in ms. The wait ends when the device
has finished that step AND the loop's thread has the interpreter back, so it
is the device's share only as far as the second is short. Source: the
window's journal ``train_step`` points."""

from benchmark import journal_reduce as jr


def read(run: dict):
    return jr.stall_ms(run, jr.block_s)
