"""Milliseconds per traced train step the host waited in the loss
readback (`train.readback`: the `device_get` that ends a step): the
device-bound part of a step as the host sees it."""

from benchmarks.trace import program_spans as PS


def read(obs):
    return PS.train_phase_ms(("train.readback",))
