"""Seconds inside `ds.initialize` (shapes, mesh, the jitted float32
init with master, optimizer state and compute copy): the `train.init`
span. Always-kept set-up spans, read from the program's buffer."""

from benchmarks.trace import program_spans as PS


def read(obs):
    return PS.total_s(PS.setup_spans(obs), *("train.init",))
