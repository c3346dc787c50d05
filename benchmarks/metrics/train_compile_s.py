"""Seconds building the train step's program on a batch shape new to
the engine (`lower().compile()`, or the persistent cache's load): the
`train.compile` spans. Always-kept set-up spans, read from the program's buffer."""

from benchmarks.trace import program_spans as PS


def read(obs):
    return PS.total_s(PS.setup_spans(obs), *("train.compile",))
