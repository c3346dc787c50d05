"""Seconds of `engine.warmup` waiting for the warmed programs to run
once on the device: the `warmup.execute` spans. Always-kept set-up spans, read from the program's buffer."""

from benchmarks.trace import program_spans as PS


def read(obs):
    return PS.total_s(PS.setup_spans(obs), *("warmup.execute",))
