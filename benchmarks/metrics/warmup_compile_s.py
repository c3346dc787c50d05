"""Seconds of `engine.warmup` inside XLA's backend compile (or the
persistent cache's load), summed over the programs: the
`warmup.compile` spans. Always-kept set-up spans, read from the program's buffer."""

from benchmarks.trace import program_spans as PS


def read(obs):
    return PS.total_s(PS.setup_spans(obs), *("warmup.compile",))
