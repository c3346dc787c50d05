"""Seconds of `engine.warmup` spent tracing the programs' Python and
lowering them to StableHLO (Pallas kernels included), summed over the
programs: the `warmup.trace` and `warmup.lower` spans. Always-kept set-up spans, read from the program's buffer."""

from benchmarks.trace import program_spans as PS


def read(obs):
    return PS.total_s(PS.setup_spans(obs), *("warmup.trace", "warmup.lower"))
