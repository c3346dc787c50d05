"""Seconds inside `init_inference` (the serving layout's transform,
the KV pool, and the rest): the `init.inference` span. Always-kept set-up spans, read from the program's buffer."""

from benchmarks.trace import program_spans as PS


def read(obs):
    return PS.total_s(PS.setup_spans(obs), *("init.inference",))
