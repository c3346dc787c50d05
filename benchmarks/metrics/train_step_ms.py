"""Median host-clock time of one train_batch call (batch generation
included), over the steps of the window before the profiler starts."""

from benchmarks.trace.reduce import median


def read(obs):
    m = median(obs.get("step_s") or [])
    return None if m is None else 1e3 * m
