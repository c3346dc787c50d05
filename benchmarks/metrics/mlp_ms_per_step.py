"""Device time on device 0, per traced step, of the instructions whose
named-scope path holds `mlp` (models/transformer.py: the feed-forward
block, routed or dense; forward, recomputation and backward)."""

from benchmarks.trace.reduce import scope_ms_per_step


def read(obs):
    return scope_ms_per_step(obs, ("mlp",))
