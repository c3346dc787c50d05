"""Device time on device 0, per traced step, of the instructions whose
named-scope path holds `attention` (models/transformer.py: the four
projections, rope and the flash kernels together; forward,
recomputation and backward)."""

from benchmarks.trace.reduce import scope_ms_per_step


def read(obs):
    return scope_ms_per_step(obs, ("attention",))
