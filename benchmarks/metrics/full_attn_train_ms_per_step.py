"""Device time on device 0, per traced TRAIN step, of the FULL layers'
attention call (scope `attn_full` inside `attention`: the three flash
kernels under the causal mask), forward, recomputation and backward.
None on a program that names no such scope."""

from benchmarks.trace.reduce import scope_ms_per_step


def read(obs):
    return scope_ms_per_step(obs, ("attn_full",))
