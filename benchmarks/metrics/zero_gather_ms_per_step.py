"""Device time on device 0, per traced step, of the instructions whose
named-scope path holds `zero_gather` ANYWHERE (runtime/overlap.py's
prefetch gather of a layer's shards, the finalizer's gather of updated
parameters under ZeRO-1/2, the model's constraint of the head): the
all-gathers a constraint of a ZeRO-sharded leaf to its gathered layout
induced. Nested inside `layer_stack`, `lm_head` or `optimizer`, whose
own readers count it too."""

from benchmarks.trace.reduce import scope_ms_per_step


def read(obs):
    return scope_ms_per_step(obs, ("zero_gather",))
