"""Device time on device 0, per traced step, of the instructions whose
named-scope path holds `lm_head` (models/transformer.py::make_loss_fn:
the output head and the chunked cross-entropy, forward and
backward)."""

from benchmarks.trace.reduce import scope_ms_per_step


def read(obs):
    return scope_ms_per_step(obs, ("lm_head",))
