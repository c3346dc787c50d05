"""Device time on device 0, per traced step, booked to the scope
`grad_clip` (runtime/engine.py: the global norm's sums of squares
over every gradient leaf, the non-finite check, the scale by the
clipping coefficient)."""

from benchmarks.metrics.train_step_named_share import booked_ms_per_step


def read(obs):
    return booked_ms_per_step(obs, "grad_clip")
