"""Device time on device 0, per traced step, booked to the scope
`param_cast` (runtime/engine.py: the master -> compute-dtype copies
the loss reads, qwZ and compression hooks, and, through `jvp` /
`transpose`, the gradient's convert back to float32; a collective
the partitioner names after such a copy, as it does the head's
all-gather, is here too). The copy of the UPDATED master is inside
`optimizer` and read there."""

from benchmarks.metrics.train_step_named_share import booked_ms_per_step


def read(obs):
    return booked_ms_per_step(obs, "param_cast")
