"""Device time on device 0, per traced step, booked to the scope
`optimizer` (runtime/engine.py: the update of master and moments, the
overflow selects, the schedule; and the compute-dtype copy of the
updated master, which XLA fuses into the same pass and which is
entered INSIDE the scope for that reason). `param_cast_ms_per_step`
holds the loss's copies; the two add up to what the optimizer costs a
step."""

from benchmarks.metrics.train_step_named_share import booked_ms_per_step


def read(obs):
    return booked_ms_per_step(obs, "optimizer")
