"""Device time on device 0, per traced step, under the scope
`layer_stack` (models/transformer.py: every scan over layers) and
inside none of the model's scopes nor another of the train step's:
the scan's slicing of stacked weights and activations (`squeeze`,
`dynamic_slice`, `dynamic_update_slice`) and the copies its control
flow costs, forward, recomputation and backward."""

from benchmarks.metrics.train_step_named_share import booked_ms_per_step


def read(obs):
    return booked_ms_per_step(obs, "layer_stack")
