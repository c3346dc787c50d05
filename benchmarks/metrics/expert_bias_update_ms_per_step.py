"""Device time on device 0, per traced TRAIN step, of the step's own
state update (scope `expert_bias_update`, utils/profiler.py
TRAIN_STEP_SCOPES: every routed layer's `expert_bias` moved by the
census after the optimizer's update, and the step's routing counters).
None on a program without the scope."""

from benchmarks.trace.reduce import scope_ms_per_step


def read(obs):
    return scope_ms_per_step(obs, ("expert_bias_update",))
