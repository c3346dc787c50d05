"""Iterations of the window in which the host held the loop for over
50 ms outside the readback wait (counters["slow_iterations"], delta).
Each also leaves an always-kept span `sched.slow_iteration` with the
iteration's split, whatever the tracing state."""


def read(obs):
    d = obs.get("counters_delta") or {}
    return d.get("slow_iterations")
