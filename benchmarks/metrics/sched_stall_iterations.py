"""Iterations of the window that took over three times what an
iteration of the loop typically takes, and over it by more than a few
milliseconds (counters["stall_iterations"], delta: the stall rule of
`profiler.Phases.end`). Each also leaves an always-kept span
`sched.slow_iteration` with the evidence (docs/tracing.md) and a line
in the run's own log. A program without the counter gives nothing."""


def read(obs):
    d = obs.get("counters_delta") or {}
    return d.get("stall_iterations")
