"""Host milliseconds per scheduler iteration in phase `admit`:
the pressure governor's update and `_admit`. counters["admit_s"] / counters["steps"],
deltas over the window (the program's always-on time sums)."""

from benchmarks.trace.program_spans import per_step_ms


def read(obs):
    return per_step_ms(obs, "admit_s")
