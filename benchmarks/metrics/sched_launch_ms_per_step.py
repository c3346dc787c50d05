"""Host milliseconds per scheduler iteration in phase `launch`:
host-to-device transfers and the jitted decode and sample calls. counters["launch_s"] / counters["steps"],
deltas over the window (the program's always-on time sums)."""

from benchmarks.trace.program_spans import per_step_ms


def read(obs):
    return per_step_ms(obs, "launch_s")
