"""Host milliseconds per scheduler iteration in phase `tick`:
the caller's hook between iterations (the benchmark's tick: submitting what is due). counters["tick_s"] / counters["steps"],
deltas over the window (the program's always-on time sums)."""

from benchmarks.trace.program_spans import per_step_ms


def read(obs):
    return per_step_ms(obs, "tick_s")
