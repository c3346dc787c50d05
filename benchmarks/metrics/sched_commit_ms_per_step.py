"""Host milliseconds per scheduler iteration in phase `commit`:
the bookkeeping that overlaps the device program (`state.commit`). counters["commit_s"] / counters["steps"],
deltas over the window (the program's always-on time sums)."""

from benchmarks.trace.program_spans import per_step_ms


def read(obs):
    return per_step_ms(obs, "commit_s")
