"""Host milliseconds per scheduler iteration in phase `build`:
filling the numpy token, context and block-table buffers (`block_table`). counters["build_s"] / counters["steps"],
deltas over the window (the program's always-on time sums)."""

from benchmarks.trace.program_spans import per_step_ms


def read(obs):
    return per_step_ms(obs, "build_s")
