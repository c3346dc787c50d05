"""Host milliseconds per scheduler iteration in phase `accept`:
`_accept` / `_finish` over the tokens read back. counters["accept_s"] / counters["steps"],
deltas over the window (the program's always-on time sums)."""

from benchmarks.trace.program_spans import per_step_ms


def read(obs):
    return per_step_ms(obs, "accept_s")
