"""Host milliseconds per scheduler iteration in phase `select`:
choosing the iteration's rows and reserving their KV room (`_reserve`, preemption and spill included; `_can_chain`). counters["select_s"] / counters["steps"],
deltas over the window (the program's always-on time sums)."""

from benchmarks.trace.program_spans import per_step_ms


def read(obs):
    return per_step_ms(obs, "select_s")
