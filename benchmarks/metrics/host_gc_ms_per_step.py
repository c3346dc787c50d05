"""Host milliseconds per scheduler iteration inside Python's collector:
counters["gc_s"] / counters["steps"], deltas over the window (the
program's `gc.callbacks` hook, stamped with the phases' clock; the time
lies inside whichever phase tripped the collector). A program without
the counter gives nothing."""

from benchmarks.trace.program_spans import per_step_ms


def read(obs):
    return per_step_ms(obs, "gc_s")
