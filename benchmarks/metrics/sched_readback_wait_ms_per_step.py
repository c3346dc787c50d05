"""Milliseconds per scheduler iteration the host spent waiting for the
device in `serving_readback`: the device-bound part of an iteration as
the host sees it. counters["readback_wait_s"] / counters["steps"],
deltas over the window."""

from benchmarks.trace.program_spans import per_step_ms


def read(obs):
    return per_step_ms(obs, "readback_wait_s")
