"""Device time on device 0, per traced TRAIN step, of the WINDOWED
layers' attention call (scope `attn_window` inside `attention`,
models/transformer.py: the three flash kernels over the band of the
window and what XLA lays out for them), forward, recomputation and
backward, all windowed layers. None on a program that names no such
scope (a model of one window, a parent commit)."""

from benchmarks.trace.reduce import scope_ms_per_step


def read(obs):
    return scope_ms_per_step(obs, ("attn_window",))
