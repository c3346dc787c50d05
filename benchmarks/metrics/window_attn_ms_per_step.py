"""Device time on device 0 of the WINDOWED layers' cache write and walk
(scope `attn_window` inside `attention`: `paged_kv_write` into the
sequence's ring and `paged_decode_grid` over the ring's table, with
what XLA lays out for them), all windowed layers, per shared-table
program of the traced window. None on a program that names no such
scope (a model of one window, a parent commit)."""

import pathlib

from benchmarks import harness

_moe = harness.load_module(pathlib.Path(__file__).with_name("moe_ms_per_step.py"))


def read(obs):
    return _moe.per_program_ms(obs, ("attn_window",))
