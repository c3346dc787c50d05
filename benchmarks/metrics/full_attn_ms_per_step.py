"""Device time on device 0 of the FULL layers' cache write and walk
(scope `attn_full` inside `attention`: `paged_kv_write` into the
sequence's pages and `paged_decode_grid` over its whole context), all
full layers of a model of mixed windows, per shared-table program of
the traced window. None on a program that names no such scope."""

import pathlib

from benchmarks import harness

_moe = harness.load_module(pathlib.Path(__file__).with_name("moe_ms_per_step.py"))


def read(obs):
    return _moe.per_program_ms(obs, ("attn_full",))
