"""Device time on device 0 of the gated memory units (scope
`gated_memory`: W_in, silu, the product with the last scan's output of
the same token, W_out), all such layers, per shared-table program of
the traced window. None on a program that names no such scope."""

import pathlib

from benchmarks import harness

_moe = harness.load_module(
    pathlib.Path(__file__).with_name("moe_ms_per_step.py"))


def read(obs):
    return _moe.per_program_ms(obs, ("gated_memory",))
