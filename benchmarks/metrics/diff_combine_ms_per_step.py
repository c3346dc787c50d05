"""Device time on device 0 of differential attention's combine (scope
`diff_combine` inside `attention`: the subtraction of a pair's two
maps, the norm over the pair's values and the factor), all attending
layers, per shared-table program of the traced window. None on a
program that names no such scope."""

import pathlib

from benchmarks import harness

_moe = harness.load_module(
    pathlib.Path(__file__).with_name("moe_ms_per_step.py"))


def read(obs):
    return _moe.per_program_ms(obs, ("diff_combine",))
