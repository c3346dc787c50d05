"""Device time on device 0 of the gated short convolution (scope
`short_conv` of inference/model.py `_layer`: `conv_project`,
`conv_state`, the taps' sum and `conv_out`, all conv layers), per
shared-table program of the traced window. None on a program that names
no such scope (a model whose layers are all attention; the parent)."""

import pathlib

from benchmarks import harness

_moe = harness.load_module(pathlib.Path(__file__).with_name("moe_ms_per_step.py"))


def read(obs):
    return _moe.per_program_ms(obs, ("short_conv",))
