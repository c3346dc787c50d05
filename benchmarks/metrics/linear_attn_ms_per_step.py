"""Device time on device 0 of the Gated DeltaNet operator (scope
`linear_attention` of inference/model.py `_layer`: the projections
`gdn_project`, the convolution and its slot traffic `gdn_conv`, the
delta rule `gdn_state`, the gated norm and `gdn_out`), all its layers,
per shared-table program of the traced window. None on a program that
names no such scope."""

import pathlib

from benchmarks import harness

_moe = harness.load_module(pathlib.Path(__file__).with_name("moe_ms_per_step.py"))


def read(obs):
    return _moe.per_program_ms(obs, ("linear_attention",))
