"""Device time on device 0 of the Mamba-2 mixer (scope `state_space` of
inference/model.py `_layer`: the projection `ssm_project`, the
convolution and its slot traffic `ssm_conv`, the recurrence
`ssm_state`, the gated norm `ssm_gate_norm` and `ssm_out`), all its
layers, per shared-table program of the traced window. None on a
program that names no such scope."""

import pathlib

from benchmarks import harness

_moe = harness.load_module(pathlib.Path(__file__).with_name("moe_ms_per_step.py"))


def read(obs):
    return _moe.per_program_ms(obs, ("state_space",))
