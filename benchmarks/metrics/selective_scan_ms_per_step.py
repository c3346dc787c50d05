"""Device time on device 0 of the selective-scan (Mamba-1) mixer of a
Phi-4-mini-flash model (scope `selective_scan` of inference/model.py
`_layer`: the projections `sscan_project`, the convolution and its slot
traffic `sscan_conv`, the recurrence `sscan_state`, the gate
`sscan_gate` and `sscan_out`), all its layers, per shared-table program
of the traced window. None on a program that names no such scope (a
parent commit, every other family)."""

import pathlib

from benchmarks import harness

_moe = harness.load_module(
    pathlib.Path(__file__).with_name("moe_ms_per_step.py"))


def read(obs):
    return _moe.per_program_ms(obs, ("selective_scan",))
