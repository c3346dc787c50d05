"""Device time on device 0 of the delta rule (scope `gdn_state`: the
kernel that reads each live sequence's float32 matrices from its slot,
advances them by the step's rows and writes them back, with the relayout
of its rows), all DeltaNet layers, per shared-table program of the
traced window. What the matrix state costs an iteration beside the
projections `linear_attn_ms_per_step` also holds."""

import pathlib

from benchmarks import harness

_moe = harness.load_module(pathlib.Path(__file__).with_name("moe_ms_per_step.py"))


def read(obs):
    return _moe.per_program_ms(obs, ("gdn_state",))
