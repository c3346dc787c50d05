"""Device time on device 0 of the convolution's STATE traffic (scope
`conv_state`: the gather of each row's two carried inputs from its
sequence's slot or its neighbour rows, and the write of each
sequence's last two back), all conv layers, per shared-table program
of the traced window. What a slot table costs an iteration beside the
projections `short_conv_ms_per_step` also holds."""

import pathlib

from benchmarks import harness

_moe = harness.load_module(pathlib.Path(__file__).with_name("moe_ms_per_step.py"))


def read(obs):
    return _moe.per_program_ms(obs, ("conv_state",))
