"""Device time on device 0 of the expert matmuls and activation (scope
`moe_experts`; on the scan path the combine column fused into the
output matmul is inside it), all layers, per shared-table program of
the traced window."""

import pathlib

from benchmarks import harness

_moe = harness.load_module(pathlib.Path(__file__).with_name("moe_ms_per_step.py"))


def read(obs):
    return _moe.per_program_ms(obs, ("moe_experts",))
