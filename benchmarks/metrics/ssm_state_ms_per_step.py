"""Device time on device 0 of the state-space step (scope `ssm_state`:
the kernel `ssm_state` over the live sequences' slots, and what XLA
lays out for it and adds after it, the skip D x), all Mamba-2 layers,
per shared-table program of the traced window. None on a program that
names no such scope."""

import pathlib

from benchmarks import harness

_moe = harness.load_module(pathlib.Path(__file__).with_name("moe_ms_per_step.py"))


def read(obs):
    return _moe.per_program_ms(obs, ("ssm_state",))
