"""Device time on device 0 of what the routed block does around its
expert matmuls: scope `moe_route` (router matmul, softmax, top-k, and
the sort or the weight matrix) and scope `moe_combine` (the ragged
wire's weighting and segment-sum), all layers, per shared-table
program of the traced window."""

import pathlib

from benchmarks import harness

_moe = harness.load_module(pathlib.Path(__file__).with_name("moe_ms_per_step.py"))


def read(obs):
    return _moe.per_program_ms(obs, ("moe_route", "moe_combine"))
