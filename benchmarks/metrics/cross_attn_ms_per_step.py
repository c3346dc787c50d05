"""Device time on device 0 of the walks of a pool the layer does not
own (scope `attn_cross` inside `attention`: a cross-attention layer's
`paged_decode_grid` over the full layer's pages, and no write), all
such layers, per shared-table program of the traced window. None on a
program that names no such scope."""

import pathlib

from benchmarks import harness

_moe = harness.load_module(
    pathlib.Path(__file__).with_name("moe_ms_per_step.py"))


def read(obs):
    return _moe.per_program_ms(obs, ("attn_cross",))
