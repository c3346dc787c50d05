"""Device time on device 0 of a denoising pass's sample-and-reveal
epilogue (scope `block_unmask` of inference/sampling.py: the draw, the
confidence over the vocabulary, the top of a block's masked positions),
per shared-table program of the traced window. None on a program that
names no such scope."""

import pathlib

from benchmarks import harness

_moe = harness.load_module(
    pathlib.Path(__file__).with_name("moe_ms_per_step.py"))


def read(obs):
    return _moe.per_program_ms(obs, ("block_unmask",))
