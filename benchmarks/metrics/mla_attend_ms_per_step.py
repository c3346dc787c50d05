"""Device time on device 0 of the latent walk (scope `mla_attend`: the
`paged_decode_grid` kernel over the latent pool and the padding of its
queries), all layers, per shared-table program of the traced window."""

import pathlib

from benchmarks import harness

_moe = harness.load_module(pathlib.Path(__file__).with_name("moe_ms_per_step.py"))


def read(obs):
    return _moe.per_program_ms(obs, ("mla_attend",))
