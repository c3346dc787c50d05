"""Device time on device 0 of the latent cache write (scope
`mla_cache_write`: `paged_latent_write` and the sort of its slots), all
layers, per shared-table program of the traced window."""

import pathlib

from benchmarks import harness

_moe = harness.load_module(pathlib.Path(__file__).with_name("moe_ms_per_step.py"))


def read(obs):
    return _moe.per_program_ms(obs, ("mla_cache_write",))
