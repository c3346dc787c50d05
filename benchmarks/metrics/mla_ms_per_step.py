"""Device time on device 0 of latent attention (the scopes
`mla_project`, `mla_cache_write`, `mla_attend` and `mla_out` of
inference/model.py, all layers), per shared-table program of the traced
window. None on a program that names no such scope (a model that caches
K and V; a program from before the scopes)."""

import pathlib

from benchmarks import harness

SCOPES = ("mla_project", "mla_cache_write", "mla_attend", "mla_out")
_moe = harness.load_module(pathlib.Path(__file__).with_name("moe_ms_per_step.py"))


def read(obs):
    return _moe.per_program_ms(obs, SCOPES)
