"""Device time on device 0 of the full-attention layers of an
Olmo-Hybrid model (scope `attention` of inference/model.py `_layer`:
the q, k, v projections, the hidden-wide QK-norm, NO rotation, the
cache write, the walk over 30 KV heads of 128 and W_o; the output norm
`norm1_post` is outside it), all of them, per shared-table program of
the traced window. None on a program that names no such scope, and
unless the configuration is of the family
(`gdn_state_roofline.of_family`)."""

import pathlib

from benchmarks import harness

_here = pathlib.Path(__file__)
_moe = harness.load_module(_here.with_name("moe_ms_per_step.py"))
_family = harness.load_module(_here.with_name("gdn_state_roofline.py"))


def read(obs):
    if not _family.of_family(obs.get("hf") or {}):
        return None
    return _moe.per_program_ms(obs, ("attention",))
