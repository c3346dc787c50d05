"""Device time on device 0 of the Gated DeltaNet operator of an
Olmo-Hybrid model (scope `linear_attention` of inference/model.py
`_layer`: the projections `gdn_project`, the convolution and its slot
traffic `gdn_conv`, the delta rule `gdn_state`, the gated norm and
`gdn_out`; the output norm `norm1_post` is outside it), all its layers,
per shared-table program of the traced window. None on a program that
names no such scope, and unless the configuration is of the family
(`gdn_state_roofline.of_family`: `linear_attn_ms_per_step` reads the
other DeltaNet family's cell)."""

import pathlib

from benchmarks import harness

_here = pathlib.Path(__file__)
_moe = harness.load_module(_here.with_name("moe_ms_per_step.py"))
_family = harness.load_module(_here.with_name("gdn_state_roofline.py"))


def read(obs):
    if not _family.of_family(obs.get("hf") or {}):
        return None
    return _moe.per_program_ms(obs, ("linear_attention",))
