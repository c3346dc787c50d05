"""Device time on device 0 of the routed block (the scopes `moe_route`,
`moe_experts` and `moe_combine` of inference/model.py `_mlp` and
moe/dropless.py, all layers), per shared-table program of the traced
window (the programs holding `paged_decode_grid`). None on a program
that names no such scope."""

from benchmarks.trace import reduce as R

SCOPES = ("moe_route", "moe_experts", "moe_combine")


def per_program_ms(obs, scopes):
    td = obs.get("trace")
    if td is None:
        return None
    n = len(R.modules_with(td, "paged_decode_grid"))
    s = R.scope_seconds(td, scopes)
    if not n or s is None:
        return None
    return 1e3 * s / n


def read(obs):
    return per_program_ms(obs, SCOPES)
