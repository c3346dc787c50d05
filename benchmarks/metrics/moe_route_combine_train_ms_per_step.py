"""Device time on device 0, per traced TRAIN step, of what a routed
block does AROUND its experts' products (scopes `moe_route` and
`moe_combine`: the router, the top-k, the census, the sort of the held
pairs, the gather of their rows and its scatter-add backward, the
weighted segment sum and its gather backward), all routed layers. None
on a program that names neither."""

from benchmarks.trace.reduce import scope_ms_per_step


def read(obs):
    return scope_ms_per_step(obs, ("moe_route", "moe_combine"))
