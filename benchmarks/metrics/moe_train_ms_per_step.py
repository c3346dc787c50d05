"""Device time on device 0, per traced TRAIN step, of a routed block's
own scopes inside `mlp` (moe/dropless.py and models/transformer.py:
`moe_route` the router, the choice, the census and the pairs' sort and
gather; `moe_experts` the held experts' grouped products; `moe_combine`
the weighted segment sum; `moe_shared` the shared expert), forward,
recomputation and backward, all routed layers. None on a program that
names none of them (a dense model, a parent commit).

The grouped products themselves carry NO scope on the chip: the TPU
compiler rewrites `ragged_dot` into instructions it names
`ragged-dot-none.<n>` with an op_name of its own making (42 of the
~70 ms of `moe_experts` a step in the cell; my chip run, PR 55). They
are counted with `moe_experts` by that name."""

from benchmarks.trace import reduce as R

SCOPES = ("moe_route", "moe_experts", "moe_combine", "moe_shared")
GROUPED_PRODUCT = "ragged-dot"


def seconds(td, scopes):
    """Device seconds inside the traced window of the instructions under
    one of `scopes`, and with `moe_experts` the grouped products the
    compiler renamed. None when none ran."""
    evs = [e for e in R.leaves(R.in_window(td.ops.get(0, []), td.window))
           if (e.scope and R.scope_of(e.scope, scopes) is not None)
           or ("moe_experts" in scopes
               and R.base_name(e.name).startswith(GROUPED_PRODUCT))]
    return sum(e.dur for e in evs) if evs else None


def ms_per_step(obs, scopes):
    td = obs.get("trace")
    s = seconds(td, scopes) if td is not None else None
    return None if s is None else 1e3 * s / obs["traced_steps"]


def read(obs):
    return ms_per_step(obs, SCOPES)
