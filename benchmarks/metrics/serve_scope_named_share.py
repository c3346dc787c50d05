"""Share of device 0's busy time inside the traced serving window that
fell into ANY of the serving model's named scopes (inference/model.py:
`embed`, `norm1`, `attention`, `norm2`, `mlp`, `lm_head`, the names
training uses; the routed block's `moe_*` scopes nest inside `mlp`).
What is left is the sampler, `_row_keys`, and the copies XLA inserts;
the largest are printed. `scope_named_share`'s reading, under the
end-to-end metric a serving cell reports."""

import pathlib

from benchmarks import harness

_share = harness.load_module(
    pathlib.Path(__file__).with_name("scope_named_share.py"))


def read(obs):
    return _share.read(obs)
