"""Share of device 0's busy time inside the traced window that fell
into ANY of the model's named scopes (models/transformer.py). What is
left carries another path or none: the optimizer and the clipping
(outside the model), ZeRO's gathers (`sharding_constraint`), the layer
scan's slicing, and the copies XLA inserts, which have no `op_name`;
the largest of them are printed on an earlier line. A fusion counts
whole for its root's scope."""

from benchmarks.trace import reduce as R

SCOPES = ("embed", "norm1", "attention", "norm2", "mlp", "lm_head")


def read(obs):
    td = obs.get("trace")
    if td is None:
        return None
    named, rest = [], {}
    for e in R.leaves(R.in_window(td.ops.get(0, []), td.window)):
        if e.scope and R.scope_of(e.scope, SCOPES):
            named.append(e)
            continue
        path = R.short_scope(e.scope) or (
            "control flow only" if e.scope else "no op_name")
        key = f"{path} ({R.base_name(e.name)})"
        rest[key] = rest.get(key, 0.0) + e.dur
    if not named:
        return None
    top = sorted(rest.items(), key=lambda kv: -kv[1])[:8]
    print("[bench] outside the model's scopes, ms in the window, "
          f"{1e3 * sum(rest.values()):.1f} in all: "
          + ", ".join(f"{k} {1e3 * v:.1f}" for k, v in top), flush=True)
    busy = R.union_s(R.clip(R.intervals(td.ops.get(0, [])), td.window))
    return 100.0 * R.union_s(R.clip(R.intervals(named), td.window)) / busy
