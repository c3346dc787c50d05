"""Share of device 0's busy time inside the traced window that fell
into ANY named scope: the model's (models/transformer.py: the six of
`scope_named_share` and `norm_f`, the final norm) or the train step's
own (deepspeed_tpu/utils/profiler.py TRAIN_STEP_SCOPES:
the parameter copies, the gradient's reduction, the clipping, the
optimizer, ZeRO's gathers, the layer scan). What is left is what no
scope can hold: copies and converts XLA inserts (no `op_name`, or one
it made up: `convert.34`), zeros the backward scan starts from (a path
of control flow alone). The largest are printed on an earlier line. A
fusion counts whole for its root's scope.

The other readers of the train step's scopes share `booked`: an
event's time goes to the OUTERMOST of these names in its path, and
`layer_stack`, which holds the model's layer scopes, keeps only what
nothing inside it names. A program without the scopes (a parent
commit) reads nothing."""

from benchmarks.trace import reduce as R

MODEL_SCOPES = ("embed", "norm1", "attention", "norm2", "mlp", "norm_f",
                "lm_head")
STEP_SCOPES = ("param_cast", "grad_reduce", "grad_clip", "optimizer",
               "zero_gather", "layer_stack")
LAYER_STACK = "layer_stack"


def booked(path):
    """The one scope an instruction's time is booked to, or None."""
    names = [c for c in R.scope_components(path)
             if c in MODEL_SCOPES or c in STEP_SCOPES] if path else []
    inner = [c for c in names if c != LAYER_STACK]
    return inner[0] if inner else (LAYER_STACK if names else None)


def work(obs):
    """Device 0's work events inside the traced window, or None without
    a trace or without any scope of the train step in it."""
    td = obs.get("trace")
    if td is None:
        return None
    evs = R.leaves(R.in_window(td.ops.get(0, []), td.window))
    if not any(e.scope and R.scope_of(e.scope, STEP_SCOPES) for e in evs):
        return None
    return evs


def booked_ms_per_step(obs, scope):
    """Milliseconds per traced step booked to `scope`; None without a
    trace, the train step's scopes, or any instruction of this one."""
    evs = work(obs)
    got = [e.dur for e in evs or () if booked(e.scope) == scope]
    return 1e3 * sum(got) / obs["traced_steps"] if got else None


def read(obs):
    evs = work(obs)
    if evs is None:
        return None
    td = obs["trace"]
    named, rest = [], {}
    for e in evs:
        if booked(e.scope):
            named.append(e)
            continue
        path = R.short_scope(e.scope) or (
            "control flow only" if e.scope else "no op_name")
        key = f"{path} ({R.base_name(e.name)})"
        rest[key] = rest.get(key, 0.0) + e.dur
    top = sorted(rest.items(), key=lambda kv: -kv[1])[:8]
    print("[bench] outside every scope of the model and the train step, ms "
          f"in the window, {1e3 * sum(rest.values()):.1f} in all: "
          + ", ".join(f"{k} {1e3 * v:.1f}" for k, v in top), flush=True)
    busy = R.union_s(R.clip(R.intervals(td.ops.get(0, [])), td.window))
    return 100.0 * R.union_s(R.clip(R.intervals(named), td.window)) / busy
