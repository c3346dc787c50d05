"""Device time on device 0, per traced step, of the instructions that
CONTAIN a collective and are not named after one: the sites of the
compiled step's manifest (the ids of the program's always-kept span
`train.compile.collectives`, docs/tracing.md) whose own name is no
collective's. On four chips these are the gradients' all-reduces, each
fused with the slice that follows it into one `fusion.<n>`
(`all-reduce-scatter`), which `collective_ms_per_step` cannot count:
it tells a collective by its instruction's name. The partitioner gives
such a fusion the path of the matmul whose output it reduces, so its
time ALSO reads in `mlp_ms_per_step` / `attention_ms_per_step`. A
program without the span (a parent commit), or a step without such a
site (one chip), reads nothing."""

from benchmarks.trace import program_spans as PS
from benchmarks.trace import reduce as R

SPAN = "train.compile.collectives"


def sites(obs):
    """[(instruction, kind, bytes)] of the last compiled step's
    manifest; None without the span."""
    spans = PS.named(PS.setup_spans(obs), SPAN)
    if not spans or "sites" not in spans[-1].ids:
        return None
    out = []
    for item in filter(None, spans[-1].ids["sites"].split(",")):
        name, kind, nbytes = item.rsplit(":", 2)
        out.append((name, kind, int(nbytes)))
    return out


def read(obs):
    td = obs.get("trace")
    fused = {name for name, _, _ in sites(obs) or ()
             if not R.is_collective(name)}
    if td is None or not fused:
        return None
    total, path = {}, {}
    for e in R.leaves(R.in_window(td.ops.get(0, []), td.window)):
        if e.name in fused:
            total[e.name] = total.get(e.name, 0.0) + e.dur
            path[e.name] = R.short_scope(e.scope)
    if not total:
        return None
    steps = obs["traced_steps"]
    print("[bench] collectives inside fusions, ms a step: " + ", ".join(
        f"{k} {1e3 * v / steps:.3f} ({path[k]})"
        for k, v in sorted(total.items(), key=lambda kv: -kv[1])), flush=True)
    return 1e3 * sum(total.values()) / steps
