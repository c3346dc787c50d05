"""Measured per-module latency from execution traces.

Closes the reference profiler's measured-latency column
(ref: deepspeed/profiling/flops_profiler/profiler.py:282
print_model_profile — there, per-module wall latency comes from forward
hooks timing each nn.Module call). Under jit there are no module
boundaries at runtime, so the measurement is reconstructed exactly from
two artifacts the runtime already produces:

1. the model's forward wraps each module in `jax.named_scope`
   (models/transformer._make_layer_body: norm1 / attention / norm2 /
   mlp, plus embed / lm_head at the top level) and the train step
   wraps its own parts (utils/profiler.TRAIN_STEP_SCOPES: the
   parameter copies, the gradient's reduction, the clipping, the
   optimizer, ZeRO's gathers, the layer scan; docs/tracing.md) — the
   scope lands in every HLO instruction's
   `metadata={op_name="..."}`, surviving jvp / transpose / scan /
   fusion;
2. the profiler trace (utils/profiler.trace → trace.json.gz inside the
   xplane dump) records every executed HLO op with its device duration
   and its `hlo_op` instruction name.

Joining (2)'s durations against (1)'s instruction→op_name map
attributes MEASURED device time to each module — not a
flops-proportional estimate. Works identically for the CPU test lane
and real-TPU xplane captures (both emit hlo_op-tagged trace events).
Backward ops are recognized by the `transpose(` transform tag in their
op_name and reported separately. A scope entered inside another is
reported under its parent (`lm_head > zero_gather`); `layer_stack`,
which holds the model's layer scopes, keeps only what none of them
names (the scan's slicing and control-flow copies). The engine's
collective manifest (`engine.collective_manifest()`: what the compiled
step moves, by site, collectives inside fusions included) is joined to
the same trace: bytes per kind, and the achieved GB/s where the sites'
instructions have times of their own.

Granularity caveat: attribution is exact per HLO *instruction*; a
fusion carries its root op's scope, so ops fused across a module
boundary land in the root's bucket. TPU fusions respect tiling and are
fine-grained; the CPU test backend fuses aggressively, so CPU numbers
are coarser (the `coverage` field reports how much device time was
attributable either way).
"""

import glob
import gzip
import json
import os
import re
from typing import Any, Dict, List, Optional

from ..utils.profiler import LAYER_STACK, MODEL_SCOPES, TRAIN_STEP_SCOPES

# the rows of the profile: the model's scopes and the train step's
DEFAULT_BUCKETS = MODEL_SCOPES + TRAIN_STEP_SCOPES

_METADATA_RE = re.compile(
    r"%?([\w.\-]+)\s*=.*metadata=\{[^}]*op_name=\"([^\"]+)\"")
# autodiff wraps the scope it was entered under: `transpose(jvp(mlp))`
_WRAPPERS = re.compile(r"^(?:\w+\()+|\)+$")


def hlo_scope_map(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name → op_name metadata (the named-scope path).

    Fusion instructions carry their root op's metadata, so a fused
    attention GEMM still maps into the attention bucket."""
    return {m.group(1): m.group(2)
            for m in _METADATA_RE.finditer(hlo_text)}


def scope_path(op_name: str, buckets=DEFAULT_BUCKETS) -> List[str]:
    """The scopes of `buckets` an op_name lies in, outermost first:
    `jit(f)/layer_stack/while/body/transpose(jvp(mlp))/dot_general`
    -> [`layer_stack`, `mlp`]."""
    out: List[str] = []
    for c in op_name.rstrip(":").split("/"):
        c = _WRAPPERS.sub("", c)
        if c in buckets and c not in out:
            out.append(c)
    return out


def _bucket_of(op_name: str, buckets) -> Optional[str]:
    """The profile row of an op_name: its outermost scope, ` > ` the
    next one inside it; `layer_stack` only for what nothing inside it
    names."""
    path = scope_path(op_name, buckets)
    if len(path) > 1 and path[0] == LAYER_STACK:
        path = path[1:]
    return " > ".join(path[:2]) or None


def _latest_trace_json(trace_dir: str) -> str:
    paths = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"),
                  recursive=True),
        # (mtime, path): equal timestamps tie-break on the path, not on
        # the filesystem's enumeration order
        key=lambda p: (os.path.getmtime(p), p),
    )
    if not paths:
        raise FileNotFoundError(f"no *.trace.json.gz under {trace_dir}")
    return paths[-1]


def attribute_trace(
    trace_dir: str,
    hlo_text: str,
    buckets=DEFAULT_BUCKETS,
    steps: int = 1,
    manifest: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """Per-module measured seconds per step from a captured trace.

    Returns {"fwd": {row: s}, "bwd": {row: s}, "other": s, "total": s,
    "coverage": fraction of device time inside ANY scope of `buckets`}
    and, with the engine's collective `manifest`, "collectives":
    {kind: {sites, bytes, seconds, gbps}} per step (seconds and gbps
    None where no site's instruction has a time of its own)."""
    scope_of = hlo_scope_map(hlo_text)
    with gzip.open(_latest_trace_json(trace_dir)) as f:
        events = json.load(f)["traceEvents"]

    fwd: Dict[str, float] = {b: 0.0 for b in buckets}
    bwd: Dict[str, float] = {b: 0.0 for b in buckets}
    by_op: Dict[str, List[float]] = {}  # instruction -> [executions, s]
    other = total = 0.0
    for e in events:
        if e.get("ph") != "X":
            continue
        args = e.get("args") or {}
        op = args.get("hlo_op")
        if not op:
            continue  # host-side / bookkeeping event, not a device op
        dur = e.get("dur", 0) / 1e6  # us → s
        total += dur
        seen = by_op.setdefault(op, [0, 0.0])
        seen[0] += 1
        seen[1] += dur
        scope = scope_of.get(op)
        b = _bucket_of(scope, buckets) if scope else None
        if b is None:
            other += dur
        else:
            side = bwd if "transpose(" in scope else fwd
            side[b] = side.get(b, 0.0) + dur

    k = max(steps, 1)
    attributed = total - other
    out = {
        "fwd": {b: v / k for b, v in fwd.items()},
        "bwd": {b: v / k for b, v in bwd.items()},
        "other": other / k,
        "total": total / k,
        "coverage": attributed / total if total else 0.0,
    }
    if manifest is not None:
        out["collectives"] = _collective_rates(manifest, by_op, k)
    return out


def _collective_rates(manifest, by_op, steps) -> Dict[str, Dict[str, Any]]:
    """The manifest's bytes per kind and step (a site in a loop body
    counts once per execution the trace saw, else once) and the rate
    over the sites whose instruction ran under its own name."""
    out: Dict[str, Dict[str, Any]] = {}
    for name, kind, nbytes in manifest["sites"]:
        row = out.setdefault(kind, {"sites": 0, "bytes": 0.0,
                                    "timed_bytes": 0.0, "seconds": 0.0})
        runs, secs = by_op.get(name, (0, 0.0))
        row["sites"] += 1
        row["bytes"] += nbytes * (runs / steps if runs else 1)
        if secs > 0:
            row["timed_bytes"] += nbytes * runs / steps
            row["seconds"] += secs / steps
    for row in out.values():
        timed = row.pop("timed_bytes")
        row["gbps"] = timed / row["seconds"] / 1e9 if row["seconds"] else None
        row["seconds"] = row["seconds"] or None
    return out


def measure_module_latency(
    engine, batch, trace_dir: str, steps: int = 3,
    buckets=DEFAULT_BUCKETS,
) -> Dict[str, Any]:
    """Trace `steps` engine steps and attribute measured device time to
    the named scopes of the model and of the train step (the engine
    variant of the reference's hook-timed print_model_profile), and
    the engine's collective manifest to the same trace."""
    from ..utils.profiler import trace

    engine.train_batch(batch)  # compile + warm OUTSIDE the capture
    with trace(trace_dir):
        for _ in range(steps):
            engine.train_batch(batch)
    compiled = getattr(engine, "_train_compiled", None)
    if compiled is None:
        raise RuntimeError("engine has no compiled train step to map")
    return attribute_trace(trace_dir, compiled.as_text(), buckets=buckets,
                           steps=steps,
                           manifest=engine.collective_manifest())


def print_measured_profile(measured: Dict[str, Any], file=None) -> None:
    """Render the measured per-module table (the reference's latency
    column, but measured from the device trace rather than hooks) and,
    where the manifest was joined, what the step moves per kind."""
    import sys

    f = file or sys.stdout
    rows = [("module", "fwd ms", "bwd ms", "total ms")]
    for b in sorted(measured["fwd"], key=lambda b: b.split(" > ")[0]):
        fw = measured["fwd"][b] * 1e3
        bw = measured["bwd"].get(b, 0.0) * 1e3
        if fw or bw:
            rows.append((b, f"{fw:.3f}", f"{bw:.3f}", f"{fw + bw:.3f}"))
    rows.append(("(unattributed)", "", "",
                 f"{measured['other']*1e3:.3f}"))
    rows.append(("device total", "", "", f"{measured['total']*1e3:.3f}"))
    w = [max(len(r[i]) for r in rows) + 2 for i in range(4)]
    print("-" * sum(w), file=f)
    print("measured per-module device time "
          f"(coverage {measured['coverage']*100:.0f}%)", file=f)
    for r in rows:
        print("".join(c.rjust(w[i]) for i, c in enumerate(r)), file=f)
    for kind, c in (measured.get("collectives") or {}).items():
        rate = ("" if c["gbps"] is None else
                f", {c['seconds']*1e3:.3f} ms = {c['gbps']:.1f} GB/s")
        print(f"{kind}: {c['sites']} sites, {c['bytes']/1e6:.2f} MB a step"
              f"{rate}", file=f)
    print("-" * sum(w), file=f)
