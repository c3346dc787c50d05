"""Graph sanitizer: static verification of compiled-program properties.

On TPU the failure modes that silently destroy throughput are *static*
properties of the program: a `donate_argnums` buffer that never aliases
(the "donated" optimizer state is copied wholesale every step), a
declared PartitionSpec the SPMD partitioner drops (one replicated param
re-gathers per step), and abstract-signature churn that recompiles the
step in a loop. None of them raise; all of them are visible in the
compiled artifact. Like profiling/hlo.py (whose parser this extends),
every check here reads the artifact — ground truth, not invocation-side
bookkeeping.

Three checks:

  check_donation   — every donated buffer must appear as an input/output
                     alias in the LOWERED module (`tf.aliasing_output`
                     argument attributes; platform-independent, present
                     exactly when JAX matched the donated input to an
                     output). First customers: the train-step builders in
                     runtime/engine.py and HostOptimizer in
                     runtime/offload.py.
  check_sharding   — declared PartitionSpecs must survive SPMD
                     partitioning: the post-partitioning HLO's entry
                     parameters (per-shard dims + `sharding=` annotation,
                     keyed by op_name keypath) are diffed against the
                     specs derived in parallel/sharding.py.
  RecompileTracker — hashes abstract call signatures (tree structure +
                     shape/dtype/weak_type per leaf) across calls and
                     classifies every cache miss: weak-type drift,
                     python-scalar promotion, shape churn, dtype churn.

`DeepSpeedTPUEngine.sanitize()` wires all three against the real train
step. Findings are plain dataclasses (analysis/report.py).
"""

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..profiling.hlo import parse_entry_parameters
from .report import Finding, SanitizerReport

__all__ = [
    "check_donation",
    "check_sharding",
    "RecompileTracker",
    "abstract_signature",
    "SanitizerReport",
]


# ----------------------------------------------------------------------
# check (a): donation aliasing
# ----------------------------------------------------------------------

# `{output_index}: (param_number, {param_index}, kind)` entries on the
# compiled HloModule header line. This table is THE donation ground
# truth: the lowered module's donation attrs (`tf.aliasing_output` /
# `jax.buffer_donor`) are intent, the decision — including aliases XLA
# establishes that lowering could not, and donations XLA drops — lands
# here. The lowered signature is also DCE'd (unused donated leaves have
# no argument at all), so flat-index alignment against it is unsound;
# entry parameters are matched by their op_name keypath instead.
_HLO_ALIAS_RE = re.compile(r"\{[^{}]*\}:\s*\((\d+),")


def _compiled_alias_info(compiled) -> Tuple[set, Dict[str, int]]:
    """(param numbers aliased to an output, op_name -> param number) of
    one compiled module."""
    text = compiled.as_text()
    header = text[: text.find("\n")]
    at = header.find("input_output_alias={")
    aliased = set()
    if at != -1:
        aliased = {int(n) for n in _HLO_ALIAS_RE.findall(header[at:])}
    by_name = {
        r["op_name"]: r["index"]
        for r in parse_entry_parameters(text)
        if r["op_name"] is not None
    }
    return aliased, by_name


def _leaf_labels(arg: Any, argname: str) -> List[str]:
    flat, _ = jax.tree_util.tree_flatten_with_path(arg)
    return [f"{argname}{jax.tree_util.keystr(p)}" for p, _ in flat]


def check_donation(
    fn: Any,
    args: Sequence[Any],
    donate_argnums: Sequence[int],
    kwargs: Optional[Dict] = None,
    argnames: Optional[Sequence[str]] = None,
    label: str = "jit",
    lowered: Any = None,
    compiled: Any = None,
) -> SanitizerReport:
    """Verify every `donate_argnums` buffer actually aliases an output.

    `fn` is a jitted callable (its own donate_argnums apply) or a plain
    function (wrapped here with `donate_argnums`). Ground truth is the
    compiled module's `input_output_alias` table (compiled here from
    `args` when not passed in). Per donated leaf, located among the
    entry parameters by its op_name keypath (`argname` + jax keystr —
    pass `argnames` matching the function's real parameter names):

      param present, in alias table — donation honored: OK
      param present, NOT in table   — donated but silently COPIED every
                                      call (error): double residency +
                                      a full extra HBM write
      param absent                  — donated but unused: the buffer is
                                      freed, not copied (no finding)
    """
    report = SanitizerReport(label=f"{label}/donation")
    if compiled is None:
        if lowered is None:
            # lowered only, never executed — the "donated buffers were
            # not usable" warning is the event S001 structures
            jit_fn = fn if hasattr(fn, "lower") else jax.jit(
                fn, donate_argnums=tuple(donate_argnums))
            import warnings

            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                lowered = jit_fn.lower(*args, **(kwargs or {}))
        compiled = lowered.compile()
    hlo_aliased, hlo_params = _compiled_alias_info(compiled)
    if not hlo_params:
        report.findings.append(Finding(
            rule="S001", path=label, line=0, severity="warning",
            message="compiled entry parameters carry no op_name metadata; "
                    "donation unverifiable",
            fix_hint="compile with default XLA metadata (no stripping)",
        ))
        return report
    if argnames is None:
        import inspect

        try:
            argnames = list(inspect.signature(fn).parameters)
        except (TypeError, ValueError):
            argnames = []
    for argnum in donate_argnums:
        if argnum >= len(args):
            continue
        name = (argnames[argnum] if argnum < len(argnames)
                else f"arg{argnum}")
        labels = _leaf_labels(args[argnum], name)
        leaves = jax.tree_util.tree_leaves(args[argnum])
        for leaf_label, leaf in zip(labels, leaves):
            pnum = hlo_params.get(leaf_label)
            if pnum is None or pnum in hlo_aliased:
                continue  # absent = unused/freed; in table = honored
            shape = tuple(getattr(leaf, "shape", ()))
            dtype = getattr(leaf, "dtype", None)
            nbytes = int(np.prod(shape, dtype=np.int64)) * (
                np.dtype(dtype).itemsize if dtype is not None else 1)
            report.findings.append(Finding(
                rule="S001", path=leaf_label, line=0, severity="error",
                message=(
                    f"donated buffer {leaf_label} ({dtype}{list(shape)}, "
                    f"{nbytes} bytes) is NOT in the compiled module's "
                    "input_output_alias table — the donation is silently "
                    "ignored and the buffer copied"),
                fix_hint=(
                    "give the program an output with matching "
                    "shape/dtype/sharding, or remove the buffer from "
                    "donate_argnums"),
            ))
    return report


# ----------------------------------------------------------------------
# check (b): PartitionSpec survival
# ----------------------------------------------------------------------

def _spec_axis_factors(spec, mesh, ndim: int) -> List[int]:
    """Per-dim sharding factor a PartitionSpec requests on `mesh`
    (axes of size 1 contribute nothing — nothing to survive)."""
    factors = [1] * ndim
    for i, entry in enumerate(tuple(spec)[:ndim]):
        axes = (entry,) if isinstance(entry, str) else tuple(entry or ())
        f = 1
        for a in axes:
            f *= int(mesh.shape.get(a, 1))
        factors[i] = f
    return factors


def check_sharding(
    compiled: Any,
    expected_specs: Any,
    example_tree: Any,
    mesh: Any,
    argname: str = "state",
    label: str = "jit",
) -> SanitizerReport:
    """Diff declared PartitionSpecs against the post-partitioning HLO.

    `expected_specs` is a pytree of PartitionSpec with the same structure
    as `example_tree` (whose leaves provide the GLOBAL shapes). Each leaf
    is located in the compiled program's entry parameters by its op_name
    keypath (`argname` + jax keystr); a parameter whose per-shard dim
    still equals the global dim on a declared-sharded axis lost its spec
    to the partitioner — it is materialized replicated and re-gathered
    every step.

    Lowering mode matters: compile from UNCOMMITTED avals
    (ShapeDtypeStruct without sharding) to audit what constraint
    propagation really assigns — a dropped/overridden in-program
    constraint shows up as a replicated parameter. Compiling from
    committed arrays audits the storage layout itself (the entry keeps
    the arrays' shardings; in-program re-gathers are a collective_volumes
    question, not a parameter one).
    """
    from jax.sharding import PartitionSpec as P

    report = SanitizerReport(label=f"{label}/sharding")
    params = {
        r["op_name"]: r
        for r in parse_entry_parameters(compiled.as_text())
        if r["op_name"] is not None
    }
    flat_specs, _ = jax.tree_util.tree_flatten_with_path(
        expected_specs, is_leaf=lambda x: isinstance(x, P))
    leaves = jax.tree_util.tree_leaves(example_tree)
    if len(leaves) != len(flat_specs):
        report.findings.append(Finding(
            rule="S002", path=label, line=0, severity="warning",
            message=(
                f"expected_specs has {len(flat_specs)} leaves but the "
                f"example tree has {len(leaves)}; structures must match"),
            fix_hint="pass the spec tree matching the example pytree",
        ))
        return report
    for (path, spec), leaf in zip(flat_specs, leaves):
        shape = tuple(getattr(leaf, "shape", ()))
        factors = _spec_axis_factors(spec, mesh, len(shape))
        if all(f == 1 for f in factors):
            continue  # nothing declared (or axes of size 1)
        key = f"{argname}{jax.tree_util.keystr(path)}"
        rec = params.get(key)
        if rec is None:
            report.findings.append(Finding(
                rule="S002", path=key, line=0, severity="warning",
                message=(
                    f"declared-sharded parameter {key} not found among the "
                    "compiled program's entry parameters (dead-code "
                    "eliminated or renamed); sharding unverifiable"),
                fix_hint="check the program actually consumes this leaf",
            ))
            continue
        dims = rec["dims"]
        if len(dims) != len(shape):
            continue  # layout change (e.g. tupled) — cannot diff dims
        dropped = [
            i for i, f in enumerate(factors)
            if f > 1 and shape[i] > 1 and dims[i] == shape[i]
        ]
        if dropped:
            want = [shape[i] // factors[i] for i in range(len(shape))]
            report.findings.append(Finding(
                rule="S002", path=key, line=0, severity="error",
                message=(
                    f"PartitionSpec {tuple(spec)} for {key} did not survive "
                    f"partitioning on dim(s) {dropped}: per-shard shape is "
                    f"{list(dims)} (expected {want}; "
                    f"sharding={{{rec['sharding']}}})"),
                fix_hint=(
                    "a with_sharding_constraint inside the program (or a "
                    "replicated consumer) overrides the declared spec; "
                    "align the constraint with parallel/sharding.py rules"),
            ))
    return report


# ----------------------------------------------------------------------
# check (c): recompilation hazards
# ----------------------------------------------------------------------

_PY_SCALARS = (bool, int, float, complex)


def _leaf_sig(leaf: Any) -> Tuple:
    """(shape, dtype, weak_type, is_python_scalar) of one call leaf."""
    if isinstance(leaf, _PY_SCALARS):
        aval = jax.typeof(leaf)
        return (tuple(aval.shape), str(aval.dtype), True, True)
    aval = getattr(leaf, "aval", None)
    if aval is not None:
        return (tuple(aval.shape), str(aval.dtype),
                bool(getattr(aval, "weak_type", False)), False)
    arr = np.asarray(leaf)
    return (tuple(arr.shape), str(arr.dtype), False, False)


def abstract_signature(args: Any, kwargs: Optional[Dict] = None) -> Tuple:
    """Hashable abstract signature of one call: per-leaf keypath +
    shape/dtype/weak_type — exactly what jit's cache keys on (minus
    static args/devices)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path((args, kwargs or {}))
    return (
        str(treedef),
        tuple((jax.tree_util.keystr(p),) + _leaf_sig(l) for p, l in flat),
    )


class RecompileTracker:
    """Tracks abstract signatures across calls and reports cache-miss
    causes. One finding per NEW signature after the first (per name):
    each is one recompilation of that program.

    >>> t = RecompileTracker()
    >>> t.record("step", (jnp.float32(1.0),))   # first call: baseline
    >>> t.record("step", (1.0,))                # weak-type drift -> miss
    >>> t.report().findings
    """

    def __init__(self, max_entries: int = 64):
        self._sigs: Dict[str, List[Tuple]] = {}
        self._findings: List[Finding] = []
        self._max = max_entries

    def record(self, name: str, args: Any,
               kwargs: Optional[Dict] = None) -> bool:
        """Returns True when this signature was already seen (cache hit)."""
        sig = abstract_signature(args, kwargs)
        seen = self._sigs.setdefault(name, [])
        if sig in seen:
            return True
        if seen:
            self._findings.append(self._classify(name, seen, sig))
        if len(seen) < self._max:
            seen.append(sig)
        return False

    def _classify(self, name: str, seen: List[Tuple], sig: Tuple) -> Finding:
        treedef, leaves = sig
        best = None
        for old_treedef, old_leaves in reversed(seen):
            if old_treedef == treedef and len(old_leaves) == len(leaves):
                best = old_leaves
                break
        if best is None:
            return Finding(
                rule="S003", path=name, line=0, severity="warning",
                message=f"recompile of {name!r}: call tree STRUCTURE changed",
                fix_hint="keep the batch pytree structure stable across steps",
            )
        weak, promo, shapes, dtypes = [], [], [], []
        for (kp, shp, dt, wk, py), (_, oshp, odt, owk, opy) in zip(
                leaves, best):
            if shp == oshp and dt == odt and wk != owk:
                (promo if (py or opy) else weak).append(kp)
            elif shp == oshp and dt != odt:
                (promo if (py or opy) else dtypes).append(kp)
            elif shp != oshp:
                shapes.append((kp, oshp, shp))
        if weak:
            return Finding(
                rule="S003", path=name, line=0, severity="error",
                message=(
                    f"recompile of {name!r}: weak-type drift on "
                    f"{weak[:3]} (same shape/dtype, weak_type flipped)"),
                fix_hint=(
                    "normalize scalars before the call: "
                    "jnp.asarray(x, dtype) or x.astype(dtype) makes the "
                    "weak_type stable"),
            )
        if promo:
            return Finding(
                rule="S003", path=name, line=0, severity="error",
                message=(
                    f"recompile of {name!r}: python-scalar promotion on "
                    f"{promo[:3]} — a host int/float traced as a fresh "
                    "weakly-typed constant"),
                fix_hint=(
                    "pass scalars as jnp arrays with an explicit dtype, or "
                    "hoist them to static closure values"),
            )
        if shapes:
            kp, old, new = shapes[0]
            return Finding(
                rule="S003", path=name, line=0, severity="warning",
                message=(
                    f"recompile of {name!r}: shape churn on {kp} "
                    f"{list(old)} -> {list(new)}"
                    + (f" (+{len(shapes)-1} more leaves)"
                       if len(shapes) > 1 else "")),
                fix_hint=(
                    "pad/bucket variable dims (inference/engine._bucket "
                    "pattern) so the compile cache stays bounded"),
            )
        if dtypes:
            return Finding(
                rule="S003", path=name, line=0, severity="warning",
                message=(
                    f"recompile of {name!r}: dtype churn on {dtypes[:3]}"),
                fix_hint="cast inputs to a fixed dtype at the boundary",
            )
        return Finding(
            rule="S003", path=name, line=0, severity="info",
            message=f"recompile of {name!r}: signature changed "
                    "(cause not classified)",
            fix_hint="diff abstract_signature() outputs across calls",
        )

    @property
    def findings(self) -> List[Finding]:
        return list(self._findings)

    def n_signatures(self, name: str) -> int:
        return len(self._sigs.get(name, ()))

    def report(self) -> SanitizerReport:
        return SanitizerReport(findings=list(self._findings),
                               label="recompile-tracker")

    def reset(self) -> None:
        self._sigs.clear()
        self._findings.clear()
