"""Determinism analyzer: RNG discipline, reassociation, and ordering.

Every gate in this tree rests on bitwise loss identity, token-identical
serving outputs, or byte-identical ledgers — yet until this pass
nothing *statically* proved the properties those pins depend on. PR 14
only caught the layout-dependent router-RNG bug (EP=1 != EP=N by
~1e-3: threefry is not partitionable, so a draw laid out across the
'expert' mesh axis computes DIFFERENT BITS per layout) because a
bitwise test happened to cover it. These checks fence that bug class —
and its host-side and serving-side siblings — at compile/lint time.

Rules
  D001  layout-dependent PRNG: a draw op (rng-bit-generator, threefry
        custom-call, or a call into jax's lowered rng helpers) in the
        PRE-OPT HLO whose result carries a mesh-tiled sharding, whose
        seed operand arrives mesh-tiled (provenance resolved through
        tuple packaging), or which sits inside a shard_map manual
        context — without a replicated pin on the draw (the
        `_replicated_draw` idiom: `with_sharding_constraint(x, P())`,
        moe/sharded_moe.py). The PR-14 bug class, caught before any
        step runs.
  D002  reassociation hazard on a bitwise-pinned program: a cross-shard
        floating-point ADDITIVE reduce collective (all-reduce /
        reduce-scatter with an `add` combiner) whose replica groups
        span a mesh axis the program's bitwise pin declares
        LAYOUT-VARYING — re-laying-out that axis changes the partial-
        sum order, so the pinned identity holds only by accident.
        Flagged ONLY for programs registered in the bitwise-pin
        registry (BITWISE_PINS); a registered program may WAIVE a
        specific reduce class with a committed reason (the waiver is
        the reviewed acceptance of the hazard, usually because a
        dynamic gate pins the identity empirically).
  D003  host-side ordering nondeterminism (AST): unsorted
        `os.listdir`/`glob`/`iterdir`/`scandir` enumeration, sorts
        keyed on mtime alone (ties fall back to enumeration order),
        `json.dump` without `sort_keys=True` (committed-artifact
        byte stability), iteration over a set, and — in
        `scripts/` capture paths — `time.time()`/unseeded
        `random`/`np.random.default_rng()`.
  D004  serving draw-key discipline (AST): a sampled draw in the
        scheduler/router/sampling/engine serving paths must key on
        (seed, stream, position) — concretely, its key expression must
        derive through `jax.random.fold_in` (the position term; the
        stream term is the per-slot key fan-out) — and must never fall
        back to process-global or wall-clock entropy. The invariant
        every requeue-for-recompute fallback silently assumes.

D003/D004 honor the ds-lint pragma syntax (`# ds-lint: ok D003 <why>`
on the offending line or the line above); D001/D002 have no source
anchor, so their override story is the registry: `allow_manual` for
deliberate per-shard draws, `waived` reduce classes for accepted
reassociation. Gate: `scripts/ds_gate.py determinism` against the committed
DETERMINISM.json — D findings have NO baseline (any active finding is
red in every mode); only the per-program rng-op/reduce-class ledger is
pinned.
"""

import ast
import dataclasses
import itertools
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from .report import Finding, LintReport, SanitizerReport, site_keys

__all__ = [
    "D_RULES",
    "BitwisePin",
    "BITWISE_PINS",
    "pin_for",
    "check_rng_discipline",
    "check_reassociation",
    "check_host_ordering",
    "check_draw_keys",
    "program_determinism",
    "rng_ledger",
    "reduce_ledger",
    "ORDERING_SCOPE",
    "DRAW_KEY_SCOPE",
]

D_RULES = {
    "D001": "layout-dependent PRNG: mesh-sharded threefry draw without "
            "a replicated pin",
    "D002": "fp additive reduce over a layout-varying mesh axis on a "
            "bitwise-pinned program",
    "D003": "host-side ordering nondeterminism feeding a committed "
            "artifact",
    "D004": "serving draw not keyed on (seed, stream, position), or "
            "wall-clock/global entropy in a serving path",
}

# repo-relative D003 scope: every committed-artifact emitter — the
# capture scripts, the analyzers that write baselines, the checkpoint
# tag machinery, and the trace-artifact reader
ORDERING_SCOPE = (
    "scripts",
    "deepspeed_tpu/analysis",
    "deepspeed_tpu/runtime/checkpoint.py",
    "deepspeed_tpu/profiling/latency.py",
)

# repo-relative D004 scope: the serving paths whose draws the
# requeue-for-recompute fallback replays
DRAW_KEY_SCOPE = (
    "deepspeed_tpu/inference/sampling.py",
    "deepspeed_tpu/inference/engine.py",
    "deepspeed_tpu/inference/scheduler.py",
    "deepspeed_tpu/inference/router.py",
)


# ----------------------------------------------------------------------
# bitwise-pin registry (D002 input)
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BitwisePin:
    """What bitwise identity one canonical program declares, and which
    mesh axes that identity re-lays-out.

    program: ledger key (ds_budget canonical-program naming)
    pins: human-readable identity names (doc + ledger, not semantics)
    mesh_axes: ordered (name, size) pairs — row-major device order,
        the layout replica groups are matched against
    varying_axes: axes the pinned identity changes across (EP=1 vs
        EP=N varies 'expert'; the P/V pipeline pin varies 'pipe').
        A fp additive reduce spanning one of these is a D002 hazard.
    waived: ((reduce-class key, reason), ...) — reviewed acceptances;
        the class key is `op:kind:dtype:axes=a|b` as reduce_ledger
        spells it. Waivers are committed in DETERMINISM.json, so
        growing one is a reviewed diff, never a silent drift."""

    program: str
    pins: Tuple[str, ...] = ("rerun_bitwise",)
    mesh_axes: Tuple[Tuple[str, int], ...] = ()
    varying_axes: Tuple[str, ...] = ()
    waived: Tuple[Tuple[str, str], ...] = ()

    def as_ledger(self) -> Dict:
        return {
            "pins": list(self.pins),
            "mesh_axes": [[n, s] for n, s in self.mesh_axes],
            "varying_axes": list(self.varying_axes),
            "waived": [[k, r] for k, r in self.waived],
        }


# The canonical programs' declared identities (docs/determinism.md).
# Waivers name the accepted hazard AND the dynamic gate that pins the
# identity empirically — the capture -> check -> override workflow.
BITWISE_PINS: Dict[str, BitwisePin] = {
    "train_step": BitwisePin(
        program="train_step",
        pins=("rerun_bitwise",),
        mesh_axes=(("data", 4), ("model", 2)),
        varying_axes=(),
    ),
    "train_step_moe": BitwisePin(
        program="train_step_moe",
        pins=("loss_bitwise_across_ep",),
        mesh_axes=(("data", 2), ("expert", 2), ("model", 2)),
        varying_axes=("expert",),
        waived=(
            ("all-reduce:add:f32:axes=expert",
             "shared (non-expert) params are replicated over the "
             "expert axis, so their grad reduce treats it as extra "
             "data parallelism; the EP=1 == EP=N loss identity these "
             "sums feed is pinned dynamically (tests/test_moe.py "
             "ep-vs-dp bitwise parity)"),
            ("all-reduce:add:f32:axes=data|expert",
             "fused data+expert grad reduce for shared params — same "
             "class as axes=expert, same dynamic pin"),
            ("all-reduce:add:f32:axes=expert|model",
             "the installed XLA fuses the shared-param expert reduce "
             "with the TP partial-sum reduce into one all-reduce — "
             "same class as axes=expert, same dynamic pin (also "
             "scripts/ds_gate.py moe ep_layout_training_invariant)"),
        ),
    ),
    "train_step_pipe3d": BitwisePin(
        program="train_step_pipe3d",
        pins=("loss_bitwise_across_pv",),
        mesh_axes=(("pipe", 2), ("data", 2), ("model", 2)),
        varying_axes=("pipe",),
        waived=(
            ("all-reduce:add:f32:axes=pipe",
             "stage-replicated grads and the microbatch loss "
             "accumulator reduce over the pipe axis; the V-schedule "
             "loss parity these sums feed is pinned dynamically "
             "(tests/test_pipeline.py interleave-vs-flat parity)"),
            ("all-reduce:add:f32:axes=pipe|model",
             "fused pipe+model reduce of the scalar loss/z-stat term "
             "— same class as axes=pipe, same dynamic pin"),
            ("all-reduce:add:f32:axes=pipe|data|model",
             "the global grad-norm's scalar sum of squares, one fused "
             "reduce over every axis since ZeRO's tile rule stacks "
             "'data' on the vocab dim of the embedding, already over "
             "(model, pipe) there (PR 24): a reported metric on this "
             "unclipped program, not an input of the loss — same "
             "class as axes=pipe, same dynamic pin"),
        ),
    ),
    "serving_decode_w8": BitwisePin(
        program="serving_decode_w8",
        pins=("token_identity_across_tp",),
        mesh_axes=(("model", 8),),
        varying_axes=("model",),
    ),
    "serving_sample_w8": BitwisePin(
        program="serving_sample_w8",
        pins=("replay_bitwise",),
        mesh_axes=(),
        varying_axes=(),
    ),
}


def pin_for(label: str,
            mesh_axes: Optional[Sequence[Tuple[str, int]]] = None,
            ) -> BitwisePin:
    """The registered pin for `label`, or a default rerun-only pin
    (varying_axes=() — D002 stays quiet on unregistered programs, per
    the registry contract). `mesh_axes` overrides the registered
    layout with the program's ACTUAL mesh (engine.sanitize passes its
    own — a user mesh need not match the canonical one)."""
    pin = BITWISE_PINS.get(label)
    if pin is None:
        pin = BitwisePin(program=label,
                         mesh_axes=tuple(mesh_axes or ()))
    elif mesh_axes is not None:
        pin = dataclasses.replace(pin, mesh_axes=tuple(mesh_axes))
    return pin


# ----------------------------------------------------------------------
# D001: layout-dependent PRNG (pre-opt HLO level)
# ----------------------------------------------------------------------

def check_rng_discipline(hlo_text: str, label: str = "program",
                         allow_manual: bool = False) -> SanitizerReport:
    """D001 over one program's (preferably pre-opt) HLO text.

    A DRAW (key-derivation ops — split/fold_in — compute the same bits
    on every layout; only draws consume the non-partitionable threefry
    counter) is a finding when its result is pinned to a mesh-TILED
    sharding, its seed operand arrives tiled, or it executes inside a
    shard_map manual context (unless `allow_manual` — deliberate
    per-shard draws whose keys are per-shard by construction). A
    replicated pin on the draw (`_replicated_draw` /
    `with_sharding_constraint(x, P())`) is the fix and the
    all-clear."""
    from ..profiling.hlo import parse_hlo_rng_ops

    rep = SanitizerReport(label=label)
    for rec in parse_hlo_rng_ops(hlo_text):
        if rec["kind"] != "draw":
            continue
        where = f"{rec['computation']}/{rec['name']} ({rec['algo']})"
        if rec["manual"] and not allow_manual:
            rep.findings.append(Finding(
                rule="D001", path=label, line=0, severity="error",
                message=f"rng draw {where} inside a shard_map manual "
                        "context: each shard advances its own threefry "
                        "counter, so the bits depend on the mesh layout",
                fix_hint="hoist the draw above the shard_map (replicated"
                         " key, broadcast the bits), or register the "
                         "program with allow_manual=True if per-shard "
                         "draws are the design (document WHY the keys "
                         "are layout-stable)"))
        elif rec["sharding_class"] == "tiled":
            rep.findings.append(Finding(
                rule="D001", path=label, line=0, severity="error",
                message=f"rng draw {where} result constrained to mesh-"
                        f"tiled sharding {{{rec['sharding']}}}: threefry"
                        " is not partitionable — each layout computes "
                        "different bits (the PR-14 EP=1 != EP=N class)",
                fix_hint="pin the draw replicated: wrap it in the "
                         "_replicated_draw idiom (jax.lax."
                         "with_sharding_constraint(draw, P()))"))
        elif rec["sharding_class"] in ("replicated", "maximal"):
            continue
        elif rec["seed_sharding_class"] == "tiled":
            rep.findings.append(Finding(
                rule="D001", path=label, line=0, severity="error",
                message=f"rng draw {where} seed operand "
                        f"({rec['seed']}) arrives mesh-tiled "
                        f"{{{rec['seed_sharding']}}}: per-shard key "
                        "slices make the draw layout-dependent",
                fix_hint="replicate the key before drawing "
                         "(with_sharding_constraint(key, P())), then "
                         "pin the draw result replicated too"))
    return rep


def rng_ledger(hlo_text: str) -> Dict[str, int]:
    """Per-class rng-op counts for one program's HLO text — the D001
    half of the committed DETERMINISM.json ledger. Class key:
    `form:algo:kind:sharding_class[:manual]`."""
    from ..profiling.hlo import parse_hlo_rng_ops

    counts: Dict[str, int] = {}
    for rec in parse_hlo_rng_ops(hlo_text):
        key = (f"{rec['form']}:{rec['algo']}:{rec['kind']}:"
               f"{rec['sharding_class']}")
        if rec["manual"]:
            key += ":manual"
        counts[key] = counts.get(key, 0) + 1
    return dict(sorted(counts.items()))


# ----------------------------------------------------------------------
# D002: reassociation hazards on bitwise-pinned programs
# ----------------------------------------------------------------------

def _axis_group_set(mesh_axes: Sequence[Tuple[str, int]],
                    subset: Sequence[str]) -> frozenset:
    """The replica groups (as a frozenset of frozensets of device ids)
    of a collective spanning exactly `subset` of `mesh_axes`, under
    row-major device ordering."""
    names = [n for n, _ in mesh_axes]
    sizes = [s for _, s in mesh_axes]
    groups: Dict[tuple, List[int]] = {}
    total = 1
    for s in sizes:
        total *= s
    for dev in range(total):
        coords, rem = [], dev
        for s in reversed(sizes):
            coords.append(rem % s)
            rem //= s
        coords.reverse()
        fixed = tuple(c for n, c in zip(names, coords) if n not in subset)
        groups.setdefault(fixed, []).append(dev)
    return frozenset(frozenset(g) for g in groups.values())


def match_group_axes(groups: List[List[int]],
                     mesh_axes: Sequence[Tuple[str, int]],
                     ) -> Optional[Tuple[str, ...]]:
    """Which mesh axes one collective's replica groups span: the
    (unique, order-preserved) axis subset whose row-major groups equal
    `groups` as sets. () for unstated/flat groups (spans the world);
    None when no subset matches (a layout the registry's mesh cannot
    express — treated as spanning everything)."""
    if not groups:
        return ()
    if not mesh_axes:
        return None
    names = [n for n, _ in mesh_axes]
    gset = frozenset(frozenset(g) for g in groups)
    for r in range(1, len(names) + 1):
        for subset in itertools.combinations(names, r):
            if _axis_group_set(mesh_axes, subset) == gset:
                return subset
    return None


def _reduce_class(rec: Dict, axes: Optional[Tuple[str, ...]],
                  world: Sequence[str]) -> str:
    if axes is None:
        spelled = "unmatched"
    elif axes == ():
        spelled = "|".join(world) if world else "world"
    else:
        spelled = "|".join(axes)
    return f"{rec['op']}:{rec['reduce_kind']}:{rec['dtype']}:axes={spelled}"


def check_reassociation(compiled_text: str, pin: BitwisePin,
                        label: str = "program") -> SanitizerReport:
    """D002 over one COMPILED program (post-partitioning text — where
    the SPMD partitioner's collectives and replica groups live),
    against the program's bitwise pin.

    Only fp ADDITIVE reduce collectives can reassociate; max/min/and/or
    select and integer adds are exact. A hazard needs its groups to
    span a pin-declared varying axis (or to fail to match the
    registered mesh at all — conservatively treated as spanning
    everything) and to not carry a committed waiver."""
    from ..profiling.hlo import FLOAT_DTYPES, parse_hlo_reduce_collectives

    rep = SanitizerReport(label=label)
    if not pin.varying_axes:
        return rep  # unpinned-across-layouts: nothing to protect
    world = [n for n, _ in pin.mesh_axes]
    waived = {k for k, _ in pin.waived}
    for rec in parse_hlo_reduce_collectives(compiled_text):
        if rec["reduce_kind"] not in ("add",) or \
                rec["dtype"] not in FLOAT_DTYPES:
            continue
        axes = match_group_axes(rec["groups"], pin.mesh_axes)
        spanned = set(world if axes in (None, ()) else axes)
        if not (spanned & set(pin.varying_axes)):
            continue
        key = _reduce_class(rec, axes, world)
        if key in waived:
            continue
        rep.findings.append(Finding(
            rule="D002", path=label, line=0, severity="error",
            message=f"{rec['name']}: {key} — a floating-point additive "
                    f"reduce spanning layout-varying axis(es) "
                    f"{sorted(spanned & set(pin.varying_axes))} on a "
                    f"program that pins {list(pin.pins)}: re-laying-out "
                    "that axis reorders the partial sums, so the "
                    "pinned bitwise identity holds only by accident",
            fix_hint="make the reduction layout-invariant (fixed tree "
                     "order / integer or compensated accumulation), "
                     "drop the varying axis from the pin, or commit a "
                     "waiver for this reduce class in BITWISE_PINS "
                     "with the dynamic gate that covers it"))
    return rep


def reduce_ledger(compiled_text: str, pin: BitwisePin) -> Dict[str, int]:
    """Per-class fp-additive-reduce counts for one compiled program —
    the D002 half of the DETERMINISM.json ledger (every class is
    recorded, hazardous or not: a class APPEARING is a reviewed
    diff)."""
    from ..profiling.hlo import FLOAT_DTYPES, parse_hlo_reduce_collectives

    world = [n for n, _ in pin.mesh_axes]
    counts: Dict[str, int] = {}
    for rec in parse_hlo_reduce_collectives(compiled_text):
        if rec["reduce_kind"] not in ("add",) or \
                rec["dtype"] not in FLOAT_DTYPES:
            continue
        key = _reduce_class(
            rec, match_group_axes(rec["groups"], pin.mesh_axes), world)
        counts[key] = counts.get(key, 0) + 1
    return dict(sorted(counts.items()))


def program_determinism(preopt_text: Optional[str],
                        compiled_text: Optional[str],
                        label: str,
                        pin: Optional[BitwisePin] = None,
                        allow_manual: bool = False,
                        ) -> Tuple[SanitizerReport, Dict]:
    """(merged D001+D002 report, ledger entry) for one program — the
    unit the ds_determinism gate captures per canonical program and
    engine.sanitize() folds into its report."""
    from .report import merge_reports

    pin = pin or pin_for(label)
    reports, entry = [], {"pin": pin.as_ledger()}
    if preopt_text:
        reports.append(check_rng_discipline(
            preopt_text, label=label, allow_manual=allow_manual))
        entry["rng_ops"] = rng_ledger(preopt_text)
    if compiled_text:
        reports.append(check_reassociation(compiled_text, pin,
                                           label=label))
        entry["reduce_classes"] = reduce_ledger(compiled_text, pin)
    return merge_reports(label, *reports), entry


# ----------------------------------------------------------------------
# D003: host-side ordering nondeterminism (AST level)
# ----------------------------------------------------------------------

_ENUM_CALLS = ("listdir", "scandir", "glob", "iglob", "iterdir",
               "rglob")
_WALLCLOCK_CALLS = ("time.time", "datetime.now", "datetime.utcnow",
                    "datetime.today", "datetime.datetime.now",
                    "datetime.datetime.utcnow")
_GLOBAL_RANDOM_FNS = ("random", "randint", "randrange", "shuffle",
                      "choice", "choices", "sample", "uniform",
                      "gauss")


def _dotted(node: ast.AST) -> str:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _is_capture_file(relpath: str) -> bool:
    return os.path.basename(relpath).startswith("ds_") and \
        relpath.replace(os.sep, "/").startswith("scripts/")


def _mtime_only_key(key: ast.AST) -> bool:
    """A sort key that is getmtime (or st_mtime) ALONE — ties fall
    back to enumeration order. A lambda returning a tuple with a
    filename tie-break is the fix and does not match."""
    if _dotted(key).endswith(("getmtime", "getctime", "getatime")):
        return True
    if isinstance(key, ast.Lambda):
        body = key.body
        if isinstance(body, ast.Call) and \
                _dotted(body.func).endswith(
                    ("getmtime", "getctime", "getatime")):
            return True
        if isinstance(body, ast.Attribute) and \
                body.attr in ("st_mtime", "st_ctime", "st_atime"):
            return True
    return False


def _d003_findings(tree: ast.Module, relpath: str) -> List[Finding]:
    findings: List[Finding] = []
    # every node textually inside a sorted(...) call is order-safe
    inside_sorted: set = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and \
                _dotted(node.func).split(".")[-1] == "sorted":
            for a in node.args:
                inside_sorted.update(id(n) for n in ast.walk(a))
    capture = _is_capture_file(relpath)

    def emit(rule_msg: str, node: ast.AST, hint: str) -> None:
        findings.append(Finding(
            rule="D003", path=relpath,
            line=getattr(node, "lineno", 0), severity="error",
            message=rule_msg, fix_hint=hint))

    for node in ast.walk(tree):
        if isinstance(node, (ast.For, ast.comprehension)):
            it = node.iter
            if isinstance(it, (ast.Set, ast.SetComp)) or (
                    isinstance(it, ast.Call)
                    and _dotted(it.func) == "set"):
                emit("iteration over a set: element order follows the "
                     "hash seed, so anything it feeds (committed JSON, "
                     "ledger rows) differs across interpreter runs",
                     it, "iterate sorted(...) over the set")
            continue
        if not isinstance(node, ast.Call):
            continue
        callee = _dotted(node.func)
        short = callee.split(".")[-1]
        if short in _ENUM_CALLS and id(node) not in inside_sorted:
            emit(f"{callee}() without sorted(): filesystem enumeration "
                 "order is kernel/filesystem-dependent — any artifact "
                 "or tag decision it feeds differs across runs",
                 node, "wrap the enumeration in sorted(...)")
        if short in ("sort", "sorted"):
            for kw in node.keywords:
                if kw.arg == "key" and _mtime_only_key(kw.value):
                    emit("sort keyed on mtime alone: equal timestamps "
                         "(same-second saves, copied trees) leave the "
                         "order to the underlying enumeration",
                         node, "tie-break deterministically: key=lambda "
                               "p: (os.path.getmtime(p), p)")
        if callee == "json.dump" and not any(
                kw.arg == "sort_keys" for kw in node.keywords):
            emit("json.dump without sort_keys=True: dict order follows "
                 "insertion (and any set/hash influence upstream), so "
                 "the committed artifact is not byte-stable",
                 node, "pass sort_keys=True")
        if capture:
            if callee in _WALLCLOCK_CALLS:
                emit(f"{callee}() in a capture path: wall-clock values "
                     "in a committed artifact make every capture a "
                     "diff", node,
                     "drop the timestamp from the artifact, or move it "
                     "to stderr logging")
            if callee in ("random.Random", "np.random.default_rng",
                          "numpy.random.default_rng") and not node.args:
                emit(f"unseeded {callee}() in a capture path: the "
                     "ledger inherits process entropy", node,
                     "pass an explicit seed")
            if callee.startswith("random.") and \
                    short in _GLOBAL_RANDOM_FNS:
                emit(f"{callee}() uses the process-global RNG in a "
                     "capture path", node,
                     "draw from a seeded random.Random(seed) instance")
    return findings


# ----------------------------------------------------------------------
# D004: serving draw-key discipline (AST level)
# ----------------------------------------------------------------------

_JAX_DRAW_FNS = ("uniform", "normal", "truncated_normal", "gumbel",
                 "categorical", "bernoulli", "randint", "choice",
                 "exponential", "laplace", "poisson", "gamma", "beta")
_NP_GLOBAL_DRAWS = ("normal", "uniform", "randint", "random", "choice",
                    "shuffle", "permutation", "rand", "randn")


def _enclosing_env(tree: ast.Module) -> Dict[int, Dict[str, ast.AST]]:
    """{id(function node): {name: value expr}} for simple assignments —
    the one-hop resolution environment the fold_in search walks."""
    envs: Dict[int, Dict[str, ast.AST]] = {}
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.Lambda)):
            continue
        env: Dict[str, ast.AST] = {}
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                env[node.targets[0].id] = node.value
        envs[id(fn)] = env
    return envs


def _derives_from_fold_in(expr: ast.AST, env: Dict[str, ast.AST],
                          depth: int = 8) -> bool:
    if depth <= 0:
        return False
    for node in ast.walk(expr):
        if isinstance(node, ast.Call) and \
                _dotted(node.func).split(".")[-1] in ("fold_in",
                                                      "fold_in_key"):
            return True
    for node in ast.walk(expr):
        if isinstance(node, ast.Name) and node.id in env:
            nxt = env[node.id]
            if nxt is not expr and _derives_from_fold_in(
                    nxt, {k: v for k, v in env.items()
                          if k != node.id}, depth - 1):
                return True
    return False


def _d004_findings(tree: ast.Module, relpath: str) -> List[Finding]:
    findings: List[Finding] = []
    envs = _enclosing_env(tree)
    # map each call to its nearest enclosing function's env
    stack: List[ast.AST] = []

    def emit(node: ast.AST, msg: str, hint: str) -> None:
        findings.append(Finding(
            rule="D004", path=relpath,
            line=getattr(node, "lineno", 0), severity="error",
            message=msg, fix_hint=hint))

    def visit(node: ast.AST) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            stack.append(node)
            for child in ast.iter_child_nodes(node):
                visit(child)
            stack.pop()
            return
        if isinstance(node, ast.Call):
            callee = _dotted(node.func)
            short = callee.split(".")[-1]
            env = envs.get(id(stack[-1]), {}) if stack else {}
            if "random." in callee and short in _JAX_DRAW_FNS and \
                    not callee.startswith(("np.", "numpy.")):
                key = node.args[0] if node.args else None
                if isinstance(key, ast.Call) and _dotted(
                        key.func).split(".")[-1] == "PRNGKey" and \
                        key.args and isinstance(key.args[0],
                                                ast.Constant):
                    emit(node,
                         f"{callee}() keyed on a literal PRNGKey: every"
                         " request and every position draws the same "
                         "bits — neither seed, stream, nor position "
                         "reaches the key",
                         "derive the key from the request seed, "
                         "fold_in the stream id and the position")
                elif key is not None and not _derives_from_fold_in(
                        key, env):
                    emit(node,
                         f"{callee}() key does not derive through "
                         "fold_in: the draw is position-independent, "
                         "so a requeue-for-recompute replays DIFFERENT "
                         "bits than the original decode step",
                         "key each draw as fold_in(stream_key, "
                         "position) — sampling.sample_tokens is the "
                         "reference shape")
            if (callee.startswith(("np.random.", "numpy.random."))
                    and short in _NP_GLOBAL_DRAWS):
                emit(node,
                     f"{callee}() draws from numpy's process-global "
                     "RNG in a serving path: replays and reruns "
                     "diverge",
                     "thread a seeded np.random.Generator (or derive "
                     "from the request seed)")
            if callee in ("np.random.default_rng",
                          "numpy.random.default_rng") and not node.args:
                emit(node,
                     f"unseeded {callee}() in a serving path: draw "
                     "streams are not replayable",
                     "seed from the request (seed, stream) pair")
            if callee == "random.Random" and not node.args:
                emit(node,
                     "unseeded random.Random() in a serving path",
                     "seed from the request (seed, stream) pair")
        for child in ast.iter_child_nodes(node):
            visit(child)

    visit(tree)
    return findings


# ----------------------------------------------------------------------
# AST drivers (shared pragma machinery with ds-lint)
# ----------------------------------------------------------------------

def _scan_sources(sources: Iterable[Tuple[str, str]],
                  findings_fn) -> LintReport:
    from .lint import _split_suppressed

    report = LintReport()
    for relpath, src in sources:
        try:
            tree = ast.parse(src)
        except SyntaxError as e:
            report.findings.append(Finding(
                rule="D000", path=relpath, line=e.lineno or 0,
                severity="error", message=f"syntax error: {e.msg}",
                fix_hint=""))
            report.files_checked += 1
            continue
        found = findings_fn(tree, relpath)
        found.sort(key=lambda f: (f.path, f.line, f.rule))
        active, suppressed = _split_suppressed(found, src.splitlines())
        report.findings.extend(active)
        report.suppressed.extend(suppressed)
        report.suppressed_sites.extend(
            site_keys(suppressed, {relpath: src}))
        report.files_checked += 1
    report.suppressed_sites.sort()
    return report


def _iter_scope(scope: Sequence[str], base: str,
                ) -> Iterable[Tuple[str, str]]:
    for entry in scope:
        path = os.path.join(base, entry)
        if os.path.isfile(path):
            files = [path]
        else:
            files = []
            for root, dirs, names in os.walk(path):
                dirs[:] = sorted(d for d in dirs if d != "__pycache__")
                files.extend(os.path.join(root, n) for n in sorted(names)
                             if n.endswith(".py"))
        for f in files:
            with open(f, "r", encoding="utf-8") as fh:
                yield os.path.relpath(f, base), fh.read()


def check_host_ordering(base: str,
                        scope: Sequence[str] = ORDERING_SCOPE,
                        sources: Optional[Iterable[Tuple[str, str]]]
                        = None) -> LintReport:
    """D003 over the committed-artifact emitters (`scope` is repo-
    relative, resolved against `base`; pass `sources` as
    (relpath, source) pairs to scan in-memory instead)."""
    return _scan_sources(sources if sources is not None
                         else _iter_scope(scope, base), _d003_findings)


def check_draw_keys(base: str,
                    scope: Sequence[str] = DRAW_KEY_SCOPE,
                    sources: Optional[Iterable[Tuple[str, str]]]
                    = None) -> LintReport:
    """D004 over the serving draw paths (same calling convention as
    check_host_ordering)."""
    return _scan_sources(sources if sources is not None
                         else _iter_scope(scope, base), _d004_findings)
