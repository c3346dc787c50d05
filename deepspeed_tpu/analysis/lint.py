"""ds-lint: project-specific AST rules for TPU-hostile patterns.

The generic linters cannot know that `float(loss)` inside a jitted body
is a trace-time error-or-sync, that `jax.device_get` inside the decode
loop serializes the pipeline, or that a dict on `self` mutated from an
`io_callback` thread needs a lock (the exact `NvmeLayerStore._inflight`
race PR 1 fixed). These rules do.

Rules
  R001  no `float()`/`int()`/`bool()`/`np.asarray`/`np.array` applied to
        traced values inside jit-compiled bodies (forces a trace-time
        concretization error or, via __array__, a silent host sync)
  R002  no `jax.block_until_ready`/`jax.device_get` inside engine
        step/decode hot paths (runtime/engine.py, inference/engine.py);
        end-of-run syncs route through the named helper
        `deepspeed_tpu.utils.sync.host_sync`, the single allowlisted
        choke point
  R003  a shared mutable dict/list on `self`, in a class that touches
        `io_callback`/threads, written with an empty lock intersection
        across concurrent contexts. Since the concurrency analyzer
        landed this is a shim over C001's interprocedural lockset pass
        (analysis/concurrency.py) — same rule id, pragma spelling, and
        --strict semantics; `*_locked` methods are lock-held by
        convention, and files without in-file thread roots keep the old
        conservative every-mutation-needs-a-lock behavior
  R004  `jax.jit(..., donate_argnums=...)` with no nearby comment
        explaining the aliasing story and no sanitizer check call
  R005  `jnp.array`/`jnp.asarray`/`jnp.full` of a bare Python
        scalar/list WITHOUT an explicit dtype inside a jit-root body —
        the constant is weakly typed, so its dtype follows the
        promotion context instead of being pinned; the same expression
        hoisted to the call boundary is the exact python-scalar-
        promotion recompile class S003 catches dynamically
  R006  precision-policy drift in a jit-root body: a `float64`
        dtype mention (TPU has no f64 — under x64-off it silently
        downcasts, under x64-on it doubles every byte), a dtype-less
        `jnp.zeros`/`jnp.ones`/`jnp.arange` (the default dtype follows
        global flags, not the active precision policy), or
        `.astype(float)`/`.astype("float64")` (widening through the
        python type). The static companion to the numerics
        sanitizer's N001 (analysis/numerics.py)
  R007  a collective call (`psum`/`all_gather`/`ppermute`/
        `psum_scatter`/`pmean`/`all_to_all`) inside a Python-level
        `for`/`while` loop in a jit-root body — tracing unrolls the
        loop into N separate collectives, the volume-blowup class the
        cost model's S005 only catches post-compile. Carry the loop
        into `lax.scan`/`lax.fori_loop` (one collective in the
        compiled body) or annotate a deliberately unrolled ring
  R008  rng draws without a replication pin under a sharded mesh: a
        `jax.random.uniform/normal/bernoulli/...` draw inside a
        jit-root body, in a module that manipulates shardings
        (with_sharding_constraint / shard_map / Mesh), neither wrapped
        in a `*replicated_draw`-style helper nor pinned through
        `with_sharding_constraint` — jax's threefry is NOT
        partitionable, so the SPMD partitioner computes DIFFERENT bits
        per mesh layout (the PR-14 EP=1 != EP=N router-noise bug; the
        static companion to the determinism analyzer's D001). Also:
        unseeded `random.Random()` / `time.time()` in the
        `scripts/` capture paths — process entropy in a
        committed ledger

Pragma: `# ds-lint: ok` suppresses every rule on that line (or the line
below a standalone pragma comment); `# ds-lint: ok R002 <reason>`
suppresses only the named rule(s). Intentional sites carry the reason in
the pragma — the allowlist is greppable.
"""

import ast
import dataclasses
import os
import re
from typing import Iterable, List, Optional, Sequence, Set, Tuple

from .report import Finding, LintReport

__all__ = ["lint_paths", "lint_source", "LintReport", "RULES"]

RULES = {
    "R001": "host conversion of traced value inside a jitted body",
    "R002": "host sync inside an engine step/decode hot path",
    "R003": "unlocked mutation of shared state in a threaded class",
    "R004": "donate_argnums without an aliasing note",
    "R005": "weak-typed literal constant (jnp.array of a python "
            "scalar/list, no dtype) inside a jitted body",
    "R006": "precision-policy drift (float64 mention, dtype-less "
            "jnp.zeros/ones/arange, astype(float)) inside a jitted "
            "body",
    "R007": "collective call inside a Python-level for/while loop in "
            "a jitted body (unrolls to N collectives)",
    "R008": "rng draw without a replication pin under a sharded mesh "
            "(layout-dependent threefry bits), or wall-clock/unseeded "
            "entropy in a ds_* capture script",
    "R009": "broad except absorbing typed resilience errors without "
            "counting, logging, or re-raising (hot files outside the "
            "lifecycle roots; per-file shim of lifecycle L004)",
}

_PRAGMA_RE = re.compile(
    r"#\s*ds-lint:\s*ok\b(?P<rules>[^#\n]*)")

# R002 scope: hot-path files and the function-name shapes of their
# per-token / per-step loops. A name matches when it equals an entry or
# starts with `entry` + one of the listed prefixes.
_HOT_FILES = ("runtime/engine.py", "inference/engine.py",
              "runtime/hybrid_engine.py", "inference/scheduler.py",
              "inference/router.py",
              # the pressure governor + SLO admission estimate run
              # once per scheduling iteration, and the spill tier sits
              # on the preemption path — host syncs here tax every
              # dispatch under exactly the pressure they exist to
              # relieve
              "inference/pressure.py",
              # resilience primitives live INSIDE the per-step hot
              # paths (fault points, health observations, SDC anomaly
              # windows) — a host sync added here would tax every
              # dispatch
              "resilience/faults.py", "resilience/health.py",
              "resilience/integrity.py",
              # the autoscaler ticks once per fleet sweep and its
              # adapter reads router/scheduler counters on that path
              "inference/autoscaler.py",
              # dropless MoE dispatch runs INSIDE every train step and
              # serving decode/prefill program — a host sync here would
              # serialize the grouped GEMM per layer per step
              "moe/dropless.py",
              # the pipeline schedule body is traced into every
              # pipelined train step (scan over v*M+P-1 chunk-steps,
              # one collective-permute per step) — a host sync or an
              # unrolled-loop collective here multiplies by the whole
              # schedule length (docs/pipeline.md)
              "runtime/pipe.py",
              # the concurrency analyzer and the interleaving harness
              # are imported by the ds_race gate and by lint itself —
              # a stray host sync here would tax every lint/gate run
              # and, for the harness, every instrumented lock op
              "analysis/concurrency.py", "resilience/interleave.py",
              # the overlap layer traces into every training step's
              # forward scan and gradient path (prefetch gathers,
              # bucketed scatters, barrier pins) — a host sync here
              # would serialize the very collectives it exists to hide
              "runtime/overlap.py",
              # the determinism analyzer is imported by engine.sanitize
              # and the ds_determinism gate — a host sync here would
              # tax every sanitize/gate run
              "analysis/determinism.py",
              # the lifecycle analyzer is imported by lint (R009 shim)
              # and the ds_lifecycle gate — same tax argument
              "analysis/lifecycle.py")
_HOT_FN_PREFIXES = (
    "train_batch", "eval_batch", "_dispatch", "decode", "_decode",
    "generate", "put", "step", "_sample", "prefill", "_prefill",
    "run", "_finalize", "_accept", "submit", "_admit",
    # router/handoff loop (inference/router.py + the engine transfer
    # path): readbacks route through utils/sync.serving_readback
    "pump", "serve", "adopt", "requeue", "_route", "fail_replica",
    "export_kv", "import_kv",
    # self-healing loop (resilience/ + router health plumbing)
    "fault_point", "_hit", "observe", "probe", "_probe", "due_probe",
    "note_step_result", "poll_health", "restore_replica", "_shed",
    "drain_fault_delay",
    # pressure governor / spill tier / SLO admission (PR 10): all run
    # per scheduling iteration or on the preemption path
    "update", "occupancy", "watermark_scale", "estimate_ttft",
    "_try_spill", "_resume_from_spill", "_brownout", "_pressure",
    "_decode_can_take", "_fleet_brownout", "trim_parked",
    # replica lifecycle + autoscaler (docs/autoscaling.md): the policy
    # tick runs per fleet sweep; spin-up/drain move KV pages through
    # the serving_readback-audited transfer path
    "tick", "add_replica", "join_replica", "drain_replica",
    "_drain_migrate", "_drain_target", "_maybe_release", "pump_drains",
    "_warm_boot", "_rebalance_to", "export_parked_kv", "parked_chains",
    "scale_up", "scale_down", "signals", "observe_time", "lifecycle",
    # dropless MoE dispatch/combine (moe/dropless.py): traced per layer
    # per step in both engines
    "dropless_", "grouped_mm", "sort_by_expert", "expert_counts",
    "router_z_loss", "_ragged_wire", "_a2a_wire", "_expert_mlp",
    # interleaved pipeline (runtime/pipe.py): the schedule body and
    # its helpers trace into every pipelined step; the host-side
    # boundary guard runs once per stage per dispatch
    "pipeline_apply", "partition_layers", "unpartition_layers",
    "stage_slice_keys", "pipe_permute_tick", "simulate_schedule",
    # comm/compute overlap layer (runtime/overlap.py): the layer
    # gather, bucket launcher, and barriers trace into every
    # overlap-on training step
    "make_prefetch_gather", "bucketed_apply",
    "bucket_partition", "overlap_stats",
)
_SYNC_CALLS = ("block_until_ready", "device_get")
# serving_readback: the scheduler loop's one named readback point
# (utils/sync.py) — double-buffered, token-ids-only
_SYNC_ALLOWED_HELPERS = ("host_sync", "serving_readback")

_HOST_CONVERSIONS = ("float", "int", "bool")
_NP_CONVERSIONS = ("asarray", "array")
# attribute reads that are static under tracing — a Name only reached
# through these is not a traced-value use
_STATIC_ATTRS = ("shape", "ndim", "dtype", "size", "sharding", "aval",
                 "itemsize")

def _dotted(node: ast.AST) -> str:
    """'jax.experimental.io_callback' for an Attribute/Name chain."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _is_jit_expr(node: ast.AST) -> bool:
    """Does this expression evaluate to a jit transform?"""
    d = _dotted(node)
    if d.split(".")[-1] in ("jit", "pjit"):
        return True
    # functools.partial(jax.jit, ...)
    if isinstance(node, ast.Call) and _dotted(node.func).split(".")[-1] == \
            "partial" and node.args and _is_jit_expr(node.args[0]):
        return True
    return False


@dataclasses.dataclass
class _Ctx:
    relpath: str
    lines: List[str]
    findings: List[Finding]

    def emit(self, rule: str, node: ast.AST, message: str, fix_hint: str,
             severity: str = "error") -> None:
        self.findings.append(Finding(
            rule=rule, path=self.relpath, line=getattr(node, "lineno", 0),
            severity=severity, message=message, fix_hint=fix_hint))


# ----------------------------------------------------------------------
# jit-context discovery
# ----------------------------------------------------------------------

def _collect_jit_roots(tree: ast.Module) -> Tuple[List[ast.AST], Set[ast.AST]]:
    """(jit-target function/lambda nodes, host-callback function nodes).

    A function is a jit target when decorated with jit/pjit (directly or
    through partial), or when its name / the lambda itself is passed to a
    jit call anywhere in the module. Functions handed to *callback* APIs
    are host code even when textually inside a jitted body.
    """
    jit_names: Set[str] = set()
    roots: List[ast.AST] = []
    callbacks: Set[ast.AST] = set()

    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            callee = _dotted(node.func).split(".")[-1]
            if _is_jit_expr(node.func):
                for a in node.args[:1]:
                    if isinstance(a, ast.Name):
                        jit_names.add(a.id)
                    elif isinstance(a, (ast.Lambda, ast.FunctionDef)):
                        roots.append(a)
            if "callback" in callee:
                for a in list(node.args) + [kw.value for kw in node.keywords]:
                    if isinstance(a, ast.Lambda):
                        callbacks.add(a)
                    elif isinstance(a, ast.Name):
                        jit_names.discard(a.id)  # name used as callback
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if _is_jit_expr(dec):
                    roots.append(node)

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and \
                node.name in jit_names and node not in roots:
            roots.append(node)
    return roots, callbacks


def _param_names(fn: ast.AST) -> List[str]:
    a = fn.args
    names = [x.arg for x in
             list(getattr(a, "posonlyargs", [])) + a.args + a.kwonlyargs]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return names


def _traced_names(expr: ast.AST, tainted: Set[str]) -> Set[str]:
    """Tainted Names referenced by `expr` as VALUES (a name reached only
    through .shape/.ndim/... or len() is static under tracing)."""
    hits: Set[str] = set()

    def visit(node: ast.AST) -> None:
        if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
            return  # x.shape, x.dtype ... — static metadata
        if isinstance(node, ast.Call):
            callee = _dotted(node.func).split(".")[-1]
            if callee == "len":
                return
            for child in list(node.args) + [k.value for k in node.keywords]:
                visit(child)
            if not isinstance(node.func, ast.Name):
                visit(node.func)
            return
        if isinstance(node, ast.Name) and node.id in tainted:
            hits.add(node.id)
            return
        for child in ast.iter_child_nodes(node):
            visit(child)

    visit(expr)
    return hits


def _check_r001(ctx: _Ctx, root: ast.AST, callbacks: Set[ast.AST]) -> None:
    """Host conversions of traced values inside one jit target."""
    tainted: Set[str] = set(_param_names(root))
    # nested defs/lambdas are traced too (their params are traced values),
    # unless they are host callbacks
    for node in ast.walk(root):
        if isinstance(node, (ast.FunctionDef, ast.Lambda)) and \
                node is not root and node not in callbacks:
            tainted.update(_param_names(node))

    # one forward taint pass over simple assignments
    for node in ast.walk(root):
        if isinstance(node, ast.Assign) and _traced_names(node.value, tainted):
            for tgt in node.targets:
                for n in ast.walk(tgt):
                    if isinstance(n, ast.Name):
                        tainted.add(n.id)

    skip: Set[ast.AST] = set()
    for cb in callbacks:
        skip.update(ast.walk(cb))
    for node in ast.walk(root):
        if node in skip or not isinstance(node, ast.Call) or not node.args:
            continue
        callee = _dotted(node.func)
        short = callee.split(".")[-1]
        is_conv = (
            (isinstance(node.func, ast.Name) and short in _HOST_CONVERSIONS)
            or (short in _NP_CONVERSIONS
                and callee.split(".")[0] in ("np", "numpy", "onp"))
        )
        if not is_conv:
            continue
        traced = _traced_names(node.args[0], tainted)
        if traced:
            ctx.emit(
                "R001", node,
                f"{callee}() applied to traced value(s) {sorted(traced)} "
                "inside a jitted body — concretization error at trace time "
                "or a hidden host sync",
                "use jnp casts (x.astype / jnp.asarray) in-graph, or move "
                "the conversion outside the compiled function",
            )


# ----------------------------------------------------------------------
# R005: weak-typed literal constants in jit bodies
# ----------------------------------------------------------------------

# jnp constructors whose FIRST (or for full, second) argument is a value
# that becomes a weakly-typed constant when given as a python literal
_WEAK_CONST_FNS = ("array", "asarray", "full")
_JNP_PREFIXES = ("jnp", "jax.numpy")


def _is_py_literal(node: ast.AST) -> bool:
    """A bare python scalar literal (or list/tuple of them), including
    negated forms like -1.0."""
    if isinstance(node, ast.Constant):
        return isinstance(node.value, (bool, int, float)) and not \
            isinstance(node.value, str)
    if isinstance(node, ast.UnaryOp) and isinstance(
            node.op, (ast.USub, ast.UAdd)):
        return _is_py_literal(node.operand)
    if isinstance(node, (ast.List, ast.Tuple)):
        return bool(node.elts) and all(_is_py_literal(e) for e in node.elts)
    return False


def _check_r005(ctx: _Ctx, root: ast.AST, callbacks: Set[ast.AST]) -> None:
    skip: Set[ast.AST] = set()
    for cb in callbacks:
        skip.update(ast.walk(cb))
    for node in ast.walk(root):
        if node in skip or not isinstance(node, ast.Call):
            continue
        callee = _dotted(node.func)
        parts = callee.rsplit(".", 1)
        if len(parts) != 2 or parts[1] not in _WEAK_CONST_FNS or \
                parts[0] not in _JNP_PREFIXES:
            continue
        # jnp.full(shape, value): the VALUE is the weak-type carrier
        vpos = 1 if parts[1] == "full" else 0
        if len(node.args) <= vpos or not _is_py_literal(node.args[vpos]):
            continue
        if any(kw.arg == "dtype" for kw in node.keywords):
            continue
        ctx.emit(
            "R005", node,
            f"{callee}() of a bare Python literal without an explicit "
            "dtype inside a jitted body — the constant is weakly typed, "
            "its dtype follows the promotion context (x64 flags, "
            "neighboring operands), and the hoisted form of this "
            "expression is the S003 python-scalar-promotion recompile "
            "class",
            "pin the dtype (jnp.array(v, dtype=...)) or fold the "
            "literal into an existing typed expression",
            severity="warning",
        )


# ----------------------------------------------------------------------
# R006: precision-policy drift in jit bodies
# ----------------------------------------------------------------------

# constructors whose DEFAULT dtype follows global flags (x64, weak-type
# promotion) instead of the active precision policy
_R006_CTORS = ("zeros", "ones", "arange")


def _check_r006(ctx: _Ctx, root: ast.AST, callbacks: Set[ast.AST]) -> None:
    skip: Set[ast.AST] = set()
    for cb in callbacks:
        skip.update(ast.walk(cb))
    for node in ast.walk(root):
        if node in skip:
            continue
        if isinstance(node, ast.Attribute) and node.attr == "float64":
            ctx.emit(
                "R006", node,
                f"{_dotted(node)} inside a jitted body — TPU has no "
                "f64: under x64-off the value silently downcasts to "
                "f32 (the config lied), under x64-on it doubles every "
                "byte of the buffer",
                "use an explicit f32/bf16 dtype from the active "
                "precision policy",
                severity="warning",
            )
            continue
        if not isinstance(node, ast.Call):
            continue
        callee = _dotted(node.func)
        parts = callee.rsplit(".", 1)
        if len(parts) == 2 and parts[0] in _JNP_PREFIXES and \
                parts[1] in _R006_CTORS and \
                not any(kw.arg == "dtype" for kw in node.keywords):
            # zeros/ones take dtype as the 2nd positional, arange as
            # the 4th — fewer args with no dtype= means the default
            dtype_pos = 3 if parts[1] == "arange" else 1
            if len(node.args) <= dtype_pos:
                ctx.emit(
                    "R006", node,
                    f"{callee}() without an explicit dtype inside a "
                    "jitted body — the default dtype follows global "
                    "flags (x64, promotion context), not the active "
                    "precision policy; a widened buffer here is a "
                    "silent 2x on bytes and a policy drift N001 only "
                    "catches after compilation",
                    "pin the dtype (e.g. jnp.zeros(shape, jnp.float32) "
                    "or the policy compute dtype)",
                    severity="warning",
                )
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr == "astype" and node.args:
            a = node.args[0]
            widens = (
                (isinstance(a, ast.Name) and a.id == "float")
                or (isinstance(a, ast.Constant)
                    and a.value in ("float64", "double"))
            )
            if widens:
                ctx.emit(
                    "R006", node,
                    ".astype(float)/.astype('float64') inside a jitted "
                    "body widens through the python type — the result "
                    "dtype follows x64 flags, not the precision policy",
                    "cast to an explicit jnp dtype (x.astype("
                    "jnp.float32))",
                    severity="warning",
                )


# ----------------------------------------------------------------------
# R007: collectives inside Python-level loops in jit bodies
# ----------------------------------------------------------------------

# the Python-callable collective surface (jax.lax.* and the comm/
# wrappers share these names): each call traced inside an unrolled
# Python loop becomes its OWN collective instruction in the compiled
# program — N x the volume, N x the latency floor
_R007_COLLECTIVES = ("psum", "all_gather", "ppermute", "psum_scatter",
                     "pmean", "pmax", "pmin", "all_to_all")


def _check_r007(ctx: _Ctx, root: ast.AST, callbacks: Set[ast.AST]) -> None:
    skip: Set[ast.AST] = set()
    for cb in callbacks:
        skip.update(ast.walk(cb))
    for loop in ast.walk(root):
        if loop in skip or not isinstance(loop, (ast.For, ast.While)):
            continue
        for node in ast.walk(loop):
            if node in skip or not isinstance(node, ast.Call):
                continue
            callee = _dotted(node.func)
            if callee.split(".")[-1] not in _R007_COLLECTIVES:
                continue
            ctx.emit(
                "R007", node,
                f"{callee}() inside a Python-level "
                f"{'for' if isinstance(loop, ast.For) else 'while'} "
                "loop in a jitted body — tracing unrolls the loop, so "
                "the compiled program carries one collective PER "
                "iteration (the unrolled-N volume blowup S005 only "
                "catches post-compile)",
                "carry the loop into lax.scan / lax.fori_loop so the "
                "compiled body holds ONE collective, or annotate a "
                "deliberately unrolled ring with "
                "`# ds-lint: ok R007 <why>`",
                severity="warning",
            )


# ----------------------------------------------------------------------
# R008: unpinned rng draws under a sharded mesh + capture-path entropy
# ----------------------------------------------------------------------

# the jax.random draw surface (key-DERIVATION — split/fold_in — is
# layout-safe: it computes the same bits on every layout; only DRAWS
# consume the non-partitionable threefry counter)
_R008_DRAW_FNS = ("uniform", "normal", "truncated_normal", "bernoulli",
                  "categorical", "gumbel", "randint", "choice",
                  "exponential", "laplace", "poisson", "gamma", "beta",
                  "bits", "random_bits")
# a module that never touches shardings cannot lay the draw out across
# a mesh axis — R008 half 1 only looks at modules referencing these
_R008_MESH_MARKERS = ("with_sharding_constraint", "shard_map",
                      "set_mesh", "Mesh", "NamedSharding")


def _r008_pinned_nodes(tree: ast.Module) -> Set[int]:
    """ids of AST nodes that sit under a replication pin: inside an
    argument of a `with_sharding_constraint(...)` call, or inside a
    lambda/function passed to a `*replicated_draw`-style helper."""
    pinned: Set[int] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        short = _dotted(node.func).split(".")[-1]
        if short == "with_sharding_constraint" and node.args:
            pinned.update(id(n) for n in ast.walk(node.args[0]))
        elif short.endswith("replicated_draw"):
            for a in list(node.args) + [kw.value for kw in node.keywords]:
                pinned.update(id(n) for n in ast.walk(a))
    return pinned


def _is_capture_script(relpath: str) -> bool:
    rel = relpath.replace(os.sep, "/")
    return os.path.basename(rel).startswith("ds_") and \
        ("scripts" in rel.split("/")[:-1] or "/" not in rel)


def _check_r008(ctx: _Ctx, tree: ast.Module, roots: Sequence[ast.AST],
                callbacks: Set[ast.AST]) -> None:
    # half 2: wall-clock / unseeded process entropy in a ds_* capture
    # script — the committed ledger inherits it
    if _is_capture_script(ctx.relpath):
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            callee = _dotted(node.func)
            if callee == "time.time":
                ctx.emit(
                    "R008", node,
                    "time.time() in a capture script — a wall-clock "
                    "value reaching the committed artifact makes every "
                    "capture a diff",
                    "keep timestamps out of the artifact (stderr "
                    "logging only), or annotate the non-artifact use "
                    "with `# ds-lint: ok R008 <why>`",
                    severity="warning",
                )
            elif callee == "random.Random" and not node.args:
                ctx.emit(
                    "R008", node,
                    "unseeded random.Random() in a capture script — "
                    "the ledger inherits process entropy",
                    "pass an explicit seed",
                    severity="warning",
                )
    # half 1: draws in jit-root bodies of mesh-touching modules must
    # carry a replication pin (threefry bits are layout-dependent)
    if not any(isinstance(n, (ast.Attribute, ast.Name)) and
               (n.attr if isinstance(n, ast.Attribute) else n.id)
               in _R008_MESH_MARKERS for n in ast.walk(tree)):
        return
    pinned = _r008_pinned_nodes(tree)
    skip: Set[ast.AST] = set()
    for cb in callbacks:
        skip.update(ast.walk(cb))
    for root in roots:
        for node in ast.walk(root):
            if node in skip or id(node) in pinned or \
                    not isinstance(node, ast.Call):
                continue
            callee = _dotted(node.func)
            parts = callee.rsplit(".", 1)
            if len(parts) != 2 or parts[1] not in _R008_DRAW_FNS or \
                    not parts[0].endswith("random"):
                continue
            ctx.emit(
                "R008", node,
                f"{callee}() inside a jitted body in a mesh-touching "
                "module without a replication pin — jax's threefry is "
                "not partitionable, so the SPMD partitioner computes "
                "DIFFERENT bits for the same key depending on the mesh "
                "layout (the PR-14 EP=1 != EP=N router-noise bug)",
                "wrap the draw in the _replicated_draw idiom "
                "(jax.lax.with_sharding_constraint(draw, P())), or "
                "annotate a deliberately per-layout draw with "
                "`# ds-lint: ok R008 <why>`",
                severity="warning",
            )


# ----------------------------------------------------------------------
# R002: hot-path host syncs
# ----------------------------------------------------------------------

def _is_hot_fn(name: str) -> bool:
    return any(name == p or name.startswith(p) for p in _HOT_FN_PREFIXES)


def _check_r002(ctx: _Ctx, tree: ast.Module) -> None:
    if not any(ctx.relpath.replace(os.sep, "/").endswith(h)
               for h in _HOT_FILES):
        return
    for fn in ast.walk(tree):
        if not isinstance(fn, ast.FunctionDef) or not _is_hot_fn(fn.name):
            continue
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            callee = _dotted(node.func)
            short = callee.split(".")[-1]
            if short in _SYNC_ALLOWED_HELPERS:
                continue
            if short in _SYNC_CALLS:
                ctx.emit(
                    "R002", node,
                    f"{callee}() inside hot path {fn.name}() — a device "
                    "round trip per step serializes dispatch against "
                    "execution",
                    "keep metrics on device (train_batch_async pattern), "
                    "route end-of-run syncs through utils.sync.host_sync, "
                    "or annotate the intentional per-step sync with "
                    "`# ds-lint: ok R002 <why>`",
                )


# ----------------------------------------------------------------------
# R003: unlocked shared-state mutation — a thin shim over the
# concurrency analyzer's C001 lockset pass (analysis/concurrency.py).
# Same rule id, pragma spelling, and --strict semantics as the old
# heuristic, but with real path sensitivity: in files that register
# their own thread roots (Thread targets, io_callback bodies, atexit
# handlers) only genuinely multi-context unlocked state fires; files
# whose roots live elsewhere fall back to the conservative
# every-method-is-concurrent mode (the old behavior). The cross-file
# picture — roots registered in ANOTHER module — is the ds_race gate's
# job (scripts/ds_gate.py race, the 13th tier-1 gate).
# ----------------------------------------------------------------------

def _check_r003(ctx: _Ctx, tree: ast.Module) -> None:
    from .concurrency import r003_findings
    ctx.findings.extend(r003_findings(tree, ctx.relpath))


# ----------------------------------------------------------------------
# R004: undocumented donation
# ----------------------------------------------------------------------

def _check_r004(ctx: _Ctx, tree: ast.Module) -> None:
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and _is_jit_expr(node.func)):
            continue
        if not any(kw.arg in ("donate_argnums", "donate_argnames")
                   for kw in node.keywords):
            continue
        lo = max(0, node.lineno - 4)
        hi = min(len(ctx.lines), getattr(node, "end_lineno", node.lineno) + 1)
        window = "\n".join(ctx.lines[lo:hi])
        documented = any(
            re.search(r"#.*(donat|alias)", ln, re.I)
            for ln in ctx.lines[lo:hi])
        checked = "check_donation" in window or "sanitize(" in window
        if not (documented or checked):
            ctx.emit(
                "R004", node,
                "jax.jit with donate_argnums but no comment explaining the "
                "aliasing story and no sanitizer check — unaliased donation "
                "silently copies the buffer",
                "add a `# donated: ...` comment naming which outputs alias, "
                "or verify with analysis.sanitizer.check_donation / "
                "engine.sanitize()",
                severity="warning",
            )


# ----------------------------------------------------------------------
# R009: swallowed typed failures on hot paths (lifecycle L004 shim)
# ----------------------------------------------------------------------

def _check_r009(ctx: _Ctx, tree: ast.Module) -> None:
    """Warn-level per-file shim of the lifecycle analyzer's L004 pass,
    scoped to the hot files NOT already audited at error level by the
    ds_lifecycle gate's roots (those would double-report)."""
    from .lifecycle import LIFECYCLE_ROOTS, l004_tree_findings
    rel = ctx.relpath.replace(os.sep, "/")
    if not any(rel.endswith(h) for h in _HOT_FILES):
        return
    if any(rel.endswith(r) for r in LIFECYCLE_ROOTS):
        return
    ctx.findings.extend(
        l004_tree_findings(tree, ctx.relpath, rule="R009",
                           severity="warning"))


# ----------------------------------------------------------------------
# driver
# ----------------------------------------------------------------------

def _split_suppressed(
    findings: List[Finding], lines: List[str]
) -> Tuple[List[Finding], List[Finding]]:
    active, suppressed = [], []
    for f in findings:
        ok = False
        for ln in (f.line, f.line - 1):  # same line, or pragma line above
            if not (1 <= ln <= len(lines)):
                continue
            m = _PRAGMA_RE.search(lines[ln - 1])
            if not m:
                continue
            named = re.findall(r"[A-Z]\d{3}", m.group("rules"))
            # R003 is the per-file shim over the concurrency analyzer's
            # C001, R009 over the lifecycle analyzer's L004 — one
            # pragma spelling covers both emitters of each pair
            if not named or f.rule in named or \
                    (f.rule == "R003" and "C001" in named) or \
                    (f.rule == "R009" and "L004" in named):
                ok = True
                break
        (suppressed if ok else active).append(f)
    return active, suppressed


def lint_source(source: str, relpath: str) -> Tuple[List[Finding],
                                                    List[Finding]]:
    """Lint one file's source. Returns (findings, suppressed)."""
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Finding(rule="R000", path=relpath, line=e.lineno or 0,
                        severity="error", message=f"syntax error: {e.msg}",
                        fix_hint="")], []
    lines = source.splitlines()
    ctx = _Ctx(relpath=relpath, lines=lines, findings=[])
    roots, callbacks = _collect_jit_roots(tree)
    for root in roots:
        _check_r001(ctx, root, callbacks)
        _check_r005(ctx, root, callbacks)
        _check_r006(ctx, root, callbacks)
        _check_r007(ctx, root, callbacks)
    _check_r002(ctx, tree)
    _check_r003(ctx, tree)
    _check_r004(ctx, tree)
    _check_r008(ctx, tree, roots, callbacks)
    _check_r009(ctx, tree)
    ctx.findings.sort(key=lambda f: (f.line, f.rule))
    return _split_suppressed(ctx.findings, lines)


def _iter_py(paths: Iterable[str]) -> Iterable[str]:
    for p in paths:
        if os.path.isfile(p):
            yield p
            continue
        for root, dirs, files in os.walk(p):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in sorted(files):
                if f.endswith(".py"):
                    yield os.path.join(root, f)


def lint_paths(paths: Sequence[str],
               base: Optional[str] = None) -> LintReport:
    """Lint every .py under `paths`; report paths relative to `base`."""
    report = LintReport()
    for path in _iter_py(paths):
        rel = os.path.relpath(path, base) if base else path
        with open(path, "r", encoding="utf-8") as fh:
            src = fh.read()
        findings, suppressed = lint_source(src, rel)
        report.findings.extend(findings)
        report.suppressed.extend(suppressed)
        report.files_checked += 1
    return report
