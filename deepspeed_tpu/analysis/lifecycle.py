"""Lifecycle analyzer: the L-series static pass over the resource
economy (paged-KV blocks, the host spill tier, handoff payloads, pool
tallies) plus the meta-audit of the chaos machinery's own coverage.
Eighth prong of the static-analysis suite (docs/static_analysis.md;
gate: scripts/ds_gate.py lifecycle, the 15th tier-1 gate).

The serving stack acquires and releases resources across deep call
chains (`scheduler._admit` -> `export_kv` -> `import_kv` -> `adopt`):
one raising path that skips a `free()` is an HBM leak no test notices
until a long trace OOMs — the partitioned-parameter/offload lifecycle
discipline the reference enforces by convention (PAPER.md L4
runtime/zero/, L0 csrc/aio), made checkable here:

L001  exception-path resource leak. Inside each rooted function the
      pass tracks an acquire vocabulary — `allocator.allocate()`
      bindings, `engine.import_kv(uid, ...)` reservations,
      `spill_store.put(key, ...)` admissions, bare `open()` handles —
      and walks the statement list with the enclosing try-structure.
      A tracked resource dies by RELEASE (`free/flush/discard/close`
      on or with the bound name), by TRANSFER (stored into a field or
      container, returned, handed to an adopting call like
      `adopt/requeue/put/restore/append`, or passed to a local
      function whose computed summary releases that parameter —
      the interprocedural edge), or by protection (an enclosing `try`
      whose handler or `finally` releases it). A statement that can
      raise (the raising vocabulary: `extend`, `import_kv`,
      `export_kv`, `adopt`, `allocate`, `fault_point`, commit/save,
      collectives, or an explicit `raise`) while an unprotected
      resource is live is the finding: that raising edge strands the
      acquisition.

L002  pool-accounting invariants. (a) Every class that declares a
      counter authority (`self.counters = {literal}`) may only mutate
      declared keys — an undeclared key silently widens metrics() and
      escapes every quiesce audit. (b) Accounting attributes of the
      pool authorities (`used_bytes`, `_entries`, `_bytes`, block
      maps) may only be written through `self` inside their owner —
      an external write bypasses the allocator authority. The dynamic
      half, `quiesce_residuals()` / `fleet_quiesce_residuals()`, is
      wired into the bench serving-sim/chaos/overload exit gates:
      zero leaked blocks, zero spill bytes, zero backlog at lane end.

L003  fault-coverage audit. Cross-references the machine-readable
      fault-point registry (`resilience/faults.py FAULT_POINTS`, read
      as a pure literal) against every committed chaos lane (repo-
      root plan JSONs, bench.py default plans, scripts/, tests/) and
      against the `fault_point("...")` call sites compiled into the
      tree. Red when: a registered point is fired by zero committed
      lanes; a registered point has no call site (registry drift); a
      committed plan or call site names an unregistered point (typo
      drift). Plus the reachability half: a ds-lint hot-path mutator
      whose call-graph component (built on the C-series walker's
      models) contains no fault point at all — a subsystem the chaos
      machinery cannot perturb.

L004  swallowed-exception audit. A broad handler (`except`,
      `Exception`, `BaseException`, `RuntimeError`, `OSError`) whose
      try-body calls the typed-failure vocabulary (`import_kv`,
      `export_kv`, `adopt`, `fault_point`, spill/store/state ops —
      the calls that raise `HandoffIntegrityError`,
      `KVCacheExhaustedError`, `CollectiveTimeoutError`,
      `InjectedFault`, ...) and whose handler neither re-raises, nor
      logs, nor counts, absorbs a typed resilience signal the
      recovery machinery was built to observe. `__del__` is exempt
      (interpreter-shutdown teardown must never raise). ds-lint R009
      is the warn-level per-file shim of this rule for hot files
      outside the lifecycle roots.

Findings have NO baseline: any active L-finding is red in every gate
mode. Intentional sites carry `# ds-lint: ok L001 <why>` pragmas
(same spelling/splitter semantics as the R/C/D series); the gate pins
the suppression inventory in LIFECYCLE.json so a new pragma is a
reviewed diff, not a silent bypass.
"""

import ast
import json
import os
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

from .report import Finding, site_keys

__all__ = [
    "L_RULES", "LIFECYCLE_ROOTS", "LifecycleReport",
    "analyze_tree", "analyze_sources",
    "l001_findings", "l002_findings", "l003_findings",
    "l003_component_findings", "l004_findings", "l004_tree_findings",
    "quiesce_residuals", "fleet_quiesce_residuals",
]

L_RULES = {
    "L001": "exception-path resource leak: an acquisition with no "
            "release, transfer, or try-protection on a raising path",
    "L002": "pool-accounting invariant: undeclared counter key, or an "
            "accounting attribute written outside its authority",
    "L003": "fault-coverage gap: a registered fault point no committed "
            "lane fires (or registry/plan/call-site drift), or a "
            "hot-path mutator in a call component with no fault point",
    "L004": "swallowed typed failure: a broad except absorbs "
            "resilience-vocabulary errors without counting, logging, "
            "or re-raising",
}

#: The files whose resource discipline the L-series roots in: every
#: acquire/release/transfer of KV blocks, spill payloads, handoff
#: buffers, and checkpoint handles lives here.
LIFECYCLE_ROOTS = (
    "deepspeed_tpu/inference/scheduler.py",
    "deepspeed_tpu/inference/router.py",
    "deepspeed_tpu/inference/engine.py",
    "deepspeed_tpu/inference/ragged.py",
    "deepspeed_tpu/inference/offload_store.py",
    "deepspeed_tpu/inference/pressure.py",
    "deepspeed_tpu/resilience/redundancy.py",
    "deepspeed_tpu/runtime/checkpoint.py",
)

_PRAGMA_RE = re.compile(r"#\s*ds-lint:\s*ok\b(?P<rules>[^#\n]*)")


def _dotted(node: ast.AST) -> str:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


@dataclass
class LifecycleReport:
    findings: List[Finding] = field(default_factory=list)
    suppressed: List[Finding] = field(default_factory=list)
    files_checked: int = 0
    #: deterministic ownership inventory (the gate's drift anchor)
    ledger: Dict[str, Any] = field(default_factory=dict)
    #: fault point -> sorted committed lanes that fire it
    coverage: Dict[str, List[str]] = field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return not self.findings

    def by_rule(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for f in self.findings:
            out[f.rule] = out.get(f.rule, 0) + 1
        return out

    def summary(self) -> str:
        return (f"ds-lifecycle: {self.files_checked} files, "
                f"{len(self.coverage)} registered fault points, "
                f"{len(self.findings)} finding(s), "
                f"{len(self.suppressed)} suppressed by pragma")


# ----------------------------------------------------------------------
# L001: exception-path resource leaks
# ----------------------------------------------------------------------

# call names whose bound result is an owned resource: x = recv.name(...)
_ACQUIRE_BINDERS = {"allocate": "kv-block", "open": "file-handle",
                    "mkdtemp": "temp-dir"}
# call statements that reserve a resource NAMED BY their first arg
_ACQUIRE_BY_ARG = {"import_kv": "kv-sequence"}
# spill-store admission: recv.put(key, payload) owns the entry at key
_STORE_HINTS = ("store", "spill", "tier")
# releasing call names (resource as receiver or argument)
_RELEASES = ("free", "flush", "discard", "close", "release",
             "release_spill", "shutdown", "cleanup", "drain")
# ownership-transfer call names (resource as argument)
_TRANSFERS = ("append", "appendleft", "add", "put", "restore", "adopt",
              "requeue", "register", "_register_full_blocks", "insert",
              "push", "submit", "setdefault")
# the raising vocabulary: calls that genuinely raise in this tree
# (typed resilience errors, pool exhaustion, injected faults)
_RAISERS = ("extend", "import_kv", "export_kv", "adopt", "allocate",
            "fault_point", "_copy_block", "commit", "save", "barrier",
            "broadcast_host", "get_or_create", "reconstruct")


@dataclass
class _Resource:
    name: str
    kind: str
    line: int


def _call_short(call: ast.Call) -> str:
    return _dotted(call.func).split(".")[-1]


def _stmt_calls(st: ast.AST) -> List[ast.Call]:
    """Every Call in the statement, not descending into nested defs."""
    out: List[ast.Call] = []
    stack = [st]
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda)) and n is not st:
            continue
        if isinstance(n, ast.Call):
            out.append(n)
        stack.extend(ast.iter_child_nodes(n))
    return out


def _names_in(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _releases_name(st: ast.AST, name: str,
                   summaries: Dict[str, Set[int]]) -> bool:
    """Statement releases or transfers ownership of `name`."""
    for call in _stmt_calls(st):
        short = _call_short(call)
        arg_names: List[Set[str]] = [_names_in(a) for a in call.args]
        flat = set().union(*arg_names) if arg_names else set()
        recv = call.func.value if isinstance(call.func, ast.Attribute) \
            else None
        recv_is = isinstance(recv, ast.Name) and recv.id == name
        if short in _RELEASES and (recv_is or name in flat):
            return True
        if short in _TRANSFERS and name in flat:
            return True
        # interprocedural edge: a local function whose summary says it
        # releases/consumes the parameter this name is passed as
        if short in summaries:
            for i, ns in enumerate(arg_names):
                if name in ns and i in summaries[short]:
                    return True
    for n in ast.walk(st):
        # escape: stored into a field/container slot, or returned
        if isinstance(n, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = n.targets if isinstance(n, ast.Assign) \
                else [n.target]
            v = getattr(n, "value", None)
            if v is not None and name in _names_in(v):
                for t in targets:
                    if isinstance(t, (ast.Attribute, ast.Subscript)):
                        return True
        if isinstance(n, (ast.Return, ast.Yield, ast.YieldFrom)) and \
                n.value is not None and name in _names_in(n.value):
            return True
    return False


def _acquisitions(st: ast.AST) -> List[_Resource]:
    out: List[_Resource] = []
    if isinstance(st, ast.Assign) and len(st.targets) == 1 and \
            isinstance(st.targets[0], ast.Name):
        tgt = st.targets[0].id
        for call in _stmt_calls(st):
            short = _call_short(call)
            if short in _ACQUIRE_BINDERS:
                out.append(_Resource(tgt, _ACQUIRE_BINDERS[short],
                                     st.lineno))
    for call in _stmt_calls(st):
        short = _call_short(call)
        if short in _ACQUIRE_BY_ARG and call.args and \
                isinstance(call.args[0], ast.Name):
            out.append(_Resource(call.args[0].id,
                                 _ACQUIRE_BY_ARG[short], st.lineno))
    return out


def _is_raising(st: ast.AST, own: Set[str]) -> Optional[int]:
    """Line of the first raising construct in the statement, skipping
    the calls that ARE this statement's own acquisitions (an
    acquisition that raises acquires nothing — atomic)."""
    for n in ast.walk(st):
        if isinstance(n, ast.Raise):
            return n.lineno
    for call in _stmt_calls(st):
        short = _call_short(call)
        if short in _RAISERS and short not in own:
            return call.lineno
        if short == "put" and isinstance(call.func, ast.Attribute) and \
                any(h in _dotted(call.func).lower()
                    for h in _STORE_HINTS):
            return call.lineno
    return None


def _try_protects(try_node: ast.Try, name: str,
                  summaries: Dict[str, Set[int]]) -> bool:
    """The try's finally or some handler releases/transfers `name` —
    the raising edge through this try cleans up the resource."""
    for st in try_node.finalbody:
        if _releases_name(st, name, summaries):
            return True
    for h in try_node.handlers:
        for st in h.body:
            if _releases_name(st, name, summaries):
                return True
    return False


def _fn_summaries(trees: Sequence[Tuple[str, ast.Module]]
                  ) -> Dict[str, Set[int]]:
    """name -> 0-based parameter positions the function releases or
    transfers somewhere in its body (self excluded from numbering).
    Two fixed-point rounds so a release can sit one call deeper."""
    fns: Dict[str, Tuple[ast.AST, List[str]]] = {}
    for _, tree in trees:
        for n in ast.walk(tree):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                params = [a.arg for a in n.args.args if a.arg != "self"]
                fns[n.name] = (n, params)
    summaries: Dict[str, Set[int]] = {k: set() for k in fns}
    for _ in range(2):
        for fname, (fn, params) in fns.items():
            for i, p in enumerate(params):
                if i in summaries[fname]:
                    continue
                for st in ast.walk(fn):
                    if isinstance(st, ast.stmt) and \
                            _releases_name(st, p, summaries):
                        summaries[fname].add(i)
                        break
    return {k: v for k, v in summaries.items() if v}


def _scan_l001_fn(fn: ast.AST, relpath: str,
                  summaries: Dict[str, Set[int]],
                  findings: List[Finding]) -> Dict[str, int]:
    stats = {"acquires": 0, "releases": 0}
    live: Dict[str, _Resource] = {}

    def walk(stmts: Sequence[ast.stmt],
             protectors: Tuple[ast.Try, ...]) -> None:
        for st in stmts:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue  # nested defs scanned as their own functions
            acqs = _acquisitions(st)
            own = {_call_short(c) for c in _stmt_calls(st)
                   if any(a.line == st.lineno for a in acqs)} \
                if acqs else set()
            for name in list(live):
                if _releases_name(st, name, summaries):
                    del live[name]
                    stats["releases"] += 1
            rl = _is_raising(st, own)
            if rl is not None:
                for name, res in list(live.items()):
                    if any(_try_protects(t, name, summaries)
                           for t in protectors):
                        continue
                    findings.append(Finding(
                        rule="L001", path=relpath, line=rl,
                        severity="error",
                        message=(
                            f"{res.kind} '{name}' acquired at line "
                            f"{res.line} has no release, transfer, or "
                            f"try-protection on the raising path at "
                            f"line {rl} — the acquisition strands if "
                            "this call raises"),
                        fix_hint=(
                            "wrap the raising region in try/finally "
                            "(or except-cleanup) that releases the "
                            "resource, hand ownership off before "
                            "raising ops, or annotate an intentional "
                            "site with `# ds-lint: ok L001 <why>`")))
                    del live[name]
            for a in acqs:
                live[a.name] = a
                stats["acquires"] += 1
            if isinstance(st, ast.Try):
                walk(st.body, protectors + (st,))
                for h in st.handlers:
                    walk(h.body, protectors)
                walk(st.orelse, protectors + (st,))
                walk(st.finalbody, protectors)
            elif isinstance(st, (ast.If,)):
                walk(st.body, protectors)
                walk(st.orelse, protectors)
            elif isinstance(st, (ast.For, ast.AsyncFor, ast.While)):
                walk(st.body, protectors)
                walk(st.orelse, protectors)
            elif isinstance(st, (ast.With, ast.AsyncWith)):
                walk(st.body, protectors)

    body = getattr(fn, "body", [])
    walk(body, ())
    return stats


def l001_findings(sources: Sequence[Tuple[str, str]]
                  ) -> Tuple[List[Finding], Dict[str, Dict[str, int]]]:
    """(findings, per-file acquire/release tallies for the ledger)."""
    trees: List[Tuple[str, ast.Module]] = []
    for rel, src in sources:
        try:
            trees.append((rel, ast.parse(src)))
        except SyntaxError:
            continue
    summaries = _fn_summaries(trees)
    findings: List[Finding] = []
    tallies: Dict[str, Dict[str, int]] = {}
    for rel, tree in trees:
        t = {"acquires": 0, "releases": 0}
        for n in ast.walk(tree):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                s = _scan_l001_fn(n, rel, summaries, findings)
                t["acquires"] += s["acquires"]
                t["releases"] += s["releases"]
        tallies[rel] = t
    return findings, tallies


# ----------------------------------------------------------------------
# L002: pool-accounting invariants
# ----------------------------------------------------------------------

# accounting attributes owned by the pool authorities: only `self.<a>`
# writes inside the owning class touch these
_ACCOUNTING_ATTRS = ("used_bytes", "peak_bytes", "_entries", "_bytes",
                     "_free", "_refcount", "_parked", "_seqs",
                     "n_tracked")


def _counter_literals(cls: ast.ClassDef) -> Optional[Set[str]]:
    """Keys of `self.counters = {literal}` declared in the class, or
    None when the class declares no literal counter authority."""
    for n in ast.walk(cls):
        targets: List[ast.AST] = []
        if isinstance(n, ast.Assign):
            targets, v = n.targets, n.value
        elif isinstance(n, ast.AnnAssign) and n.value is not None:
            targets, v = [n.target], n.value
        else:
            continue
        for t in targets:
            if isinstance(t, ast.Attribute) and t.attr == "counters" \
                    and isinstance(t.value, ast.Name) \
                    and t.value.id == "self" and isinstance(v, ast.Dict):
                keys = set()
                for k in v.keys:
                    if isinstance(k, ast.Constant) and \
                            isinstance(k.value, str):
                        keys.add(k.value)
                return keys
    return None


def l002_findings(sources: Sequence[Tuple[str, str]]
                  ) -> Tuple[List[Finding], Dict[str, List[str]]]:
    """(findings, {class: sorted declared counter keys} ledger)."""
    findings: List[Finding] = []
    authorities: Dict[str, List[str]] = {}
    for rel, src in sources:
        try:
            tree = ast.parse(src)
        except SyntaxError:
            continue
        classes = [n for n in ast.walk(tree)
                   if isinstance(n, ast.ClassDef)]
        for cls in classes:
            declared = _counter_literals(cls)
            if declared is not None:
                authorities[f"{rel}::{cls.name}"] = sorted(declared)
            for n in ast.walk(cls):
                if not isinstance(n, (ast.Assign, ast.AugAssign)):
                    continue
                targets = n.targets if isinstance(n, ast.Assign) \
                    else [n.target]
                for t in targets:
                    # (a) undeclared counter-key mutation
                    if declared is not None and \
                            isinstance(t, ast.Subscript) and \
                            isinstance(t.value, ast.Attribute) and \
                            t.value.attr == "counters" and \
                            isinstance(t.value.value, ast.Name) and \
                            t.value.value.id == "self" and \
                            isinstance(t.slice, ast.Constant) and \
                            isinstance(t.slice.value, str) and \
                            t.slice.value not in declared:
                        findings.append(Finding(
                            rule="L002", path=rel, line=n.lineno,
                            severity="error",
                            message=(
                                f"{cls.name} mutates undeclared counter "
                                f"key '{t.slice.value}' — the authority "
                                "literal in __init__ does not declare "
                                "it, so metrics() widens silently and "
                                "quiesce audits never see the tally"),
                            fix_hint=(
                                "declare the key (initialized to 0) in "
                                "the class's counters literal")))
                    # (b) accounting attribute written outside `self`
                    if isinstance(t, ast.Attribute) and \
                            t.attr in _ACCOUNTING_ATTRS and not (
                                isinstance(t.value, ast.Name)
                                and t.value.id == "self"):
                        findings.append(Finding(
                            rule="L002", path=rel, line=n.lineno,
                            severity="error",
                            message=(
                                f"accounting attribute "
                                f"'{_dotted(t)}' written outside its "
                                "authority class — pool bookkeeping "
                                "must flow through the owner's "
                                "methods"),
                            fix_hint=(
                                "add/extend a method on the owning "
                                "class and call it instead of poking "
                                "its accounting state")))
    return findings, authorities


# ----------------------------------------------------------------------
# L003: fault-coverage audit
# ----------------------------------------------------------------------

_FAULTS_REL = "deepspeed_tpu/resilience/faults.py"


def load_registry(repo_root: str
                  ) -> Tuple[Dict[str, Any], Dict[str, int]]:
    """(FAULT_POINTS literal, point -> declaration line), read from
    the faults module AST so the analyzer never imports product
    code."""
    path = os.path.join(repo_root, _FAULTS_REL)
    with open(path, "r", encoding="utf-8") as fh:
        tree = ast.parse(fh.read())
    for n in tree.body:
        if isinstance(n, ast.Assign) and len(n.targets) == 1 and \
                isinstance(n.targets[0], ast.Name) and \
                n.targets[0].id == "FAULT_POINTS":
            reg = ast.literal_eval(n.value)
            lines = {}
            if isinstance(n.value, ast.Dict):
                for k in n.value.keys:
                    if isinstance(k, ast.Constant):
                        lines[k.value] = k.lineno
            return reg, lines
    raise RuntimeError(f"FAULT_POINTS literal not found in {path}")


#: committed-lane sources: plans here may only name registered points
_STRICT_LANE_FILES = ("bench.py",)


def scan_lanes(repo_root: str) -> Dict[str, Dict[str, Set[int]]]:
    """lane relpath -> {point: {lines}} for every committed chaos
    lane: repo-root plan JSONs with a `faults` list, plus dict-literal
    fault specs in bench.py, scripts/, and tests/."""
    lanes: Dict[str, Dict[str, Set[int]]] = {}

    def note(lane: str, point: str, line: int) -> None:
        lanes.setdefault(lane, {}).setdefault(point, set()).add(line)

    for name in sorted(os.listdir(repo_root)):
        if not name.endswith(".json"):
            continue
        try:
            with open(os.path.join(repo_root, name)) as fh:
                d = json.load(fh)
        except (OSError, ValueError):
            continue
        if isinstance(d, dict) and isinstance(d.get("faults"), list):
            for spec in d["faults"]:
                if isinstance(spec, dict) and \
                        isinstance(spec.get("point"), str):
                    note(name, spec["point"], 0)

    py_files = [os.path.join(repo_root, "bench.py")]
    for sub in ("scripts", "tests"):
        root = os.path.join(repo_root, sub)
        if os.path.isdir(root):
            for dirpath, dirs, files in os.walk(root):
                dirs[:] = [d for d in dirs if d != "__pycache__"]
                for f in sorted(files):
                    if f.endswith(".py"):
                        py_files.append(os.path.join(dirpath, f))
    for path in py_files:
        if not os.path.isfile(path):
            continue
        rel = os.path.relpath(path, repo_root).replace(os.sep, "/")
        try:
            with open(path, "r", encoding="utf-8") as fh:
                tree = ast.parse(fh.read())
        except (OSError, SyntaxError):
            continue
        for n in ast.walk(tree):
            if not isinstance(n, ast.Dict):
                continue
            for k, v in zip(n.keys, n.values):
                if isinstance(k, ast.Constant) and k.value == "point" \
                        and isinstance(v, ast.Constant) and \
                        isinstance(v.value, str):
                    note(rel, v.value, k.lineno)
    return lanes


def scan_call_sites(repo_root: str) -> Dict[str, List[Tuple[str, int]]]:
    """point -> [(relpath, line)] for every fault_point("...") call
    compiled into deepspeed_tpu/."""
    sites: Dict[str, List[Tuple[str, int]]] = {}
    pkg = os.path.join(repo_root, "deepspeed_tpu")
    for dirpath, dirs, files in os.walk(pkg):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in sorted(files):
            if not f.endswith(".py"):
                continue
            path = os.path.join(dirpath, f)
            rel = os.path.relpath(path, repo_root).replace(os.sep, "/")
            try:
                with open(path, "r", encoding="utf-8") as fh:
                    tree = ast.parse(fh.read())
            except (OSError, SyntaxError):
                continue
            for n in ast.walk(tree):
                if isinstance(n, ast.Call) and \
                        _call_short(n) == "fault_point" and n.args and \
                        isinstance(n.args[0], ast.Constant) and \
                        isinstance(n.args[0].value, str):
                    sites.setdefault(n.args[0].value, []).append(
                        (rel, n.lineno))
    return sites


def l003_findings(
    registry: Dict[str, Any],
    lanes: Dict[str, Dict[str, Set[int]]],
    call_sites: Dict[str, List[Tuple[str, int]]],
    registry_lines: Optional[Dict[str, int]] = None,
) -> Tuple[List[Finding], Dict[str, List[str]]]:
    """(findings, coverage matrix point -> sorted firing lanes)."""
    registry_lines = registry_lines or {}
    findings: List[Finding] = []
    coverage: Dict[str, List[str]] = {
        p: sorted(lane for lane, pts in lanes.items() if p in pts)
        for p in sorted(registry)}
    for p in sorted(registry):
        if not coverage[p]:
            findings.append(Finding(
                rule="L003", path=_FAULTS_REL,
                line=registry_lines.get(p, 0), severity="error",
                message=(
                    f"registered fault point '{p}' is fired by ZERO "
                    "committed chaos lanes — its recovery path ships "
                    "untested"),
                fix_hint=(
                    "add the point to a committed plan (repo-root "
                    "*.json, a bench default plan, or an armed test) "
                    "or retire it from FAULT_POINTS")))
        if p not in call_sites:
            findings.append(Finding(
                rule="L003", path=_FAULTS_REL,
                line=registry_lines.get(p, 0), severity="error",
                message=(
                    f"registered fault point '{p}' has no "
                    "fault_point() call site in the tree — registry "
                    "drift"),
                fix_hint="wire the call site or retire the entry"))
    # committed plans / bench defaults naming an unregistered point is
    # drift; tests/scripts may use synthetic points for unit coverage
    for lane in sorted(lanes):
        strict = lane.endswith(".json") or lane in _STRICT_LANE_FILES
        if not strict:
            continue
        for p, lns in sorted(lanes[lane].items()):
            if p not in registry:
                findings.append(Finding(
                    rule="L003", path=lane, line=min(lns),
                    severity="error",
                    message=(
                        f"committed lane fires unregistered fault "
                        f"point '{p}' — a typo here silently never "
                        "injects"),
                    fix_hint="register the point in FAULT_POINTS or "
                             "fix the plan spelling"))
    for p in sorted(call_sites):
        if p not in registry:
            rel, line = call_sites[p][0]
            findings.append(Finding(
                rule="L003", path=rel, line=line, severity="error",
                message=(
                    f"fault_point('{p}') call site is not in the "
                    "FAULT_POINTS registry — unreachable by any "
                    "audited plan"),
                fix_hint="register the point in FAULT_POINTS"))
    return findings, coverage


def _deep_edges(sources: Sequence[Tuple[str, str]]
                ) -> Dict[str, List[str]]:
    """node key -> called short names, descending into NESTED defs —
    the C-series scanner stops at nested functions (its lock models
    don't need them), but a method that invokes `self._sample_fn`
    from a jit closure is still one call component for coverage."""
    edges: Dict[str, List[str]] = {}
    for rel, src in sources:
        try:
            tree = ast.parse(src)
        except SyntaxError:
            continue
        scopes: List[Tuple[str, ast.AST]] = []
        for n in tree.body:
            if isinstance(n, ast.ClassDef):
                for m in n.body:
                    if isinstance(m, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                        scopes.append((f"{rel}::{n.name}.{m.name}", m))
            elif isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scopes.append((f"{rel}::{n.name}", n))
        for key, fn in scopes:
            out: List[str] = []
            for c in ast.walk(fn):
                if isinstance(c, ast.Call):
                    short = _call_short(c)
                    if short:
                        out.append(short)
            edges[key] = out
    return edges


def l003_component_findings(
    sources: Sequence[Tuple[str, str]],
    hot_prefixes: Optional[Sequence[str]] = None,
) -> List[Finding]:
    """Hot-path mutators in a call-graph component containing zero
    fault points: the chaos machinery cannot perturb that subsystem at
    all. Built on the C-series walker's per-method call facts."""
    from .concurrency import _build_models
    if hot_prefixes is None:
        from .lint import _HOT_FN_PREFIXES as hot_prefixes

    mods, known, _ = _build_models(sources)
    # node key -> (relpath, fn-name, line, calls fault_point?)
    nodes: Dict[str, Tuple[str, str, int, bool]] = {}
    by_name: Dict[str, List[str]] = {}
    calls: Dict[str, List[str]] = {}

    def method_calls_fp(m) -> bool:
        return "fault_point" in m.bare_calls or any(
            e.name == "fault_point" for e in m.ext_calls)

    for mod in mods:
        for fname, m in mod.functions.items():
            key = f"{mod.relpath}::{fname}"
            nodes[key] = (mod.relpath, fname, m.line, method_calls_fp(m))
            by_name.setdefault(fname, []).append(key)
        for cls in mod.classes.values():
            for fname, m in cls.methods.items():
                key = f"{mod.relpath}::{cls.name}.{fname}"
                nodes[key] = (mod.relpath, fname, m.line,
                              method_calls_fp(m))
                by_name.setdefault(fname, []).append(key)
    for mod in mods:
        for cls in mod.classes.values():
            for fname, m in cls.methods.items():
                key = f"{mod.relpath}::{cls.name}.{fname}"
                out: List[str] = []
                for sc in m.self_calls:
                    tk = f"{mod.relpath}::{cls.name}.{sc.name}"
                    out.extend([tk] if tk in nodes
                               else by_name.get(sc.name, []))
                for ec in m.ext_calls:
                    out.extend(by_name.get(ec.name, []))
                for b in m.bare_calls:
                    out.extend(by_name.get(b, []))
                calls[key] = out
        for fname, m in mod.functions.items():
            key = f"{mod.relpath}::{fname}"
            out = []
            for ec in m.ext_calls:
                out.extend(by_name.get(ec.name, []))
            for b in m.bare_calls:
                out.extend(by_name.get(b, []))
            calls[key] = out
    for key, shorts in _deep_edges(sources).items():
        if key not in calls:
            continue
        for short in shorts:
            calls[key].extend(by_name.get(short, []))

    # union-find over undirected call edges
    parent = {k: k for k in nodes}

    def find(x: str) -> str:
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for a, outs in calls.items():
        for b in outs:
            if b in parent:
                ra, rb = find(a), find(b)
                if ra != rb:
                    parent[ra] = rb
    fp_roots = {find(k) for k, (_, _, _, fp) in nodes.items() if fp}

    findings: List[Finding] = []
    for key in sorted(nodes):
        rel, fname, line, _ = nodes[key]
        hot = any(fname == p or fname.startswith(p)
                  for p in hot_prefixes)
        if hot and fname != "__init__" and find(key) not in fp_roots:
            findings.append(Finding(
                rule="L003", path=rel, line=line, severity="error",
                message=(
                    f"hot-path mutator '{key.split('::')[1]}' lives in "
                    "a call component with NO fault point — no "
                    "committed chaos plan can perturb this subsystem"),
                fix_hint=(
                    "wire a fault_point() into the component's entry "
                    "path (and a committed lane that fires it), or "
                    "annotate with `# ds-lint: ok L003 <why>`")))
    return findings


# ----------------------------------------------------------------------
# L004: swallowed typed failures
# ----------------------------------------------------------------------

_BROAD_TYPES = ("Exception", "BaseException", "RuntimeError", "OSError")
_L4_VOCAB = ("import_kv", "export_kv", "adopt", "fault_point",
             "export_parked_kv", "pipe_permute_tick", "reconstruct",
             "_io_retry", "barrier", "broadcast_host")
_L4_HINTED = ("put", "get", "extend", "restore", "drain")


def _l4_vocab_call(call: ast.Call) -> bool:
    d = _dotted(call.func)
    short = d.split(".")[-1]
    if short in _L4_VOCAB:
        return True
    if short in _L4_HINTED and isinstance(call.func, ast.Attribute):
        low = d.lower()
        return any(h in low for h in _STORE_HINTS + ("state",))
    return False


def _handler_observes(handler: ast.ExceptHandler) -> bool:
    """Handler re-raises, logs, or counts — the typed signal is
    observed, not swallowed."""
    for n in ast.walk(handler):
        if isinstance(n, ast.Raise):
            return True
        if isinstance(n, ast.Call):
            d = _dotted(n.func).lower()
            short = d.split(".")[-1]
            if "log" in d or short in ("warn", "warning", "error",
                                       "info", "debug", "exception"):
                return True
            if short.startswith("_count"):
                return True
        if isinstance(n, (ast.AugAssign, ast.Assign)):
            targets = n.targets if isinstance(n, ast.Assign) \
                else [n.target]
            for t in targets:
                if isinstance(t, ast.Subscript):
                    d = _dotted(t.value).lower()
                    if "counter" in d or "rejection" in d or \
                            "stats" in d:
                        return True
    return False


def l004_tree_findings(tree: ast.Module, relpath: str,
                       rule: str = "L004",
                       severity: str = "error") -> List[Finding]:
    """Per-file L004 pass over a parsed module (also the body of the
    ds-lint R009 shim, which calls it with rule='R009',
    severity='warning' for hot files outside the lifecycle roots)."""
    findings: List[Finding] = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if fn.name == "__del__":
            continue  # interpreter-shutdown teardown must never raise
        for node in ast.walk(fn):
            if not isinstance(node, ast.Try):
                continue
            vocab = [c for st in node.body for c in _stmt_calls(st)
                     if _l4_vocab_call(c)]
            if not vocab:
                continue
            for h in node.handlers:
                broad = h.type is None or (
                    isinstance(h.type, (ast.Name, ast.Attribute)) and
                    _dotted(h.type).split(".")[-1] in _BROAD_TYPES)
                if not broad or _handler_observes(h):
                    continue
                names = sorted({_call_short(c) for c in vocab})
                findings.append(Finding(
                    rule=rule, path=relpath, line=h.lineno,
                    severity=severity,
                    message=(
                        f"broad except in {fn.name}() absorbs typed "
                        f"resilience errors from {', '.join(names)} "
                        "without counting, logging, or re-raising — "
                        "the recovery signal vanishes"),
                    fix_hint=(
                        "narrow the except to the expected type, or "
                        "count/log before swallowing; annotate an "
                        "intentional absorb with "
                        f"`# ds-lint: ok {rule} <why>`")))
    return findings


def l004_findings(sources: Sequence[Tuple[str, str]]) -> List[Finding]:
    findings: List[Finding] = []
    for rel, src in sources:
        try:
            tree = ast.parse(src)
        except SyntaxError:
            continue
        findings.extend(l004_tree_findings(tree, rel))
    return findings


# ----------------------------------------------------------------------
# dynamic quiesce audit (the L002 runtime half — bench exit gates)
# ----------------------------------------------------------------------

def quiesce_residuals(sched) -> Dict[str, int]:
    """Nonzero residuals one drained scheduler still holds: leaked
    pool blocks (free+parked must equal the pool), tracked sequences,
    spill-tier bytes/entries, and queue backlog. Empty dict = fully
    quiesced. Parked prefix-cache blocks are NOT residuals — they are
    reclaimable by design (allocator.available_blocks counts them)."""
    res: Dict[str, int] = {}
    eng = getattr(sched, "engine", None)
    state = getattr(eng, "state", None)
    alloc = getattr(state, "allocator", None)
    if alloc is not None:
        leaked = int(alloc.total_blocks) - int(alloc.available_blocks)
        if leaked:
            res["leaked_blocks"] = leaked
    if state is not None and int(getattr(state, "n_tracked", 0)):
        res["tracked_seqs"] = int(state.n_tracked)
    store = getattr(sched, "spill_store", None)
    if store is not None:
        s = store.stats()
        if s["spill_used_bytes"]:
            res["spill_bytes"] = int(s["spill_used_bytes"])
        if s["spill_entries"]:
            res["spill_entries"] = int(s["spill_entries"])
    for qname in ("waiting", "active", "handoff_ready"):
        q = getattr(sched, qname, None)
        if q is not None and len(q):
            res[f"backlog_{qname}"] = len(q)
    return res


def fleet_quiesce_residuals(router) -> Dict[str, Dict[str, int]]:
    """Per-replica residuals across a fleet, skipping DEAD replicas
    (their device state is unreachable by design until
    restore_replica drains it). Empty dict = the fleet quiesced."""
    out: Dict[str, Dict[str, int]] = {}
    dead = getattr(router, "dead", set())
    for i, s in enumerate(getattr(router, "schedulers", [])):
        if i in dead:
            continue
        r = quiesce_residuals(s)
        if r:
            out[f"replica{i}"] = r
    return out


# ----------------------------------------------------------------------
# drivers
# ----------------------------------------------------------------------

def _split_suppressed(
    findings: List[Finding],
    lines_by_path: Dict[str, List[str]],
) -> Tuple[List[Finding], List[Finding]]:
    active, suppressed = [], []
    for f in findings:
        lines = lines_by_path.get(f.path)
        ok = False
        if lines:
            for ln in (f.line, f.line - 1):
                if not (1 <= ln <= len(lines)):
                    continue
                m = _PRAGMA_RE.search(lines[ln - 1])
                if not m:
                    continue
                named = re.findall(r"[A-Z]\d{3}", m.group("rules"))
                # L004 and its lint shim R009 share pragma spelling
                if not named or f.rule in named or \
                        (f.rule == "L004" and "R009" in named):
                    ok = True
                    break
        (suppressed if ok else active).append(f)
    return active, suppressed


def analyze_sources(
    sources: Sequence[Tuple[str, str]],
    registry: Optional[Dict[str, Any]] = None,
    lanes: Optional[Dict[str, Dict[str, Set[int]]]] = None,
    call_sites: Optional[Dict[str, List[Tuple[str, int]]]] = None,
) -> LifecycleReport:
    """Run every L-check over in-memory (relpath, source) pairs —
    every source is treated as lifecycle-rooted. The registry/lane
    inputs are optional so fixtures can seed the L003 audit."""
    rep = LifecycleReport(files_checked=len(sources))
    f1, tallies = l001_findings(sources)
    f2, authorities = l002_findings(sources)
    findings = f1 + f2 + l004_findings(sources)
    findings += l003_component_findings(sources)
    coverage: Dict[str, List[str]] = {}
    if registry is not None:
        f3, coverage = l003_findings(registry, lanes or {},
                                     call_sites or {})
        findings += f3
    lines = {rel: src.splitlines() for rel, src in sources}
    rep.findings, rep.suppressed = _split_suppressed(findings, lines)
    rep.findings.sort(key=lambda f: (f.path, f.line, f.rule))
    rep.coverage = coverage
    rep.ledger = {"roots": tallies, "authorities": sorted(authorities)}
    return rep


def analyze_tree(repo_root: str) -> LifecycleReport:
    """The gate entry: L001/L002/L004 + the component pass over the
    lifecycle roots, the L003 registry/lane/call-site audit over the
    whole tree."""
    sources: List[Tuple[str, str]] = []
    for rel in LIFECYCLE_ROOTS:
        path = os.path.join(repo_root, rel)
        if os.path.isfile(path):
            with open(path, "r", encoding="utf-8") as fh:
                sources.append((rel, fh.read()))
    registry, reg_lines = load_registry(repo_root)
    lanes = scan_lanes(repo_root)
    call_sites = scan_call_sites(repo_root)

    rep = LifecycleReport(files_checked=len(sources))
    f1, tallies = l001_findings(sources)
    f2, authorities = l002_findings(sources)
    findings = f1 + f2 + l004_findings(sources)
    findings += l003_component_findings(sources)
    f3, coverage = l003_findings(registry, lanes, call_sites, reg_lines)
    findings += f3

    texts: Dict[str, str] = dict(sources)
    faults_path = os.path.join(repo_root, _FAULTS_REL)
    if os.path.isfile(faults_path):
        with open(faults_path, "r", encoding="utf-8") as fh:
            texts[_FAULTS_REL] = fh.read()
    rep.findings, rep.suppressed = _split_suppressed(
        findings, {rel: src.splitlines() for rel, src in texts.items()})
    rep.findings.sort(key=lambda f: (f.path, f.line, f.rule))
    rep.coverage = coverage
    # what LIFECYCLE.json pins is what a rule's verdict rests on, never
    # an inventory or a position: L001's acquire/release tallies a
    # root, the classes L002 holds to a counters literal (the keys are
    # the source's), the waived sites by function. A change that alters
    # no finding alters no byte of it (docs/static_analysis.md)
    rep.ledger = {
        "roots": tallies,
        "authorities": sorted(authorities),
        "suppressions": site_keys(rep.suppressed, texts),
    }
    return rep
