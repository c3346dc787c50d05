"""Concurrency analyzer: interprocedural lockset race detection.

The sixth analysis prong (docs/concurrency.md). Pure AST — no jax
import, safe anywhere — like ds-lint, but cross-file: the thread roots
that make `NvmeLayerStore.read_layer` concurrent live in
inference/engine.py (the io_callback registration), not in
offload_store.py, so a per-file heuristic can only guess. This module
builds the whole-package picture and checks it Eraser-style
(Savage et al.: the candidate lock set of a shared variable is the
intersection of locks held over all accesses; an empty intersection
across two concurrent contexts with at least one write is a race).

Checks
  C001  lockset race: a shared mutable `self.<attr>` reachable from two
        concurrent contexts (main thread + a thread/callback/atexit
        root, or two distinct roots) where the intersection of locks
        held across all access paths is empty and at least one path
        writes. Subsumes ds-lint R003's single-function heuristic with
        real path sensitivity: lint.py's `_check_r003` is now a thin
        shim over `r003_findings` below.
  C002  lock-order deadlock: the held-while-acquiring graph over every
        `with <lock>:` nest (interprocedural through self-calls) has a
        cycle — including the length-1 cycle of re-acquiring a plain
        (non-R) Lock already held.
  C003  callback-thread escape: a direct attribute store from an inline
        callback/thread body (lambda or nested def handed to
        `io_callback`/`Thread`/`atexit.register`) with no lock held and
        no delegation to a method — state mutated on a foreign thread
        without a choke point.

Thread roots (the contexts of C001):
  - `threading.Thread(target=...)` / `Timer(..., f)` /
    `start_new_thread(f, ...)`           -> "thread"
  - `*callback*(f, ...)` (io_callback, pure_callback,
    jax.debug.callback)                  -> "callback"
  - `atexit.register(f)`                 -> "atexit"
Root discovery is interprocedural: a callback body that calls
`store.read_layer(...)` where `store = self._nvme_store` and
`self._nvme_store = NvmeLayerStore(...)` roots
`NvmeLayerStore.read_layer` in the callback context; bare calls into
module functions (`fault_point`) are scanned transitively, so
`FaultPlan._hit` is rooted through the `fault_point -> plan._hit`
chain. Unresolvable receivers fall back to a *weak* name match applied
only to classes that themselves touch threading machinery (and never
for generic container-method names).

Every method except `__init__`/`__del__` is additionally reachable from
the main thread ("main" context) — unless it IS a root (a scanner loop
like `HealthMonitor._run` is not also called inline) or is named
`*_locked` (caller holds the lock by convention; its accesses count
only on propagated paths). Classes with threading markers but no
discoverable roots are checked in a conservative mode equivalent to the
old R003 rule: any unlocked write of a shared container fires.

Pragmas: `# ds-lint: ok C001 <reason>` on the finding line (or the line
above); `R003` suppresses C001 too — existing suppressions keep
working. `scripts/ds_gate.py race` gates the tree (CONCURRENCY.json ledger);
`resilience/interleave.py` is the dynamic twin that proves a finding
real or a suppression safe.
"""

import ast
import dataclasses
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .report import Finding, site_keys

__all__ = ["C_RULES", "ConcurrencyReport", "analyze_paths",
           "analyze_sources", "r003_findings"]

C_RULES = {
    "C001": "lockset race: shared attr with empty lock intersection "
            "across concurrent contexts",
    "C002": "lock-order deadlock: cycle in the held-while-acquiring "
            "graph",
    "C003": "callback-thread escape: unlocked direct attribute store "
            "from a callback/thread body",
}

_LOCK_CTORS = ("Lock", "RLock", "Condition", "Semaphore",
               "BoundedSemaphore")
_REENTRANT_OK = ("RLock", "Semaphore", "BoundedSemaphore")
_THREAD_CTORS = ("Thread", "Timer", "start_new_thread")
_THREAD_MARKERS = ("io_callback", "pure_callback", "Thread",
                   "ThreadPoolExecutor", "start_new_thread", "Timer")
_MUTATORS = ("append", "extend", "insert", "remove", "pop", "popitem",
             "clear", "update", "setdefault", "add", "discard")
_CONTAINER_CTORS = ("dict", "list", "set", "defaultdict", "OrderedDict",
                    "deque")
# never promoted to weak thread roots: generic container/file/thread
# protocol names that callback bodies call on objects we cannot type
_WEAK_DENY = set(_MUTATORS) | {
    "write", "flush", "close", "read", "get", "put", "start", "join",
    "wait", "set", "release", "acquire", "notify", "notify_all",
    "cancel", "send", "recv", "items", "keys", "values", "copy",
    "format", "split", "strip", "encode", "decode", "register"}

_PRAGMA_RE = re.compile(r"#\s*ds-lint:\s*ok\b(?P<rules>[^#\n]*)")


def _dotted(node: ast.AST) -> str:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _is_lock_expr(node: ast.AST) -> bool:
    d = _dotted(node).lower()
    return "lock" in d or "mutex" in d or "cond" in d


def _lock_name(node: ast.AST) -> str:
    """Normalized lock id for a `with <expr>:` item: `self.X` -> 'X',
    anything else -> its dotted spelling."""
    if isinstance(node, ast.Attribute) and \
            isinstance(node.value, ast.Name) and node.value.id == "self":
        return node.attr
    return _dotted(node) or "<lock>"


def _is_container(v: ast.AST) -> bool:
    return (
        isinstance(v, (ast.Dict, ast.List, ast.Set, ast.ListComp,
                       ast.DictComp, ast.SetComp))
        or (isinstance(v, ast.Call)
            and _dotted(v.func).split(".")[-1] in _CONTAINER_CTORS)
        or (isinstance(v, ast.BinOp) and isinstance(v.op, ast.Mult)
            and (isinstance(v.left, ast.List)
                 or isinstance(v.right, ast.List)))
    )


def _ann_class(ann: Optional[ast.AST], known: Set[str]) -> Optional[str]:
    """Class name referenced by an annotation (handles Optional[X])."""
    if ann is None:
        return None
    for n in ast.walk(ann):
        if isinstance(n, (ast.Name, ast.Attribute)):
            last = _dotted(n).split(".")[-1]
            if last in known:
                return last
    return None


# ----------------------------------------------------------------------
# per-method facts
# ----------------------------------------------------------------------

@dataclasses.dataclass
class _Access:
    attr: str
    write: bool
    line: int
    locks: frozenset  # relative to method entry


@dataclasses.dataclass
class _SelfCall:
    name: str
    locks: frozenset
    line: int


@dataclasses.dataclass
class _ExtCall:
    recv_type: Optional[str]  # resolved class name, None = unresolved
    name: str
    line: int


@dataclasses.dataclass
class _Acquire:
    lock: str
    held: frozenset
    line: int


@dataclasses.dataclass
class _Method:
    name: str
    line: int
    accesses: List[_Access] = dataclasses.field(default_factory=list)
    self_calls: List[_SelfCall] = dataclasses.field(default_factory=list)
    ext_calls: List[_ExtCall] = dataclasses.field(default_factory=list)
    bare_calls: List[str] = dataclasses.field(default_factory=list)
    acquires: List[_Acquire] = dataclasses.field(default_factory=list)
    root_kind: Optional[str] = None  # pseudo-methods carry theirs here
    # unlocked direct attribute stores, for C003 on pseudo bodies
    raw_stores: List[Tuple[str, int]] = dataclasses.field(
        default_factory=list)


@dataclasses.dataclass
class _Class:
    name: str
    relpath: str
    line: int
    locks: Dict[str, str] = dataclasses.field(default_factory=dict)
    shared: Set[str] = dataclasses.field(default_factory=set)
    attr_types: Dict[str, str] = dataclasses.field(default_factory=dict)
    methods: Dict[str, _Method] = dataclasses.field(default_factory=dict)
    threaded: bool = False
    # (method, kind) roots registered inside this module
    local_roots: Dict[str, str] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class _Mod:
    relpath: str
    classes: Dict[str, _Class] = dataclasses.field(default_factory=dict)
    # module function name -> facts (self-less _Method)
    functions: Dict[str, _Method] = dataclasses.field(default_factory=dict)
    global_types: Dict[str, str] = dataclasses.field(default_factory=dict)
    # module functions registered as thread/callback targets
    func_roots: Dict[str, str] = dataclasses.field(default_factory=dict)
    # plain `import X [as Y]` top-level names: calls on these are
    # library calls, never weak-root candidates
    import_names: Set[str] = dataclasses.field(default_factory=set)


@dataclasses.dataclass
class ConcurrencyReport:
    findings: List[Finding] = dataclasses.field(default_factory=list)
    suppressed: List[Finding] = dataclasses.field(default_factory=list)
    #: report.site_keys of `suppressed` (what CONCURRENCY.json holds)
    suppressed_sites: List[str] = dataclasses.field(default_factory=list)
    files_checked: int = 0
    ledger: Dict[str, dict] = dataclasses.field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return not self.findings

    def by_rule(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for f in self.findings:
            out[f.rule] = out.get(f.rule, 0) + 1
        return out

    def summary(self) -> str:
        return (f"ds-race: {self.files_checked} files, "
                f"{len(self.ledger)} analyzed classes, "
                f"{len(self.findings)} finding(s), "
                f"{len(self.suppressed)} suppressed by pragma")


# ----------------------------------------------------------------------
# model building
# ----------------------------------------------------------------------

def _callback_kind(call: ast.Call) -> Optional[Tuple[str, List[ast.AST]]]:
    """(root kind, candidate target exprs) when `call` registers a
    thread/callback entry, else None."""
    d = _dotted(call.func)
    short = d.split(".")[-1]
    args = list(call.args)
    kws = {kw.arg: kw.value for kw in call.keywords if kw.arg}
    if short == "Thread":
        tgt = [kws["target"]] if "target" in kws else []
        return ("thread", tgt)
    if short == "Timer":
        tgt = [kws["function"]] if "function" in kws else args[1:2]
        return ("thread", tgt)
    if short == "start_new_thread":
        return ("thread", args[:1])
    if d == "atexit.register" or (short == "register" and "atexit" in d):
        return ("atexit", args[:1])
    if "callback" in short:
        # io_callback(cb, result_shape, *args): only the callable slot
        return ("callback", args[:1] + [kws[k] for k in ("callback",)
                                        if k in kws])
    return None


def _local_types(fn: ast.AST, cls: Optional[_Class],
                 mod: _Mod, known: Set[str]) -> Dict[str, str]:
    """name -> class for locals we can type inside one function body."""
    env: Dict[str, str] = {}
    a = getattr(fn, "args", None)
    if a is not None:
        for arg in list(getattr(a, "posonlyargs", [])) + a.args + \
                a.kwonlyargs:
            t = _ann_class(arg.annotation, known)
            if t:
                env[arg.arg] = t
    for node in ast.walk(fn):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        tgt, v = node.targets[0], node.value
        if not isinstance(tgt, ast.Name):
            continue
        if isinstance(v, ast.Call):
            last = _dotted(v.func).split(".")[-1]
            if last in known:
                env[tgt.id] = last
        elif isinstance(v, ast.Attribute) and \
                isinstance(v.value, ast.Name) and v.value.id == "self" \
                and cls is not None and v.attr in cls.attr_types:
            env[tgt.id] = cls.attr_types[v.attr]
        elif isinstance(v, ast.Name) and v.id in mod.global_types:
            env[tgt.id] = mod.global_types[v.id]
    return env


def _scan_fn(fn: ast.AST, cls: Optional[_Class], mod: _Mod,
             known: Set[str], name: str, root_kind: Optional[str],
             registered: Dict[int, str],
             extra_env: Optional[Dict[str, str]] = None) -> _Method:
    """Extract accesses/calls/acquires from one function body, tracking
    the locks held at each site. Nested defs/lambdas that are NOT
    registered callbacks are scanned inline (held stack carries
    through); registered ones become separate pseudo-methods, handled
    by the caller (which passes the enclosing scope's types in
    `extra_env` so closure receivers still resolve)."""
    m = _Method(name=name, line=getattr(fn, "lineno", 0),
                root_kind=root_kind)
    env = dict(extra_env or {})
    env.update(_local_types(fn, cls, mod, known))
    shared = cls.shared if cls is not None else set()

    def self_attr(e: ast.AST) -> Optional[str]:
        if isinstance(e, ast.Attribute) and \
                isinstance(e.value, ast.Name) and e.value.id == "self":
            return e.attr
        return None

    def recv_type(e: ast.AST) -> Optional[str]:
        if isinstance(e, ast.Name):
            return env.get(e.id)
        a = self_attr(e)
        if a and cls is not None:
            return cls.attr_types.get(a)
        if isinstance(e, ast.Name) and e.id in mod.global_types:
            return mod.global_types[e.id]
        return None

    def note_store(e: ast.AST, held: frozenset, line: int,
                   write: bool = True) -> None:
        a = self_attr(e)
        if a is not None and a in shared:
            m.accesses.append(_Access(a, write, line, held))
        if write and isinstance(e, ast.Attribute) and not held:
            m.raw_stores.append((_dotted(e), line))

    def visit(node: ast.AST, held: frozenset) -> None:
        if id(node) in registered:
            return  # a registered callback body: scanned as a pseudo
        if isinstance(node, ast.With):
            acquired = []
            for item in node.items:
                if _is_lock_expr(item.context_expr):
                    lk = _lock_name(item.context_expr)
                    m.acquires.append(_Acquire(lk, held, node.lineno))
                    acquired.append(lk)
                else:
                    visit(item.context_expr, held)
            inner = held | frozenset(acquired)
            for st in node.body:
                visit(st, inner)
            return
        if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            flat: List[ast.AST] = []
            for t in targets:
                if isinstance(t, (ast.Tuple, ast.List)):
                    flat.extend(t.elts)
                else:
                    flat.append(t)
            for t in flat:
                if isinstance(t, ast.Subscript):
                    note_store(t.value, held, node.lineno)
                    visit(t.slice, held)
                else:
                    note_store(t, held, node.lineno)
            if getattr(node, "value", None) is not None:
                visit(node.value, held)
            return
        if isinstance(node, ast.Delete):
            for t in node.targets:
                if isinstance(t, ast.Subscript):
                    note_store(t.value, held, node.lineno)
            return
        if isinstance(node, ast.Call):
            callee = node.func
            if isinstance(callee, ast.Attribute):
                base, attr = callee.value, callee.attr
                if attr in _MUTATORS:
                    note_store(base, held, node.lineno)
                a = self_attr(base)
                if isinstance(base, ast.Name) and base.id == "self":
                    m.self_calls.append(
                        _SelfCall(attr, held, node.lineno))
                elif a is not None and cls is not None and \
                        a in cls.attr_types:
                    m.ext_calls.append(_ExtCall(
                        cls.attr_types[a], attr, node.lineno))
                elif not (isinstance(base, ast.Name)
                          and base.id in mod.import_names):
                    # library-module calls (os.pread, np.frombuffer…)
                    # never feed the weak-root name pool
                    m.ext_calls.append(_ExtCall(
                        recv_type(base), attr, node.lineno))
                # read of self.<shared>.method() receivers
                if a is not None and a in shared and attr not in _MUTATORS:
                    m.accesses.append(
                        _Access(a, False, node.lineno, held))
                visit(base, held)
            elif isinstance(callee, ast.Name):
                m.bare_calls.append(callee.id)
            for child in list(node.args) + \
                    [kw.value for kw in node.keywords]:
                visit(child, held)
            return
        if isinstance(node, ast.Attribute) and \
                isinstance(node.ctx, ast.Load):
            a = self_attr(node)
            if a is not None and a in shared:
                m.accesses.append(_Access(a, False, node.lineno, held))
                return
            visit(node.value, held)
            return
        for child in ast.iter_child_nodes(node):
            visit(child, held)

    body = fn.body if isinstance(fn.body, list) else [fn.body]
    for st in body:
        visit(st, frozenset())
    return m


def _build_models(sources: Sequence[Tuple[str, str]]
                  ) -> Tuple[List[_Mod], Set[str], int]:
    """Parse every (relpath, source), two passes: class inventory, then
    per-module models. Returns (modules, known class names, parsed)."""
    trees: List[Tuple[str, ast.Module]] = []
    known: Set[str] = set()
    for rel, src in sources:
        try:
            tree = ast.parse(src)
        except SyntaxError:
            continue
        trees.append((rel, tree))
        for n in ast.walk(tree):
            if isinstance(n, ast.ClassDef):
                known.add(n.name)
    mods = [_build_module(rel, tree, known) for rel, tree in trees]
    return mods, known, len(trees)


def _build_module(rel: str, tree: ast.Module, known: Set[str]) -> _Mod:
    mod = _Mod(relpath=rel)
    module_threaded = False
    for n in ast.walk(tree):
        if isinstance(n, ast.Import):
            for a in n.names:
                mod.import_names.add((a.asname or a.name).split(".")[0])
                if "thread" in a.name.lower():
                    module_threaded = True
        elif isinstance(n, ast.ImportFrom):
            if "thread" in (n.module or "").lower() or any(
                    "thread" in (a.name or "").lower() for a in n.names):
                module_threaded = True
    # module-level global types (G = Cls(...) / G: Optional[Cls] = ...)
    for n in tree.body:
        if isinstance(n, ast.AnnAssign) and \
                isinstance(n.target, ast.Name):
            t = _ann_class(n.annotation, known)
            if t:
                mod.global_types[n.target.id] = t
        elif isinstance(n, ast.Assign) and len(n.targets) == 1 and \
                isinstance(n.targets[0], ast.Name) and \
                isinstance(n.value, ast.Call):
            last = _dotted(n.value.func).split(".")[-1]
            if last in known:
                mod.global_types[n.targets[0].id] = last

    # class skeletons first (locks / shared / attr types / markers)
    for cnode in ast.walk(tree):
        if not isinstance(cnode, ast.ClassDef):
            continue
        c = _Class(name=cnode.name, relpath=rel, line=cnode.lineno)
        markers = {
            _dotted(n).split(".")[-1] for n in ast.walk(cnode)
            if isinstance(n, (ast.Name, ast.Attribute))}
        c.threaded = bool(markers & set(_THREAD_MARKERS)) or (
            module_threaded
            and any("lock" in mk.lower() for mk in markers))
        for n in ast.walk(cnode):
            if isinstance(n, ast.Assign):
                targets, v = n.targets, n.value
            elif isinstance(n, ast.AnnAssign) and n.value is not None:
                targets, v = [n.target], n.value
            else:
                continue
            for tgt in targets:
                if not (isinstance(tgt, ast.Attribute)
                        and isinstance(tgt.value, ast.Name)
                        and tgt.value.id == "self"):
                    continue
                if isinstance(v, ast.Call):
                    last = _dotted(v.func).split(".")[-1]
                    if last in _LOCK_CTORS:
                        c.locks[tgt.attr] = last
                        continue
                    if last in known:
                        c.attr_types[tgt.attr] = last
                if _is_container(v):
                    c.shared.add(tgt.attr)
        c.shared -= set(c.locks)
        mod.classes[cnode.name] = c

    # methods + registrations + pseudo-methods
    for cnode in ast.walk(tree):
        if isinstance(cnode, ast.ClassDef):
            c = mod.classes[cnode.name]
            for fnode in cnode.body:
                if isinstance(fnode, ast.FunctionDef):
                    _scan_scope(fnode, c, mod, known, fnode.name)
    for fnode in tree.body:
        if isinstance(fnode, ast.FunctionDef):
            _scan_scope(fnode, None, mod, known, fnode.name)
    # module-level registrations (atexit.register(main) at import)
    _collect_regs(tree.body, None, None, mod, known, skip_defs=True)
    return mod


def _collect_regs(stmts: Iterable[ast.AST], cls: Optional[_Class],
                  owner_fn: Optional[ast.AST], mod: _Mod,
                  known: Set[str], skip_defs: bool = False
                  ) -> Dict[int, Tuple[str, ast.AST]]:
    """Find thread/callback registrations in `stmts`. Marks self-method
    and module-function targets as roots; returns {id(node): (kind,
    node)} for inline lambda/local-def targets (pseudo bodies)."""
    local_defs: Dict[str, ast.AST] = {}
    if owner_fn is not None:
        for n in ast.walk(owner_fn):
            if isinstance(n, ast.FunctionDef) and n is not owner_fn:
                local_defs[n.name] = n
    pseudo: Dict[int, Tuple[str, ast.AST]] = {}
    for top in stmts:
        if skip_defs and isinstance(top, (ast.FunctionDef,
                                          ast.AsyncFunctionDef,
                                          ast.ClassDef)):
            continue
        for node in ast.walk(top):
            if not isinstance(node, ast.Call):
                continue
            reg = _callback_kind(node)
            if reg is None:
                continue
            kind, targets = reg
            for t in targets:
                if isinstance(t, ast.Lambda):
                    pseudo[id(t)] = (kind, t)
                elif isinstance(t, ast.Attribute) and \
                        isinstance(t.value, ast.Name) and \
                        t.value.id == "self" and cls is not None:
                    cls.local_roots.setdefault(t.attr, kind)
                elif isinstance(t, ast.Name):
                    if t.id in local_defs:
                        pseudo[id(local_defs[t.id])] = \
                            (kind, local_defs[t.id])
                    else:
                        # a module function (possibly defined later, or
                        # in another module); resolved at fixpoint time
                        mod.func_roots.setdefault(t.id, kind)
                elif isinstance(t, ast.Attribute):
                    # obj.method: resolved (or weak) at fixpoint time
                    mod.func_roots.setdefault(
                        "." + t.attr, kind)
    return pseudo


def _scan_scope(fnode: ast.FunctionDef, cls: Optional[_Class],
                mod: _Mod, known: Set[str], name: str) -> None:
    """Scan one def: registrations first (so registered inline bodies
    become pseudo-methods), then the body itself."""
    pseudo = _collect_regs([fnode], cls, fnode, mod, known)
    registered = {i: k for i, (k, _) in pseudo.items()}
    m = _scan_fn(fnode, cls, mod, known, name, None, registered)
    target = cls.methods if cls is not None else mod.functions
    target[name] = m
    outer_env = _local_types(fnode, cls, mod, known) if pseudo else {}
    # an inline body is named by its order among the bodies of its
    # kind in this def, never by its line: a root's name is a key of
    # CONCURRENCY.json, and a moved line is not a finding
    nth: Dict[str, int] = {}
    for nid, (kind, pnode) in sorted(
            pseudo.items(),
            key=lambda kv: getattr(kv[1][1], "lineno", 0)):
        nth[kind] = nth.get(kind, 0) + 1
        pname = f"{name}.<{kind}#{nth[kind]}>"
        pm = _scan_fn(pnode, cls, mod, known, pname, kind, {},
                      extra_env=outer_env)
        target[pname] = pm


# ----------------------------------------------------------------------
# interprocedural root discovery (fixpoint)
# ----------------------------------------------------------------------

def _discover_roots(mods: List[_Mod]
                    ) -> Tuple[Dict[Tuple[str, str], str],
                               Dict[str, str]]:
    """(strong roots {(class, method): kind}, weak root names
    {method: kind}) reached transitively from every registration."""
    by_class: Dict[str, _Class] = {}
    funcs: Dict[str, List[_Method]] = {}
    for mod in mods:
        for c in mod.classes.values():
            by_class.setdefault(c.name, c)
        for fname, fm in mod.functions.items():
            funcs.setdefault(fname, []).append(fm)

    strong: Dict[Tuple[str, str], str] = {}
    weak: Dict[str, str] = {}
    work: List[Tuple[_Method, Optional[str], str]] = []
    seen: Set[int] = set()

    def add_body(m: _Method, cls_name: Optional[str], kind: str) -> None:
        if id(m) in seen:
            return
        seen.add(id(m))
        work.append((m, cls_name, kind))

    def add_strong(cls_name: str, meth: str, kind: str) -> None:
        if (cls_name, meth) in strong:
            return
        strong[(cls_name, meth)] = kind
        c = by_class.get(cls_name)
        if c is not None and meth in c.methods:
            add_body(c.methods[meth], cls_name, kind)

    for mod in mods:
        for c in mod.classes.values():
            for meth, kind in c.local_roots.items():
                add_strong(c.name, meth, kind)
            for m in c.methods.values():
                if m.root_kind:  # pseudo callback bodies
                    add_body(m, c.name, m.root_kind)
        for fname, kind in mod.func_roots.items():
            if fname.startswith("."):
                meth = fname[1:]
                if meth not in _WEAK_DENY:
                    weak.setdefault(meth, kind)
                continue
            for fm in funcs.get(fname, []):
                add_body(fm, None, kind)

    while work:
        m, cls_name, kind = work.pop()
        for call in m.self_calls:
            if m.root_kind and cls_name is not None:
                # a pseudo body's self-call runs ON the foreign thread:
                # the method itself is a root
                add_strong(cls_name, call.name, kind)
            elif cls_name is not None:
                # a rooted method's self-call is a same-thread
                # continuation — not a new root (in-class propagation
                # owns its contexts), but its body must still be
                # scanned so cross-class chains like
                # read_layer -> _io_retry -> fault_point -> plan._hit
                # keep resolving
                c = by_class.get(cls_name)
                if c is not None and call.name in c.methods:
                    add_body(c.methods[call.name], cls_name, kind)
        for call in m.ext_calls:
            if call.recv_type is not None:
                add_strong(call.recv_type, call.name, kind)
            elif call.name not in _WEAK_DENY:
                weak.setdefault(call.name, kind)
        for fname in m.bare_calls:
            for fm in funcs.get(fname, []):
                add_body(fm, None, kind)
    return strong, weak


# ----------------------------------------------------------------------
# per-class lockset analysis
# ----------------------------------------------------------------------

_SKIP_METHODS = ("__init__", "__del__", "__post_init__")


def _class_roots(c: _Class, strong: Dict[Tuple[str, str], str],
                 weak: Dict[str, str]) -> Dict[str, str]:
    roots = dict(c.local_roots)
    for (cn, meth), kind in strong.items():
        if cn == c.name and meth in c.methods:
            roots.setdefault(meth, kind)
    for m in c.methods.values():
        if m.root_kind:
            roots.setdefault(m.name, m.root_kind)
    if c.threaded:
        for meth, kind in weak.items():
            if meth in c.methods:
                roots.setdefault(meth, kind)
    return roots


@dataclasses.dataclass
class _Site:
    ctx: str
    write: bool
    locks: frozenset
    line: int
    method: str


def _propagate(c: _Class, roots: Dict[str, str]
               ) -> Tuple[Dict[str, List[_Site]],
                          List[Tuple[str, str, frozenset, int, str]]]:
    """(per-attr access sites under each context, acquire records
    (ctx, lock, held, line, method)) via worklist over self-calls."""
    sites: Dict[str, List[_Site]] = {}
    acquires: List[Tuple[str, str, frozenset, int, str]] = []
    work: List[Tuple[str, str, frozenset]] = []
    for name, m in c.methods.items():
        if name in _SKIP_METHODS:
            continue
        if name in roots:
            work.append((name, f"{roots[name]}:{name}", frozenset()))
        elif not name.endswith("_locked") and not m.root_kind:
            work.append((name, "main", frozenset()))
    seen: Set[Tuple[str, str, frozenset]] = set()
    while work:
        item = work.pop()
        if item in seen:
            continue
        seen.add(item)
        name, ctx, entry = item
        m = c.methods.get(name)
        if m is None:
            continue
        for acc in m.accesses:
            sites.setdefault(acc.attr, []).append(_Site(
                ctx, acc.write, entry | acc.locks, acc.line, name))
        for acq in m.acquires:
            acquires.append((ctx, acq.lock, entry | acq.held,
                             acq.line, name))
        for call in m.self_calls:
            if call.name in c.methods and call.name not in _SKIP_METHODS:
                work.append((call.name, ctx, entry | call.locks))
    return sites, acquires


def _check_class(c: _Class, roots: Dict[str, str],
                 findings: List[Finding]) -> dict:
    """C001 for one class; returns its ledger entry."""
    entry = {
        "locks": sorted(c.locks),
        "roots": {k: roots[k] for k in sorted(roots)},
        "shared": sorted(c.shared),
        "mode": "lockset" if roots else "conservative",
        "guarded": {},
        "unguarded": [],
    }
    if roots:
        sites, _ = _propagate(c, roots)
        for attr in sorted(sites):
            sl = sites[attr]
            common = frozenset.intersection(*[s.locks for s in sl])
            ctxs = sorted({s.ctx for s in sl})
            writes = [s for s in sl if s.write]
            if common:
                entry["guarded"][attr] = sorted(common)
                continue
            entry["unguarded"].append(attr)
            if len(ctxs) < 2 or not writes:
                continue
            anchor = next((s for s in writes if not s.locks),
                          next((s for s in sl if not s.locks),
                               writes[0]))
            held = {s.ctx: sorted(s.locks) for s in sl}
            findings.append(Finding(
                rule="C001", path=c.relpath, line=anchor.line,
                severity="error",
                message=(
                    f"self.{attr} in {c.name} is reached from "
                    f"concurrent contexts {ctxs} with an empty lock "
                    f"intersection (locks per context: {held}) and "
                    f"written in {anchor.method}() — unordered "
                    "threads can interleave the mutation"),
                fix_hint=(
                    "guard every path with one class lock, rename the "
                    "method *_locked if the caller holds it, or "
                    "annotate a provably single-threaded phase with "
                    "`# ds-lint: ok C001 <why>`")))
    else:
        # conservative: the old R003 semantics — any unlocked write of
        # a shared container in a threaded class with no known roots
        for name in sorted(c.methods):
            m = c.methods[name]
            if name in _SKIP_METHODS or name.endswith("_locked"):
                continue
            for acc in m.accesses:
                if acc.write and not acc.locks:
                    if acc.attr not in entry["unguarded"]:
                        entry["unguarded"].append(acc.attr)
                    findings.append(Finding(
                        rule="C001", path=c.relpath, line=acc.line,
                        severity="error",
                        message=(
                            f"self.{acc.attr} (shared mutable container "
                            f"in threaded class {c.name}) mutated in "
                            f"{name}() outside a `with <lock>:` block — "
                            "no thread roots are discoverable here, so "
                            "every method is assumed concurrent (the "
                            "NvmeLayerStore._inflight race class)"),
                        fix_hint=(
                            "guard the mutation with the class lock, "
                            "rename the method *_locked if the caller "
                            "holds it, or annotate single-threaded "
                            "phases with `# ds-lint: ok C001 <why>`")))
        for attr in sorted(c.shared):
            if attr not in entry["unguarded"]:
                all_locked = all(
                    acc.locks for m in c.methods.values()
                    for acc in m.accesses if acc.attr == attr)
                if all_locked:
                    entry["guarded"][attr] = sorted(c.locks)
    return entry


def _check_deadlocks(mods: List[_Mod],
                     strong: Dict[Tuple[str, str], str],
                     weak: Dict[str, str],
                     findings: List[Finding]) -> None:
    """C002: cycles in the global held-while-acquiring graph."""
    edges: Dict[str, Dict[str, Tuple[str, int, str]]] = {}
    for mod in mods:
        for c in mod.classes.values():
            roots = _class_roots(c, strong, weak)
            if not (roots or c.threaded or c.locks):
                continue
            _, acquires = _propagate(c, roots or {
                n: "any" for n in c.methods if n not in _SKIP_METHODS})
            for ctx, lock, held, line, meth in acquires:
                ln = f"{c.name}.{lock}"
                kind = c.locks.get(lock, "")
                for h in held:
                    hn = f"{c.name}.{h}"
                    if hn == ln and kind in _REENTRANT_OK:
                        continue
                    edges.setdefault(hn, {}).setdefault(
                        ln, (c.relpath, line, meth))
        for fm in mod.functions.values():
            for acq in fm.acquires:
                for h in acq.held:
                    if h != acq.lock:
                        edges.setdefault(h, {}).setdefault(
                            acq.lock, (mod.relpath, acq.line, fm.name))

    emitted: Set[frozenset] = set()

    def dfs(node: str, path: List[str]) -> None:
        for nxt, (rel, line, meth) in sorted(edges.get(node, {}).items()):
            if nxt in path:
                cyc = path[path.index(nxt):] + [nxt]
                key = frozenset(cyc)
                if key in emitted:
                    continue
                emitted.add(key)
                findings.append(Finding(
                    rule="C002", path=rel, line=line, severity="error",
                    message=(
                        "lock-order cycle "
                        + " -> ".join(cyc)
                        + f" (closing acquisition in {meth}()) — two "
                        "threads taking the ends in opposite order "
                        "deadlock; a plain Lock re-acquired while held "
                        "self-deadlocks"),
                    fix_hint=(
                        "impose one global lock order (acquire in a "
                        "fixed sequence), release before calling out, "
                        "or make the inner lock an RLock if "
                        "re-entrancy is the intent")))
            elif len(path) < 12:
                dfs(nxt, path + [nxt])

    for start in sorted(edges):
        dfs(start, [start])


def _check_escapes(mods: List[_Mod], c001_attrs: Set[Tuple[str, str]],
                   findings: List[Finding]) -> None:
    """C003: unlocked direct attribute stores inside registered inline
    callback/thread bodies (and rooted module functions)."""
    for mod in mods:
        for c in mod.classes.values():
            for m in c.methods.values():
                if not m.root_kind:
                    continue
                for dotted, line in m.raw_stores:
                    attr = dotted.split(".")[-1]
                    if dotted.startswith("self.") and \
                            (c.name, attr) in c001_attrs:
                        continue  # C001 already owns this race
                    if attr in c.locks:
                        continue
                    findings.append(Finding(
                        rule="C003", path=c.relpath, line=line,
                        severity="error",
                        message=(
                            f"`{dotted}` stored from a {m.root_kind} "
                            f"body ({m.name}) with no lock held — "
                            "state escapes onto a foreign thread "
                            "without a choke point"),
                        fix_hint=(
                            "hold the owning lock around the store, or "
                            "route the result through a lock-guarded "
                            "method; annotate a deliberate handoff "
                            "with `# ds-lint: ok C003 <why>`")))
        for fname, kind in mod.func_roots.items():
            for fm in ([mod.functions[fname]]
                       if fname in mod.functions else []):
                for dotted, line in fm.raw_stores:
                    findings.append(Finding(
                        rule="C003", path=mod.relpath, line=line,
                        severity="error",
                        message=(
                            f"`{dotted}` stored from {kind}-rooted "
                            f"function {fname}() with no lock held"),
                        fix_hint="hold the owning lock around the "
                                 "store or hand off through a queue"))


# ----------------------------------------------------------------------
# drivers
# ----------------------------------------------------------------------

def _split_suppressed(findings: List[Finding], lines_by_path:
                      Dict[str, List[str]]
                      ) -> Tuple[List[Finding], List[Finding]]:
    active, suppressed = [], []
    for f in findings:
        lines = lines_by_path.get(f.path, [])
        ok = False
        for ln in (f.line, f.line - 1):
            if not (1 <= ln <= len(lines)):
                continue
            mt = _PRAGMA_RE.search(lines[ln - 1])
            if not mt:
                continue
            named = re.findall(r"[CR]\d{3}", mt.group("rules"))
            if not named or f.rule in named or \
                    (f.rule == "C001" and "R003" in named):
                ok = True
                break
        (suppressed if ok else active).append(f)
    return active, suppressed


def analyze_sources(sources: Sequence[Tuple[str, str]]
                    ) -> ConcurrencyReport:
    """Whole-program analysis over (relpath, source) pairs."""
    mods, known, parsed = _build_models(sources)
    strong, weak = _discover_roots(mods)
    report = ConcurrencyReport(files_checked=parsed)
    findings: List[Finding] = []
    c001_attrs: Set[Tuple[str, str]] = set()
    for mod in mods:
        for c in mod.classes.values():
            roots = _class_roots(c, strong, weak)
            if not (roots or (c.threaded and c.shared)):
                continue
            before = len(findings)
            entry = _check_class(c, roots, findings)
            for f in findings[before:]:
                mobj = re.match(r"self\.(\w+)", f.message)
                if mobj:
                    c001_attrs.add((c.name, mobj.group(1)))
            report.ledger[f"{c.relpath}::{c.name}"] = entry
    _check_deadlocks(mods, strong, weak, findings)
    _check_escapes(mods, c001_attrs, findings)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    lines_by_path = {rel: src.splitlines() for rel, src in sources}
    report.findings, report.suppressed = _split_suppressed(
        findings, lines_by_path)
    report.suppressed_sites = site_keys(report.suppressed, dict(sources))
    sup_by_key: Dict[str, int] = {}
    for f in report.suppressed:
        for key in report.ledger:
            if key.startswith(f.path + "::"):
                sup_by_key[key] = sup_by_key.get(key, 0) + 1
    for key, entry in report.ledger.items():
        entry["suppressed"] = sup_by_key.get(key, 0)
    return report


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


def analyze_paths(paths: Sequence[str],
                  base: Optional[str] = None) -> ConcurrencyReport:
    sources = []
    for path in _iter_py(paths):
        rel = os.path.relpath(path, base) if base else path
        with open(path, "r", encoding="utf-8") as fh:
            sources.append((rel, fh.read()))
    return analyze_sources(sources)


def r003_findings(tree: ast.Module, relpath: str) -> List[Finding]:
    """Per-file C001 pass for the ds-lint R003 shim: same lockset
    engine, roots limited to what this file registers (suppression is
    the caller's — lint runs its own pragma splitter)."""
    known = {n.name for n in ast.walk(tree)
             if isinstance(n, ast.ClassDef)}
    mod = _build_module(relpath, tree, known)
    strong, weak = _discover_roots([mod])
    findings: List[Finding] = []
    for c in mod.classes.values():
        roots = _class_roots(c, strong, weak)
        if not (roots or (c.threaded and c.shared)):
            continue
        _check_class(c, roots, findings)
    out = [dataclasses.replace(f, rule="R003")
           for f in findings if f.rule == "C001"]
    out.sort(key=lambda f: (f.line, f.rule))
    return out
