"""Collective-traffic accounting from compiled HLO.

The comms-logging redesign (ref: deepspeed/utils/comms_logging.py
CommsLogger:67 + comm/comm.py timed_op:101). The reference wraps every
eager collective call in a timing decorator; on TPU the engine issues NO
collectives from Python — XLA's SPMD partitioner inserts them — so the
per-op volume story must come from the compiled program itself. This
module parses the post-partitioning HLO of a compiled step and returns
exact per-collective byte counts: ground truth, not invocation-side
bookkeeping (fixes VERDICT r1 W6: the facade logger observed nothing).
"""

import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    # token/opaque types carry no payload (sequencing values only)
    "token": 0, "opaque": 0,
}

_COLLECTIVES = (
    "all-gather-start", "all-gather",
    "all-reduce-start", "all-reduce",
    # async sugar prints generic async-start wrappers as `<op>-start`
    # for these two as well (the overlap restructure's bucketed
    # reduce-scatters land in exactly this form on TPU) — without the
    # -start alternatives a sugared instance would count ZERO times:
    # the start site wouldn't match and the sugar hides the wrapped body
    "reduce-scatter-start", "reduce-scatter",
    "all-to-all-start", "all-to-all",
    "collective-permute-start", "collective-permute",
    "collective-broadcast",
)

# One dimension: static (`128`) or dynamic-bounded (`<=128`).
_DIM = r"(?:<=)?\d+"
# One array shape: `bf16[4,128]`, `f32[]`, `bf16[<=128,64]`.
_ARRAY = rf"[a-z][a-z0-9]*\[(?:{_DIM}(?:,\s*{_DIM})*)?\]"
# A result: a bare array (with optional layout suffix), a tuple, or a
# tuple of tuples (async -start ops on multi-operand collectives emit
# e.g. `((bf16[4], bf16[8]), (bf16[16], bf16[32]))`).
_INSTR_RE = re.compile(
    r"=\s*(?P<result>\((?:[^()]|\([^()]*\))*\)|" + _ARRAY + r"[^ ]*)\s+"
    r"(?P<op>" + "|".join(_COLLECTIVES) + r")\((?P<tail>[^\n]*)"
)
_SHAPE_RE = re.compile(
    rf"(?P<dtype>[a-z][a-z0-9]*)\[(?P<dims>(?:{_DIM}(?:,\s*{_DIM})*)?)\]"
)
# `replica_groups={{0,1},{2,3}}` (explicit) — first group's member count
_GROUPS_EXPLICIT_RE = re.compile(r"replica_groups=\{\{(?P<first>[\d,]+)\}")
# `replica_groups=[4,2]<=[8]` (iota form): 4 groups of 2
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(?P<n>\d+),(?P<size>\d+)\]")
# full iota form incl. the generator dims and optional transpose:
# `replica_groups=[4,2]<=[2,4]T(1,0)`
_GROUPS_IOTA_FULL_RE = re.compile(
    r"replica_groups=\[(?P<n>\d+),(?P<size>\d+)\]"
    r"<=\[(?P<dims>[\d,]+)\](?:T\((?P<perm>[\d,]+)\))?")
_GROUPS_ALL_EXPLICIT_RE = re.compile(
    r"replica_groups=\{(?P<body>\{[\d,]*\}(?:,\{[\d,]*\})*)\}")
_PAIRS_RE = re.compile(r"source_target_pairs=\{(?P<body>[^}]*(?:\},\{[^}]*)*)\}")
# the TPU compiler's async collective fusion: ONE collective becomes a
# chain of instructions of its kind that share a `chain_id`, the first
# inside the fusion named `async-collective-start.<n>`, the last inside
# `async-collective-done.<n>`, any between inside the compute fusions
# it runs beside
_CHAIN_RE = re.compile(r'chain_id="(?P<id>\d+)"')
_CHANNEL_RE = re.compile(r"channel_id=(?P<id>\d+)")


_ASYNC_CALLS_RE = re.compile(
    r"(?:" + "|".join(c for c in _COLLECTIVES if c.endswith("-start"))
    + r")\([^\n]*?calls=%?(?P<comp>[\w.\-]+)")


def _async_wrapped_spans(hlo_text: str) -> List[Tuple[int, int]]:
    """Text spans of computations wrapped by a counted `-start` op
    (async sugar printed with its body): collectives inside them must
    not be counted again next to the start site."""
    spans = []
    for m in _ASYNC_CALLS_RE.finditer(hlo_text):
        h = re.search(r"^\s*%?" + re.escape(m.group("comp"))
                      + r"\b[^\n=]*\{\s*$", hlo_text, re.M)
        if h is not None:
            end = hlo_text.find("\n}", h.end())
            spans.append((h.end(), end if end != -1 else len(hlo_text)))
    return spans


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        d = d.strip().replace("<=", "")  # dynamic dim: count its bound
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _top_level_elements(result: str) -> List[str]:
    """Split a tuple result string into its top-level elements
    (`((a, b), (c, d), u32[])` -> ['(a, b)', '(c, d)', 'u32[]']).
    Returns [] for a non-tuple result."""
    result = result.strip()
    if not result.startswith("("):
        return []
    body = result[1:result.rfind(")")]
    out, depth, start = [], 0, 0
    for i, ch in enumerate(body):
        if ch in "({[":  # layout `{1,0}` / dims `[4,128]` commas nest too
            depth += 1
        elif ch in ")}]":
            depth -= 1
        elif ch == "," and depth == 0:
            out.append(body[start:i].strip())
            start = i + 1
    tailpiece = body[start:].strip()
    if tailpiece:
        out.append(tailpiece)
    return out


def _start_payload_bytes(result: str) -> int:
    """Payload of an async `-start` op's result tuple: the OUTPUT lives
    in the second top-level element — `(operand(s), output(s), aux...)`
    — so the payload is that element's shape sum. This matters for ops
    where max-of-members picks the wrong side: a reduce-scatter-start's
    output is SMALLER than its input (max would return input bytes),
    and a collective-permute-start carries trailing u32[] context
    scalars. Falls back to max over all members when the tuple doesn't
    have two elements."""
    elems = _top_level_elements(result)
    if len(elems) >= 2:
        return sum(_shape_bytes(s.group("dtype"), s.group("dims"))
                   for s in _SHAPE_RE.finditer(elems[1]))
    sizes = [_shape_bytes(s.group("dtype"), s.group("dims"))
             for s in _SHAPE_RE.finditer(result)]
    return max(sizes) if sizes else 0


_GATHER_RE = re.compile(
    # result-shape ... gather( — the lookbehind keeps all-gather (a
    # collective, counted by parse_hlo_collectives) out of this probe
    r"=\s*(?P<dtype>[a-z]+\d+)\[(?P<dims>[0-9,<=\s]*)\][^\n]*?"
    r"(?<![\w-])gather\(",
)


def max_gather_bytes(hlo_text: str) -> int:
    """Largest gather-instruction RESULT in the program, in bytes.

    The ds_schedule gate probes the fused paged-decode program with
    this: the Pallas kernel indexes KV blocks in place, so the only
    gathers left are small table/embedding lookups — a regression back
    to the per-step block-table gather (k_cache[block_table]
    materializing [S, NB*bs, KV, D]) shows up as a result orders of
    magnitude past the committed limit."""
    best = 0
    for m in _GATHER_RE.finditer(hlo_text):
        best = max(best, _shape_bytes(m.group("dtype"), m.group("dims")))
    return best


def _group_size(tail: str) -> int:
    """Replica-group size of one collective instruction's attribute
    tail (0 = not stated / flat world group `{}`)."""
    m = _GROUPS_EXPLICIT_RE.search(tail)
    if m is not None:
        return len([x for x in m.group("first").split(",") if x.strip()])
    m = _GROUPS_IOTA_RE.search(tail)
    if m is not None:
        return int(m.group("size"))
    return 0


def _iota_group_list(n: int, size: int, dims: List[int],
                     perm: Optional[List[int]]) -> List[List[int]]:
    """Expand the iota replica-group form to explicit member lists:
    iota over prod(dims), reshaped to `dims`, transposed by `perm` when
    present, then reshaped to n groups of `size`."""
    total = 1
    for d in dims:
        total *= d
    vals = list(range(total))
    if perm and list(perm) != list(range(len(dims))):
        strides = [0] * len(dims)
        s = 1
        for i in range(len(dims) - 1, -1, -1):
            strides[i] = s
            s *= dims[i]
        tdims = [dims[p] for p in perm]
        out: List[int] = []
        idx = [0] * len(tdims)
        for _ in range(total):
            out.append(sum(idx[j] * strides[perm[j]]
                           for j in range(len(perm))))
            for j in range(len(tdims) - 1, -1, -1):
                idx[j] += 1
                if idx[j] < tdims[j]:
                    break
                idx[j] = 0
        vals = out
    return [vals[i * size:(i + 1) * size] for i in range(n)]


def parse_replica_groups(tail: str) -> List[List[int]]:
    """FULL replica-group member lists of one collective's attribute
    tail ([] = unstated / flat world `{}`): explicit `{{0,1},{2,3}}`
    and iota `[n,size]<=[dims](T(perm))` forms both expand to explicit
    device-id lists — the input the hierarchy-placement check (S008)
    maps onto slice boundaries."""
    m = _GROUPS_ALL_EXPLICIT_RE.search(tail)
    if m is not None:
        return [[int(x) for x in g.split(",") if x.strip()]
                for g in re.findall(r"\{([\d,]*)\}", m.group("body"))
                if g.strip()]
    m = _GROUPS_IOTA_FULL_RE.search(tail)
    if m is not None:
        dims = [int(d) for d in m.group("dims").split(",")]
        perm = ([int(p) for p in m.group("perm").split(",")]
                if m.group("perm") else None)
        return _iota_group_list(int(m.group("n")), int(m.group("size")),
                                dims, perm)
    m = _GROUPS_IOTA_RE.search(tail)
    if m is not None:  # bare [n,size] with no generator: contiguous iota
        return _iota_group_list(int(m.group("n")), int(m.group("size")),
                                [int(m.group("n")) * int(m.group("size"))],
                                None)
    return []


def parse_source_target_pairs(tail: str) -> List[Tuple[int, int]]:
    """(src, dst) device-id pairs of a collective-permute's attribute
    tail ([] when unstated)."""
    m = _PAIRS_RE.search(tail)
    if m is None:
        return []
    return [(int(a), int(b))
            for a, b in re.findall(r"\{(\d+),(\d+)\}",
                                   "{" + m.group("body") + "}")]


def parse_hlo_collectives(hlo_text: str) -> List[Dict]:
    """Every collective instruction in the HLO with its payload bytes.

    Async `-start` ops return a tuple carrying the input operand alongside
    the output (e.g. `(bf16[4,128], bf16[16,128]) all-gather-start`); the
    payload is the OUTPUT — the second top-level tuple element, which
    also handles multi-operand `((ins), (outs))` forms (outputs summed)
    and ops whose output is not the largest member (reduce-scatter-start
    shrinks; collective-permute-start carries trailing u32[] context
    scalars). Plain (possibly multi-result all-to-all) forms sum.

    Each record additionally carries the operand payload (`operand_bytes`,
    summed over the shapes inside the call parens) and the replica-group
    size (`group_size`, 0 when unstated/flat) — the inputs the costmodel's
    per-link volume math needs — the instruction's `name` (what
    collective_manifest resolves to a site) and `async`: whether the
    compiler left it asynchronous (a `-start` op, or a member of an
    async-collective-fusion chain).

    Async pairs count ONCE: `-done` ops never match (the op alternation
    requires an opening paren right after the collective kind), when
    a `-start` op carries a `calls=` computation (async sugar printed
    alongside its wrapped body) the body's inner collective is skipped —
    only the start site contributes bytes — and of the instructions that
    share a `chain_id` (one collective the TPU compiler runs as an
    async-collective-start / -done chain of fusions) the first in the
    text stands for all. A collective inside a fusion or while-loop
    body has no start site and IS attributed (once, like every other
    instruction — trip counts are not statically known)."""
    skip_spans = _async_wrapped_spans(hlo_text)
    chains = set()
    out = []
    for m in _INSTR_RE.finditer(hlo_text):
        if any(lo <= m.start() < hi for lo, hi in skip_spans):
            continue  # body of an already-counted async -start wrapper
        chain = _CHAIN_RE.search(m.group("tail"))
        if chain is not None:
            channel = _CHANNEL_RE.search(m.group("tail"))
            key = (chain.group("id"), channel and channel.group("id"))
            if key in chains:
                continue  # a later link of a chain already counted
            chains.add(key)
        is_start = m.group("op").endswith("-start")
        op = m.group("op").replace("-start", "")
        result = m.group("result")
        sizes = [
            _shape_bytes(s.group("dtype"), s.group("dims"))
            for s in _SHAPE_RE.finditer(result)
        ]
        if not sizes:
            continue
        nbytes = _start_payload_bytes(result) if is_start else sum(sizes)
        dtypes = sorted({s.group("dtype") for s in _SHAPE_RE.finditer(result)})
        tail = m.group("tail")
        operands = tail.split(")", 1)[0]
        operand_bytes = sum(
            _shape_bytes(s.group("dtype"), s.group("dims"))
            for s in _SHAPE_RE.finditer(operands)
        )
        out.append({"op": op, "bytes": nbytes, "dtypes": dtypes,
                    "operand_bytes": operand_bytes,
                    "group_size": _group_size(tail),
                    "name": _name_before(hlo_text, m.start()),
                    "async": is_start or chain is not None})
    return out


def _name_before(hlo_text: str, eq: int) -> str:
    """The name of the instruction whose `=` sits at `eq`: the last
    word of its line before it (`  ROOT %all-reduce.45 = ...`)."""
    head = hlo_text[hlo_text.rfind("\n", 0, eq) + 1:eq].split()
    return head[-1].lstrip("%") if head else ""


# --- entry-parameter extraction (analysis/sanitizer.py consumer) -------
#
# Post-partitioning entry parameters carry the per-shard shape chosen by
# the SPMD partitioner plus the final `sharding=` annotation and the
# `op_name` metadata JAX stamps with the argument keypath — ground truth
# for whether a declared PartitionSpec survived compilation.

_PARAM_RE = re.compile(
    r"=\s*(?P<result>"
    r"\((?:[^()\n]|\([^()\n]*\))*\)"          # tuple-nested param
    rf"|(?:[a-z][a-z0-9]*)(?:\[(?:{_DIM}(?:,\s*{_DIM})*)?\])?"  # array/token
    r")(?:\{[^}]*\})?"                         # optional layout suffix
    r"[^\n]*?parameter\((?P<idx>\d+)\)(?P<rest>[^\n]*)"
)
# an array (or bare token/opaque) result — the non-tuple param form
_RESULT_SHAPE_RE = re.compile(
    rf"^(?P<dtype>[a-z][a-z0-9]*)(?:\[(?P<dims>(?:{_DIM}(?:,\s*{_DIM})*)?)\])?$"
)
_SHARDING_ATTR_RE = re.compile(r"sharding=\{(?P<sharding>[^}]*)\}")
_OP_NAME_RE = re.compile(r'op_name="(?P<name>(?:[^"\\]|\\.)*)"')


def _entry_text(hlo_text: str) -> str:
    """The ENTRY computation's body (parameters elsewhere belong to
    fusions/called computations, not the program signature)."""
    m = re.search(r"^ENTRY\b[^\n]*\{", hlo_text, re.M)
    if m is None:
        return hlo_text
    end = hlo_text.find("\n}", m.end())
    return hlo_text[m.end(): end if end != -1 else len(hlo_text)]


def parse_entry_parameters(hlo_text: str) -> List[Dict]:
    """Entry parameters of a compiled module: per-shard dtype/dims plus
    the `sharding=` annotation and op_name keypath (when present).

    Returns [{index, dtype, dims, sharding, op_name, nbytes}], dims as a
    tuple of ints (dynamic `<=N` bounds count as N). Newer XLA emits
    entry params this parser must not trip on: token-typed params
    (`token[]` — dtype "token", zero bytes) and tuple-nested params
    (`(f32[2,4], s32[])` — dtype "tuple", dims (), nbytes summed over
    the element shapes)."""
    out = []
    for m in _PARAM_RE.finditer(_entry_text(hlo_text)):
        rest = m.group("rest")
        sh = _SHARDING_ATTR_RE.search(rest)
        nm = _OP_NAME_RE.search(rest)
        result = m.group("result").strip()
        am = _RESULT_SHAPE_RE.match(result)
        if am is not None:
            dtype = am.group("dtype")
            dims = tuple(
                int(d.strip().replace("<=", ""))
                for d in (am.group("dims") or "").split(",") if d.strip()
            )
            nbytes = _shape_bytes(dtype, am.group("dims") or "")
        else:  # tuple-nested: sum the element payloads
            dtype, dims = "tuple", ()
            nbytes = sum(
                _shape_bytes(s.group("dtype"), s.group("dims"))
                for s in _SHAPE_RE.finditer(result)
            )
        out.append({
            "index": int(m.group("idx")),
            "dtype": dtype,
            "dims": dims,
            "nbytes": nbytes,
            "sharding": sh.group("sharding") if sh else None,
            "op_name": (nm.group("name").replace("\\'", "'")
                        .replace('\\"', '"') if nm else None),
        })
    return out


# --- dtype-flow extraction (analysis/numerics.py consumer) -------------
#
# The numerics sanitizer (N001-N004) cross-checks accumulator/operand
# dtypes against the declared precision policy. Accumulation dtypes must
# be read from the PRE-OPTIMIZATION module (`lowered.compiler_ir('hlo')`)
# — backend legalization rewrites them (CPU upcasts bf16 compute to f32,
# so the optimized text no longer shows what the program declared).
# Collective payload dtypes come from the compiled text, where the SPMD
# partitioner has inserted them. Both forms parse here: compiled
# instructions carry inline operand shapes (`dot(f32[4,8] %x, ...)`),
# pre-opt instructions name bare operands (`dot(Arg_0.1, Arg_1.2)`) —
# resolved through a definition symbol table.

LOW_PRECISION_FLOATS = ("f16", "bf16", "f8e4m3fn", "f8e4m3", "f8e5m2")
FLOAT_DTYPES = ("f64", "f32") + LOW_PRECISION_FLOATS

_DTYPE_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<result>\((?:[^()]|\([^()]*\))*\)|" + _ARRAY + r")[^\s]*\s+"
    r"(?P<op>all-reduce-start|all-reduce|reduce-scatter-start|"
    r"reduce-scatter|all-to-all-start|all-to-all|"
    r"all-gather-start|all-gather|reduce-window|reduce|convert|dot)"
    r"\((?P<tail>[^\n]*)",
    re.M,
)
# every instruction definition (symbol table for operand resolution)
_ANY_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<result>\((?:[^()]|\([^()]*\))*\)|" + _ARRAY + r")",
    re.M,
)
_TO_APPLY_RE = re.compile(r"to_apply=%?(?P<region>[\w.\-]+)")
# reduce-combiner classification: the region's ROOT binary op decides
# whether the reduce ACCUMULATES (add/multiply — precision-sensitive) or
# selects (max/min/and/or — dtype-preserving, no accumulation error)
_REGION_ROOT_OPS = ("add", "multiply", "maximum", "minimum", "and", "or",
                    "xor")
_ACCUMULATING_KINDS = ("add", "multiply")


def _shape_list(result: str) -> List[Tuple[str, int]]:
    """[(dtype, elems)] for every array shape in a result string
    (scalars like `f32[]` -> 1 elem; `token[]`/`opaque[]` -> 0)."""
    out = []
    for s in _SHAPE_RE.finditer(result):
        n = 1
        for d in (s.group("dims") or "").split(","):
            d = d.strip().replace("<=", "")
            if d:
                n *= int(d)
        dt = s.group("dtype")
        out.append((dt, 0 if dt in ("token", "opaque") else n))
    return out


def _region_kinds(hlo_text: str) -> Dict[str, str]:
    """{region name: root binary op} for the reduce-combiner
    computations. Pre-opt headers are bare (`region_0.4 {`), compiled
    ones carry a signature (`%region_0.4 (x: f32[]) -> f32[] {`) —
    both are a name-led line ending in `{` with no `=`."""
    kinds: Dict[str, str] = {}
    for m in re.finditer(
            r"^\s*%?(?P<name>[\w.\-]+)[^={\n]*\{\s*$", hlo_text, re.M):
        body_at = m.end()
        end = hlo_text.find("\n}", body_at)
        body = hlo_text[body_at: end if end != -1 else body_at + 2000]
        root = re.search(
            r"ROOT[^\n=]*=[^\n]*?\b(" + "|".join(_REGION_ROOT_OPS) + r")\(",
            body)
        if root is not None:
            kinds[m.group("name")] = root.group(1)
    return kinds


def parse_hlo_dtype_ops(hlo_text: str) -> List[Dict]:
    """Dtype-flow records for every reduce/dot/convert/collective
    instruction in `hlo_text` (pre-opt or compiled form).

    Each record: {op, name, dtype (primary result dtype — first
    non-token shape), elems (summed over result shapes), operands
    ([(dtype|None, elems|None)], inline shapes or symbol-table
    resolved), reduce_kind ('add'/'maximum'/... for reduce ops whose
    combiner region resolves, else None)}. Tuple-typed reduce results,
    `convert` chains, and pred/token-typed operands are all well-formed
    records, never a crash — the numerics checks filter by dtype."""
    defs: Dict[str, Tuple[Optional[str], Optional[int]]] = {}
    for m in _ANY_DEF_RE.finditer(hlo_text):
        shapes = _shape_list(m.group("result"))
        if shapes:
            defs[m.group("name")] = (shapes[0][0],
                                     sum(n for _, n in shapes))
    regions = _region_kinds(hlo_text)
    out = []
    for m in _DTYPE_OP_RE.finditer(hlo_text):
        shapes = _shape_list(m.group("result"))
        if not shapes:
            continue
        primary = next((dt for dt, _ in shapes if dt not in
                        ("token", "opaque")), shapes[0][0])
        tail = m.group("tail")
        args = tail.split(")", 1)[0]
        operands: List[Tuple[Optional[str], Optional[int]]] = []
        inline = _shape_list(args)
        if inline:
            operands = [(dt, n) for dt, n in inline]
        else:
            for name in re.findall(r"%?([\w.\-]+)", args):
                if name in defs:
                    operands.append(defs[name])
        kind = None
        op = m.group("op").replace("-start", "")
        if op in ("reduce", "reduce-window", "all-reduce",
                  "reduce-scatter"):
            r = _TO_APPLY_RE.search(tail)
            if r is not None:
                kind = regions.get(r.group("region"))
        out.append({
            "op": op,
            "name": m.group("name"),
            "dtype": primary,
            "elems": sum(n for _, n in shapes),
            "operands": operands,
            "reduce_kind": kind,
        })
    return out


def preopt_hlo_text(lowered) -> Optional[str]:
    """Pre-optimization HLO of a lowered (not yet compiled) module, or
    None when the dialect is unavailable. This is where the program's
    DECLARED dtypes live — backend legalization (CPU bf16->f32 upcast)
    has not yet rewritten them."""
    try:
        return lowered.compiler_ir(dialect="hlo").as_hlo_text()
    except Exception:
        return None


def entry_parameter_shardings(compiled) -> Dict[str, Dict]:
    """op_name-keyed entry parameters of one compiled program (params
    without op_name metadata are keyed by their index)."""
    recs = parse_entry_parameters(compiled.as_text())
    return {
        (r["op_name"] if r["op_name"] is not None else f"#{r['index']}"): r
        for r in recs
    }


def compiled_memory_stats(compiled) -> Optional[Dict[str, int]]:
    """Byte totals from `compiled.memory_analysis()`, or None when the
    backend leaves it unimplemented (jaxlib raises, returns None, or the
    attribute is missing entirely on some CPU builds) — callers degrade
    to entry-parameter accounting instead of crashing."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None

    def get(name: str) -> int:
        try:
            return int(getattr(ma, name, 0) or 0)
        except (TypeError, ValueError):
            return 0

    return {
        "argument_bytes": get("argument_size_in_bytes"),
        "output_bytes": get("output_size_in_bytes"),
        "temp_bytes": get("temp_size_in_bytes"),
        "alias_bytes": get("alias_size_in_bytes"),
        "generated_code_bytes": get("generated_code_size_in_bytes"),
    }


def compiled_cost_stats(compiled) -> Optional[Dict[str, float]]:
    """{flops, bytes_accessed} from `compiled.cost_analysis()`, or None
    when unimplemented. Normalizes the jax-version drift: older releases
    return a one-element list of dicts, newer ones a plain dict."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    return {
        "flops": float(ca.get("flops", 0.0) or 0.0),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0) or 0.0),
    }


# --- computation/DAG extraction (analysis/schedule.py consumer) --------
#
# The schedule analyzer needs more than flat per-collective totals: it
# needs each computation's instruction SEQUENCE (post-scheduling HLO
# text order IS the schedule — compiled modules print
# `is_scheduled=true`), def-use edges to find a collective's first
# consumer, and async start/done pairing. Parsed per computation so
# collectives inside fusion bodies and while-loop bodies keep their own
# schedule context.

_GENERIC_INSTR_RE = re.compile(
    r"^\s+(?P<root>ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<result>\((?:[^()]|\([^()]*\))*\)"
    r"|[a-z][a-z0-9]*(?:\[[^\]]*\])?)"
    r"\S*\s+(?P<op>[\w\-]+)\((?P<tail>.*)$")


def _operand_region(tail: str) -> str:
    """The operand list of one instruction tail (text up to the paren
    that closes the call, balancing nested shape tuples)."""
    depth = 1
    for i, ch in enumerate(tail):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return tail[:i]
    return tail


def parse_hlo_computations(hlo_text: str,
                           ) -> Tuple[Dict[str, List[Dict]], Optional[str]]:
    """({computation name: [instruction records in schedule order]},
    entry computation name or None).

    Each record: {name, op, result (raw result string), nbytes (summed
    over result shapes), operands ([referenced %names]), attrs (text
    after the operand list — replica_groups etc. live here), called
    ([computation names via calls=/to_apply=/body=/condition=]),
    root (bool)}."""
    comps: Dict[str, List[Dict]] = {}
    entry: Optional[str] = None
    cur: Optional[List[Dict]] = None

    def _operand_names(region: str) -> List[str]:
        # compiled text prefixes operands with % ; the pre-opt dialect
        # (`lowered.compiler_ir('hlo').as_hlo_text()`) prints bare names
        names = re.findall(r"%([\w.\-]+)", region)
        if names or "%" in region:
            return names
        inner = region.strip()
        if inner.startswith("("):
            inner = inner[1:-1] if inner.endswith(")") else inner[1:]
        out: List[str] = []
        for part in inner.split(","):
            toks = part.split()
            if toks and "[" not in toks[-1] and "]" not in toks[-1]:
                out.append(toks[-1])
        return out

    for line in hlo_text.splitlines():
        stripped = line.strip()
        if cur is None:
            if (stripped.endswith("{") and " = " not in line
                    and not stripped.startswith("HloModule")):
                head = stripped[:-1].strip()
                is_entry = head.startswith("ENTRY")
                if is_entry:
                    head = head[len("ENTRY"):].strip()
                name = head.split("(")[0].split()[0].lstrip("%") if head \
                    else ""
                if name:
                    cur = comps.setdefault(name, [])
                    if is_entry:
                        entry = name
            continue
        if stripped.startswith("}"):
            cur = None
            continue
        m = _GENERIC_INSTR_RE.match(line)
        if m is None:
            continue
        tail = m.group("tail")
        region = _operand_region(tail)
        attrs = tail[len(region):]
        nbytes = sum(
            _shape_bytes(s.group("dtype"), s.group("dims") or "")
            for s in _SHAPE_RE.finditer(m.group("result")))
        cur.append({
            "name": m.group("name"),
            "op": m.group("op"),
            "result": m.group("result"),
            "nbytes": nbytes,
            "operands": _operand_names(region),
            "attrs": attrs,
            "called": re.findall(
                r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", attrs),
            "root": m.group("root") is not None,
        })
    return comps, entry


def _volumes(records) -> Dict[str, Dict[str, float]]:
    agg: Dict[str, Dict[str, float]] = defaultdict(lambda: {"count": 0, "bytes": 0})
    for rec in records:
        agg[rec["op"]]["count"] += 1
        agg[rec["op"]]["bytes"] += rec["bytes"]
    return dict(agg)


def collective_volumes(compiled) -> Dict[str, Dict[str, float]]:
    """Per-collective-kind totals for one compiled step.

    Returns {op: {count, bytes}} — e.g. how many bytes of all-gather one
    train step moves (the reference's comms summary table, per op kind,
    ref: comms_logging.py log_summary)."""
    return _volumes(parse_hlo_collectives(compiled.as_text()))


# instructions whose called computations run as events of their own on
# the device (a loop's body, a branch): everything else that calls a
# computation (a fusion, an async wrapper, a reduction) runs as ONE
# event under the caller's name
_CONTROL_FLOW_OPS = ("while", "conditional", "call")
MANIFEST_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                  "collective-permute", "all-to-all")


def collective_manifest(hlo_text: str) -> Dict:
    """What one compiled step moves between devices, by SITE.

    {"kinds": {op: {count, bytes}} (collective_volumes' table: static
    sites, one execution each), "in_fusion": {count, bytes} (the
    collectives whose site is not themselves), "sites": [(name, op,
    bytes)], "async": {op: count} (the collectives the compiler left
    asynchronous: a `-start` / `-done` pair, or an
    `async-collective-start` / `-done` chain of fusions, booked once to
    its start; a kind with none is absent)}. A site is the top-level
    instruction of the entry computation or of a loop body or branch
    that IS a collective or CONTAINS one: a collective in a fused computation is booked to the
    `fusion.<n>` that calls it, which is the name its time carries in
    a device trace (a trace's own names tell only the collectives that
    stand alone). Several collectives under one site and kind add up."""
    records = parse_hlo_collectives(hlo_text)
    comps, _ = parse_hlo_computations(hlo_text)
    home = {ins["name"]: c for c, body in comps.items() for ins in body}
    caller = {}
    for c, body in comps.items():
        for ins in body:
            for callee in ins["called"]:
                caller.setdefault(callee, (ins, c))

    def site_of(name: str) -> str:
        comp = home.get(name)
        while comp in caller:
            ins, outer = caller[comp]
            if ins["op"] in _CONTROL_FLOW_OPS:
                break
            name, comp = ins["name"], outer
        return name

    sites: Dict[Tuple[str, str], int] = {}
    in_fusion = {"count": 0, "bytes": 0}
    n_async: Dict[str, int] = {}
    for rec in records:
        site = site_of(rec["name"])
        if site != rec["name"]:
            in_fusion["count"] += 1
            in_fusion["bytes"] += rec["bytes"]
        if rec["async"]:
            n_async[rec["op"]] = n_async.get(rec["op"], 0) + 1
        key = (site, rec["op"])
        sites[key] = sites.get(key, 0) + rec["bytes"]
    return {"kinds": _volumes(records), "in_fusion": in_fusion,
            "sites": [(name, op, nbytes)
                      for (name, op), nbytes in sites.items()],
            "async": n_async}


def manifest_ids(manifest: Dict) -> Dict[str, object]:
    """The manifest as span ids (`train.compile.collectives`,
    docs/tracing.md): `<kind>_n` / `<kind>_bytes` / `<kind>_async_n`
    per kind with `-` as `_`, `in_fusion_n` / `in_fusion_bytes`, and
    `sites`, one compact string of `name:kind:bytes` joined by commas."""
    ids: Dict[str, object] = {}
    for kind in MANIFEST_KINDS:
        v = manifest["kinds"].get(kind, {"count": 0, "bytes": 0})
        key = kind.replace("-", "_")
        ids[f"{key}_n"] = int(v["count"])
        ids[f"{key}_bytes"] = int(v["bytes"])
        ids[f"{key}_async_n"] = int(manifest["async"].get(kind, 0))
    ids["in_fusion_n"] = int(manifest["in_fusion"]["count"])
    ids["in_fusion_bytes"] = int(manifest["in_fusion"]["bytes"])
    ids["sites"] = ",".join(f"{n}:{k}:{b}" for n, k, b in manifest["sites"])
    return ids


# --- rng extraction (analysis/determinism.py consumer) -----------------
#
# The determinism analyzer's D001 needs every PRNG op in a program plus
# the sharding story around it: a threefry draw whose RESULT is laid out
# across a mesh axis computes DIFFERENT BITS per layout (threefry is not
# partitionable — the PR-14 EP=1 != EP=N router-noise bug), so the only
# layout-independent forms are a replicated pin on the draw or no mesh
# sharding at all. PRNG appears in four textual forms depending on
# backend/jax version: `rng-bit-generator` ops, legacy `rng` ops,
# custom-calls with a threefry target (GPU/TPU lowerings), and — the
# pre-opt CPU form this tree compiles — `call(...)` into named rng
# computations (`_uniform.103`, `_threefry_fold_in.256`). Shardings ride
# either the instruction itself or a `Sharding` custom-call consumer;
# shard_map bodies show up as `xla.sdy.manual_computation_body*`
# computations.

# rng computation names jax stamps on the lowered helpers, leading
# underscore stripped and trailing `.N` suffix removed. split/fold_in/
# seed DERIVE keys (layout-safe by themselves); the rest DRAW bits.
_RNG_KEY_DERIVE_BASES = (
    "split", "fold_in", "seed", "threefry_split", "threefry_fold_in",
    "threefry_seed", "random_wrap", "random_unwrap",
)
_RNG_DRAW_BASES = (
    "uniform", "normal", "normal_real", "truncated_normal",
    "random_bits", "threefry_random_bits", "random_seed", "gamma",
    "beta", "poisson", "categorical", "bernoulli", "gumbel", "randint",
    "choice", "exponential", "laplace", "rbg",
)
_CUSTOM_CALL_TARGET_RE = re.compile(r'custom_call_target="(?P<t>[^"]*)"')
_GTE_INDEX_RE = re.compile(r"index=(?P<idx>\d+)")
# ops a seed value flows through unchanged (provenance walk)
_RNG_PASSTHROUGH_OPS = (
    "reshape", "convert", "bitcast", "bitcast-convert", "copy",
    "transpose", "broadcast", "slice", "concatenate",
)


def _rng_comp_base(comp_name: str) -> Optional[str]:
    """'threefry_fold_in' for `_threefry_fold_in.256`, None when the
    computation is not one of jax's lowered rng helpers."""
    base = re.sub(r"\.\d+$", "", comp_name).lstrip("_")
    if base in _RNG_KEY_DERIVE_BASES or base in _RNG_DRAW_BASES:
        return base
    return None


def classify_sharding(sharding: Optional[str]) -> str:
    """'replicated' | 'manual' | 'maximal' | 'tiled' | 'none' for one
    raw `sharding={...}` annotation body.

    `last_tile_dim_replicate` tiles whose non-replicated dims are all 1
    (e.g. `devices=[1,1,4]<=[4] last_tile_dim_replicate`) are
    effectively replicated and classify as such — the partitioner
    spells "replicated over this mesh" both ways."""
    if sharding is None:
        return "none"
    if "manual" in sharding:
        return "manual"
    if "maximal" in sharding:
        return "maximal"
    m = re.search(r"devices=\[(?P<dims>[\d,]+)\]", sharding)
    if m is not None:
        dims = [int(d) for d in m.group("dims").split(",")]
        if "last_tile_dim_replicate" in sharding:
            dims = dims[:-1]
        return "replicated" if all(d == 1 for d in dims) else "tiled"
    if "replicated" in sharding:
        return "replicated"
    return "tiled"


def _manual_computations(comps: Dict[str, List[Dict]]) -> set:
    """Names of computations that execute inside a shard_map manual
    context: the `xla.sdy.manual_computation_body*` computations a
    shard_map lowers to, closed transitively over the call graph."""
    manual = {name for name in comps
              if name.startswith("xla.sdy.manual_computation_body")}
    # a call inside a manual computation is manual too
    changed = True
    while changed:
        changed = False
        for name in list(manual):
            for ins in comps.get(name, ()):
                for callee in ins["called"]:
                    if callee not in manual:
                        manual.add(callee)
                        changed = True
    return manual


def _resolve_seed(start: str, defs: Dict[str, Dict],
                  depth: int = 32) -> Tuple[str, Optional[str]]:
    """(root def name, sharding annotation) reached by walking one
    operand back through tuple packaging (`tuple` /
    `get-tuple-element` with matched indices), value-preserving unary
    ops, and `Sharding` custom-calls — the seed-provenance input D001
    classifies. Stops at parameters, annotated defs, or anything that
    computes."""
    name, sharding = start, None
    seen = set()
    while depth > 0 and name in defs and name not in seen:
        seen.add(name)
        depth -= 1
        rec = defs[name]
        sh = _SHARDING_ATTR_RE.search(rec["attrs"])
        if sh is not None and sharding is None:
            sharding = sh.group("sharding")
        op = rec["op"]
        if op == "get-tuple-element" and rec["operands"]:
            src = defs.get(rec["operands"][0])
            gm = _GTE_INDEX_RE.search(rec["attrs"])
            if (src is not None and src["op"] == "tuple"
                    and gm is not None
                    and int(gm.group("idx")) < len(src["operands"])):
                name = src["operands"][int(gm.group("idx"))]
                continue
            name = rec["operands"][0]
            continue
        if op == "custom-call" and "Sharding" in rec["attrs"] \
                and rec["operands"]:
            name = rec["operands"][0]
            continue
        if op in _RNG_PASSTHROUGH_OPS and rec["operands"]:
            name = rec["operands"][0]
            continue
        break
    return name, sharding


def parse_hlo_rng_ops(hlo_text: str) -> List[Dict]:
    """Every PRNG instruction in `hlo_text` (pre-opt or compiled form)
    with its sharding/provenance story.

    Each record: {name, computation, form ('rng-bit-generator' | 'rng'
    | 'custom-call' | 'call'), algo (rng helper base name or custom-
    call target), kind ('draw' | 'key-derive'), dtype, sharding (own
    annotation, else the first `Sharding` custom-call consumer's —
    None when unannotated), sharding_class (classify_sharding of
    that), manual (True inside a shard_map manual context), seed
    (root def name of the first operand, tuple packaging resolved),
    seed_sharding, seed_sharding_class}."""
    comps, _ = parse_hlo_computations(hlo_text)
    manual = _manual_computations(comps)
    out: List[Dict] = []
    for comp_name, instrs in comps.items():
        defs = {i["name"]: i for i in instrs}
        # result name -> sharding constraint applied by a consumer
        pins: Dict[str, str] = {}
        for ins in instrs:
            if ins["op"] == "custom-call" and "Sharding" in ins["attrs"] \
                    and "SPMD" not in ins["attrs"] and ins["operands"]:
                sh = _SHARDING_ATTR_RE.search(ins["attrs"])
                if sh is not None:
                    pins.setdefault(ins["operands"][0],
                                    sh.group("sharding"))
        for ins in instrs:
            algo = None
            form = None
            if ins["op"] in ("rng-bit-generator", "rng"):
                form = ins["op"]
                am = re.search(r"algorithm=(\w+)", ins["attrs"])
                algo = am.group(1) if am else ins["op"]
                kind = "draw"
            elif ins["op"] == "custom-call":
                tm = _CUSTOM_CALL_TARGET_RE.search(ins["attrs"])
                if tm is None or "threefry" not in tm.group("t").lower():
                    continue
                form, algo, kind = "custom-call", tm.group("t"), "draw"
            elif ins["called"]:
                bases = [(_rng_comp_base(c), c) for c in ins["called"]]
                hit = next((b for b, _ in bases if b is not None), None)
                if hit is None:
                    continue
                form, algo = "call", hit
                kind = ("key-derive" if hit in _RNG_KEY_DERIVE_BASES
                        else "draw")
            else:
                continue
            own = _SHARDING_ATTR_RE.search(ins["attrs"])
            sharding = own.group("sharding") if own else \
                pins.get(ins["name"])
            sm = _SHAPE_RE.search(ins["result"])
            seed, seed_sh = (_resolve_seed(ins["operands"][0], defs)
                             if ins["operands"] else (None, None))
            out.append({
                "name": ins["name"],
                "computation": comp_name,
                "form": form,
                "algo": algo,
                "kind": kind,
                "dtype": sm.group("dtype") if sm else None,
                "sharding": sharding,
                "sharding_class": classify_sharding(sharding),
                "manual": comp_name in manual,
                "seed": seed,
                "seed_sharding": seed_sh,
                "seed_sharding_class": classify_sharding(seed_sh),
            })
    return out


def parse_hlo_reduce_collectives(hlo_text: str) -> List[Dict]:
    """Every all-reduce / reduce-scatter in `hlo_text` with its
    combiner kind, payload dtype, and FULL replica-group member lists
    — the reassociation-hazard input (D002): a floating-point `add`
    whose groups span a mesh axis the bitwise-pin registry declares
    layout-varying sums its partials in a layout-dependent order."""
    kinds = _region_kinds(hlo_text)
    out = []
    for m in _DTYPE_OP_RE.finditer(hlo_text):
        op = m.group("op").replace("-start", "")
        if op not in ("all-reduce", "reduce-scatter"):
            continue
        shapes = _shape_list(m.group("result"))
        primary = next((dt for dt, _ in shapes if dt not in
                        ("token", "opaque")), None)
        tail = m.group("tail")
        r = _TO_APPLY_RE.search(tail)
        out.append({
            "op": op,
            "name": m.group("name"),
            "dtype": primary,
            "groups": parse_replica_groups(tail),
            "group_size": _group_size(tail),
            "reduce_kind": kinds.get(r.group("region")) if r else None,
        })
    return out
