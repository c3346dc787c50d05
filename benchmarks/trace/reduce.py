"""From a profiler trace to numbers: the reduction every PR shares.

`load_xplane` reads the `.xplane.pb` the JAX profiler writes
(`jax.profiler.ProfileData`, nothing but JAX) into plain event lists;
everything after that is arithmetic on (name, start, duration) and is
checked in benchmarks/tests against `recorded/*.json` (events kept
from a chip run) and hand-made cases.

What a v5e trace holds (looked at by hand, PR 22; see PERF.md §3):
planes `/device:TPU:<n>` are the chips (beside `#Chip<n> ...`,
`/host:metadata`, `/device:CUSTOM:Megascale Trace`, `Task
Environment`, all empty or irrelevant). On a chip, line `XLA Modules`
has one event per executed program (`jit_<fn>(<fingerprint>)`); line
`XLA Ops` one event per HLO instruction executed, whose name is the
instruction's WHOLE text (`%flash_fwd.6 = bf16[...] custom-call(...)`),
so the Pallas kernels show under their `name=` plus XLA's numbering
(`flash_fwd.6`, `flash_bwd_dq.10`, `paged_decode_grid.16`), and control
flow (`while.124`) as events that CONTAIN their bodies' events; line
`Async XLA Ops` holds the spans of asynchronous instructions from
`-start` to `-done` (copies, slices, collectives); `Steps` numbers the
executions. The host plane `/host:CPU` holds one line per thread; the
benchmark's `jax.profiler.TraceAnnotation` spans (`bench.*`) are events
on the `python3` line, on the same clock as the device lines.

Where an instruction's named scope lives (looked at by hand, PR 26): an
event carries only its device offset and duration; its METADATA (one
entry per distinct instruction of a plane) carries the stats
`hlo_category`, `flops`, `bytes_accessed`, `source` and `tf_op`, the
last being the HLO `op_name`: the `jax.named_scope` path,
`jit(step_fn)/while/body/closed_call/jvp()/while/body/closed_call/mlp/
bsf,fe->bse/dot_general:`, with the HLO proto off. 522 of the 711
instructions of a train step have one; copies and other instructions
XLA inserts have none. `jax.profiler.ProfileData` does not hand
metadata stats out, so `metadata_scopes` reads them from the file's
protobuf wire format with nothing but the standard library.
"""

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]  # [start, end) in seconds

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.trace_window"
COLLECTIVE_PREFIXES = ("all-gather", "reduce-scatter", "all-reduce",
                       "collective-permute", "all-to-all")


@dataclasses.dataclass
class Event:
    name: str
    start: float  # seconds
    dur: float    # seconds
    scope: str = ""  # the instruction's `op_name`: its named-scope path

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class TraceData:
    ops: Dict[int, List[Event]]       # device index -> XLA Ops, by start
    modules: Dict[int, List[Event]]   # device index -> XLA Modules
    spans: List[Event]                # host bench.* annotations
    window: Interval                  # the traced window (bench.trace_window)
    # device index -> Async XLA Ops (start-to-done spans)
    async_ops: Dict[int, List[Event]] = dataclasses.field(default_factory=dict)
    # instruction name -> what it computes (output types and op kind)
    details: Dict[str, str] = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        """Seconds an operation ran, averaged over the chips traced."""
        per_dev = [union_s(clip(intervals(evs), self.window))
                   for evs in self.ops.values()]
        return sum(per_dev) / len(per_dev)


def base_name(name: str) -> str:
    """`%flash_fwd.12` -> `flash_fwd`: the instruction's name without
    the sigil and the numbering XLA appends."""
    name = name.lstrip("%")
    return re.sub(r"(\.\d+)+$", "", name)


def split_instruction(text: str) -> Tuple[str, str]:
    """`%fusion.3 = f32[8,128]{1,0:T(8,128)} fusion(...)` ->
    (`fusion.3`, `f32[8,128] fusion(...`): the instruction's name, and
    its result types and kind without the layout annotations."""
    name, _, rhs = text.partition(" = ")
    rhs = re.sub(r"\{[^{}]*\}", "", rhs)
    return name.lstrip("%"), rhs[:72]


def _varint(buf, i):
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, value) pairs of one protobuf message: an int for
    a varint, a memoryview for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        else:
            if kind == 2:
                size, i = _varint(buf, i)
            elif kind in (1, 5):
                size = 8 if kind == 1 else 4
            else:
                raise ValueError(f"protobuf wire type {kind} in an xplane file")
            value = buf[i:i + size]
            i += size
        yield key >> 3, value


def metadata_scopes(path: str, stat: str = "tf_op") -> Dict[str, Dict[str, str]]:
    """plane name -> {event name -> the `tf_op` stat of its metadata}.
    The schema (tsl/profiler/protobuf/xplane.proto): XSpace.planes = 1;
    XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5 (maps:
    key 1, value 2); XEventMetadata.name = 2, .stats = 5;
    XStatMetadata.name = 2; XStat.metadata_id = 1, .str_value = 5,
    .ref_value = 7 (the text is then the NAME of that stat metadata)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for num, value in _fields(plane):
            if num == 2:
                name = bytes(value).decode()
            elif num in (4, 5):
                entry = dict(_fields(value))
                msg = list(_fields(entry[2]))
                text = next((bytes(v).decode(errors="replace")
                             for n, v in msg if n == 2), "")
                if num == 5:
                    stat_names[entry[1]] = text
                else:
                    events.append((text, [dict(_fields(v)) for n, v in msg if n == 5]))
        want = {i for i, n in stat_names.items() if n == stat}
        scopes = {}
        for text, stats in events:
            for st in stats:
                if st.get(1) in want:
                    scopes[text] = (bytes(st[5]).decode(errors="replace") if 5 in st
                                    else stat_names.get(st.get(7), ""))
        if scopes:
            out[name] = scopes
    return out


def load_xplane(path: str) -> TraceData:
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    scopes = metadata_scopes(path)
    lines: Dict[str, Dict[int, List[Event]]] = {
        OPS_LINE: {}, ASYNC_LINE: {}, MODULES_LINE: {}}
    details: Dict[str, str] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in lines:
                evs = []
                scope_of = scopes.get(plane.name, {})
                for e in line.events:
                    name, what = split_instruction(e.name)
                    if what:
                        details.setdefault(name, what)
                    evs.append(Event(name, e.start_ns * 1e-9,
                                     e.duration_ns * 1e-9,
                                     scope_of.get(e.name, "")))
                lines[line.name][int(m.group(1))] = sorted(
                    evs, key=lambda e: e.start)
            elif plane.name.startswith("/host:"):
                spans.extend(Event(e.name, e.start_ns * 1e-9,
                                   e.duration_ns * 1e-9)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    td = from_events(lines[OPS_LINE], lines[MODULES_LINE], spans)
    td.async_ops, td.details = lines[ASYNC_LINE], details
    return td


def from_events(ops, modules, spans) -> TraceData:
    spans = sorted(spans, key=lambda e: e.start)
    win = [s for s in spans if s.name == WINDOW_SPAN]
    if win:
        window = (win[0].start, win[-1].end)
    else:  # no marker: from the first device event to the last
        all_evs = [e for evs in ops.values() for e in evs]
        if not all_evs:
            raise ValueError("the trace holds no device operation")
        window = (min(e.start for e in all_evs), max(e.end for e in all_evs))
    return TraceData(ops, modules, spans, window)


# -- interval arithmetic ----------------------------------------------------

def intervals(events: Iterable[Event]) -> List[Interval]:
    return [(e.start, e.end) for e in events]


def clip(ivs: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in ivs if b > lo and a < hi]


def merge(ivs: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(ivs):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def union_s(ivs: Iterable[Interval]) -> float:
    return sum(b - a for a, b in merge(ivs))


def subtract(ivs: Iterable[Interval], cover: Iterable[Interval]) -> List[Interval]:
    """The parts of `ivs` (merged) that `cover` (merged) leaves bare."""
    cover = merge(cover)
    out = []
    for a, b in merge(ivs):
        cur = a
        for c, d in cover:
            if d <= cur:
                continue
            if c >= b:
                break
            if c > cur:
                out.append((cur, c))
            cur = max(cur, d)
            if cur >= b:
                break
        if cur < b:
            out.append((cur, b))
    return out


# -- device operations -----------------------------------------------------

CONTAINER_PREFIXES = ("while", "conditional", "call")


def leaves(events: Sequence[Event]) -> List[Event]:
    """Events that are work: control-flow instructions (`while`,
    `conditional`, `call`) span their bodies' events, and their bodies
    are the work. Told by name, not by nesting: an asynchronous
    collective legitimately overlaps the compute it hides behind."""
    return [e for e in events
            if not base_name(e.name).startswith(CONTAINER_PREFIXES)]


def in_window(events: Sequence[Event], window: Interval) -> List[Event]:
    return [e for e in events if e.end > window[0] and e.start < window[1]]


def kernel_seconds(td: TraceData, names: Sequence[str], device: int = 0) -> Optional[float]:
    """Sum of device durations of the events that are one of the
    kernels `names`, inside the traced window. None when none ran."""
    evs = [e for e in in_window(td.ops.get(device, []), td.window)
           if is_kernel(e.name, names)]
    return sum(e.dur for e in evs) if evs else None


_WRAPPERS = re.compile(r"^(?:\w+\()+|\)+$")
# path components that say how the program is built, not what it computes
_STRUCTURAL = {"", "while", "body", "cond", "closed_call", "checkpoint",
               "rematted_computation", "branch", "remat", "custom_jvp_call",
               "custom_vjp_call"}


def scope_components(path: str) -> List[str]:
    """`jit(f)/while/body/transpose(jvp(lm_head))/dot_general:` ->
    [`f`, `while`, `body`, `lm_head`, `dot_general`]: autodiff wraps
    the scope it was entered under (`jvp(mlp)`, `transpose(jvp(mlp))`)
    or nothing (`jvp()`); the wrappers go, the name stays."""
    return [_WRAPPERS.sub("", c) for c in path.rstrip(":").split("/")]


def scope_of(path: str, names: Sequence[str]) -> Optional[str]:
    """The first (outermost) component of the path that is one of
    `names`: a scope entered inside another counts for the outer one."""
    return next((c for c in scope_components(path) if c in names), None)


def short_scope(path: str) -> str:
    """The outermost scope and the operation, `lm_head/reduce_sum`: the
    path without its wrappers, the enclosing `jit(...)`s, the control
    flow and what lies between the two."""
    kept = [c for raw, c in zip(path.rstrip(":").split("/"), scope_components(path))
            if c not in _STRUCTURAL and not raw.startswith(("jit(", "pjit("))]
    return "/".join(kept if len(kept) < 3 else (kept[0], kept[-1]))


def scope_events(td: TraceData, names: Sequence[str], device: int = 0) -> List[Event]:
    """The work events (leaves only: a `while` CONTAINS its body) inside
    the traced window whose scope path holds one of `names`. An event
    is one execution of one instruction, so each is counted once. A
    fusion carries the scope of its root instruction: what XLA fused
    across a scope's boundary is booked to one side."""
    return [e for e in leaves(in_window(td.ops.get(device, []), td.window))
            if e.scope and scope_of(e.scope, names) is not None]


def scope_seconds(td: TraceData, names: Sequence[str], device: int = 0) -> Optional[float]:
    """Sum of device durations of the events inside the named scopes
    `names`, inside the traced window. None when none ran (a trace
    without scopes, a program without these names)."""
    evs = scope_events(td, names, device)
    return sum(e.dur for e in evs) if evs else None


def scope_ms_per_step(obs, names: Sequence[str]) -> Optional[float]:
    """What a per-layer reader of one scope returns: milliseconds per
    traced step on device 0, or None without a trace or the scope."""
    td = obs.get("trace")
    s = scope_seconds(td, names) if td is not None else None
    return None if s is None else 1e3 * s / obs["traced_steps"]


def is_kernel(event_name: str, names: Sequence[str]) -> bool:
    """A Pallas kernel's event carries the kernel's `name=` inside the
    instruction name; autodiff wraps it (`jvp_flash_fwd_.1`,
    `transpose_jvp_flash_bwd_dq__.1`), so match by substring. No kernel
    name of this repo is a substring of another."""
    return any(k in event_name for k in names)


def is_collective(name: str) -> bool:
    return base_name(name).startswith(COLLECTIVE_PREFIXES)


def collective_seconds(td: TraceData, device: int = 0) -> Tuple[float, float]:
    """(total, exposed): the union of the collective operations'
    intervals on the device — synchronous ones on `XLA Ops`, and the
    start-to-done spans of asynchronous ones on `Async XLA Ops` — and
    the part of it in which no other operation runs there (the
    `-start` / `-done` instructions themselves are not other work)."""
    evs = leaves(in_window(td.ops.get(device, []), td.window))
    spans = in_window(td.async_ops.get(device, []), td.window)
    coll = clip(intervals(e for e in list(evs) + list(spans)
                          if is_collective(e.name)), td.window)
    other = intervals(e for e in evs if not is_collective(e.name))
    return union_s(coll), union_s(subtract(coll, other))


def modules_with(td: TraceData, kernel: str, device: int = 0) -> List[Event]:
    """Executed programs (XLA Modules events, inside the window) that
    ran the kernel `kernel`."""
    ks = [e.start for e in in_window(td.ops.get(device, []), td.window)
          if kernel in e.name]
    out = []
    for m in in_window(td.modules.get(device, []), td.window):
        if any(m.start <= s < m.end for s in ks):
            out.append(m)
    return out


def median(xs: Sequence[float]) -> Optional[float]:
    xs = sorted(xs)
    if not xs:
        return None
    n = len(xs)
    return xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2])


# -- idle time, named by what the host was doing ---------------------------

def idle_share(td: TraceData, device: int = 0) -> float:
    """Share of the window in which no operation ran on the device."""
    busy = union_s(clip(intervals(td.ops.get(device, [])), td.window))
    return 1.0 - busy / td.window_s


def idle_gaps(td: TraceData, device: int = 0) -> List[Tuple[str, float]]:
    """Every idle interval of the device inside the window, longest
    first, named by the benchmark span that covers its midpoint (the
    shortest such span: the most specific)."""
    busy = merge(clip(intervals(td.ops.get(device, [])), td.window))
    gaps = subtract([td.window], busy)
    named = []
    for a, b in gaps:
        mid = 0.5 * (a + b)
        cover = [s for s in td.spans
                 if s.start <= mid < s.end and s.name != WINDOW_SPAN]
        name = min(cover, key=lambda s: s.dur).name if cover else "no_span"
        named.append((name, b - a))
    return sorted(named, key=lambda g: -g[1])


def breakdown(td: TraceData, device: int = 0) -> Dict[str, list]:
    """The contract's `breakdown`: the ten device operations with most
    time, work events summed per instruction, and the longest idle gaps
    by covering span. A name reads `<n>x <instruction> <scope> <what it
    computes>`: n is how often the instruction ran in the window (three
    PRs read 16 executions as one slow one), the scope is its named
    scope where the trace has one, and `fusion.335` alone says little."""
    total: Dict[str, float] = {}
    count: Dict[str, int] = {}
    scope: Dict[str, str] = {}
    for e in leaves(in_window(td.ops.get(device, []), td.window)):
        total[e.name] = total.get(e.name, 0.0) + e.dur
        count[e.name] = count.get(e.name, 0) + 1
        if e.scope:
            scope.setdefault(e.name, short_scope(e.scope))
    top = sorted(total.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[" ".join(filter(None, (
        f"{count[k]}x", k, scope.get(k), td.details.get(k)))), v]
        for k, v in top],
        "idle_gaps": [[k, v] for k, v in idle_gaps(td, device)[:5]]}
