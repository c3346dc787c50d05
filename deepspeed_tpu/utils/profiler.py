"""The program's one tracing facility: spans, phase clocks, and
jax.profiler capture (docs/tracing.md).

The tracing half of the reference's observability stack
(ref: deepspeed/utils/nvtx.py instrument_w_nvtx + accelerator
range_push/pop abstract_accelerator.py:189-193; SURVEY §5 'TPU
equivalent: jax.profiler traces (xplane→tensorboard)'). Traces are
XPlane protobufs viewable in TensorBoard's profile plugin or Perfetto.

A span is a named interval at a layer boundary. When tracing is
active it is written twice: as a `jax.profiler.TraceAnnotation`
("ds." + name, ids as event stats), so it sits in any profiler trace
on the device lines' clock, and as a `SpanRecord` stamped with
`time.perf_counter_ns` in a bounded process-global buffer that
`spans()` returns and `dump()` writes as Chrome-trace JSON. Active
means `enable()` was called or a profiler session is open; inactive,
`span()` is one flag read. `always=True` spans (set-up phases and rare
events: dozens per process) record to the buffer whatever the state,
in a buffer of their own so per-iteration spans never evict them.

`Phases` also holds the one stall rule of the loops that use it (an
iteration that takes three times what its loop typically takes), and
the evidence a kept span of such an iteration carries: the collector's
time on the spans' clock (one `gc.callbacks` hook, process-wide).
"""

import contextlib
import gc
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, Iterable, Iterator, List, NamedTuple, Optional, Sequence, Tuple

import jax

from .logging import logger

PREFIX = "ds."
HOT_SPANS = 65536    # per-iteration spans kept (minutes of serving)
KEPT_SPANS = 4096    # always=True spans kept
STAGE_GAP_NS = 1_000_000

# The stall rule of `Phases.end()` (docs/tracing.md "The serving loop").
# An iteration is a stall when it takes over STALL_FACTOR x what its
# loop typically takes (PERF.md section 7 asked for this mark: the
# iterations of a loop whose program IS the iteration lie within a few
# percent of each other, and the pauses looked for are of 4 x and up) ...
STALL_FACTOR = 3
# ... AND exceeds it by more than this: a loop of microseconds (the
# CPU lane, an idle pass) trebles at every hiccup of the OS, and a
# pause under a few milliseconds is under a third of the shortest
# serving program (13.8 ms). Also what a full collection has to take
# to leave a `host.gc` span.
STALL_FLOOR_NS = 5_000_000
# iterations a loop's pace is learned from before any is judged: their
# mean less the largest of them (a loop's first pass compiles, or
# faults its pages in); after them the pace follows at 1 / 2**STALL_EMA_SHIFT
# an iteration (16 iterations: a third of a second of serving)
STALL_MIN_SEEN = 8
STALL_EMA_SHIFT = 4
# this many stalls in a row are no exception but the loop's new pace
# (longer prompts, a wider batch): it is learned anew from there
STALL_RESEED = 8
# a loop logs a line for each of its first stalls, then their count at
# every power of two: a loop that stalls for good must not fill a log
STALL_LOG_LINES = 8

# The device scopes of the compiled train step (`jax.named_scope`, so
# metadata alone: docs/tracing.md "Device scopes of the train step").
# With the model's own (MODEL_SCOPES, models/transformer.py: the six
# the benchmark has read since PR 26 and `norm_f`, the final norm) they
# name every instruction the program itself wrote; a reader takes the
# OUTERMOST of the names it asks for.
(PARAM_CAST, GRAD_REDUCE, GRAD_CLIP, OPTIMIZER, ZERO_GATHER,
 LAYER_STACK, EXPERT_BIAS_UPDATE) = TRAIN_STEP_SCOPES = (
    "param_cast", "grad_reduce", "grad_clip", "optimizer", "zero_gather",
    "layer_stack",
    # a routed model's step state, written after the optimizer's update
    # (models/transformer.py step_state_rule)
    "expert_bias_update")
MODEL_SCOPES = ("embed", "norm1", "attention", "norm2", "mlp", "norm_f",
                "lm_head")

_is_profiling = jax.profiler.TraceAnnotation.is_enabled

# what jax.monitoring calls the three host stages of building a program
COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}


class SpanRecord(NamedTuple):
    name: str
    t0_ns: int            # time.perf_counter_ns at entry
    t1_ns: int
    sid: int
    parent: int           # 0 = root
    ids: Dict[str, Any]

    @property
    def dur_s(self) -> float:
        return (self.t1_ns - self.t0_ns) * 1e-9


_lock = threading.Lock()
_enabled = False
_hot: "deque[SpanRecord]" = deque(maxlen=HOT_SPANS)
_kept: "deque[SpanRecord]" = deque(maxlen=KEPT_SPANS)
_next_sid = itertools.count(1)
_tls = threading.local()  # .stack: open span ids of this thread


def enable() -> None:
    """Record spans from now on, profiler session or not."""
    global _enabled
    with _lock:
        _enabled = True


def disable() -> None:
    global _enabled
    with _lock:
        _enabled = False


def active() -> bool:
    return _enabled or _is_profiling()


def _stack() -> List[int]:
    try:
        return _tls.stack
    except AttributeError:
        _tls.stack = []
        return _tls.stack


def _append(rec: SpanRecord, always: bool) -> None:
    with _lock:
        (_kept if always else _hot).append(rec)


def record(name: str, t0_ns: int, t1_ns: int, parent: Optional[int] = None,
           always: bool = False, **ids) -> int:
    """A span after the fact, from stamps the caller already took
    (buffer only: an annotation cannot be backdated). parent=None
    hangs it under this thread's innermost open span; always=True
    reads the device memory as `span` does. Returns its id (0 when
    nothing was recorded)."""
    if not (always or active()):
        return 0
    if parent is None:
        st = _stack()
        parent = st[-1] if st else 0
    if always:
        ids.update(memory_ids())
    sid = next(_next_sid)
    _append(SpanRecord(name, int(t0_ns), int(t1_ns), sid, parent, ids), always)
    return sid


def memory_ids() -> Dict[str, int]:
    """`bytes_in_use` / `peak_bytes_in_use` of the fullest local device
    ({} where the backend reports none, as the CPU does)."""
    out: Dict[str, int] = {}
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        for k in ("bytes_in_use", "peak_bytes_in_use"):
            if k in st:
                out[k] = max(out.get(k, 0), int(st[k]))
    return out


class span:
    """`with span("sched.admit", rid=7): ...`. Ids may be added until
    exit with `set()`. always=True also reads the device memory at its
    end, so the phase that sets the process peak is named."""

    __slots__ = ("name", "always", "ids", "sid", "t0_ns", "t1_ns", "_ann")

    def __init__(self, name: str, always: bool = False, **ids):
        self.name = name
        self.always = always
        self.ids = ids
        self.sid = self.t0_ns = self.t1_ns = 0

    def set(self, **ids) -> None:
        self.ids.update(ids)

    def __enter__(self) -> "span":
        if not (self.always or _enabled or _is_profiling()):
            return self
        self.sid = next(_next_sid)
        self._ann = jax.profiler.TraceAnnotation(PREFIX + self.name, **self.ids)
        self._ann.__enter__()
        _stack().append(self.sid)
        self.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        if not self.sid:
            return
        self.t1_ns = time.perf_counter_ns()
        st = _stack()
        st.remove(self.sid)
        self._ann.__exit__(*exc)
        if self.always:
            self.ids.update(memory_ids())
        _append(SpanRecord(self.name, self.t0_ns, self.t1_ns, self.sid,
                           st[-1] if st else 0, self.ids), self.always)


# -- the collector's clock ---------------------------------------------------

_gc_ns = 0             # nanoseconds inside collections, process-wide
_gc_n = 0              # collections
_gc_t0 = 0             # start stamp of the collection in progress
_gc_last = [0, 0, 0]   # by generation: the stop stamp of its last collection


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    """The one `gc.callbacks` hook (two calls a collection), stamped
    with the spans' clock. It runs on whichever thread tripped the
    collector, between two bytecodes of whatever that thread was doing,
    so it takes no lock and calls nothing of JAX: the totals are plain
    ints, and a full collection that takes over STALL_FLOOR_NS goes
    into the kept buffer as `host.gc` by the deque's own atomic append,
    whichever loop is running or none (a warm-up, a reference pass)."""
    global _gc_ns, _gc_n, _gc_t0
    if phase == "start":
        _gc_t0 = time.perf_counter_ns()
        return
    if not _gc_t0:
        return  # hooked while this collection ran
    now = time.perf_counter_ns()
    d, _gc_t0 = now - _gc_t0, 0
    _gc_ns += d
    _gc_n += 1
    gen = info["generation"]
    _gc_last[gen] = now
    if gen == 2 and d > STALL_FLOOR_NS:
        _kept.append(SpanRecord(
            "host.gc", now - d, now, next(_next_sid), 0,
            {"generation": gen, "collected": info["collected"]}))


def gc_clock() -> Tuple[int, int]:
    """(nanoseconds, collections) of Python's collector in this process
    since the hook went in (the first `Phases` installs it)."""
    return _gc_ns, _gc_n


def gc_generation_since(t0_ns: int) -> Optional[int]:
    """The highest generation a collection that ended after `t0_ns`
    (`perf_counter_ns`) collected; None when none did."""
    for gen in (2, 1, 0):
        if _gc_last[gen] > t0_ns:
            return gen
    return None


class _Pace:
    """What one pass of a loop typically takes: a running estimate in
    O(1) a pass, with no list and no sort. Nothing until STALL_MIN_SEEN
    passes were fed; then their mean less the largest; from there an
    exponential average."""

    __slots__ = ("ns", "seen", "_sum", "_max")

    def __init__(self):
        self.ns = self.seen = self._sum = self._max = 0

    def feed(self, ns: int) -> None:
        n = self.seen = self.seen + 1
        if n > STALL_MIN_SEEN:
            self.ns += (ns - self.ns) >> STALL_EMA_SHIFT
            return
        self._sum += ns
        if ns > self._max:
            self._max = ns
        if n == STALL_MIN_SEEN:
            self.ns = (self._sum - self._max) // (n - 1)

    def forget(self) -> None:
        """Learn the pace anew (`ns` stands until it is)."""
        self.seen = self._sum = self._max = 0


class Phases:
    """The consecutive phases of one loop iteration on one clock: each
    boundary is ONE `perf_counter_ns` stamp that closes a phase and
    opens the next, so the phases tile the iteration with nothing
    between them. The stamps always feed `sums` (seconds per phase,
    e.g. ServingScheduler.counters); when tracing is active at
    `begin()` the same stamps become a parent span `<prefix>.<what>`
    with one child `<prefix>.<phase>` per visit. One instance per loop;
    an iteration begins and ends on one thread.

    `end()` also judges the iteration by the stall rule (STALL_FACTOR,
    STALL_FLOOR_NS), tracing on or off, and leaves what its owner
    writes on the span it keeps of a stall: `typical_ns`, `excess_ns`
    (0 when it was none), `excess_wait_ns` and the collector's `gc_ns`
    / `gc_n` inside the iteration (`keep()` writes them on a span,
    `log_stall()` in a line of the log). No `getrusage` beside them:
    one read an iteration cost 8 us on the benchmark's host, more than
    everything else here (PERF.md section 6, PR 53)."""

    def __init__(self, prefix: str, what: str, phases: Sequence[str],
                 sums: Optional[Dict[str, float]] = None,
                 wait: Optional[str] = None):
        """phases: their names, or with `sums` a mapping from each
        name to its key in `sums`. wait: the phase in which the host
        waits for the device; its own pace is kept, so that
        `excess_wait_ns` is the part of a stall that lies in it."""
        self.sums = sums
        self._key = dict(phases) if sums is not None else {}
        self._what = f"{prefix}.{what}"
        self._name = {p: f"{prefix}.{p}" for p in phases}
        self.ns = dict.fromkeys(phases, 0)  # this iteration, by phase
        self._phase: Optional[str] = None
        self._live = False
        self._t0 = self._t = 0
        self._sid = self._child = 0
        self._ids: Dict[str, Any] = {}
        self._child_ids: Dict[str, Any] = {}
        self._ann = self._child_ann = None
        self._wait = wait
        self._pace, self._wait_pace = _Pace(), _Pace()
        self._streak = 0
        self.stalls = 0  # iterations the rule has fired on
        self.excess_ns = self.excess_wait_ns = 0
        self.gc_ns = self.gc_n = self._gc_ns0 = self._gc_n0 = 0
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)

    @property
    def typical_ns(self) -> int:
        """What an iteration of this loop typically takes (0 until
        STALL_MIN_SEEN were seen); a stall does not move it."""
        return self._pace.ns

    def begin(self, phase: str, **ids) -> None:
        for p in self.ns:
            self.ns[p] = 0
        self._phase = phase
        self._gc_ns0, self._gc_n0 = _gc_ns, _gc_n
        self._live = _enabled or _is_profiling()
        if self._live:
            self._sid = next(_next_sid)
            self._ids = ids
            self._ann = jax.profiler.TraceAnnotation(
                PREFIX + self._what, **ids)
            self._ann.__enter__()
            _stack().append(self._sid)
            self._open_child(phase, {})
        self._t0 = self._t = time.perf_counter_ns()

    def mark(self, phase: str, **ids) -> None:
        """Boundary: what ran since the last stamp was `self._phase`;
        what runs from here is `phase`. No-op outside an iteration."""
        if self._phase is None:
            return
        now = time.perf_counter_ns()
        self._close(now)
        self._phase = phase
        if self._live:
            self._open_child(phase, ids)

    def end(self, feed: bool = True, **ids) -> int:
        """Close the iteration; returns its nanoseconds (`self.ns` holds
        the split until the next begin). feed=False keeps an iteration
        that is no sample of the loop's pace (it launched nothing) out
        of the estimate; it is judged all the same."""
        if self._phase is None:
            return 0
        now = time.perf_counter_ns()
        self._close(now)
        self._phase = None
        if self._live:
            self._live = False
            st = _stack()
            st.remove(self._sid)
            self._ann.__exit__(None, None, None)
            self._ids.update(ids)
            _append(SpanRecord(self._what, self._t0, now, self._sid,
                               st[-1] if st else 0, self._ids), False)
        total = now - self._t0
        self.gc_ns, self.gc_n = _gc_ns - self._gc_ns0, _gc_n - self._gc_n0
        pace, typical = self._pace, self._pace.ns
        if (pace.seen >= STALL_MIN_SEEN and total > STALL_FACTOR * typical
                and total - typical > STALL_FLOOR_NS):
            self.excess_ns = excess = total - typical
            self.excess_wait_ns = 0 if self._wait is None else min(
                excess, max(0, self.ns[self._wait] - self._wait_pace.ns))
            self.stalls += 1
            self._streak += 1
            if self._streak >= STALL_RESEED:
                self._streak = 0
                pace.forget()
                self._wait_pace.forget()
        else:
            self.excess_ns = self.excess_wait_ns = 0
            if feed:
                self._streak = 0
                pace.feed(total)
                if self._wait is not None:
                    self._wait_pace.feed(self.ns[self._wait])
        return total

    def keep(self, name: str, **ids) -> int:
        """The iteration just ended as an always-kept span on its own
        stamps, tracing on or off: the owner's ids, every phase's
        `_ms`, the pace it was held to, and what the collector did
        inside it."""
        return record(
            name, self._t0, self._t, always=True, **ids,
            **{f"{p}_ms": ns * 1e-6 for p, ns in self.ns.items()},
            typical_ms=self.typical_ns * 1e-6,
            excess_ms=self.excess_ns * 1e-6, gc_ms=self.gc_ns * 1e-6,
            gc_gen=gc_generation_since(self._t0) if self.gc_n else None)

    def log_stall(self, what: str, tail: str = "") -> None:
        """One warning in the process's own log for the stall just
        ended, for the first STALL_LOG_LINES of this loop: `sched:
        <what> took 2134.0 ms (typical 20.1): readback 2113.2, tick
        1.9, ...; gc 0.0 ms; <tail>`, the phases longest first. Then only their count, at the next
        and at every power of two."""
        n, loop = self.stalls, self._what.split(".")[0]
        if n <= STALL_LOG_LINES:
            split = ", ".join(
                f"{p} {ns * 1e-6:.1f}" for p, ns in
                sorted(self.ns.items(), key=lambda kv: -kv[1]) if ns)
            logger.warning(
                f"{loop}: {what} took {sum(self.ns.values()) * 1e-6:.1f} ms "
                f"(typical {self.typical_ns * 1e-6:.1f}): {split}; gc "
                f"{self.gc_ns * 1e-6:.1f} ms{tail and '; ' + tail}")
        elif n == STALL_LOG_LINES + 1 or n & (n - 1) == 0:
            logger.warning(
                f"{loop}: {n} stalls so far; past the first "
                f"{STALL_LOG_LINES} each is kept as a span and counted, "
                f"not logged")

    def _close(self, now: int) -> None:
        d = now - self._t
        self.ns[self._phase] += d
        if self.sums is not None:
            self.sums[self._key[self._phase]] += d * 1e-9
        if self._live:
            self._child_ann.__exit__(None, None, None)
            _append(SpanRecord(self._name[self._phase], self._t, now,
                               self._child, self._sid, self._child_ids),
                    False)
        self._t = now

    def _open_child(self, phase: str, ids: Dict[str, Any]) -> None:
        self._child = next(_next_sid)
        self._child_ids = ids
        self._child_ann = jax.profiler.TraceAnnotation(
            PREFIX + self._name[phase], **ids)
        self._child_ann.__enter__()


@contextlib.contextmanager
def compile_spans(prefix: str) -> Iterator[List[SpanRecord]]:
    """While open, collect what JAX spends tracing, lowering and
    compiling programs (the durations jax.monitoring reports as each
    stage ends, backdated from then). At exit they become always-kept
    child spans `<prefix>.trace|lower|compile` of the innermost open
    span and fill the yielded list. A report nested in another is that
    one's work (lowering a Pallas kernel traces its body: lowering);
    reports of one stage that follow within STAGE_GAP_NS become ONE
    span (tracing a model is hundreds of small traces with Python
    between them, and that Python is tracing too)."""
    st = _stack()
    parent = st[-1] if st else 0
    seen: List[Tuple[int, int, str]] = []
    got: List[SpanRecord] = []

    def on_duration(event: str, duration: float, **_) -> None:
        stage = COMPILE_STAGES.get(event)
        if stage is not None:
            t1 = time.perf_counter_ns()
            seen.append((t1 - int(duration * 1e9), t1, stage))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield got
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        top: Dict[str, List[Tuple[int, int]]] = {}
        end = 0
        for a, b, stage in sorted(seen, key=lambda x: (x[0], -x[1])):
            if b > end:  # else nested in one already kept
                top.setdefault(stage, []).append((a, b))
                end = b
        for stage, ivs in top.items():
            for a, b in merge_ns(ivs, STAGE_GAP_NS):
                got.append(SpanRecord(f"{prefix}.{stage}", a, b,
                                      next(_next_sid), parent, {}))
        got.sort(key=lambda r: r.t0_ns)
        for rec in got:
            _append(rec, True)


# -- reading the buffer ------------------------------------------------------

def spans(clear: bool = False) -> List[SpanRecord]:
    """Every recorded span, kept and hot, by start time (a parent
    before the child that starts with it)."""
    with _lock:
        # copied in C before any Python runs over them: the collector's
        # hook appends to _kept between two bytecodes of any thread
        out = list(_kept) + list(_hot)
        if clear:
            _kept.clear()
            _hot.clear()
    out.sort(key=lambda r: (r.t0_ns, -r.t1_ns))
    return out


def clear() -> None:
    spans(clear=True)


def merge_ns(intervals: Iterable[Tuple[int, int]],
             gap_ns: int = 0) -> List[Tuple[int, int]]:
    """Union of intervals, bridging gaps of at most gap_ns."""
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1] + gap_ns:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def union_ns(intervals: Iterable[Tuple[int, int]]) -> int:
    return sum(b - a for a, b in merge_ns(intervals))


def split_ns(t0_ns: int, t1_ns: int,
             groups: Sequence[Tuple[str, Iterable[Tuple[int, int]]]]
             ) -> Dict[str, int]:
    """[t0, t1) divided among named groups of intervals, in order: a
    group gets what its intervals cover (clipped; overlaps once) that
    no earlier group covered, `other` the rest. Sums to t1 - t0."""
    out: Dict[str, int] = {}
    seen: List[Tuple[int, int]] = []
    covered = 0
    for name, ivs in groups:
        seen += [(max(a, t0_ns), min(b, t1_ns)) for a, b in ivs
                 if b > t0_ns and a < t1_ns]
        now = union_ns(seen)
        out[name] = now - covered
        covered = now
    out["other"] = (t1_ns - t0_ns) - covered
    return out


def self_ns(records: Sequence[SpanRecord]) -> Dict[int, int]:
    """Span id -> its time less what its children cover."""
    kids: Dict[int, List[Tuple[int, int]]] = {}
    for r in records:
        kids.setdefault(r.parent, []).append((r.t0_ns, r.t1_ns))
    return {r.sid: split_ns(r.t0_ns, r.t1_ns,
                            [("children", kids.get(r.sid, ()))])["other"]
            for r in records}


def dump(path: str) -> str:
    """Write the buffer as Chrome-trace JSON (chrome://tracing,
    ui.perfetto.dev): complete events in microseconds of the
    perf_counter clock, one track per root span name's first word."""
    events = [{"name": r.name, "ph": "X", "ts": r.t0_ns / 1e3,
               "dur": (r.t1_ns - r.t0_ns) / 1e3, "pid": os.getpid(),
               "tid": r.name.split(".")[0],
               "args": dict(r.ids, sid=r.sid, parent=r.parent)}
              for r in spans()]
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f,
                  default=str)
    return path


# -- jax.profiler capture ----------------------------------------------------

@contextlib.contextmanager
def trace(output_dir: str) -> Iterator[None]:
    """Capture a device+host trace for the enclosed steps
    (ref: torch.profiler usage; xplane output for tensorboard). Spans
    are active for as long as it is open."""
    os.makedirs(output_dir, exist_ok=True)
    jax.profiler.start_trace(output_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
