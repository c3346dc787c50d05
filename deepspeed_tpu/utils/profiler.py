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
"""

import contextlib
import functools
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, Iterable, Iterator, List, NamedTuple, Optional, Sequence, Tuple

import jax

PREFIX = "ds."
HOT_SPANS = 65536    # per-iteration spans kept (minutes of serving)
KEPT_SPANS = 4096    # always=True spans kept
STAGE_GAP_NS = 1_000_000

# The device scopes of the compiled train step (`jax.named_scope`, so
# metadata alone: docs/tracing.md "Device scopes of the train step").
# With the model's own (MODEL_SCOPES, models/transformer.py: the six
# the benchmark has read since PR 26 and `norm_f`, the final norm) they
# name every instruction the program itself wrote; a reader takes the
# OUTERMOST of the names it asks for.
(PARAM_CAST, GRAD_REDUCE, GRAD_CLIP, OPTIMIZER, ZERO_GATHER,
 LAYER_STACK) = TRAIN_STEP_SCOPES = (
    "param_cast", "grad_reduce", "grad_clip", "optimizer", "zero_gather",
    "layer_stack")
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


def annotate(name: Optional[str] = None, **ids):
    """Decorator form of `span` (ref: utils/nvtx.py instrument_w_nvtx)."""

    def deco(fn):
        label = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapped(*a, **kw):
            with span(label, **ids):
                return fn(*a, **kw)

        return wrapped

    return deco


class Phases:
    """The consecutive phases of one loop iteration on one clock: each
    boundary is ONE `perf_counter_ns` stamp that closes a phase and
    opens the next, so the phases tile the iteration with nothing
    between them. The stamps always feed `sums` (seconds per phase,
    e.g. ServingScheduler.counters); when tracing is active at
    `begin()` the same stamps become a parent span `<prefix>.<what>`
    with one child `<prefix>.<phase>` per visit. One instance per loop;
    an iteration begins and ends on one thread."""

    def __init__(self, prefix: str, what: str, phases: Sequence[str],
                 sums: Optional[Dict[str, float]] = None):
        """phases: their names, or with `sums` a mapping from each
        name to its key in `sums`."""
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

    def begin(self, phase: str, **ids) -> None:
        for p in self.ns:
            self.ns[p] = 0
        self._phase = phase
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

    def end(self, **ids) -> int:
        """Close the iteration; returns its nanoseconds (`self.ns` holds
        the split until the next begin)."""
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
        return now - self._t0

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
        out = sorted(itertools.chain(_kept, _hot),
                     key=lambda r: (r.t0_ns, -r.t1_ns))
        if clear:
            _kept.clear()
            _hot.clear()
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


def capture_step_trace(engine, batch, output_dir: str, steps: int = 3) -> str:
    """Profile `steps` engine steps (first call compiles OUTSIDE the
    trace so the capture shows steady-state execution). Returns the
    trace directory for `tensorboard --logdir`."""
    engine.train_batch(batch)  # compile + warmup outside the trace
    with trace(output_dir):
        for i in range(steps):
            with jax.profiler.StepTraceAnnotation("train", step_num=i):
                engine.train_batch(batch)
    return output_dir
