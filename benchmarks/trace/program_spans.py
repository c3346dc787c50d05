"""The program's own spans beside the device trace.

`deepspeed_tpu.utils.profiler` keeps the spans of the serving loop, the
warm-up and the train step in a process-global buffer, stamped with
`time.perf_counter_ns` (docs/tracing.md). The profiler's trace counts
from its own start. One constant separates the two clocks, and the
benchmark already holds an anchor for it: every `bench.sched_iteration`
starts as the runner's tick returns, which is where the program's
`sched.tick` ends, and every `bench.train_batch` starts where the
program's `train.batch` does. `load()` recovers the constant from
those pairs, puts the buffer on the trace's clock, and names each idle
gap of the device by the shortest program span over its midpoint.

A program without the facility (a parent commit) gives None everywhere
and the readers built on this return nothing.
"""

import dataclasses
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from benchmarks.trace import reduce as R

# trace-side span -> (program span, which of its ends coincides)
ANCHORS = {"bench.sched_iteration": ("sched.tick", "end"),
           "bench.train_batch": ("train.batch", "start")}
# spans that tile into children: a gap named by one of these alone is
# not attributed to a layer boundary
PARENTS = ("sched.iteration", "train.batch")
# spans of a request's life overlap every iteration and name nothing
NOT_HOST_WORK = ("request",)
NO_SPAN = "no_span"
# a candidate offset counts an anchor as matched within this
MATCH_TOL_S = 200e-6
MEMO = "_program_spans"


@dataclasses.dataclass
class PSpan:
    name: str
    start: float          # seconds, trace clock once aligned
    end: float
    sid: int
    parent: int
    ids: Dict[str, Any]

    @property
    def dur(self) -> float:
        return self.end - self.start


def records() -> Optional[List[PSpan]]:
    """The program's buffer in seconds of ITS clock, by start; None
    when the program has no span buffer."""
    from deepspeed_tpu.utils import profiler

    if not hasattr(profiler, "spans"):
        return None
    return [PSpan(r.name, r.t0_ns * 1e-9, r.t1_ns * 1e-9, r.sid, r.parent,
                  dict(r.ids)) for r in profiler.spans()]


def named(spans: Optional[Iterable[PSpan]], name: str) -> List[PSpan]:
    return [s for s in spans or () if s.name == name]


def total_s(spans: Optional[Iterable[PSpan]], *names: str) -> Optional[float]:
    """Sum of the durations of the spans called one of `names`; None
    when there is none."""
    got = [s.dur for s in spans or () if s.name in names]
    return sum(got) if got else None


def self_s(spans: Sequence[PSpan]) -> Dict[int, float]:
    """Span id -> its duration less what its children cover of it."""
    kids: Dict[int, List[R.Interval]] = {}
    for s in spans:
        kids.setdefault(s.parent, []).append((s.start, s.end))
    return {s.sid: s.dur - R.union_s(R.clip(kids.get(s.sid, []),
                                            (s.start, s.end)))
            for s in spans}


def per_step_ms(obs, key: str) -> Optional[float]:
    """A scheduler time sum (seconds over the window) per iteration."""
    d = obs.get("counters_delta") or {}
    if not d.get("steps") or key not in d:
        return None
    return 1e3 * d[key] / d["steps"]


def train_phase_ms(names: Sequence[str]) -> Optional[float]:
    """Self time of the spans called one of `names`, per `train.batch`
    span in the buffer (a traced run holds the traced steps alone)."""
    spans = records()
    steps = len(named(spans, "train.batch"))
    if not steps:
        return None
    own = self_s(spans)
    return 1e3 * sum(own[s.sid] for s in spans if s.name in names) / steps


SETUP_PREFIXES = ("init.", "warmup.", "train.init", "train.compile",
                  "sched.slow_iteration")


def setup_spans(obs) -> Optional[List[PSpan]]:
    """The always-kept spans of set-up and rare events, logged once per
    run with the device memory each read at its end."""
    key = MEMO + "_setup"
    if key in obs:
        return obs[key]
    spans = records()
    obs[key] = out = None if spans is None else [
        s for s in spans if s.name.startswith(SETUP_PREFIXES)]
    if out:
        t0 = out[0].start
        for s in out:
            ids = {k: (round(v / 1e9, 3) if k.endswith("bytes_in_use") else v)
                   for k, v in s.ids.items()}
            print(f"[bench] set-up span {s.name} at +{s.start - t0:.3f}s "
                  f"took {s.dur:.3f}s {ids}", flush=True)
        peak = [(s.ids["peak_bytes_in_use"], s.end, s.name) for s in out
                if "peak_bytes_in_use" in s.ids]
        if peak:
            top = max(p[0] for p in peak)
            first = min((p for p in peak if p[0] == top), key=lambda p: p[1])
            print(f"[bench] highest peak_bytes_in_use a set-up span read: "
                  f"{top / 1e9:.3f} GB, first at the end of {first[2]}",
                  flush=True)
    return out


# -- one clock -----------------------------------------------------------------

def align(trace_anchors: Sequence[float], program_anchors: Sequence[float]
          ) -> Optional[Tuple[float, float, int]]:
    """(offset, residual, matched): trace time = program time + offset.
    Each of the first trace anchors is tried against every program
    anchor; the offset under which most trace anchors land within
    MATCH_TOL_S of a program anchor wins, and is then refined to the
    median over the matched pairs. residual is the largest distance
    left among them."""
    a = np.sort(np.asarray(trace_anchors, np.float64))
    p = np.sort(np.asarray(program_anchors, np.float64))
    if len(a) == 0 or len(p) == 0:
        return None

    def distances(off):
        q = p + off
        i = np.clip(np.searchsorted(q, a), 1, len(q) - 1) if len(q) > 1 \
            else np.zeros(len(a), int)
        lo = q[np.maximum(i - 1, 0)]
        hi = q[i]
        return np.where(np.abs(a - lo) <= np.abs(a - hi), a - lo, a - hi)

    best = None
    for a0 in a[:3]:
        for pj in p:
            d = distances(a0 - pj)
            n = int((np.abs(d) <= MATCH_TOL_S).sum())
            key = (n, -float(np.median(np.abs(d))))
            if best is None or key > best[0]:
                best = (key, a0 - pj)
    off = best[1]
    d = distances(off)
    ok = np.abs(d) <= MATCH_TOL_S
    if not ok.any():
        return None
    off += float(np.median(d[ok]))
    d = distances(off)
    ok = np.abs(d) <= MATCH_TOL_S
    return off, float(np.abs(d[ok]).max()), int(ok.sum())


def anchor_times(td: R.TraceData, spans: Sequence[PSpan]
                 ) -> Tuple[List[float], List[float]]:
    for bench_name, (prog_name, which) in ANCHORS.items():
        t = [s.start for s in td.spans if s.name == bench_name]
        q = [s.end if which == "end" else s.start
             for s in named(spans, prog_name)]
        if t and q:
            return t, q
    return [], []


# -- idle gaps by program span ---------------------------------------------------

def host_spans(spans: Iterable[PSpan]) -> List[PSpan]:
    return [s for s in spans if s.name.split(".")[0] not in NOT_HOST_WORK]


def name_gaps(td: R.TraceData, spans: Sequence[PSpan], device: int = 0
              ) -> List[Tuple[str, float, float]]:
    """(name, seconds, midpoint) of every idle interval of the device
    inside the window, longest first; the name is the shortest program
    span over the gap's midpoint."""
    busy = R.merge(R.clip(R.intervals(td.ops.get(device, [])), td.window))
    cand = host_spans(spans)
    out = []
    for a, b in R.subtract([td.window], busy):
        mid = 0.5 * (a + b)
        cover = [s for s in cand if s.start <= mid < s.end]
        name = min(cover, key=lambda s: s.dur).name if cover else NO_SPAN
        out.append((name, b - a, mid))
    return sorted(out, key=lambda g: -g[1])


def named_share(gaps: Sequence[Tuple[str, float, float]]) -> Optional[float]:
    """Share of the idle time whose gap a LEAF program span names."""
    total = sum(g[1] for g in gaps)
    if total <= 0:
        return None
    leaf = sum(g[1] for g in gaps if g[0] != NO_SPAN and g[0] not in PARENTS)
    return leaf / total


# a program worth the name: the key helpers beside it run for microseconds
PROGRAM_MIN_S = 1e-3


def launch_to_device_s(td: R.TraceData, spans: Sequence[PSpan],
                       device: int = 0) -> List[float]:
    """For each iteration's FIRST `sched.launch` span inside the window
    (the decode program; the sampler follows it), the signed time from
    the span's start to the start of the program on the device nearest
    to it. The device lines carry the device's clock as the profiler
    mapped it to the host's: a negative value is that mapping's error,
    not a program that ran before it was launched."""
    starts = np.asarray(sorted(
        m.start for m in td.modules.get(device, []) if m.dur >= PROGRAM_MIN_S))
    if len(starts) == 0:
        return []
    first: Dict[int, PSpan] = {}
    for s in named(spans, "sched.launch"):
        if td.window[0] <= s.start < td.window[1] \
                and (s.parent not in first or s.start < first[s.parent].start):
            first[s.parent] = s
    out = []
    for s in first.values():
        i = int(np.clip(np.searchsorted(starts, s.start), 1, len(starts) - 1)) \
            if len(starts) > 1 else 0
        near = min(starts[max(i - 1, 0)], starts[i],
                   key=lambda t: abs(t - s.start))
        out.append(float(near - s.start))
    return out


# -- what the readers share ------------------------------------------------------

def load(obs) -> Optional[Dict[str, Any]]:
    """The program's spans on the trace's clock, with the idle gaps
    named: {spans, offset_s, residual_s, matched, gaps}. Computed once
    per run (kept in obs) and logged once; None when there is no
    trace, no buffer or no anchor."""
    if MEMO in obs:
        return obs[MEMO]
    obs[MEMO] = out = _load(obs.get("trace"), records())
    if out is not None:
        _log(out)
    return out


def _load(td, spans) -> Optional[Dict[str, Any]]:
    if td is None or not spans:
        return None
    got = align(*anchor_times(td, spans))
    if got is None:
        return None
    off, residual, matched = got
    shifted = [dataclasses.replace(s, start=s.start + off, end=s.end + off)
               for s in spans]
    return {"spans": shifted, "offset_s": off, "residual_s": residual,
            "matched": matched, "gaps": name_gaps(td, shifted),
            "phase_medians_ms": phase_medians_ms(shifted, td.window)}


def phase_medians_ms(spans: Sequence[PSpan], window: R.Interval,
                     parent: str = "sched.iteration") -> Dict[str, float]:
    """Median over the iterations that lie inside the window of the
    time each spent in each phase (its children summed by name), and
    of the iteration itself under the parent's name: the split as the
    traced iterations alone show it, free of what the window's time
    sums also hold (the profiler's own start and stop in a tick)."""
    its = {s.sid: {} for s in named(spans, parent)
           if window[0] <= s.start and s.end <= window[1]}
    for s in spans:
        if s.parent in its:
            d = its[s.parent]
            d[s.name] = d.get(s.name, 0.0) + s.dur
    names = sorted({n for d in its.values() for n in d})
    out = {n: 1e3 * R.median([d.get(n, 0.0) for d in its.values()])
           for n in names}
    if its:
        out[parent] = 1e3 * R.median(
            [s.dur for s in named(spans, parent) if s.sid in its])
    return out


def _log(out) -> None:
    gaps = out["gaps"]
    share = named_share(gaps)
    print(f"[bench] program spans on the trace's clock: offset "
          f"{out['offset_s']:.6f}s, {out['matched']} anchors matched, "
          f"largest residual {1e6 * out['residual_s']:.1f}us; "
          f"{len(gaps)} idle gaps, "
          f"{'none' if share is None else f'{100 * share:.1f}%'} of idle "
          f"time in a leaf span", flush=True)
    for name, dur, mid in gaps[:10]:
        print(f"[bench] idle gap {1e3 * dur:.3f}ms at {mid:.6f}s: {name}",
              flush=True)
    if out["phase_medians_ms"]:
        print("[bench] median per traced iteration, ms: " + ", ".join(
            f"{k} {v:.3f}" for k, v in out["phase_medians_ms"].items()),
            flush=True)
