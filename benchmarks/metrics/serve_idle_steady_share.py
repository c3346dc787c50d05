"""The share of the traced window in which no operation ran on device
0 (`serve_device_idle_share`'s arithmetic) with the stalled iterations
taken out: the idle gaps whose midpoint lies inside a kept
`sched.slow_iteration` span of the program (put on the trace's clock by
`program_spans.load`) leave the idle time and the window both. What is
left is the share the steady loop idles; the difference to
`serve_device_idle_share` is what the named stalls cost (in every
traced run the iteration that held the profiler's own start). Nothing
without aligned spans."""

from benchmarks.trace import program_spans as PS


def read(obs):
    ps = PS.load(obs)
    if ps is None:
        return None
    kept = PS.named(ps["spans"], "sched.slow_iteration")
    idle = sum(dur for _, dur, _ in ps["gaps"])
    out = sum(dur for _, dur, mid in ps["gaps"]
              if any(s.start <= mid < s.end for s in kept))
    window_s = obs["trace"].window_s - out
    return None if window_s <= 0 else 100.0 * (idle - out) / window_s
