"""What the step's all-gathers achieve: the bytes of the manifest's
all-gather sites (`train.compile.collectives`: the gathered RESULT of
each, so a gather over four chips receives three quarters of it) times
their executions inside the traced window, over their summed time on
device 0, in GB/s. A synchronous gather's time is its instruction's
event, an asynchronous one's its start-to-done span. The chip's
interconnect is 1,600 Gbit/s = 200 GB/s (benchmarks/peaks.json has no
such entry: this is a rate, not a share)."""

from benchmarks.metrics.collective_in_fusion_ms_per_step import sites
from benchmarks.trace import reduce as R


def read(obs):
    td = obs.get("trace")
    size = {name: nbytes for name, kind, nbytes in sites(obs) or ()
            if kind == "all-gather"}
    if td is None or not size:
        return None
    moved = seconds = 0.0
    # an asynchronous site is its `-start`: that instruction's own event
    # is the launch, the span on the asynchronous line the transfer
    for line, is_async in ((td.ops, False), (td.async_ops, True)):
        for e in R.in_window(line.get(0, []), td.window):
            if e.name in size and ("-start" in e.name) == is_async:
                moved += size[e.name]
                seconds += e.dur
    return moved / seconds / 1e9 if seconds else None
