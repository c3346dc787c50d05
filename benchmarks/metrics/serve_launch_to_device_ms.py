"""Median over the traced iterations of (start of the next program on
the device) - (start of the iteration's first `sched.launch` span):
transfers, jit dispatch and the runtime's queue, on the one clock
`program_spans.load` recovers."""

from benchmarks.trace import program_spans as PS
from benchmarks.trace import reduce as R


def read(obs):
    ps = PS.load(obs)
    if ps is None:
        return None
    xs = PS.launch_to_device_s(obs["trace"], ps["spans"])
    if xs:
        print(f"[bench] launch to device over {len(xs)} iterations: min "
              f"{1e3 * min(xs):.3f}ms max {1e3 * max(xs):.3f}ms", flush=True)
    m = R.median(xs)
    return None if m is None else 1e3 * m
