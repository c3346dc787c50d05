"""What the always-on part of `profiler.Phases` costs an iteration of
the serving loop: nanoseconds per begin / mark x 7 / end cycle of the
scheduler's eight phases, tracing off (PERF.md section 6, PR 53; the
budget of everything `end()` does beside the stamps is 5 us).

  python scripts/phases_cost.py                       # this checkout
  python scripts/phases_cost.py --tree .scratch/parent   another one's (the
                                  parent's, unpacked with git archive)

No device is touched (`JAX_PLATFORMS=cpu` is set here): a host number,
to be taken on the host it is reported for. One JSON line.
"""
import argparse
import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
ap = argparse.ArgumentParser()
ap.add_argument("--tree", default=os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ap.add_argument("--cycles", type=int, default=20000)
ap.add_argument("--reps", type=int, default=9)
args = ap.parse_args()
sys.path.insert(0, os.path.abspath(args.tree))

from deepspeed_tpu.inference.scheduler import PHASES  # noqa: E402
from deepspeed_tpu.utils import profiler  # noqa: E402

sums = dict.fromkeys(PHASES.values(), 0.0)
try:
    ph = profiler.Phases("sched", "iteration", PHASES, sums=sums,
                         wait="readback")
except TypeError:  # a tree from before the stall rule
    ph = profiler.Phases("sched", "iteration", PHASES, sums=sums)
rest = [p for p in PHASES if p != "tick"]


def cycle():
    ph.begin("tick", iteration=1)
    for p in rest:
        ph.mark(p)
    ph.end(rows=128, kind="mixed")


for _ in range(2000):
    cycle()
ns = []
for _ in range(args.reps):
    t = time.perf_counter_ns()
    for _ in range(args.cycles):
        cycle()
    ns.append((time.perf_counter_ns() - t) / args.cycles)
ns.sort()
print(json.dumps({"tree": args.tree, "ns_per_cycle_min": ns[0],
                  "ns_per_cycle_median": ns[len(ns) // 2],
                  "ns_per_cycle_max": ns[-1], "reps": args.reps,
                  "cycles": args.cycles,
                  "stalls_fired": getattr(ph, "stalls", None)}))
