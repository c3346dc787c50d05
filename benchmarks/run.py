#!/usr/bin/env python3
"""One cell of the benchmark, in one process, on the machine it is
started on:

    python3 benchmarks/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Fails (non-zero, no result line) unless JAX's backend is a TPU with the
chips the cell asks for. Details go on earlier lines; the LAST line of
stdout is the contract's one JSON object: `correct`, `attempted`,
`failed`, `metrics`, `device`, and with --trace 1 `breakdown`. With
--trace 0 the metrics are the cell's end-to-end metrics (profiler
off); with --trace 1 its per-layer metrics.
"""

import time

_T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    from benchmarks import harness

    cell = harness.load_cell(args.workload)
    try:
        devices = harness.require_tpu(cell.chips)
    except harness.NoAcceleratorError as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 3

    cache_dir = harness.enable_compile_cache()
    print(f"[bench] cell {cell.name} seed {args.seed} seconds "
          f"{args.seconds} trace {args.trace} cache {cache_dir}", flush=True)

    def log(msg):
        print(msg, flush=True)

    line = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                            devices, _T0, log=log)
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
