#!/usr/bin/env python3
"""Find a serving cell's knee, once, on the chip: the highest offered
rate the system sustains. One process, one engine, one warm-up, a fresh
scheduler per (rate, seed):

    python3 benchmarks/knee.py --workload <cell> --rates 1,2,4 \
        --seeds 11,12 --seconds 51

A rate is SUSTAINED when, after the mix's drain, fewer than 2% of the
requests due inside the window have no first token (they sit in a
queue that grew) and, where the answers are short enough to finish
within the drain (`count: due_in_window`), fewer than 2% are
unfinished. This is a tool for the PR that defines or re-finds a
cell's rate; the rate it finds is written into the mix's file as a
number, and no check runs this.
"""

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    ap.add_argument("--seeds", default="11,12")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--set", default="", help="scheduler overrides, k=v,k=v")
    args = ap.parse_args(argv)

    from benchmarks import harness

    cell = harness.load_cell(args.workload)
    try:
        devices = harness.require_tpu(cell.chips)
    except harness.NoAcceleratorError as e:
        print(f"benchmarks/knee.py: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    for kv in filter(None, args.set.split(",")):
        k, v = kv.split("=")
        cell.config["serve"]["scheduler"][k] = int(v)
    runner = harness.load_module(cell.bench_dir / "runners" / "serve.py")
    out_dir = ROOT / "chiprun_out" / "knee"
    out_dir.mkdir(parents=True, exist_ok=True)
    ctx = harness.RunContext(
        cell=cell, seed=0, seconds=args.seconds, trace=False, devices=devices,
        t_process_start=_T0, compiles=harness.CompileCounter(),
        out_dir=out_dir, log=lambda m: print(m, flush=True))
    eng, mcfg, _, _ = runner.setup(ctx)
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        for seed in [int(s) for s in args.seeds.split(",")]:
            m = runner.measure(ctx, eng, mcfg, seed, rate_rps=rate)
            n = m["notes"]
            due = max(1, n["due_in_window"])
            row = {
                "rate_rps": rate, "seed": seed, "due": n["due_in_window"],
                "no_first_token": n["no_first_token"],
                "unfinished": n["unfinished_after_drain"],
                "waiting_at_end": n["waiting_at_end"],
                "ttft_ms": n["ttft_ms"], "tpot_ms": n["tpot_ms"],
                "tokens_per_s": m["end_to_end"]["serve_tokens_per_s"],
                "finished_in_window": n["finished_in_window"],
                "rows_per_step": (n["counters_delta"]["batched_tokens"]
                                  / max(1, n["counters_delta"]["steps"])),
                "steps": n["counters_delta"]["steps"],
                "preemptions": n["counters_delta"]["preemptions"],
                "lateness_p99_ms": n["gen_lateness_ms_p99"],
                "failed": m["failed"], "checks": m["checks"],
            }
            row["sustained"] = bool(
                row["no_first_token"] < 0.02 * due
                and (cell.traffic["count"] != "due_in_window"
                     or row["unfinished"] < 0.02 * due))
            rows.append(row)
            print("[knee] " + json.dumps(row), flush=True)
    name = f"{cell.name}{'_' + args.set.replace('=', '') if args.set else ''}.json"
    with open(out_dir / name, "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
