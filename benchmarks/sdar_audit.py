#!/usr/bin/env python3
"""Read, once, on the chip, what the block check of a `serve_blocks` cell
reads over several seeds, and what the same check reads of the controls
put in the engine's place: the data the cell's `logits_check` limits are
set from. One process; fresh weights and a fresh engine a seed, through
the runner's own `build_engine`, `block_errors` and `control_errors` (no
warm-up of the serving widths, no window):

    python3 benchmarks/sdar_audit.py --workload <cell> \
        --seeds 1,2,3000000001 --controls 3

Per seed: the largest and the median position's error as shares of the
largest |reference logit|, the feed that holds the largest; for the
first `--controls` seeds the same two numbers of every control
(runners/serve_blocks.py: the precision below, two wrong protocols, the
reference's mutants). The limits it finds are written into the mix's
file with their reason; no check runs this.
"""

import argparse
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=2,
                    help="seeds (the first) on which the controls are read")
    args = ap.parse_args(argv)

    import numpy as np

    from benchmarks import harness

    cell = harness.load_cell(args.workload)
    try:
        harness.require_tpu(cell.chips)
    except harness.NoAcceleratorError as e:
        print(f"benchmarks/sdar_audit.py: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    sb = harness.load_module(cell.bench_dir / "runners" / "serve_blocks.py")
    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        eng, mcfg, host, _ = sb.serve.build_engine(cell, seed)
        e = sb.block_errors(cell, eng, mcfg, host, seed)
        share = e["err"] / e["ref_max"]
        p = int(np.argmax(share.max(axis=0)))
        row = {"seed": seed, "ref_max": e["ref_max"],
               "max": float(share.max()), "median": float(np.median(share)),
               "p90": float(np.quantile(share, 0.9)),
               "worst": sb.position_name(e["proto"], p),
               "by_feed_max": share.reshape(
                   share.shape[0], -1, e["proto"]["B"]).max(
                   axis=(0, 2)).round(5).tolist()}
        if i < args.controls:
            row["controls"] = {
                name: {"max": float((c / e["ref_max"]).max()),
                       "median": float(np.median(c / e["ref_max"]))}
                for name, c in sb.control_errors(cell, host, e).items()}
        row["seconds"] = round(time.perf_counter() - t0, 1)
        rows.append(row)
        print("[audit] " + json.dumps(row), flush=True)
        del eng, host, e
        gc.collect()
    out = ROOT / "chiprun_out" / "audit"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{cell.name}.json").write_text(json.dumps(rows, indent=1))
    print(f"[audit] sound seeds: largest max {max(r['max'] for r in rows):.5f}"
          f", largest median {max(r['median'] for r in rows):.5f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
